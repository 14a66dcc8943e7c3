"""The tiny rehearsal configuration through the train generator, end to end, as
the driver would run a cell: a process of its own, the last line of its output."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
RUN = [sys.executable, str(ROOT / "benchmark" / "run.py")]
REHEARSE = ["--manifest", str(ROOT / "benchmark" / "rehearsal" / "manifest.json"), "--rehearse"]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(workload, seed, trace, extra=()):
    proc = subprocess.run(
        RUN + REHEARSE + ["--workload", workload, "--seed", str(seed), "--seconds", "2",
                          "--trace", str(trace), *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def check_line(line, man_metrics):
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert set(line["metrics"]) <= set(man_metrics)
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        # a CPU run reports counts; a time, a rate or a share of a peak never
        if man_metrics[name] != "program_counter":
            assert m["value"] is None, name
    assert "busy_s" not in line["device"] and "breakdown" not in line

MAN = json.loads((ROOT / "benchmark" / "rehearsal" / "manifest.json").read_text())
SOURCES = {m["name"]: m["source"] for g in ("end_to_end", "per_layer") for m in MAN[g]}


@pytest.mark.parametrize("workload,seed,trace", [
    ("tiny_train", 3, 0), ("tiny_train_remat", 2**31 + 7, 1)])
def test_train_cell_prints_the_contracts_line(workload, seed, trace):
    line = run_cell(workload, seed, trace)
    check_line(line, SOURCES)
    if trace:
        assert line["metrics"]["window_compiles.train"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"train_img_tok_per_s", "setup_s"}
    assert line["detail"]["correct"]["logits_rms_err"] < 1e-4


def test_a_run_that_finds_no_accelerator_fails_and_prints_no_result():
    proc = subprocess.run(
        RUN + ["--workload", "train_d8", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode not in (0, None)
    assert "no accelerator" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_an_unknown_workload_fails():
    proc = subprocess.run(
        RUN + REHEARSE + ["--workload", "nope", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and not proc.stdout.strip()
