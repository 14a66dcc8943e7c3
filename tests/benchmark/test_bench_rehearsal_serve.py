"""The tiny rehearsal configuration through the closed-loop generator, end to
end, as the driver would run a cell."""
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
    ("tiny_serve", 2**31 + 7, 1), ("tiny_guided", 5, 0)])
def test_serve_cell_prints_the_contracts_line(workload, seed, trace):
    line = run_cell(workload, seed, trace)
    check_line(line, SOURCES)
    if trace:
        assert line["metrics"]["window_compiles.serve"]["value"] == 0
        assert line["metrics"]["lane_occupancy_pct"]["value"] == 100.0
    else:
        assert set(line["metrics"]) == {"gen_img_tok_per_s", "image_latency_p50_s", "setup_s"}
    replay = line["detail"]["correct"]
    # float32 on the CPU: every delivered code lies inside the reference's top k
    assert replay["replayed"] == 2 and replay["codes"] == 2 * 16
    assert replay["outside_top_k_share"] == 0 and replay["pixels_rms_err"] < 1e-4
    assert line["detail"]["window_compiles"] == 0
