"""The hybrid trunk's rehearsal: a tiny configuration of the same layer cycle
(gated_delta x3 + gated_full, 16 routed experts of which 4 are held, a shared
expert) through `run.py --rehearse` with `--trace 1`, as the driver would run
the cell `train_q3n_ep16`; the new per-layer readers where a trace names
nothing; and what `train_q3n_mfu_pct` is measured against."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import bench_rules  # noqa: E402
from benchmark.harness import manifest, work_q3n  # noqa: E402

MANIFEST = ROOT / "benchmark" / "rehearsal" / "manifest_q3n.json"
MAN = json.loads(MANIFEST.read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["train_q3n_mfu_pct", "train_moe_device_ms", "train_moe_experts_device_ms",
       "train_gdn_device_ms", "train_gdn_scan_device_ms"]


@pytest.fixture(scope="module")
def line():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--manifest", str(MANIFEST),
         "--rehearse", "--workload", "tiny_q3n_train", "--seed", str(2**31 + 11),
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_rehearsal_cell_is_correct_against_the_new_reference(line):
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # float32 on the CPU: reduction order only (tests/test_hybrid_trunk.py has the reasons)
    assert line["detail"]["correct"]["logits_rms_err"] < 1e-4
    assert line["detail"]["correct"]["loss_rel_err"] < 1e-5
    assert line["metrics"]["window_compiles.train"]["value"] == 0


def test_a_cpu_line_carries_counts_only_and_no_reader_raised(line):
    sources = {m["name"]: m["source"] for m in MAN["per_layer"]}
    assert set(line["metrics"]) <= set(sources)
    for name, m in line["metrics"].items():
        if sources[name] != "program_counter":
            assert m["value"] is None, name
    assert "breakdown" not in line


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_where_nothing_is_named(name):
    read = manifest.reader(name)
    sizes = manifest.config_sizes(MAN, "tiny_q3n")
    ctx = manifest.Context(sizes=sizes, traffic={}, records={}, trace=None, peaks=None,
                           end_to_end={})
    assert read(ctx) is None  # no trace taken, no peaks: a rehearsal or an untraced run
    # a parent's program (the DALL-E block) names none of the new scopes
    dalle = manifest.config_sizes(BENCH, "dalle_2048_d8")
    ctx = manifest.Context(sizes=dalle, traffic={}, trace=None,
                           records={"steps": 3, "elapsed_s": 1.0, "batch": 4},
                           peaks={"bf16_flops_per_s": 197e12}, end_to_end={})
    assert read(ctx) is None


def test_scope_reader_finds_the_new_scopes_in_a_trace_and_nothing_in_an_old_one():
    from benchmark.harness import program_trace

    def trace_of(paths):
        ops = [["fusion", 10.0 * i, 5.0, p] for i, p in enumerate(paths)]
        events = {"devices": {"/device:TPU:0": {
            "ops": ops, "modules": [["jit_train_step(1)", 0.0, 10.0 * len(paths)]]}}, "host": []}
        ctx = manifest.Context(sizes={}, traffic={}, records={}, trace=object(), peaks=None,
                               end_to_end={})
        ctx.program_trace = program_trace.ProgramTrace(events)
        return ctx

    new = trace_of(["jit(train_step)/fwd_bwd/jvp(attn)/gdn_scan/dot_general",
                    "jit(train_step)/fwd_bwd/transpose(jvp(attn))/gdn_scan/while/body/dot_general",
                    "jit(train_step)/fwd_bwd/jvp(ff)/checkpoint/moe_experts/gmm",
                    "jit(train_step)/fwd_bwd/jvp(ff)/moe_router/dot_general",
                    "jit(train_step)/fwd_bwd/jvp(attn)/gdn_proj/dot_general",
                    "jit(train_step)/optimizer_update/add"])
    assert work_q3n.scope_device_ms(new, ("gdn_scan",)) == pytest.approx(10e-6)
    assert manifest.reader("train_gdn_device_ms")(new) == pytest.approx(15e-6)
    assert manifest.reader("train_moe_device_ms")(new) == pytest.approx(10e-6)
    assert manifest.reader("train_moe_experts_device_ms")(new) == pytest.approx(5e-6)
    old = trace_of(["jit(train_step)/fwd_bwd/jvp(attn)/flash_attn/mul",
                    "jit(train_step)/fwd_bwd/jvp(ff)/dot_general"])
    for name in NEW[1:]:
        assert manifest.reader(name)(old) is None


def test_required_operations_of_the_cell():
    """work_q3n against the issue's own arithmetic: about 0.42 GFLOP a token
    forward, 21 TFLOP an optimizer step of 16,896 tokens."""
    sizes = manifest.config_sizes(BENCH, "qwen3_next_ep16_p1")
    assert work_q3n.seq_len(sizes) == 4224 and work_q3n.vocabulary(sizes) == 18992
    per_token = work_q3n.train_step_flops(sizes, 1) / 3 / 4224
    assert 0.40e9 < per_token < 0.45e9
    step = work_q3n.train_step_flops(sizes, 4)
    assert 20e12 < step < 23e12
    # the held experts count at the EXPECTED pairs a token: ten times the share held
    more = dict(sizes, moe_experts_held=64)
    extra = work_q3n.matmul_weights_per_token(more) - work_q3n.matmul_weights_per_token(sizes)
    assert extra == pytest.approx(4 * 10 * 32 / 512 * 3 * 2048 * 512)


def the_q3n_cell_and_its_metrics_are_as_the_issue_names_them(bench):
    """A rule of the manifest (bench_rules.py): what this cell must carry and
    must not, and that the four cells it was appended to come first.  How many
    cells follow, and on how many chips, is not this test's to say."""
    cell = manifest.cell(bench, "train_q3n_ep16")
    assert cell["config"] == "qwen3_next_ep16_p1" and cell["traffic"] == "steps_adam_b4"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    traffic = manifest.traffic(cell["traffic"])
    assert traffic["kind"] == "train_steps"
    assert traffic["microbatch"] * traffic["grad_accum"] == 4
    assert traffic["distinct_batches"] == 4 and traffic["trace_steps"] == 4
    per_layer = bench_rules.per_layer_of(bench, "train_q3n_ep16")
    assert set(NEW) <= per_layer
    assert not per_layer & {"mfu_pct", "train_shift_device_ms", "train_stack_device_ms"}, \
        "the DALL-E block's arithmetic"
    assert bench_rules.names(bench["workloads"])[:4] == [
        "serve_batch", "train_d24", "train_d8", "serve_guided"]
    assert bench_rules.in_order(["train_d8", "train_d24", "train_q3n_ep16"],
                                bench_rules.entry(bench, "end_to_end", "train_img_tok_per_s")["workloads"])


MANIFEST_RULES = [the_q3n_cell_and_its_metrics_are_as_the_issue_names_them]


def test_the_new_cell_and_its_metrics_are_in_the_manifest_as_the_issue_names_them():
    the_q3n_cell_and_its_metrics_are_as_the_issue_names_them(BENCH)
