"""The latent-attention trunk's rehearsal: a tiny configuration of the same
kinds of layer (a dense layer, two `mla` layers with 16 bias-balanced experts
of which 4 are held, the prediction module) through `run.py --rehearse` with
`--trace 1`, as the driver would run the cell `train_glm47_ep8`; the new kind's
records; the new per-layer readers where a trace names nothing; what
`train_glm_mfu_pct` is measured against; the cell as the manifest names it,
stated as rules a later cell, configuration or reader does not break
(`MANIFEST_RULES`, bench_rules.py); that the two train kinds keep one
loop; and that `correct_mtp`'s comparison fails what it is there to fail."""
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
from benchmark.harness import manifest, work_glm, work_q3n  # noqa: E402

MANIFEST = ROOT / "benchmark" / "rehearsal" / "manifest_glm.json"
MAN = json.loads(MANIFEST.read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["train_glm_mfu_pct", "train_mla_device_ms", "train_mla_core_device_ms",
       "train_mtp_device_ms", "train_dense_ff_device_ms"]
SHARED = ["train_img_tok_per_s", "window_compiles.train", "host_dispatch_ms",
          "train_moe_device_ms", "train_moe_experts_device_ms"]
# what the benchmark held when PR 32 turned these tests into rules: later entries FOLLOW them
ACCEPTED_CELLS = ["serve_batch", "train_d24", "train_d8", "serve_guided", "train_q3n_ep16",
                  "train_glm47_ep8"]
ACCEPTED_CONFIGS = ["dalle_2048_d8", "dalle_2048_d24", "qwen3_next_ep16_p1", "glm47_flash_ep8_d5"]
Q3N_NEW = ["train_q3n_mfu_pct", "train_moe_device_ms", "train_moe_experts_device_ms",
           "train_gdn_device_ms", "train_gdn_scan_device_ms"]
# the other blocks' arithmetic and the other trunk's scope readers: never on this cell
NOT_THIS_TRUNKS = {"mfu_pct", "train_q3n_mfu_pct", "train_shift_device_ms", "train_stack_device_ms",
                   "train_gdn_device_ms", "train_gdn_scan_device_ms"}


@pytest.fixture(scope="module")
def line():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--manifest", str(MANIFEST),
         "--rehearse", "--workload", "tiny_glm_train", "--seed", str(2**31 + 11),
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_rehearsal_cell_is_correct_against_the_new_reference(line):
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    detail = line["detail"]["correct"]
    # float32 on the CPU: reduction order only (tests/test_glm_trunk.py has the reasons)
    assert detail["logits_rms_err"] < 1e-4 and detail["mtp_logits_rms_err"] < 1e-4
    assert detail["logits_worst_row_err"] < 1e-4 and detail["mtp_logits_worst_row_err"] < 1e-4
    assert detail["loss_rel_err"] < 1e-5
    assert line["metrics"]["window_compiles.train"]["value"] == 0


def test_a_cpu_line_carries_counts_only_and_no_reader_raised(line):
    sources = {m["name"]: m["source"] for m in MAN["per_layer"]}
    assert set(line["metrics"]) <= set(sources)
    for name, m in line["metrics"].items():
        if sources[name] != "program_counter":
            assert m["value"] is None, name
    assert "breakdown" not in line and line["detail"]["window"] is None


def test_the_kind_keeps_each_window_steps_aux(monkeypatch):
    """The generator itself, in this process at the tiny size: the records the
    issue's load criterion is read from."""
    from benchmark.harness import device, tracer as tracer_mod
    from benchmark.kinds import train_steps_mtp

    sizes = manifest.config_sizes(MAN, "tiny_glm")
    out = train_steps_mtp.run(sizes, manifest.traffic("tiny_steps_mtp"), 2**31 + 3, 0.5,
                              tracer_mod.Tracer(False, ROOT / ".bench_trace" / "unused"),
                              device.CompileCounter())
    r = out["records"]
    assert out["correct"] and out["failed"] == 0 and r["steps"] == out["attempted"] >= 1
    for name in ("moe_pairs_here", "moe_overflow_share", "main_loss", "mtp_loss", "moe_bias_abs_max"):
        assert len(r[name]) == r["steps"], name
        assert set(r["window_detail"][name]) == {"first8_mean", "last8_mean", "max"}
    assert r["moe_bias_abs_max"][0] == pytest.approx(0.002), "the window starts behind two warm-up steps"
    assert all(a + 0.3 * b == pytest.approx(l, rel=1e-5)
               for a, b, l in zip(r["main_loss"], r["mtp_loss"], r["losses"]))
    assert r["window_compiles"] == 0 and r["window_detail"]["batches_used_twice"] >= 0


def test_the_two_train_kinds_keep_one_loop(monkeypatch):
    """`train_steps_mtp.run` is `train_steps.run`'s loop a second time (PERF.md
    section 7 has the fold).  Until they are one, both on the dense tiny
    configuration under a clock that moves a millisecond each time it is read:
    a loop that reads the clock once more or once less, closes its window by
    another rule or counts its steps otherwise gives other numbers."""
    from benchmark.harness import correct, correct_mtp, device, tracer as tracer_mod
    from benchmark.kinds import train_steps, train_steps_mtp

    class Clock:
        def __init__(self):
            self.now = 0.0

        def monotonic(self):
            self.now += 1e-3
            return self.now

        perf_counter = monotonic

    dense = manifest.config_sizes(manifest.load(ROOT / "benchmark" / "rehearsal" / "manifest.json"), "tiny")
    traffic = manifest.traffic("tiny_steps")
    # the comparisons are not the loop's: the dense reference has no module for correct_mtp to read
    monkeypatch.setattr(correct, "train_forward_agrees", lambda *a: (True, {}))
    monkeypatch.setattr(correct_mtp, "train_forward_agrees", lambda *a: (True, {}))
    outs = []
    for kind in (train_steps, train_steps_mtp):
        monkeypatch.setattr(kind, "time", Clock())
        outs.append(kind.run(dense, traffic, 2**31 + 3, 0.05,
                             tracer_mod.Tracer(False, ROOT / ".bench_trace" / "unused"),
                             device.CompileCounter()))
    one, two = outs
    assert one["records"]["steps"] == two["records"]["steps"] > 2
    assert one["records"]["elapsed_s"] == pytest.approx(two["records"]["elapsed_s"], rel=1e-9)
    assert one["end_to_end"]["train_img_tok_per_s"] == pytest.approx(
        two["end_to_end"]["train_img_tok_per_s"], rel=1e-9)
    assert one["attempted"] == two["attempted"] and one["records"]["batch"] == two["records"]["batch"]
    assert len(one["records"]["host_dispatch_ms"]) == len(two["records"]["host_dispatch_ms"])
    # the same draws and the same keys behind different warm-up bookkeeping: the same first loss
    assert one["records"]["losses"][0] == pytest.approx(two["records"]["losses"][0], rel=1e-6)


@pytest.fixture(scope="module")
def control_verdicts():
    import jax.numpy as jnp
    from benchmark.harness import build, correct_mtp

    sizes = manifest.config_sizes(MAN, "tiny_glm")
    cfg = build.dalle_config(sizes)
    params = build.make_weights(cfg, 3, jnp.float32)
    out = correct_mtp.controls(params, cfg, sizes, 3)
    out["system_bfloat16"] = correct_mtp.train_forward_agrees(params, cfg, sizes, jnp.bfloat16, 3)
    return out


@pytest.mark.parametrize("name,refused_by", [
    ("system_bfloat16", []),  # one routing choice flips at this seed: one row of 23 at 3.7 %, sound
    ("bfloat16_products", []),
    ("float8_e4m3fn_products", ["rms", "rows_over"]),
    ("rows_shifted_by_one", ["rms", "worst_row", "rows_over"]),
    ("one_row_wrong", ["rms", "worst_row"]),  # 24 rows here: at 4,224 one row is 2.2 % in the RMS
    ("one_row_in_12_off_by_8pct", ["rows_over"]),
])
def test_the_comparison_refuses_what_each_limit_is_for(control_verdicts, name, refused_by):
    """`correct_mtp.verdict`, the function that decides the cell's `correct`,
    on forwards whose answer is known beforehand (`correct_mtp.controls`; at
    the published widths on the chip: `tools/correct_mtp_controls.py`)."""
    ok, detail = control_verdicts[name]
    assert detail["refused_by"] == refused_by and ok is (not refused_by), detail
    if name in ("system_bfloat16", "bfloat16_products"):
        assert detail["mtp_logits_rows_over"] == 1 and detail["mtp_logits_worst_row_err"] > 0.03
    if name == "float8_e4m3fn_products":
        assert detail["loss_rel_err"] < detail["loss_tolerance"], "the loss does not see precision"


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_where_nothing_is_named(name):
    read = manifest.reader(name)
    sizes = manifest.config_sizes(MAN, "tiny_glm")
    ctx = manifest.Context(sizes=sizes, traffic={}, records={}, trace=None, peaks=None,
                           end_to_end={})
    assert read(ctx) is None  # no trace taken, no peaks: a rehearsal or an untraced run
    # a parent's program (the DALL-E block, the other hybrid trunk) names none of the new scopes
    for other in ("dalle_2048_d8", "qwen3_next_ep16_p1"):
        ctx = manifest.Context(sizes=manifest.config_sizes(BENCH, other), traffic={}, trace=None,
                               records={"steps": 3, "elapsed_s": 1.0, "batch": 4},
                               peaks={"bf16_flops_per_s": 197e12}, end_to_end={})
        assert read(ctx) is None


def test_scope_readers_find_the_new_scopes_in_a_trace_and_nothing_in_an_old_one():
    from benchmark.harness import program_trace

    def trace_of(paths):
        ops = [["fusion", 10.0 * i, 5.0, p] for i, p in enumerate(paths)]
        events = {"devices": {"/device:TPU:0": {
            "ops": ops, "modules": [["jit_train_step(1)", 0.0, 10.0 * len(paths)]]}}, "host": []}
        ctx = manifest.Context(sizes={}, traffic={}, records={}, trace=object(), peaks=None,
                               end_to_end={})
        ctx.program_trace = program_trace.ProgramTrace(events)
        return ctx

    new = trace_of(["jit(train_step)/fwd_bwd/jvp(attn)/mla_core/flash_fwd",
                    "jit(train_step)/fwd_bwd/transpose(jvp(attn))/mla_core/flash_dkv",
                    "jit(train_step)/fwd_bwd/jvp(attn)/mla_q_proj/dot_general",
                    "jit(train_step)/fwd_bwd/jvp(mtp)/attn/mla_kv_proj/dot_general",
                    "jit(train_step)/fwd_bwd/jvp(mtp)/mtp_merge/dot_general",
                    "jit(train_step)/fwd_bwd/transpose(jvp(mtp))/ff/moe_experts/gmm",
                    "jit(train_step)/fwd_bwd/jvp(ff)/dense_ff/dot_general",
                    "jit(train_step)/moe_bias_update/sign",
                    "jit(train_step)/optimizer_update/add"])
    assert manifest.reader("train_mla_core_device_ms")(new) == pytest.approx(10e-6)
    assert manifest.reader("train_mla_device_ms")(new) == pytest.approx(20e-6)
    assert manifest.reader("train_mtp_device_ms")(new) == pytest.approx(15e-6)
    assert manifest.reader("train_dense_ff_device_ms")(new) == pytest.approx(5e-6)
    assert manifest.reader("train_moe_experts_device_ms")(new) == pytest.approx(5e-6)
    assert work_q3n.scope_device_ms(new, ("moe_bias_update",)) == pytest.approx(5e-6)
    old = trace_of(["jit(train_step)/fwd_bwd/jvp(attn)/flash_attn/mul",
                    "jit(train_step)/fwd_bwd/jvp(attn)/gdn_scan/dot_general",
                    "jit(train_step)/fwd_bwd/jvp(ff)/moe_experts/gmm"])
    for name in NEW[1:]:
        assert manifest.reader(name)(old) is None


def test_required_operations_of_the_cell():
    """work_glm against the issue's own arithmetic: ~353 M matmul weights a
    token, ~2.9 GFLOP a token forward and backward, ~49 TFLOP a step."""
    sizes = manifest.config_sizes(BENCH, "glm47_flash_ep8_d5")
    assert work_q3n.seq_len(sizes) == 4224 and work_q3n.vocabulary(sizes) == 19360
    assert work_glm.mla_weights(sizes) == pytest.approx(21.76e6, rel=1e-3)
    assert work_glm.dense_weights(sizes) == 3 * 2048 * 10240
    weights = work_glm.trunk_weights_per_token(sizes) + work_glm.module_weights_per_token(sizes)
    assert weights == pytest.approx(352.6e6, rel=1e-3)
    per_token = work_glm.train_step_flops(sizes, 1) / 4224
    assert 2.8e9 < per_token < 3.0e9
    assert 48e12 < work_glm.train_step_flops(sizes, 4) < 50e12
    # the held experts count at the EXPECTED pairs a token: four times the share held
    more = dict(sizes, moe_experts_held=16)
    extra = work_glm.routed_weights(more) - work_glm.routed_weights(sizes)
    assert extra == pytest.approx(4 * 8 / 64 * 3 * 2048 * 1536)
    # without the module: its weights and its attention go, nothing else
    less = dict(sizes, mtp_depth=0)
    gone = work_glm.train_step_flops(sizes, 1) - work_glm.train_step_flops(less, 1)
    assert gone == pytest.approx(3 * (2 * work_glm.module_weights_per_token(sizes) * 4223
                                      + work_glm.attention_flops(sizes, 4223)))


# ---- the manifest, as rules (functions of a manifest: bench_rules.py) ---------
def the_accepted_entries_keep_their_order(bench):
    """Append-only: the cells and configurations the benchmark held keep their
    places; each PR's per-layer readers stay one run, PR 26's before PR 30's."""
    assert bench_rules.names(bench["workloads"])[:6] == ACCEPTED_CELLS
    assert bench_rules.names(bench["configs"])[:4] == ACCEPTED_CONFIGS
    readers = bench_rules.names(bench["per_layer"])
    assert bench_rules.run_of(Q3N_NEW, readers) and bench_rules.in_order(Q3N_NEW + NEW, readers)


def the_glm_cell_and_its_metrics_are_as_the_issue_names_them(bench):
    cell = manifest.cell(bench, "train_glm47_ep8")
    assert cell["config"] == "glm47_flash_ep8_d5" and cell["traffic"] == "steps_adam_b4_fresh"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    traffic = manifest.traffic(cell["traffic"])
    assert traffic["kind"] == "train_steps_mtp"
    assert traffic["microbatch"] * traffic["grad_accum"] == 4
    assert traffic["distinct_batches"] == 128 and traffic["trace_steps"] == 4
    per_layer = bench_rules.per_layer_of(bench, "train_glm47_ep8")
    # held from both sides by name: what it must carry, and what it must not
    assert set(NEW) | set(SHARED[1:]) <= per_layer
    assert not per_layer & NOT_THIS_TRUNKS, "the other blocks' arithmetic, the other trunk's scopes"
    assert "train_img_tok_per_s" in bench_rules.names(
        manifest.metrics_for(bench, "end_to_end", "train_glm47_ep8"))
    # its readers are one run; a shared list holds both hybrid cells in their order, and may go on
    assert bench_rules.run_of(NEW, bench_rules.names(bench["per_layer"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in SHARED:
            assert bench_rules.in_order(["train_q3n_ep16", "train_glm47_ep8"], m["workloads"]), m["name"]


def the_other_hybrid_cell_is_still_named_as_its_issue_named_it(bench):
    """`test_bench_rehearsal_q3n.py` holds its own cell to its traffic and to
    what it must and must not carry of what PR 26 knew; what only this file
    knows is that the latent-attention trunk's readers stay off it."""
    cell = manifest.cell(bench, "train_q3n_ep16")
    assert cell["config"] == "qwen3_next_ep16_p1" and cell["traffic"] == "steps_adam_b4"
    per_layer = bench_rules.per_layer_of(bench, "train_q3n_ep16")
    assert set(Q3N_NEW) <= per_layer
    assert not per_layer & set(NEW), "the latent-attention trunk's readers"


MANIFEST_RULES = [the_accepted_entries_keep_their_order,
                  the_glm_cell_and_its_metrics_are_as_the_issue_names_them,
                  the_other_hybrid_cell_is_still_named_as_its_issue_named_it]


def test_the_new_cell_and_its_metrics_are_in_the_manifest_as_the_issue_names_them():
    the_glm_cell_and_its_metrics_are_as_the_issue_names_them(BENCH)
    the_accepted_entries_keep_their_order(BENCH)


def test_the_other_hybrid_cell_is_still_named_as_its_issue_named_it():
    the_other_hybrid_cell_is_still_named_as_its_issue_named_it(BENCH)
