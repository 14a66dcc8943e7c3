"""The window-and-full-attention trunk's rehearsal: a tiny configuration of the
same kinds of layer (a dense layer, three `gated_window` layers and a
`gated_full` one with their own head counts and per-head gates, 32 softmax
experts top-8 x 2.5 of which 4 are held) through `run.py --rehearse` with
`--trace 1`, as the benchmark's command runs the cell `train_laguna_ep8`; the kind's
records and its comparison; the new readers where a trace names nothing and
where it names their scopes; what `train_laguna_mfu_pct` and
`train_window_flash_roofline` are measured against; and the cell as the
manifest names it, stated as rules a later cell, configuration or reader does
not break (`MANIFEST_RULES`, bench_rules.py)."""
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
from benchmark.harness import manifest, work_laguna, work_q3n  # noqa: E402

MANIFEST = ROOT / "benchmark" / "rehearsal" / "manifest_laguna.json"
MAN = json.loads(MANIFEST.read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "train_laguna_ep8"
NEW = ["train_window_attn_device_ms", "train_window_flash_roofline", "train_laguna_mfu_pct"]
# the accepted readers the cell joins, and its end-to-end metric
SHARED = ["train_img_tok_per_s", "window_compiles.train", "host_dispatch_ms", "flash_device_ms",
          "train_attn_device_ms", "train_logits_loss_device_ms", "train_remat_device_ms",
          "train_unscoped_pct", "train_moe_device_ms", "train_moe_experts_device_ms",
          "train_dense_ff_device_ms"]
# the other blocks' arithmetic and the other trunks' scope readers: never on this cell
NOT_THIS_TRUNKS = {"mfu_pct", "train_q3n_mfu_pct", "train_glm_mfu_pct", "train_gdn_device_ms",
                   "train_gdn_scan_device_ms", "train_mla_device_ms", "train_mla_core_device_ms",
                   "train_mtp_device_ms", "train_shift_device_ms", "train_stack_device_ms"}
# what the benchmark held before this cell: later entries FOLLOW them
ACCEPTED_CELLS = ["serve_batch", "train_d24", "train_d8", "serve_guided", "train_q3n_ep16",
                  "train_glm47_ep8", "serve_olmoh_s32"]


@pytest.fixture(scope="module")
def line():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--manifest", str(MANIFEST),
         "--rehearse", "--workload", "tiny_laguna_train", "--seed", str(2**31 + 43),
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_rehearsal_cell_is_correct_against_the_new_reference(line):
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    detail = line["detail"]["correct"]
    # float32 on the CPU: reduction order only (tests/test_laguna_trunk.py has the reasons)
    assert detail["logits_rms_err"] < 1e-4 and detail["logits_worst_row_err"] < 1e-4
    assert detail["loss_rel_err"] < 1e-5 and detail["refused_by"] == []
    assert line["metrics"]["window_compiles.train"]["value"] == 0


def test_a_cpu_line_carries_counts_only_and_no_reader_raised(line):
    sources = {m["name"]: m["source"] for m in MAN["per_layer"]}
    assert set(line["metrics"]) <= set(sources)
    for name, m in line["metrics"].items():
        if sources[name] != "program_counter":
            assert m["value"] is None, name
    assert "breakdown" not in line and line["detail"]["window"] is None


def test_the_kind_keeps_the_load_of_each_window_step_and_leaves_the_mtp_kind_as_it_was():
    """The generator itself, in this process at the tiny size: the load at
    the window's start and end, and both halves of its comparison (float32
    here: the step's gradient is the reference's to rounding)."""
    from benchmark.harness import correct_mtp, device, tracer as tracer_mod
    from benchmark.kinds import train_steps_mtp, train_steps_routed

    sizes = manifest.config_sizes(MAN, "tiny_laguna")
    out = train_steps_routed.run(sizes, manifest.traffic("tiny_steps_routed"), 2**31 + 3, 0.5,
                                 tracer_mod.Tracer(False, ROOT / ".bench_trace" / "unused"),
                                 device.CompileCounter())
    r = out["records"]
    assert out["correct"] and out["failed"] == 0 and r["steps"] == out["attempted"] >= 1
    assert train_steps_mtp.correct_mtp is correct_mtp
    for name in ("moe_pairs_here", "moe_overflow_share", "moe_load_max_over_mean"):
        assert len(r[name]) == r["steps"], name
        assert set(r["window_detail"][name]) == {"first8_mean", "last8_mean", "max"}
    assert "mtp_loss" not in r and "moe_bias_abs_max" not in r, "no module, no bias"
    assert set(r["correct_detail"]) >= {"logits_rms_err", "logits_rows_over", "refused_by", "step"}
    step = r["correct_detail"]["step"]
    assert step["refused_by"] == [] and r["correct_detail"]["refused_by"] == []
    assert step["grad_worst_leaf_err"] < 1e-5 and step["update_worst_leaf_err"] < 1e-3


def test_the_routed_kind_keeps_the_mtp_loop_and_its_step_verdict_decides_correct(monkeypatch):
    """`train_steps_routed.run` is `train_steps_mtp.run`'s loop a second time
    (PERF.md section 7 has the fold).  Until they are one, both on the dense
    tiny configuration under a clock that moves a millisecond each time it
    is read (as test_bench_rehearsal_glm.py holds `train_steps` and
    `train_steps_mtp`): a loop that reads the clock once more or once less,
    closes its window by another rule, counts its steps or draws its batches
    otherwise gives other numbers.  And a step the comparison refuses makes
    the run's `correct` false, whatever the forward read."""
    from benchmark.harness import correct_mtp, correct_routed, device, tracer as tracer_mod
    from benchmark.kinds import train_steps_mtp, train_steps_routed

    class Clock:
        def __init__(self):
            self.now = 0.0

        def monotonic(self):
            self.now += 1e-3
            return self.now

        perf_counter = monotonic

    dense = manifest.config_sizes(manifest.load(ROOT / "benchmark" / "rehearsal" / "manifest.json"), "tiny")
    traffic = manifest.traffic("tiny_steps_routed")
    monkeypatch.setattr(correct_mtp, "train_forward_agrees", lambda *a: (True, {}))
    monkeypatch.setattr(correct_routed, "train_forward_agrees", lambda *a: (True, {"refused_by": []}))
    monkeypatch.setattr(correct_routed, "train_step_agrees", lambda *a: (False, {"refused_by": ["grad"]}))
    outs = []
    for kind in (train_steps_mtp, train_steps_routed):
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
    # the same draws and the same keys: the same losses, step for step
    assert one["records"]["losses"] == pytest.approx(two["records"]["losses"], rel=1e-6)
    assert one["correct"] is True and two["correct"] is False
    assert two["records"]["correct_detail"]["refused_by"] == ["grad"]


@pytest.fixture(scope="module")
def tiny_step():
    """The tiny trunk's training step at four microbatches (the cell's
    accumulation), its first state and first batch, as the kind makes them."""
    from benchmark.harness import build
    from benchmark.kinds import train_steps_routed

    sizes = manifest.config_sizes(MAN, "tiny_laguna")
    traffic = dict(manifest.traffic("tiny_steps_routed"), grad_accum=4)
    cfg, _, init, step_fn = train_steps_routed.build_step(sizes, traffic)
    _, _, _, one_microbatch = train_steps_routed.build_step(sizes, dict(traffic, grad_accum=1))
    batch = train_steps_routed.draw_batches(cfg, 4, 1, build.seed_key(5, 2))[0]
    return sizes, lambda: init(build.seed_key(5, 0)), step_fn, one_microbatch, batch


@pytest.mark.parametrize("fault", ["none", "state_unchanged", "first_microbatch_only"])
def test_the_step_comparison_refuses_a_planted_fault(tiny_step, fault):
    """`correct_routed.train_step_agrees`, the half of the cell's `correct`
    that reads the timed step, on the real step and on two planted into it:
    a step that hands its state back as it was, and one that trains on the
    first of its four microbatches only."""
    import jax

    from benchmark.harness import build, correct_routed

    sizes, init, step_fn, one_microbatch, batch = tiny_step
    planted = {
        "none": step_fn,
        "state_unchanged": lambda state, b, key: (state, {}),
        "first_microbatch_only": lambda state, b, key: one_microbatch(
            state, jax.tree.map(lambda a: a[:1], b), key),
    }[fault]
    ok, detail = correct_routed.train_step_agrees(planted, init, batch, build.raw_key(5, 0), sizes)
    if fault == "none":
        assert ok and detail["refused_by"] == [], detail
        assert detail["grad_worst_leaf_err"] < 1e-5 and detail["update_worst_leaf_err"] < 1e-3
    else:
        assert not ok and detail["refused_by"] == ["grad"], detail
        assert detail["grad_worst_leaf_err"] > 3 * detail["grad_worst_leaf_limit"]


@pytest.fixture(scope="module")
def control_verdicts():
    import jax.numpy as jnp
    from benchmark.harness import build, correct_routed

    sizes = manifest.config_sizes(MAN, "tiny_laguna")
    cfg = build.dalle_config(sizes)
    params = build.make_weights(cfg, 3, jnp.float32)
    out = correct_routed.controls(params, cfg, sizes, 3)
    out["system_bfloat16"] = correct_routed.train_forward_agrees(params, cfg, sizes, jnp.bfloat16, 3)
    return out


@pytest.mark.parametrize("name,refused_by", [
    ("system_bfloat16", []),
    ("bfloat16_products", []),
    ("float8_e4m3fn_products", ["rms", "rows_over"]),
    ("rows_shifted_by_one", ["rms", "worst_row", "rows_over"]),
    ("one_row_wrong", ["rms", "worst_row"]),  # 24 rows here: at 4,224 one row is ~2 % in the RMS
    ("one_row_in_12_off_by_8pct", ["rows_over"]),
])
def test_the_comparison_refuses_what_each_limit_is_for(control_verdicts, name, refused_by):
    """`correct_routed.verdict`, the function that decides the cell's
    `correct`, on forwards whose answer is known beforehand
    (`correct_routed.controls`; at the published widths on the chip:
    `tools/correct_routed_controls.py`)."""
    ok, detail = control_verdicts[name]
    assert detail["refused_by"] == refused_by and ok is (not refused_by), detail
    if name == "float8_e4m3fn_products":
        assert detail["loss_rel_err"] < detail["loss_tolerance"], "the loss does not see precision"


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_where_nothing_is_named(name):
    read = manifest.reader(name)
    sizes = manifest.config_sizes(MAN, "tiny_laguna")
    ctx = manifest.Context(sizes=sizes, traffic={}, records={}, trace=None, peaks=None, end_to_end={})
    assert read(ctx) is None  # no trace taken, no peaks: a rehearsal or an untraced run
    # the other trunks' programs name none of the new scopes and have no window layer
    for other in ("dalle_2048_d8", "qwen3_next_ep16_p1", "glm47_flash_ep8_d5"):
        ctx = manifest.Context(sizes=manifest.config_sizes(BENCH, other), traffic={}, trace=None,
                               records={"steps": 3, "elapsed_s": 1.0, "batch": 4},
                               peaks={"bf16_flops_per_s": 197e12}, end_to_end={})
        assert read(ctx) is None


def _trace_of(paths, names=None):
    from benchmark.harness import program_trace

    names = names or ["fusion"] * len(paths)
    ops = [[name, 10.0 * i, 5.0, p] for i, (name, p) in enumerate(zip(names, paths))]
    events = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_train_step(1)", 0.0, 10.0 * len(paths)]]}}, "host": []}
    ctx = manifest.Context(sizes=manifest.config_sizes(BENCH, "laguna_xs2_ep8_d5"), traffic={},
                           records={"batch": 4}, trace=object(), peaks={"bf16_flops_per_s": 197e12},
                           end_to_end={})
    ctx.program_trace = program_trace.ProgramTrace(events)
    return ctx


def test_scope_readers_find_the_new_scopes_in_a_trace_and_nothing_in_an_old_one():
    new = _trace_of(["jit(train_step)/fwd_bwd/jvp(attn)/swa/swa_core/flash_fwd",
                     "jit(train_step)/fwd_bwd/transpose(jvp(attn))/swa/swa_core/flash_dkv",
                     "jit(train_step)/fwd_bwd/jvp(attn)/swa/dot_general",
                     "jit(train_step)/fwd_bwd/jvp(attn)/full_core/flash_fwd",
                     "jit(train_step)/fwd_bwd/jvp(ff)/moe_experts/gmm"],
                    ["%flash_compact_fwd.1", "%flash_compact_dkv.2", "%fusion.3", "%flash_compact_fwd.4",
                     "%gmm.5"])
    assert manifest.reader("train_window_attn_device_ms")(new) == pytest.approx(15e-6)
    assert work_laguna.flash_ms_under(new, "swa_core") == pytest.approx(10e-6)
    assert work_laguna.flash_ms_under(new, "full_core") == pytest.approx(5e-6)
    share = manifest.reader("train_window_flash_roofline")(new)
    assert share == pytest.approx(100 * work_laguna.window_flash_flops(new.sizes, 4) / 10e-9 / 197e12)
    old = _trace_of(["jit(train_step)/fwd_bwd/jvp(attn)/flash_attn/mul",
                     "jit(train_step)/fwd_bwd/jvp(attn)/mla_core/flash_fwd",
                     "jit(train_step)/fwd_bwd/jvp(ff)/moe_experts/gmm"],
                    ["%fusion.1", "%flash_compact_fwd.2", "%gmm.3"])
    for name in NEW[:2]:
        assert manifest.reader(name)(old) is None


def test_required_operations_of_the_cell():
    """work_laguna against the cut's own arithmetic: ~2.1 GFLOP a token
    forward and backward, ~36 TFLOP a step; the band's pairs only."""
    sizes = manifest.config_sizes(BENCH, "laguna_xs2_ep8_d5")
    assert work_q3n.seq_len(sizes) == 4224 and work_q3n.vocabulary(sizes) == 12544
    assert work_laguna.attention_weights(sizes, "gated_window") == pytest.approx(37.88e6, rel=1e-3)
    assert work_laguna.attention_weights(sizes, "gated_full") == pytest.approx(29.46e6, rel=1e-3)
    assert work_laguna.pairs(sizes, "gated_window", 4224) == 2_031_872
    assert work_laguna.pairs(sizes, "gated_full", 4224) == 4224 * 4225 / 2
    per_token = work_laguna.train_step_flops(sizes, 1) / 4224
    assert 2.0e9 < per_token < 2.2e9
    assert 35e12 < work_laguna.train_step_flops(sizes, 4) < 36.5e12
    # the window calls: 2,031,872 pairs x 128 x 4 x 64 heads x 3 layers x 4 sequences x 3
    assert work_laguna.window_flash_flops(sizes, 4) == 3 * 4 * 3 * 4.0 * 64 * 128 * 2_031_872
    # the held experts count at the EXPECTED pairs a token: twice the share held, twice the routed part
    more = dict(sizes, moe_experts_held=64)
    extra = work_laguna.weights_per_token(more) - work_laguna.weights_per_token(sizes)
    assert extra == pytest.approx(4 * 32 * 8 / 256 * 3 * 2048 * 512)


# ---- the manifest, as rules (functions of a manifest: bench_rules.py) ---------
def the_laguna_cell_follows_the_accepted_entries(bench):
    """Append-only: the cells the benchmark held keep their places and this
    one follows them; its readers are one run after the accepted ones."""
    cells = bench_rules.names(bench["workloads"])
    assert cells[:len(ACCEPTED_CELLS)] == ACCEPTED_CELLS and CELL in cells[len(ACCEPTED_CELLS):]
    assert "laguna_xs2_ep8_d5" in bench_rules.names(bench["configs"])[5:]
    readers = bench_rules.names(bench["per_layer"])
    assert bench_rules.run_of(NEW, readers)
    assert bench_rules.in_order(["train_glm_mfu_pct"] + NEW, readers)


def the_laguna_cell_and_its_metrics_are_as_named(bench):
    cell = manifest.cell(bench, CELL)
    assert cell["config"] == "laguna_xs2_ep8_d5" and cell["traffic"] == "steps_adam_b4_fresh128"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    traffic = manifest.traffic(cell["traffic"])
    assert traffic["kind"] == "train_steps_routed"
    assert traffic["microbatch"] == 1 and traffic["grad_accum"] == 4
    assert traffic["distinct_batches"] == 128 and traffic["trace_steps"] == 4
    per_layer = bench_rules.per_layer_of(bench, CELL)
    # held from both sides by name: what it must carry, and what it must not
    assert set(NEW) | set(SHARED[1:]) <= per_layer
    assert not per_layer & NOT_THIS_TRUNKS, "the other blocks' arithmetic, the other trunks' scopes"
    assert "train_img_tok_per_s" in bench_rules.names(manifest.metrics_for(bench, "end_to_end", CELL))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in SHARED:
            assert bench_rules.in_order(["train_glm47_ep8", CELL], m["workloads"]), m["name"]


MANIFEST_RULES = [the_laguna_cell_follows_the_accepted_entries,
                  the_laguna_cell_and_its_metrics_are_as_named]


def test_the_new_cell_and_its_metrics_are_in_the_manifest_as_named():
    the_laguna_cell_and_its_metrics_are_as_named(BENCH)
    the_laguna_cell_follows_the_accepted_entries(BENCH)
