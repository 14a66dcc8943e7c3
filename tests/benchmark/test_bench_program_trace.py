"""What the program names (PR 24) and the readers built on it: the jitted
programs' module names and the scopes inside their optimized HLO at tiny
sizes; `program_trace` on a fixture cut from chip traces, stats kept, with the
numbers it gave when cut; every new per-layer reader on that fixture and, for
the span and idle readers, on a trace made here on the CPU from a tiny
engine."""
import collections
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import bench_rules  # noqa: E402
from benchmark.harness import manifest, program_trace as pt  # noqa: E402
from benchmark.tools import program_report  # noqa: E402

FIXTURE = json.loads((ROOT / "benchmark" / "fixtures" / "program_trace_v5e.json").read_text())
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW_SERVE = ["decode_kv_gather_device_ms", "decode_compute_device_ms", "decode_sample_device_ms",
             "decode_unscoped_pct", "vae_decode_device_ms", "admit_host_ms", "evict_host_ms",
             "idle_in_program_spans_pct"]
NEW_TRAIN = ["train_attn_device_ms", "train_shift_device_ms", "train_logits_loss_device_ms",
             "train_remat_device_ms", "train_stack_device_ms", "train_unscoped_pct"]
PR23 = ["window_compiles.train", "window_compiles.serve", "host_dispatch_ms", "mfu_pct",
        "flash_device_ms", "lane_occupancy_pct", "gen_tok_per_s_median", "queue_wait_p50_ms",
        "ttft_p50_ms", "image_latency_done_p50_s", "decode_step_device_ms", "prefill_device_ms",
        "decode_step_roofline"]
# by scope of the named program, whatever block the step runs: every training cell's (ISSUEs 26, 32)
EVERY_TRUNKS = ["train_attn_device_ms", "train_logits_loss_device_ms", "train_remat_device_ms",
                "train_unscoped_pct", "flash_device_ms"]


# ---- the programs' names and scopes, at tiny sizes on the CPU ---------------
SCOPES_OF = {
    "serve_decode_step": ("embed", "norm", "token_shift", "kv_gather", "attn", "kv_write", "ff",
                          "sample", "codes_write"),
    "serve_admit": ("embed", "norm", "token_shift", "attn", "kv_write", "ff", "sample",
                    "codes_write"),
    "train_step": ("embed", "norm", "token_shift", "attn", "ff", "logits_loss", "stack_layers",
                   "fwd_bwd", "grad_norm", "optimizer_update"),
}
PROGRAMS = ["serve_decode_step", "serve_admit", "serve_ingest", "serve_vae_decode",
            "serve_spec_draft", "serve_spec_verify", "serve_prefill", "train_step"]
_TRIVIAL = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
_LINE = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]+)"')


@pytest.fixture(scope="module")
def lowered():
    """{program: its lowering} for every named program, tiny sizes."""
    import jax
    import jax.numpy as jnp
    import optax

    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models.vae import DiscreteVAEConfig, init_discrete_vae
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine
    from dalle_pytorch_tpu.serving.fleet import PrefillWorker
    from test_serving import tiny_cfg

    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    vcfg = DiscreteVAEConfig(image_size=16, num_tokens=cfg.num_image_tokens, num_layers=2,
                             hidden_dim=8, codebook_dim=8)
    eng = GenerationEngine(params, cfg, vae_params=init_discrete_vae(jax.random.PRNGKey(3), vcfg),
                           vae_cfg=vcfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4, spec_k=2))
    state = eng._state
    text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    k0, temp = jnp.zeros((2,), jnp.uint32), jnp.asarray(1.0, jnp.float32)
    tables = jnp.zeros((1, eng.pool.blocks_per_seq), jnp.int32)
    lane = jnp.zeros((1,), jnp.int32)
    prefill = PrefillWorker(params, cfg)._fn_for(1.0)
    layers, code = jax.eval_shape(prefill, params, text, k0, temp)
    out = {
        "serve_decode_step": eng._decode_fn.lower(params, state),
        "serve_admit": eng._admit_fn_for(1.0, 1).lower(params, state, text, k0, temp, tables, lane),
        "serve_ingest": eng._ingest_fn_for(1).lower(state, layers, code, tables, lane),
        "serve_vae_decode": eng._vae_decode.lower(jnp.zeros((1, cfg.image_seq_len), jnp.int32)),
        "serve_spec_draft": eng._spec_draft_fn.lower(params, state),
        "serve_spec_verify": eng._spec_verify_fn.lower(
            params, state, jax.eval_shape(eng._spec_draft_fn, params, state)),
        "serve_prefill": prefill.lower(params, text, k0, temp),
    }
    tcfg = tiny_cfg(execution="remat", scan_layers=True)

    def loss_fn(p, b, key):
        return dalle_mod.forward(p, tcfg, b["text"], b["image_codes"], return_loss=True)

    init_fn, step_fn = make_train_step(loss_fn, optax.adam(1e-3), settings=StepSettings())
    batch = {"text": jnp.ones((2, tcfg.text_seq_len), jnp.int32),
             "image_codes": jnp.ones((2, tcfg.image_seq_len), jnp.int32)}
    out["train_step"] = step_fn.lower(init_fn(dalle_mod.init_dalle(jax.random.PRNGKey(0), tcfg)),
                                      batch, jax.random.PRNGKey(0))
    return out


@pytest.fixture(scope="module")
def op_names(lowered):
    """{program: [op_name of each instruction of the optimized HLO that came
    from the program]} (compiler-made instructions carry no op_name, and the
    bodies of reductions carry one without the `jit(` root)."""
    out = {}
    for program in SCOPES_OF:
        names = []
        for line in lowered[program].compile().as_text().splitlines():
            m = _LINE.match(line)
            if not m or m.group(1) in _TRIVIAL:
                continue
            o = _OP_NAME.search(line)
            if o and o.group(1).startswith("jit("):
                names.append(o.group(1))
        out[program] = names
    return out


@pytest.mark.parametrize("program", PROGRAMS)
def test_lowered_program_carries_its_stable_name(lowered, program):
    assert f"module @jit_{program} " in lowered[program].as_text()[:200]
    assert pt.program_of(f"jit_{program}(1234567890)") == program


@pytest.mark.parametrize("program,scope", [(p, s) for p, ss in SCOPES_OF.items() for s in ss])
def test_optimized_hlo_names_every_scope(op_names, program, scope):
    by = collections.Counter(pt.scope_of(n) for n in op_names[program])
    assert by[scope] > 0, dict(by)


@pytest.mark.parametrize("program", sorted(SCOPES_OF))
def test_few_instructions_escape_the_scopes(op_names, program):
    names = op_names[program]
    unscoped = [n for n in names if pt.scope_of(n) == pt.UNSCOPED]
    assert len(names) > 100
    assert len(unscoped) <= 0.05 * len(names), collections.Counter(unscoped).most_common(8)


def test_remat_and_scan_show_in_the_train_steps_paths(op_names):
    paths = op_names["train_step"]
    assert any(pt.is_remat(p) and pt.scope_of(p) == "attn" for p in paths)
    assert any("/while/body/" in p and pt.scope_of(p) == "ff" for p in paths)


@pytest.mark.parametrize("path,scope", [
    ("jit(train_step)/fwd_bwd/transpose(jvp(attn))/flash_attn_bwd/mul", "attn"),
    ("jit(train_step)/fwd_bwd/jvp(logits_loss)/reduce_max", "logits_loss"),
    ("jit(train_step)/fwd_bwd/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/ff/dot_general", "ff"),
    ("jit(train_step)/fwd_bwd/jvp()/while/body/dynamic_slice", "fwd_bwd"),
    ("jit(serve_decode_step)/vmap(attn)/kv_gather/gather", "kv_gather"),
    ("jit(serve_decode_step)/vmap()/squeeze", ""),
    ("jit(serve_decode_step)/sample/attn_like/dot_general", "sample"),
    ("", ""),
])
def test_scope_of_takes_the_innermost_known_component(path, scope):
    assert pt.scope_of(path) == scope


# ---- program_trace on the chip fixture --------------------------------------
def _trace(which):
    return pt.ProgramTrace(json.loads(json.dumps(FIXTURE[which]["events"])))


def _close(a, b):
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            _close(a[k], b[k])
    elif isinstance(a, float) and b is not None:
        assert a == pytest.approx(b, rel=1e-9, abs=1e-9)
    else:
        assert a == b


@pytest.mark.parametrize("which", ["serve", "train"])
def test_fixture_reduces_to_the_numbers_it_was_cut_with(which):
    _close(program_report.tables(_trace(which)), FIXTURE[which]["expected"])


def test_fixture_serve_spans_nest_and_carry_their_stats():
    t = _trace("serve")
    (evict,) = t.spans_named("serve/evict")
    assert evict.parent.name == "serve/poll" and evict.stats["iter"] == evict.parent.stats["iter"]
    kids = [c.name for c in evict.children]
    assert kids[0] == "serve/evict.flag_sync" and "serve/evict.vae_decode" in kids
    (admit,) = t.spans_named("serve/admit")
    assert [c.name for c in admit.children] == [
        "serve/admit.alloc", "serve/admit.dispatch", "serve/admit.lane_meta",
        "serve/admit.ttft_sync"]
    assert {c.stats["req"] for c in admit.children} == {admit.stats["req"]}
    assert all(not s.children or s.name in ("serve/poll", "serve/admit", "serve/evict")
               for s in t.spans)


def test_fixture_serve_programs_are_found_by_name():
    t = _trace("serve")
    assert {"serve_decode_step", "serve_admit", "serve_vae_decode"} <= set(t.programs())
    runs = t.executions("serve_decode_step")
    assert len(runs) >= 2
    # the scopes account for the whole of one execution's self time
    for (start, dur), by in zip(runs, t.time_by("serve_decode_step", pt.scope_of)):
        assert sum(by.values()) <= dur * (1 + 1e-9)
        assert sum(by.values()) >= 0.9 * dur
    assert t.executions("no_such_program") == [] and t.scope_ms("no_such_program", ("attn",)) is None


def test_fixture_train_while_encloses_its_body():
    t = _trace("train")
    (run,) = t.executions("train_step")[:1]
    ops = t.op_self_times(*run)
    whiles = [o for o in ops if o[0].lstrip("%").startswith("while")]
    assert whiles, "a scanned model has a while"
    # self time: the while keeps only what its body's events do not cover
    assert sum(t for _, _, t in ops) <= run[1] * (1 + 1e-9)
    assert t.remat_ms("train_step") > 0 and t.scope_ms("train_step", ("stack_layers",)) > 0


def test_op_ms_is_the_median_over_whole_executions_of_the_named_operations_self_time():
    """`flash_device_ms` reads through this accessor.  The committed train
    fixture was cut from a step too small for the flash kernels, so the
    operations asked for here are the ones it has."""
    t = _trace("train")
    runs = t.executions("train_step")
    assert len(runs) == 3
    per = [sum(own for name, _, own in t.op_self_times(*run) if name.startswith("%custom-call"))
           for run in runs]
    assert min(per) > 0 and t.op_ms("train_step", "custom-call") == pytest.approx(sorted(per)[1] * 1e-6)
    # by the NAME's beginning, the `%` aside: not by a word further on in it
    assert t.op_ms("train_step", "call") is None
    # a `while` keeps only what its body does not cover
    spanned = [sum(d for n, a, d, _ in t.ops if n.startswith("%while") and s <= a < s + dur)
               for s, dur in runs]
    assert 0 < t.op_ms("train_step", "while") < 0.5 * sorted(spanned)[1] * 1e-6
    # nothing of that name, no such program, no device plane: nothing, and no raise
    assert t.op_ms("train_step", "flash_") is None and t.op_ms("serve_decode_step", "fusion") is None
    assert manifest.reader("flash_device_ms")(_ctx(object(), t)) is None
    assert manifest.reader("flash_device_ms")(_ctx(object(), _trace("serve"))) is None
    assert manifest.reader("flash_device_ms")(_ctx(None)) is None


@pytest.mark.parametrize("cut_at,want_ms", [(31.0, 6.0), (29.0, 5.0), (19.5, 4.0)])
def test_flash_device_ms_counts_whole_executions_only(cut_at, want_ms):
    """Three steps of 10 us whose kernels take 4, 6 and 8 us, forward inside a
    scanned layer's `while`: the stretch's end cuts the third step or not, and a
    cut step's kernels count neither above nor below the line (the reader this
    one replaces divided five steps' kernels by six executions)."""
    ops, modules = [], []
    for i, kernels in enumerate((4.0, 6.0, 8.0)):
        t0 = 10.0 * i + 1.0
        modules.append(["jit_train_step(7)", t0 * 1e3, 9.0e3])
        ops += [["%while.1", t0 * 1e3, (kernels / 2 + 1.0) * 1e3, "jit(train_step)/fwd_bwd/jvp()/while"],
                ["%flash_compact_fwd.3", (t0 + 0.5) * 1e3, kernels / 2 * 1e3,
                 "jit(train_step)/fwd_bwd/jvp()/while/body/attn/flash_attn"],
                ["%flash_dkv.9", (t0 + 4.5) * 1e3, kernels / 2 * 1e3,
                 "jit(train_step)/fwd_bwd/transpose(jvp(attn))/flash_attn"],
                ["%fusion.2", (t0 + 8.6) * 1e3, 0.3e3, "jit(train_step)/optimizer_update/add"]]
    events = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
              "host": [["bench/steps", 0.0, cut_at * 1e3, 0, {}]]}
    ctx = _ctx(object(), pt.ProgramTrace(events))
    assert manifest.reader("flash_device_ms")(ctx) == pytest.approx(want_ms * 1e-3)


@pytest.mark.parametrize("loader_knows,whole,want_ms", [(True, 2, 4.0), (False, 4, 3.9)])
def test_an_execution_the_profiler_clipped_is_not_whole(loader_knows, whole, want_ms):
    """As `train_d8`'s traced stretch looks on the chip (PR 32): the step in
    flight when the profiler starts is recorded from the session's first event,
    3.8 of its 4 us of kernels left; two whole steps; the step in flight when it
    stops ends with the last event, 2 us in, no kernel yet.  The harness's spans
    cover all four.  `load_xplane` says what the session recorded, and the two
    clipped ones are then not whole; a piece cut for a fixture says nothing,
    since `cut` keeps whole executions only."""
    ops, modules = [], []
    for t0, dur, kernels in ((5.0, 9.5, 3.8), (14.5, 10.0, 4.0), (24.5, 10.0, 4.0), (34.5, 0.2, 0.0)):
        modules.append(["jit_train_step(7)", t0 * 1e3, dur * 1e3])
        ops.append(["%fusion.1", t0 * 1e3, 0.2e3, "jit(train_step)/fwd_bwd/jvp(ff)/mul"])
        if kernels:
            ops.append(["%flash_fwd.3", (t0 + 1.0) * 1e3, kernels * 1e3,
                        "jit(train_step)/fwd_bwd/jvp(attn)/flash_attn"])
    events = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
              "host": [["bench/dispatch", 4.0e3, 31.0e3, 0, {}]]}
    if loader_knows:
        events["recorded"] = {"/device:TPU:0": [5.0e3, 34.7e3]}
    t = pt.ProgramTrace(events)
    assert len(t.executions("train_step")) == whole
    assert manifest.reader("flash_device_ms")(_ctx(object(), t)) == pytest.approx(want_ms * 1e-3)
    assert t.program_ms("train_step") == pytest.approx(0.010 if loader_knows else 0.00975)


def _msg(*fields):
    """A protobuf message from (field number, int | bytes | str) pairs."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out

    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_device_planes_are_read_from_the_files_wire_format(tmp_path):
    """The scope path is a stat of the event's METADATA, which ProfileData does
    not hand out: `_device_planes` reads xplane.proto's wire format itself."""
    text = "%fusion.7 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p.1), kind=kLoop"
    plane = _msg(
        (2, "/device:TPU:0"),
        (5, _msg((1, 3), (2, _msg((1, 3), (2, "tf_op"))))),          # stat_metadata[3]
        (5, _msg((1, 9), (2, _msg((1, 9), (2, "jit(train_step)/fwd_bwd/jvp(ff)/mul:"))))),
        (4, _msg((1, 7), (2, _msg((1, 7), (2, text), (4, "fusion.7"),  # event_metadata[7]
                                  (5, _msg((1, 3), (5, "jit(serve_decode_step)/vmap(attn)/mul:"))))))),
        (4, _msg((1, 8), (2, _msg((1, 8), (2, "%copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.7)"))))),
        (4, _msg((1, 6), (2, _msg((1, 6), (2, "%while.1 = () while()"),
                                  (5, _msg((1, 3), (7, 9))))))),      # a ref_value stat
        (4, _msg((1, 5), (2, _msg((1, 5), (2, "jit_serve_decode_step(42)"))))),
        (3, _msg((2, "XLA Ops"), (3, 1000),
                 (4, _msg((1, 7), (2, 5_000_000), (3, 2_500_000))),
                 (4, _msg((1, 8), (2, 8_000_000), (3, 1_000_000))),
                 (4, _msg((1, 6), (2, 9_500_000), (3, 250_000))))),
        (3, _msg((2, "XLA Modules"), (3, 1000), (4, _msg((1, 5), (2, 4_000_000), (3, 6_000_000))))),
        (3, _msg((2, "Steps"), (4, _msg((1, 5), (2, 1), (3, 1))))))
    other = _msg((2, "/host:CPU"), (3, _msg((2, "python"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, other), (1, plane)))
    assert pt._device_planes(str(path)) == {"/device:TPU:0": {
        "ops": [["%fusion.7", 6000.0, 2500.0, "jit(serve_decode_step)/vmap(attn)/mul"],
                ["%copy.2", 9000.0, 1000.0, ""],
                ["%while.1", 10500.0, 250.0, "jit(train_step)/fwd_bwd/jvp(ff)/mul"]],
        "modules": [["jit_serve_decode_step(42)", 5000.0, 6000.0]]}}


# ---- the new per-layer readers ----------------------------------------------
def _ctx(trace, program_trace=None):
    ctx = manifest.Context(sizes={}, traffic={}, records={}, trace=trace, peaks=None, end_to_end={})
    if program_trace is not None:
        ctx.program_trace = program_trace
    return ctx


def new_entry_of_the_manifest_names_its_cells_and_layer(man, name):
    m = bench_rules.entry(man, "per_layer", name)
    serve = name in NEW_SERVE
    # BEGINS with the two cells PR 24 named; a later cell of the same kind may follow
    assert m["workloads"][:2] == (["serve_batch", "serve_guided"] if serve else ["train_d8", "train_d24"])
    assert m["moves"] in (("gen_img_tok_per_s", "image_latency_p50_s") if serve
                          else ("train_img_tok_per_s",))


def pr24s_entries_follow_pr23s_and_the_scope_readers_serve_every_trunk(man):
    """A rule of the manifest (bench_rules.py)."""
    readers = bench_rules.names(man["per_layer"])
    # appended: the thirteen entries PR 23 accepted come first, then PR 24's as one run
    assert readers[:13] == PR23 and bench_rules.run_of(NEW_SERVE + NEW_TRAIN, readers)
    for name in NEW_SERVE + NEW_TRAIN:
        new_entry_of_the_manifest_names_its_cells_and_layer(man, name)
    for name in EVERY_TRUNKS:
        assert bench_rules.in_order(["train_d8", "train_d24", "train_q3n_ep16", "train_glm47_ep8"],
                                    bench_rules.entry(man, "per_layer", name)["workloads"]), name


MANIFEST_RULES = [pr24s_entries_follow_pr23s_and_the_scope_readers_serve_every_trunk]


@pytest.mark.parametrize("name", NEW_SERVE + NEW_TRAIN)
def test_new_entry_of_the_manifest_names_its_cells_and_layer(name):
    new_entry_of_the_manifest_names_its_cells_and_layer(MAN, name)
    assert bench_rules.names(MAN["per_layer"]).index(name) >= 13


def test_pr24s_entries_follow_pr23s_and_the_scope_readers_serve_every_trunk():
    pr24s_entries_follow_pr23s_and_the_scope_readers_serve_every_trunk(MAN)


@pytest.mark.parametrize("name", NEW_SERVE + NEW_TRAIN)
def test_reader_on_the_chip_fixture(name):
    which = "serve" if name in NEW_SERVE else "train"
    value = manifest.reader(name)(_ctx(object(), _trace(which)))
    assert value == pytest.approx(FIXTURE[which]["expected"]["metrics"][name], rel=1e-9)
    assert value >= 0.0
    # the other kind of cell has no such program or span: nothing, and no raise
    other = "train" if which == "serve" else "serve"
    assert manifest.reader(name)(_ctx(object(), _trace(other))) is None


@pytest.mark.parametrize("name", NEW_SERVE + NEW_TRAIN)
def test_reader_without_a_trace_or_without_names_returns_nothing(name):
    assert manifest.reader(name)(_ctx(None)) is None
    # a program from before PR 24: a device plane and harness spans, no names
    old = {"devices": {"/device:TPU:0": {
        "ops": [["%fusion.1", 10, 50, ""], ["%copy.2", 70, 20, ""]],
        "modules": [["jit__decode_step_impl(123)", 5, 90]]}},
        "host": [["bench/poll", 0, 100, 0, {}]]}
    assert manifest.reader(name)(_ctx(object(), pt.ProgramTrace(old))) is None


def test_decode_split_accounts_for_the_step_on_the_fixture():
    t = _trace("serve")
    r = FIXTURE["serve"]["expected"]["metrics"]
    step = t.program_ms("serve_decode_step")
    parts = (r["decode_kv_gather_device_ms"] + r["decode_compute_device_ms"]
             + r["decode_sample_device_ms"] + step * r["decode_unscoped_pct"] / 100.0)
    assert parts == pytest.approx(step, rel=0.02)


@pytest.fixture(scope="module")
def cpu_engine_trace(tmp_path_factory):
    """A profiler trace of a tiny engine's polls, made here on the CPU."""
    import jax

    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine
    from test_serving import tiny_cfg

    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=2, block_size=4))
    out = tmp_path_factory.mktemp("cpu_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=options)
    try:
        for i in range(3):
            eng.submit([1 + i] * cfg.text_seq_len, key=jax.random.PRNGKey(i))
        with jax.profiler.TraceAnnotation("bench/poll"):
            eng.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    return pt.ProgramTrace(pt.load_xplane(str(out)))


@pytest.mark.parametrize("name", ["admit_host_ms", "evict_host_ms", "idle_in_program_spans_pct"])
def test_span_readers_on_a_cpu_trace_of_a_tiny_engine(cpu_engine_trace, name):
    t = cpu_engine_trace
    assert len(t.spans_named("serve/admit")) == 3 and len(t.spans_named("serve/evict")) >= 2
    value = manifest.reader(name)(_ctx(object(), t))
    if name == "idle_in_program_spans_pct":
        assert value is None  # no device plane on the CPU: no idle time to place
        by, total = t.idle_by_leaf_span()
        assert total == pytest.approx((t.hi - t.lo) * 1e-9) and 0 < sum(by.values()) <= total
    else:
        assert 0.0 < value < 60e3
        if name == "evict_host_ms":
            whole = [s.dur * 1e-6 for s in t.spans_named("serve/evict")]
            assert value < max(whole)  # the drain is taken out
