"""Serving subsystem (serving/): paged KV pool + continuous batching engine.

The load-bearing property is BIT parity: a request served through the paged
engine — admitted into a shared block pool, decoded in a slot batch beside
unrelated sequences at other positions, evicted, its blocks reused — must
produce exactly the codes `sample_image_codes` produces for a batch-1 call
with the same prompt and key.  Everything else (admission control, flood
degradation, the ledger rows) is behavior the acceptance criteria name.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models.dalle import DALLEConfig
from dalle_pytorch_tpu.models.sampling import sample_image_codes
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine
from dalle_pytorch_tpu.serving.scheduler import AdmissionRefused
from dalle_pytorch_tpu.training import resilience


def tiny_cfg(**kw):
    base = dict(
        dim=32, depth=2, num_text_tokens=64, text_seq_len=8, heads=2,
        dim_head=8, num_image_tokens=32, image_fmap_size=4, shift_tokens=True,
    )
    base.update(kw)
    return DALLEConfig(**base)


def fused_ref(params, cfg, text_row, key, temperature=1.0, cond_scale=1.0):
    return np.asarray(sample_image_codes(
        params, cfg, jnp.asarray(text_row)[None], key,
        filter_thres=0.9, temperature=temperature, cond_scale=cond_scale,
    ))


@pytest.fixture(scope="module")
def base():
    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    text = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (4, cfg.text_seq_len), 1, cfg.num_text_tokens))
    return cfg, params, text


def test_paged_parity_staggered_with_block_reuse(base):
    """4 requests through 2 slots: the 3rd and 4th are admitted only after
    evictions, onto REUSED physical blocks, mid-decode of the others — and
    every one is bit-identical to its fused batch-1 reference."""
    cfg, params, text = base
    eng = GenerationEngine(params, cfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4))
    seen_tables = []
    orig_alloc = eng.pool.alloc_table

    def tracking_alloc(owner):
        t = orig_alloc(owner)
        seen_tables.append(set(int(b) for b in t))
        return t

    eng.pool.alloc_table = tracking_alloc

    keys = [jax.random.PRNGKey(10 + i) for i in range(4)]
    reqs = eng.generate(text, keys=keys)
    for i, req in enumerate(reqs):
        want = fused_ref(params, cfg, text[i], keys[i])
        np.testing.assert_array_equal(req.codes[None], want)
        assert req.ttft_s is not None and req.latency_s is not None
    # eviction returned every block; later allocations reused earlier blocks
    assert eng.pool.free_blocks == eng.pool.num_blocks
    early = set().union(*seen_tables[:2])
    late = set().union(*seen_tables[2:])
    assert early & late, "expected block-table reuse after eviction"
    assert 0 not in early | late, "the trash block must never be handed out"


def test_paged_parity_guided_cfg_lanes(base):
    """cond_scale != 1: a guided request rides two lanes ([cond] + [null])
    whose logits recombine inside the fused step — still bit-identical to
    the fused guided sampler."""
    cfg, params, text = base
    eng = GenerationEngine(params, cfg,
                           engine_cfg=EngineConfig(num_slots=4, block_size=4))
    keys = [jax.random.PRNGKey(20 + i) for i in range(2)]
    reqs = eng.generate(text[:2], keys=keys, cond_scale=2.0)
    for i, req in enumerate(reqs):
        want = fused_ref(params, cfg, text[i], keys[i], cond_scale=2.0)
        np.testing.assert_array_equal(req.codes[None], want)


def test_paged_parity_scan_layers():
    """A model trained with `scan_layers` is served unrolled, through the
    per-layer pool, and still delivers the fused sampler's codes."""
    cfg = tiny_cfg(scan_layers=True, attn_types=("full", "axial_row"))
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    text = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (1, cfg.text_seq_len), 1, cfg.num_text_tokens))
    key = jax.random.PRNGKey(3)
    eng = GenerationEngine(params, cfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4))
    (req,) = eng.generate(text, keys=[key])
    np.testing.assert_array_equal(req.codes[None], fused_ref(params, cfg, text[0], key))


@pytest.mark.parametrize("kw,bs,want", [
    (dict(), 4, (0, 2)),
    (dict(dim=256, dim_head=128), 8, (2, 0)),
], ids=["gather_path", "kernel_path"])
def test_engine_ignores_scan_layers(kw, bs, want):
    """An engine built from a `scan_layers=True` config (what a checkpoint
    trained with `--scan_layers` hands `cli/common.py`) is the engine built
    from the `False` one: the same delivered codes, a guided lane pair
    included, and the same count of layers on the kernel and on the gather."""
    cfgs = [tiny_cfg(attn_types=("full", "axial_row"), scan_layers=scan, **kw)
            for scan in (False, True)]
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfgs[0])
    text = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, cfgs[0].text_seq_len), 1, cfgs[0].num_text_tokens))
    keys = [jax.random.PRNGKey(40 + i) for i in range(2)]
    codes, paths = [], []
    for cfg in cfgs:
        eng = GenerationEngine(params, cfg,
                               engine_cfg=EngineConfig(num_slots=4, block_size=bs))
        plain = eng.generate(text, keys=keys)
        guided = eng.generate(text, keys=keys, cond_scale=2.0)
        codes.append([np.asarray(r.codes) for r in plain + guided])
        paths.append(eng.paged_path_state())
    for got, ref in zip(codes[1], codes[0]):
        np.testing.assert_array_equal(got, ref)
    assert paths[1] == paths[0] == {"paged_attn_kernel_layers": want[0],
                                    "paged_attn_fallback_layers": want[1]}


def _full_width_pipeline(params, cfg, out, offsets, key_index, state,
                         filter_thres, degraded_filter_thres):
    """`speculative.lane_sample_pipeline` as it stood before the engine laid
    the head's image half out once: the whole vocabulary's logits, the mask
    row of each lane's position, top-k, gumbel and the code's offset over all
    `total_tokens` columns.  Kept as the narrowed pipeline's definition."""
    from dalle_pytorch_tpu.ops.sampling import gumbel_sample
    from dalle_pytorch_tpu.ops.stable import divide_max

    S = out.shape[0]
    if cfg.stable:
        out = divide_max(out)
    logits = dalle_mod.to_logits(params, cfg, out)[:, 0]  # (S, V)
    rows = jnp.take(dalle_mod.logits_mask_slice(cfg, cfg.total_seq_len),
                    offsets, axis=0, mode="clip")
    logits = jnp.where(rows, jnp.finfo(logits.dtype).min, logits)
    inject = jnp.arange(S, dtype=jnp.int32) == state["poison_lane"]
    logits = jnp.where(inject[:, None], jnp.asarray(jnp.nan, logits.dtype), logits)
    null_lg = jnp.take(logits, state["partner"], axis=0)
    lg = jnp.where(
        state["guided"][:, None],
        null_lg + (logits - null_lg) * state["cscale"][:, None].astype(logits.dtype),
        logits)
    bad = ~jnp.isfinite(lg).all(axis=-1) & state["active"]
    lg = jnp.where(bad[:, None], jnp.zeros_like(lg), lg)
    V = lg.shape[-1]
    k = max(int((1.0 - filter_thres) * V), 1)
    k_cap = min(max(int((1.0 - degraded_filter_thres) * V), 1), k)
    val, ind = jax.lax.top_k(lg, k)
    keep = jnp.where(state["cand_cap"][:, None], jnp.arange(k) < k_cap, True)
    val = jnp.where(keep, val, -jnp.inf)
    filtered = jnp.put_along_axis(
        jnp.full_like(lg, -jnp.inf), ind, val, axis=-1, inplace=False)
    keys_t = jnp.take_along_axis(
        state["keys"],
        jnp.clip(key_index, 0, state["keys"].shape[1] - 1)[:, None, None], axis=1)[:, 0]

    def sample_one(lg_row, kk, t):
        return gumbel_sample(kk, lg_row[None], temperature=t)[0]

    toks = jax.vmap(sample_one)(filtered, keys_t, state["temp"].astype(logits.dtype))
    code = jnp.clip(toks - cfg.num_text_tokens_padded, 0,
                    cfg.num_image_tokens - 1).astype(jnp.int32)
    return jnp.take(code, state["feed_src"], axis=0), bad


@pytest.mark.parametrize("poison_lane", [-1, 3], ids=["healthy", "poisoned_lane"])
@pytest.mark.parametrize("weights", ["tied", "untied", "tied_int8"])
@pytest.mark.parametrize("thres", [0.9, 0.5, 0.0, 0.6384],
                         ids=["k_under", "k_over", "k_all", "k_equal"])
def test_image_width_pipeline_emits_the_full_width_codes(thres, weights, poison_lane):
    """The decode pipeline over the image columns of the table laid out at
    engine build emits, bit for bit, the codes of the masked full-vocabulary
    pipeline it replaced, fed the same transformer output: k of the whole
    vocabulary under, over and equal to the image columns' count (11, 56, 112
    and 40 of 112 against 40), a guided lane pair at scale 3, temperatures
    either side of 1, a degrade-capped lane, tied, untied and int8 weights —
    and screens the same lanes as nonfinite.  A screened lane's code is not
    compared: its request fails as poisoned, and where the full-width
    pipeline sampled its zeroed row among text columns (code 0) this one
    samples it among the first k image codes.  Lane 5 is idle, as an evicted
    lane is (offset 0, a TEXT row of the mask): its code is discarded by the
    step, and differs.  The lookup reads the same rows."""
    from dalle_pytorch_tpu import quantization
    from dalle_pytorch_tpu.models import speculative as spec_mod

    cfg = tiny_cfg(num_image_tokens=40, share_input_output_emb=weights != "untied")
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    if weights == "tied_int8":
        params = quantization.quantize_tree(params)
    V, n_img = cfg.total_tokens, cfg.num_image_tokens
    assert (V, n_img) == (112, 40)
    assert max(int((1.0 - thres) * V), 1) == {0.9: 11, 0.5: 56, 0.0: 112, 0.6384: 40}[thres]
    S, nk = 6, 5
    state = {
        "head": dalle_mod.image_head(params, cfg),
        "poison_lane": jnp.asarray(poison_lane, jnp.int32),
        "partner": jnp.asarray([1, 1, 2, 3, 4, 5], jnp.int32),
        "guided": jnp.asarray([True, False, False, False, False, False]),
        "feed_src": jnp.asarray([0, 0, 2, 3, 4, 5], jnp.int32),
        "cscale": jnp.asarray([3.0, 3.0, 1.0, 1.0, 1.0, 1.0], jnp.float32),
        "temp": jnp.asarray([1.0, 1.0, 0.7, 1.3, 1.0, 1.0], jnp.float32),
        "cand_cap": jnp.asarray([False, False, True, False, True, False]),
        "active": jnp.asarray([True, True, True, True, True, False]),
        "keys": jax.random.bits(jax.random.PRNGKey(7), (S, nk, 2), jnp.uint32),
    }
    offsets = jnp.asarray([9, 9, 12, 24, 15, 0], jnp.int32)
    narrow = jax.jit(lambda out, ki: spec_mod.lane_sample_pipeline(
        params, cfg, out, ki, state, thres, 0.98))
    full = jax.jit(lambda out, ki: _full_width_pipeline(
        params, cfg, out, offsets, ki, state, thres, 0.98))
    live = np.array(state["active"])
    if poison_lane >= 0:
        live[poison_lane] = False
    seen = set()
    for draw in range(6):
        out = 3.0 * jax.random.normal(jax.random.PRNGKey(100 + draw), (S, 1, cfg.dim))
        ki = jnp.asarray([draw, draw, draw + 1, draw + 2, draw, draw], jnp.int32)
        (code, bad), (want, want_bad) = narrow(out, ki), full(out, ki)
        np.testing.assert_array_equal(np.asarray(bad), np.asarray(want_bad))
        np.testing.assert_array_equal(np.asarray(bad), ~live & np.asarray(state["active"]))
        np.testing.assert_array_equal(np.asarray(code)[live], np.asarray(want)[live])
        assert code.dtype == jnp.int32 and int(code.min()) >= 0 and int(code.max()) < n_img
        assert int(code[0]) == int(code[1])  # the pair is fed one code
        seen.update(np.asarray(code)[live].tolist())
    assert len(seen) > 4, seen  # the comparison was not of one constant

    prev = jnp.asarray([0, 39, 7, 45, 13, 2], jnp.int32)  # 45: past the table, clipped
    got = spec_mod._embed_prev(params, cfg, state["head"], prev, jnp.zeros((S,), jnp.int32))
    want = jnp.take(dalle_mod._image_table(params, cfg), prev[:, None], axis=0, mode="clip")
    pos = dalle_mod.image_pos_table(params, cfg)
    if pos is not None:
        want = want + pos[0][None, None]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_program_reads_the_image_table_where_it_lies():
    """The lowered decode program at tiny sizes (the twin of
    tests/test_chip_compile.py's check of the program compiled for the chip):
    the whole head (32 x 112) is not in it at all, the table laid out at build
    (40 x 32) is an ARGUMENT — never a constant — that only the lookup's
    gather and the head's contraction read, with no transpose or slice of it,
    `top_k` sees the 40 image columns, and the table leaves the donated state
    aliased to the argument it came in by."""
    import re

    cfg = tiny_cfg(num_image_tokens=40, share_input_output_emb=True)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=2, block_size=4))
    text = eng._decode_fn.lower(params, eng._state).as_text()
    assert f"tensor<{cfg.dim}x{cfg.total_tokens}x" not in text
    ops = set()
    for line in text.splitlines():
        if "tensor<40x32x" in line or "tensor<32x40x" in line:
            ops.add(re.match(r"\s*(?:%[\w:#]+(?:, %[\w:#]+)* = )?\"?([\w.]+)", line).group(1))
    assert ops == {"func.func", "call", "stablehlo.gather", "stablehlo.dot_general", "return"}, ops
    assert re.findall(r"top_k\(.*?\) : tensor<2x(\d+)xf32>", text) == ["40"]
    (out_index,) = re.findall(
        r"tensor<40x32xf32> \{tf.aliasing_output = (\d+) : i32\}", text)
    results = re.findall(r'jax.result_info = "(.*?)"', text)
    assert results[int(out_index)] == "result['head']['table']"


@pytest.mark.slow
@pytest.mark.parametrize("kw,sample_kw", [
    (dict(rotary_emb=False), {}),
    (dict(stable=True), {}),
    (dict(execution="reversible"), {}),
    (dict(shift_tokens=False, attn_types=("axial_row", "conv_like")), {}),
    (dict(), dict(temperature=0.7)),
])
def test_paged_parity_config_matrix(kw, sample_kw):
    cfg = tiny_cfg(**kw)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    text = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (1, cfg.text_seq_len), 1, cfg.num_text_tokens))
    key = jax.random.PRNGKey(7)
    eng = GenerationEngine(params, cfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4))
    (req,) = eng.generate(text, keys=[key], **sample_kw)
    np.testing.assert_array_equal(
        req.codes[None], fused_ref(params, cfg, text[0], key, **sample_kw))


@pytest.mark.slow  # tier-1 budget: paged parity stays fast via the
#                    staggered/guided/scan/config-matrix legs above; this leg
#                    adds the bf16 weak-temperature dtype variant
def test_paged_parity_bf16_weak_temperature(base):
    """Deployment-dtype serving: bf16 params, non-trivial temperature.  The
    engine's per-lane temperature vector must behave like the fused path's
    WEAKLY-typed python float (no silent f32 promotion of bf16 logits)."""
    from dalle_pytorch_tpu.core.pytree import cast_floating

    cfg, params, text = base
    p16 = cast_floating(params, jnp.bfloat16)
    key = jax.random.PRNGKey(60)
    eng = GenerationEngine(p16, cfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4))
    (req,) = eng.generate(text[:1], keys=[key], temperature=0.7)
    np.testing.assert_array_equal(
        req.codes[None], fused_ref(p16, cfg, text[0], key, temperature=0.7))


def test_admission_refusal_tiny_pool(base):
    """A pool smaller than one sequence refuses at submit — queueing the
    request would hang the client forever."""
    cfg, params, text = base
    eng = GenerationEngine(params, cfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4,
                                                   num_blocks=2))
    before = obs_metrics.counter("serving/refused").value
    with pytest.raises(AdmissionRefused, match="pool only has 2"):
        eng.submit(text[0])
    assert obs_metrics.counter("serving/refused").value == before + 1
    # guided needs 2 x blocks/seq: refuse even when one sequence would fit
    eng2 = GenerationEngine(
        params, cfg,
        engine_cfg=EngineConfig(num_slots=2, block_size=4,
                                num_blocks=eng.pool.blocks_per_seq))
    with pytest.raises(AdmissionRefused):
        eng2.submit(text[0], cond_scale=2.0)


def test_pool_exhaustion_serializes_not_ooms(base):
    """A pool that fits exactly ONE sequence serializes two requests through
    deferrals (backpressure) — both still complete, bit-exact."""
    cfg, params, text = base
    blocks_per_seq = -(-cfg.total_seq_len // 4)
    eng = GenerationEngine(params, cfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4,
                                                   num_blocks=blocks_per_seq))
    before = obs_metrics.counter("serving/admission_deferrals").value
    keys = [jax.random.PRNGKey(30 + i) for i in range(2)]
    reqs = eng.generate(text[:2], keys=keys)
    assert len([r for r in reqs if r.codes is not None]) == 2
    assert obs_metrics.counter("serving/admission_deferrals").value > before
    for i, req in enumerate(reqs):
        np.testing.assert_array_equal(req.codes[None],
                                      fused_ref(params, cfg, text[i], keys[i]))


def test_hbm_headroom_backpressure(base):
    """Live-allocator pressure defers FURTHER admissions while work is in
    flight (HbmMonitor-basis gate) and flow resumes when usage recedes —
    but an idle engine always admits (deferring with zero active lanes can
    never lower usage; it would livelock the service)."""
    cfg, params, text = base
    usage = {"v": 0.1}
    eng = GenerationEngine(params, cfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4),
                           usage_fn=lambda: usage["v"])
    eng.submit(text[0], key=jax.random.PRNGKey(40))
    eng.poll()
    assert len(eng._inflight) == 1
    usage["v"] = 0.99  # pressure: the second request must wait
    eng.submit(text[1], key=jax.random.PRNGKey(41))
    for _ in range(3):
        eng.poll()
    assert len(eng._inflight) == 1 and len(eng.queue) == 1
    usage["v"] = 0.2
    done = eng.run_until_idle()
    assert len(done) == 2 and all(r.codes is not None for r in done)
    # idle engine under sustained pressure: admits anyway (no livelock),
    # counted as a headroom override
    before = obs_metrics.counter("serving/headroom_overrides").value
    usage["v"] = 0.99
    eng.submit(text[2], key=jax.random.PRNGKey(42))
    done = eng.run_until_idle()
    assert len(done) == 1 and done[0].codes is not None
    assert obs_metrics.counter("serving/headroom_overrides").value > before


def test_flood_fault_degrades_to_refusals(base):
    """`--inject_fault flood@1:6` with a 3-deep queue: the burst is shed via
    refusals, admitted requests all complete, nothing crashes or OOMs."""
    cfg, params, text = base
    refused0 = obs_metrics.counter("serving/refused").value
    inj = resilience.FaultInjector(resilience.parse_fault("flood@1:6")).install()
    try:
        eng = GenerationEngine(
            params, cfg,
            engine_cfg=EngineConfig(num_slots=2, block_size=4, max_queue=3))
        eng.submit(text[0], key=jax.random.PRNGKey(50))
        done = eng.run_until_idle()
    finally:
        inj.uninstall()
    assert inj.fired
    refused = obs_metrics.counter("serving/refused").value - refused0
    assert refused > 0, "the burst must overflow the queue into refusals"
    # 1 organic + whatever of the burst fit the queue, all completed
    assert len(done) >= 1
    assert all(r.codes is not None for r in done)


def test_flood_fault_parse_and_default():
    f = resilience.parse_fault("flood@8")
    assert f.kind == "flood" and f.step == 8 and int(f.stall_s) == 32
    f2 = resilience.parse_fault("flood@3:7")
    assert f2.step == 3 and int(f2.stall_s) == 7


def test_sampling_ledger_paged_rows(base):
    """The serving ledger prices the shared pool + the transient one-layer
    gather instead of the dense per-batch KV row."""
    from dalle_pytorch_tpu.observability.memory import sampling_memory_ledger

    cfg, params, _ = base
    ledger = sampling_memory_ledger(
        cfg, 4, params,
        paged_pool={"num_blocks": 13, "block_size": 4, "num_slots": 4,
                    "itemsize": 4},
    )
    rows = {r["name"]: r["bytes"] for r in ledger["rows"]}
    assert "kv_cache" not in rows
    assert rows["paged_kv_pool"] == (
        2.0 * cfg.depth * 13 * cfg.heads * 4 * cfg.dim_head * 4)
    assert rows["paged_gather"] == (
        2.0 * 4 * cfg.heads * cfg.total_seq_len * cfg.dim_head * 4)
    # engine.memory_ledger wires its own pool geometry through the same path
    eng = GenerationEngine(base[1], cfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4))
    led2 = eng.memory_ledger()
    names = [r["name"] for r in led2["rows"]]
    assert "paged_kv_pool" in names and "params" in names


def test_loadgen_report_shape():
    """Arrival schedule and report arithmetic without any engine."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    from loadgen import PoissonLoadGen

    gen = PoissonLoadGen(7, rate=10.0, streams=2, seed=3)
    assert len(gen.arrivals) == 7
    assert all(gen.arrivals[i][0] <= gen.arrivals[i + 1][0]
               for i in range(len(gen.arrivals) - 1))

    class R:
        def __init__(self, t, l):
            self.ttft_s, self.latency_s = t, l

    rep = gen.report([R(0.1, 0.5), R(0.2, 0.6)], refused=1, elapsed_s=2.0)
    assert rep["requests_completed"] == 2 and rep["requests_refused"] == 1
    assert rep["ttft_p50_s"] is not None and rep["images_per_sec_per_chip"] == 1.0


@pytest.mark.slow
def test_loadgen_end_to_end_smoke(base, tmp_path):
    """The acceptance run: >= 2 concurrent Poisson streams, every request
    completes, TTFT recorded per request, and the serving report renders
    the request/window/backpressure sections from the telemetry stream."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    from loadgen import PoissonLoadGen, synthetic_request_maker
    from serving_report import build_report

    from dalle_pytorch_tpu.observability import telemetry

    cfg, params, _ = base
    tele = telemetry.configure(str(tmp_path), run_name="serve",
                               heartbeat_s=None, watch_compiles=False)
    try:
        eng = GenerationEngine(
            params, cfg,
            engine_cfg=EngineConfig(num_slots=2, block_size=4,
                                    telemetry_every=4))
        gen = PoissonLoadGen(5, rate=20.0, streams=2, seed=0)
        report = gen.run(eng, synthetic_request_maker(cfg, seed=0))
    finally:
        tele.flush(fleet=False)
        tele.close()
    assert report["requests_completed"] == 5
    assert report["ttft_p50_s"] is not None and report["ttft_p99_s"] is not None
    assert report["latency_p99_s"] >= report["latency_p50_s"]
    assert report["images_per_sec_per_chip"] > 0
    from telemetry_report import load_records

    recs = load_records(tmp_path / "serve.spans.jsonl")
    text = build_report(recs)
    assert "requests: 5 completed" in text
    assert "TTFT" in text and "engine windows" in text


# ---------------------------------------------------------------------------
# quantized serving (ISSUE 13): capacity the int8 pool buys
# ---------------------------------------------------------------------------

def test_quantized_kv_admission_double_slots(base):
    """The staggered-admission scenario at 2x the slot count with an int8
    KV pool: 4 concurrent lanes through quantized blocks, every request
    completing, and batching still invisible — each request's codes are
    bit-identical to a 1-slot quantized engine serving it alone (per-token
    scales never couple lanes)."""
    cfg, params, text = base
    keys = [jax.random.PRNGKey(70 + i) for i in range(4)]

    eng = GenerationEngine(
        params, cfg,
        engine_cfg=EngineConfig(num_slots=4, block_size=4,
                                quantize_kv="int8"))
    assert eng.pool.quant == "int8"
    reqs = eng.generate(text[:4], keys=keys)
    assert len(reqs) == 4 and all(r.codes is not None for r in reqs)

    solo = GenerationEngine(
        params, cfg,
        engine_cfg=EngineConfig(num_slots=1, block_size=4,
                                quantize_kv="int8"))
    for i, req in enumerate(reqs):
        ref = solo.generate(text[i:i + 1], keys=[keys[i]])[0]
        np.testing.assert_array_equal(req.codes, ref.codes)


def test_quantized_pool_refusal_and_ledger_pricing(base):
    """Admission refusal logic is quantization-blind (block accounting, not
    bytes), while the ledger prices the int8 pool at its true at-rest
    bytes — strictly under the float pool's."""
    cfg, params, _ = base
    eng = GenerationEngine(
        params, cfg,
        engine_cfg=EngineConfig(num_slots=2, block_size=4, num_blocks=2,
                                quantize_kv="int8"))
    with pytest.raises(AdmissionRefused, match="pool only has 2"):
        eng.submit(jnp.zeros((cfg.text_seq_len,), jnp.int32) + 1)
    qbytes = eng.pool.bytes(itemsize=4)
    fbytes = GenerationEngine(
        params, cfg,
        engine_cfg=EngineConfig(num_slots=2, block_size=4,
                                num_blocks=2)).pool.bytes(itemsize=4)
    assert qbytes < fbytes / 2.5  # 1 + 2/dim_head bytes/elem vs 4
    ledger = eng.memory_ledger()
    row = next(r for r in ledger["rows"] if r["name"] == "paged_kv_pool")
    assert "int8" in row["detail"]


def test_quantized_headroom_admits_more_lanes(base):
    """Under the SAME modeled HBM capacity, the int8 pool's smaller
    per-lane footprint lets the headroom gate admit strictly more
    concurrent lanes than bf16 — the capacity claim of the quantized
    serving row, reproduced at test scale.  Usage is modeled as
    in-flight-lanes x per-lane-KV-bytes / capacity, with per-lane bytes
    priced by the same kv_bytes_per_elem formula the ledger quotes."""
    from dalle_pytorch_tpu.quantization import kv_bytes_per_elem

    cfg, params, text = base
    tcfg = cfg.transformer_config()
    lane_elems = 2 * tcfg.depth * tcfg.heads * cfg.total_seq_len * tcfg.dim_head
    capacity = 2.5 * lane_elems * 4.0  # bf16-engine f32 pool: 2.5 lanes' worth

    def run(quant):
        per_lane = lane_elems * kv_bytes_per_elem(quant, 4, tcfg.dim_head)
        holder = {}

        def usage():
            return len(holder["eng"]._inflight) * per_lane / capacity

        eng = GenerationEngine(
            params, cfg,
            engine_cfg=EngineConfig(num_slots=4, block_size=4,
                                    quantize_kv=quant),
            usage_fn=usage)
        holder["eng"] = eng
        before = obs_metrics.counter("serving/admission_deferrals").value
        for i in range(4):
            eng.submit(text[i % len(text)], key=jax.random.PRNGKey(80 + i))
        peak, done = 0, []
        for _ in range(400):
            done.extend(eng.poll())
            peak = max(peak, len(eng._inflight))
            if len(done) == 4:
                break
        assert len(done) == 4 and all(r.codes is not None for r in done)
        defers = obs_metrics.counter("serving/admission_deferrals").value - before
        return peak, defers

    peak_f, defers_f = run(None)
    peak_q, defers_q = run("int8")
    # f32 KV: the 4th lane's check sees 3 lanes x 0.4 = 1.2 usage -> it
    # defers until a completion frees a lane (concurrency caps at 3);
    # int8 KV: per-lane frac 0.125, all four run at once, zero deferrals
    assert peak_f == 3 and defers_f > 0
    assert peak_q == 4 and defers_q == 0
    assert peak_q > peak_f
