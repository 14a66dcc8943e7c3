"""Self-speculative decoding (models/speculative.py + engine integration).

The load-bearing property is the exactness gate: in the default match mode
every emitted token is re-derived from the SAME per-position step key the
sequential sampler would have used, so speculative output must be
`array_equal` to sequential output — at any temperature, on the fused
sampler AND the serving engine, with CFG lane pairs, int8 paged KV, sparse
decode tables, and scan_layers all composed in.  The stochastic mode trades
stream parity for distribution parity (standard rejection/residual
sampling) and is gated statistically.  The rollback satellite pins
`kv_pool.truncate_slot` (frees nothing, gauges stay consistent) and that a
rolled-back-then-refilled slot is bit-identical to a never-speculated one.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models import speculative as spec_mod
from dalle_pytorch_tpu.models.dalle import DALLEConfig
from dalle_pytorch_tpu.models.sampling import _prefill_phase, sample_image_codes
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.serving.degrade import DegradeConfig, DegradeLadder
from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine
from dalle_pytorch_tpu.serving.kv_pool import BlockPool


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


# ------------------------------------------------------ fused-sampler parity


def _spec_vs_seq(cfg, params, text, key, *, spec_k, temperature=1.0,
                 cond_scale=1.0, spec_draft_layers=None):
    seq = np.asarray(sample_image_codes(
        params, cfg, text, key, filter_thres=0.9, temperature=temperature,
        cond_scale=cond_scale))
    spec = np.asarray(sample_image_codes(
        params, cfg, text, key, filter_thres=0.9, temperature=temperature,
        cond_scale=cond_scale, spec_k=spec_k,
        spec_draft_layers=spec_draft_layers))
    np.testing.assert_array_equal(spec, seq)
    return seq


def test_fused_spec_parity_guided(base):
    """CFG at non-unit temperature: bit-identical to the sequential scan
    (the exactness gate on the fused path, in its hardest fast-tier form —
    guided logits + temperature scaling).  Solo lanes run fast via the
    scan/sparse/no-shift legs below and the engine tests; the k and
    cond_scale sweeps live in the slow matrix — each static k is a fresh
    compile."""
    cfg, params, text = base
    t = jnp.asarray(text[:2])
    _spec_vs_seq(cfg, params, t, jax.random.PRNGKey(7),
                 spec_k=3, cond_scale=3.0, temperature=0.7)


def test_fused_spec_parity_scan_layers_and_draft_depth():
    """A `scan_layers` config decodes through the per-layer cache like any
    other.  A non-default boundary (d=2 of 3) stays exact; the d sweep
    lives in the slow matrix."""
    cfg = tiny_cfg(depth=3, scan_layers=True)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(2), cfg)
    text = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.text_seq_len),
                              1, cfg.num_text_tokens)
    _spec_vs_seq(cfg, params, text, jax.random.PRNGKey(8),
                 spec_k=2, spec_draft_layers=2, cond_scale=2.0)


def test_fused_spec_parity_sparse_decode_gather(base):
    """Sparse attention with the decode-gather tables on (the default,
    load-bearing path): spec == seq.  The full-cache-reads leg
    (sparse_decode=False) lives in the slow matrix — each path is compared
    against itself; the two paths differ by reduction order, the spec/seq
    pair must not."""
    cfg = tiny_cfg(attn_types=("full", "axial_row"), sparse_decode=True)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(4), cfg)
    text = jax.random.randint(jax.random.PRNGKey(5),
                              (2, cfg.text_seq_len), 1,
                              cfg.num_text_tokens)
    _spec_vs_seq(cfg, params, text, jax.random.PRNGKey(9), spec_k=2)


def test_fused_spec_parity_no_shift_tokens():
    """shift_tokens=False has no rings to roll back — the rollback helper
    must no-op, not crash, and parity must hold."""
    cfg = tiny_cfg(shift_tokens=False)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(6), cfg)
    text = jax.random.randint(jax.random.PRNGKey(7), (2, cfg.text_seq_len),
                              1, cfg.num_text_tokens)
    _spec_vs_seq(cfg, params, text, jax.random.PRNGKey(10), spec_k=3)


@pytest.mark.slow
def test_fused_spec_parity_matrix():
    """Slow twin: the full composition matrix (scan x sparse x stable x
    guided x temperature x k) on a deeper model."""
    for kw in (dict(depth=4, scan_layers=True),
               dict(depth=3, attn_types=("full", "axial_row", "conv_like")),
               dict(depth=2, attn_types=("full", "axial_row"),
                    sparse_decode=False),
               dict(depth=2, stable=True),
               dict(depth=2, rotary_emb=False)):
        cfg = tiny_cfg(**kw)
        params = dalle_mod.init_dalle(jax.random.PRNGKey(11), cfg)
        text = jax.random.randint(jax.random.PRNGKey(12),
                                  (2, cfg.text_seq_len), 1,
                                  cfg.num_text_tokens)
        for spec_k in (1, 2, 3):
            for cond_scale in (1.0, 2.0):
                for temp in (1.0, 0.5):
                    _spec_vs_seq(cfg, params, text, jax.random.PRNGKey(13),
                                 spec_k=spec_k, cond_scale=cond_scale,
                                 temperature=temp)


def test_validate_spec_errors(base):
    cfg, _, _ = base
    tcfg = cfg.transformer_config()
    with pytest.raises(ValueError, match="spec_k"):
        spec_mod.validate_spec(tcfg, 0, None)
    with pytest.raises(ValueError, match="image_fmap_size"):
        # shift rings hold fmap slots: k+1 must fit (fmap=4 -> k <= 3)
        spec_mod.validate_spec(tcfg, 4, None)
    with pytest.raises(ValueError, match="1 <= d < depth"):
        spec_mod.validate_spec(tcfg, 2, 2)  # d == depth
    rcfg = tiny_cfg(reversible=True).transformer_config()
    with pytest.raises(ValueError, match="reversible"):
        spec_mod.validate_spec(rcfg, 2, None)
    d1 = tiny_cfg(depth=1).transformer_config()
    with pytest.raises(ValueError, match="depth"):
        spec_mod.validate_spec(d1, 2, None)


# -------------------------------------------------------- stochastic parity


def _pooled_hist(codes, vocab):
    return np.bincount(np.asarray(codes).ravel(), minlength=vocab) / codes.size


def _stochastic_tv(base, b, seed):
    """Total-variation distance between pooled token histograms of the
    sequential sampler and the stochastic rejection-sampler, same prompt
    batch (streams differ by construction; only the marginals must agree)."""
    cfg, params, text = base
    t = jnp.asarray(np.tile(text[:1], (b, 1)))
    seq = np.asarray(sample_image_codes(
        params, cfg, t, jax.random.PRNGKey(seed), filter_thres=0.9))

    @jax.jit
    def spec_fn(p, tt, k):
        cache, last = _prefill_phase(p, cfg, tt, None, 0, 1.0)
        return spec_mod.fused_spec_decode(
            p, cfg, cache, last, k, 0.9, 1.0, 1.0, None, 0, 2, None,
            stochastic=True, return_stats=True)

    spec, stats = spec_fn(params, t, jax.random.PRNGKey(seed + 1))
    rounds = int(stats["spec_rounds"])
    # acceptance statistics: every round commits at least one token, and
    # the rejection sampler must accept MORE than that on average (rounds
    # strictly below the sequential step count) or speculation is a no-op
    assert 1 <= rounds < cfg.image_seq_len - 1
    h_seq = _pooled_hist(seq, cfg.num_image_tokens)
    h_spec = _pooled_hist(np.asarray(spec), cfg.num_image_tokens)
    return 0.5 * np.abs(h_seq - h_spec).sum()


def test_stochastic_distribution_parity(base):
    assert _stochastic_tv(base, b=64, seed=31) < 0.25


@pytest.mark.slow
def test_stochastic_distribution_parity_large(base):
    """Slow twin: 4x the batch, half the statistical-noise budget."""
    assert _stochastic_tv(base, b=256, seed=37) < 0.12


# ----------------------------------------------------------- engine parity


def _engine_parity(cfg, params, text, *, quantize_kv=None, spec_k=3):
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
        num_slots=4, block_size=4, spec_k=spec_k, quantize_kv=quantize_kv))
    keys = [jax.random.PRNGKey(40 + i) for i in range(4)]
    cscales = [1.0, 3.0, 1.0, 2.0]
    rejected0 = obs_metrics.counter("serving/spec_rejected_tokens").value
    reqs = [eng.submit(text[i], key=keys[i], cond_scale=cscales[i])
            for i in range(4)]
    eng.run_until_idle()
    for i, req in enumerate(reqs):
        want = fused_ref(params, cfg, text[i], keys[i],
                         cond_scale=cscales[i])
        np.testing.assert_array_equal(req.codes[None], want)
        assert req.spec_rounds > 0
        assert req.accepted_tokens_per_step is not None
        assert 1.0 <= req.accepted_tokens_per_step <= spec_k + 1
    # rejections must actually have happened for this to test ROLLBACK (a
    # rolled-back-then-refilled slot producing the never-speculated bits is
    # the whole point); random-init acceptance never hits 100%
    assert (obs_metrics.counter("serving/spec_rejected_tokens").value
            > rejected0)
    return eng


def test_engine_spec_parity_cfg_lanes(base):
    """Mixed solo + guided lane pairs through the speculative engine: every
    request bit-identical to its fused batch-1 reference, with rollback
    exercised (rejected tokens observed)."""
    cfg, params, text = base
    _engine_parity(cfg, params, text)


@pytest.mark.slow  # tier-1 budget: int8 composition rides the slow tier
# (test_engine_spec_parity_cfg_lanes is the fast twin; the slow
# test_engine_spec_parity_matrix composes int8 with the other variants).
def test_engine_spec_parity_int8_kv(base):
    """Same gate with the paged pool stored int8 (per-token scales are
    rewritten on every speculative position, accepted or rejected)."""
    cfg, params, text = base
    _engine_parity(cfg, params, text, quantize_kv="int8")


@pytest.mark.slow
def test_engine_spec_parity_matrix(base):
    """Slow twin: sparse decode tables and scan_layers composed with spec
    on the engine path, k sweep."""
    for kw in (dict(scan_layers=True),
               dict(attn_types=("full", "axial_row"), sparse_decode=True)):
        cfg = tiny_cfg(**kw)
        params = dalle_mod.init_dalle(jax.random.PRNGKey(14), cfg)
        text = np.asarray(jax.random.randint(
            jax.random.PRNGKey(15), (4, cfg.text_seq_len), 1,
            cfg.num_text_tokens))
        for spec_k in (1, 2):
            _engine_parity(cfg, params, text, spec_k=spec_k)


def test_engine_spec_off_is_sequential_path(base):
    """spec_k=0 must not even build the spec jits — today's path, same
    bits, zero spec bookkeeping."""
    cfg, params, text = base
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
        num_slots=2, block_size=4))
    assert eng._spec is None
    key = jax.random.PRNGKey(50)
    req = eng.submit(text[0], key=key)
    eng.run_until_idle()
    np.testing.assert_array_equal(req.codes[None],
                                  fused_ref(params, cfg, text[0], key))
    assert req.spec_rounds == 0 and req.accepted_tokens_per_step is None


# ------------------------------------------------------- truncate_slot pool


def test_truncate_slot_properties(base):
    """Rollback is a ledger commit, not an allocator event: repeated
    truncations free nothing, move no high-water mark, and leave the
    fragmentation gauge consistent; misuse raises."""
    cfg, _, _ = base
    pool = BlockPool(cfg.transformer_config(), num_blocks=12, block_size=4)
    t7 = pool.alloc_table(7)
    pool.alloc_table(9)
    free_before = pool.free_blocks
    hw = pool.high_water
    frag = pool.fragmentation_frac
    max_tokens = pool.blocks_per_seq * pool.block_size
    for n in (0, 3, max_tokens, 5, 4, 1, max_tokens // 2):
        live = pool.truncate_slot(7, n)
        assert live == -(-n // pool.block_size)
        assert pool.free_blocks == free_before      # frees NOTHING
        assert pool.high_water == hw                # no phantom peak
        assert pool.fragmentation_frac == frag      # free list untouched
        assert set(int(b) for b in t7) == set(pool._owned[7])
    with pytest.raises(KeyError):
        pool.truncate_slot(8, 1)                    # never allocated
    with pytest.raises(ValueError):
        pool.truncate_slot(7, -1)
    with pytest.raises(ValueError):
        pool.truncate_slot(7, max_tokens + 1)
    pool.free_table(7)
    with pytest.raises(KeyError):
        pool.truncate_slot(7, 1)                    # freed -> unknown owner
    assert pool.free_blocks == free_before + pool.blocks_per_seq


@pytest.mark.slow
def test_truncated_slot_refill_bit_identical(base):
    """A lane that speculated, rolled back, and refilled must end with the
    never-speculated codes — the engine-parity gate run back-to-back with a
    spec-off engine on the same pool geometry.  (Fast-tier twins:
    `test_engine_spec_parity_cfg_lanes` pins spec-on == fused reference
    with rejections observed, and `test_engine_spec_off_is_sequential_path`
    pins spec-off == the same reference.)"""
    cfg, params, text = base
    key = jax.random.PRNGKey(60)
    eng_off = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
        num_slots=2, block_size=4))
    r_off = eng_off.submit(text[0], key=key)
    eng_off.run_until_idle()
    eng_on = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
        num_slots=2, block_size=4, spec_k=3))
    r_on = eng_on.submit(text[0], key=key)
    eng_on.run_until_idle()
    np.testing.assert_array_equal(r_on.codes, r_off.codes)


# ---------------------------------------------------------- degrade ladder


def test_degrade_suppress_spec_rungs():
    """The rung pin: speculation is suppressed from cap_candidates up and
    re-enabled on descent."""
    lad = DegradeLadder(DegradeConfig(), text_seq_len=8)
    for rung, want in ((0, False), (1, False), (2, True), (3, True),
                       (4, True)):
        lad.rung = rung
        assert lad.suppress_spec is want


@pytest.mark.slow  # tier-1 budget: the engine-level rung drill rides the
# slow tier (test_degrade_suppress_spec_rungs pins the rung table fast;
# the fleet load-shed tests exercise ladder pressure in tier 1).
def test_degrade_rung2_falls_back_to_sequential(base):
    """Engine with spec armed + ladder at cap_candidates: the poll must run
    the sequential decode jit (zero spec rounds), stay bit-exact for the
    rung-0-admitted request, and resume speculating after descent."""
    cfg, params, text = base
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
        num_slots=2, block_size=4, spec_k=3))
    eng.degrade = DegradeLadder(DegradeConfig(), text_seq_len=cfg.text_seq_len)
    eng.degrade_observe = False          # pin the rung for the test
    key = jax.random.PRNGKey(70)
    req = eng.submit(text[0], key=key)    # admitted under rung 0: no cap
    eng.degrade.rung = 2                  # pressure hits before decode
    eng.run_until_idle()
    np.testing.assert_array_equal(req.codes[None],
                                  fused_ref(params, cfg, text[0], key))
    assert req.spec_rounds == 0           # every round ran sequentially
    eng.degrade.rung = 0                  # calm again -> speculation resumes
    key2 = jax.random.PRNGKey(71)
    req2 = eng.submit(text[1], key=key2)
    eng.run_until_idle()
    np.testing.assert_array_equal(req2.codes[None],
                                  fused_ref(params, cfg, text[1], key2))
    assert req2.spec_rounds > 0


# --------------------------------------------------- drain mid-speculation


@pytest.mark.slow  # tier-1 budget: the spec-engine drain leg rides the
# slow tier (the fast-tier drain-resubmit exactness twins live in
# tests/test_fleet_serving.py on the sequential engine).
def test_drain_mid_speculation_resubmit_exact(base):
    """Drain between verify rounds: the export carries only VERIFIED codes,
    and a second replica resubmitting (same text, same key) completes the
    request bit-identically to the fused reference."""
    cfg, params, text = base
    key = jax.random.PRNGKey(80)
    eng1 = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
        num_slots=2, block_size=4, spec_k=3))
    req = eng1.submit(text[2], key=key)
    eng1.poll()                                  # admit + first spec round
    eng1.poll()                                  # a second round
    assert 0 < req.codes_done < cfg.image_seq_len, "finished too fast to drain mid-flight"
    exports = eng1.drain()
    assert len(exports) == 1
    exp = exports[0]
    want = fused_ref(params, cfg, text[2], key)
    # the exported prefix is the verified prefix of the reference stream
    np.testing.assert_array_equal(exp["codes"], want[0, :exp["codes_done"]])
    eng2 = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
        num_slots=2, block_size=4, spec_k=3))
    req2 = eng2.submit(exp["text"], key=exp["key"],
                       temperature=exp["temperature"],
                       cond_scale=exp["cond_scale"])
    eng2.run_until_idle()
    np.testing.assert_array_equal(req2.codes[None], want)
