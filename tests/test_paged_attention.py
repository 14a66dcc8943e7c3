"""The Pallas paged-attention decode kernel (kernels/paged_attention.py) and
the predicate that chooses it (`transformer._use_paged_kernel`).

The XLA path (`_paged_attention_step` + `_paged_scatter_cols`) is the meaning
of the operation and the reference here.  The kernel is the same mathematics
in another order of summation (blockwise online softmax, float32), so outputs
agree to float32 round-off — TOL below — while the POOL it hands back is held
to the reference's byte for byte: the new column is data movement, not
arithmetic.  Everything runs in interpret mode on the CPU; that the kernel
compiles for the chip at DALL-E width is tests/test_chip_compile.py's.

TOL: the attention output is a convex combination of values of order 1
followed by a (2 x 128 -> 256) projection with weights of order 256**-0.5,
so results are of order 1 and a float32 ulp is 1.2e-7; the two paths sum 160
products in different orders (pairwise over the whole row vs block by block
with a running rescale), which measured <= 9e-7 here.  2e-6 absolute is the
repo's bound for reduction-order differences (tests/test_flash_compact.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.kernels import paged_attention as pa
from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models import transformer as tr
from dalle_pytorch_tpu.models.dalle import DALLEConfig
from dalle_pytorch_tpu.models.sampling import sample_image_codes
from dalle_pytorch_tpu.observability import health as health_mod
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

TOL = 2e-6
HEADS, DH, FMAP, SEQ = 2, 128, 12, 160  # text_len 17 + 144 image positions


def _cfg(**kw):
    base = dict(dim=HEADS * DH, depth=1, seq_len=SEQ, heads=HEADS, dim_head=DH,
                image_fmap_size=FMAP, rotary_emb=True)
    base.update(kw)
    return tr.TransformerConfig(**base)


def _pattern(cfg, kind):
    if kind == "none":
        return None
    if kind == "full":  # a pattern that permits every key
        return np.ones((cfg.seq_len, cfg.seq_len), bool)
    return tr._pattern_for(cfg, kind)


def _slots(cfg, bs, seed):
    """Six slots over a pool of random bytes (so every block a slot does not
    own, and every row past its offset, is stale garbage): offsets at 0, at
    a block's first and last row and at seq_len - 1, shuffled non-contiguous
    tables, and two inactive slots (all-zero tables) sharing trash block 0."""
    r = np.random.RandomState(seed)
    nblk = tr.paged_blocks_per_seq(cfg, bs)
    nb = 6 * nblk + 1
    offsets = np.array([0, bs, 2 * bs - 1, cfg.seq_len - 1, 0, 5], np.int32)
    tables = np.zeros((6, nblk), np.int32)
    tables[:4] = r.permutation(np.arange(1, nb))[: 4 * nblk].reshape(4, nblk)
    pool = {
        "k": jnp.asarray(r.randn(nb, cfg.heads, bs, cfg.dim_head), jnp.float32),
        "v": jnp.asarray(r.randn(nb, cfg.heads, bs, cfg.dim_head), jnp.float32),
    }
    x = jnp.asarray(r.randn(6, 1, cfg.dim), jnp.float32)
    return pool, jnp.asarray(tables), jnp.asarray(offsets), x


def _both(cfg, kind, bs, seed=0, pool=None):
    shared = tr.init_transformer(jax.random.PRNGKey(seed), cfg)["shared_attn"]["0"]
    pool0, tables, offsets, x = _slots(cfg, bs, seed)
    pool = pool0 if pool is None else pool
    pattern, rotary = _pattern(cfg, kind), tr.transformer_rotary(cfg)
    assert tr._use_paged_kernel(cfg, pool, pattern, bs)

    @jax.jit
    def kernel(pool):
        return tr._paged_attention_kernel_step(
            shared, cfg, pool, tables, offsets, x, pattern, rotary)

    @jax.jit
    def reference(pool):
        out, cols = tr._paged_attention_step(
            shared, cfg, pool, tables, offsets, x, pattern, rotary)
        return out, tr._paged_scatter_cols(pool, tables, offsets, cols, bs)

    return kernel(pool), reference(pool), (pool, tables, offsets)


@pytest.mark.parametrize("bs", [8, 64])
@pytest.mark.parametrize("kind", ["none", "full", "axial_row", "axial_col", "conv_like"])
def test_kernel_matches_gather_path(kind, bs):
    (out, new), (want, want_pool), (pool, tables, offsets) = _both(_cfg(), kind, bs)
    active = slice(0, 4)  # an inactive slot's output is discarded
    np.testing.assert_allclose(out[active], want[active], rtol=0, atol=TOL)
    # the pool: every block but the trash block equals the reference's, which
    # is the input with each active slot's column written
    for name in ("k", "v"):
        np.testing.assert_array_equal(new[name][1:], want_pool[name][1:])
        changed = np.argwhere((np.asarray(new[name]) != np.asarray(pool[name])).any(axis=(1, 3)))
        wrote = {(int(tables[s, offsets[s] // bs]), int(offsets[s] % bs)) for s in range(4)}
        assert {tuple(c) for c in changed if c[0] != 0} == wrote


@pytest.mark.parametrize("kind", ["none", "axial_col"])
def test_stale_bytes_past_the_offset_change_nothing(kind):
    """Other bytes in every row past a slot's offset (the rest of its current
    block and all of its later blocks): the same output, bit for bit."""
    cfg, bs = _cfg(), 8
    (out, _), _, (pool, tables, offsets) = _both(cfg, kind, bs)
    r = np.random.RandomState(7)
    dirty = {n: np.array(a) for n, a in pool.items()}
    for s in range(4):
        for j in range(tables.shape[1]):
            lo = max(int(offsets[s]) + 1 - j * bs, 0)
            for n in dirty:
                dirty[n][int(tables[s, j]), :, lo:] = 1e4 * r.randn(cfg.heads, bs - min(lo, bs), cfg.dim_head)
    dirty = {n: jnp.asarray(a) for n, a in dirty.items()}
    (out2, _), _, _ = _both(cfg, kind, bs, pool=dirty)
    np.testing.assert_array_equal(out[:4], out2[:4])


@pytest.mark.parametrize("kind", ["axial_row", "conv_like"])
def test_blocks_without_a_permitted_key_are_skipped(kind):
    """NaN in every block of a slot in which its mask row permits no key
    (past the offset, or dead under the pattern) other than the one its
    column goes into: had the kernel computed on such a tile, 0 x NaN would
    reach the output."""
    cfg, bs = _cfg(), 8
    (out, new), _, (pool, tables, offsets) = _both(cfg, kind, bs)
    pattern = _pattern(cfg, kind)
    dirty = {n: np.array(a) for n, a in pool.items()}
    dead = 0
    for s in range(4):
        off = int(offsets[s])
        row = pattern[off, :cfg.seq_len] & (np.arange(cfg.seq_len) <= off)
        for j in range(tables.shape[1]):
            if not row[j * bs:(j + 1) * bs].any() and j != off // bs:
                dead += j * bs <= off
                for n in dirty:
                    dirty[n][int(tables[s, j])] = np.nan
    assert dead > 0, "the pattern should kill blocks under the offset too"
    (out2, new2), _, _ = _both(cfg, kind, bs, pool={n: jnp.asarray(a) for n, a in dirty.items()})
    assert np.isfinite(np.asarray(out2[:4])).all()
    np.testing.assert_array_equal(out[:4], out2[:4])


def _model_step(cfg, bs, seed=0, **range_kw):
    """paged_decode_step through the kernel and with the predicate forced
    off, on the same params, pool, tables, offsets and rings."""
    params = tr.init_transformer(jax.random.PRNGKey(seed), cfg)
    pool0, tables, offsets, x = _slots(cfg, bs, seed)
    r = np.random.RandomState(seed + 1)
    pool = tr.init_paged_pool(cfg, pool0["k"].shape[0], bs)
    pool = jax.tree_util.tree_map(
        lambda a: jnp.asarray(r.randn(*a.shape), a.dtype), pool)
    rings = tr.init_slot_rings(cfg, 6)
    offsets = jnp.maximum(offsets, cfg.text_len)  # token shift: image region only

    def step(pool):
        paths = {"kernel": 0, "fallback": 0}
        res = tr.paged_decode_step(params, cfg, x, pool, tables, offsets,
                                   rings, bs, path_tally=paths, **range_kw)
        return res, paths

    got, paths = step(pool)
    mp = pytest.MonkeyPatch()
    mp.setattr(tr, "_use_paged_kernel", lambda *a: False)
    try:
        want, fell = step(pool)
    finally:
        mp.undo()
    return got, want, paths, fell, pool


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan_layers"])
def test_model_step_matches_gather_path(scan):
    """Three layers (full, axial_row, conv_like) with token shift, per-layer
    pools: `scan_layers` (a property of the training forward) changes nothing."""
    cfg = _cfg(depth=3, attn_types=("full", "axial_row", "conv_like"),
               shift_tokens=True, scan_layers=scan)
    (out, pool, rings), (w_out, w_pool, w_rings), paths, fell, _ = _model_step(cfg, 8)
    assert paths == {"kernel": 3, "fallback": 0} and fell == {"kernel": 0, "fallback": 3}
    # three layers deep the first layer's round-off has passed through two
    # more: 3 x TOL
    np.testing.assert_allclose(out[:4], w_out[:4], rtol=0, atol=3 * TOL)
    # layer 0's column comes from the same input; deeper layers' columns are
    # projections of hidden states that differ by round-off
    np.testing.assert_array_equal(pool["layers"][0]["k"][1:], w_pool["layers"][0]["k"][1:])
    for g, w in zip(jax.tree_util.tree_leaves(pool), jax.tree_util.tree_leaves(w_pool)):
        np.testing.assert_allclose(g[..., 1:, :, :, :], w[..., 1:, :, :, :], rtol=0, atol=3 * TOL)
    for g, w in zip(jax.tree_util.tree_leaves(rings), jax.tree_util.tree_leaves(w_rings)):
        np.testing.assert_allclose(g[..., :4, :, :, :], w[..., :4, :, :, :], rtol=0, atol=3 * TOL)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan_layers"])
def test_layer_range_leaves_other_layers_alone(scan):
    """layer_start / layer_stop (the speculative verify half): layers [1, 3)
    run through the kernel, layer 0's pool comes back untouched."""
    cfg = _cfg(depth=3, attn_types=("full", "axial_row", "conv_like"),
               shift_tokens=True, scan_layers=scan)
    (out, pool, _), (w_out, w_pool, _), paths, _, pool_in = _model_step(
        cfg, 8, layer_start=1, layer_stop=3)
    assert paths == {"kernel": 2, "fallback": 0}
    np.testing.assert_allclose(out[:4], w_out[:4], rtol=0, atol=3 * TOL)
    first, first_in = pool["layers"][0]["k"], pool_in["layers"][0]["k"]
    second, w_second = pool["layers"][1]["k"], w_pool["layers"][1]["k"]
    np.testing.assert_array_equal(first, first_in)
    np.testing.assert_array_equal(second[1:], w_second[1:])  # same input: same column


@pytest.mark.parametrize("step", ["paged_decode_step", "decode_step"])
def test_decode_program_ignores_scan_layers(step):
    """A `scan_layers=True` config decodes through the program the `False`
    config decodes through: the same jaxpr, so no `scan` over depth and no
    layer weights stacked inside the decode program."""
    kw = dict(depth=3, attn_types=("full", "axial_row", "conv_like"), shift_tokens=True)

    def program(cfg):
        params = jax.eval_shape(lambda: tr.init_transformer(jax.random.PRNGKey(0), cfg))
        x = jax.ShapeDtypeStruct((2, 1, cfg.dim), jnp.float32)
        if step == "decode_step":
            cache = jax.eval_shape(lambda: tr.init_cache(cfg, 2))
            return jax.make_jaxpr(lambda p, x, c: tr.decode_step(p, cfg, x, c))(params, x, cache)
        nblk = tr.paged_blocks_per_seq(cfg, 8)
        pool = jax.eval_shape(lambda: tr.init_paged_pool(cfg, 2 * nblk + 1, 8))
        rings = jax.eval_shape(lambda: tr.init_slot_rings(cfg, 2))
        tables = jax.ShapeDtypeStruct((2, nblk), jnp.int32)
        offsets = jax.ShapeDtypeStruct((2,), jnp.int32)
        return jax.make_jaxpr(
            lambda p, x, pool, t, o, r: tr.paged_decode_step(p, cfg, x, pool, t, o, r, 8)
        )(params, x, pool, tables, offsets, rings)

    loop, scan = program(_cfg(**kw)), program(_cfg(scan_layers=True, **kw))
    assert str(scan) == str(loop)
    assert not any(e.primitive.name == "scan" for e in scan.jaxpr.eqns)
    if step == "paged_decode_step":  # eligible shapes: every layer calls the kernel in place
        assert str(scan).count("paged_decode_attn") == 3


# ---------------------------------------------------------------------------
# the predicate
# ---------------------------------------------------------------------------

def _dense_and_paged(cfg, bs, steps=2, seed=0):
    """Prefill a dense cache, copy it into a pool, then decode `steps` tokens
    through `decode_step` on the cache and `paged_decode_step` on the pool."""
    params = tr.init_transformer(jax.random.PRNGKey(seed), cfg)
    r = np.random.RandomState(seed)
    n_pre = cfg.text_len
    emb = jnp.asarray(r.randn(1, n_pre, cfg.dim), jnp.float32)
    cache = tr.init_cache(cfg, 1)
    _, cache = tr.prefill(params, cfg, emb, cache)
    nblk = tr.paged_blocks_per_seq(cfg, bs)
    pool = tr.init_paged_pool(cfg, nblk + 1, bs)
    tables = jnp.asarray(r.permutation(np.arange(1, nblk + 1)), jnp.int32)[None]
    pool = tr.write_prefill_to_pool(pool, tables, cache["layers"], n_pre, bs)
    outs, paths = [], {"kernel": 0, "fallback": 0}
    for t in range(steps):
        x = jnp.asarray(r.randn(1, 1, cfg.dim), jnp.float32)
        dense, cache = tr.decode_step(params, cfg, x, cache)
        paged, pool, _ = tr.paged_decode_step(
            params, cfg, x, pool, tables, jnp.asarray([n_pre + t], jnp.int32), None, bs,
            path_tally=paths)
        outs.append((dense, paged))
    return outs, paths


DISQUALIFIED = {
    "per_head_pattern": (dict(attn_types=("sparse",), sparse_per_head=True, sparse_block_size=8), 8),
    "stable": (dict(stable=True), 8),
    "dim_head_64": (dict(dim=128, dim_head=64), 8),
    "block_size_4": (dict(), 4),
}


@pytest.mark.parametrize("name", list(DISQUALIFIED))
def test_disqualified_input_takes_gather_path_bit_identical_to_dense(name):
    kw, bs = DISQUALIFIED[name]
    cfg = _cfg(depth=2, **kw)
    outs, paths = _dense_and_paged(cfg, bs)
    assert paths == {"kernel": 0, "fallback": 2 * cfg.depth}  # two steps traced
    for dense, paged in outs:
        np.testing.assert_array_equal(dense, paged)


def test_int8_pool_takes_gather_path_bit_identical_to_dense_view():
    """An int8 pool falls back, and its attention equals `_attention_cached`
    on the slot's dense int8 view (the path's definition) bit for bit."""
    from dalle_pytorch_tpu.quantization import quantize_kv

    cfg, bs = _cfg(), 8
    shared = tr.init_transformer(jax.random.PRNGKey(0), cfg)["shared_attn"]["0"]
    fpool, tables, offsets, x = _slots(cfg, bs, 0)
    kq, ks = quantize_kv(fpool["k"])
    vq, vs = quantize_kv(fpool["v"])
    pool = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    assert not tr._use_paged_kernel(cfg, pool, None, bs)
    rotary = tr.transformer_rotary(cfg)
    for s in range(4):  # one slot a call: the same shapes as the dense call
        out, _ = tr._paged_attention_step(
            shared, cfg, pool, tables[s:s + 1], offsets[s:s + 1], x[s:s + 1], None, rotary)
        def view(a):  # (blocks, h, bs, ...) -> (1, h, seq, ...)
            a = a[tables[s]]
            a = jnp.moveaxis(a, 0, 1).reshape(cfg.heads, -1, *a.shape[3:])
            return a[None, :, :cfg.seq_len]
        dense, _ = tr._attention_cached(
            shared, cfg, {n: view(a) for n, a in pool.items()}, x[s][None],
            None, rotary, offsets[s])
        np.testing.assert_array_equal(out[0], dense[0])


def test_predicate_reads_only_its_input(monkeypatch):
    """Each clause alone turns the kernel off; nothing else is consulted."""
    cfg = _cfg()
    pool = {"k": jnp.zeros((3, HEADS, 8, DH)), "v": jnp.zeros((3, HEADS, 8, DH))}
    assert tr._use_paged_kernel(cfg, pool, None, 8)
    assert tr._use_paged_kernel(cfg, pool, np.ones((SEQ, SEQ), bool), 8)
    assert not tr._use_paged_kernel(cfg, pool, np.ones((HEADS, SEQ, SEQ), bool), 8)
    assert not tr._use_paged_kernel(cfg, dict(pool, k_scale=0, v_scale=0), None, 8)
    assert not tr._use_paged_kernel(_cfg(stable=True), pool, None, 8)
    assert not tr._use_paged_kernel(_cfg(dim=128, dim_head=64), pool, None, 8)
    assert not tr._use_paged_kernel(cfg, pool, None, 4)
    bf16 = {n: a.astype(jnp.bfloat16) for n, a in pool.items()}
    assert not tr._use_paged_kernel(cfg, bf16, None, 8)  # a bf16 tile is 16 rows
    assert tr._use_paged_kernel(cfg, bf16, None, 16)
    assert pa.supports(128, 64, jnp.float32) and not pa.supports(128, 64, jnp.int8)
    with health_mod.capture_taps():
        assert not tr._use_paged_kernel(cfg, pool, None, 8)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert not tr._use_paged_kernel(cfg, pool, None, 8)


# ---------------------------------------------------------------------------
# the engine: counters, status file, delivered codes
# ---------------------------------------------------------------------------

def _engine_cfg(dim_head):
    return DALLEConfig(
        dim=2 * dim_head, depth=2, num_text_tokens=64, text_seq_len=8, heads=2,
        dim_head=dim_head, num_image_tokens=32, image_fmap_size=4,
        shift_tokens=True, attn_types=("full", "axial_row"))


def _counters():
    snap = obs_metrics.REGISTRY.snapshot(reset_window=False)
    return tuple(int(snap[f"serving/paged_attn_{k}_layers"]["total"])
                 for k in ("kernel", "fallback"))


@pytest.mark.parametrize("dim_head,bs,want", [(128, 8, (2, 0)), (8, 4, (0, 2))],
                         ids=["eligible", "existing_tiny"])
def test_engine_counters_status_and_delivered_codes(dim_head, bs, want, tmp_path):
    """The registry counters say which path the decode program's layers took,
    and so do the `kind:"metrics"` record of a telemetry flush, the
    serving_window event and the status file; the eligible engine's delivered
    codes lie in the reference's top k at every position
    (benchmark/harness/correct.py's rule), the tiny one's equal the fused
    sampler's."""
    import json

    from dalle_pytorch_tpu.observability import telemetry

    cfg = _engine_cfg(dim_head)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    text = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (3, cfg.text_seq_len), 1, cfg.num_text_tokens))
    keys = [jax.random.PRNGKey(30 + i) for i in range(3)]
    obs_metrics.REGISTRY.reset()
    tele = telemetry.configure(str(tmp_path), run_name="serve", heartbeat_s=None,
                               watch_compiles=False)
    try:
        eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
            num_slots=2, block_size=bs, telemetry_every=4))
        eng._status_path = str(tmp_path / "status.json")
        assert eng.paged_path_state() == {"paged_attn_kernel_layers": None,
                                          "paged_attn_fallback_layers": None}
        # dim x 32 image codes and their 32 bias entries, float32
        head_bytes = 4 * (32 * cfg.dim + 32)
        assert eng.decode_head_state() == {"decode_head_prepared": None,
                                           "decode_head_bytes": head_bytes}
        reqs = eng.generate(text, keys=keys)  # three requests through two slots
        eng.close()
    finally:
        tele.flush(fleet=False)
        tele.close()
    assert _counters() == want
    recs = [json.loads(line) for line in (tmp_path / "serve.spans.jsonl").read_text().splitlines()]
    flushed = [r for r in recs if r.get("kind") == "metrics"][-1]["metrics"]
    window = [r for r in recs if r.get("kind") == "serving_window"][-1]
    serving = json.loads((tmp_path / "status.json").read_text())["serving"]
    for names in (window, serving):
        assert (names["paged_attn_kernel_layers"], names["paged_attn_fallback_layers"]) == want
    assert (flushed["serving/paged_attn_kernel_layers"]["total"],
            flushed["serving/paged_attn_fallback_layers"]["total"]) == want
    # the decode program was traced once, on the table laid out at build
    snap = obs_metrics.REGISTRY.snapshot(reset_window=False)
    for names in (window, serving, eng.decode_head_state()):
        assert (names["decode_head_prepared"], names["decode_head_bytes"]) == (1, head_bytes)
    for reg in (snap, flushed):
        assert reg["serving/decode_head_prepared"]["total"] == 1
        assert reg["serving/decode_head_bytes"]["last"] == head_bytes

    k = max(int((1.0 - eng.ecfg.filter_thres) * cfg.total_tokens), 1)
    for i, req in enumerate(reqs):
        fused = np.asarray(sample_image_codes(
            params, cfg, jnp.asarray(text[i])[None], keys[i],
            filter_thres=eng.ecfg.filter_thres))
        if dim_head == 8:
            np.testing.assert_array_equal(req.codes[None], fused)
            continue
        codes = jnp.asarray(req.codes, jnp.int32)
        logits = dalle_mod.forward(params, cfg, jnp.asarray(text[i])[None], codes[None])[0]
        lg = logits[cfg.text_seq_len:, cfg.num_text_tokens_padded:]
        chosen = jnp.take_along_axis(lg, codes[:, None], axis=1)
        ranks = np.asarray((lg > chosen).sum(axis=1))
        assert (ranks < k).all(), ranks
