"""Compacted-grid block-sparse flash attention (PR 8).

Bit-parity of the compacted (scalar-prefetch) grid against the dense
pl.when-skipping grid: the compacted kernels visit the same live tiles in
the same order, so every float op sequence — forward online softmax, dq
row accumulation, dk/dv column accumulation — is identical and the outputs
must match to the last bit (np.testing.assert_array_equal, not allclose).

Also covered: the sparse_index table builders (liveness round-trip,
placeholder/padding semantics, decode gather tables vs brute force),
per-head sparse layouts, key-mask interaction, the VFA two-pass forward
(allclose by design — fixed-max accumulation reorders the sums),
scan_layers stacked tables, sparse-aware cached decode, resolve_block's
rule (the largest lane-aligned divisor under the cap, then the divisor
fallback) with everything that must agree on it, parity at the 384 x 384 tile
the train cells run, and the seq-4096 axial scenario (tile-count speedup
ratio asserted on CPU; ledger verdict + decode gather width).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.kernels.flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    flash_attention,
    resolve_block,
)
from dalle_pytorch_tpu.kernels import sparse_index as si
from dalle_pytorch_tpu.models.transformer import (
    TransformerConfig,
    _pattern_for,
    apply_transformer,
    decode_step,
    init_cache,
    init_transformer,
    prefill,
)
from dalle_pytorch_tpu.ops.masks import ATTN_TYPES, block_live_np

# 3x3 tile grid at 128x128: big enough that axial/conv/sparse patterns kill
# tiles inside the causal triangle, small enough for interpret mode
N, FMAP, BLOCK = 384, 16, 128
DIM = 32


def _tcfg(**kw):
    base = dict(
        dim=DIM, depth=1, seq_len=N, heads=2, dim_head=DIM,
        image_fmap_size=FMAP, sparse_block_size=16,
    )
    base.update(kw)
    return TransformerConfig(**base)


def qkv(b=1, h=1, n=N, d=DIM, seed=0, dtype=jnp.float32):
    # h=1 default: the grid is (b*h, T), so single-head halves interpret-mode
    # work; multi-head broadcast/layout is covered by the per-head test
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, do = (jax.random.normal(ks[i], (b, h, n, d), jnp.float32).astype(dtype)
                   for i in range(4))
    return q, k, v, do


def _run(grid, mask, q, k, v, do, block=BLOCK, **kw):
    """(out, dq, dk, dv) for one grid choice; the loss contracts with a fixed
    random cotangent so every output element influences every grad."""

    def loss(q, k, v):
        out = flash_attention(q, k, v, mask=mask, block_q=block, block_k=block,
                              grid=grid, **kw)
        return jnp.sum(out * do), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert all(t.dtype == q.dtype for t in (out, *grads))
    return (np.asarray(out),) + tuple(np.asarray(g) for g in grads)


# every pattern runs the same kernel code path — they differ only in which
# tiles the tables mark live — so tier-1 keeps the banded flagship
# (axial_row) and the irregular per-block layout (sparse); the rest ride the
# slow suite to respect the tier-1 time budget
_SLOW_PATTERNS = ("full", "axial_col", "conv_like")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "attn_type",
    [pytest.param(t, marks=pytest.mark.slow) if t in _SLOW_PATTERNS else t
     for t in ATTN_TYPES],
)
def test_compact_matches_dense_grid_bitexact(attn_type, dtype):
    """Forward + dq + dk/dv bit-parity for every pattern ('full' runs the
    causal-only tables: mask=None, liveness = the causal triangle), on
    float32 operands and on the bfloat16 ones the train cells feed: the two
    grids share every line that touches a tile, whatever its type."""
    mask = _pattern_for(_tcfg(), attn_type)
    if mask is not None:
        mask = jnp.asarray(mask)
    q, k, v, do = qkv(dtype=dtype)
    dense = _run("dense", mask, q, k, v, do)
    compact = _run("compact", mask, q, k, v, do)
    for a, b in zip(dense, compact):
        np.testing.assert_array_equal(a, b)


def test_compact_per_head_sparse_bitexact():
    """Per-head random block layouts need per-head tables (H == h); the
    union-table shortcut would let dead tiles contribute exp(0)=1 mass."""
    cfg = _tcfg(sparse_per_head=True)
    mask = jnp.asarray(_pattern_for(cfg, "sparse"))
    assert mask.ndim == 3 and mask.shape[0] == cfg.heads
    q, k, v, do = qkv(h=cfg.heads)
    dense = _run("dense", mask, q, k, v, do)
    compact = _run("compact", mask, q, k, v, do)
    for a, b in zip(dense, compact):
        np.testing.assert_array_equal(a, b)


def test_compact_per_head_mask_requires_per_head_tables():
    cfg = _tcfg(sparse_per_head=True)
    mask = jnp.asarray(_pattern_for(cfg, "sparse"))
    q, k, v, _ = qkv(h=cfg.heads)
    shared = si.build_compacted_tables(
        np.ones((N // BLOCK, N // BLOCK), np.int32), BLOCK, BLOCK)
    with pytest.raises(ValueError, match="per-head"):
        flash_attention(q, k, v, mask=mask, block_q=BLOCK, block_k=BLOCK,
                        grid="compact", tables=shared)


def test_compact_with_key_mask_bitexact():
    """Traced key-padding rows compose with the static compacted tables."""
    mask = jnp.asarray(_pattern_for(_tcfg(), "axial_row"))
    q, k, v, do = qkv(seed=3)
    km = (jnp.arange(N) < N - 53)[None].astype(jnp.int32)
    dense = _run("dense", mask, q, k, v, do, key_mask=km)
    compact = _run("compact", mask, q, k, v, do, key_mask=km)
    for a, b in zip(dense, compact):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_vfa_forward_allclose():
    """The VFA two-pass forward (global max first, no per-tile rescale) is
    allclose — NOT bit-identical — to the online-softmax forward: the fixed
    max changes the float sequence.  Backward reuses the standard kernels."""
    mask = jnp.asarray(_pattern_for(_tcfg(), "conv_like"))
    q, k, v, do = qkv(seed=5)
    dense = _run("dense", mask, q, k, v, do)
    vfa = _run("compact", mask, q, k, v, do, vfa=True)
    for a, b in zip(dense, vfa):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


def test_auto_grid_compacts_wherever_a_step_is_dead():
    """'auto' == 'compact' for a tile-killing pattern (same bits out), and for
    mask=None too: causality alone kills the 3 x 3 grid's 3 steps above the
    diagonal, so 'auto' builds the causal tables and the output is still the
    dense grid's, bit for bit."""
    from dalle_pytorch_tpu.kernels.flash_attention import _resolve_tables

    mask = jnp.asarray(_pattern_for(_tcfg(), "axial_row"))
    q, k, v, do = qkv(seed=7)
    auto = _run("auto", mask, q, k, v, do)
    compact = _run("compact", mask, q, k, v, do)
    for a, b in zip(auto, compact):
        np.testing.assert_array_equal(a, b)
    tabs = _resolve_tables("auto", None, None, 1, N, True, BLOCK, BLOCK)
    assert si.live_tile_counts(dict(zip(si.TABLE_KEYS, tabs))) == (6, 6)
    out_auto = flash_attention(q, k, v, block_q=BLOCK, block_k=BLOCK, grid="auto")
    out_dense = flash_attention(q, k, v, block_q=BLOCK, block_k=BLOCK, grid="dense")
    np.testing.assert_array_equal(np.asarray(out_auto), np.asarray(out_dense))


_CAUSAL_3x3 = si.block_causal_live_np(3, 3, BLOCK, BLOCK)


@pytest.mark.parametrize("live,causal,dead", [
    (np.ones((3, 3), bool), True, True),  # causality alone kills the upper triangle
    (~np.eye(3, dtype=bool), False, True),  # the pattern alone kills the diagonal
    (_CAUSAL_3x3 & ~np.eye(3, k=-1, dtype=bool), True, True),  # both
    (np.ones((3, 3), bool), False, False),  # neither: every step is live
    (np.ones((1, 1), bool), True, False),  # a 1 x 1 grid: its one step is live
    (np.ones((2, 3, 3), bool), False, False),  # per-head, all live ...
    (np.stack([np.ones((3, 3), bool), ~np.eye(3, dtype=bool)]), False, True),  # ... one head not
], ids=["causal_only", "pattern_only", "both", "neither", "one_by_one",
        "per_head_live", "per_head_one_dead"])
def test_grid_has_dead_step(live, causal, dead):
    """`grid="auto"`'s one rule, on hand-made tile liveness: compact exactly
    when some step of the grid is dead, whoever kills it."""
    assert si.grid_has_dead_step(live, BLOCK, BLOCK, causal=causal) is dead


# --- sparse_index table builders ---------------------------------------------


def test_compacted_tables_roundtrip():
    """Tables reproduce the exact (causal & live) tile set, row-major with
    correct first/last flags; transposed tables reproduce it column-major;
    fully-dead rows/columns get a placeholder (first=last=1, valid=0)."""
    rng = np.random.RandomState(0)
    bl = rng.rand(5, 5) < 0.4
    bl[3, :] = False  # force a dead query row inside the causal triangle
    tabs = si.build_compacted_tables(bl, 64, 64)
    cl = si.block_causal_live_np(5, 5, 64, 64)
    want = {(i, j) for i, j in zip(*np.nonzero(bl & cl))}

    for qk, kk, fk, lk, vk, outer in (
        ("qrow", "kcol", "first", "last", "valid", "qrow"),
        ("qrowT", "kcolT", "firstT", "lastT", "validT", "kcolT"),
    ):
        qr, kc = tabs[qk][0], tabs[kk][0]
        fr, la, va = tabs[fk][0], tabs[lk][0], tabs[vk][0]
        got = {(int(i), int(j)) for i, j, v in zip(qr, kc, va) if v}
        assert got == want
        # every traversal group (query row / key column — dead ones included,
        # via placeholders) opens with first=1 and closes with last=1 exactly
        # once; no padding entries exist for unpadded tables
        axis = tabs[outer][0]
        opened = [int(axis[t]) for t in range(len(axis)) if fr[t]]
        assert sorted(opened) == list(range(5)) and len(set(opened)) == 5
        assert fr.sum() == la.sum() == 5
        assert ((fr | la | va) == 1).all()

    # placeholder for the dead query row: init+finalize, no compute
    qr, fr, la, va = tabs["qrow"][0], tabs["first"][0], tabs["last"][0], tabs["valid"][0]
    ph = [(f, l, v) for r, f, l, v in zip(qr, fr, la, va) if r == 3 and (f or l)]
    assert ph == [(1, 1, 0)]


def test_compacted_tables_padding():
    bl = np.tril(np.ones((3, 3), bool))
    tabs = si.build_compacted_tables(bl, 128, 128, pad_to=(10, 11))
    assert tabs["qrow"].shape == (1, 10) and tabs["qrowT"].shape == (1, 11)
    assert si.table_grid_sizes(tabs) == (10, 11)
    assert si.live_tile_counts(tabs) == (6, 6)
    # padding entries replicate the final coordinates with all-zero flags
    assert (tabs["valid"][0, 6:] == 0).all() and (tabs["first"][0, 6:] == 0).all()
    assert (tabs["qrow"][0, 6:] == tabs["qrow"][0, 5]).all()


def test_decode_tables_match_brute_force():
    cfg = _tcfg()
    for attn_type in ("axial_row", "conv_like", "sparse"):
        p = np.asarray(_pattern_for(cfg, attn_type), bool)
        idx, counts = si.build_decode_tables(p)
        assert int(counts.max()) == idx.shape[-1] == si.decode_kv_span(p, N)
        for t in range(0, N, 37):
            hits = np.flatnonzero(p[t, : t + 1])
            assert counts[t] == hits.size
            np.testing.assert_array_equal(idx[t, : hits.size], hits)
            assert (idx[t, hits.size:] == 0).all()
    assert si.decode_kv_span(None, N) == N
    # per-head: one table stack per head
    ph = np.asarray(_pattern_for(_tcfg(sparse_per_head=True), "sparse"), bool)
    idx, counts = si.build_decode_tables(ph)
    assert idx.ndim == 3 and idx.shape[0] == ph.shape[0]
    for h in range(ph.shape[0]):
        np.testing.assert_array_equal(
            counts[h], si.decode_kv_counts(ph[h]))


# --- resolve_block: the tile rule ----------------------------------------------

# (n, cap) -> block.  Under the default cap of 384 the LARGEST multiple of the
# 128-lane width that divides n: 384 where 384 divides (both sequences the
# train cells run, 1,152 and 4,224), 256 where only 256 does (the package's
# default 1,280; fmap 64's 4,352), else 128.  A caller's smaller cap still
# caps.  Where no multiple of 128 under the cap divides n, as before: the cap
# halved, then divisors (aligned ones first), then the error.
RESOLVE_TABLE = [
    (1152, 384, 384), (4224, 384, 384), (768, 384, 384), (384, 384, 384),
    (1280, 384, 256), (4352, 384, 256), (1024, 384, 256), (256, 384, 256),
    (640, 384, 128), (128, 384, 128),
    (1152, 128, 128), (4224, 128, 128),      # block_q=128 passed in stays 128
    (1152, 256, 128), (4224, 256, 128),      # no divisor 256: what every cell ran before
    (640, 256, 128), (256, 256, 256), (1280, 256, 256),
    (4224, 512, 384),                        # the largest, not the first that fits
    (129, 384, 129), (129, 128, 43),         # a 129-position prefill: one tile; 43 = 129 / 3
    (64, 384, 64), (96, 384, 96),            # shorter than a lane tile: the cap is n
    # 270 = 2*3^3*5: halving bottoms out at 2 (<8); the largest divisor <= cap
    (270, 384, 270), (270, 256, 135), (270, 135, 135),
]


@pytest.mark.parametrize("n,cap,want", RESOLVE_TABLE,
                         ids=[f"{n}_cap{cap}" for n, cap, _ in RESOLVE_TABLE])
def test_resolve_block_rule(n, cap, want):
    assert resolve_block(n, cap) == want
    assert n % want == 0 and want <= cap


def test_resolve_block_divisor_fallback():
    assert DEFAULT_BLOCK_Q == DEFAULT_BLOCK_K == 384
    # 2305 = 5*461: no divisor in [8, cap] exists — the error must say so
    with pytest.raises(ValueError, match="no divisor"):
        resolve_block(2305, DEFAULT_BLOCK_Q)
    with pytest.raises(ValueError, match="no divisor"):
        resolve_block(2305, 256)


@pytest.mark.parametrize("n,fmap,want", [(1152, 32, 384), (1280, 32, 256), (4224, 64, 384)])
def test_scan_path_profiler_and_kernel_resolve_one_block(n, fmap, want, monkeypatch):
    """Three places must agree on the tile: `flash_attention` (the grid it
    runs), `transformer._apply_scan` (the liveness and compacted tables it
    builds for the traced per-layer select) and `profiling._attn_tile_density`
    (what it prices as executed).  Each is watched where it USES its blocks,
    with the model only traced (`eval_shape`): the kernel's tile counter, the
    blocks `_stacked_flash_tables` is handed and the shape of the liveness
    table that reaches the kernel, and the blocks the profiler asks the causal
    tile table for."""
    from dalle_pytorch_tpu.kernels import flash_attention as fa
    from dalle_pytorch_tpu.models import transformer as tr
    from dalle_pytorch_tpu.observability import metrics as obs_metrics
    from dalle_pytorch_tpu.training import profiling

    cfg = _tcfg(seq_len=n, image_fmap_size=fmap, depth=2, dim=16, heads=1, dim_head=16,
                attn_types=("full", "axial_row"), scan_layers=True, attn_kernel="flash")
    seen = {"scan": [], "live": [], "profiler": []}
    stacked, flash, causal_live = tr._stacked_flash_tables, fa.flash_attention, si.block_causal_live_np

    def spy_stacked(cfg, masks_np, bq, bk, causal):
        seen["scan"].append((bq, bk))
        return stacked(cfg, masks_np, bq, bk, causal)

    def spy_flash(q, k, v, **kw):
        seen["live"].append(tuple(kw["live"].shape))
        return flash(q, k, v, **kw)

    monkeypatch.setattr(tr, "_stacked_flash_tables", spy_stacked)
    monkeypatch.setattr(fa, "flash_attention", spy_flash)
    tile = obs_metrics.counter(f"kernels/flash_tile_{want}x{want}")
    before = tile.value
    params = jax.eval_shape(lambda k: init_transformer(k, cfg), jax.random.PRNGKey(0))
    jax.eval_shape(lambda p, x: apply_transformer(p, cfg, x), params,
                   jax.ShapeDtypeStruct((1, n, cfg.dim), jnp.float32))
    assert tile.value == before + 1  # one scanned layer body, one call, THIS tile
    assert seen["scan"] == [(want, want)]
    assert seen["live"] == [(n // want, n // want)]

    def spy_causal(nq, nk, bq, bk):
        seen["profiler"].append((nq, nk, bq, bk))
        return causal_live(nq, nk, bq, bk)

    monkeypatch.setattr(si, "block_causal_live_np", spy_causal)
    density = profiling._attn_tile_density(cfg)
    assert seen["profiler"] == [(n // want, n // want, want, want)]
    assert 0 < density <= (n // want + 1) / (2 * (n // want))  # at most the causal tiles


# --- transformer integration -------------------------------------------------


# The model path passes no block: the sequence's divisors choose it.  640 =
# 5 x 128 (text 65 + 24 x 24 - 1) has no divisor 384 or 256, so these stacks keep
# a 5 x 5 grid of 128-tiles in which both patterns kill tiles inside the
# causal triangle (at N = 384 the default cap now gives ONE 384-tile, and the
# stacked tables would be `None`).
N_SCAN = 640


def _scan_cfg():
    cfg = _tcfg(
        depth=2, dim_head=16, attn_types=("axial_row", "conv_like"), seq_len=N_SCAN,
        image_fmap_size=24, shift_tokens=True, scan_layers=True, attn_kernel="flash",
    )
    assert resolve_block(N_SCAN, DEFAULT_BLOCK_Q) == resolve_block(N_SCAN, DEFAULT_BLOCK_K) == BLOCK
    for t in cfg.attn_types:  # the premise: each pattern has dead tiles to compact away
        live = block_live_np(np.asarray(_pattern_for(cfg, t), bool), BLOCK, BLOCK)
        assert not (live | ~si.block_causal_live_np(5, 5, BLOCK, BLOCK)).all(), t
    return cfg


def test_scan_layers_stacked_tables_bitexact():
    """scan_layers selects per-layer tables out of a stacked (depth-padded)
    array by traced index; the forward must match the dense grid bit-for-bit.
    (Forward-only to stay inside the tier-1 time budget — the grad legs and
    the unrolled cross-check live in the slow companion below.)"""
    cfg = _scan_cfg()
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, N_SCAN, cfg.dim), jnp.float32)
    o_dense = apply_transformer(params, dataclasses.replace(cfg, attn_grid="dense"), x)
    o_comp = apply_transformer(params, dataclasses.replace(cfg, attn_grid="compact"), x)
    np.testing.assert_array_equal(np.asarray(o_dense), np.asarray(o_comp))


@pytest.mark.slow
def test_scan_layers_stacked_tables_grads_bitexact():
    """Grad legs of the scan stacked-table parity: input grads match the
    dense grid bit-for-bit (the dq and dk/dv compacted kernels under the
    traced table select), and the unrolled compact path is allclose."""
    cfg = _scan_cfg()
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, N_SCAN, cfg.dim), jnp.float32)

    def run(c):
        f = lambda x: jnp.sum(jnp.sin(apply_transformer(params, c, x)))
        out = apply_transformer(params, c, x)
        return np.asarray(out), np.asarray(jax.grad(f)(x))

    o_dense, g_dense = run(dataclasses.replace(cfg, attn_grid="dense"))
    o_comp, g_comp = run(dataclasses.replace(cfg, attn_grid="compact"))
    np.testing.assert_array_equal(o_dense, o_comp)
    np.testing.assert_array_equal(g_dense, g_comp)
    # scan vs unrolled is allclose only — the scan itself reorders
    # NON-attention float ops (stacked-param layout), dense grid included
    o_unrl, g_unrl = run(dataclasses.replace(cfg, attn_grid="compact",
                                             scan_layers=False))
    np.testing.assert_allclose(o_dense, o_unrl, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(g_dense, g_unrl, atol=1e-5, rtol=1e-5)


def _decode_roll(cfg, params, x_prefix, n_steps):
    """prefill the prefix, then decode n_steps single tokens; returns the
    stacked decode outputs."""
    cache = init_cache(cfg, x_prefix.shape[0])
    _, cache = prefill(params, cfg, x_prefix, cache)
    outs = []
    step = jax.jit(lambda x, c: decode_step(params, cfg, x, c))
    for t in range(n_steps):
        x_t = x_prefix[:, -1:] * (0.1 * t + 1.0)
        out, cache = step(x_t, cache)
        outs.append(np.asarray(out))
    return np.stack(outs)


@pytest.mark.parametrize("kw", [
    dict(attn_types=("axial_row", "conv_like")),
    dict(attn_types=("sparse",), sparse_per_head=True),
])
def test_sparse_decode_matches_full_cache(kw):
    """Sparse-aware decode gathers only the pattern-permitted keys.  The
    row-masked full-cache softmax and the gathered softmax see the same live
    scores, but XLA sums them with different reduction-tree widths (Kmax vs
    seq_len), so parity is to reduction-order ulp, not bitwise — the tight
    atol below fails loudly if the gather ever selects a wrong key."""
    cfg = _tcfg(depth=2, dim_head=16, image_fmap_size=8, seq_len=80,
                shift_tokens=True, **kw)
    params = init_transformer(jax.random.PRNGKey(2), cfg)
    # prefix ends inside the image region (cached decode's domain)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, cfg.text_len + 5, cfg.dim))
    sparse = _decode_roll(cfg, params, x, 4)
    full = _decode_roll(dataclasses.replace(cfg, sparse_decode=False), params, x, 4)
    np.testing.assert_allclose(sparse, full, atol=2e-6, rtol=2e-6)


# --- the 384 x 384 tile the train cells run -------------------------------------

N384, FMAP384, TILE = 1152, 32, 384  # the DALL-E cells' sequence: text 128 + 32 x 32


def _tcfg384(**kw):
    return _tcfg(seq_len=N384, image_fmap_size=FMAP384, **kw)


def _masks384(case):
    """(mask for the kernel, key_mask or None, dense bool mask for `attend`)."""
    causal = np.tril(np.ones((N384, N384), bool))
    if case == "key_mask":
        km = np.arange(N384)[None] < np.asarray([N384 - 53])[:, None]
        return None, jnp.asarray(km), jnp.asarray(causal[None, None] & km[:, None, None, :])
    if case == "per_head":
        pm = np.asarray(_pattern_for(_tcfg384(sparse_per_head=True), "sparse"), bool)
        assert pm.ndim == 3
        return jnp.asarray(pm), None, jnp.asarray(causal[None, None] & pm[None])
    pm = _pattern_for(_tcfg384(), case)
    if pm is None:
        return None, None, jnp.asarray(causal)
    pm = np.asarray(pm, bool)
    return jnp.asarray(pm), None, jnp.asarray(causal & pm)


@pytest.mark.parametrize("case", ["full", "axial_row", "axial_col", "conv_like",
                                  "per_head", "key_mask"])
def test_tile_384_matches_dense_attention(case):
    """Forward and all three gradients at the tile the default now resolves for
    1,152 positions (a 3 x 3 grid of 384 x 384 tiles, under the default cap),
    against `ops.attention.attend` on the same float32 inputs: plain causal,
    the three image patterns at fmap 32, a per-head layout and a key-padding
    row.  And the compacted grid at the same tile equals the dense one bit for
    bit (where nothing is dead inside the triangle its tables list every
    causal tile)."""
    from dalle_pytorch_tpu.observability import metrics as obs_metrics
    from dalle_pytorch_tpu.ops.attention import attend

    mask, key_mask, dense_mask = _masks384(case)
    h = 2 if case == "per_head" else 1
    d = 16
    q, k, v, do = qkv(h=h, n=N384, d=d, seed=11)
    kw = {} if key_mask is None else {"key_mask": key_mask}
    tile = obs_metrics.counter(f"kernels/flash_tile_{TILE}x{TILE}")
    before = tile.value
    dense = _run("dense", mask, q, k, v, do, block=DEFAULT_BLOCK_Q, **kw)
    assert tile.value == before + 1

    def loss(q, k, v):
        out = attend(q * d ** -0.5, k, v, mask=dense_mask)
        return jnp.sum(out * do), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), dense, (out, *grads)):
        np.testing.assert_allclose(a, np.asarray(b), atol=5e-5, err_msg=name)
    compact = _run("compact", mask, q, k, v, do, block=DEFAULT_BLOCK_Q, **kw)
    for a, b in zip(dense, compact):
        np.testing.assert_array_equal(a, b)


def test_tile_384_compacts_the_causal_grid_at_1152():
    """Why every causal layer of the DALL-E cells takes the compacted grid:
    at 1,152 positions and fmap 32 the three patterns leave 30, 45 and 35 of
    the 45 causal 128-tiles live, and all 6 of the 6 causal 384-tiles, so no
    pattern kills a tile inside the triangle at 384; causality still kills 3
    of the 9 steps, so `grid="auto"` builds tables of the 6 live ones for each
    pattern and for `mask=None`.  It builds none where every step is live: a
    1 x 1 grid, a non-causal call without a mask."""
    from dalle_pytorch_tpu.kernels.flash_attention import _resolve_tables

    def live_steps(pm, b, causal=True):
        tabs = _resolve_tables("auto", None, pm, 1, N384, causal, b, b)
        return None if tabs is None else si.live_tile_counts(dict(zip(si.TABLE_KEYS, tabs)))

    live_at = {}
    for kind in ("axial_row", "axial_col", "conv_like"):
        pm = np.asarray(_pattern_for(_tcfg384(), kind), bool)
        for b in (128, TILE):
            cl = si.block_causal_live_np(N384 // b, N384 // b, b, b)
            live_at[kind, b] = (int((block_live_np(pm, b, b) & cl).sum()), int(cl.sum()))
            assert live_steps(pm, b) == (live_at[kind, b][0],) * 2
    assert live_at == {("axial_row", 128): (30, 45), ("axial_row", TILE): (6, 6),
                       ("axial_col", 128): (45, 45), ("axial_col", TILE): (6, 6),
                       ("conv_like", 128): (35, 45), ("conv_like", TILE): (6, 6)}
    assert live_steps(None, TILE) == (6, 6)
    assert live_steps(None, TILE, causal=False) is None
    assert _resolve_tables("auto", None, None, 1, TILE, True, TILE, TILE) is None


def test_scan_remat_stack_at_1152_matches_unrolled():
    """The d24 cell's path at its own sequence, small widths: `scan_layers` +
    remat `full` over the four-pattern cycle (one traced mask a layer, its
    (3, 3) liveness table and its compacted tables) against the unrolled stack,
    output and input gradient.  allclose, not equal: the scan reorders float
    operations OUTSIDE attention (stacked parameters)."""
    cfg = _tcfg384(depth=4, dim=16, heads=1, dim_head=16, shift_tokens=True,
                   attn_types=("full", "axial_row", "axial_col", "conv_like"),
                   scan_layers=True, execution="remat", remat_policy="full",
                   attn_kernel="flash")
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, N384, cfg.dim), jnp.float32)

    def run(c):
        f = lambda x: jnp.sum(jnp.sin(apply_transformer(params, c, x)))
        return np.asarray(apply_transformer(params, c, x)), np.asarray(jax.grad(f)(x))

    o_scan, g_scan = run(cfg)
    o_unrl, g_unrl = run(dataclasses.replace(cfg, scan_layers=False, execution="sequential"))
    np.testing.assert_allclose(o_scan, o_unrl, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(g_scan, g_unrl, atol=1e-5, rtol=1e-5)


def test_scan_remat_stack_at_1152_compacts_and_equals_the_dense_grid():
    """`train_d24`'s path under `grid="auto"`: `_stacked_flash_tables` builds
    the stacked tables at 1,152 positions and 384-tiles (causality kills 3 of
    the 9 steps in every layer, though no pattern kills a tile inside the
    triangle), and the scanned, rematted stack run on them equals the dense
    grid's output and gradients (input and parameters) bit for bit."""
    from dalle_pytorch_tpu.models.transformer import (
        _stacked_flash_tables, _stacked_masks, derive_layer_specs,
    )

    cfg = _tcfg384(depth=4, dim=16, heads=1, dim_head=16, shift_tokens=True,
                   attn_types=("full", "axial_row", "axial_col", "conv_like"),
                   scan_layers=True, execution="remat", remat_policy="full",
                   attn_kernel="flash")
    masks_np, _ = _stacked_masks(cfg, derive_layer_specs(cfg), N384)
    tabs = _stacked_flash_tables(cfg, masks_np, TILE, TILE, True)
    assert tabs is not None and tabs["qrow"].shape == (4, 1, 6)
    assert int(tabs["valid"].sum()) == int(tabs["validT"].sum()) == 4 * 6
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, N384, cfg.dim), jnp.float32)

    def run(c):
        f = lambda p, x: jnp.sum(jnp.sin(apply_transformer(p, c, x)))
        grads = jax.grad(f, argnums=(0, 1))(params, x)
        return [np.asarray(apply_transformer(params, c, x))] + [
            np.asarray(g) for g in jax.tree_util.tree_leaves(grads)]

    auto, dense = run(cfg), run(dataclasses.replace(cfg, attn_grid="dense"))
    assert len(auto) == len(dense) > 2
    for a, b in zip(auto, dense):
        np.testing.assert_array_equal(a, b)


# --- seq-4096 scenario -------------------------------------------------------


def test_seq4096_axial_tile_ratio():
    """At 64x64 fmaps (seq 4096 image side) the compacted grid runs >= 4x
    fewer tiles than the dense causal grid for axial patterns — the static
    tile counts ARE the speedup model (each live tile costs the same MXU
    work), so the ratio is asserted here on CPU; no cell times it on the chip yet
    (ROADMAP S6, `train_fmap64`)."""
    n = 4096
    cfg = _tcfg(seq_len=n, image_fmap_size=64)
    # 128x128 tiles: a query block spans 2 image rows, so axial_row's live
    # band stays narrow (at 256 the one-row block misalignment from the text
    # prefix drags the ratio to ~3x; axial_col connects every row of a column
    # and is tile-dense at any block >= fmap — it rides the text/causal skip
    # only, which is why the scenario pairs it with axial_row layers)
    bq = resolve_block(n, 128)
    nq = n // bq
    dense_tiles = int(si.block_causal_live_np(nq, nq, bq, bq).sum())
    mask = np.asarray(_pattern_for(cfg, "axial_row"), bool)
    tabs = si.build_compacted_tables(block_live_np(mask, bq, bq), bq, bq)
    fwd_live, dkv_live = si.live_tile_counts(tabs)
    assert dense_tiles / fwd_live >= 4.0, (dense_tiles, fwd_live)
    assert dense_tiles / dkv_live >= 4.0, (dense_tiles, dkv_live)


def _seq4096_cfg():
    from dalle_pytorch_tpu.models.dalle import DALLEConfig

    return DALLEConfig(
        dim=32, depth=2, num_text_tokens=64, text_seq_len=256, heads=2,
        dim_head=16, num_image_tokens=32, image_fmap_size=64,
        attn_types=("axial_row", "axial_col"), shift_tokens=True,
    )


def test_seq4096_scenario_ledger_and_knobs():
    """image_fmap_size=64 (seq 4352): the sampling ledger's HBM verdict holds
    (the decode-gather row prices Kmax reads, far below the full cache), and
    the grid/decode knobs ride DALLEConfig -> transformer_config().  The
    actual seq-4352 decode roll lives in the slow e2e test below; sparse
    decode parity runs tier-1 at seq 80 above."""
    cfg = _seq4096_cfg()
    from dalle_pytorch_tpu.observability.memory import sampling_memory_ledger

    led = sampling_memory_ledger(cfg, 1, itemsize=4, capacity_bytes=16e9)
    assert led["fits"] is True
    rows = {r["name"]: r for r in led["rows"]}
    assert "decode_gather" in rows
    # axial patterns bound the gather width well below the sequence length
    tcfg = cfg.transformer_config()
    spans = [si.decode_kv_span(np.asarray(_pattern_for(tcfg, t), bool),
                               cfg.total_seq_len)
             for t in cfg.attn_types]
    assert max(spans) < cfg.total_seq_len // 4
    assert led["decode_kv_read_bytes_per_step"] < (
        2 * cfg.depth * cfg.heads * cfg.total_seq_len * cfg.dim_head * 4)

    # the knobs ride DALLEConfig -> transformer_config() (CLI/serving reach)
    off = dataclasses.replace(cfg, sparse_decode=False, attn_grid="dense")
    assert off.transformer_config().sparse_decode is False
    assert off.transformer_config().attn_grid == "dense"


@pytest.mark.slow
def test_seq4096_axial_trains_and_samples():
    """End-to-end at seq 4352: one train grad step produces finite grads and
    a cached sampling roll stays in range — the scenario the compacted
    kernels + sparse decode exist to make tractable."""
    from dalle_pytorch_tpu.models import dalle as dalle_mod

    cfg = _seq4096_cfg()
    tcfg = cfg.transformer_config()

    # sparse decode roll agrees with the full-cache decode at seq 4352
    tparams = init_transformer(jax.random.PRNGKey(0), tcfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, tcfg.text_len + 3, tcfg.dim))
    sparse = _decode_roll(tcfg, tparams, x, 3)
    full = _decode_roll(dataclasses.replace(tcfg, sparse_decode=False),
                        tparams, x, 3)
    np.testing.assert_allclose(sparse, full, atol=2e-6, rtol=2e-6)

    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    text = jax.random.randint(jax.random.PRNGKey(1), (1, cfg.text_seq_len),
                              1, cfg.num_text_tokens)
    codes = jax.random.randint(jax.random.PRNGKey(2), (1, cfg.image_seq_len),
                               0, cfg.num_image_tokens)

    loss, grads = jax.value_and_grad(
        lambda p: dalle_mod.forward(p, cfg, text, codes, return_loss=True)
    )(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))

    from dalle_pytorch_tpu.models.sampling import sample_image_codes

    primer = jax.random.randint(jax.random.PRNGKey(3),
                                (1, cfg.image_seq_len - 8), 0,
                                cfg.num_image_tokens)
    out = np.asarray(sample_image_codes(
        params, cfg, text, jax.random.PRNGKey(4), primer_codes=primer,
        prime_len=int(primer.shape[1])))
    assert out.shape == (1, cfg.image_seq_len)
    assert (out >= 0).all() and (out < cfg.num_image_tokens).all()
    np.testing.assert_array_equal(out[:, : primer.shape[1]], np.asarray(primer))
