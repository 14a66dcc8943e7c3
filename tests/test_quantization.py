"""Quantized serving (quantization.py + the int8 paged pool).

The load-bearing properties, in the order the ISSUE's acceptance names
them: (1) greedy paged decode with int8 KV (and int8 weights) stays within
the DECLARED drift budget of the bf16/f32 path — measured through the real
serving path, not a synthetic matmul; (2) the at-rest byte reductions the
ledgers quote actually materialize (>=1.9x for weights and for the KV pool
at realistic geometry); (3) the fused (quantize-at-scatter) and
disaggregated (quantize-at-handoff) paths write BIT-IDENTICAL pools — the
per-token scale design makes the orders commute, so prefill/decode
disaggregation does not perturb parity; (4) quantized trees survive the v3
checkpoint seam bit-exactly and reshard under the registry with scales
placed beside their blocks.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu import quantization as quant
from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models import transformer as tr
from dalle_pytorch_tpu.models.dalle import DALLEConfig


def tiny_cfg(**kw):
    base = dict(
        dim=32, depth=2, num_text_tokens=64, text_seq_len=8, heads=2,
        dim_head=8, num_image_tokens=32, image_fmap_size=4, shift_tokens=True,
    )
    base.update(kw)
    return DALLEConfig(**base)


@pytest.fixture(scope="module")
def base():
    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    text = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (1, cfg.text_seq_len), 1, cfg.num_text_tokens))
    return cfg, params, text


# ---------------------------------------------------------------------------
# weight quantization round trip
# ---------------------------------------------------------------------------

def test_quantize_weight_round_trip_error_bound():
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 48), jnp.float32)
    q = quant.quantize_weight(w, "int8")
    assert q["qvalue"].dtype == jnp.int8 and q["scale"].shape == (48,)
    deq = quant.maybe_dequant_weight(q)
    # symmetric int8: per-channel error bounded by half a quantization step
    step = np.asarray(q["scale"])[None, :]
    assert np.all(np.abs(np.asarray(deq) - np.asarray(w)) <= step * 0.5 + 1e-7)


def test_quantize_table_per_row_scales():
    t = jax.random.normal(jax.random.PRNGKey(3), (10, 16), jnp.float32) * \
        jnp.arange(1, 11, dtype=jnp.float32)[:, None]  # rows at wild scales
    q = quant.quantize_table(t, "int8")
    assert q["scale"].shape == (10, 1)  # per ROW, broadcastable in dequant
    deq = np.asarray(quant.maybe_dequant_weight(q))
    step = np.asarray(q["scale"])
    assert np.all(np.abs(deq - np.asarray(t)) <= step * 0.5 + 1e-7)


def test_quantize_tree_targets_and_idempotence(base):
    cfg, params, _ = base
    q = quant.quantize_tree(params, "int8")
    assert quant.tree_is_quantized(q) and not quant.tree_is_quantized(params)
    assert quant.weight_quant_kind(q) == "int8"
    assert quant.weight_quant_kind(params) is None
    # matmul blocks and the vocab tables are quantized ...
    assert quant.is_quantized_weight(q["logits_linear"]["w"])
    assert quant.is_quantized_weight(q["text_emb"]["table"])
    # ... norms/biases/positional tables stay float (scales would not
    # commute with the pos-sum; see the module docstring)
    flat = jax.tree_util.tree_leaves_with_path(q)
    for path, leaf in flat:
        s = jax.tree_util.keystr(path)
        if "pos" in s or "norm" in s or "/b" in s.replace("'", ""):
            assert leaf.dtype != jnp.int8, s
    # idempotent: quantizing twice is a no-op, not a re-round
    q2 = quant.quantize_tree(q, "int8")
    for (p1, l1), (_, l2) in zip(
            jax.tree_util.tree_leaves_with_path(q),
            jax.tree_util.tree_leaves_with_path(q2)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2),
                                      err_msg=jax.tree_util.keystr(p1))


def test_fp8_quantize_or_gated():
    w = jax.random.normal(jax.random.PRNGKey(4), (16, 8), jnp.float32)
    if quant.fp8_dtype() is None:
        with pytest.raises(ValueError, match="fp8"):
            quant.quantize_weight(w, "fp8")
    else:
        q = quant.quantize_weight(w, "fp8")
        deq = np.asarray(quant.maybe_dequant_weight(q))
        assert np.allclose(deq, np.asarray(w), rtol=0.15, atol=0.1)


# ---------------------------------------------------------------------------
# KV quantization: per-token scales, fused == disaggregated
# ---------------------------------------------------------------------------

def test_kv_round_trip_per_token():
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 16, 8), jnp.float32)
    qv, scale = quant.quantize_kv(x)
    assert qv.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    assert scale.dtype == quant.KV_SCALE_DTYPE
    deq = np.asarray(quant.dequantize_kv(qv, scale, jnp.float32))
    # int8 half-step (0.5*scale) + the bf16 rounding of the scale itself
    # (rel 2^-9, times up to 127 quantization steps)
    bound = np.max(np.asarray(scale).astype(np.float32)) * (0.5 + 127 / 512)
    assert np.max(np.abs(deq - np.asarray(x))) <= bound + 1e-6


def test_fused_equals_disaggregated_pool_writes(base):
    """quantize-at-scatter (fused engine) and quantize-at-handoff
    (disaggregated prefill worker) must produce the SAME pool bits — the
    property that lets the fleet compress on the prefill mesh."""
    cfg, params, text = base
    tcfg = cfg.transformer_config()
    n_pre = cfg.text_seq_len + 1
    block_size = 4
    ids = dalle_mod.remap_and_bos(cfg, jnp.asarray(text))
    emb = dalle_mod.embed_text_ids(params, cfg, ids)
    cache = tr.init_cache(tcfg, 1, dtype=jnp.float32)
    _, cache = tr.prefill(params["transformer"], tcfg, emb, cache)

    bps = tr.paged_blocks_per_seq(tcfg, block_size)
    bt = jnp.arange(1, bps + 1, dtype=jnp.int32)[None]

    pool_a = tr.init_paged_pool(tcfg, bps + 1, block_size, jnp.float32,
                                quantize="int8")
    pool_a = tr.write_prefill_to_pool(pool_a, bt, cache["layers"],
                                      n_pre, block_size)
    pool_b = tr.init_paged_pool(tcfg, bps + 1, block_size, jnp.float32,
                                quantize="int8")
    qlayers = quant.quantize_cache_layers(cache["layers"])
    pool_b = tr.write_prefill_to_pool(pool_b, bt, qlayers,
                                      n_pre, block_size)

    la, lb = pool_a["layers"], pool_b["layers"]
    entries = [(la, lb)] if isinstance(la, dict) else list(zip(la, lb))
    for ea, eb in entries:
        for k in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(np.asarray(ea[k]),
                                          np.asarray(eb[k]), err_msg=k)


# ---------------------------------------------------------------------------
# numerics parity through the real paged serving path
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: pool-write parity stays fast via
# test_fused_equals_disaggregated_pool_writes + the serving paged-parity tests
def test_greedy_parity_within_declared_budgets(base):
    cfg, params, text = base
    ref = quant.paged_greedy_logits(params, cfg, text)
    kv = quant.paged_greedy_logits(params, cfg, text, quantize_kv_mode="int8")
    m_kv = quant.greedy_parity_metrics(ref, kv)
    assert m_kv["greedy_logit_drift_rel"] <= quant.KV_PARITY_REL_BUDGET, m_kv

    full = quant.paged_greedy_logits(
        quant.quantize_tree(params, "int8"), cfg, text,
        quantize_kv_mode="int8")
    m_full = quant.greedy_parity_metrics(ref, full)
    assert m_full["greedy_logit_drift_rel"] <= quant.FULL_PARITY_REL_BUDGET, m_full
    # greedy tokens agree (tiny drift may flip a near-tie, hence not ==1.0
    # as a hard invariant — but most steps must match or serving quality
    # visibly degrades)
    assert m_kv["token_match_frac"] >= 0.95
    assert m_full["token_match_frac"] >= 0.9
    # the parity harness itself is deterministic
    m_self = quant.greedy_parity_metrics(ref, ref)
    assert m_self["greedy_logit_drift_abs"] == 0.0
    assert m_self["token_match_frac"] == 1.0


# ---------------------------------------------------------------------------
# pricing: the >=1.9x acceptance bars, measured not asserted
# ---------------------------------------------------------------------------

def test_kv_bytes_per_elem_and_pool_reduction():
    assert quant.kv_bytes_per_elem(None, 2, 64) == 2.0
    assert quant.kv_bytes_per_elem("int8", 2, 64) == 1.0 + 2.0 / 64
    with pytest.raises(ValueError):
        quant.kv_bytes_per_elem("int4", 2, 64)
    # realistic serving geometry (dim_head 64+): clears the 1.9x bar
    assert quant.kv_pool_reduction(64) >= 1.9
    assert quant.kv_pool_reduction(128) >= 1.9
    quant.assert_quantized_reduction("kv_pool", quant.kv_pool_reduction(64))
    # tiny test geometry honestly does NOT (the ledger still prices it
    # truthfully; only realistic geometry carries the acceptance assert)
    assert quant.kv_pool_reduction(8) < 1.9
    with pytest.raises(AssertionError):
        quant.assert_quantized_reduction("kv_pool", quant.kv_pool_reduction(8))


def test_weight_reduction_realistic_geometry():
    """>=1.9x at a serving-shaped model, via eval_shape (no giant init)."""
    big = tiny_cfg(dim=512, heads=8, dim_head=64, num_text_tokens=8192,
                   text_seq_len=64, num_image_tokens=8192, image_fmap_size=16)
    shapes = jax.eval_shape(
        lambda k: dalle_mod.init_dalle(k, big), jax.random.PRNGKey(0))
    qshapes = jax.eval_shape(lambda p: quant.quantize_tree(p, "int8"), shapes)
    red = quant.weight_reduction(shapes, qshapes)
    assert red >= 1.9, red
    quant.assert_quantized_reduction("weights", red)


def test_blocks_within_bytes_quantized_holds_more():
    from dalle_pytorch_tpu.serving.kv_pool import blocks_within_bytes
    cfg = tiny_cfg(dim_head=64, heads=2, dim=128).transformer_config()
    block_size = 8
    per_block_f = (2 * cfg.depth * cfg.heads * block_size * cfg.dim_head) * 2
    budget = 40 * per_block_f  # what a 40-block bf16 pool costs
    n_f = blocks_within_bytes(cfg, budget, block_size, itemsize=2)
    n_q = blocks_within_bytes(cfg, budget, block_size, itemsize=2,
                              kv_quant="int8")
    assert n_f == 39  # -1: block 0 is the reserved trash block
    assert n_q >= int(1.9 * n_f)  # the bytes buy ~1.94x the blocks


# ---------------------------------------------------------------------------
# checkpoint + registry seams
# ---------------------------------------------------------------------------

def test_quantized_tree_checkpoint_round_trip(base, tmp_path):
    from dalle_pytorch_tpu.training.checkpoint import (
        load_checkpoint, save_checkpoint)
    cfg, params, _ = base
    q = quant.quantize_tree(params, "int8")
    path = str(tmp_path / "q.npz")
    save_checkpoint(path, {"weights": q}, {"quantization": {"weights": "int8"}})
    trees, meta = load_checkpoint(path)
    assert meta["quantization"] == {"weights": "int8"}
    loaded = trees["weights"]
    assert quant.weight_quant_kind(loaded) == "int8"
    for (p1, l1), (_, l2) in zip(
            jax.tree_util.tree_leaves_with_path(q),
            jax.tree_util.tree_leaves_with_path(loaded)):
        assert l1.dtype == l2.dtype, jax.tree_util.keystr(p1)
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2),
                                      err_msg=jax.tree_util.keystr(p1))


def test_registry_places_scales_beside_blocks():
    from dalle_pytorch_tpu.parallel.registry import default_registry
    reg = default_registry()
    axes = {"tp": 4, "dp": 2}
    # column-parallel blocks shard over tp on the out dim; their per-out-
    # channel scales shard over tp too (each rank holds its columns' scales)
    spec = reg.resolve("transformer/layers/0/attn/qkv/w/qvalue",
                       (128, 384), axes)
    assert "tp" in tuple(spec), spec
    assert tuple(reg.resolve("transformer/layers/0/attn/qkv/w/scale",
                             (384,), axes)) == ("tp",)
    # row-parallel blocks shard the IN dim; every rank computes all output
    # columns, so their scales replicate
    assert tuple(reg.resolve("transformer/layers/0/ff/w2/w/scale",
                             (128,), axes)) in ((), (None,))


def test_dequant_overhead_accounting():
    cfg = tiny_cfg().transformer_config()
    none = quant.dequant_overhead_flops(cfg, None, None, slots=1)
    assert none["dequant_flops_per_step"] == 0.0
    both = quant.dequant_overhead_flops(cfg, "int8", True, slots=2,
                                        emb_rows=100)
    assert both["dequant_flops_per_step"] > 0
    assert 0.0 < both["dequant_frac_of_step"] < 1.0


# ---------------------------------------------------------------------------
# offline quantizer tool
# ---------------------------------------------------------------------------

def test_tools_quantize_round_trip(base, tmp_path):
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import quantize as qt
    from dalle_pytorch_tpu.training.checkpoint import (
        load_checkpoint, save_checkpoint)

    cfg, params, _ = base
    src = str(tmp_path / "plain.npz")
    dst = str(tmp_path / "int8.npz")
    save_checkpoint(src, {"weights": params}, {"step": 7})

    assert qt.main([src, "--dry_run"]) == 0
    assert not (tmp_path / "int8.npz").exists()
    # refuse absurd floors (tiny geometry cannot reach 5x), and refuse
    # writing without --out
    assert qt.main([src, "--require_reduction", "5.0"]) == 2
    assert qt.main([src]) == 2
    assert qt.main([src, "--out", src]) == 2

    assert qt.main([src, "--out", dst, "--require_reduction", "1.5"]) == 0
    trees, meta = load_checkpoint(dst)
    assert meta["quantization"] == {"weights": "int8"}
    assert meta["step"] == 7  # original meta preserved
    loaded = trees["weights"]
    assert quant.weight_quant_kind(loaded) == "int8"
    # dequantized weights approximate the originals (int8 half-step bound
    # checked leaf-exactly above; here a coarse sanity on the whole tree)
    deq = quant.dequantize_tree(loaded)
    w0 = np.asarray(params["logits_linear"]["w"])
    d0 = np.asarray(deq["logits_linear"]["w"])
    assert np.allclose(w0, d0, atol=float(np.abs(w0).max()) / 127 + 1e-6)
    # quantizing twice is refused, not silently re-rounded
    assert qt.main([dst, "--out", str(tmp_path / "x.npz")]) == 1


def test_tools_quantize_drops_optimizer_state(base, tmp_path, capsys):
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import optax
    import quantize as qt
    from dalle_pytorch_tpu.training.checkpoint import (
        TreeBundle, load_checkpoint, save_checkpoint)

    cfg, params, _ = base
    src = str(tmp_path / "train.npz")
    dst = str(tmp_path / "serve_int8.npz")
    save_checkpoint(src, {"weights": params,
                          "opt_state": optax.adam(1e-3).init(params)},
                    {"global_step": 5})
    # the round trip that bites: optax node types live outside this repo, so
    # the reloaded opt_state is a TreeBundle the v3 format cannot re-encode —
    # quantize must drop it rather than pickle it into an unloadable file
    trees, _ = load_checkpoint(src)
    assert isinstance(trees["opt_state"], TreeBundle)

    assert qt.main([src, "--out", dst]) == 0
    assert "dropping opt_state" in capsys.readouterr().out

    trees, meta = load_checkpoint(dst)  # must not raise (no pickled leaves)
    assert "opt_state" not in trees
    assert quant.weight_quant_kind(trees["weights"]) == "int8"
    assert meta["quantization"] == {"weights": "int8"}
    assert meta["global_step"] == 5
