"""A Laguna trunk (`gated_full` layers of 6 query heads under a YaRN rotary
and `gated_window` layers of 8 under the default one over a causal band, both
over 2 key/value heads with a per-head output gate; a leading dense SwiGLU
layer; a softmax router over 32 experts, top-8 times 2.5, of which 4 are held,
beside a sigmoid-gated shared expert) in the DALL-E stream, on the CPU at a
small size with seeded random weights: the system against the plain float32
reference (benchmark/reference/laguna_reference.py), the expert layer's shares
against the uncut layer, the band in the flash kernels, the YaRN table at the
published numbers, the gate's shape, the counters a traced step leaves, the
configuration's file and every entry point that must refuse the window kind.

The size keeps the ratios that matter: two head counts over one key/value
count (groups of 3 and 4, as 48 / 8 and 64 / 8), half of each head rotated in
the full kind and all of it in the window kind, a window of 8 keys over 24
positions, top-8 of 32 experts of which an eighth (4) is held, as published;
one dense layer + three window + one full layer.

Tolerances, and why: in float32 the system and the reference compute the same
mathematics in different orders (sorted grouped products against a dense loop,
one batched softmax against blocks of rows), measured 6e-7 on logits of order
2: LOGITS_ATOL 2e-5, LOSS_RTOL 1e-5 and GRAD_RTOL 1e-4 of each leaf's largest
entry, the bounds tests/test_glm_trunk.py holds its block to.  The band in the
kernels is held bit for bit: the compacted and the dense grid visit the same
live tiles in the same order (tests/test_flash_compact.py).
"""
import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import build  # noqa: E402
from benchmark.reference import laguna_reference as ref  # noqa: E402
from dalle_pytorch_tpu.kernels import sparse_index as si  # noqa: E402
from dalle_pytorch_tpu.kernels.flash_attention import flash_attention  # noqa: E402
from dalle_pytorch_tpu.models import dalle as dalle_mod  # noqa: E402
from dalle_pytorch_tpu.models import gated_layers, moe  # noqa: E402
from dalle_pytorch_tpu.models import transformer as tr  # noqa: E402
from dalle_pytorch_tpu.ops.attention import attend  # noqa: E402
from dalle_pytorch_tpu.ops.masks import block_live_np, window_mask_np  # noqa: E402

LOGITS_ATOL = 2e-5
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4

TINY = ROOT / "benchmark" / "rehearsal" / "tiny_laguna.json"
SIZES = json.loads(TINY.read_text())
PUBLISHED = json.loads((ROOT / "benchmark" / "configs" / "laguna_xs2_ep8_d5.json").read_text())


@pytest.fixture(scope="module")
def model():
    cfg = build.dalle_config(SIZES)
    return cfg, build.make_weights(cfg, 2**31 + 43, jnp.float32)


def _sequence(cfg, n_codes, pad_tail=2):
    rng = np.random.default_rng(1)
    text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,))
    text[cfg.text_seq_len - pad_tail:] = 0
    return text.astype(np.int32), rng.integers(0, cfg.num_image_tokens, (n_codes,)).astype(np.int32)


# ------------------------------------------------ the system against the reference
@pytest.mark.parametrize("n_codes,execution", [(16, "sequential"), (5, "sequential"), (16, "remat")])
def test_logits_match_the_reference(model, n_codes, execution):
    cfg, params = model
    cfg = dataclasses.replace(cfg, execution=execution)
    text, codes = _sequence(cfg, n_codes)
    got = jax.jit(lambda p: dalle_mod.forward(p, cfg, jnp.asarray(text)[None],
                                              jnp.asarray(codes)[None]))(params)
    want = np.asarray(jax.jit(lambda p: ref.forward_logits(p, SIZES, text, codes))(params))
    got = np.asarray(got[0])
    n = min(1 + cfg.text_seq_len + n_codes, cfg.total_seq_len)  # <bos> + text + codes, cut to the sequence
    assert got.shape == want.shape == (n, cfg.total_tokens)
    ok = np.isfinite(want)
    np.testing.assert_allclose(got[ok], want[ok], atol=LOGITS_ATOL)
    assert (got[~ok] < -1e30).all(), "the program forbids what the reference forbids"


def test_loss_and_every_gradient_leaf_match_the_reference(model):
    cfg, params = model
    text, codes = _sequence(cfg, cfg.image_seq_len)
    t, c = jnp.asarray(text)[None], jnp.asarray(codes)[None]
    got, g_sys = jax.jit(jax.value_and_grad(
        lambda p: dalle_mod.forward(p, cfg, t, c, return_loss=True)))(params)
    want, g_ref = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, SIZES, text, codes)))(params)
    assert float(got) == pytest.approx(float(want), rel=LOSS_RTOL)
    flat_sys = jax.tree_util.tree_leaves_with_path(g_sys)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    assert len(flat_sys) == len(flat_ref) and len(flat_sys) > 60
    for (path, a), b in zip(flat_sys, flat_ref):
        name, scale = jax.tree_util.keystr(path), float(jnp.abs(b).max())
        if name.endswith("['logits_linear']['b']") or "text_emb" in name or "image_emb" in name:
            scale = max(scale, 1e-3)  # rows no token of the sequence reaches are zero on both sides
        assert scale > 0, f"{name}: the reference's gradient is all zero"
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=GRAD_RTOL * scale, err_msg=name)


def test_the_window_is_a_band_and_the_full_kind_sees_every_earlier_key():
    """A key outside the band moves nothing of a window layer's output at
    that position; the full kind sees it."""
    tcfg = build.dalle_config(SIZES).transformer_config()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, tcfg.dim))
    later = x.at[:, 2].add(1.0)  # position 2: outside the band of position 10 (10 - 2 >= 8), inside 9's
    for kind, moved in (("gated_window", (9,)), ("gated_full", (9, 10, 23))):
        p = gated_layers.init_gated_full(jax.random.PRNGKey(2), tcfg, kind=kind)
        a = gated_layers.gated_full_attention(p, tcfg, x, kind=kind)[0]
        b = gated_layers.gated_full_attention(p, tcfg, later, kind=kind)[0]
        change = np.abs(np.asarray(b - a)).max(axis=-1)
        assert all(change[i] > 1e-4 for i in moved), kind
        np.testing.assert_allclose(np.asarray(a[:2]), np.asarray(b[:2]), atol=0)
        if kind == "gated_window":
            assert change[10:].max() == 0, "keys 8 or more back are outside the window"
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref.attention(SIZES, p, x[0], kind)),
                                   atol=2e-6)


# ---------------------------------------------------------------- the band in the kernels
BAND_N, BAND_BLOCK, BAND_WINDOW = 768, 128, 200


def _band_run(grid, q, k, v, do):
    mask = window_mask_np(BAND_N, BAND_WINDOW)

    def loss(q, k, v):
        out = flash_attention(q, k, v, mask=mask, causal=True, block_q=BAND_BLOCK,
                              block_k=BAND_BLOCK, grid=grid)
        return jnp.sum(out * do), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (np.asarray(out),) + tuple(np.asarray(g) for g in grads)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_band_compacted_grid_is_the_dense_grid_bit_for_bit(dtype):
    """Forward, dq and dk/dv of a band of 200 keys over a 6 x 6 grid of
    128-tiles: the band kills 6 of the 21 tiles inside the causal triangle,
    and the compacted grid (what `grid="auto"` takes) runs the 15 live ones
    only, bit-exact against the dense grid that `pl.when`-skips the rest."""
    live = si.build_compacted_tables(
        block_live_np(window_mask_np(BAND_N, BAND_WINDOW), BAND_BLOCK, BAND_BLOCK), BAND_BLOCK, BAND_BLOCK)
    causal = si.build_compacted_tables(np.ones((6, 6), bool), BAND_BLOCK, BAND_BLOCK)
    assert si.live_tile_counts(live) == (15, 15) and si.live_tile_counts(causal) == (21, 21)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, (1, 1, BAND_N, 32), jnp.float32).astype(dtype) for kk in ks)
    dense, compact = _band_run("dense", q, k, v, do), _band_run("compact", q, k, v, do)
    for a, b in zip(dense, compact):
        np.testing.assert_array_equal(a, b)
    if dtype == jnp.float32:  # and both are the masked softmax the band defines
        band = jnp.asarray(window_mask_np(BAND_N, BAND_WINDOW))[None, None]
        want = attend(q * 32 ** -0.5, k, v, mask=band)
        np.testing.assert_allclose(compact[0], np.asarray(want), atol=2e-6)


def test_the_published_band_leaves_30_live_steps_of_121():
    n, window, tile = 4224, PUBLISHED["window"], 384
    live = block_live_np(window_mask_np(n, window), tile, tile)
    tables = si.build_compacted_tables(live, tile, tile)
    assert si.live_tile_counts(tables) == (30, 30)
    assert si.live_tile_counts(si.build_compacted_tables(np.ones((11, 11), bool), tile, tile)) == (66, 66)
    assert int(window_mask_np(n, window).sum()) == 2_031_872  # the (query, key) pairs a head requires


# ------------------------------------------------------------------------ rotary
def test_the_yarn_table_at_the_published_numbers_is_the_equations():
    tcfg = build.dalle_config(PUBLISHED).transformer_config()
    rot, theta = 64, 500000.0

    def c(b):
        return 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(theta))

    low, high = math.floor(c(64)), math.ceil(c(1))
    assert (low, high) == (5, 16)
    i = np.arange(32, dtype=np.float64)
    extrap = theta ** (-2 * i / rot)
    r = np.clip((i - low) / (high - low), 0, 1)
    inv = extrap * (1 - r) + extrap / 64 * r
    n = 4224
    got = gated_layers.partial_rotary_angles(tcfg, n)
    np.testing.assert_allclose(got, (np.arange(n)[:, None] * inv[None, :]).astype(np.float32), rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(PUBLISHED)[0], inv, rtol=1e-12)
    factor = gated_layers.rotary_attention_factor(tcfg)
    assert factor == pytest.approx(1.4158883, abs=1e-7) == pytest.approx(0.1 * math.log(64) + 1, abs=1e-7)
    # the window kind: the default rotary on all 128 channels, theta 10,000, no factor
    window = gated_layers.partial_rotary_angles(tcfg, 8, "gated_window")
    np.testing.assert_allclose(window[3], 3 * 10000.0 ** (-np.arange(0, 128, 2) / 128), rtol=1e-6)
    assert gated_layers.rotary_attention_factor(tcfg, "gated_window") == 1.0
    # the factor scales the rotated channels of q and k only
    t = jnp.ones((1, 2, 1, 128))
    turned = gated_layers.apply_partial_rotary(jnp.zeros((2, 32)), t, factor)
    np.testing.assert_allclose(np.asarray(turned[0, 0, 0]), [factor] * 64 + [1.0] * 64, rtol=1e-6)


# -------------------------------------------------------------------- the gate
@pytest.mark.parametrize("kind,heads", [("gated_full", 48), ("gated_window", 64)])
def test_the_gate_is_one_scalar_a_head(kind, heads):
    """At the published widths: W_q is [q (H x 128) ; gate (H)].  At the tiny
    size: moving one head's gate column moves that head's 16 output channels
    and no other's."""
    big = build.dalle_config(PUBLISHED).transformer_config()
    shapes = jax.eval_shape(lambda k: gated_layers.init_gated_full(k, big, kind=kind), jax.random.PRNGKey(0))
    assert shapes["q"]["w"].shape == (2048, heads * 128 + heads)
    assert shapes["out"]["w"].shape == (heads * 128, 2048)
    assert shapes["k"]["w"].shape == shapes["v"]["w"].shape == (2048, 8 * 128)
    tcfg = build.dalle_config(SIZES).transformer_config()
    h, dh = gated_layers.attn_heads(tcfg, kind), tcfg.dim_head
    p = gated_layers.init_gated_full(jax.random.PRNGKey(1), tcfg, kind=kind)
    p = dict(p, out={"w": jnp.eye(h * dh)})  # W_o = identity: the gated heads themselves
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, tcfg.dim))
    moved = dict(p, q={"w": p["q"]["w"].at[:, h * dh + 1].add(1.0)})  # head 1's gate column
    change = np.abs(np.asarray(gated_layers.gated_full_attention(moved, tcfg, x, kind=kind)
                               - gated_layers.gated_full_attention(p, tcfg, x, kind=kind)))
    per_head = change.reshape(1, 12, h, dh).max(axis=(0, 1, 3))
    assert per_head[1] > 1e-3 and np.delete(per_head, 1).max() == 0


# -------------------------------------------------------------------- the router
def _moe_cfg(**kw):
    base = dict(dim=32, depth=1, seq_len=16, moe_experts=32, moe_top_k=8, moe_ff_dim=24,
                moe_shared_ff_dim=24, moe_router="softmax", moe_routed_scale=2.5, moe_shared_gated=True)
    base.update(kw)
    return tr.TransformerConfig(**base)


def _sizes_of(cfg):
    return {k: getattr(cfg, k) for k in ("moe_experts", "moe_top_k", "moe_experts_held",
                                         "moe_first_expert", "moe_routed_scale")}


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Eight ranks of 4 experts each: their partial outputs, with the gated
    shared expert (which every rank computes alike) counted once, are the
    uncut reference's layer; the softmax weights of a token sum to 2.5."""
    whole_cfg = _moe_cfg()
    whole = moe.init_moe(jax.random.PRNGKey(0), whole_cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    x2 = x.reshape(-1, 32)
    want = ref.moe(_sizes_of(whole_cfg), whole, x2)
    shared = jax.nn.sigmoid(x2 @ whole["shared"]["gate"]["w"]) * ref.dense_ff(whole["shared"], x2)
    total = 0.0
    for first in range(0, 32, 4):
        cfg = _moe_cfg(moe_experts_held=4, moe_first_expert=first)
        share = dict(whole, experts=jax.tree_util.tree_map(lambda w: w[first:first + 4], whole["experts"]))
        out, _ = moe.moe_feed_forward(share, cfg, x)
        np.testing.assert_allclose(np.asarray(out.reshape(-1, 32)),
                                   np.asarray(ref.moe(_sizes_of(cfg), share, x2)), atol=2e-6)
        total = total + out.reshape(-1, 32) - shared
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=5e-6)
    w, _ = moe.route(whole["router"], whole_cfg, x2)
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.5, rtol=1e-6)


# --------------------------------------------------- what a traced step counts
def test_a_traced_forward_counts_its_layers_and_the_band_steps():
    """The published trunk's forward TRACED with the flash path (nothing runs):
    three window layers, two full ones, three window flash calls of 30 live
    steps each at 384-tiles."""
    from dalle_pytorch_tpu.observability import metrics as obs_metrics

    cfg = dataclasses.replace(build.dalle_config(PUBLISHED), attn_kernel="flash")
    params = jax.eval_shape(lambda k: dalle_mod.init_dalle(k, cfg), jax.random.PRNGKey(0))

    def totals():
        snap = obs_metrics.REGISTRY.snapshot(reset_window=False)
        names = ("train/window_layers", "train/full_attn_layers", "kernels/flash_window_calls",
                 "kernels/flash_window_live_steps", "kernels/flash_tile_384x384")
        return {n: snap.get(n, {}).get("total", 0) for n in names}

    before = totals()
    t = jax.ShapeDtypeStruct((1, cfg.text_seq_len), jnp.int32)
    c = jax.ShapeDtypeStruct((1, cfg.image_seq_len), jnp.int32)
    jax.eval_shape(lambda p, t, c: dalle_mod.forward(p, cfg, t, c, return_loss=True), params, t, c)
    grew = {n: v - before[n] for n, v in totals().items()}
    assert grew == {"train/window_layers": 3, "train/full_attn_layers": 2,
                    "kernels/flash_window_calls": 3, "kernels/flash_window_live_steps": 90,
                    "kernels/flash_tile_384x384": 5}


# ------------------------------------------------------------------- refusals
@pytest.mark.parametrize("what", ["init_cache", "prefill", "decode_step", "init_paged_pool",
                                  "paged_decode_step", "GenerationEngine"])
def test_serving_refuses_the_window_kind_by_name(what):
    """A trunk of window layers and nothing else of the hybrid block (no
    routed experts, no dense layer): what refuses is the kind itself."""
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    cfg = dataclasses.replace(build.dalle_config(SIZES), moe_experts=0, dense_layers=0,
                              attn_types=("gated_window",))
    tcfg = cfg.transformer_config()
    assert tcfg.unserved and tcfg.hybrid and not tcfg.recurrent
    x = jnp.zeros((1, 1, cfg.dim))
    calls = {
        "init_cache": lambda: tr.init_cache(tcfg, 1),
        "prefill": lambda: tr.prefill({}, tcfg, x, {}),
        "decode_step": lambda: tr.decode_step({}, tcfg, x, {}),
        "init_paged_pool": lambda: tr.init_paged_pool(tcfg, 4, 8),
        "paged_decode_step": lambda: tr.paged_decode_step(
            {}, tcfg, x, {}, {}, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32), 8),
        "GenerationEngine": lambda: GenerationEngine(
            build.make_weights(cfg, 1, jnp.float32), cfg,
            engine_cfg=EngineConfig(num_slots=2, block_size=8)),
    }
    with pytest.raises(NotImplementedError, match="training path only") as e:
        calls[what]()
    assert what in str(e.value) and "'gated_window'" in str(e.value)
    assert "gated_full / gated_window / mla layers" in str(e.value)


def test_a_window_layer_needs_a_window_and_the_gate_a_known_form():
    tcfg = dataclasses.replace(build.dalle_config(SIZES).transformer_config(), window=0)
    with pytest.raises(ValueError, match="window >= 1"):
        gated_layers.init_gated_full(jax.random.PRNGKey(0), tcfg, kind="gated_window")
    with pytest.raises(ValueError, match="attn_gate"):
        gated_layers.init_gated_full(jax.random.PRNGKey(0), dataclasses.replace(tcfg, attn_gate="per_row"))


# --------------------------------------------------- the configuration's file
def test_the_cells_configuration_states_the_source_and_the_program_reads_the_same_model():
    sizes = PUBLISHED
    cut = {"num_experts": 32, "vocab_size": 12544}
    published = {"num_experts": 256, "vocab_size": 100352, "num_hidden_layers": 40}
    for key, value in published.items():
        assert sizes["published"][key] == value
        assert sizes[key] == cut.get(key, value), key
    assert set(sizes["reduced"]) == {"depth", "num_experts", "vocab_size"}
    assert sizes["source"] == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    same = {"hidden_size": "dim", "num_attention_heads": "heads", "num_key_value_heads": "kv_heads",
            "head_dim": "dim_head", "intermediate_size": "dense_ff_dim", "sliding_window": "window",
            "moe_intermediate_size": "moe_ff_dim", "num_experts_per_tok": "moe_top_k",
            "moe_routed_scaling_factor": "moe_routed_scale", "rms_norm_eps": "norm_eps",
            "shared_expert_intermediate_size": "moe_shared_ff_dim", "num_experts": "moe_experts_held"}
    for source_key, program_key in same.items():
        assert sizes[source_key] == sizes[program_key], (source_key, program_key)
    full, window = sizes["rope_parameters"]["full_attention"], sizes["rope_parameters"]["sliding_attention"]
    assert (sizes["rotary_theta"], sizes["partial_rotary_factor"]) == (full["rope_theta"], full["partial_rotary_factor"])
    assert (sizes["rope_yarn_factor"], sizes["rope_yarn_original_max"], sizes["rope_yarn_beta_fast"],
            sizes["rope_yarn_beta_slow"]) == (
        full["factor"], full["original_max_position_embeddings"], full["beta_fast"], full["beta_slow"])
    # the published attention factor is source data: the program derives it from the factor
    assert gated_layers.rotary_attention_factor(build.dalle_config(sizes)) == pytest.approx(
        full["attention_factor"], rel=1e-15)
    assert (sizes["window_rotary_theta"], 1) == (window["rope_theta"], window["partial_rotary_factor"])
    period = ["gated_full" if t == "full_attention" else "gated_window" for t in sizes["layer_types"][:4]]
    assert sizes["attn_types"] == period and sizes["layer_types"][4:8] == sizes["layer_types"][:4]
    heads = sizes["num_attention_heads_per_layer"]
    assert (heads[0], heads[1]) == (sizes["heads"], sizes["window_heads"]) == (48, 64)
    assert sizes["mlp_layer_types"][:2] == ["dense", "sparse"] and sizes["dense_layers"] == 1
    cfg = build.dalle_config(sizes)
    assert cfg.total_tokens == sizes["vocab_size"] and cfg.moe_experts == 256
    assert cfg.depth == 5 and cfg.total_seq_len == 4224 and cfg.attn_gate == "per_head"
    shapes = jax.eval_shape(lambda k: dalle_mod.init_dalle(k, cfg), jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n_params == 691_645_952  # the deployment's 691.6 M: 11.07 GB at 16 bytes


# -------------------------------------------------------- the normal entry point
def test_train_dalle_trains_the_block_from_the_command_line(tmp_path, capsys):
    import re

    from dalle_pytorch_tpu.cli import train_dalle

    train_dalle.main(["--dummy_run", "8", "--batch_size", "1", "--block_json", str(TINY), "--telemetry", "off",
                      "--log_every_n_steps", "1", "--save_every_n_steps", "0",
                      "--sample_every_n_steps", "0", "--learning_rate", "3e-3",
                      "--dalle_output_file_name", str(tmp_path / "d")])
    lines = [l for l in capsys.readouterr().out.splitlines() if re.match(r"\[\d+\] loss=", l)]
    assert len(lines) >= 8
    losses = [float(dict(kv.split("=") for kv in l.split()[1:])["loss"]) for l in lines]
    assert np.isfinite(losses).all() and np.mean(losses[-3:]) < np.mean(losses[:3])
