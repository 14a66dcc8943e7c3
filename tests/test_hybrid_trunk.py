"""A hybrid trunk (gated_delta / gated_full layers, routed experts with a shared
expert, zero-centred RMSNorm) in the DALL-E stream, on the CPU at a small size
with seeded random weights: the system against the plain float32 reference,
the chunked delta rule against its recurrence, the expert layer's shares
against the uncut layer, and every entry point that must refuse the block.

Tolerances, and why.  Everything here is float32 on the CPU, the system and
the reference compute the same mathematics in different orders (chunked scan
against recurrence, sorted grouped products against a dense loop), so what
separates them is float32 reduction order: 1e-7 relative a product, a few
1e-6 after four layers.  LOGITS_ATOL 2e-5 on logits of order 1 is ten times
that (the same bound tests/benchmark/test_bench_reference.py holds the DALL-E
block to); LOSS_RTOL 1e-5 likewise; GRAD_RTOL 1e-3 of each leaf's largest
entry, because a gradient goes through every layer twice and the routed
weights' gradients are sums of few terms (measured 4e-5); RULE_ATOL 2e-6 on
delta-rule outputs of order 0.3 (measured 3e-7).  A bfloat16 pass anywhere
(2**-8 = 4e-3 a product) fails each of them by two orders of magnitude.
"""
import dataclasses
import json
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
from benchmark.reference import qwen3_next_reference as ref  # noqa: E402
from dalle_pytorch_tpu.models import dalle as dalle_mod  # noqa: E402
from dalle_pytorch_tpu.models import gated_layers, moe  # noqa: E402
from dalle_pytorch_tpu.models import transformer as tr  # noqa: E402
from dalle_pytorch_tpu.ops.delta_rule import gated_delta_rule  # noqa: E402

LOGITS_ATOL = 2e-5
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-3
RULE_ATOL = 2e-6

SIZES = json.loads((ROOT / "benchmark" / "rehearsal" / "tiny_q3n.json").read_text())


@pytest.fixture(scope="module")
def model():
    cfg = build.dalle_config(SIZES)
    return cfg, build.make_weights(cfg, 2**31 + 5, jnp.float32)


def _sequence(cfg, n_codes, pad_tail=2):
    rng = np.random.default_rng(1)
    text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,))
    text[cfg.text_seq_len - pad_tail:] = 0
    return text.astype(np.int32), rng.integers(0, cfg.num_image_tokens, (n_codes,)).astype(np.int32)


# ------------------------------------------------ the system against the reference
@pytest.mark.parametrize("n_codes,execution", [(16, "sequential"), (5, "sequential"),
                                              (16, "remat")])
def test_logits_match_the_reference(model, n_codes, execution):
    cfg, params = model
    cfg = dataclasses.replace(cfg, execution=execution)
    text, codes = _sequence(cfg, n_codes)
    want = np.asarray(jax.jit(lambda p: ref.forward_logits(p, SIZES, text, codes))(params))
    got = np.asarray(jax.jit(lambda p: dalle_mod.forward(
        p, cfg, jnp.asarray(text)[None], jnp.asarray(codes)[None])[0])(params))
    ok = np.isfinite(want)
    assert got.shape == want.shape
    np.testing.assert_allclose(got[ok], want[ok], atol=LOGITS_ATOL)
    assert (got[~ok] < -1e30).all(), "the program forbids what the reference forbids"


def test_loss_and_gradients_match_the_reference(model):
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
        scale = float(jnp.abs(b).max())
        assert scale > 0, f"{jax.tree_util.keystr(path)}: the reference's gradient is all zero"
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=GRAD_RTOL * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_batch_rows_are_independent_and_aux_is_reported(model):
    cfg, params = model
    text, codes = _sequence(cfg, cfg.image_seq_len)
    rng = np.random.default_rng(3)
    other = rng.integers(0, cfg.num_image_tokens, codes.shape).astype(np.int32)
    t = jnp.asarray(np.stack([text, text]))
    c = jnp.asarray(np.stack([codes, other]))
    logits, aux = jax.jit(lambda p: dalle_mod.forward(p, cfg, t, c, return_aux=True))(params)
    alone = jax.jit(lambda p: dalle_mod.forward(p, cfg, t[:1], c[:1]))(params)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(alone[0]), atol=LOGITS_ATOL)
    assert set(aux) == {"moe_pairs_here", "moe_load_max_over_mean", "moe_overflow_share"}
    assert float(aux["moe_overflow_share"]) == 0.0
    # 2 sequences x 24 positions x top-3 over 16 experts, 4 held: 36 pairs expected
    assert 0 < float(aux["moe_pairs_here"]) < 2 * 24 * 3
    assert float(aux["moe_load_max_over_mean"]) >= 1.0


# ------------------------------------------------------------ the delta rule
def _rule_inputs(n, h=3, dk=16, dv=24, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, n, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, n, dk)))
    v = jax.random.normal(ks[2], (b, h, n, dv))
    rate = jax.random.uniform(ks[3], (h,), minval=0.01, maxval=16.0)  # slow and fast heads
    g = -rate[None, :, None] * jax.nn.softplus(jax.random.normal(ks[4], (b, h, n)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, h, n)))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule (one sequence: (n, heads, ...)), over a batch."""
    seq = lambda a: jnp.moveaxis(a, 1, 2)  # (b, h, n, ...) -> (b, n, h, ...)
    out = jax.vmap(ref.delta_rule_recurrence)(seq(q), seq(k), seq(v), seq(jnp.exp(g)), seq(beta))
    return jnp.moveaxis(out, 2, 1)


@pytest.mark.parametrize("n,chunk", [(150, 64), (64, 64), (37, 16), (5, 64)])
def test_chunked_delta_rule_equals_the_recurrence(n, chunk):
    args = _rule_inputs(n)
    got, _ = gated_delta_rule(*args, chunk=chunk)
    want = _recurrence(*args)
    assert got.shape == want.shape == (2, 3, n, 24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=RULE_ATOL)


def test_chunked_delta_rule_gradients_equal_the_recurrences():
    args = _rule_inputs(100)
    g_chunk = jax.jit(jax.grad(lambda *a: (gated_delta_rule(*a)[0] ** 2).sum(),
                               argnums=(0, 1, 2, 3, 4)))(*args)
    g_rec = jax.jit(jax.grad(lambda *a: (_recurrence(*a) ** 2).sum(),
                             argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(g_chunk, g_rec):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=GRAD_RTOL * float(jnp.abs(b).max()))


# ------------------------------------------------ grouped-query heads, rotary
def test_grouped_query_expansion_serves_consecutive_query_heads():
    t = jnp.arange(2 * 3 * 2 * 4, dtype=jnp.float32).reshape(2, 3, 2, 4)
    out = gated_layers.expand_kv_heads(t, 6)
    assert out.shape == (2, 3, 6, 4)
    for h in range(6):
        np.testing.assert_array_equal(np.asarray(out[:, :, h]), np.asarray(t[:, :, h // 3]))


def test_partial_rotary_rotates_the_first_share_only_and_matches_the_reference(model):
    cfg, _ = model
    tcfg = cfg.transformer_config()
    n, dh = 11, tcfg.dim_head
    rot = int(dh * tcfg.partial_rotary_factor)
    assert rot == 4
    t = jax.random.normal(jax.random.PRNGKey(2), (1, n, 3, dh))
    got = gated_layers.apply_partial_rotary(jnp.asarray(gated_layers.partial_rotary_angles(tcfg, n)), t)
    np.testing.assert_array_equal(np.asarray(got[..., rot:]), np.asarray(t[..., rot:]))
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(t[:, 0]))  # position 0: no turn
    cos, sin = ref.rotary_tables(SIZES, n)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref._rotate_half(t[0], cos, sin)),
                               atol=1e-6)  # one multiply-add a channel
    # a rotation: each rotated pair keeps its length
    pair = lambda a, i: a[..., i] ** 2 + a[..., i + rot // 2] ** 2
    np.testing.assert_allclose(np.asarray(pair(got, 0)), np.asarray(pair(t, 0)), rtol=1e-5)


# ----------------------------------------------------------------- the experts
def _moe_cfg(**kw):
    base = dict(dim=32, depth=1, seq_len=8, moe_experts=16, moe_top_k=3, moe_ff_dim=24,
                moe_shared_ff_dim=24, norm="rmsnorm_zc", layer_scale=False)
    base.update(kw)
    return tr.TransformerConfig(**base)


def _sizes_of(cfg):
    return {"moe_experts": cfg.moe_experts, "moe_top_k": cfg.moe_top_k,
            "moe_experts_held": cfg.moe_held, "moe_first_expert": cfg.moe_first_expert}


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4: the routed parts of all the shares, plus
    the shared expert counted once, are the uncut reference's whole layer."""
    whole = _moe_cfg()
    params = moe.init_moe(jax.random.PRNGKey(0), whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    want = ref.moe(_sizes_of(whole), params, x.reshape(16, 32))
    shared_only = ref.moe(_sizes_of(whole), {**params, "experts": jax.tree_util.tree_map(
        jnp.zeros_like, params["experts"])}, x.reshape(16, 32))
    total = -3.0 * shared_only  # every share computes the shared expert: count it once
    pairs = 0.0
    for share in range(4):
        cfg = _moe_cfg(moe_experts_held=4, moe_first_expert=4 * share)
        part = {**params, "experts": jax.tree_util.tree_map(
            lambda w: w[4 * share:4 * share + 4], params["experts"])}
        out, stats = jax.jit(lambda p, cfg=cfg: moe.moe_feed_forward(p, cfg, x))(part)
        np.testing.assert_allclose(  # each share against the reference given the same share
            np.asarray(out.reshape(16, 32)), np.asarray(ref.moe(_sizes_of(cfg), part, x.reshape(16, 32))),
            atol=LOGITS_ATOL)
        total = total + out.reshape(16, 32)
        pairs += float(stats["moe_pairs_here"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=LOGITS_ATOL)
    assert pairs == 16 * 3, "every (token, expert) pair is computed by exactly one share"


def test_an_overloaded_expert_drops_nothing():
    """A router that sends every token to expert 5 first: its group holds all
    the tokens, and the output is still the reference's."""
    cfg = _moe_cfg(moe_experts_held=4, moe_first_expert=4)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    w = np.zeros((32, 16), np.float32)
    w[:, 5] = 50.0  # positive inputs below: logit 5 dominates for every token
    params = {**params, "router": {"w": jnp.asarray(w) + 0.01 * params["router"]["w"]}}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))) + 0.1
    out, stats = moe.moe_feed_forward(params, cfg, x)
    weights, ids = moe.route(params["router"], cfg, x.reshape(32, 32))
    assert (np.asarray(ids[:, 0]) == 5).all()
    assert float(stats["moe_load_max_over_mean"]) > 2.0
    assert float(stats["moe_pairs_here"]) >= 32
    np.testing.assert_allclose(np.asarray(out.reshape(32, 32)),
                               np.asarray(ref.moe(_sizes_of(cfg), params, x.reshape(32, 32))),
                               atol=LOGITS_ATOL)


_CLASSES = [(0, 1, 2), (4, 0, 1), (4, 5, 0), (4, 5, 6)]  # a token class's top-3, in order


def _routed_to(counts, held, tokens=128, experts=16):
    """A router and inputs that send `counts[j]` tokens to the experts of
    class j, of which [4, 4 + held) are held: token classes one-hot in x's
    first four channels, a router row per class, logits 6, 5.5, 5 against
    0.01s, so no choice is near a tie and gradients flow.  Also the pairs
    that makes here."""
    cfg = _moe_cfg(moe_experts=experts, moe_experts_held=held, moe_first_expert=4)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    w = np.zeros((32, experts), np.float32)
    for j, chosen in enumerate(_CLASSES):
        w[j, list(chosen)] = (6.0, 5.5, 5.0)
    params = {**params, "router": {"w": jnp.asarray(w) + 0.01 * params["router"]["w"]}}
    x = 0.3 * np.asarray(jax.random.normal(jax.random.PRNGKey(1), (tokens, 32)))
    x[:, :4] = 0.0
    kinds = np.repeat(np.arange(4), [tokens - sum(counts[1:]), *counts[1:]])
    x[np.arange(tokens), np.random.default_rng(0).permutation(kinds)] = 1.0
    pairs = sum(n * sum(4 <= e < 4 + held for e in chosen) for n, chosen in zip(counts[1:], _CLASSES[1:]))
    return cfg, params, jnp.asarray(x), pairs


# Top-3.  128 tokens, 4 of 16 experts held: 96 of 384 pairs expected, chunks of
# 256 rows.  512 tokens, 1 of 32 held: 48 of 1,536, chunks of 128 rows.
@pytest.mark.parametrize("tokens,experts,held,counts,pairs,chunks", [
    (128, 16, 4, (0, 20, 10, 5), 55, 1),     # well under a chunk
    (128, 16, 4, (0, 1, 0, 85), 256, 1),     # the last row of the chunk is a pair
    (128, 16, 4, (0, 2, 0, 85), 257, 2),     # one pair too many: a second chunk for it
    (128, 16, 4, (0, 0, 0, 128), 384, 2),    # every pair of every token lies here
    (512, 32, 1, (0, 128, 0, 0), 128, 1),    # a thirty-second of the experts: a twelfth of the rows
    (512, 32, 1, (0, 200, 200, 112), 512, 4),  # ... and the held expert in every token's top-3
    (512, 32, 1, (512, 0, 0, 0), 0, 0),      # ... and in no token's
], ids=["under", "exactly", "one_over", "every_pair", "small_share", "small_share_busiest",
        "small_share_idle"])
def test_the_pair_buffer_is_bounded_and_its_overflow_is_exact(monkeypatch, tokens, experts, held,
                                                              counts, pairs, chunks):
    cfg, params, x, made = _routed_to(counts, held, tokens, experts)
    rows = moe.pair_rows(cfg, tokens)
    assert made == pairs and rows == {4: 256, 1: 128}[held] < tokens * 3
    sizes = _sizes_of(cfg)
    cot = jax.random.normal(jax.random.PRNGKey(2), (tokens, 32))
    ran = []  # the first row of every chunk that RAN, forward and backward
    chunk_terms = moe._chunk_terms

    def noted(cfg, rows, tally, ranking, first_row, *args):
        jax.debug.callback(lambda r: ran.append(int(r)), first_row)
        return chunk_terms(cfg, rows, tally, ranking, first_row, *args)

    monkeypatch.setattr(moe, "_chunk_terms", noted)

    def system(p, x):
        out, stats = moe.moe_feed_forward(p, cfg, x[None])
        return jnp.sum(out[0] * cot), (out[0], stats)

    (_, (out, stats)), g_sys = jax.block_until_ready(
        jax.jit(jax.value_and_grad(system, argnums=(0, 1), has_aux=True))(params, x))
    jax.effects_barrier()
    assert sorted(ran) == sorted(2 * [j * rows for j in range(chunks)])
    assert float(stats["moe_pairs_here"]) == pairs
    assert float(stats["moe_overflow_share"]) == (chunks > 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.moe(sizes, params, x)), atol=LOGITS_ATOL)
    g_ref = jax.jit(jax.grad(lambda p, x: jnp.sum(ref.moe(sizes, p, x) * cot), argnums=(0, 1)))(params, x)
    flat_sys = jax.tree_util.tree_leaves_with_path(g_sys)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    assert len(flat_sys) == len(flat_ref) == 9  # x, the router, three expert and four shared leaves
    for (path, a), b in zip(flat_sys, flat_ref):
        scale = float(jnp.abs(b).max())
        assert scale > 0 or not pairs, f"{jax.tree_util.keystr(path)}: the reference's gradient is all zero"
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=GRAD_RTOL * scale,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("held,tokens,rows", [
    (16, 128, 384),     # every expert held: every pair has its row, one chunk
    (16, 10, 128),      # ... padded to the kernel's row tile
    (4, 128, 256),      # 2 x 96 expected pairs, in whole tiles
    (4, 8, 24 + 104),   # never more than every pair, padded
    (1, 1024, 384),     # 2 x 192
    (1, 4096, 1536),    # 2 x 768
])
def test_pair_rows_follow_the_share_of_the_experts_held(held, tokens, rows):
    cfg = _moe_cfg(moe_experts_held=held)
    assert moe.pair_rows(cfg, tokens) == rows
    assert moe.pair_rows(cfg, tokens) % 128 == 0
    if held == cfg.moe_experts:
        assert rows == moe._padded_rows(cfg, tokens)  # the one chunk is the whole ranking


def test_routing_ties_go_to_the_lower_expert_in_program_and_reference():
    cfg = _moe_cfg(moe_shared_ff_dim=0)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    params = {**params, "router": {"w": jnp.zeros((32, 16))}}  # all sixteen tie
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
    weights, ids = moe.route(params["router"], cfg, x.reshape(8, 32))
    np.testing.assert_array_equal(np.asarray(ids), np.tile(np.arange(3), (8, 1)))
    np.testing.assert_allclose(np.asarray(weights), 1.0 / 3.0, rtol=1e-6)
    want = ref.routing(_sizes_of(cfg), params, x.reshape(8, 32))
    assert (np.asarray(want)[:, :3] > 0).all() and (np.asarray(want)[:, 3:] == 0).all()
    out, _ = moe.moe_feed_forward(params, cfg, x)
    np.testing.assert_allclose(np.asarray(out.reshape(8, 32)),
                               np.asarray(ref.moe(_sizes_of(cfg), params, x.reshape(8, 32))),
                               atol=LOGITS_ATOL)


def test_grouped_matmul_rows_past_the_groups_are_zero_and_carry_no_gradient():
    lhs = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 5))
    sizes = jnp.asarray([4, 0, 6])
    tally = {"kernel": 0, "fallback": 0}
    out = moe.grouped_matmul(lhs, rhs, sizes, tally)
    assert tally == {"kernel": 0, "fallback": 1}  # the CPU takes lax.ragged_dot
    np.testing.assert_allclose(np.asarray(out[:4]), np.asarray(lhs[:4] @ rhs[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[4:10]), np.asarray(lhs[4:10] @ rhs[2]), atol=1e-5)
    assert not np.asarray(out[10:]).any()
    g = jax.grad(lambda a: moe.grouped_matmul(a, rhs, sizes).sum())(lhs)
    assert not np.asarray(g[10:]).any() and np.asarray(g[:10]).all()


# ------------------------------------------------------------------- refusals
def _refused(fn, what):
    with pytest.raises(NotImplementedError, match="training path only") as e:
        fn()
    assert what in str(e.value)


@pytest.mark.parametrize("what", ["init_cache", "prefill", "decode_step", "init_paged_pool",
                                  "paged_decode_step"])
def test_cached_and_paged_entry_points_refuse_the_block(model, what):
    cfg, params = model
    tcfg = cfg.transformer_config()
    x = jnp.zeros((1, 1, cfg.dim))
    calls = {
        "init_cache": lambda: tr.init_cache(tcfg, 1),
        "prefill": lambda: tr.prefill(params["transformer"], tcfg, x, {}),
        "decode_step": lambda: tr.decode_step(params["transformer"], tcfg, x, {}),
        "init_paged_pool": lambda: tr.init_paged_pool(tcfg, 4, 8),
        "paged_decode_step": lambda: tr.paged_decode_step(
            params["transformer"], tcfg, x, {}, {}, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1,), jnp.int32), 8),
    }
    _refused(calls[what], what)


@pytest.mark.parametrize("change", [{"scan_layers": True}, {"execution": "reversible"},
                                    {"scan_layers": True, "pipeline_axis": "pp"},
                                    {"seq_shard_axis": "sp"}])
def test_scan_reversible_pipeline_and_sequence_sharding_refuse_the_block(model, change):
    cfg, params = model
    cfg = dataclasses.replace(cfg, **change)
    text, codes = _sequence(cfg, cfg.image_seq_len)
    _refused(lambda: dalle_mod.forward(params, cfg, jnp.asarray(text)[None],
                                       jnp.asarray(codes)[None]), "apply_transformer")


def test_sampling_and_the_engine_refuse_the_block(model):
    from dalle_pytorch_tpu.models import sampling
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    cfg, params = model
    text = jnp.ones((1, cfg.text_seq_len), jnp.int32)
    _refused(lambda: sampling.sample_image_codes(params, cfg, text, jax.random.PRNGKey(0)),
             "sample_image_codes")
    _refused(lambda: GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=2, block_size=8)),
             "GenerationEngine")


def test_an_unknown_layer_kind_is_still_refused():
    with pytest.raises(ValueError, match="is not valid"):
        tr.derive_layer_specs(tr.TransformerConfig(dim=8, depth=1, seq_len=4, attn_types=("gated",)))


def test_a_dense_model_reports_no_aux_and_keeps_its_parameter_tree():
    cfg = dalle_mod.DALLEConfig(dim=32, depth=2, heads=2, dim_head=16, num_text_tokens=20,
                                text_seq_len=4, num_image_tokens=8, image_fmap_size=2)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    assert set(params["transformer"]["layers"][0]) == {"attn_norm", "ff_norm", "attn_scale", "ff_scale"}
    assert set(params["logits_norm"]) == {"scale", "bias"}
    loss, aux = jax.jit(lambda p: dalle_mod.forward(
        p, cfg, jnp.ones((1, 4), jnp.int32), jnp.zeros((1, 4), jnp.int32), return_loss=True,
        return_aux=True))(params)
    assert aux == {} and np.isfinite(float(loss))


# --------------------------------------------------- the configuration's file
def test_the_cells_configuration_states_the_source_and_the_program_reads_the_same_model():
    sizes = json.loads((ROOT / "benchmark" / "configs" / "qwen3_next_ep16_p1.json").read_text())
    same = {"hidden_size": "dim", "head_dim": "dim_head", "num_attention_heads": "heads",
            "num_key_value_heads": "kv_heads", "linear_conv_kernel_dim": "gdn_conv_kernel",
            "linear_key_head_dim": "gdn_key_dim", "linear_value_head_dim": "gdn_value_dim",
            "linear_num_key_heads": "gdn_key_heads", "linear_num_value_heads": "gdn_value_heads",
            "moe_intermediate_size": "moe_ff_dim", "shared_expert_intermediate_size": "moe_shared_ff_dim",
            "num_experts_per_tok": "moe_top_k",
            "partial_rotary_factor": "partial_rotary_factor", "rms_norm_eps": "norm_eps",
            "rope_theta": "rotary_theta", "num_experts": "moe_experts_held"}
    for source_key, program_key in same.items():
        assert sizes[source_key] == sizes[program_key], (source_key, program_key)
    assert sizes["tie_word_embeddings"] is (not sizes["share_input_output_emb"]) or \
        sizes["tie_word_embeddings"] == sizes["share_input_output_emb"]
    cfg = build.dalle_config(sizes)
    assert cfg.total_tokens == sizes["vocab_size"] == 18992
    assert cfg.moe_experts == sizes["published"]["num_experts"] == 512
    assert cfg.depth == sizes["full_attention_interval"] == 4
    assert list(cfg.attn_types) == ["gated_delta"] * 3 + ["gated_full"]
    assert cfg.total_seq_len == 4224 and set(sizes["reduced"]) == {"depth", "num_experts", "vocab_size"}
    shapes = jax.eval_shape(lambda k: dalle_mod.init_dalle(k, cfg), jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert 620e6 < n_params < 632e6  # the deployment's 626 M: 10.0 GB at 16 bytes


# ------------------------------------------- the step's metrics and the counters
@pytest.mark.parametrize("accum", [1, 2])
def test_train_step_carries_the_experts_load_beside_the_loss_and_counts_the_layers(model, accum):
    import optax

    from dalle_pytorch_tpu.observability import metrics as obs_metrics
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step

    cfg, params = model

    def loss_fn(p, b, key):
        return dalle_mod.forward(p, cfg, b["text"], b["image_codes"], return_loss=True,
                                 return_aux=True)

    names = ("train/gdn_layers", "train/moe_layers", "train/moe_experts_held", "train/moe_pair_rows",
             "train/moe_gmm_fallback_calls", "train/moe_gmm_kernel_calls")
    before = {n: obs_metrics.counter(n).value for n in names}
    init_fn, step_fn = make_train_step(loss_fn, optax.adam(1e-3),
                                       settings=StepSettings(grad_accum=accum))
    state = init_fn(jax.tree_util.tree_map(jnp.copy, params))
    text, codes = _sequence(cfg, cfg.image_seq_len)
    batch = {"text": jnp.asarray(np.stack([text] * 2 * accum)),
             "image_codes": jnp.asarray(np.stack([codes] * 2 * accum))}
    state, m = step_fn(state, batch, jax.random.PRNGKey(0))
    assert {"loss", "grad_norm", "moe_pairs_here", "moe_load_max_over_mean",
            "moe_overflow_share"} <= set(m)
    assert float(m["moe_overflow_share"]) == 0.0
    assert np.isfinite(float(m["loss"])) and int(m["skipped"]) == 0
    # every microbatch is the same two sequences: the mean over them is one microbatch's
    alone = jax.jit(lambda p: dalle_mod.forward(
        p, cfg, batch["text"][:2], batch["image_codes"][:2], return_loss=True,
        return_aux=True)[1])(params)
    assert float(m["moe_pairs_here"]) == pytest.approx(float(alone["moe_pairs_here"]))
    grew = {n: obs_metrics.counter(n).value - before[n] for n in names}
    assert grew["train/gdn_layers"] >= 3 and grew["train/moe_layers"] >= 4
    assert grew["train/moe_experts_held"] >= 16
    # 2 sequences x 24 positions x top-3 = 144 pairs, a quarter expected: one row tile of two
    assert grew["train/moe_pair_rows"] >= 4 * 128 and grew["train/moe_pair_rows"] % (4 * 128) == 0
    assert grew["train/moe_gmm_fallback_calls"] >= 12 and grew["train/moe_gmm_kernel_calls"] == 0
