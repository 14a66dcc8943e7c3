"""A latent-attention trunk (`mla` layers, a sigmoid bias-balanced router with
an ungated shared expert, a leading dense SwiGLU layer, plain RMSNorm, a
multi-token-prediction module) in the DALL-E stream, on the CPU at a small
size with seeded random weights: the system against the plain float32
reference, the expert layer's shares against the uncut layer, the bias and its
rule, the module's targets, and every entry point that must refuse the block.

The size keeps every ratio that matters: key width = nope + rope = value width
(12 + 4 = 16), both latent ranks under the hidden size (24, 16 of 64), 16
experts top-4 of which 4 are held, one dense layer + two routed + the module.

Tolerances, and why.  In float32 the system and the reference compute the same
mathematics in different orders (sorted grouped products against a dense loop,
one batched softmax against blocks of rows), so what separates them is
float32 reduction order: measured 5e-7 on logits of order 2.  LOGITS_ATOL 2e-5
is the bound tests/test_hybrid_trunk.py holds its block to; LOSS_RTOL 1e-5;
GRAD_RTOL 1e-4 of each leaf's largest entry (measured 2e-5).  Under bfloat16
compute a product rounds at 2**-8 = 0.4 %: benchmark/harness/correct.py's
TOLERANCE 0.03 of the logits' RMS (measured 0.8 % here, worst row 2.7 % of
9 %) and its 0.2 % on the loss (measured 0.04 %); a gradient passes every
layer twice and the routed weights' are sums of few terms, so each leaf is
held to 3 x TOLERANCE of its own RMS (measured 5 % at the worst leaf).
"""
import dataclasses
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import build, correct  # noqa: E402
from benchmark.reference import glm47_flash_reference as ref  # noqa: E402
from dalle_pytorch_tpu.core.pytree import cast_floating  # noqa: E402
from dalle_pytorch_tpu.models import dalle as dalle_mod  # noqa: E402
from dalle_pytorch_tpu.models import latent_attention, moe  # noqa: E402
from dalle_pytorch_tpu.models import transformer as tr  # noqa: E402

LOGITS_ATOL = 2e-5
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4

TINY = ROOT / "benchmark" / "rehearsal" / "tiny_glm.json"
SIZES = json.loads(TINY.read_text())
PUBLISHED = json.loads((ROOT / "benchmark" / "configs" / "glm47_flash_ep8_d5.json").read_text())
BIAS = re.compile(r"router'\]\['bias'\]$")


@pytest.fixture(scope="module")
def model():
    cfg = build.dalle_config(SIZES)
    params = build.make_weights(cfg, 2**31 + 5, jnp.float32)
    # a bias that is not zero, so that it decides choices in every comparison
    rng = np.random.default_rng(4)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.asarray(rng.normal(0, 0.05, a.shape), a.dtype)
        if BIAS.search(jax.tree_util.keystr(path)) else a, params)
    return cfg, params


@pytest.fixture(scope="module")
def reference_loss_and_grads(model):
    """The reference's loss on the full sequence and its gradient (jax.grad of it)."""
    cfg, params = model
    text, codes = _sequence(cfg, cfg.image_seq_len)
    return jax.jit(jax.value_and_grad(lambda p: ref.loss(p, SIZES, text, codes)))(params)


def _sequence(cfg, n_codes, pad_tail=2):
    rng = np.random.default_rng(1)
    text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,))
    text[cfg.text_seq_len - pad_tail:] = 0
    return text.astype(np.int32), rng.integers(0, cfg.num_image_tokens, (n_codes,)).astype(np.int32)


def _system_logits(cfg, params, text, codes):
    return jax.jit(lambda p: dalle_mod.forward(
        p, cfg, jnp.asarray(text)[None], jnp.asarray(codes)[None], with_mtp_logits=True))(params)


# ------------------------------------------------ the system against the reference
@pytest.mark.parametrize("n_codes,execution", [(16, "sequential"), (5, "sequential"), (16, "remat")])
def test_main_and_module_logits_match_the_reference(model, n_codes, execution):
    cfg, params = model
    cfg = dataclasses.replace(cfg, execution=execution)
    text, codes = _sequence(cfg, n_codes)
    n = min(1 + cfg.text_seq_len + n_codes, cfg.total_seq_len)  # <bos> + text + codes, cut to the sequence
    main, module = _system_logits(cfg, params, text, codes)
    assert main.shape == (1, n, cfg.total_tokens)
    assert module.shape == (1, n - 1, cfg.total_tokens), "the last position has no target two ahead"
    for got, fn in ((main, ref.forward_logits), (module, ref.forward_mtp_logits)):
        want = np.asarray(jax.jit(lambda p: fn(p, SIZES, text, codes))(params))
        got = np.asarray(got[0])
        ok = np.isfinite(want)
        assert got.shape == want.shape
        np.testing.assert_allclose(got[ok], want[ok], atol=LOGITS_ATOL)
        assert (got[~ok] < -1e30).all(), "the program forbids what the reference forbids"


def test_module_row_i_is_masked_as_the_position_of_token_i_plus_2(model):
    cfg, params = model
    text, codes = _sequence(cfg, cfg.image_seq_len)
    main, module = _system_logits(cfg, params, text, codes)
    allowed_main = np.asarray(main[0]) > -1e30
    allowed_module = np.asarray(module[0]) > -1e30
    # the main row i predicts token i + 1; the module's row i predicts i + 2, as main row i + 1
    np.testing.assert_array_equal(allowed_module, allowed_main[1:])
    ts = cfg.text_seq_len
    assert allowed_module[ts - 2, 0] and not allowed_module[ts - 1, 0]  # text targets end a row earlier


def test_loss_and_every_gradient_leaf_match_the_reference(model, reference_loss_and_grads):
    cfg, params = model
    text, codes = _sequence(cfg, cfg.image_seq_len)
    t, c = jnp.asarray(text)[None], jnp.asarray(codes)[None]
    (got, aux), g_sys = jax.jit(jax.value_and_grad(
        lambda p: dalle_mod.forward(p, cfg, t, c, return_loss=True, return_aux=True),
        has_aux=True))(params)
    want, g_ref = reference_loss_and_grads
    assert float(got) == pytest.approx(float(want), rel=LOSS_RTOL)
    assert float(aux["main_loss"] + cfg.mtp_loss_weight * aux["mtp_loss"]) == pytest.approx(float(got))
    main = jax.jit(lambda p: ref.loss_from_logits(ref.forward_logits(p, SIZES, text, codes),
                                                  SIZES, text, codes))(params)
    assert float(aux["main_loss"]) == pytest.approx(float(main), rel=LOSS_RTOL)
    flat_sys = jax.tree_util.tree_leaves_with_path(g_sys)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    assert len(flat_sys) == len(flat_ref) and len(flat_sys) > 60
    for (path, a), b in zip(flat_sys, flat_ref):
        name, scale = jax.tree_util.keystr(path), float(jnp.abs(b).max())
        if BIAS.search(name):  # no gradient trains it: zero in the program and in the reference
            assert scale == 0 and float(jnp.abs(a).max()) == 0, name
            continue
        assert scale > 0, f"{name}: the reference's gradient is all zero"
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=GRAD_RTOL * scale, err_msg=name)


def test_bfloat16_compute_stays_inside_the_benchmarks_band(model, reference_loss_and_grads):
    cfg, params = model
    text, codes = _sequence(cfg, cfg.image_seq_len)
    t, c = jnp.asarray(text)[None], jnp.asarray(codes)[None]
    low = cast_floating(params, jnp.bfloat16)
    main, module = _system_logits(cfg, low, text, codes)
    for got, fn in ((main, ref.forward_logits), (module, ref.forward_mtp_logits)):
        err, worst = correct.logits_error(got[0], jax.jit(lambda p: fn(p, SIZES, text, codes))(params))
        assert float(err) <= correct.TOLERANCE and float(worst) <= 3 * correct.TOLERANCE
    got, g_sys = jax.jit(jax.value_and_grad(lambda p: dalle_mod.forward(
        cast_floating(p, jnp.bfloat16), cfg, t, c, return_loss=True)))(params)
    want, g_ref = reference_loss_and_grads
    assert abs(float(got) - float(want)) / float(want) <= correct.LOSS_TOLERANCE
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_sys), jax.tree_util.tree_leaves(g_ref)):
        rms = float(jnp.sqrt(jnp.mean(b ** 2)))
        err = float(jnp.sqrt(jnp.mean((a.astype(jnp.float32) - b) ** 2)))
        assert err <= 3 * correct.TOLERANCE * rms + 1e-12, jax.tree_util.keystr(path)


# ----------------------------------------------------------- latent attention
def _mla_cfg():
    return build.dalle_config(SIZES).transformer_config()


def test_the_rope_key_is_one_vector_that_every_head_reads():
    cfg = _mla_cfg()
    p = latent_attention.init_mla(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 10, cfg.dim))
    vd = cfg.mla_v_dim

    def per_head(p):  # W_o = identity over the heads' outputs: (1, n, heads, v)
        eye = dict(p, out={"w": jnp.eye(cfg.heads * vd)})
        return latent_attention.mla_attention(eye, cfg, x).reshape(1, 10, cfg.heads, vd)

    moved = dict(p, kv_a={"w": p["kv_a"]["w"].at[:, cfg.mla_kv_rank:].add(0.5)})  # k_rope's columns only
    change = jnp.abs(per_head(moved) - per_head(p)).max(axis=(0, 1, 3))
    assert change.shape == (cfg.heads,) and (np.asarray(change) > 1e-4).all()
    # and it is not one of the per-head projections: kv_b's width has no room for it
    assert p["kv_b"]["w"].shape == (cfg.mla_kv_rank, cfg.heads * (cfg.mla_nope_dim + vd))
    assert p["kv_a"]["w"].shape == (cfg.dim, cfg.mla_kv_rank + cfg.mla_rope_dim)


def test_mla_alone_matches_the_reference_and_position_0_sees_itself_only():
    cfg = _mla_cfg()
    p = latent_attention.init_mla(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, cfg.dim))
    got = latent_attention.mla_attention(p, cfg, x)[0]
    want = ref.latent_attention(SIZES, p, x[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    later = x.at[:, 1:].add(1.0)
    np.testing.assert_allclose(np.asarray(latent_attention.mla_attention(p, cfg, later)[0, 0]),
                               np.asarray(got[0]), atol=1e-6)


# ------------------------------------------------------------------ the router
def _moe_cfg(**kw):
    base = dict(dim=32, depth=1, seq_len=16, moe_experts=16, moe_top_k=4, moe_ff_dim=24,
                moe_shared_ff_dim=24, moe_router="sigmoid_bias", moe_routed_scale=1.8,
                moe_shared_gated=False)
    base.update(kw)
    return tr.TransformerConfig(**base)


def _sizes_of(cfg):
    return {k: getattr(cfg, k) for k in ("moe_experts", "moe_top_k", "moe_experts_held",
                                         "moe_first_expert", "moe_routed_scale")}


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    whole_cfg = _moe_cfg()
    whole = moe.init_moe(jax.random.PRNGKey(0), whole_cfg)
    whole["router"]["bias"] = jax.random.normal(jax.random.PRNGKey(5), (16,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    want = ref.moe(_sizes_of(whole_cfg), whole, x.reshape(-1, 32))
    shared = ref.dense_ff(whole["shared"], x.reshape(-1, 32))
    total, counts = 0.0, []
    for first in (0, 4, 8, 12):
        cfg = _moe_cfg(moe_experts_held=4, moe_first_expert=first)
        share = dict(whole, experts=jax.tree_util.tree_map(lambda w: w[first:first + 4],
                                                           whole["experts"]))
        out, stats = moe.moe_feed_forward(share, cfg, x)
        total = total + out.reshape(-1, 32) - shared  # what every rank computes alike, once
        counts.append(np.asarray(stats["moe_choice_counts"]))
        want_share = ref.moe(_sizes_of(cfg), share, x.reshape(-1, 32))
        np.testing.assert_allclose(np.asarray(out.reshape(-1, 32)), np.asarray(want_share), atol=2e-6)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=5e-6)
    # the router is whole on every rank: each counts every expert's choices alike
    assert all((c == counts[0]).all() for c in counts) and counts[0].sum() == 32 * 4


def test_the_bias_moves_the_choice_and_never_the_weights():
    cfg = _moe_cfg()
    router = moe.init_moe(jax.random.PRNGKey(0), cfg)["router"]
    x2 = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    w0, ids0 = moe.route(router, cfg, x2)
    never = int(np.bincount(np.asarray(ids0).reshape(-1), minlength=16).argmin())
    pushed = dict(router, bias=router["bias"].at[never].set(10.0))
    w1, ids1 = moe.route(pushed, cfg, x2)
    assert (np.asarray(ids1) == never).any(axis=1).all(), "a large b_e makes e chosen by every token"
    scores = np.asarray(jax.nn.sigmoid(x2 @ router["w"]))
    chosen = np.take_along_axis(scores, np.asarray(ids1), axis=1)
    np.testing.assert_allclose(np.asarray(w1), chosen / chosen.sum(1, keepdims=True) * 1.8, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w1).sum(1), 1.8, rtol=1e-5)
    assert np.asarray(w1).max() < 1.8, "10.0 is in no weight"
    # and the reference chooses and weighs alike
    want = np.asarray(ref.routing(_sizes_of(cfg), {"router": pushed}, x2))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(ids1), np.asarray(w1), axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_softmax_routing_and_the_gated_shared_expert_are_what_they_were():
    cfg = _moe_cfg(moe_router="softmax", moe_routed_scale=1.0, moe_shared_gated=True)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    assert "bias" not in params["router"] and "gate" in params["shared"]
    w, _ = moe.route(params["router"], cfg, jax.random.normal(jax.random.PRNGKey(1), (8, 32)))
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, rtol=1e-6)
    _, stats = moe.moe_feed_forward(params, cfg, jnp.ones((1, 16, 32)))
    assert "moe_choice_counts" not in stats
    with pytest.raises(ValueError, match="moe_router"):
        moe.init_moe(jax.random.PRNGKey(0), _moe_cfg(moe_router="noisy"))


# --------------------------------------------- the step: the bias's rule, metrics
def _step(cfg, params, accum, optimizer=None, param_rule="cfg"):
    import optax

    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step

    def loss_fn(p, b, key):
        return dalle_mod.forward(p, cfg, b["text"], b["image_codes"], return_loss=True,
                                 return_aux=True)

    init_fn, step_fn = make_train_step(
        loss_fn, optimizer or optax.adam(1e-3), settings=StepSettings(grad_accum=accum),
        param_rule=dalle_mod.param_rule(cfg) if param_rule == "cfg" else param_rule)
    state = init_fn(jax.tree_util.tree_map(jnp.copy, params))
    rng = np.random.default_rng(2)
    batch = {"text": jnp.asarray(rng.integers(1, cfg.num_text_tokens, (2 * accum, cfg.text_seq_len)),
                                 jnp.int32),
             "image_codes": jnp.asarray(rng.integers(0, cfg.num_image_tokens,
                                                     (2 * accum, cfg.image_seq_len)), jnp.int32)}
    return state, step_fn, batch, loss_fn


@pytest.mark.parametrize("accum", [1, 2])
def test_one_step_moves_the_bias_by_gamma_toward_the_under_loaded_and_leaves_it_out_of_adam(model, accum):
    import optax

    from dalle_pytorch_tpu.observability import metrics as obs_metrics

    cfg, params = model
    names = ("train/mla_layers", "train/dense_ff_layers", "train/mtp_layers", "train/moe_layers",
             "train/moe_experts_held", "train/moe_pair_rows")
    # weight decay would shrink the bias: the rule starts from the value BEFORE the update
    state, step_fn, batch, loss_fn = _step(cfg, params, accum, optax.adamw(1e-3, weight_decay=0.1))
    # the counts the rule has to have read: the whole step's, every microbatch's summed
    counts = {}
    rule_inputs = jax.jit(lambda p, mb: loss_fn(p, mb, None)[1]["rule_inputs"])
    for i in range(accum):
        mb = jax.tree_util.tree_map(lambda a: a[2 * i:2 * i + 2], batch)
        for path, c in rule_inputs(params, mb).items():
            counts[path] = counts.get(path, 0) + np.asarray(c)
    old = {p: np.asarray(dalle_mod._leaf_at(params, p)) for p in counts}
    before = {n: obs_metrics.counter(n).value for n in names}
    state, m = step_fn(state, batch, jax.random.PRNGKey(0))
    assert len(counts) == 3 and all(c.sum() == 2 * accum * 24 * 4 for c in counts.values())
    for path, c in counts.items():
        want = old[path] + cfg.moe_bias_rate * np.sign(c.mean() - c)
        np.testing.assert_allclose(np.asarray(dalle_mod._leaf_at(state.params, path)), want, atol=1e-7)
        assert (np.abs(np.sign(c.mean() - c)) == 1).any()
        for moments in (state.opt_state[0].mu, state.opt_state[0].nu):
            assert float(jnp.abs(dalle_mod._leaf_at(moments, path)).max()) == 0.0
    assert {"loss", "main_loss", "mtp_loss", "moe_bias_abs_max", "moe_pairs_here",
            "moe_load_max_over_mean", "moe_overflow_share", "grad_norm"} <= set(m)
    assert "rule_inputs" not in m
    assert float(m["loss"]) == pytest.approx(float(m["main_loss"]) + 0.3 * float(m["mtp_loss"]), rel=1e-5)
    assert float(m["moe_bias_abs_max"]) == pytest.approx(max(np.abs(o).max() for o in old.values()))
    # one traced forward: 3 mla layers + the module's, ONE dense layer, 2 + 1 routed layers
    got = {n: obs_metrics.counter(n).value - before[n] for n in names}
    tokens = 2 * 24
    assert got == {"train/mla_layers": 4, "train/dense_ff_layers": 1, "train/mtp_layers": 1,
                   "train/moe_layers": 3, "train/moe_experts_held": 12,
                   "train/moe_pair_rows": 3 * moe.pair_rows(cfg.transformer_config(), tokens)}


def test_a_loss_that_names_parameters_needs_a_rule_and_a_skipped_step_leaves_them(model):
    cfg, params = model
    state, step_fn, batch, _ = _step(cfg, params, 1, param_rule=None)
    with pytest.raises(ValueError, match="no param_rule"):
        step_fn(state, batch, jax.random.PRNGKey(0))
    poisoned = jax.tree_util.tree_map(jnp.copy, params)
    poisoned["logits_linear"]["w"] = poisoned["logits_linear"]["w"].at[0, 0].set(jnp.nan)
    state, step_fn, batch, _ = _step(cfg, poisoned, 1)
    path = "transformer/shared_ff/1/router/bias"
    old = np.asarray(dalle_mod._leaf_at(poisoned, path))
    state, m = step_fn(state, batch, jax.random.PRNGKey(0))
    assert int(m["skipped"]) == 1
    np.testing.assert_array_equal(np.asarray(dalle_mod._leaf_at(state.params, path)), old)


# ------------------------------------------------------- the parameter tree
def test_layer_0_is_dense_and_the_module_shares_embedding_and_head(model):
    cfg, params = model
    ff = params["transformer"]["shared_ff"]
    assert set(ff["0"]) == {"wg", "wu", "wd"}, "no router and no experts in a dense layer"
    assert ff["0"]["wg"]["w"].shape == (cfg.dim, cfg.dense_ff_dim)
    for i in ("1", "2"):
        assert set(ff[i]) == {"router", "experts", "shared"}
        assert set(ff[i]["router"]) == {"w", "bias"} and ff[i]["router"]["w"].shape == (cfg.dim, 16)
        assert set(ff[i]["shared"]) == {"wg", "wu", "wd"}, "no gate"
        assert ff[i]["experts"]["wg"].shape == (4, cfg.dim, cfg.moe_ff_dim)
    mtp = params["mtp"]
    assert set(mtp) == {"h_norm", "e_norm", "merge", "block", "norm"}
    assert mtp["merge"]["w"].shape == (2 * cfg.dim, cfg.dim)
    assert set(mtp["block"]["shared_ff"]["0"]) == {"router", "experts", "shared"}
    assert "text_emb" not in mtp and "logits_linear" not in mtp
    assert float(params["logits_norm"]["w"][0]) == 1.0, "plain RMSNorm starts at 1"
    tcfg = cfg.transformer_config()
    assert [tcfg.ff_type(i) for i in range(3)] == ["swiglu", "moe", "moe"] and tcfg.hybrid


def test_a_dense_model_keeps_its_tree_and_an_unsupported_module_depth_is_refused():
    cfg = dalle_mod.DALLEConfig(dim=32, depth=2, heads=2, dim_head=16, num_text_tokens=20,
                                text_seq_len=4, num_image_tokens=8, image_fmap_size=2)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    assert "mtp" not in params and set(params["transformer"]["shared_ff"]["0"]) == {"w1", "w1g", "w2"}
    assert dalle_mod.param_rule(cfg) is None and not cfg.transformer_config().hybrid
    with pytest.raises(ValueError, match="mtp_depth"):
        dalle_mod.init_dalle(jax.random.PRNGKey(0), dataclasses.replace(cfg, mtp_depth=2))
    with pytest.raises(ValueError, match="norm"):
        tr.norm_init(dataclasses.replace(cfg.transformer_config(), norm="rms"))


# ------------------------------------------------------------------- refusals
def _refused(fn, what):
    with pytest.raises(NotImplementedError, match="training path only") as e:
        fn()
    assert what in str(e.value) and "mla" in str(e.value) and "dense" in str(e.value)


@pytest.mark.parametrize("size", ["tiny", "published"])
@pytest.mark.parametrize("what", ["init_cache", "prefill", "decode_step", "init_paged_pool",
                                  "paged_decode_step"])
def test_cached_and_paged_entry_points_refuse_the_block(size, what):
    cfg = build.dalle_config(SIZES if size == "tiny" else PUBLISHED)
    tcfg = cfg.transformer_config()
    x = jnp.zeros((1, 1, cfg.dim))
    calls = {
        "init_cache": lambda: tr.init_cache(tcfg, 1),
        "prefill": lambda: tr.prefill({}, tcfg, x, {}),
        "decode_step": lambda: tr.decode_step({}, tcfg, x, {}),
        "init_paged_pool": lambda: tr.init_paged_pool(tcfg, 4, 8),
        "paged_decode_step": lambda: tr.paged_decode_step(
            {}, tcfg, x, {}, {}, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32), 8),
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


def test_a_dense_layer_alone_makes_a_trunk_hybrid():
    """Hybrid for the training path's refusals (scan, pipeline, reversible), and
    served since PR 33: what the cached entry points refuse is narrower."""
    cfg = tr.TransformerConfig(dim=8, depth=2, seq_len=4, dense_layers=1, dense_ff_dim=16)
    assert cfg.hybrid and not cfg.unserved
    tr.refuse_hybrid(cfg, "init_cache")  # a dense SwiGLU layer is served
    with pytest.raises(NotImplementedError, match="1 leading dense layers"):
        tr.apply_transformer({}, dataclasses.replace(cfg, scan_layers=True), jnp.zeros((1, 4, 8)))
    with pytest.raises(NotImplementedError, match="1 leading dense layers"):
        tr.refuse_hybrid(dataclasses.replace(cfg, moe_experts=4), "init_cache")


# --------------------------------------------------- the configuration's file
def test_the_cells_configuration_states_the_source_and_the_program_reads_the_same_model():
    sizes = PUBLISHED
    catalog = {"attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 10240, "max_position_embeddings": 202752,
               "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
               "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20,
               "n_group": 1, "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
               "routed_scaling_factor": 1.8, "num_experts_per_tok": 4, "first_k_dense_replace": 1,
               "num_hidden_layers": 47, "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
               "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
               "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
               "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
               "v_head_dim": 256, "vocab_size": 154880}
    cut = {"n_routed_experts": 8, "vocab_size": 19360}
    for key, value in catalog.items():
        assert sizes[key] == cut.get(key, value), key
    assert set(sizes["reduced"]) == {"depth", "n_routed_experts", "vocab_size"}
    assert sizes["published"]["n_routed_experts"] == 64 and sizes["published"]["vocab_size"] == 154880
    same = {"hidden_size": "dim", "num_attention_heads": "heads", "q_lora_rank": "mla_q_rank",
            "kv_lora_rank": "mla_kv_rank", "qk_nope_head_dim": "mla_nope_dim",
            "qk_rope_head_dim": "mla_rope_dim", "v_head_dim": "mla_v_dim",
            "intermediate_size": "dense_ff_dim", "first_k_dense_replace": "dense_layers",
            "moe_intermediate_size": "moe_ff_dim", "num_experts_per_tok": "moe_top_k",
            "routed_scaling_factor": "moe_routed_scale", "rms_norm_eps": "norm_eps",
            "rope_theta": "rotary_theta", "num_nextn_predict_layers": "mtp_depth",
            "n_routed_experts": "moe_experts_held"}
    for source_key, program_key in same.items():
        assert sizes[source_key] == sizes[program_key], (source_key, program_key)
    assert sizes["moe_shared_ff_dim"] == sizes["n_shared_experts"] * sizes["moe_intermediate_size"]
    cfg = build.dalle_config(sizes)
    assert cfg.total_tokens == sizes["vocab_size"] == 19360
    assert cfg.moe_experts == sizes["published"]["n_routed_experts"] == 64
    assert cfg.depth == 5 and cfg.total_seq_len == 4224 and cfg.attn_types == ("mla",)
    assert cfg.moe_router == "sigmoid_bias" and cfg.moe_shared_gated is False and cfg.norm == "rmsnorm"
    shapes = jax.eval_shape(lambda k: dalle_mod.init_dalle(k, cfg), jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert 704e6 < n_params < 709e6  # the deployment's 706.5 M: 11.3 GB at 16 bytes


# -------------------------------------------------------- the normal entry point
def test_train_dalle_trains_the_block_from_the_command_line(tmp_path, capsys):
    from dalle_pytorch_tpu.cli import train_dalle

    train_dalle.main(["--dummy_run", "20", "--batch_size", "1", "--block_json", str(TINY), "--telemetry", "off",
                      "--log_every_n_steps", "1", "--save_every_n_steps", "0",
                      "--sample_every_n_steps", "0", "--learning_rate", "3e-3",
                      "--dalle_output_file_name", str(tmp_path / "d")])
    lines = [l for l in capsys.readouterr().out.splitlines() if re.match(r"\[\d+\] loss=", l)]
    assert len(lines) >= 20
    fields = [dict(kv.split("=") for kv in l.split()[1:]) for l in lines]
    for name in ("main_loss", "mtp_loss", "moe_pairs_here", "moe_bias_abs_max"):
        assert all(name in f for f in fields), name
    losses = [float(f["loss"]) for f in fields]
    assert np.isfinite(losses).all() and np.mean(losses[-3:]) < 0.8 * np.mean(losses[:3])
    assert float(fields[-1]["moe_bias_abs_max"]) > float(fields[0]["moe_bias_abs_max"])
