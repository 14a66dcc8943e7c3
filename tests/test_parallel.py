"""Multi-device tests on the 8-device virtual CPU mesh (conftest.py)."""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models.dalle import DALLEConfig
from dalle_pytorch_tpu.ops.attention import attend
from dalle_pytorch_tpu.ops.masks import causal_mask
from dalle_pytorch_tpu.parallel import backend as backend_mod
from dalle_pytorch_tpu.parallel.mesh import MeshConfig, make_mesh
from dalle_pytorch_tpu.parallel.ring import ring_attention
from dalle_pytorch_tpu.parallel.sharding import opt_state_specs, param_specs
from dalle_pytorch_tpu.parallel.train_step import StepSettings, TrainState, make_train_step

P = PartitionSpec


def tiny_cfg(**kw):
    base = dict(
        dim=32, depth=2, num_text_tokens=64, text_seq_len=8, heads=4, dim_head=8,
        num_image_tokens=32, image_fmap_size=4,
    )
    base.update(kw)
    return DALLEConfig(**base)


def batch_for(cfg, b=8, seed=0):
    kt, ki = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "text": jax.random.randint(kt, (b, cfg.text_seq_len), 0, cfg.num_text_tokens),
        "image_codes": jax.random.randint(ki, (b, cfg.image_seq_len), 0, cfg.num_image_tokens),
    }


def dalle_loss(cfg):
    def loss_fn(params, batch, key):
        return dalle_mod.forward(
            params, cfg, batch["text"], batch["image_codes"], return_loss=True
        )

    return loss_fn


def test_mesh_construction():
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
    assert mesh.shape == {"dp": 2, "fsdp": 2, "tp": 2, "sp": 1, "pp": 1}
    mesh = make_mesh(MeshConfig())  # all 8 into dp
    assert mesh.shape["dp"] == 8


@pytest.mark.slow
@pytest.mark.multichip
def test_ring_attention_matches_dense():
    mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=1, sp=8))
    b, h, n, d = 2, 4, 64, 16
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, n, d), jnp.float32) for i in range(3)
    )
    got = np.asarray(ring_attention(q, k, v, mesh, causal=True))
    want = np.asarray(attend(q * d ** -0.5, k, v, mask=causal_mask(n)))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_ring_attention_non_causal():
    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=4))
    b, h, n, d = 1, 2, 32, 8
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, n, d), jnp.float32) for i in range(3)
    )
    got = np.asarray(ring_attention(q, k, v, mesh, causal=False))
    want = np.asarray(attend(q * d ** -0.5, k, v, mask=None))
    np.testing.assert_allclose(got, want, atol=2e-5)


# tier-1 budget: z1 is slow-marked — the mechanism sweep stays fast via the
# z0 / z3 extremes (z1 differs only in optimizer-state partitioning, which
# z3 exercises a superset of)
@pytest.mark.parametrize("zero_stage",
                         [0, pytest.param(1, marks=pytest.mark.slow), 3])
def test_sharded_training_matches_single_device(zero_stage):
    """The same params + batch must produce the same loss trajectory on an
    8-way mesh (any ZeRO stage) as on a single device."""
    cfg = tiny_cfg()
    batch = batch_for(cfg)
    opt = optax.adam(1e-3)
    loss_fn = dalle_loss(cfg)

    # single-device reference (fresh buffers — step_fn donates its input state)
    init_s, step_s = make_train_step(loss_fn, opt, mesh=None)
    state_s = init_s(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg))
    losses_s = []
    for i in range(3):
        state_s, m = step_s(state_s, batch, jax.random.PRNGKey(i))
        losses_s.append(float(m["loss"]))

    mesh = make_mesh(MeshConfig(dp=4, fsdp=2))
    init_m, step_m = make_train_step(
        loss_fn, opt, mesh=mesh, settings=StepSettings(zero_stage=zero_stage)
    )
    state_m = init_m(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg))
    losses_m = []
    for i in range(3):
        state_m, m = step_m(state_m, batch, jax.random.PRNGKey(i))
        losses_m.append(float(m["loss"]))

    np.testing.assert_allclose(losses_s, losses_m, rtol=2e-4)


def test_zero3_params_actually_sharded():
    cfg = tiny_cfg(dim=64)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(MeshConfig(dp=1, fsdp=8))
    specs = param_specs(params, mesh, zero_stage=3)
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert any(s != P() for s in leaves), "no parameter got sharded under ZeRO-3"

    init_fn, _ = make_train_step(dalle_loss(cfg), optax.adam(1e-3), mesh=mesh,
                                 settings=StepSettings(zero_stage=3))
    state = init_fn(params)
    emb = state.params["text_emb"]["table"]
    assert len(emb.sharding.device_set) == 8


def test_zero1_opt_state_sharded_params_replicated():
    cfg = tiny_cfg(dim=64)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(MeshConfig(dp=1, fsdp=8))
    init_fn, _ = make_train_step(dalle_loss(cfg), optax.adam(1e-3), mesh=mesh,
                                 settings=StepSettings(zero_stage=1))
    state = init_fn(params)
    # params replicated
    assert state.params["text_emb"]["table"].sharding.is_fully_replicated
    # some moment is sharded
    shardings = [l.sharding for l in jax.tree_util.tree_leaves(state.opt_state) if hasattr(l, "sharding") and l.ndim > 0]
    assert any(not s.is_fully_replicated for s in shardings)


def test_tensor_parallel_step():
    cfg = tiny_cfg()
    batch = batch_for(cfg, b=4)
    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=4))
    init_fn, step_fn = make_train_step(dalle_loss(cfg), optax.adam(1e-3), mesh=mesh)
    state = init_fn(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg))
    qkv = state.params["transformer"]["shared_attn"]["0"]["qkv"]["w"]
    assert not qkv.sharding.is_fully_replicated

    init_s, step_s = make_train_step(dalle_loss(cfg), optax.adam(1e-3), mesh=None)
    state_s = init_s(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg))
    _, m_s = step_s(state_s, batch, jax.random.PRNGKey(0))
    _, m_m = step_fn(state, batch, jax.random.PRNGKey(0))
    np.testing.assert_allclose(float(m_s["loss"]), float(m_m["loss"]), rtol=2e-4)


def test_grad_accumulation_equivalence():
    """accum=4 over batch 8 must equal accum=1 over the same batch (mean loss
    and resulting params)."""
    cfg = tiny_cfg()
    batch = batch_for(cfg, b=8)
    opt = optax.sgd(1e-2)
    loss_fn = dalle_loss(cfg)

    init1, step1 = make_train_step(loss_fn, opt, settings=StepSettings(grad_accum=1))
    init4, step4 = make_train_step(loss_fn, opt, settings=StepSettings(grad_accum=4))
    s1, _ = step1(init1(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)), batch, jax.random.PRNGKey(0))
    s4, _ = step4(init4(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)), batch, jax.random.PRNGKey(0))
    for a, b_ in zip(jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s4.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)


def test_bf16_compute_policy():
    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    batch = batch_for(cfg)
    init_fn, step_fn = make_train_step(
        dalle_loss(cfg), optax.adam(1e-3),
        settings=StepSettings(compute_dtype=jnp.bfloat16),
    )
    state, m = step_fn(init_fn(params), batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"]))
    # master params stay f32
    assert state.params["logits_linear"]["w"].dtype == jnp.float32


def test_stochastic_round_is_unbiased_and_exact():
    from dalle_pytorch_tpu.parallel.train_step import _stochastic_round

    # exactly-representable values pass through unchanged under every key
    x = jnp.asarray([1.0, -2.5, 0.0, 3.140625], jnp.float32)  # all bf16-exact
    for seed in range(3):
        got = _stochastic_round(x, jax.random.PRNGKey(seed), jnp.bfloat16)
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(x))

    # a value 1/4 of the way between two bf16 neighbours rounds up ~25% of
    # the time, and the MEAN equals the true value (unbiased) — whereas
    # nearest-rounding would pin it to the lower neighbour every time
    lo = np.float32(1.0)
    hi = np.float32(np.nextafter(jnp.bfloat16(1.0), jnp.bfloat16(2.0)))
    x = jnp.full((4096,), lo + 0.25 * (hi - lo), jnp.float32)
    got = np.asarray(_stochastic_round(x, jax.random.PRNGKey(7), jnp.bfloat16), np.float32)
    frac_up = (got == hi).mean()
    assert abs(frac_up - 0.25) < 0.03, frac_up
    assert set(np.unique(got)) <= {lo, hi}


def test_pure_bf16_params_with_stochastic_rounding():
    """param_dtype=bf16: storage is bf16 with NO f32 master, optimizer stats
    stay f32, and tiny-lr training still makes progress (sub-ulp updates
    survive stochastic rounding; deterministic rounding would freeze)."""
    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    batch = batch_for(cfg)
    init_fn, step_fn = make_train_step(
        dalle_loss(cfg), optax.adafactor(3e-3),
        settings=StepSettings(compute_dtype=jnp.bfloat16, grad_dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16),
    )
    state = init_fn(params)
    assert state.params["logits_linear"]["w"].dtype == jnp.bfloat16
    # adafactor's factored/full second moments derive from the f32 view
    stat_dtypes = {x.dtype for x in jax.tree_util.tree_leaves(state.opt_state)
                   if jnp.issubdtype(x.dtype, jnp.floating)}
    assert stat_dtypes == {jnp.dtype(jnp.float32)}

    first = None
    for i in range(30):
        state, m = step_fn(state, batch, jax.random.PRNGKey(i))
        if first is None:
            first = float(m["loss"])
    assert np.isfinite(float(m["loss"]))
    assert float(m["loss"]) < first  # training moves despite bf16 storage
    assert state.params["logits_linear"]["w"].dtype == jnp.bfloat16


@pytest.mark.slow
@pytest.mark.multichip
def test_pure_bf16_on_mesh_matches_single_device():
    """param_dtype=bf16 + stochastic rounding must be replica-consistent on a
    mesh: same key -> same rounding decisions on every shard, so the sharded
    loss trajectory tracks the single-device one."""
    cfg = tiny_cfg()
    batch = batch_for(cfg)
    opt = optax.adafactor(1e-3)
    settings = StepSettings(param_dtype=jnp.bfloat16, grad_dtype=jnp.bfloat16)
    loss_fn = dalle_loss(cfg)

    init_s, step_s = make_train_step(loss_fn, opt, settings=settings)
    state_s = init_s(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg))
    losses_s = []
    for i in range(3):
        state_s, m = step_s(state_s, batch, jax.random.PRNGKey(i))
        losses_s.append(float(m["loss"]))

    mesh = make_mesh(MeshConfig(dp=2, fsdp=4))
    init_m, step_m = make_train_step(
        loss_fn, opt, mesh=mesh, settings=dataclasses.replace(settings, zero_stage=3)
    )
    state_m = init_m(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg))
    assert state_m.params["logits_linear"]["w"].dtype == jnp.bfloat16
    losses_m = []
    for i in range(3):
        state_m, m = step_m(state_m, batch, jax.random.PRNGKey(i))
        losses_m.append(float(m["loss"]))

    # bf16 storage widens tolerance vs the f32 equivalence test
    np.testing.assert_allclose(losses_s, losses_m, rtol=3e-2)


def test_grad_clipping():
    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    batch = batch_for(cfg)
    init_fn, step_fn = make_train_step(
        dalle_loss(cfg), optax.sgd(1e-3), settings=StepSettings(clip_grad_norm=0.1)
    )
    _, m = step_fn(init_fn(params), batch, jax.random.PRNGKey(0))
    assert float(m["grad_norm"]) <= 0.1 + 1e-5


def _pp_cfg(**kw):
    """Depth-4 flagship-shaped tiny config: full+axial+conv cycle, shift,
    rotary — everything the pipeline body must thread through stages."""
    base = dict(
        dim=32, depth=4, num_text_tokens=64, text_seq_len=8, heads=4, dim_head=8,
        num_image_tokens=32, image_fmap_size=4,
        attn_types=("full", "axial_row", "axial_col", "conv_like"),
        shift_tokens=True, rotary_emb=True,
        execution="remat", scan_layers=True,
    )
    base.update(kw)
    return DALLEConfig(**base)


@pytest.mark.parametrize("pp,extra", [(4, {}), (2, {"pp_num_micro": 3})])
@pytest.mark.slow
@pytest.mark.multichip
def test_pipeline_matches_scan(pp, extra):
    """GPipe over pp stages must reproduce the single-stage scan: loss AND
    grads (AD through ppermute = the reverse pipeline schedule).  pp=2 with
    M=3 exercises a bubble-heavy, non-power-of-two microbatching."""
    cfg_s = _pp_cfg()
    cfg_p = _pp_cfg(pipeline_axis="pp", **extra)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_s)
    batch = batch_for(cfg_s, b=6 if extra else 8)

    def loss(cfg):
        def f(p):
            return dalle_mod.forward(p, cfg, batch["text"], batch["image_codes"], return_loss=True)
        return f

    l_s, g_s = jax.jit(jax.value_and_grad(loss(cfg_s)))(params)

    mesh = make_mesh(MeshConfig(dp=-1, fsdp=1, tp=1, sp=1, pp=pp))
    with mesh:
        l_p, g_p = jax.jit(jax.value_and_grad(loss(cfg_p)))(params)
        l_p, g_p = jax.device_get((l_p, g_p))

    np.testing.assert_allclose(float(l_s), float(l_p), rtol=1e-5)
    for a, b_ in zip(jax.tree_util.tree_leaves(g_s), jax.tree_util.tree_leaves(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-3, atol=2e-5)


@pytest.mark.slow
@pytest.mark.multichip
def test_pipeline_train_step_with_zero3():
    """Full train step with pp=2 composed with dp=2/fsdp=2 ZeRO-3: the loss
    trajectory must track the single-device run."""
    cfg_s = _pp_cfg()
    cfg_p = _pp_cfg(pipeline_axis="pp")
    batch = batch_for(cfg_s, b=8)
    opt = optax.adam(1e-3)

    init_s, step_s = make_train_step(dalle_loss(cfg_s), opt)
    state_s = init_s(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_s))
    losses_s = []
    for i in range(3):
        state_s, m = step_s(state_s, batch, jax.random.PRNGKey(i))
        losses_s.append(float(m["loss"]))

    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=1, sp=1, pp=2))
    init_m, step_m = make_train_step(
        dalle_loss(cfg_p), opt, mesh=mesh, settings=StepSettings(zero_stage=3)
    )
    state_m = init_m(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_p))
    losses_m = []
    for i in range(3):
        state_m, m = step_m(state_m, batch, jax.random.PRNGKey(i))
        losses_m.append(float(m["loss"]))

    np.testing.assert_allclose(losses_s, losses_m, rtol=5e-4)


@pytest.mark.slow
@pytest.mark.multichip
def test_pipeline_pp4_depth8_matches_scan():
    """pp=4 with 2 layers per stage at depth 8 (the scale where round-3's
    bubble-tick waste became material): loss and grads must still match the
    single-stage scan."""
    cfg_s = _pp_cfg(depth=8, attn_types=("full", "axial_row", "axial_col", "conv_like"))
    cfg_p = _pp_cfg(depth=8, pipeline_axis="pp",
                    attn_types=("full", "axial_row", "axial_col", "conv_like"))
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_s)
    batch = batch_for(cfg_s, b=8)

    def loss(cfg):
        def f(p):
            return dalle_mod.forward(p, cfg, batch["text"], batch["image_codes"], return_loss=True)
        return f

    l_s, g_s = jax.jit(jax.value_and_grad(loss(cfg_s)))(params)
    mesh = make_mesh(MeshConfig(dp=-1, fsdp=1, tp=1, sp=1, pp=4))
    with mesh:
        l_p, g_p = jax.jit(jax.value_and_grad(loss(cfg_p)))(params)
        l_p, g_p = jax.device_get((l_p, g_p))
    np.testing.assert_allclose(float(l_s), float(l_p), rtol=1e-5)
    for a, b_ in zip(jax.tree_util.tree_leaves(g_s), jax.tree_util.tree_leaves(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-3, atol=2e-5)


@pytest.mark.slow
@pytest.mark.multichip
def test_pp_params_sharded_at_rest():
    """ADVICE r3 (medium): with pp stages in the mesh, params and optimizer
    moments must shard over pp at rest — pipeline scale-out has to buy
    memory, not just compute.  Checked via per-device addressable shard
    sizes, and the step must still run."""
    # dim 128 / dim_head 32: the qkv leaf is 128x384 = 49152 elems, above
    # _shard_largest's 2**14 min_size, so the at-rest pp sharding engages
    cfg = _pp_cfg(dim=128, dim_head=32, pipeline_axis="pp")
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=1, pp=4))
    init_fn, step_fn = make_train_step(
        dalle_loss(cfg), optax.adam(1e-3), mesh=mesh, settings=StepSettings()
    )
    state = init_fn(params)
    # at least one transformer-layer leaf must be split over pp devices;
    # attention weights live under shared_attn/<id>/qkv/w — tree-search so
    # the test survives param-tree refactors
    leaves = jax.tree_util.tree_flatten_with_path(state.params)[0]
    qkv_leaves = [
        leaf for path, leaf in leaves
        if "qkv" in jax.tree_util.keystr(path) and jax.tree_util.keystr(path).endswith("'w']")
    ]
    assert qkv_leaves, "no qkv/w leaf found in param tree"
    qkv = max(qkv_leaves, key=lambda l: l.size)
    assert len(qkv.sharding.device_set) >= 4, qkv.sharding
    shard = qkv.addressable_shards[0].data
    assert shard.size < qkv.size, "params replicated over pp at rest"
    # optimizer moments mirror it
    mu = jax.tree_util.tree_leaves(state.opt_state)
    assert any(
        hasattr(m, "addressable_shards") and m.size > 0
        and m.addressable_shards[0].data.size < m.size
        for m in mu if hasattr(m, "size") and getattr(m, "ndim", 0) >= 2
    )
    state, m = step_fn(state, batch_for(cfg, b=8), jax.random.PRNGKey(1))
    assert np.isfinite(float(m["loss"]))


@pytest.mark.slow
@pytest.mark.multichip
def test_composed_dp_tp_pp_matches_single_device():
    """VERDICT r4 weak #3: one train step composing THREE parallelism axes in
    ONE mesh (dp=2 × tp=2 × pp=2) — exactly where the (fsdp, pp) axis-folding
    rules in sharding.py and the shard_map(pp)-with-auto-tp interaction would
    break — must track the single-device trajectory."""
    cfg_s = _pp_cfg()
    cfg_p = _pp_cfg(pipeline_axis="pp")
    # host copies: the donating step would otherwise delete the buffers the
    # second engine's init still aliases
    params = jax.tree_util.tree_map(
        np.asarray, dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_s)
    )
    batch = batch_for(cfg_s, b=8)
    opt = optax.adam(1e-3)

    init_s, step_s = make_train_step(dalle_loss(cfg_s), opt, mesh=None)
    _, m_s = step_s(init_s(params), batch, jax.random.PRNGKey(7))

    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=2, sp=1, pp=2))
    init_m, step_m = make_train_step(dalle_loss(cfg_p), opt, mesh=mesh)
    _, m_m = step_m(init_m(params), batch, jax.random.PRNGKey(7))

    np.testing.assert_allclose(float(m_s["loss"]), float(m_m["loss"]), rtol=2e-4)


@pytest.mark.slow
@pytest.mark.multichip
def test_composed_fsdp_sp_pp_matches_single_device():
    """The other three-axis composition: ZeRO-3 param sharding (fsdp=2) ×
    sequence parallelism (sp=2) × pipeline stages (pp=2) in one mesh —
    with the interleaved schedule on top (bubble ticks must still execute
    the seq-shard halo collectives on every device)."""
    cfg_s = _pp_cfg()
    cfg_p = _pp_cfg(pipeline_axis="pp", seq_shard_axis="sp", pp_interleave=2)
    params = jax.tree_util.tree_map(
        np.asarray, dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_s)
    )
    batch = batch_for(cfg_s, b=8)
    opt = optax.adam(1e-3)

    init_s, step_s = make_train_step(
        dalle_loss(cfg_s), opt, mesh=None, settings=StepSettings()
    )
    _, m_s = step_s(init_s(params), batch, jax.random.PRNGKey(7))

    mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=1, sp=2, pp=2))
    init_m, step_m = make_train_step(
        dalle_loss(cfg_p), opt, mesh=mesh, settings=StepSettings(zero_stage=3)
    )
    _, m_m = step_m(init_m(params), batch, jax.random.PRNGKey(7))

    np.testing.assert_allclose(float(m_s["loss"]), float(m_m["loss"]), rtol=2e-4)


def test_default_num_micro_uses_best_divisor():
    from dalle_pytorch_tpu.parallel.pipeline import default_num_micro

    assert default_num_micro(8, 2) == 4       # 2P sweet spot
    assert default_num_micro(8, 4) == 8       # 2P exactly
    assert default_num_micro(6, 4) == 6       # no multiple of P divides 6
    assert default_num_micro(3, 4) == 3       # batch < stages: largest divisor
    assert default_num_micro(12, 2) == 4      # prefers 2P over larger splits


@pytest.mark.slow
@pytest.mark.multichip
def test_pipeline_microbatches_get_distinct_keys():
    """The fold_micro hook must give each microbatch its own key stream —
    identical input rows in different microbatches produce different
    key-derived outputs (without folding they would be bit-identical)."""
    from dalle_pytorch_tpu.parallel.pipeline import pipeline_scan

    mesh = make_mesh(MeshConfig(dp=-1, fsdp=1, tp=1, sp=1, pp=2))
    depth, batch, d = 2, 4, 8
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(depth))
    x = jnp.ones((batch, d))  # every row identical

    def body(h, k):
        return h + jax.random.uniform(k, h.shape), None

    def fold(k_local, micro_id):
        return jax.vmap(lambda k: jax.random.fold_in(k, micro_id))(k_local)

    with mesh:
        out_folded = jax.jit(
            lambda x: pipeline_scan(body, x, keys, mesh, num_micro=2, fold_micro=fold)
        )(x)
        out_plain = jax.jit(
            lambda x: pipeline_scan(body, x, keys, mesh, num_micro=2)
        )(x)
    out_folded, out_plain = np.asarray(out_folded), np.asarray(out_plain)
    # microbatches are rows [0,1] and [2,3]
    assert not np.allclose(out_folded[0], out_folded[2])  # folded: distinct
    np.testing.assert_array_equal(out_plain[0], out_plain[2])  # unfolded: shared


@pytest.mark.slow
@pytest.mark.multichip
def test_pipeline_dropout_runs_and_is_deterministic():
    cfg = _pp_cfg(pipeline_axis="pp", attn_dropout=0.1, ff_dropout=0.1)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    batch = batch_for(cfg, b=8)
    mesh = make_mesh(MeshConfig(dp=-1, fsdp=1, tp=1, sp=1, pp=2))

    def loss(p, key):
        return dalle_mod.forward(
            p, cfg, batch["text"], batch["image_codes"], return_loss=True,
            key=key,
        )

    with mesh:
        l1 = float(jax.jit(loss)(params, jax.random.PRNGKey(7)))
        l2 = float(jax.jit(loss)(params, jax.random.PRNGKey(7)))
    assert np.isfinite(l1)
    assert l1 == l2  # same key -> same masks (deterministic replay)


def test_pipeline_without_mesh_falls_back():
    cfg = _pp_cfg(pipeline_axis="pp")
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    batch = batch_for(cfg, b=4)
    with pytest.warns(UserWarning, match="pipeline_axis"):
        loss = dalle_mod.forward(
            params, cfg, batch["text"], batch["image_codes"], return_loss=True
        )
    assert np.isfinite(float(loss))


def test_pipeline_rejects_reversible_execution():
    """pp with execution='reversible' must fail loudly: the reversible runner
    bypasses the scan path, so pp would silently replicate every stage."""
    cfg = _pp_cfg(pipeline_axis="pp", execution="reversible")
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), _pp_cfg())
    batch = batch_for(cfg, b=4)
    with pytest.raises(ValueError, match="reversible"):
        dalle_mod.forward(params, cfg, batch["text"], batch["image_codes"], return_loss=True)


def test_backend_registry_and_dummy():
    parser = argparse.ArgumentParser()
    parser = backend_mod.wrap_arg_parser(parser)
    args = parser.parse_args(["--distributed_backend", "none"])
    be = backend_mod.set_backend_from_args(args)
    be.initialize()
    assert be.get_world_size() == 1 and be.is_root_worker()
    assert not backend_mod.is_distributed
    be.check_batch_size(4)
    assert be.average_all(3.0) == 3.0

    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    state, step_fn, data, sched = be.distribute(
        loss_fn=dalle_loss(cfg), params=params, optimizer=optax.adam(1e-3),
        training_data="data", lr_scheduler="sched",
    )
    assert isinstance(state, TrainState) and data == "data" and sched == "sched"
    _, m = step_fn(state, batch_for(cfg), jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"]))


def test_backend_unknown_raises():
    ns = argparse.Namespace(distributed_backend="nccl")
    with pytest.raises(ValueError, match="unknown distributed backend"):
        backend_mod.set_backend_from_args(ns)


@pytest.mark.slow
@pytest.mark.multichip
def test_ring_attention_differentiable():
    """Ring attention must be trainable (grads flow through ppermute)."""
    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=4))
    b, h, n, d = 1, 2, 32, 8
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, n, d), jnp.float32) for i in range(3)
    )

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attend(q * d ** -0.5, k, v, mask=causal_mask(n)) ** 2)

    g_r = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_r, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=3e-5)


@pytest.mark.slow
@pytest.mark.multichip
def test_sequence_parallel_training_matches_single_device():
    """seq_shard_axis='sp': activations sharded over the sequence dim; the
    loss trajectory must match the unsharded run."""
    cfg_sp = tiny_cfg(seq_shard_axis="sp")
    cfg_sd = tiny_cfg()
    batch = batch_for(cfg_sd, b=4)
    opt = optax.adam(1e-3)

    init_s, step_s = make_train_step(dalle_loss(cfg_sd), opt, mesh=None)
    state_s = init_s(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_sd))
    _, m_s = step_s(state_s, batch, jax.random.PRNGKey(0))

    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=4))
    init_m, step_m = make_train_step(dalle_loss(cfg_sp), opt, mesh=mesh)
    state_m = init_m(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_sp))
    _, m_m = step_m(state_m, batch, jax.random.PRNGKey(0))

    np.testing.assert_allclose(float(m_s["loss"]), float(m_m["loss"]), rtol=2e-4)


@pytest.mark.slow
@pytest.mark.multichip
def test_ring_attention_grads_match_dense_8dev():
    """Ring-recompute backward (custom_vjp: the (q, do, lse, delta, dq)
    packet rotates, K/V stay local, probabilities rebuilt from the saved
    logsumexp) must match dense gradients at a full 8-device ring."""
    mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=1, sp=8))
    b, h, n, d = 1, 2, 32, 16
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(10 + i), (b, h, n, d), jnp.float32)
        for i in range(3)
    )

    # causal only: the non-causal backward is the same code minus the block
    # mask, and sp=4 non-causal is covered by test_ring_attention_non_causal
    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attend(q * d ** -0.5, k, v, mask=causal_mask(n)) ** 2)

    g_r = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_r, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5)


def test_sequence_parallel_ring_backend_matches_single_device():
    """attn_kernel='ring' + seq_shard_axis: full-attention layers run the
    explicit ppermute ring (O(n/P) memory fwd AND bwd via the ring-recompute
    VJP) inside the sharded train step; the loss must match the unsharded
    run."""
    cfg_ring = tiny_cfg(seq_shard_axis="sp", attn_kernel="ring",
                        rotary_emb=True, shift_tokens=True)
    cfg_sd = tiny_cfg(rotary_emb=True, shift_tokens=True)
    batch = batch_for(cfg_sd, b=4)
    opt = optax.adam(1e-3)

    init_s, step_s = make_train_step(dalle_loss(cfg_sd), opt, mesh=None)
    state_s = init_s(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_sd))
    state_s, m_s = step_s(state_s, batch, jax.random.PRNGKey(0))
    state_s, m_s2 = step_s(state_s, batch, jax.random.PRNGKey(1))

    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=4))
    init_m, step_m = make_train_step(dalle_loss(cfg_ring), opt, mesh=mesh)
    state_m = init_m(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_ring))
    state_m, m_m = step_m(state_m, batch, jax.random.PRNGKey(0))
    state_m, m_m2 = step_m(state_m, batch, jax.random.PRNGKey(1))

    np.testing.assert_allclose(float(m_s["loss"]), float(m_m["loss"]), rtol=2e-4)
    # second step compares post-update params transitively through the loss
    np.testing.assert_allclose(float(m_s2["loss"]), float(m_m2["loss"]), rtol=2e-4)


def test_plain_user_mesh_visible_to_model_code():
    """A user-built plain jax.sharding.Mesh (not a ContextMesh) passed to
    make_train_step must still be discoverable by model code — ring
    attention / pipeline engagement read active_mesh() (code-review
    regression guard for the thread-resources removal)."""
    import numpy as _np
    from jax.sharding import Mesh as PlainMesh

    from dalle_pytorch_tpu.parallel.mesh import MESH_AXES, active_mesh, mesh_context

    devs = _np.asarray(jax.devices()).reshape(2, 2, 1, 1, 2)
    plain = PlainMesh(devs, MESH_AXES)
    assert active_mesh() is None
    with mesh_context(plain):
        assert active_mesh() is plain
    assert active_mesh() is None

    # and end-to-end: the train step wrapper publishes it during dispatch
    cfg = tiny_cfg()
    init_fn, step_fn = make_train_step(dalle_loss(cfg), optax.sgd(1e-3), mesh=plain)
    state = init_fn(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg))
    _, m = step_fn(state, batch_for(cfg), jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"]))


def test_loss_scale_static_matches_unscaled():
    """A static loss scale must be numerically transparent: scaled-then-
    unscaled grads drive the same trajectory as no scaling (SURVEY §2.2
    fp16-parity mode)."""
    cfg = tiny_cfg()
    params = jax.tree_util.tree_map(
        np.asarray, dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    )
    batch = batch_for(cfg, b=4)
    opt = optax.sgd(1e-2)

    init_p, step_p = make_train_step(dalle_loss(cfg), opt, settings=StepSettings())
    init_s, step_s = make_train_step(
        dalle_loss(cfg), opt, settings=StepSettings(loss_scale=1024.0)
    )
    s_p, m_p = step_p(init_p(params), batch, jax.random.PRNGKey(1))
    s_s, m_s = step_s(init_s(params), batch, jax.random.PRNGKey(1))
    np.testing.assert_allclose(float(m_p["loss"]), float(m_s["loss"]), rtol=1e-5)
    assert float(m_s["loss_scale"]) == 1024.0 and int(m_s["skipped"]) == 0
    for a, b_ in zip(
        jax.tree_util.tree_leaves(s_p.params), jax.tree_util.tree_leaves(s_s.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)


def test_loss_scale_dynamic_overflow_skips_and_halves():
    """Dynamic scaling: a nonfinite gradient must skip the update entirely
    (params/moments untouched) and halve the scale; a clean step then
    applies normally at the reduced scale."""
    def loss_fn(p, batch, key):
        # second invocation produces a nonfinite loss (traced-safe: driven
        # by batch content, not python state)
        return jnp.sum(p["w"] ** 2) * batch["blow"]

    params = {"w": jnp.ones((4, 4))}
    init_fn, step_fn = make_train_step(
        loss_fn, optax.sgd(1e-2), settings=StepSettings(loss_scale="dynamic")
    )
    state = init_fn(jax.tree_util.tree_map(np.asarray, params))
    scale0 = float(state.opt_state[1]["loss_scale"])
    assert scale0 == 2.0 ** 15

    # overflow step: loss = inf
    state, m = step_fn(state, {"blow": jnp.asarray(jnp.inf)}, jax.random.PRNGKey(0))
    assert int(m["skipped"]) == 1
    assert float(state.opt_state[1]["loss_scale"]) == scale0 / 2
    np.testing.assert_array_equal(np.asarray(state.params["w"]), np.ones((4, 4)))

    # clean step at the reduced scale applies
    state, m = step_fn(state, {"blow": jnp.asarray(1.0)}, jax.random.PRNGKey(1))
    assert int(m["skipped"]) == 0
    assert float(state.opt_state[1]["loss_scale"]) == scale0 / 2
    assert not np.allclose(np.asarray(state.params["w"]), np.ones((4, 4)))


def test_loss_scale_growth_clamped_at_2_pow_24():
    """Dynamic scale growth must cap at 2^24: unbounded doubling every 2000
    clean steps eventually overflows the scale itself and wedges the
    skip-step branch into a permanent skip/halve/grow limit cycle."""
    def loss_fn(p, batch, key):
        return jnp.sum(p["w"] ** 2)

    init_fn, step_fn = make_train_step(
        loss_fn, optax.sgd(0.0), settings=StepSettings(loss_scale="dynamic")
    )
    state = init_fn({"w": jnp.ones((4,))})
    inner, _ = state.opt_state
    # one clean step away from a growth event, already at the ceiling
    ls = {"loss_scale": jnp.asarray(2.0 ** 24, jnp.float32),
          "good_steps": jnp.asarray(1999, jnp.int32)}
    state = TrainState(state.step, state.params, (inner, ls))
    state, m = step_fn(state, {}, jax.random.PRNGKey(0))
    assert int(m["skipped"]) == 0
    assert float(state.opt_state[1]["loss_scale"]) == 2.0 ** 24  # clamped
    assert int(state.opt_state[1]["good_steps"]) == 0  # growth event consumed


def test_context_mesh_unbalanced_exit_raises_descriptive():
    """__exit__ with no matching __enter__ must raise a descriptive
    RuntimeError, not an IndexError from the token stack."""
    mesh = make_mesh(MeshConfig())
    with mesh:
        pass
    with pytest.raises(RuntimeError, match="no matching __enter__"):
        mesh.__exit__(None, None, None)


def test_loss_scale_with_grad_accum_and_bf16_storage():
    """Loss scaling composes with microbatch accumulation and pure-bf16
    param storage (the full fp16-parity recipe in one step)."""
    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    batch = batch_for(cfg, b=8)
    init_fn, step_fn = make_train_step(
        dalle_loss(cfg), optax.adam(1e-3),
        settings=StepSettings(grad_accum=2, loss_scale="dynamic",
                              param_dtype=jnp.bfloat16),
    )
    state, m = step_fn(init_fn(params), batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"])) and int(m["skipped"]) == 0


def test_jax_set_mesh_discovered_inside_jit():
    """A plain jax.sharding.Mesh installed through jax's own plumbing
    (`jax.sharding.set_mesh`) is visible to active_mesh() — outside a trace
    and INSIDE one, where model code (ring attention, the flash shard_map
    wrap, pipeline engagement) asks.  A bare `with mesh:` on a plain Mesh is
    no longer discovered: that read jax's deprecated thread-resources state
    (removed in PR 21) — enter such a mesh through mesh_context()."""
    import numpy as _np
    from jax.sharding import Mesh as PlainMesh

    from dalle_pytorch_tpu.parallel.mesh import MESH_AXES, active_mesh

    devs = _np.asarray(jax.devices()).reshape(2, 2, 1, 1, 2)
    plain = PlainMesh(devs, MESH_AXES)
    assert active_mesh() is None
    with jax.sharding.set_mesh(plain):
        got = active_mesh()
        assert got is not None and dict(got.shape) == dict(plain.shape)
        seen = []
        jax.jit(lambda x: seen.append(active_mesh()) or x)(1.0)
        assert dict(seen[0].shape) == dict(plain.shape)
    with plain:
        assert active_mesh() is None
    assert active_mesh() is None


@pytest.mark.parametrize("pp,v,extra", [(2, 2, {}), (2, 2, {"pp_num_micro": 2}), (4, 1, {})])
@pytest.mark.slow
@pytest.mark.multichip
def test_interleaved_pipeline_matches_scan(pp, v, extra):
    """Circular/interleaved pipeline (v chunks per device, microbatches loop
    the ring v times) must reproduce the single-stage scan: loss AND grads —
    including the M == P same-tick wrap handoff (pp=2, num_micro=2)."""
    cfg_s = _pp_cfg()
    cfg_p = _pp_cfg(pipeline_axis="pp", pp_interleave=v, **extra)
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_s)
    batch = batch_for(cfg_s)

    def loss(cfg):
        def f(p):
            return dalle_mod.forward(p, cfg, batch["text"], batch["image_codes"], return_loss=True)
        return f

    l_s, g_s = jax.jit(jax.value_and_grad(loss(cfg_s)))(params)
    mesh = make_mesh(MeshConfig(dp=-1, fsdp=1, tp=1, sp=1, pp=pp))
    with mesh:
        l_p, g_p = jax.jit(jax.value_and_grad(loss(cfg_p)))(params)
        l_p, g_p = jax.device_get((l_p, g_p))
    np.testing.assert_allclose(float(l_s), float(l_p), rtol=1e-5)
    for a, b_ in zip(jax.tree_util.tree_leaves(g_s), jax.tree_util.tree_leaves(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-3, atol=2e-5)


@pytest.mark.slow
@pytest.mark.multichip
def test_ring_attention_with_pattern_matches_dense():
    """Static patterns ride the ring: axial pattern + causal over 8 devices,
    fwd AND grads vs dense (VERDICT r4 long-context: patterned layers no
    longer fall back to O(n^2) dense under sequence parallelism)."""
    from dalle_pytorch_tpu.ops.masks import build_pattern_mask

    mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=1, sp=8))
    fmap = 4
    n = 16 + fmap * fmap  # 32
    b, h, d = 2, 2, 16
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, n, d), jnp.float32)
        for i in range(3)
    )
    pattern = build_pattern_mask("axial_row", n, fmap)
    dense_mask = causal_mask(n)[None, None] & pattern[None, None]

    got = np.asarray(ring_attention(q, k, v, mesh, causal=True, mask=pattern))
    want = np.asarray(attend(q * d ** -0.5, k, v, mask=dense_mask))
    np.testing.assert_allclose(got, want, atol=3e-5)

    def loss_r(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True, mask=pattern) ** 2)

    def loss_d(q, k, v):
        return jnp.sum(attend(q * d ** -0.5, k, v, mask=dense_mask) ** 2)

    g_r = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_r, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5)


@pytest.mark.slow
@pytest.mark.multichip
def test_sequence_parallel_ring_with_patterned_cycle():
    """attn_kernel='ring' + a full+axial+conv attention cycle: every layer
    type stays on the ring path under sequence sharding, and the loss
    trajectory matches the unsharded run."""
    cfg_ring = tiny_cfg(seq_shard_axis="sp", attn_kernel="ring",
                        attn_types=("full", "axial_row", "conv_like"),
                        depth=3, rotary_emb=True, shift_tokens=True)
    cfg_sd = tiny_cfg(attn_types=("full", "axial_row", "conv_like"),
                      depth=3, rotary_emb=True, shift_tokens=True)
    batch = batch_for(cfg_sd, b=4)
    opt = optax.adam(1e-3)

    init_s, step_s = make_train_step(dalle_loss(cfg_sd), opt, mesh=None)
    state_s = init_s(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_sd))
    _, m_s = step_s(state_s, batch, jax.random.PRNGKey(0))

    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=4))
    init_m, step_m = make_train_step(dalle_loss(cfg_ring), opt, mesh=mesh)
    state_m = init_m(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg_ring))
    _, m_m = step_m(state_m, batch, jax.random.PRNGKey(0))

    np.testing.assert_allclose(float(m_s["loss"]), float(m_m["loss"]), rtol=2e-4)


def test_loss_scale_on_sharded_mesh():
    """Dynamic loss scaling composes with ZeRO-3 mesh sharding: the scale
    state rides beside the optimizer state through opt_state_specs and the
    sharded step, and the trajectory still matches the unsharded run."""
    cfg = tiny_cfg()
    params = jax.tree_util.tree_map(
        np.asarray, dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    )
    batch = batch_for(cfg)
    st = StepSettings(loss_scale="dynamic", zero_stage=3)

    init_s, step_s = make_train_step(dalle_loss(cfg), optax.adam(1e-3),
                                     settings=StepSettings(loss_scale="dynamic"))
    _, m_s = step_s(init_s(params), batch, jax.random.PRNGKey(0))

    mesh = make_mesh(MeshConfig(dp=4, fsdp=2))
    init_m, step_m = make_train_step(dalle_loss(cfg), optax.adam(1e-3),
                                     mesh=mesh, settings=st)
    state = init_m(params)
    state, m_m = step_m(state, batch, jax.random.PRNGKey(0))
    np.testing.assert_allclose(float(m_s["loss"]), float(m_m["loss"]), rtol=2e-4)
    assert float(m_m["loss_scale"]) == 2.0 ** 15 and int(m_m["skipped"]) == 0


@pytest.mark.multichip
def test_flash_kernels_shard_mapped_on_mesh(monkeypatch):
    """On a multi-device mesh the flash kernels run under shard_map (batch
    over dp/fsdp, heads over tp): the chip's compiler refuses a Mosaic
    kernel inside a GSPMD-partitioned program, and the CPU mesh takes the
    same wrap in interpret mode.  One train step on fsdp2 x tp2 (ZeRO-3) —
    a shared pattern on the dense grid and a per-head pattern with per-head
    compacted tables — must match the single-device step."""
    from dalle_pytorch_tpu.kernels import flash_attention as fa

    wraps = []
    wrap = fa._shard_over_mesh
    monkeypatch.setattr(fa, "_shard_over_mesh",
                        lambda *a: wraps.append(a[1]) or wrap(*a))
    cfg = tiny_cfg(
        text_seq_len=64, image_fmap_size=8, attn_kernel="flash",
        attn_types=("axial_row", "sparse"), sparse_per_head=True,
        sparse_block_size=16, rotary_emb=True,
    )
    assert cfg.total_seq_len == 128
    params = jax.tree_util.tree_map(
        np.asarray, dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg))
    batch = batch_for(cfg, b=4)

    init_1, step_1 = make_train_step(dalle_loss(cfg), optax.sgd(1e-2))
    s1, m1 = step_1(init_1(params), batch, jax.random.PRNGKey(1))
    assert not wraps  # no mesh, no wrap

    mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=2), devices=jax.devices()[:4])
    init_m, step_m = make_train_step(
        dalle_loss(cfg), optax.sgd(1e-2), mesh=mesh,
        settings=StepSettings(zero_stage=3))
    sm, mm = step_m(init_m(params), batch, jax.random.PRNGKey(1))
    assert wraps and all(m is mesh for m in wraps)

    np.testing.assert_allclose(float(mm["loss"]), float(m1["loss"]), rtol=1e-5)
    for a, b_ in zip(jax.tree_util.tree_leaves(s1.params),
                     jax.tree_util.tree_leaves(sm.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)
