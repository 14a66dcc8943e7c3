"""Sharded, jitted training steps.

Replaces the reference's backend-dispatched backward/step
(/root/reference/train_dalle.py:609-619 + the DeepSpeed/Horovod engines): one
jit-compiled function containing forward, backward, gradient accumulation
(lax.scan microbatching — SURVEY.md §2.3), optimizer update, and the loss
all-reduce.  Gradient reduction across data axes is emitted by XLA from the
sharding annotations; nothing here calls a collective explicitly.

Mixed precision is the TPU-native bf16 policy: master params and optimizer
state in f32, forward/backward compute in bf16, gradient accumulation in f32
(no loss scaling needed on TPU — replacing Apex AMP / fp16 engines)."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dalle_pytorch_tpu.core.pytree import cast_floating
from dalle_pytorch_tpu.observability import health as health_mod
from dalle_pytorch_tpu.parallel.mesh import BATCH_AXES
from dalle_pytorch_tpu.parallel.sharding import opt_state_specs, param_specs
from dalle_pytorch_tpu.training.resilience import nonfinite_guard

P = PartitionSpec


@dataclasses.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    opt_state: Any


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.step, s.params, s.opt_state), None),
    lambda _, c: TrainState(*c),
)


@dataclasses.dataclass(frozen=True)
class StepSettings:
    grad_accum: int = 1
    compute_dtype: Any = jnp.float32
    clip_grad_norm: Optional[float] = None
    zero_stage: int = 0
    # dtype gradients are kept in between backward and the optimizer update.
    # f32 is the safe default; bf16 halves the gradient buffer (the single-chip
    # memory wall for billion-parameter configs) and is sound with
    # scale-invariant optimizers like adafactor.  Accumulation across
    # microbatches always runs in f32.
    grad_dtype: Any = jnp.float32
    # Storage dtype for the params themselves.  None keeps whatever dtype the
    # caller initialized (f32 masters — the safe default).  jnp.bfloat16 is
    # the T5/mesh-tf recipe: NO f32 master copy exists (halves resident param
    # memory — the other single-chip wall at >1B params); optimizer math still
    # runs in f32 (casts fuse into the update), and the weight update applies
    # with STOCHASTIC rounding so sub-ulp updates (lr·rms ~1e-3 relative,
    # below bf16's 2^-8 ulp) accumulate in expectation instead of rounding
    # away.  Pair with adafactor (its f32 factored stats are O(rows+cols)).
    param_dtype: Any = None
    # None → stochastic rounding on iff param_dtype is low-precision.
    stochastic_round: Optional[bool] = None
    # fp16-style loss scaling for parity experiments (SURVEY §2.2: the
    # reference's DeepSpeed fp16 / Apex AMP path, train_dalle.py:485-491).
    # bf16 training on TPU does not need it — this exists so reference fp16
    # runs can be reproduced exactly.  None = off; a float = static scale;
    # "dynamic" = DeepSpeed-style dynamic scaling (start 2^15, halve on
    # nonfinite grads + skip the step, double after 2000 clean steps).
    loss_scale: Optional[Any] = None
    # Bad-step guard (training/resilience.py): skip the optimizer update
    # when the gradient norm is non-finite, so one poisoned batch cannot
    # write NaN into params and moments.  Previously this protection existed
    # only under loss_scale; None (default) enables it for every run —
    # bf16-without-scaling included.  False restores the unguarded update.
    skip_nonfinite: Optional[bool] = None


def _stochastic_round(x32: jnp.ndarray, key: jax.Array, dtype) -> jnp.ndarray:
    """Round f32 -> bf16 stochastically: add uniform random bits below the
    bf16 mantissa, then truncate.  P(round up) equals the fractional distance
    to the next representable value, so E[rounded] = x and tiny optimizer
    updates survive in expectation.  (Finite inputs only: +-inf would carry
    into the NaN space — params/updates are finite in any sane run.)"""
    assert dtype == jnp.bfloat16, "stochastic rounding implemented for bf16"
    bits = jax.lax.bitcast_convert_type(x32.astype(jnp.float32), jnp.uint32)
    rnd = jax.random.bits(key, x32.shape, jnp.uint32) & jnp.uint32(0xFFFF)
    bits = (bits + rnd) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(jnp.bfloat16)


def _apply_updates_lowp(params, updates, key, dtype, stochastic: bool):
    """params (low-precision) + updates (f32) -> new low-precision params."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    uleaves = treedef.flatten_up_to(updates)
    keys = jax.random.split(key, len(leaves))
    new = []
    for p, u, k in zip(leaves, uleaves, keys):
        if not jnp.issubdtype(p.dtype, jnp.floating):
            new.append(p)
            continue
        x32 = p.astype(jnp.float32) + u.astype(jnp.float32)
        if stochastic:
            new.append(_stochastic_round(x32, k, dtype))
        else:
            new.append(x32.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, new)


def _apply_param_rule(rule, old_params, new_params, rule_inputs: dict, finite):
    """`new_params` with each parameter `rule_inputs` names ("a/b/c": a path
    of dict keys) set to `rule(path, its value in old_params, its input)`;
    where `finite` (None = always) is false, to its old value."""
    def put(tree, keys, value):
        return value if not keys else {**tree, keys[0]: put(tree[keys[0]], keys[1:], value)}

    for path, rule_input in rule_inputs.items():
        keys = path.split("/")
        old = old_params
        for k in keys:
            old = old[k]
        new = rule(path, old, rule_input).astype(old.dtype)
        new_params = put(new_params, keys, new if finite is None else jnp.where(finite, new, old))
    return new_params


def make_train_step(
    loss_fn: Callable,  # (params, batch, key) -> scalar loss
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    settings: StepSettings = StepSettings(),
    pspecs: Any = None,
    registry: Any = None,
    param_rule: Optional[Callable] = None,
):
    """Build (init_fn, step_fn).

    `param_rule(path, value, rule_input) -> new value`: a loss may name
    parameters that no gradient trains and a rule moves once an optimizer
    step.  It names them in its aux under `rule_inputs`, {the parameter's
    path of dict keys in the tree, joined by "/": what its rule reads}, SUMMED over the
    step's microbatches; after the optimizer update each named parameter
    becomes `param_rule` of the value it had BEFORE the update and its input
    (so whatever the optimizer did to it, nothing for a zero gradient under
    Adam, is replaced), and a step the bad-step guard skips leaves it alone.

    `registry` (parallel/registry.PartitionRegistry, default the process
    default) is the ONE source of truth for where params and optimizer
    state live on the mesh — the same rule table checkpoint topology
    records and the analytic comms/memory ledgers are priced from.
    `pspecs` still overrides the param half for callers that hand-build
    specs.

    init_fn(params) -> TrainState (sharded when a mesh is given).
    `loss_fn` returns a scalar loss, or `(loss, aux)` with `aux` a dict of
    device scalars that land in `metrics` beside the loss (no new sync).
    step_fn(state, batch, key) -> (state, metrics); batch leaves have leading
    dim grad_accum * microbatch and are sharded over the data axes.

    step_fn additionally accepts a STATIC keyword `with_health=True` that
    compiles a second "diagnostic step" executable whose metrics carry a
    `health` pytree (observability/health.py: per-leaf grad/param/update
    norms, nonfinite localization vectors, activation taps from a probe
    forward).  The default executable's HLO is unchanged — diagnostics cost
    nothing except on the steps the caller asks for them."""

    ls_enabled = settings.loss_scale is not None
    ls_dynamic = settings.loss_scale == "dynamic"
    ls_init = 2.0 ** 15 if ls_dynamic else float(settings.loss_scale or 1.0)
    LS_GROWTH_INTERVAL = 2000
    # growth ceiling: past 2^24 the scale itself overflows bf16/f32 gradient
    # headroom — the first overflow then halves-and-skips, 2000 clean steps
    # double it back over the edge, and the skip-step branch wedges into a
    # permanent skip/halve/grow limit cycle.  DeepSpeed/AMP cap here too.
    LS_MAX = 2.0 ** 24

    lowp = settings.param_dtype is not None and jnp.dtype(settings.param_dtype).itemsize < 4
    sr = settings.stochastic_round if settings.stochastic_round is not None else lowp
    if lowp and jnp.dtype(settings.param_dtype) != jnp.dtype(jnp.bfloat16):
        raise ValueError(
            f"param_dtype {settings.param_dtype} not supported: low-precision "
            "param storage is implemented for bfloat16 (stochastic rounding)"
        )
    if settings.stochastic_round and not lowp:
        raise ValueError(
            "stochastic_round=True requires a low-precision param_dtype "
            f"(got param_dtype={settings.param_dtype})"
        )

    from dalle_pytorch_tpu.parallel.registry import default_registry

    reg = registry if registry is not None else default_registry()

    def init_fn(params):
        if settings.param_dtype is not None:
            # storage in param_dtype; optimizer state derives from the f32
            # view when storage is low-precision, so factored stats and any
            # full-shape moments stay f32 even though storage is bf16
            params = cast_floating(params, settings.param_dtype)
            opt_state = optimizer.init(cast_floating(params, jnp.float32) if lowp else params)
        else:
            opt_state = optimizer.init(params)
        if ls_enabled:
            # the scale rides beside the optimizer state so no TrainState /
            # checkpoint structure change is needed (it round-trips through
            # the same template restore as any other opt_state leaf)
            opt_state = (opt_state, {
                "loss_scale": jnp.asarray(ls_init, jnp.float32),
                "good_steps": jnp.zeros((), jnp.int32),
            })
        state = TrainState(jnp.zeros((), jnp.int32), params, opt_state)
        if mesh is None:
            return state
        ps = pspecs if pspecs is not None else param_specs(
            params, mesh, settings.zero_stage, registry=reg)
        os_specs = opt_state_specs(opt_state, mesh, settings.zero_stage,
                                   registry=reg)
        state_specs = TrainState(P(), ps, os_specs)
        return jax.tree_util.tree_map(
            lambda spec, leaf: jax.device_put(leaf, NamedSharding(mesh, spec)),
            state_specs,
            state,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )

    def grads_and_loss(params, batch, key, scale=None):
        """(grads, loss, aux): `aux` is the dict of device scalars a loss_fn
        may return beside its loss (`(loss, aux)`; {} from a plain one), the
        mean over the microbatches."""
        accum = settings.grad_accum
        compute_params = cast_floating(params, settings.compute_dtype)

        def fn(p, b, k):
            out = loss_fn(p, b, k)
            loss, aux = out if isinstance(out, tuple) else (out, {})
            if scale is not None:
                loss = loss * scale.astype(settings.compute_dtype)
            return loss, aux

        inv = None if scale is None else 1.0 / scale

        if accum == 1:
            (loss, aux), grads = jax.value_and_grad(fn, has_aux=True)(compute_params, batch, key)
            if inv is not None:
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype), grads
                )
                loss = loss * inv
            return cast_floating(grads, settings.grad_dtype), loss, aux

        micro = jax.tree_util.tree_map(
            lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]), batch
        )
        keys = jax.random.split(key, accum)

        def body(carry, mb_and_key):
            g_acc, l_acc = carry
            mb, k = mb_and_key
            (loss, aux), grads = jax.value_and_grad(fn, has_aux=True)(compute_params, mb, k)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, grads
            )
            return (g_acc, l_acc + loss), aux

        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (g, l), aux = jax.lax.scan(body, (zero, 0.0), (micro, keys))
        mean = (1.0 / accum) if inv is None else inv / accum
        g = jax.tree_util.tree_map(
            lambda x: (x * mean).astype(settings.grad_dtype), g
        )
        rule_inputs = aux.pop("rule_inputs", None)
        aux = jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), aux)
        if rule_inputs is not None:  # a rule reads the whole step's, not a microbatch's mean
            aux["rule_inputs"] = jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), rule_inputs)
        return g, l * mean, aux

    # allow schedules that consume the loss (e.g. reduce_on_plateau)
    optimizer = optax.with_extra_args_support(optimizer)

    def _health_outputs(state, batch, loss_key, grads, loss, new_params):
        """Diagnostic outputs (with_health=True executable only): per-leaf
        numerics plus an activation-tap probe — one extra PLAIN forward on
        the first microbatch under capture_taps().  The probe is separate
        from the differentiated forward because tap() must not record
        jax.grad's inner tracers (they would leak out of that trace)."""
        with jax.named_scope("health"):
            h = health_mod.tree_health(state.params, grads, new_params)
            h["loss_nonfinite"] = (~jnp.isfinite(loss)).astype(jnp.int32)
            accum = settings.grad_accum
            probe_batch = batch if accum == 1 else jax.tree_util.tree_map(
                lambda x: x[: x.shape[0] // accum], batch
            )
            with health_mod.capture_taps() as taps:
                probe_loss = loss_fn(
                    cast_floating(state.params, settings.compute_dtype),
                    probe_batch, loss_key,
                )
            h["taps"] = taps
            # taps from scan/remat inner traces are dropped (their tracers
            # cannot escape); the count makes the absence visible
            h["taps_dropped_inner_trace"] = jnp.asarray(
                health_mod.taps_skipped(), jnp.int32
            )
            h["probe_loss"] = probe_loss[0] if isinstance(probe_loss, tuple) else probe_loss
        return h

    def step_fn_inner(state: TrainState, batch, key, with_health: bool = False):
        if lowp:
            # reserve a rounding key BEFORE the loss consumes the stream
            key, round_key = jax.random.split(key)
        else:
            round_key = None
        if ls_enabled:
            inner_opt_state, ls = state.opt_state
            scale = ls["loss_scale"]
        else:
            inner_opt_state, ls, scale = state.opt_state, None, None
        # named scopes land in the HLO metadata, so these phases show up as
        # labelled regions in xprof/TensorBoard traces of the step
        with jax.named_scope("fwd_bwd"):
            grads, loss, aux = grads_and_loss(state.params, batch, key, scale=scale)
        rule_inputs = aux.pop("rule_inputs", {})
        if rule_inputs and param_rule is None:
            raise ValueError(f"the loss names parameters for a rule ({sorted(rule_inputs)}) "
                             "and make_train_step was given no param_rule")
        with jax.named_scope("grad_norm"):
            # norm in f32 regardless of grad_dtype (per-leaf fused reductions,
            # no f32 copy of the gradient buffer is materialized)
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads)
            ))
            if settings.clip_grad_norm is not None:
                factor = jnp.minimum(1.0, settings.clip_grad_norm / (gnorm + 1e-6))
                grads = jax.tree_util.tree_map(
                    lambda g: g * factor.astype(g.dtype), grads
                )
                gnorm = gnorm * factor  # the metric reports the applied norm

        @jax.named_scope("optimizer_update")
        def do_update(grads, opt_state, params, rk):
            if lowp:
                # optimizer math in f32 (the casts fuse into the update
                # kernels — no resident f32 copy); storage stays
                # low-precision via stochastic rounding
                updates, opt_state = optimizer.update(
                    cast_floating(grads, jnp.float32), opt_state,
                    cast_floating(params, jnp.float32), value=loss,
                )
                params = _apply_updates_lowp(
                    params, updates, rk, settings.param_dtype, sr
                )
            else:
                updates, opt_state = optimizer.update(
                    grads, opt_state, params, value=loss
                )
                params = optax.apply_updates(params, updates)
            return params, opt_state

        # bad-step guard (training/resilience.py): a nonfinite gradient
        # skips the update entirely — always on under loss scaling (the
        # fp16 overflow-skip semantics), and by default for every other run
        # too, so one poisoned batch cannot write NaN into params/moments
        guarded = ls_enabled or settings.skip_nonfinite is not False
        if guarded:
            finite = jnp.isfinite(gnorm)
            params, opt_state = nonfinite_guard(
                do_update, grads, inner_opt_state, state.params, round_key, finite
            )
        else:
            finite = None
            params, opt_state = do_update(
                grads, inner_opt_state, state.params, round_key
            )
        if rule_inputs:
            params = _apply_param_rule(param_rule, state.params, params, rule_inputs, finite)

        if not ls_enabled:
            new_state = TrainState(state.step + 1, params, opt_state)
            metrics = {**aux, "loss": loss, "grad_norm": gnorm}
            if guarded:
                metrics["skipped"] = (~finite).astype(jnp.int32)
            if with_health:
                metrics["health"] = _health_outputs(
                    state, batch, key, grads, loss, params
                )
            return new_state, metrics

        # loss-scale bookkeeping: halve on overflow, grow back on clean steps
        if ls_dynamic:
            good = jnp.where(finite, ls["good_steps"] + 1, 0)
            grow = good >= LS_GROWTH_INTERVAL
            new_scale = jnp.where(
                finite,
                jnp.where(grow, jnp.minimum(ls["loss_scale"] * 2.0, LS_MAX),
                          ls["loss_scale"]),
                jnp.maximum(ls["loss_scale"] * 0.5, 1.0),
            )
            good = jnp.where(grow, 0, good)
        else:
            new_scale = ls["loss_scale"]
            good = ls["good_steps"]
        new_ls = {"loss_scale": new_scale, "good_steps": good}
        new_state = TrainState(state.step + 1, params, (opt_state, new_ls))
        metrics = {
            **aux,
            "loss": loss, "grad_norm": gnorm,
            "loss_scale": new_scale,
            "skipped": (~finite).astype(jnp.int32),
        }
        if with_health:
            metrics["health"] = _health_outputs(
                state, batch, key, grads, loss, params
            )
        return new_state, metrics

    batch_sh = None if mesh is None else NamedSharding(mesh, P(BATCH_AXES))

    # the function's name is the program's in a trace: `jit_train_step`
    def train_step(state, batch, key, with_health: bool = False):
        if batch_sh is not None:
            batch = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, batch_sh), batch
            )
        return step_fn_inner(state, batch, key, with_health=with_health)

    if mesh is None:
        jitted_single = jax.jit(
            train_step, donate_argnums=0, static_argnames=("with_health",)
        )
        # donation introspection: the memory observability stack
        # (observability/memory.audit_donation) verifies that argument 0 —
        # the TrainState — was actually aliased by the compiled executable
        jitted_single.donate_argnums = (0,)
        jitted_single.settings = settings
        jitted_single.registry = reg
        return init_fn, jitted_single

    jitted = jax.jit(train_step, donate_argnums=0, static_argnames=("with_health",))

    def with_mesh_ctx(state, batch, key, with_health: bool = False):
        # mesh in context during trace + dispatch so models can use raw
        # PartitionSpec constraints (e.g. the transformer's seq_shard_axis);
        # mesh_context also publishes plain user-built Meshes to
        # active_mesh(), which ring attention / pipeline engagement read
        from dalle_pytorch_tpu.parallel.mesh import mesh_context

        with mesh_context(mesh):
            return jitted(state, batch, key, with_health=with_health)

    # telemetry reaches through the closure: observability.step_cost_analysis
    # lowers `.jitted` inside `.mesh`'s context for the XLA FLOPs cross-check,
    # and the comms ledger (observability/comms.py) prices the collectives
    # these settings made XLA emit
    with_mesh_ctx.jitted = jitted
    with_mesh_ctx.mesh = mesh
    with_mesh_ctx.settings = settings
    # the rule table the state was placed under — checkpoint topology
    # stamping and the ledger re-pricing read it back from the step_fn
    with_mesh_ctx.registry = reg
    # donation introspection for the memory stack's audit (argument 0, the
    # TrainState, must come back aliased from memory_analysis)
    with_mesh_ctx.donate_argnums = (0,)
    return init_fn, with_mesh_ctx
