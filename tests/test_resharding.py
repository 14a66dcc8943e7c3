"""Elastic resilience (ISSUE 6): the partitioning registry, topology-aware
checkpoints, and cross-mesh resume.

Four pillars:

* **Refactor safety net** — the declarative regex rules in
  parallel/registry.py must reproduce the OLD imperative `shard_specs`
  logic leaf-for-leaf (params AND optimizer state) on dp / fsdp-z1 / z3 /
  tp / pp meshes.  The reference implementation is embedded here verbatim
  (frozen at the pre-registry commit) so the parity claim survives further
  registry edits.
* **Reshard parity** — a live TrainState moved dp8 → tp4×dp2 → dp8 comes
  back bit-identical, and the memory preflight refuses targets that cannot
  fit BEFORE touching devices.
* **Topology error family** — checkpoints stamp their topology; validation
  under a different live topology raises ReshardRequired (distinct from
  the Truncated/Meta/MissingLeaves/FutureFormat family — `--resume auto`
  must NOT fall back past a perfectly good checkpoint that merely needs a
  reshard).
* **THE acceptance proof** — a run SIGKILLed via `--inject_fault shrink@4`
  on 8 CPU devices, resumed with `--resume auto` on 4, continues its loss
  trajectory (subprocess test; the same data stream is pinned on both
  sides with an explicit --batch_size).
"""
import json
import math
import signal
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models.dalle import DALLEConfig
from dalle_pytorch_tpu.models.vae import DiscreteVAEConfig
from dalle_pytorch_tpu.parallel import reshard as reshard_mod
from dalle_pytorch_tpu.parallel.mesh import (
    AXIS_FSDP,
    AXIS_PP,
    AXIS_TP,
    MeshConfig,
    make_mesh,
)
from dalle_pytorch_tpu.parallel.registry import (
    PartitionRegistry,
    Rule,
    default_registry,
    meshes_equal,
    normalize_mesh_axes,
    topology_meta,
)
from dalle_pytorch_tpu.parallel.sharding import opt_state_specs, param_specs
from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step
from dalle_pytorch_tpu.training import resilience
from dalle_pytorch_tpu.training.checkpoint import (
    save_checkpoint,
    topology_from_meta,
)

REPO = Path(__file__).resolve().parent.parent

P = PartitionSpec


# ---------------------------------------------------------------------------
# the FROZEN pre-registry implementation (parallel/sharding.py as of PR 5) —
# the parity reference.  Do not "fix" this copy: its whole value is that it
# does not change when the registry does.
# ---------------------------------------------------------------------------

def _legacy_path_str(path):
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _legacy_data_axes(mesh, include_fsdp):
    axes = []
    if include_fsdp and mesh.shape.get(AXIS_FSDP, 1) > 1:
        axes.append(AXIS_FSDP)
    if mesh.shape.get(AXIS_PP, 1) > 1:
        axes.append(AXIS_PP)
    return tuple(axes)


def _legacy_axes_prod(mesh, axes):
    return math.prod(mesh.shape[a] for a in axes)


def _legacy_shard_largest(leaf, axes, mesh, min_size=2 ** 14):
    if not axes or leaf.ndim == 0 or leaf.size < min_size:
        return P()
    candidates = [axes] if len(axes) == 1 else [axes, *[(a,) for a in axes]]
    dims = list(leaf.shape)
    order = sorted(range(len(dims)), key=lambda i: -dims[i])
    for cand in candidates:
        size = _legacy_axes_prod(mesh, cand)
        for i in order:
            if dims[i] % size == 0 and dims[i] >= size:
                spec = [None] * len(dims)
                spec[i] = cand if len(cand) > 1 else cand[0]
                return P(*spec)
    return P()


def _legacy_data_slot(dim_size, axes, mesh):
    best = None
    for end in range(1, len(axes) + 1):
        cand = axes[:end]
        if dim_size % _legacy_axes_prod(mesh, cand) == 0:
            best = cand
    if best is None:
        return None
    return best if len(best) > 1 else best[0]


def _legacy_tp_spec(path, leaf, data_axes, mesh):
    if leaf.ndim == 2:
        if "qkv/w" in path or "w1/w" in path or "w1g/w" in path:
            return P(_legacy_data_slot(leaf.shape[0], data_axes, mesh), AXIS_TP)
        if ("shared_attn" in path and "out/w" in path) or "w2/w" in path:
            return P(AXIS_TP, _legacy_data_slot(leaf.shape[1], data_axes, mesh))
        if "logits_linear/w" in path:
            return P(_legacy_data_slot(leaf.shape[0], data_axes, mesh), AXIS_TP)
    if leaf.ndim == 1:
        if "w1/b" in path or "w1g/b" in path or "logits_linear/b" in path:
            return P(AXIS_TP)
    return None


def _legacy_rule(path, leaf, mesh, zero_stage, tensor_parallel, params_sharded):
    axes = _legacy_data_axes(mesh, include_fsdp=params_sharded)
    if tensor_parallel:
        tp = _legacy_tp_spec(path, leaf, axes, mesh)
        if tp is not None:
            return tp
    return _legacy_shard_largest(leaf, axes, mesh)


def legacy_param_specs(params, mesh, zero_stage=0, tensor_parallel=None):
    if tensor_parallel is None:
        tensor_parallel = mesh.shape[AXIS_TP] > 1
    params_sharded = zero_stage >= 3 and mesh.shape[AXIS_FSDP] > 1

    def rule(path, leaf):
        return _legacy_rule(_legacy_path_str(path), leaf, mesh, zero_stage,
                            tensor_parallel, params_sharded)

    return jax.tree_util.tree_map_with_path(rule, params)


def legacy_opt_state_specs(opt_state, mesh, zero_stage=0, tensor_parallel=None):
    if tensor_parallel is None:
        tensor_parallel = mesh.shape[AXIS_TP] > 1
    params_sharded = zero_stage >= 3 and mesh.shape[AXIS_FSDP] > 1
    moments_sharded = zero_stage >= 1 and mesh.shape[AXIS_FSDP] > 1

    def rule(path, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return P()
        p = _legacy_path_str(path)
        spec = _legacy_rule(p, leaf, mesh, zero_stage, tensor_parallel,
                            params_sharded)
        if spec == P() and moments_sharded:
            return _legacy_shard_largest(
                leaf, _legacy_data_axes(mesh, include_fsdp=True), mesh)
        return spec

    return jax.tree_util.tree_map_with_path(rule, opt_state)


# ---------------------------------------------------------------------------
# fixtures: real DALLE trees (unrolled and scan-stacked), real adam states
# ---------------------------------------------------------------------------

def _dalle_params(scan_layers=False, depth=4):
    vae_cfg = DiscreteVAEConfig(
        image_size=32, num_tokens=512, codebook_dim=64, num_layers=2,
        num_resnet_blocks=0, hidden_dim=16,
    )
    cfg = DALLEConfig.from_vae(
        vae_cfg, dim=128, depth=depth, num_text_tokens=384, text_seq_len=16,
        heads=4, dim_head=32, scan_layers=scan_layers,
    )
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    return params, cfg


MESH_CASES = [
    # (mesh config, zero_stage) — the dp / fsdp-z1 / z3 / tp / pp coverage
    # the ISSUE names, plus a composed everything-at-once mesh
    (MeshConfig(dp=8), 0),
    (MeshConfig(dp=1, fsdp=8), 1),
    (MeshConfig(dp=1, fsdp=8), 3),
    (MeshConfig(dp=2, tp=4), 0),
    (MeshConfig(dp=2, pp=4), 0),
    (MeshConfig(dp=1, fsdp=2, tp=2, pp=2), 3),
]


@pytest.mark.parametrize("mesh_cfg,zero_stage", MESH_CASES)
def test_registry_reproduces_legacy_param_specs(mesh_cfg, zero_stage):
    """The refactor safety net: the declarative rules place every PARAM leaf
    exactly where the imperative code did — on unrolled AND scan-stacked
    trees (stacked 3-d weights must fall through the 2-d TP rules)."""
    mesh = make_mesh(mesh_cfg)
    for scan in (False, True):
        params, _ = _dalle_params(scan_layers=scan)
        got = param_specs(params, mesh, zero_stage=zero_stage)
        want = legacy_param_specs(params, mesh, zero_stage=zero_stage)
        paths = jax.tree_util.tree_flatten_with_path(params)[0]
        for (path, _), g, w in zip(
                paths, jax.tree_util.tree_leaves(
                    got, is_leaf=lambda x: isinstance(x, PartitionSpec)),
                jax.tree_util.tree_leaves(
                    want, is_leaf=lambda x: isinstance(x, PartitionSpec))):
            assert g == w, (
                f"placement changed for {_legacy_path_str(path)} on "
                f"{dict(mesh.shape)} z{zero_stage} scan={scan}: "
                f"registry {g} vs legacy {w}"
            )


@pytest.mark.parametrize("mesh_cfg,zero_stage", MESH_CASES)
def test_registry_reproduces_legacy_opt_specs(mesh_cfg, zero_stage):
    """...and every OPTIMIZER-STATE leaf (adam moments mirror param paths;
    the ZeRO-1 moments-shard-while-params-replicate extra must survive)."""
    mesh = make_mesh(mesh_cfg)
    params, _ = _dalle_params()
    opt_state = optax.adam(1e-3).init(params)
    got = opt_state_specs(opt_state, mesh, zero_stage=zero_stage)
    want = legacy_opt_state_specs(opt_state, mesh, zero_stage=zero_stage)
    gl = jax.tree_util.tree_leaves(
        got, is_leaf=lambda x: isinstance(x, PartitionSpec))
    wl = jax.tree_util.tree_leaves(
        want, is_leaf=lambda x: isinstance(x, PartitionSpec))
    assert gl == wl


def test_registry_fingerprint_stable_and_sensitive():
    reg = default_registry()
    assert reg.fingerprint() == reg.fingerprint()
    assert reg.fingerprint() == PartitionRegistry().fingerprint()
    edited = PartitionRegistry(rules=(
        Rule(r"qkv/w", ("tp", None), tp_only=True), *reg.rules))
    assert edited.fingerprint() != reg.fingerprint()
    # min_shard_size is part of the semantics, not cosmetic
    assert PartitionRegistry(min_shard_size=1).fingerprint() != reg.fingerprint()
    # ...but a note rewording IS cosmetic: documentation edits must not
    # flag every existing checkpoint as rules-changed
    renoted = PartitionRegistry(rules=tuple(
        Rule(r.pattern, r.spec, r.tp_only, note="reworded")
        for r in reg.rules))
    assert renoted.fingerprint() == reg.fingerprint()


def test_topology_meta_and_mesh_equality():
    topo = topology_meta({"dp": 8, "fsdp": 1, "tp": 1}, default_registry())
    assert topo["device_count"] == 8
    assert topo["mesh"] == {"dp": 8, "fsdp": 1, "tp": 1}
    assert meshes_equal(topo["mesh"], {"dp": 8})  # size-1 axes are identity
    assert not meshes_equal({"dp": 8}, {"dp": 2, "tp": 4})
    assert normalize_mesh_axes({"dp": 1, "tp": 1}) == {}


# ---------------------------------------------------------------------------
# live-state resharding
# ---------------------------------------------------------------------------

def _train_one_step(mesh, zero_stage=0):
    params, cfg = _dalle_params(depth=2)

    def loss_fn(p, batch, key):
        return dalle_mod.forward(p, cfg, batch["text"], batch["image"],
                                 return_loss=True, key=key)

    init_fn, step_fn = make_train_step(
        loss_fn, optax.adam(1e-3), mesh=mesh,
        settings=StepSettings(zero_stage=zero_stage))
    state = init_fn(params)
    batch = {
        "text": jnp.zeros((8, cfg.text_seq_len), jnp.int32),
        "image": jnp.zeros((8, cfg.image_seq_len), jnp.int32),
    }
    state, _ = step_fn(state, batch, jax.random.PRNGKey(1))
    return state


def test_reshard_round_trip_bit_identical():
    """dp8 → tp4×dp2 → dp8: a real post-step TrainState (params + adam
    moments + step counter) survives the round trip bit-for-bit."""
    mesh_a = make_mesh(MeshConfig(dp=8))
    state = _train_one_step(mesh_a)
    before = [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]

    mesh_b = make_mesh(MeshConfig(dp=2, tp=4))
    moved = reshard_mod.reshard_state(state, mesh_a, mesh_b)
    # the move actually re-lays TP-ruled leaves out over tp
    qkv = moved.params["transformer"]["shared_attn"]["0"]["qkv"]["w"]
    assert "tp" in str(qkv.sharding.spec)
    back = reshard_mod.reshard_state(moved, mesh_b, mesh_a)
    after = [np.asarray(x) for x in jax.tree_util.tree_leaves(back)]
    assert len(before) == len(after)
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)


def test_reshard_preflight_refuses_unfit_target():
    mesh_a = make_mesh(MeshConfig(dp=8))
    state = _train_one_step(mesh_a)
    mesh_b = make_mesh(MeshConfig(dp=2, tp=4))
    with pytest.raises(reshard_mod.ReshardPreflightError) as ei:
        reshard_mod.reshard_state(state, mesh_a, mesh_b, capacity_bytes=64.0)
    # the refusal carries the ledger it judged by, and nothing moved
    assert ei.value.ledger["fits"] is False
    assert ei.value.ledger["dominant"] in ("params", "grads", "opt_state")
    # a generous capacity passes
    moved = reshard_mod.reshard_state(
        state, mesh_a, mesh_b, capacity_bytes=1e12)
    assert moved.params is not state.params


def test_preflight_ledger_prices_exact_registry_fractions():
    """Ledger-vs-registry agreement: the preflight's param row IS
    tree_float_bytes x the registry's exact shard fraction (no scalar
    approximation in the loop), for every mesh in the matrix."""
    from dalle_pytorch_tpu.observability.comms import tree_float_bytes

    params, _ = _dalle_params()
    reg = default_registry()
    for axes, zero in [({"dp": 8}, 0), ({"fsdp": 8}, 3),
                       ({"dp": 2, "tp": 4}, 0), ({"dp": 2, "pp": 4}, 0)]:
        led = reshard_mod.reshard_preflight_ledger(
            params, None, axes, zero_stage=zero, registry=reg)
        frac = reg.shard_fraction(params, axes, zero)
        rows = {r["name"]: r["bytes"] for r in led["rows"]}
        assert rows["params"] == pytest.approx(
            tree_float_bytes(params) * frac)
        assert led["registry_fingerprint"] == reg.fingerprint()


def test_ledgers_repriced_from_registry_agree_with_scalar_model():
    """The analytic memory/comms ledgers priced from the registry stay
    within a sane band of the scalar rest_shard_fraction model on a real
    tree (the exact figure is >= the scalar one: small leaves do not
    shard), and the mem ledger's params row equals the registry pricing
    exactly — ledger and placement share one source of truth."""
    from dalle_pytorch_tpu.observability import comms as comms_mod
    from dalle_pytorch_tpu.observability import memory as mem_mod

    params, cfg = _dalle_params()
    reg = default_registry()
    axes = {"dp": 2, "tp": 2, "pp": 2}
    exact = reg.shard_fraction(params, axes, 0)
    scalar = mem_mod.rest_shard_fraction(axes, 0)
    assert scalar <= exact <= 3.0 * scalar

    led = mem_mod.dalle_step_memory(axes, params, None, cfg, 16,
                                    registry=reg)
    rows = {r["name"]: r["bytes"] for r in led["rows"]}
    assert rows["params"] == pytest.approx(
        comms_mod.tree_float_bytes(params) * exact)

    cled = comms_mod.dalle_step_comms(axes, params, cfg, 16, registry=reg)
    dp_row = next(r for r in cled["per_axis"] if r["axis"] == "dp")
    grad_local = comms_mod.tree_float_bytes(params, itemsize=4) * exact
    assert dp_row["bytes_per_step"] == pytest.approx(
        comms_mod.ring_all_reduce_bytes(grad_local, 2))


# ---------------------------------------------------------------------------
# topology error family: ReshardRequired beside the invalid-checkpoint family
# ---------------------------------------------------------------------------

def _save_with_topology(path, axes, global_step=7):
    meta = {"epoch": 0, "global_step": global_step,
            "topology": topology_meta(axes)}
    save_checkpoint(str(path),
                    trees={"weights": {"w": jnp.arange(8.0)}}, meta=meta)


def test_validate_raises_reshard_required_on_topology_change(tmp_path):
    p = tmp_path / "t.npz"
    _save_with_topology(p, {"dp": 8})
    live = topology_meta({"dp": 2, "tp": 4})
    # same topology: clean pass
    resilience.validate_checkpoint(
        str(p), expect_topology=topology_meta({"dp": 8}))
    with pytest.raises(resilience.ReshardRequired) as ei:
        resilience.validate_checkpoint(str(p), expect_topology=live)
    err = ei.value
    assert err.saved["mesh"] == {"dp": 8}
    assert not err.rules_changed  # same registry, different shape
    # the distinction that keeps auto-resume honest: a reshardable
    # checkpoint is NOT an invalid one
    assert not isinstance(err, resilience.CheckpointInvalidError)
    # a registry-fingerprint change IS flagged as a rules change
    meta = topology_from_meta(resilience.validate_checkpoint(str(p)))
    live2 = dict(topology_meta({"dp": 8}))
    live2["registry_fingerprint"] = "deadbeefdeadbeef"
    with pytest.raises(resilience.ReshardRequired) as ei2:
        resilience.check_topology({"topology": meta}, live2, path=str(p))
    assert ei2.value.rules_changed


def test_auto_resume_does_not_skip_reshardable_checkpoints(tmp_path):
    """find_latest_valid_checkpoint must return a topology-mismatched
    checkpoint (the CLI reshards it) — only genuinely broken files are
    fallen past."""
    out = tmp_path / "run.pt"
    _save_with_topology(tmp_path / "run_step5.npz", {"dp": 8}, global_step=6)
    found, meta = resilience.find_latest_valid_checkpoint(str(out))
    assert found == str(tmp_path / "run_step5.npz")
    assert topology_from_meta(meta)["mesh"] == {"dp": 8}
    # pre-topology checkpoints (no record) restore as before: no error
    assert resilience.check_topology(meta={"x": 1},
                                     live_topology=topology_meta({"dp": 4})) is None


def test_validate_orbax_directory_shapes(tmp_path):
    """Directory checkpoints validate structurally: a real-looking orbax
    layout passes, a torn one raises the distinct error classes."""
    d = tmp_path / "run_step4.npz"  # the CLI's sharded paths keep .npz names
    (d / "state").mkdir(parents=True)
    with pytest.raises(resilience.CheckpointMetaError, match="meta.json"):
        resilience.validate_checkpoint(str(d))
    (d / "meta.json").write_text(json.dumps(
        {"global_step": 5, "topology": topology_meta({"dp": 8})}))
    meta = resilience.validate_checkpoint(str(d))
    assert meta["global_step"] == 5
    with pytest.raises(resilience.ReshardRequired):
        resilience.validate_checkpoint(
            str(d), expect_topology=topology_meta({"dp": 2}))
    empty = tmp_path / "empty_step1.npz"
    empty.mkdir()
    with pytest.raises(resilience.TruncatedCheckpointError, match="state"):
        resilience.validate_checkpoint(str(empty))
    # ...and discovery ranks the directory like any stepped candidate
    found, _ = resilience.find_latest_valid_checkpoint(str(tmp_path / "run.pt"))
    assert found == str(d)


def test_validate_orbax_directory_rejects_missing_vae_sidecar(tmp_path):
    """A directory whose meta declares a VAE sidecar (vae_class_name) but
    has no vae.npz was torn mid-save (pre-commit-marker write ordering, or
    an incomplete copy): validation must fail it — TruncatedCheckpointError,
    so --resume auto falls back to an older checkpoint — instead of letting
    the restore crash on the missing file."""
    d = tmp_path / "run_step7.npz"
    (d / "state").mkdir(parents=True)
    (d / "meta.json").write_text(json.dumps(
        {"global_step": 8, "vae_class_name": "DiscreteVAE"}))
    with pytest.raises(resilience.TruncatedCheckpointError, match="vae.npz"):
        resilience.validate_checkpoint(str(d))
    # with the sidecar present the same directory validates
    save_checkpoint(str(d / "vae.npz"), trees={"vae_weights": {}},
                    meta={"vae_class_name": "DiscreteVAE"})
    assert resilience.validate_checkpoint(str(d))["global_step"] == 8
    # and discovery falls back past the torn variant to an intact npz
    (d / "vae.npz").unlink()
    _save_with_topology(tmp_path / "run_step5.npz", {"dp": 8}, global_step=6)
    found, meta = resilience.find_latest_valid_checkpoint(
        str(tmp_path / "run.pt"))
    assert found == str(tmp_path / "run_step5.npz")
    assert meta["global_step"] == 6


def test_rollback_screen_falls_past_orbax_dirs_to_npz(tmp_path):
    """The finite (rollback) screen cannot read orbax shards: a sharded
    directory ranking newest must be REJECTED under check_finite so the
    rollback lands on the newest npz it can actually read — not crash the
    whole run with np.load(<directory>)."""
    d = tmp_path / "run_step9.npz"
    (d / "state").mkdir(parents=True)
    (d / "meta.json").write_text(json.dumps({"global_step": 10}))
    _save_with_topology(tmp_path / "run_step5.npz", {"dp": 8}, global_step=6)
    with pytest.raises(resilience.CheckpointInvalidError, match="finite"):
        resilience.validate_checkpoint(str(d), check_finite=True)
    # plain (auto-resume) validation still accepts the directory...
    assert resilience.validate_checkpoint(str(d))["global_step"] == 10
    # ...but the rollback discovery falls past it to the readable npz
    found, meta = resilience.find_latest_valid_checkpoint(
        str(tmp_path / "run.pt"), check_finite=True)
    assert found == str(tmp_path / "run_step5.npz")
    assert meta["global_step"] == 6


def test_shrink_grow_fault_kinds_parse():
    f = resilience.parse_fault("shrink@4")
    assert f.kind == "shrink" and f.step == 4
    assert resilience.parse_fault("grow@2").kind == "grow"


# ---------------------------------------------------------------------------
# THE acceptance proof: SIGKILL on 8 devices, resume on 4, loss continuity
# ---------------------------------------------------------------------------

def _import_chaos():
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import chaos
    finally:
        sys.path.pop(0)
    return chaos


def _run_cli(cli_args, cwd, devices, timeout=240):
    # one subprocess launch recipe, shared with tools/chaos.py (the elastic
    # drill's engine) — the env scrub lives there, not in two copies
    return _import_chaos()._run_train(cli_args, cwd, devices, timeout=timeout)


def _losses(metrics_jsonl):
    out = {}
    for line in open(metrics_jsonl):
        rec = json.loads(line)
        if "loss" in rec:
            out[rec["step"]] = rec["loss"]  # later records win (resume re-log)
    return out


# --batch_size pinned so the 8-device and 4-device runs consume the SAME
# synthetic batch stream (dummy_run otherwise scales it with device count)
_DUMMY = ["--dummy_run", "8", "--telemetry", "off", "--log_every_n_steps",
          "1", "--batch_size", "8"]


@pytest.mark.slow  # tier-1 budget: the mechanisms stay fast via
#                    test_reshard_round_trip_bit_identical (the reshard math),
#                    test_validate_raises_reshard_required_on_topology_change
#                    (detection), and test_auto_resume_does_not_skip_
#                    reshardable_checkpoints (selection); this leg is the
#                    two-subprocess end-to-end stitch
def test_shrink_at_step_n_and_resume_on_fewer_devices(tmp_path):
    """THE acceptance proof: `--inject_fault shrink@4` SIGKILLs a dp8 run;
    `--resume auto` on FOUR devices detects the topology change
    (ReshardRequired → elastic reshard), and the stitched loss trajectory
    continues the uninterrupted 8-device run's within tolerance (the same
    batches flow; only the reduction layout changed)."""
    # uninterrupted 8-device reference
    a = _run_cli(
        [*_DUMMY, "--save_every_n_steps", "0",
         "--dalle_output_file_name", str(tmp_path / "A")], tmp_path, 8,
    )
    assert a.returncode == 0, a.stderr[-2000:]
    ref = _losses(tmp_path / "A.metrics.jsonl")
    assert sorted(ref) == list(range(8))

    # the shrink drill: checkpoint every step, SIGKILL self at step 4
    b = _run_cli(
        [*_DUMMY, "--save_every_n_steps", "1",
         "--inject_fault", "shrink@4",
         "--dalle_output_file_name", str(tmp_path / "B")], tmp_path, 8,
    )
    assert b.returncode == -signal.SIGKILL, (b.returncode, b.stderr[-2000:])
    assert "shrink drill" in b.stdout

    # relaunch on HALF the devices: --resume auto must reshard, not fail
    c = _run_cli(
        [*_DUMMY, "--save_every_n_steps", "0", "--resume", "auto",
         "--dalle_output_file_name", str(tmp_path / "B")], tmp_path, 4,
    )
    assert c.returncode == 0, c.stderr[-2000:]
    assert "saved under a different topology" in c.stdout
    assert "resharding onto the live mesh" in c.stdout
    assert "--resume auto: resuming from" in c.stdout

    got = _losses(tmp_path / "B.metrics.jsonl")
    assert sorted(got) == list(range(8))
    for step in range(8):
        # bitwise-or-tolerance: the replayed steps run on a different
        # device layout, so reduction order may differ at float epsilon
        assert got[step] == pytest.approx(ref[step], rel=1e-4), (
            f"loss diverged at step {step}: shrunk-resume {got[step]} vs "
            f"uninterrupted {ref[step]}"
        )
    # the resumed run's checkpoints carry the NEW topology
    found, meta = resilience.find_latest_valid_checkpoint(
        str(tmp_path / "B.pt"))
    if found is not None and topology_from_meta(meta):
        assert topology_from_meta(meta)["mesh"].get("dp") in (4, 8)


@pytest.mark.slow
def test_chaos_elastic_grow_drill(tmp_path):
    """The tools/chaos.py `elastic` driver end to end, in the GROW
    direction (4 → 8 devices)."""
    chaos = _import_chaos()
    rc = chaos.elastic_drill(devices=4, resume_devices=8, step=4, steps=8,
                             batch_size=8, workdir=str(tmp_path / "drill"))
    assert rc == 0
