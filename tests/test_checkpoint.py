import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.training.checkpoint import (
    load_checkpoint,
    rotate_checkpoints,
    save_checkpoint,
    to_host,
)


def test_roundtrip_trees_and_meta(tmp_path):
    trees = {
        "weights": {"a": jnp.arange(6.0).reshape(2, 3), "nested": [{"b": jnp.ones(4)}]},
        "opt_state": (jnp.zeros(3), {"mu": jnp.full((2, 2), 2.0)}),
    }
    meta = {"hparams": {"dim": 64, "attn_types": ["full", "axial_row"]}, "epoch": 3,
            "version": "0.1.0", "vae_class_name": "DiscreteVAE", "scheduler_state": None}
    path = tmp_path / "ckpt.pt"
    save_checkpoint(str(path), trees, meta)

    loaded, meta2 = load_checkpoint(str(path))
    assert meta2 == meta
    np.testing.assert_array_equal(np.asarray(loaded["weights"]["a"]), np.arange(6.0).reshape(2, 3))
    np.testing.assert_array_equal(np.asarray(loaded["weights"]["nested"][0]["b"]), np.ones(4))
    np.testing.assert_array_equal(np.asarray(loaded["opt_state"][1]["mu"]), np.full((2, 2), 2.0))


def test_roundtrip_bf16_leaves(tmp_path):
    """npz has no bfloat16: bf16 leaves (param_dtype=bfloat16 checkpoints)
    round-trip bit-exactly via the uint bit-view + dtype sidecar."""
    trees = {
        "weights": {
            "w": jnp.asarray([[1.5, -2.25], [3.0, 0.007812]], jnp.bfloat16),
            "scalar": jnp.asarray(2.5, jnp.bfloat16),  # 0-d must survive too
            "f32": jnp.ones((3,), jnp.float32),
            "step": jnp.asarray(7, jnp.int32),
        }
    }
    path = tmp_path / "bf16.pt"
    save_checkpoint(str(path), trees, {"epoch": 0})
    loaded, _ = load_checkpoint(str(path))
    w = loaded["weights"]
    assert w["w"].dtype == jnp.bfloat16 and w["w"].shape == (2, 2)
    assert w["scalar"].dtype == jnp.bfloat16 and w["scalar"].shape == ()
    assert w["f32"].dtype == np.float32 and w["step"].dtype == np.int32
    np.testing.assert_array_equal(
        np.asarray(w["w"], np.float32), np.asarray(trees["weights"]["w"], np.float32)
    )
    assert float(np.asarray(w["scalar"], np.float32)) == 2.5
    # jax must accept the restored leaves directly (the original failure mode:
    # void-dtype arrays out of npz broke jit argument interpretation)
    jnp.asarray(w["w"]) + 1


def test_format_version_stamped_and_checked(tmp_path):
    """New files carry FORMAT_VERSION; a file newer than the loader fails
    loudly (ADVICE r3: old loaders must not silently return uint16 bit-views),
    and legacy files without the stamp still load (treated as v1)."""
    from dalle_pytorch_tpu.training import checkpoint as ck

    path = tmp_path / "v.pt"
    save_checkpoint(str(path), {"w": {"x": jnp.ones(2)}}, {"epoch": 0})
    with np.load(str(path)) as data:
        assert int(data["__format"]) == ck.FORMAT_VERSION

    # future-format file: loader must reject, not mis-read
    with np.load(str(path)) as data:
        payload = {k: data[k] for k in data.files}
    payload["__format"] = np.array(ck.FORMAT_VERSION + 1, dtype=np.int64)
    future = tmp_path / "future.pt"
    with open(future, "wb") as f:
        np.savez(f, **payload)
    with pytest.raises(ValueError, match="format version"):
        load_checkpoint(str(future))

    # pre-stamp legacy file (no __format key, pickled treedef) loads as v1
    import json as _json
    import pickle as _pickle

    tree = {"x": np.ones(2, np.float32)}
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    legacy_payload = {
        "__meta": np.frombuffer(_json.dumps({"epoch": 0}).encode(), dtype=np.uint8),
        "__treedef_w": np.frombuffer(_pickle.dumps(treedef), dtype=np.uint8),
        "__dtypes_w": np.frombuffer(_json.dumps(["float32"]).encode(), dtype=np.uint8),
        "w:0": leaves[0],
    }
    legacy = tmp_path / "legacy.pt"
    with open(legacy, "wb") as f:
        np.savez(f, **legacy_payload)
    # legacy formats unpickle their treedefs — loading them now requires the
    # explicit trusted-source opt-in (format-downgrade hole)
    with pytest.raises(ValueError, match="allow_legacy_pickle"):
        load_checkpoint(str(legacy))
    loaded, meta = load_checkpoint(str(legacy), allow_legacy_pickle=True)
    assert meta["epoch"] == 0
    np.testing.assert_array_equal(np.asarray(loaded["w"]["x"]), np.ones(2))

    # v2 file (stamped, pickled treedef) also still loads with the opt-in
    legacy_payload["__format"] = np.array(2, dtype=np.int64)
    v2 = tmp_path / "v2.pt"
    with open(v2, "wb") as f:
        np.savez(f, **legacy_payload)
    with pytest.raises(ValueError, match="legacy v2"):
        load_checkpoint(str(v2))
    loaded, _ = load_checkpoint(str(v2), allow_legacy_pickle=True)
    np.testing.assert_array_equal(np.asarray(loaded["w"]["x"]), np.ones(2))


def test_v3_loads_without_pickle(tmp_path, monkeypatch):
    """VERDICT r4 weak #6: the v3 format must be safe on untrusted files —
    loading must never unpickle (arbitrary code execution vector)."""
    import pickle

    import optax

    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros(4)}
    opt_state = optax.adam(1e-3).init(params)  # namedtuple nodes
    path = tmp_path / "safe.pt"
    save_checkpoint(
        str(path), {"weights": params, "opt_state": to_host(opt_state)}, {"epoch": 1}
    )

    def boom(*a, **k):
        raise AssertionError("pickle.loads called during v3 load")

    monkeypatch.setattr(pickle, "loads", boom)
    loaded, meta = load_checkpoint(str(path))
    assert meta["epoch"] == 1
    # weights: pure-container tree, exact structure back
    np.testing.assert_array_equal(np.asarray(loaded["weights"]["w"]), np.ones((4, 4)))
    # optimizer state: library node types -> TreeBundle + template restore
    from dalle_pytorch_tpu.training.checkpoint import TreeBundle, unflatten_like

    assert isinstance(loaded["opt_state"], TreeBundle)
    restored = unflatten_like(opt_state, loaded["opt_state"])
    assert jax.tree_util.tree_structure(restored) == jax.tree_util.tree_structure(opt_state)
    for a, b in zip(jax.tree_util.tree_leaves(restored), jax.tree_util.tree_leaves(opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unflatten_like_rejects_mismatched_template(tmp_path):
    """A checkpoint from a different optimizer must fail loudly, not silently
    transpose leaves into the wrong slots."""
    import optax

    from dalle_pytorch_tpu.training.checkpoint import unflatten_like

    params = {"w": jnp.ones((4, 4))}
    opt_state = optax.adam(1e-3).init(params)
    path = tmp_path / "adam.pt"
    save_checkpoint(str(path), {"opt_state": to_host(opt_state)}, {})
    loaded, _ = load_checkpoint(str(path))
    wrong_template = optax.sgd(1e-3, momentum=0.9).init(params)
    with pytest.raises(ValueError, match="template"):
        unflatten_like(wrong_template, loaded["opt_state"])


def test_atomic_overwrite(tmp_path):
    path = tmp_path / "c.pt"
    save_checkpoint(str(path), {"w": {"x": jnp.zeros(2)}}, {"v": 1})
    save_checkpoint(str(path), {"w": {"x": jnp.ones(2)}}, {"v": 2})
    loaded, meta = load_checkpoint(str(path))
    assert meta["v"] == 2
    np.testing.assert_array_equal(np.asarray(loaded["w"]["x"]), np.ones(2))


def test_rotation(tmp_path):
    import time

    for i in range(5):
        save_checkpoint(str(tmp_path / f"m_step{i}.npz"), {"w": {"x": jnp.zeros(1)}}, {})
        time.sleep(0.01)
    rotate_checkpoints(str(tmp_path), "m_step*.npz", keep_n=2)
    left = sorted(p.name for p in tmp_path.glob("m_step*.npz"))
    assert left == ["m_step3.npz", "m_step4.npz"]


def test_sharded_cross_mesh_restore(tmp_path):
    """ZeRO-3 train on an 8-device mesh -> orbax save (no host gather) ->
    restore onto a 4-device mesh: sharding is a property of the restore mesh,
    not the file (SURVEY §5).  The restored state must be numerically
    identical, laid out on the new mesh, and usable for further steps."""
    pytest.importorskip("orbax.checkpoint")
    import optax

    from dalle_pytorch_tpu.parallel.mesh import AXIS_FSDP, MeshConfig, make_mesh
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step
    from dalle_pytorch_tpu.training.checkpoint import load_sharded, save_sharded

    def loss_fn(p, batch, key):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    # host-side copies: the donating step_fn would otherwise delete the
    # device buffers these alias, breaking the second init below
    params = jax.tree_util.tree_map(np.asarray, {
        "w": jax.random.normal(jax.random.PRNGKey(0), (128, 128)) * 0.02,
        "b": jnp.zeros((128,)),
    })
    batch = {
        "x": jax.random.normal(jax.random.PRNGKey(1), (8, 128)),
        "y": jax.random.normal(jax.random.PRNGKey(2), (8, 128)),
    }
    settings = StepSettings(zero_stage=3)

    mesh8 = make_mesh(MeshConfig(dp=2, fsdp=4))
    init8, step8 = make_train_step(loss_fn, optax.adam(1e-2), mesh=mesh8, settings=settings)
    state8, _ = step8(init8(params), batch, jax.random.PRNGKey(3))
    # params actually sharded over fsdp on the big mesh (not a trivial case)
    assert len(state8.params["w"].sharding.device_set) > 1
    save_sharded(str(tmp_path / "ck"),
                 {"step": state8.step, "weights": state8.params, "opt_state": state8.opt_state},
                 {"epoch": 2})

    mesh4 = make_mesh(MeshConfig(dp=1, fsdp=4), devices=jax.devices()[:4])
    init4, step4 = make_train_step(loss_fn, optax.adam(1e-2), mesh=mesh4, settings=settings)
    state4 = init4(params)
    restored, meta = load_sharded(
        str(tmp_path / "ck"),
        {"step": state4.step, "weights": state4.params, "opt_state": state4.opt_state},
    )
    assert meta["epoch"] == 2
    # restored onto the 4-device mesh, still fsdp-sharded there
    w = restored["weights"]["w"]
    assert w.sharding.mesh.shape[AXIS_FSDP] == 4
    assert len(w.sharding.device_set) == 4
    np.testing.assert_array_equal(np.asarray(w), np.asarray(state8.params["w"]))
    for a, b in zip(
        jax.tree_util.tree_leaves(restored["opt_state"]),
        jax.tree_util.tree_leaves(state8.opt_state),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and trainable: one more step on the small mesh from the restored state
    from dalle_pytorch_tpu.parallel.train_step import TrainState

    state4b, m = step4(
        TrainState(restored["step"], restored["weights"], restored["opt_state"]),
        batch, jax.random.PRNGKey(4),
    )
    assert np.isfinite(float(m["loss"]))
    assert int(state4b.step) == 2


def test_sharded_weights_only_restore(tmp_path):
    """ADVICE r4: inference restore must not materialize optimizer moments —
    `only=('weights',)` builds its template from checkpoint metadata and
    partial-restores just the weights (+ nothing else)."""
    pytest.importorskip("orbax.checkpoint")
    from dalle_pytorch_tpu.training.checkpoint import load_sharded, save_sharded

    state = {
        "step": jnp.asarray(5),
        "weights": {"w": jnp.full((8, 8), 2.0)},
        "opt_state": {"mu": jnp.zeros((8, 8)), "nu": jnp.zeros((8, 8))},
    }
    save_sharded(str(tmp_path / "ck"), state, {"epoch": 9})
    restored, meta = load_sharded(str(tmp_path / "ck"), only=("weights",))
    assert meta["epoch"] == 9
    assert set(restored) == {"weights"}
    np.testing.assert_array_equal(np.asarray(restored["weights"]["w"]), np.full((8, 8), 2.0))
    with pytest.raises(KeyError, match="no items"):
        load_sharded(str(tmp_path / "ck"), only=("nope",))


def test_sharded_roundtrip(tmp_path):
    """orbax sharded save/restore re-shards onto the current mesh."""
    pytest.importorskip("orbax.checkpoint")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dalle_pytorch_tpu.parallel.mesh import MeshConfig, make_mesh
    from dalle_pytorch_tpu.training.checkpoint import load_sharded, save_sharded

    mesh = make_mesh(MeshConfig(dp=8))
    sharding = NamedSharding(mesh, P("dp"))
    state = {"w": jax.device_put(jnp.arange(16.0), sharding)}
    save_sharded(str(tmp_path / "ck"), state, {"epoch": 1})

    template = {"w": jax.device_put(jnp.zeros(16), sharding)}
    restored, meta = load_sharded(str(tmp_path / "ck"), template)
    assert meta["epoch"] == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(16.0))
    assert restored["w"].sharding == sharding


def test_rotation_orders_by_step_number_not_mtime(tmp_path):
    """ISSUE 3 satellite: rotation must parse the step from the filename —
    mtime lies under clock skew or a `cp` restore, and evicting the NEWEST
    checkpoint would destroy the resume point."""
    import os
    import time

    for i in (1, 2, 10, 20):  # 10 > 2 numerically, though "10" < "2" lexically
        save_checkpoint(str(tmp_path / f"m_step{i}.npz"), {"w": {"x": jnp.zeros(1)}}, {})
    # clock skew: the OLDEST step gets the newest mtime
    now = time.time()
    os.utime(tmp_path / "m_step1.npz", (now + 3600, now + 3600))
    rotate_checkpoints(str(tmp_path), "m_step*.npz", keep_n=2)
    left = sorted(p.name for p in tmp_path.glob("m_step*.npz"))
    assert left == ["m_step10.npz", "m_step20.npz"]


def test_rotation_never_touches_tmp_files(tmp_path):
    """An in-progress `*.tmp` write (the async writer's scratch file) must
    neither count against keep_n nor be deleted."""
    for i in (1, 2, 3):
        save_checkpoint(str(tmp_path / f"m_step{i}.npz"), {"w": {"x": jnp.zeros(1)}}, {})
    (tmp_path / "m_step4.npz.tmp").write_bytes(b"partial")
    rotate_checkpoints(str(tmp_path), "m_step*", keep_n=2)
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "m_step2.npz", "m_step3.npz", "m_step4.npz.tmp"
    ]


def test_save_checkpoint_fsyncs_before_rename(tmp_path, monkeypatch):
    """ISSUE 3 satellite: the tmp file is flushed + fsynced BEFORE
    os.replace — a crash right after rotation cannot leave zero durable
    checkpoints."""
    import os

    events = []
    real_fsync, real_replace = os.fsync, os.replace
    monkeypatch.setattr(os, "fsync", lambda fd: events.append("fsync") or real_fsync(fd))
    monkeypatch.setattr(
        os, "replace", lambda a, b: events.append("replace") or real_replace(a, b)
    )
    save_checkpoint(str(tmp_path / "c.npz"), {"w": {"x": jnp.zeros(1)}}, {})
    assert "fsync" in events and "replace" in events
    assert events.index("fsync") < events.index("replace")
