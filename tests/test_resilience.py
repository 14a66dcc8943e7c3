"""Fault-tolerant training (training/resilience.py, ISSUE 3).

The headline is the crash-and-resume EQUIVALENCE proof: a training run
SIGKILLed at step N and restarted with `--resume auto` must produce the same
per-step loss sequence (same batches, same order, same RNG) as an
uninterrupted run — resume is exact, not approximate.  Those tests drive the
real CLI in subprocesses (JAX_PLATFORMS=cpu) through the `--inject_fault`
chaos harness.  The unit tests pin down each piece: checkpoint validation's
distinct error types, `--resume auto` fallback, the async writer's
durability/back-pressure/error-surfacing, the preemption handler, and the
in-graph bad-step guard."""
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dalle_pytorch_tpu.training import resilience
from dalle_pytorch_tpu.training.checkpoint import load_checkpoint, save_checkpoint

REPO = Path(__file__).resolve().parent.parent


# --- checkpoint validation: one distinct, actionable error per failure -----

def _save_small(path, global_step=7):
    save_checkpoint(
        str(path),
        trees={"weights": {"w": jnp.arange(8.0), "b": jnp.zeros(3)}},
        meta={"epoch": 1, "global_step": global_step,
              "data_state": {"epoch": 1, "epoch_batches": 2, "seed": 0}},
    )


def test_validate_ok(tmp_path):
    p = tmp_path / "ok.npz"
    _save_small(p)
    meta = resilience.validate_checkpoint(str(p))
    assert meta["global_step"] == 7
    assert meta["data_state"]["epoch_batches"] == 2


def test_validate_truncated_npz(tmp_path):
    p = tmp_path / "trunc.npz"
    _save_small(p)
    resilience.truncate_file(str(p), frac=0.5)
    with pytest.raises(resilience.TruncatedCheckpointError, match="npz"):
        resilience.validate_checkpoint(str(p))


def test_validate_garbage_meta(tmp_path):
    p = tmp_path / "garbage.npz"
    _save_small(p)
    # corrupt_file targets the head of the archive — the __meta member
    resilience.corrupt_file(str(p))
    with pytest.raises(resilience.CheckpointMetaError):
        resilience.validate_checkpoint(str(p))


def test_validate_missing_leaves(tmp_path):
    p = tmp_path / "full.npz"
    _save_small(p)
    with np.load(str(p)) as data:
        payload = {k: data[k] for k in data.files if k != "weights:1"}
    partial = tmp_path / "partial.npz"
    with open(partial, "wb") as f:
        np.savez(f, **payload)
    with pytest.raises(resilience.MissingLeavesError, match="weights:1"):
        resilience.validate_checkpoint(str(partial))


def test_validate_future_format(tmp_path):
    from dalle_pytorch_tpu.training import checkpoint as ck

    p = tmp_path / "v.npz"
    _save_small(p)
    with np.load(str(p)) as data:
        payload = {k: data[k] for k in data.files}
    payload["__format"] = np.array(ck.FORMAT_VERSION + 1, dtype=np.int64)
    future = tmp_path / "future.npz"
    with open(future, "wb") as f:
        np.savez(f, **payload)
    with pytest.raises(resilience.FutureFormatError, match="upgrade"):
        resilience.validate_checkpoint(str(future))


def test_validate_missing_file(tmp_path):
    with pytest.raises(resilience.TruncatedCheckpointError, match="exist"):
        resilience.validate_checkpoint(str(tmp_path / "nope.npz"))


# --- auto-resume discovery ---------------------------------------------------

def test_candidates_ordered_by_step_not_mtime(tmp_path):
    out = tmp_path / "run.pt"
    for step in (5, 20, 100):
        _save_small(tmp_path / f"run_step{step}.npz", global_step=step + 1)
    _save_small(out, global_step=0)  # stale epoch-end file ranks last
    # a clock-skewed copy makes the OLDEST file mtime-newest — the step
    # (meta global_step / filename) must still rank, never mtime
    now = time.time()
    os.utime(tmp_path / "run_step5.npz", (now + 3600, now + 3600))
    (tmp_path / "run_step999.npz.tmp").write_bytes(b"in-progress")
    cands = resilience.checkpoint_candidates(str(out))
    assert [p.name for p in cands] == [
        "run_step100.npz", "run_step20.npz", "run_step5.npz", "run.pt"
    ]
    # ...but an epoch-end file strictly NEWER than every step file (saved
    # at the epoch boundary after the last periodic save) ranks first —
    # resuming from run_step100 would silently lose progress
    _save_small(out, global_step=250)
    cands = resilience.checkpoint_candidates(str(out))
    assert cands[0].name == "run.pt"


def test_resume_auto_falls_back_past_corrupt_and_truncated(tmp_path):
    out = tmp_path / "run.pt"
    for step in (1, 2, 3):
        _save_small(tmp_path / f"run_step{step}.npz", global_step=step + 1)
    resilience.corrupt_file(str(tmp_path / "run_step3.npz"))
    resilience.truncate_file(str(tmp_path / "run_step2.npz"))
    logs = []
    found, meta = resilience.find_latest_valid_checkpoint(str(out), log=logs.append)
    assert found == str(tmp_path / "run_step1.npz")
    assert meta["global_step"] == 2
    assert len(logs) == 2  # both bad files reported, in newest-first order
    assert "run_step3" in logs[0] and "run_step2" in logs[1]


def test_resume_auto_nothing_found(tmp_path):
    found, meta = resilience.find_latest_valid_checkpoint(str(tmp_path / "x.pt"))
    assert found is None and meta is None


# --- async checkpoint writer -------------------------------------------------

def test_async_writer_durable_and_rotating(tmp_path):
    w = resilience.AsyncCheckpointWriter()
    for step in range(1, 5):
        w.submit(
            str(tmp_path / f"m_step{step}.npz"),
            {"weights": {"x": np.full(4, float(step))}},
            {"global_step": step},
            keep_n=2, rotation_glob="m_step*.npz",
        )
    w.flush()
    left = sorted(p.name for p in tmp_path.glob("m_step*.npz"))
    assert left == ["m_step3.npz", "m_step4.npz"]
    trees, meta = load_checkpoint(str(tmp_path / "m_step4.npz"))
    np.testing.assert_array_equal(np.asarray(trees["weights"]["x"]), np.full(4, 4.0))
    assert w.last_completed == str(tmp_path / "m_step4.npz")
    w.close()
    with pytest.raises(RuntimeError, match="closed"):
        w.submit("x", {}, {})


def test_async_writer_surfaces_write_errors(tmp_path):
    def boom(path, trees, meta):
        raise OSError("disk is gone")

    w = resilience.AsyncCheckpointWriter(save_fn=boom)
    w.submit(str(tmp_path / "a.npz"), {}, {})
    with pytest.raises(RuntimeError, match="disk is gone"):
        w.flush()
    # the error is consumed once surfaced; the writer keeps working
    w.close()


# --- preemption handler ------------------------------------------------------

def test_shutdown_handler_sets_flag_then_escalates():
    h = resilience.ShutdownHandler(signals=(signal.SIGTERM,)).install()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        # signal delivery is synchronous for self-kill on the main thread
        assert h.requested and h.signum == signal.SIGTERM
        # second signal escalates so a wedged run stays killable
        with pytest.raises(KeyboardInterrupt):
            h._on_signal(signal.SIGTERM, None)
    finally:
        h.uninstall()
    assert signal.getsignal(signal.SIGTERM) != h._on_signal


# --- in-graph bad-step guard -------------------------------------------------

def test_bad_step_guard_without_loss_scale():
    """The nonfinite-update skip now protects plain (no loss_scale) runs: a
    poisoned batch leaves params/moments untouched and reports skipped=1."""
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step

    def loss_fn(p, batch, key):
        return jnp.sum(p["w"] ** 2) * batch["blow"]

    init_fn, step_fn = make_train_step(loss_fn, optax.sgd(1e-2))
    state = init_fn(jax.tree_util.tree_map(np.asarray, {"w": jnp.ones((4, 4))}))
    state, m = step_fn(state, {"blow": jnp.asarray(jnp.inf)}, jax.random.PRNGKey(0))
    assert int(m["skipped"]) == 1
    np.testing.assert_array_equal(np.asarray(state.params["w"]), np.ones((4, 4)))
    # a clean step then applies normally
    state, m = step_fn(state, {"blow": jnp.asarray(1.0)}, jax.random.PRNGKey(1))
    assert int(m["skipped"]) == 0
    assert not np.allclose(np.asarray(state.params["w"]), np.ones((4, 4)))
    # explicit opt-out restores the unguarded update (no skipped metric)
    init2, step2 = make_train_step(
        loss_fn, optax.sgd(1e-2), settings=StepSettings(skip_nonfinite=False)
    )
    _, m2 = step2(
        init2({"w": jnp.ones((2,))}), {"blow": jnp.asarray(1.0)},
        jax.random.PRNGKey(0),
    )
    assert "skipped" not in m2


# --- fault parsing / chaos primitives ---------------------------------------

def test_parse_fault():
    f = resilience.parse_fault("kill-process@40")
    assert f.kind == "kill-process" and f.step == 40
    f = resilience.parse_fault("stall-data@10:2.5")
    assert f.step == 10 and f.stall_s == 2.5
    with pytest.raises(ValueError, match="unknown fault kind"):
        resilience.parse_fault("set-on-fire@1")


def test_chaos_cli_corrupt_and_validate(tmp_path):
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import chaos
    finally:
        sys.path.pop(0)
    p = tmp_path / "c.npz"
    _save_small(p)
    assert chaos.main(["validate", str(p)]) == 0
    chaos.main(["corrupt", str(p)])
    assert chaos.main(["validate", str(p)]) == 1


# --- subprocess crash-and-resume equivalence ---------------------------------

def _run_cli(cli_args, cwd, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    return subprocess.run(
        [sys.executable, "-m", "dalle_pytorch_tpu.cli.train_dalle", *cli_args],
        cwd=str(cwd), env=env, capture_output=True, text=True, timeout=timeout,
    )


def _losses(metrics_jsonl):
    out = {}
    for line in open(metrics_jsonl):
        rec = json.loads(line)
        if "loss" in rec:
            out[rec["step"]] = rec["loss"]  # later records win (resume re-log)
    return out


_DUMMY = ["--dummy_run", "8", "--telemetry", "off", "--log_every_n_steps", "1"]


@pytest.mark.slow  # tier-1 budget: the mechanisms stay fast via
#                    test_preempt_writes_emergency_checkpoint_and_exit_75
#                    (emergency write + exit codes),
#                    test_resume_auto_falls_back_past_corrupt_and_truncated
#                    (resume selection), and
#                    test_rollback_recovers_from_transient_divergence
#                    (exact state restore); this leg is the two-subprocess
#                    end-to-end stitch
def test_kill_at_step_n_and_resume_matches_uninterrupted(tmp_path):
    """THE acceptance proof: SIGKILL mid-run, `--resume auto`, and the
    stitched loss trajectory equals an uninterrupted run batch-for-batch
    (state, data cursor, and RNG key all restore exactly)."""
    # uninterrupted reference
    a = _run_cli(
        [*_DUMMY, "--save_every_n_steps", "0",
         "--dalle_output_file_name", str(tmp_path / "A")], tmp_path,
    )
    assert a.returncode == 0, a.stderr[-2000:]
    ref = _losses(tmp_path / "A.metrics.jsonl")
    assert sorted(ref) == list(range(8))

    # crashed run: checkpoint every step, SIGKILL self at step 4
    b = _run_cli(
        [*_DUMMY, "--save_every_n_steps", "1",
         "--inject_fault", "kill-process@4",
         "--dalle_output_file_name", str(tmp_path / "B")], tmp_path,
    )
    assert b.returncode == -signal.SIGKILL, (b.returncode, b.stderr[-2000:])

    # resume: --resume auto discovers the newest VALID checkpoint (a save
    # may have been mid-write at the kill — its .tmp must be skipped) and
    # continues mid-epoch
    c = _run_cli(
        [*_DUMMY, "--save_every_n_steps", "0", "--resume", "auto",
         "--dalle_output_file_name", str(tmp_path / "B")], tmp_path,
    )
    assert c.returncode == 0, c.stderr[-2000:]
    assert "--resume auto: resuming from" in c.stdout

    got = _losses(tmp_path / "B.metrics.jsonl")
    assert sorted(got) == list(range(8))
    for step in range(8):
        assert got[step] == pytest.approx(ref[step], rel=1e-6), (
            f"loss diverged at step {step}: resumed {got[step]} vs "
            f"uninterrupted {ref[step]}"
        )


def test_preempt_writes_emergency_checkpoint_and_exit_75(tmp_path):
    """SIGTERM (here self-injected) finishes the in-flight step, writes an
    emergency checkpoint with the exact-resume cursor, and exits
    EXIT_PREEMPTED — the contract an outer supervisor restarts on."""
    p = _run_cli(
        ["--dummy_run", "4", "--telemetry", "off", "--log_every_n_steps", "1",
         "--save_every_n_steps", "0", "--inject_fault", "preempt@2",
         "--dalle_output_file_name", str(tmp_path / "P")], tmp_path,
    )
    assert p.returncode == resilience.EXIT_PREEMPTED, (
        p.returncode, p.stderr[-2000:]
    )
    ckpt = tmp_path / "P_step2.npz"
    assert ckpt.exists()
    meta = resilience.validate_checkpoint(str(ckpt))
    # steps 0..2 ran (the in-flight step finished); next step is 3
    assert meta["global_step"] == 3
    assert meta["data_state"]["epoch_batches"] == 3
    assert meta["data_state"]["rng_key"] is not None

    # and the supervisor's restart completes the run cleanly
    r = _run_cli(
        ["--dummy_run", "4", "--telemetry", "off", "--log_every_n_steps", "1",
         "--save_every_n_steps", "0", "--resume", "auto",
         "--dalle_output_file_name", str(tmp_path / "P")], tmp_path,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    got = _losses(tmp_path / "P.metrics.jsonl")
    assert sorted(got) == list(range(4))


# --- exact-resume data state helpers ----------------------------------------

def test_rng_key_roundtrip():
    key = jax.random.PRNGKey(123)
    words = resilience.encode_rng_key(key)
    assert isinstance(words, list) and all(isinstance(w, int) for w in words)
    back = resilience.decode_rng_key(words)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(key))
    # the restored key drives the same stream
    a = jax.random.split(key)
    b = jax.random.split(back)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_data_state_dict_json_serializable():
    ds = resilience.data_state_dict(
        epoch=2, epoch_batches=17, seed=42, rng_key=jax.random.PRNGKey(7)
    )
    json.dumps(ds)  # must not raise
    assert ds["epoch"] == 2 and ds["epoch_batches"] == 17


# --- divergence rollback -----------------------------------------------------

def test_rollback_recovers_from_transient_divergence(tmp_path):
    """A NaN injected mid-run trips the sustained-nonfinite alarm; the run
    rolls back PAST the NaN-poisoned step-3 checkpoint (check_finite screen)
    to the last good one, replays, and finishes with the same loss
    trajectory an undisturbed run produces."""
    r = _run_cli(
        [*_DUMMY, "--save_every_n_steps", "1", "--health_every", "1",
         "--health_inject_nan", "3", "--rollback_retries", "2",
         "--dalle_output_file_name", str(tmp_path / "R")], tmp_path,
    )
    assert r.returncode == 0, (r.returncode, r.stderr[-2000:])
    assert "rolled back to" in r.stdout
    assert "contains NaN/Inf" in r.stdout  # poisoned checkpoint screened out
    got = _losses(tmp_path / "R.metrics.jsonl")
    assert sorted(got) == list(range(8))
    # the replayed tail is finite (recovery, not NaN-propagation)
    assert all(np.isfinite(v) for v in got.values())


@pytest.mark.slow  # tier-1 budget: the rollback mechanism stays fast via
# test_rollback_recovers_from_transient_divergence; this leg only adds the
# budget-exhaustion exit path
def test_rollback_budget_exhaustion_aborts_with_exit_76(tmp_path):
    """A divergence that recurs after every rollback (the injection spec
    repeats) exhausts the bounded retries and aborts CLEANLY with
    EXIT_DIVERGED — no NaN training, no infinite loop."""
    r = _run_cli(
        [*_DUMMY, "--save_every_n_steps", "1", "--health_every", "1",
         "--health_inject_nan", "3,3,3", "--rollback_retries", "1",
         "--dalle_output_file_name", str(tmp_path / "X")], tmp_path,
    )
    assert r.returncode == resilience.EXIT_DIVERGED, (
        r.returncode, r.stderr[-2000:]
    )
    assert "rollback budget exhausted" in r.stdout


# --- drop-remote-stream fault ------------------------------------------------

def test_drop_remote_stream_fault_fires_once():
    inj = resilience.FaultInjector(
        resilience.parse_fault("drop-remote-stream@0")
    ).install()
    try:
        assert resilience.take_stream_fault() is True
        assert resilience.take_stream_fault() is False  # one-shot
    finally:
        inj.uninstall()
    assert resilience.take_stream_fault() is False  # nothing armed


def test_drop_remote_stream_fault_exercises_reconnect():
    """The injected mid-read disconnect drives the real Range-reconnect path
    in the remote stream reader — the caller still sees every byte."""
    import io
    import urllib.request

    from dalle_pytorch_tpu.data.loader import _open_remote

    payload = bytes(range(251)) * 40
    opens = []

    def fake_urlopen(req, timeout=None):
        rng = req.get_header("Range")
        opens.append(rng)
        start = int(rng[len("bytes="):-1]) if rng else 0
        resp = io.BytesIO(payload[start:])
        resp.getcode = lambda: 206 if rng else 200
        return resp

    inj = resilience.FaultInjector(
        resilience.parse_fault("drop-remote-stream@0")
    ).install()
    real = urllib.request.urlopen
    try:
        urllib.request.urlopen = fake_urlopen
        stream = _open_remote("https://host/s.tar", retries=3, timeout=1.0)
        got = b""
        while True:
            chunk = stream.read(512)
            if not chunk:
                break
            got += chunk
    finally:
        urllib.request.urlopen = real
        inj.uninstall()
    assert got == payload
    assert inj.fired
    assert len(opens) == 2  # initial open + one chaos-driven reconnect


def test_check_finite_screens_nan_and_bf16_views(tmp_path):
    """The rollback screen rejects NaN leaves — including bf16 param storage,
    where leaves live in the file as uint16 bit-views and must be viewed
    back through the dtype sidecar before the isfinite check."""
    good = tmp_path / "good.npz"
    save_checkpoint(str(good),
                    {"weights": {"w": jnp.ones((4,), jnp.bfloat16)}}, {})
    assert resilience.validate_checkpoint(str(good), check_finite=True) == {}

    bad_f32 = tmp_path / "bad32.npz"
    save_checkpoint(str(bad_f32),
                    {"weights": {"w": jnp.asarray([1.0, jnp.nan])}}, {})
    with pytest.raises(resilience.NonFiniteCheckpointError, match="NaN"):
        resilience.validate_checkpoint(str(bad_f32), check_finite=True)
    # ...but the cheap structural screen (resume-auto path) still accepts it
    resilience.validate_checkpoint(str(bad_f32))

    bad_bf16 = tmp_path / "bad16.npz"
    save_checkpoint(str(bad_bf16),
                    {"weights": {"w": jnp.asarray([1.0, jnp.nan], jnp.bfloat16)}}, {})
    with pytest.raises(resilience.NonFiniteCheckpointError, match="NaN"):
        resilience.validate_checkpoint(str(bad_bf16), check_finite=True)
