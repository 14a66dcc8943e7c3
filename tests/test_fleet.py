"""Fleet observability (ISSUE 4): cross-host aggregation + straggler alarm,
the analytic comms ledger + its drift cross-check, on-alarm profiler capture
(rate limiting, window bounds, SIGUSR2), per-device memory gauges,
process-tagged hang dumps, the fleet/telemetry report tools, and the
fleet-off HLO-equality guarantee."""
import json
import os
import signal
import socket
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dalle_pytorch_tpu.observability import comms as comms_mod
from dalle_pytorch_tpu.observability import telemetry as tele_mod
from dalle_pytorch_tpu.observability.capture import TraceTrigger, parse_profile_steps
from dalle_pytorch_tpu.observability.fleet import (
    FleetAggregator,
    merge_step_records,
)
from dalle_pytorch_tpu.observability.metrics import MetricsRegistry

REPO = Path(__file__).resolve().parent.parent


# --- comms ledger ------------------------------------------------------------

def _ledger(axes, **kw):
    base = dict(param_bytes=1e6, grad_bytes=4e6, batch=16, seq_len=64,
                dim=32, depth=4, heads=4, dim_head=8)
    base.update(kw)
    return comms_mod.step_comms_ledger(axes, **base)


def test_comms_ledger_active_axes_and_formulas():
    led = _ledger({"dp": 2, "tp": 2, "pp": 2})
    rows = {r["axis"]: r for r in led["per_axis"]}
    assert set(rows) == {"dp", "tp", "pp"}  # inactive axes are absent
    # dp: one ring all-reduce of each chip's gradient SHARD — params (and so
    # grads) are tp- and pp-sharded at rest, so the per-chip payload is
    # grad_bytes / (tp * pp)
    assert rows["dp"]["bytes_per_step"] == pytest.approx(
        2 * (4e6 / 4) * (2 - 1) / 2
    )
    # tp: depth x 2 branches x fwd+bwd all-reduces of the LOCAL activations
    batch_local = 16 // 2  # dp=2 shards the batch
    act = batch_local * 64 * 32 * 4
    assert rows["tp"]["bytes_per_step"] == pytest.approx(
        4 * 2 * 2 * 2 * act * (2 - 1) / 2
    )
    assert rows["pp"]["bytes_per_step"] > 0 and rows["pp"]["num_micro"] >= 2
    assert led["total_bytes_per_step"] == pytest.approx(
        sum(r["bytes_per_step"] for r in led["per_axis"])
    )


def test_comms_ledger_fsdp_zero_stages():
    z0 = _ledger({"fsdp": 4})["per_axis"][0]
    z1 = _ledger({"fsdp": 4}, zero_stage=1)["per_axis"][0]
    z3 = _ledger({"fsdp": 4}, zero_stage=3, grad_accum=2)["per_axis"][0]
    assert z0["op"] == "all_reduce"
    assert z1["op"] == "all_reduce+all_gather"
    assert z3["op"] == "all_gather+reduce_scatter"
    # ZeRO-3: 2 gathers per microbatch x grad_accum=2 + one reduce-scatter
    assert z3["bytes_per_step"] == pytest.approx(
        2 * 2 * 1e6 * 3 / 4 + 4e6 * 3 / 4
    )
    # ZeRO-1: grad all-reduce + updated-shard all-gather
    assert z1["bytes_per_step"] == pytest.approx(2 * 4e6 * 3 / 4 + 1e6 * 3 / 4)


def test_comms_ledger_sp_uses_ring_accounting():
    from dalle_pytorch_tpu.parallel.ring import ring_comm_bytes

    led = _ledger({"sp": 4})
    row = led["per_axis"][0]
    assert row["axis"] == "sp" and row["op"] == "ppermute_ring"
    per_layer = ring_comm_bytes(16, 4, 64 // 4, 8, 4, itemsize=4)
    assert row["bytes_per_step"] == pytest.approx(4 * per_layer)  # x depth


def test_dalle_step_comms_from_live_mesh_and_settings():
    from dalle_pytorch_tpu.parallel.mesh import MeshConfig, make_mesh
    from dalle_pytorch_tpu.parallel.train_step import StepSettings

    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
    params = {"w": jnp.ones((64, 64), jnp.float32),
              "b": jnp.ones((64,), jnp.bfloat16),
              "ids": jnp.ones((4,), jnp.int32)}  # non-float: not counted

    class Cfg:
        total_seq_len, dim, depth, heads, dim_head = 64, 32, 4, 4, 8
        pp_num_micro, pp_interleave = None, 1

    led = comms_mod.dalle_step_comms(
        mesh, params, Cfg(), 16,
        settings=StepSettings(zero_stage=3, compute_dtype=jnp.bfloat16,
                              grad_dtype=jnp.bfloat16),
    )
    rows = {r["axis"]: r for r in led["per_axis"]}
    assert set(rows) == {"dp", "fsdp", "tp"}
    param_bytes = 64 * 64 * 4 + 64 * 2  # storage dtypes; int leaf excluded
    grad_bytes = (64 * 64 + 64) * 2     # bf16 grad_dtype
    # payloads are the per-chip SHARDS: tp=2 halves the tree at rest
    assert rows["fsdp"]["payload_bytes"] == pytest.approx(param_bytes / 2)
    assert rows["dp"]["payload_bytes"] == pytest.approx(grad_bytes / 2)
    assert comms_mod.dalle_step_comms(None, params, Cfg(), 16) is None


def test_comms_crosscheck_drift_alarm():
    alarms = []
    chk = comms_mod.CommsCrosscheck(1e6, rtol=0.5, persistence=2,
                                    on_alarm=alarms.append)
    # bytes-accessed >> wire bytes is fine — only DRIFT of the ratio alarms
    assert chk.check(900e6) == pytest.approx(900.0)
    chk.check(950e6)
    chk.check(5000e6)
    assert not alarms  # first divergence: not yet persistent
    chk.check(5000e6)
    assert len(alarms) == 1 and alarms[0]["drift"] > 0.5


def test_comms_roofline_bound():
    roof = comms_mod.comms_roofline(1e9, 1e12, peak_flops=1e14,
                                    ici_bytes_per_s=1e11)
    assert roof["comms_s_at_peak"] == pytest.approx(0.01)
    assert roof["compute_s_at_peak"] == pytest.approx(0.01 / 1.0)
    assert roof["bound"] in ("comms", "compute")
    fast_net = comms_mod.comms_roofline(1e6, 1e12, peak_flops=1e12,
                                        ici_bytes_per_s=1e12)
    assert fast_net["bound"] == "compute"
    # n_chips: both sides must be per-chip — fleet FLOPs over 8 chips
    # against one chip's wire bytes would hide a comms-bound step
    fleet = comms_mod.comms_roofline(1e9, 8e12, peak_flops=1e12,
                                     ici_bytes_per_s=1e9, n_chips=8)
    assert fleet["compute_s_at_peak"] == pytest.approx(1.0)
    assert fleet["comms_s_at_peak"] == pytest.approx(1.0)
    assert fleet["n_chips"] == 8


# --- fleet aggregation -------------------------------------------------------

def _gather_rows(times):
    """gather_fn returning one row per fake process: 1 step of `t` seconds,
    all spent in dispatch."""
    def gather(vec):
        return np.asarray(
            [[1.0, t, 0.0, t, 0.0, 0.0] for t in times], np.float32
        )
    return gather


def test_fleet_skew_gauges_and_record():
    reg = MetricsRegistry()
    agg = FleetAggregator(process_index=0, process_count=4,
                          gather_fn=_gather_rows([0.1, 0.1, 0.4, 0.1]),
                          registry=reg)
    rec = agg.observe_window(10, {"dispatch": 0.1}, 0.1, 1)
    assert rec["processes"] == 4
    assert rec["slowest_process"] == 2
    assert rec["step_time"]["max_s"] == pytest.approx(0.4)
    assert rec["step_time"]["median_s"] == pytest.approx(0.1)
    assert rec["skew_ratio"] == pytest.approx(4.0)
    assert rec["phases"]["dispatch"]["argmax"] == 2
    snap = reg.snapshot(reset_window=False)
    assert snap["fleet/step_time_max_s"]["last"] == pytest.approx(0.4)
    assert snap["fleet/slowest_process"]["last"] == 2
    assert snap["fleet/dispatch_max_s"]["last"] == pytest.approx(0.4)
    # empty window: no gather, no record
    assert agg.observe_window(11, {}, 0.0, 0) is None


def test_straggler_alarm_sustained_fires_once_and_rearms():
    reg = MetricsRegistry()
    alarms = []
    slow = _gather_rows([0.1, 0.5, 0.1, 0.1])
    even = _gather_rows([0.1, 0.1, 0.1, 0.1])
    agg = FleetAggregator(process_index=0, process_count=4, gather_fn=slow,
                          skew_factor=1.5, patience=3, on_alarm=alarms.append,
                          registry=reg)
    agg.observe_window(0, {"dispatch": 0.1}, 0.1, 1)
    agg.observe_window(1, {"dispatch": 0.1}, 0.1, 1)
    assert not alarms  # not sustained yet
    agg.observe_window(2, {"dispatch": 0.1}, 0.1, 1)
    assert len(alarms) == 1
    a = alarms[0]
    assert a["type"] == "straggler" and a["process"] == 1
    assert a["windows"] == 3 and a["ratio"] == pytest.approx(5.0)
    # still slow: streak continues but the episode does NOT re-alarm
    agg.observe_window(3, {"dispatch": 0.1}, 0.1, 1)
    agg.observe_window(4, {"dispatch": 0.1}, 0.1, 1)
    assert len(alarms) == 1
    # recovery resets; a NEW sustained episode alarms again
    agg.gather_fn = even
    agg.observe_window(5, {"dispatch": 0.1}, 0.1, 1)
    agg.gather_fn = slow
    for w in range(6, 9):
        agg.observe_window(w, {"dispatch": 0.1}, 0.1, 1)
    assert len(alarms) == 2
    assert reg.snapshot()["fleet/straggler_alarms"]["total"] == 2


def test_straggler_uniform_slowdown_does_not_alarm():
    alarms = []
    agg = FleetAggregator(process_index=0, process_count=4, patience=2,
                          on_alarm=alarms.append, registry=MetricsRegistry())
    agg.gather_fn = _gather_rows([0.1, 0.1, 0.1, 0.1])
    agg.observe_window(0, {"dispatch": 0.1}, 0.1, 1)
    # the WHOLE fleet slows 5x: median moves with it -> no straggler
    agg.gather_fn = _gather_rows([0.5, 0.5, 0.5, 0.5])
    for w in range(1, 5):
        agg.observe_window(w, {"dispatch": 0.5}, 0.5, 1)
    assert alarms == []


def test_fleet_state_roundtrip():
    agg = FleetAggregator(process_index=0, process_count=2,
                          gather_fn=_gather_rows([0.1, 0.3]),
                          registry=MetricsRegistry())
    agg.observe_window(0, {"dispatch": 0.1}, 0.1, 1)
    state = agg.state_dict()
    fresh = FleetAggregator(process_index=0, process_count=2,
                            registry=MetricsRegistry())
    fresh.load_state_dict(json.loads(json.dumps(state)))  # JSON round-trip
    assert fresh._median_ema == pytest.approx(agg._median_ema)
    assert fresh._streaks == agg._streaks


def test_single_process_gather_identity():
    reg = MetricsRegistry()
    agg = FleetAggregator(process_index=0, process_count=1, registry=reg)
    rec = agg.observe_window(0, {"dispatch": 0.2}, 0.25, 2)
    assert rec["processes"] == 1 and rec["skew_ratio"] == pytest.approx(1.0)
    assert rec["step_time"]["median_s"] == pytest.approx(0.125)


# --- telemetry wiring: alarm hub + fleet window ------------------------------

def test_telemetry_fleet_window_and_alarm_hub(tmp_path):
    heard = []
    tele = tele_mod.configure(dir=str(tmp_path), run_name="f",
                              heartbeat_s=None, watch_compiles=False)
    try:
        tele.add_alarm_listener(lambda t, fields: heard.append((t, fields)))
        agg = tele.attach_fleet(FleetAggregator(
            process_index=0, process_count=2, skew_factor=1.5, patience=1,
            gather_fn=_gather_rows([0.01, 0.9]), registry=MetricsRegistry(),
        ))
        assert agg.on_alarm is not None  # hub-wired by attach_fleet
        with tele.step(0):
            with tele_mod.span("dispatch"):
                pass
        tele.flush(None, step=0)
    finally:
        tele.close()
    recs = [json.loads(l) for l in open(tmp_path / "f.spans.jsonl") if l.strip()]
    fleet = [r for r in recs if r["kind"] == "fleet"]
    assert len(fleet) == 1 and fleet[0]["slowest_process"] == 1
    alarms = [r for r in recs if r["kind"] == "alarm"]
    assert [a["type"] for a in alarms] == ["straggler"]
    assert heard and heard[0][0] == "straggler"
    # window drained: a second flush with no steps gathers nothing
    tele2_windows = fleet
    assert len(tele2_windows) == 1


# --- on-alarm profiler capture ----------------------------------------------

class _FakeProfiler:
    def __init__(self):
        self.starts, self.stops = [], []

    def start(self, path):
        self.starts.append(path)

    def stop(self):
        self.stops.append(True)


def test_trace_trigger_window_bounds(tmp_path):
    prof = _FakeProfiler()
    clock = [0.0]
    trig = TraceTrigger(str(tmp_path), window_steps=3, cooldown_s=100.0,
                        start_fn=prof.start, stop_fn=prof.stop,
                        clock=lambda: clock[0])
    assert trig.request("straggler")
    for step in range(10, 16):
        trig.on_step_start(step)
        trig.on_step_end(step)
    assert len(prof.starts) == 1 and "step10" in prof.starts[0]
    assert "straggler" in prof.starts[0]
    assert len(prof.stops) == 1  # stopped after exactly window_steps steps
    assert trig.captures == 1


def test_trace_trigger_rate_limit_cooldown_and_budget(tmp_path):
    prof = _FakeProfiler()
    clock = [0.0]
    trig = TraceTrigger(str(tmp_path), window_steps=1, cooldown_s=100.0,
                        max_captures=2, start_fn=prof.start, stop_fn=prof.stop,
                        clock=lambda: clock[0])
    step = 0

    def run_capture():
        nonlocal step
        trig.on_step_start(step)
        trig.on_step_end(step)
        step += 1

    assert trig.request("a")
    # an alarm STORM while pending/active: all suppressed
    assert not trig.request("b")
    run_capture()
    assert len(prof.starts) == 1
    # within cooldown: suppressed
    assert not trig.request("c")
    run_capture()
    assert len(prof.starts) == 1
    # past cooldown: second capture allowed
    clock[0] = 200.0
    assert trig.request("d")
    run_capture()
    assert len(prof.starts) == 2
    # budget (max_captures=2) spent: never again, even past cooldown
    clock[0] = 1000.0
    assert not trig.request("e")
    run_capture()
    assert len(prof.starts) == 2
    assert trig.suppressed == 3


def test_trace_trigger_manual_window_and_signal(tmp_path):
    prof = _FakeProfiler()
    trig = TraceTrigger(str(tmp_path), window_steps=2, max_captures=0,
                        manual_window=(5, 7), start_fn=prof.start,
                        stop_fn=prof.stop, clock=lambda: 0.0)
    # max_captures=0 would suppress any alarm capture — the manual window
    # bypasses the budget entirely
    assert not trig.request("alarm")
    for step in range(4, 9):
        trig.on_step_start(step)
        trig.on_step_end(step)
    assert len(prof.starts) == 1 and "manual" in prof.starts[0]
    assert len(prof.stops) == 1

    prof2 = _FakeProfiler()
    trig2 = TraceTrigger(str(tmp_path), window_steps=1, start_fn=prof2.start,
                         stop_fn=prof2.stop, clock=lambda: 0.0)
    trig2._signal_flag = True  # what the SIGUSR2 handler sets
    trig2.on_step_start(0)
    trig2.on_step_end(0)
    assert len(prof2.starts) == 1 and "sigusr2" in prof2.starts[0]


def test_trace_trigger_capture_events_in_stream(tmp_path):
    from dalle_pytorch_tpu.observability.spans import SpanRecorder

    rec = SpanRecorder(str(tmp_path / "s.spans.jsonl"))
    prof = _FakeProfiler()
    trig = TraceTrigger(str(tmp_path / "traces"), window_steps=1,
                        start_fn=prof.start, stop_fn=prof.stop,
                        clock=lambda: 0.0, recorder=rec)
    trig.request("recompile")
    trig.on_step_start(3)
    trig.on_step_end(3)
    rec.close()
    evs = [json.loads(l) for l in open(tmp_path / "s.spans.jsonl") if l.strip()]
    caps = [e for e in evs if e["kind"] == "trace_capture"]
    assert [c["action"] for c in caps] == ["start", "stop"]
    assert caps[0]["step"] == 3 and caps[0]["reason"] == "recompile"


def test_parse_profile_steps():
    assert parse_profile_steps("20:25") == (20, 25)
    assert parse_profile_steps("7") == (7, 8)
    with pytest.raises(ValueError):
        parse_profile_steps("9:9")


# --- satellites: per-device memory gauges, hang-dump process tags ------------

class _FakeDevice:
    def __init__(self, id, bytes_in_use):
        self.id = id
        self._stats = {"bytes_in_use": bytes_in_use,
                       "peak_bytes_in_use": bytes_in_use * 2}

    def memory_stats(self):
        return self._stats


def test_memory_gauges_per_device_and_max(monkeypatch):
    from dalle_pytorch_tpu.observability import metrics as metrics_mod
    from dalle_pytorch_tpu.observability.xla import record_memory_gauges

    reg = MetricsRegistry()
    monkeypatch.setattr(metrics_mod, "REGISTRY", reg)
    monkeypatch.setattr(metrics_mod, "gauge", reg.gauge)
    out = record_memory_gauges(devices=[_FakeDevice(0, 100.0),
                                        _FakeDevice(3, 700.0)])
    assert out["bytes_in_use"] == 700.0
    snap = reg.snapshot(reset_window=False)
    assert snap["device0/bytes_in_use"]["last"] == 100.0
    assert snap["device3/bytes_in_use"]["last"] == 700.0  # the hot chip, by id
    assert snap["device_bytes_in_use"]["last"] == 700.0
    assert snap["device_bytes_in_use_max_across_devices"]["last"] == 700.0
    assert snap["device_peak_bytes_in_use"]["last"] == 1400.0


def test_memory_gauges_cpu_returns_none():
    from dalle_pytorch_tpu.observability.xla import record_memory_gauges

    class _NoStats:
        id = 0

        def memory_stats(self):
            return None

    assert record_memory_gauges(devices=[_NoStats()]) is None


def test_hang_dump_carries_process_index(tmp_path):
    import time

    from dalle_pytorch_tpu.observability import Heartbeat

    hb = Heartbeat(0.15, dir=str(tmp_path), poll_s=0.05,
                   process_index=3).start()
    try:
        hb.beat(step=7)
        deadline = time.time() + 5.0
        while hb.hangs == 0 and time.time() < deadline:
            time.sleep(0.05)
        assert hb.hangs == 1
    finally:
        hb.stop()
    dumps = list(tmp_path.glob("hang_*.txt"))
    assert len(dumps) == 1
    assert "_p3_step7" in dumps[0].name  # process + step in the filename
    text = dumps[0].read_text()
    assert "process 3" in text and "last step 7" in text


# --- report tools ------------------------------------------------------------

def _load_tool(name):
    import importlib.util

    path = REPO / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_stream(path, steps, extra=()):
    recs = [{"kind": "meta", "schema": 1, "ts": 0.0}]
    for step, dur in steps:
        recs.append({"kind": "step", "step": step, "ts": 1.0 + step,
                     "dur_s": dur, "spans": {"dispatch": dur * 0.8}, "agg": {}})
    recs.extend(extra)
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")


def test_fleet_report_merges_and_ranks(tmp_path):
    _write_stream(tmp_path / "run.spans.jsonl", [(0, 0.1), (1, 0.1)], extra=[
        {"kind": "comms_ledger", "ts": 2.0, "mesh": {"dp": 2, "tp": 2},
         "per_axis": [
             {"axis": "dp", "op": "all_reduce", "bytes_per_step": 2e6},
             {"axis": "tp", "op": "all_reduce", "bytes_per_step": 1e6}],
         "total_bytes_per_step": 3e6,
         "roofline": {"comms_s_at_peak": 1e-4, "compute_s_at_peak": 2e-4,
                      "bound": "compute"}},
        {"kind": "comms_crosscheck", "ts": 2.0, "bytes_accessed": 9e8,
         "ratio": 300.0},
        {"kind": "fleet", "ts": 2.5, "step": 1, "processes": 2,
         "step_time": {"median_s": 0.2, "max_s": 0.3, "min_s": 0.1},
         "skew_ratio": 1.5, "slowest_process": 1},
    ])
    _write_stream(tmp_path / "run.p1.spans.jsonl", [(0, 0.4), (1, 0.1)], extra=[
        {"kind": "alarm", "type": "straggler", "ts": 3.0, "process": 1},
        {"kind": "trace_capture", "action": "start", "ts": 3.1, "step": 1,
         "reason": "alarm_straggler", "path": "/x"},
    ])
    fr = _load_tool("fleet_report")
    streams = fr.load_streams([str(tmp_path)])
    assert set(streams) == {0, 1}
    merged = merge_step_records(streams)
    assert merged[0]["skew_s"] == pytest.approx(0.3)
    assert merged[0]["slowest_process"] == 1
    out = fr.build_report(streams)
    assert "per-step cross-host step time" in out
    assert "straggler ranking" in out and "p1" in out
    assert "comms ledger" in out and "dp" in out and "compute-bound" in out
    assert "measured cross-check" in out
    assert "straggler" in out and "profiler captures (1)" in out
    # skew helper feeds the telemetry_report column
    skew = fr.per_step_skew(streams)
    assert skew[0] == pytest.approx(0.3) and skew[1] == pytest.approx(0.0)


def test_telemetry_report_multi_file_skew_column(tmp_path):
    _write_stream(tmp_path / "r.spans.jsonl", [(0, 0.1), (1, 0.2)])
    _write_stream(tmp_path / "r.p1.spans.jsonl", [(0, 0.35), (1, 0.2)])
    tr = _load_tool("telemetry_report")
    fr = _load_tool("fleet_report")
    skew = fr.per_step_skew(fr.load_streams(
        [str(tmp_path / "r.spans.jsonl"), str(tmp_path / "r.p1.spans.jsonl")]
    ))
    out = tr.build_report(tr.load_records(str(tmp_path / "r.spans.jsonl")),
                          skew_by_step=skew)
    assert "xproc skew_s" in out
    assert "0.2500" in out  # step 0: |0.35 - 0.1|
    # single-file rendering is unchanged (no skew column)
    solo = tr.build_report(tr.load_records(str(tmp_path / "r.spans.jsonl")))
    assert "xproc skew_s" not in solo


# --- fleet-off HLO equality --------------------------------------------------

def _toy_step():
    from dalle_pytorch_tpu.parallel.train_step import make_train_step

    def loss_fn(params, batch, key):
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    init_fn, step_fn = make_train_step(loss_fn, optax.adam(1e-3))
    state = init_fn({"w": jnp.ones((8, 8), jnp.float32)})
    batch = {"x": jnp.ones((4, 8), jnp.float32)}
    return state, step_fn, batch


def test_fleet_off_train_step_hlo_identical(tmp_path):
    """The whole fleet stack lives OUTSIDE jit: the train-step HLO with
    telemetry + fleet + capture all active must be byte-identical to the
    bare step (the PR 2 discipline, extended to this layer)."""
    state, step_fn, batch = _toy_step()
    bare = step_fn.lower(state, batch, jax.random.PRNGKey(0)).as_text()
    tele = tele_mod.configure(dir=str(tmp_path), run_name="h",
                              heartbeat_s=None, watch_compiles=False)
    try:
        tele.attach_fleet(FleetAggregator(process_index=0, process_count=1,
                                          registry=MetricsRegistry()))
        trig = TraceTrigger(str(tmp_path / "traces"), start_fn=lambda p: None,
                            stop_fn=lambda: None, clock=lambda: 0.0)
        tele.add_alarm_listener(trig.on_alarm)
        with_fleet = step_fn.lower(state, batch, jax.random.PRNGKey(0)).as_text()
    finally:
        tele.close()
    assert bare == with_fleet


# --- multichip dryrun: dp2 x tp2 x pp2 with the full fleet stack -------------

@pytest.mark.multichip
def test_multichip_fleet_skew_and_comms_ledger(tmp_path):
    """8-device (virtual CPU) three-axis train step under active telemetry:
    skew gauges publish, the fleet window and comms ledger land in the
    JSONL, and the ledger prices every active axis (dp2 x tp2 x pp2)."""
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models.dalle import DALLEConfig
    from dalle_pytorch_tpu.parallel.mesh import MeshConfig, make_mesh
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step

    cfg = DALLEConfig(
        dim=32, depth=2, num_text_tokens=64, text_seq_len=8, heads=4,
        dim_head=8, num_image_tokens=32, image_fmap_size=4,
        scan_layers=True, pipeline_axis="pp",
    )

    def loss_fn(params, batch, key):
        return dalle_mod.forward(params, cfg, batch["text"],
                                 batch["image_codes"], return_loss=True)

    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=2, sp=1, pp=2))
    init_fn, step_fn = make_train_step(loss_fn, optax.adam(1e-3), mesh=mesh,
                                       settings=StepSettings())
    state = init_fn(dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg))
    batch = {
        "text": jax.random.randint(jax.random.PRNGKey(1), (8, cfg.text_seq_len),
                                   0, cfg.num_text_tokens),
        "image_codes": jax.random.randint(jax.random.PRNGKey(2),
                                          (8, cfg.image_seq_len), 0,
                                          cfg.num_image_tokens),
    }

    reg = MetricsRegistry()
    tele = tele_mod.configure(dir=str(tmp_path), run_name="mc",
                              heartbeat_s=None, watch_compiles=False)
    try:
        tele.attach_fleet(FleetAggregator(process_index=0, process_count=1,
                                          registry=reg))
        for i in range(2):
            with tele.step(i):
                with tele_mod.span("dispatch"):
                    state, metrics = step_fn(state, batch, jax.random.PRNGKey(i))
                with tele_mod.span("block"):
                    loss = float(metrics["loss"])
        assert np.isfinite(loss)
        ledger = comms_mod.dalle_step_comms(
            getattr(step_fn, "mesh", None), state.params, cfg, 8,
            settings=getattr(step_fn, "settings", None),
        )
        comms_mod.publish_gauges(ledger, reg)
        tele.spans.write_event("comms_ledger", **ledger)
        tele.flush(None, step=1)
    finally:
        tele.close()

    axes = {r["axis"]: r["bytes_per_step"] for r in ledger["per_axis"]}
    assert set(axes) == {"dp", "tp", "pp"}
    assert all(v > 0 for v in axes.values())
    snap = reg.snapshot(reset_window=False)
    assert snap["fleet/step_time_max_s"]["last"] > 0
    assert snap["fleet/step_skew_ratio"]["last"] == pytest.approx(1.0)
    assert snap["comms/total_bytes_per_step"]["last"] == pytest.approx(
        sum(axes.values())
    )
    recs = [json.loads(l) for l in open(tmp_path / "mc.spans.jsonl") if l.strip()]
    kinds = {r["kind"] for r in recs}
    assert {"step", "fleet", "comms_ledger"} <= kinds


# --- multiprocess: real allgather, injected straggler, one capture -----------

_MP_SCRIPT = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
pid, port, out = int(sys.argv[1]), sys.argv[2], sys.argv[3]
jax.distributed.initialize(f"127.0.0.1:{port}", 2, pid)

from dalle_pytorch_tpu.observability import telemetry as tele_mod
from dalle_pytorch_tpu.observability.capture import TraceTrigger
from dalle_pytorch_tpu.observability.fleet import FleetAggregator

tele = tele_mod.configure(dir=out, run_name="mp", heartbeat_s=None,
                          watch_compiles=False, process_index=pid)
tele.attach_fleet(FleetAggregator(skew_factor=1.5, patience=2))
cap = TraceTrigger(out + "/traces", window_steps=1, cooldown_s=60.0,
                   max_captures=2, recorder=tele.spans, process_index=pid)
tele.add_alarm_listener(cap.on_alarm)
for step in range(6):
    tele.begin_step(step)
    cap.on_step_start(step)
    with tele_mod.span("dispatch"):
        time.sleep(0.02 + (0.4 if pid == 1 else 0.0))  # p1 is the straggler
    cap.on_step_end(step)
    tele.finish_step(step)
    if step % 2 == 1:
        tele.flush(None, step=step)  # collective: same cadence everywhere
cap.close()
tele.close()
print("DONE", pid)
"""


@pytest.mark.slow
@pytest.mark.multichip
def test_multiprocess_straggler_alarm_and_single_capture(tmp_path):
    """TWO real processes (jax.distributed over CPU/gloo), a sleep injected
    on process 1: both processes' fleet gathers must agree, the straggler
    alarm must fire on the sustained skew, and the on-alarm TraceTrigger
    must produce exactly ONE rate-limited capture per process."""
    script = tmp_path / "mp_driver.py"
    script.write_text(_MP_SCRIPT)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), str(port), str(tmp_path)],
            env=env, cwd=str(tmp_path), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        for pid in (0, 1)
    ]
    outs = [p.communicate(timeout=300) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, (p.returncode, err[-3000:])

    recs = [json.loads(l) for l in open(tmp_path / "mp.spans.jsonl") if l.strip()]
    fleet = [r for r in recs if r["kind"] == "fleet"]
    assert len(fleet) >= 2
    assert all(r["processes"] == 2 for r in fleet)
    # the pre-capture windows show the injected skew (the capture window
    # itself is slow on BOTH processes — start/stop_trace is expensive —
    # which correctly reads as a uniform slowdown, not a straggler)
    assert fleet[0]["slowest_process"] == 1
    assert fleet[0]["skew_ratio"] > 1.5
    assert fleet[1]["slowest_process"] == 1 and fleet[1]["skew_ratio"] > 1.5
    alarms = [r for r in recs if r["kind"] == "alarm"
              and r["type"] == "straggler"]
    assert len(alarms) == 1 and alarms[0]["process"] == 1
    # exactly ONE rate-limited capture on this process (cooldown swallows
    # any further requests inside the run)
    starts = [r for r in recs if r["kind"] == "trace_capture"
              and r["action"] == "start"]
    assert len(starts) == 1 and "straggler" in starts[0]["reason"]
    # process 1 sees the same fleet view in its own stream
    recs1 = [json.loads(l) for l in open(tmp_path / "mp.p1.spans.jsonl")
             if l.strip()]
    # co-located processes must not clobber each other's trace: p1's path
    # carries the process tag, p0's does not
    starts1 = [r for r in recs1 if r["kind"] == "trace_capture"
               and r["action"] == "start"]
    assert starts1 and starts1[0]["path"].endswith("_p1")
    assert not starts[0]["path"].endswith("_p1")
    fleet1 = [r for r in recs1 if r["kind"] == "fleet"]
    assert fleet1 and fleet1[0]["slowest_process"] == 1
    assert fleet1[0]["step_time"] == fleet[0]["step_time"]  # gathers agree
    # and the offline merger renders the merged cross-host table
    fr = _load_tool("fleet_report")
    report = fr.build_report(fr.load_streams([str(tmp_path)]))
    assert "straggler ranking" in report and "p1" in report


# --- CLI acceptance: dummy run end-to-end ------------------------------------

@pytest.mark.slow
def test_cli_dummy_run_emits_fleet_and_comms_and_captures(tmp_path):
    """`--dummy_run` on the 8-device CPU platform: the fleet window, comms
    ledger (dp mesh), comms cross-check, and an on-alarm capture (the
    deliberate ragged-batch recompile) all land in the telemetry stream."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable, "-m", "dalle_pytorch_tpu.cli.train_dalle",
         "--dummy_run", "6", "--log_every_n_steps", "2",
         "--dalle_output_file_name", str(tmp_path / "D")],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=420,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    spans = tmp_path / "D.telemetry" / "D.spans.jsonl"
    recs = [json.loads(l) for l in open(spans) if l.strip()]
    kinds = {x["kind"] for x in recs}
    assert {"fleet", "comms_ledger", "comms_crosscheck"} <= kinds
    led = next(x for x in recs if x["kind"] == "comms_ledger")
    assert led["mesh"]["dp"] == 8 and led["per_axis"][0]["axis"] == "dp"
    assert "roofline" in led
    starts = [x for x in recs if x["kind"] == "trace_capture"
              and x["action"] == "start"]
    assert len(starts) == 1  # ragged-batch recompile alarm -> one capture
    assert (tmp_path / "D.telemetry" / "traces").is_dir()
