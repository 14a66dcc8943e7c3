"""Serving observability (PR 11): request lifecycle traces, windowed SLO
burn-rate alarms and decode-loop phase attribution.

The contract under test: every request that enters the engine leaves a
`kind:"request"` record whose phases sum to its latency, whatever its
outcome (completed / shed / deferred); the SLO monitor pages once per
breach episode and re-arms with hysteresis; and none of it adds a host
sync to the telemetry-off poll loop (the lint proves that mechanically,
the bit-parity test proves the decode math never noticed).
"""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.observability import telemetry
from dalle_pytorch_tpu.observability.metrics import (
    HistogramWindow, MetricsRegistry,
)
from dalle_pytorch_tpu.observability.slo import (
    SloMonitor, SloTargets, write_status_json,
)
from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine
from dalle_pytorch_tpu.serving.scheduler import AdmissionRefused

from test_serving import base, fused_ref, tiny_cfg  # noqa: F401 — fixtures


def _load_spans(path: Path):
    from telemetry_report import load_records

    return load_records(path)


# --------------------------------------------------------------------------
# request lifecycle records


def test_request_records_all_outcomes(base, tmp_path):  # noqa: F811
    """completed, shed, and deferred requests each leave a request record;
    completed phases sum exactly to the measured latency."""
    cfg, params, text = base
    tele = telemetry.configure(str(tmp_path), run_name="serve",
                               heartbeat_s=None, watch_compiles=False)
    try:
        # shed: a pool too small for one sequence refuses at submit
        tiny = GenerationEngine(params, cfg,
                                engine_cfg=EngineConfig(num_slots=2,
                                                        block_size=4,
                                                        num_blocks=2))
        with pytest.raises(AdmissionRefused):
            tiny.submit(text[0])

        eng = GenerationEngine(params, cfg,
                               engine_cfg=EngineConfig(num_slots=2,
                                                       block_size=4,
                                                       telemetry_every=4))
        eng.submit(text[0], key=jax.random.PRNGKey(0))
        done = eng.run_until_idle()
        assert len(done) == 1
        # deferred: queued work the server shuts down on
        eng.submit(text[1], key=jax.random.PRNGKey(1))
        eng.close()
    finally:
        tele.flush(fleet=False)
        tele.close()

    recs = [r for r in _load_spans(tmp_path / "serve.spans.jsonl")
            if r.get("kind") == "request"]
    by_outcome = {}
    for r in recs:
        by_outcome.setdefault(r["outcome"], []).append(r)
    assert set(by_outcome) == {"completed", "shed", "deferred"}
    assert len(by_outcome["completed"]) == 1

    comp = by_outcome["completed"][0]
    phases = comp["phases"]
    for name in ("queue_wait", "admission", "prefill", "decode", "evict_sync",
                 "codes_pull", "evict"):
        assert name in phases, f"missing phase {name}"
    assert comp["latency_s"] == pytest.approx(sum(phases.values()), abs=1e-4)
    assert comp["decode_tokens"] == cfg.image_seq_len
    assert comp["request_id"] is not None

    shed = by_outcome["shed"][0]
    assert shed["reason"] and "queue_wait" in shed["phases"]
    deferred = by_outcome["deferred"][0]
    assert "queue_wait" in deferred["phases"]


def test_phases_recorded_with_telemetry_off(base):  # noqa: F811
    """The trace is stamped on the Request object regardless of telemetry —
    only the JSONL write is gated — and decode output stays bit-exact with
    the monitor attached (no jax work happens on the bookkeeping path)."""
    cfg, params, text = base
    assert telemetry.active() is None
    reg = MetricsRegistry()
    eng = GenerationEngine(params, cfg,
                           engine_cfg=EngineConfig(num_slots=2, block_size=4))
    eng.attach_slo(SloMonitor(SloTargets(ttft_p99_s=1e-6), registry=reg))
    keys = [jax.random.PRNGKey(70 + i) for i in range(2)]
    reqs = eng.generate(text[:2], keys=keys)
    for i, req in enumerate(reqs):
        np.testing.assert_array_equal(req.codes[None],
                                      fused_ref(params, cfg, text[i], keys[i]))
        assert req.outcome == "completed"
        assert req.latency_s == pytest.approx(sum(req.phases.values()),
                                              abs=1e-4)


def test_serving_window_phase_gauges_and_status_json(base, tmp_path):  # noqa: F811
    """serving_window events carry the poll-loop phase split + goodput;
    slo_window events and the atomic status.json ride the same cadence."""
    cfg, params, text = base
    status = tmp_path / "status.json"
    tele = telemetry.configure(str(tmp_path), run_name="serve",
                               heartbeat_s=None, watch_compiles=False)
    try:
        eng = GenerationEngine(params, cfg,
                               engine_cfg=EngineConfig(num_slots=2,
                                                       block_size=4,
                                                       telemetry_every=4))
        mon = SloMonitor(
            SloTargets(ttft_p99_s=1e-6), short_windows=1, long_windows=2,
            on_alarm=lambda a: tele.alarm(a.pop("type", "slo_burn_rate"), **a))
        eng.attach_slo(mon, status_path=str(status))
        eng.generate(text[:2], keys=[jax.random.PRNGKey(80 + i)
                                     for i in range(2)])
        eng.close()
    finally:
        tele.flush(fleet=False)
        tele.close()

    recs = _load_spans(tmp_path / "serve.spans.jsonl")
    windows = [r for r in recs if r.get("kind") == "serving_window"]
    assert windows
    w = windows[-1]
    assert set(w["phase_s"]) == {"admit", "dispatch", "block", "evict"}
    assert 0.0 <= w["goodput_frac"] <= 1.0
    assert [r for r in recs if r.get("kind") == "slo_window"]
    assert [r for r in recs if r.get("kind") == "alarm"
            and r.get("type") == "slo_burn_rate"]

    doc = json.loads(status.read_text())
    assert doc["targets"] == {"ttft_p99_s": 1e-6}
    assert "ttft_p99" in doc["active_alarms"]
    assert doc["live"]["completed"] >= 2
    assert doc["serving"]["queue_depth"] == 0

    # the renderer understands the new stream end to end
    from serving_report import build_report

    out = build_report(recs)
    assert "phase attribution" in out and "waterfall" in out
    assert "SLO windows" in out and "SLO burn-rate alarms" in out


# --------------------------------------------------------------------------
# the engine's phases as spans on the profiler's clock


# child span -> the span it must lie inside (the tree of serving/engine.py)
SPAN_TREE = {
    "serve/admit": "serve/poll",
    "serve/admit.alloc": "serve/admit",
    "serve/admit.dispatch": "serve/admit",
    "serve/admit.lane_meta": "serve/admit",
    "serve/admit.ttft_sync": "serve/admit",
    "serve/decode.dispatch": "serve/poll",
    "serve/spec.draft": "serve/decode.dispatch",
    "serve/spec.verify": "serve/decode.dispatch",
    "serve/evict": "serve/poll",
    "serve/evict.flag_sync": "serve/evict",
    "serve/evict.codes_pull": "serve/evict",
    "serve/evict.lane_reset": "serve/evict",
    "serve/evict.vae_decode": "serve/evict",
    "serve/evict.pixels_pull": "serve/evict",
}


@pytest.fixture(scope="module")
def profiled_polls(base, tmp_path_factory):  # noqa: F811
    """(serve/ events of the host plane, the requests) of two tiny engines,
    one with a VAE and one speculative, polled to completion under a
    `jax.profiler` session with NO Telemetry configured."""
    from jax.profiler import ProfileData

    from dalle_pytorch_tpu.models.vae import DiscreteVAEConfig, init_discrete_vae

    cfg, params, text = base
    assert telemetry.active() is None
    vcfg = DiscreteVAEConfig(image_size=16, num_tokens=cfg.num_image_tokens,
                             num_layers=2, hidden_dim=8, codebook_dim=8)
    vparams = init_discrete_vae(jax.random.PRNGKey(3), vcfg)
    engines = [
        GenerationEngine(params, cfg, vae_params=vparams, vae_cfg=vcfg,
                         engine_cfg=EngineConfig(num_slots=2, block_size=4)),
        GenerationEngine(params, cfg,
                         engine_cfg=EngineConfig(num_slots=2, block_size=4, spec_k=2)),
    ]
    out = tmp_path_factory.mktemp("prof")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=options)
    try:
        reqs = [eng.generate(text[:2], keys=[jax.random.PRNGKey(90 + i) for i in range(2)])
                for eng in engines]
    finally:
        jax.profiler.stop_trace()
    events = []
    pb = sorted(out.glob("**/*.xplane.pb"))[-1]
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                           for ev in line.events if ev.name.startswith("serve/")]
    return events, reqs


@pytest.mark.parametrize("child,parent", sorted(SPAN_TREE.items()))
def test_profiler_session_alone_records_the_span_tree(profiled_polls, child, parent):
    """A running profiler session is the one switch: every span of the tree
    is in the host plane, each inside a span of its parent's name, carrying
    the poll's `iter` (and the request's `req` where it belongs to one)."""
    events, _ = profiled_polls
    kids = [e for e in events if e[0] == child]
    assert kids, f"no {child} event in the host plane"
    parents = [e for e in events if e[0] == parent]
    for _, a, b, stats in kids:
        inside = [p for p in parents if p[1] <= a and b <= p[2]]
        assert inside, f"{child} outside every {parent}"
        assert str(stats["iter"]) == str(inside[-1][3]["iter"])
        if child.startswith("serve/admit") or child in (
                "serve/evict.codes_pull", "serve/evict.vae_decode", "serve/evict.pixels_pull"):
            assert "req" in stats


def test_spans_of_one_request_share_its_id(profiled_polls):
    events, reqs = profiled_polls
    req = reqs[0][0]  # first request of the engine with a VAE
    first_engine_polls = max(int(e[3]["iter"]) for e in events if e[0] == "serve/evict.vae_decode")
    mine = {e[0] for e in events if e[3].get("req") is not None
            and int(e[3]["req"]) == req.id and int(e[3]["iter"]) <= first_engine_polls}
    assert {"serve/submit", "serve/admit", "serve/admit.alloc", "serve/admit.dispatch",
            "serve/admit.lane_meta", "serve/admit.ttft_sync", "serve/evict.codes_pull",
            "serve/evict.vae_decode", "serve/evict.pixels_pull"} <= mine
    for r in reqs[0]:
        assert set(r.phases) == {"queue_wait", "admission", "prefill", "decode", "evict_sync",
                                 "codes_pull", "vae_decode", "evict"}
        assert r.latency_s == pytest.approx(sum(r.phases.values()), abs=1e-4)
        assert r.images is not None


def test_span_without_session_or_telemetry_writes_nothing(tmp_path, monkeypatch):
    """No profiler session, no Telemetry: `span` is a bare TraceAnnotation
    (inert), aggregate spans stay no-ops, `timed_span` still hands back its
    duration, and nothing is written anywhere."""
    monkeypatch.chdir(tmp_path)
    assert telemetry.active() is None
    cm = telemetry.span("serve/poll", iter=1, req=2)
    assert isinstance(cm, jax.profiler.TraceAnnotation)
    with cm:
        pass
    with telemetry.span("decode_image", aggregate=True) as nothing:
        assert nothing is None
    with telemetry.timed_span("serve/evict", iter=1) as t:
        time.sleep(0.002)
    assert 0.002 <= t.s < 1.0
    assert list(tmp_path.iterdir()) == []


def test_telemetry_configured_writes_the_same_spans_to_jsonl(base, tmp_path):  # noqa: F811
    """With a Telemetry the same calls also leave JSONL span records, flushed
    once a telemetry window (the engine has no step boundary)."""
    cfg, params, text = base
    tele = telemetry.configure(str(tmp_path), run_name="serve",
                               heartbeat_s=None, watch_compiles=False)
    try:
        eng = GenerationEngine(params, cfg,
                               engine_cfg=EngineConfig(num_slots=2, block_size=4,
                                                       telemetry_every=4))
        (req,) = eng.generate(text[:1], keys=[jax.random.PRNGKey(5)])
        eng.close()
    finally:
        tele.close()
    spans = [r for r in _load_spans(tmp_path / "serve.spans.jsonl") if r.get("kind") == "span"]
    names = {r["name"] for r in spans}
    assert {"serve/submit", "serve/poll", "serve/decode.dispatch", "serve/evict",
            "serve/evict.flag_sync"} <= names
    assert {k for k, v in SPAN_TREE.items() if v == "serve/admit"} <= names
    admit = next(r for r in spans if r["name"] == "serve/admit.alloc")
    assert admit["req"] == req.id
    assert admit["dur_s"] == pytest.approx(req.phases["admission"], abs=1e-4)
    polls = [r for r in spans if r["name"] == "serve/poll"]
    assert len(polls) == cfg.image_seq_len - 1  # none dropped, none buffered at close


# --------------------------------------------------------------------------
# windowed percentiles + burn-rate episodes


def test_histogram_window_delta_percentiles():
    """advance() sees exactly the observations since the previous advance();
    log2-bucket percentiles are within 2x of the exact value and clamped to
    the cumulative extrema."""
    reg = MetricsRegistry()
    h = reg.histogram("t")
    win = HistogramWindow(h)

    first = [0.010, 0.011, 0.012, 0.013]
    for v in first:
        h.observe(v)
    d = win.advance()
    assert d["count"] == len(first)
    assert d["total"] == pytest.approx(sum(first))
    assert max(first) / 2 <= d["p99"] <= max(first)

    # empty window: no signal, percentiles None
    d = win.advance()
    assert d["count"] == 0 and d["p50"] is None and d["mean"] is None

    # a much slower second window must NOT be averaged with the first
    second = [1.0, 1.1, 1.2, 1.3]
    for v in second:
        h.observe(v)
    d = win.advance()
    assert d["count"] == len(second)
    assert d["p50"] >= 0.5, "window percentile leaked earlier fast samples"
    assert d["p99"] <= h.max

    # cumulative view still covers everything
    assert h.count == len(first) + len(second)


def test_slo_monitor_fires_once_rearms_and_roundtrips():
    """A sustained breach pages exactly once; recovery re-arms the episode;
    a restart that loads state_dict does not re-page mid-episode."""
    reg = MetricsRegistry()
    clock = {"t": 0.0}
    alarms = []
    mon = SloMonitor(SloTargets(ttft_p99_s=0.1), registry=reg,
                     on_alarm=alarms.append, short_windows=1, long_windows=3,
                     clock=lambda: clock["t"])
    h = reg.histogram("serving/ttft_s")
    comp = reg.counter("serving/completed")

    def window(ttfts):
        clock["t"] += 10.0
        for v in ttfts:
            h.observe(v)
            comp.inc()
        return mon.observe(iteration=int(clock["t"]))

    window([1.0, 1.2])            # burn 10x+: breach
    assert [a["slo"] for a in alarms] == ["ttft_p99"]
    assert alarms[0]["burn_short"] >= 1.0 and alarms[0]["measured"] > 0.1
    window([1.0, 1.2])            # still breaching: same episode, no re-page
    assert len(alarms) == 1
    rec = window([0.001, 0.002])  # healthy: episode ends, re-arms
    assert rec["active_alarms"] == []
    window([1.0])                 # new breach -> second page
    assert len(alarms) == 2
    assert mon.alarms_total == 2

    # restart mid-episode: loaded state remembers the live alarm
    state = mon.state_dict()
    mon2 = SloMonitor(SloTargets(ttft_p99_s=0.1), registry=reg,
                      on_alarm=alarms.append, short_windows=1, long_windows=3,
                      clock=lambda: clock["t"])
    mon2.load_state_dict(state)
    assert mon2.state_dict() == state
    clock["t"] += 10.0
    h.observe(1.0)
    comp.inc()
    mon2.observe()
    assert len(alarms) == 2, "restart re-paged for an already-paged episode"


def test_slo_monitor_empty_windows_do_not_page():
    """Windows with no signal neither burn nor heal: an idle server with a
    live episode keeps it; an idle healthy server never pages."""
    reg = MetricsRegistry()
    alarms = []
    mon = SloMonitor(SloTargets(ttft_p99_s=0.1, shed_rate_ceiling=0.5),
                     registry=reg, on_alarm=alarms.append,
                     clock=iter(range(0, 1000, 10)).__next__)
    for _ in range(5):
        assert mon.observe()["burns"] == {}
    assert alarms == [] and mon.state_dict()["alarmed"] == []


# --------------------------------------------------------------------------
# telemetry-off purity + heartbeat context


def test_serving_modules_host_sync_clean():
    """The lint that keeps the poll loop sync-free covers the serving
    package and the SLO monitor; slo.py never imports jax at all."""
    from lint_host_sync import lint_paths

    root = Path(__file__).resolve().parents[1]
    findings = lint_paths(str(root), targets=(
        "dalle_pytorch_tpu/serving", "dalle_pytorch_tpu/observability/slo.py"))
    assert findings == [], "\n".join(str(f) for f in findings)

    src = (root / "dalle_pytorch_tpu/observability/slo.py").read_text()
    assert "import jax" not in src


def test_heartbeat_context_fn_in_hang_dump(tmp_path):
    """A stalled poll loop's hang report includes the engine-state context
    the serve CLI wires in (which phase, which requests in flight)."""
    from dalle_pytorch_tpu.observability.heartbeat import Heartbeat

    hb = Heartbeat(deadline_s=0.2, dir=str(tmp_path), poll_s=0.05,
                   context_fn=lambda: {"phase": "dispatch", "iter": 7,
                                       "queue_depth": 3})
    hb.start()
    try:
        hb.beat(1)
        deadline = time.monotonic() + 5.0
        while hb.hangs == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        hb.stop()
    assert hb.hangs >= 1
    dumps = list(tmp_path.glob("hang_*.txt"))
    assert dumps
    report = dumps[0].read_text()
    assert "--- state context ---" in report
    assert "phase: dispatch" in report and "queue_depth: 3" in report


def test_write_status_json_atomic(tmp_path):
    p = tmp_path / "deep" / "status.json"
    write_status_json(str(p), {"a": 1})
    assert json.loads(p.read_text()) == {"a": 1}
    write_status_json(str(p), {"a": 2})
    assert json.loads(p.read_text()) == {"a": 2}
    assert not list(p.parent.glob(".*tmp"))
