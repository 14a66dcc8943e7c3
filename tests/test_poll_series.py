"""The registry's fixed-capacity series, and the row the engine writes into
`serving/polls` for every poll() (PR 35).

The series: rows go into one block allocated at construction (no growth a
row), wrap-around keeps their order and counts what it overwrote, and
`rows(since=)` hands back copies by sequence number.  The engine: one row a
poll with the spans' own readings, of which the `serving_window` event and
the status file's `worst_poll` are sums and a maximum; a wedged poll leaves
none.
"""
import json
import time
import tracemalloc

import numpy as np
import pytest

import jax

from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.observability import telemetry
from dalle_pytorch_tpu.observability.metrics import MetricsRegistry, Series
from dalle_pytorch_tpu.observability.slo import SloMonitor, SloTargets
from dalle_pytorch_tpu.serving import engine as engine_mod
from dalle_pytorch_tpu.serving.engine import (
    POLL_COLUMNS, POLL_PHASES, POLL_SERIES, EngineConfig, GenerationEngine,
)

from test_serving import base, tiny_cfg  # noqa: F401 — fixtures


# --------------------------------------------------------------------------
# the instrument


def test_series_keeps_rows_in_order_by_named_column():
    reg = MetricsRegistry()
    s = reg.series("t/rows", ("a", "b"), capacity=8)
    for i in range(5):
        s.append(i, 10.0 * i)
    rows = s.rows()
    assert list(rows) == ["a", "b"]
    assert rows["a"].tolist() == [0, 1, 2, 3, 4] and rows["b"].tolist() == [0, 10, 20, 30, 40]
    assert len(s) == 5 and s.total == 5 and s.dropped == 0
    # a copy: what the caller does to it does not reach the series
    rows["a"][:] = -1
    assert s.rows()["a"].tolist() == [0, 1, 2, 3, 4]


def test_series_wrap_around_keeps_order_and_counts_dropped():
    s = MetricsRegistry().series("t/wrap", ("n", "x"), capacity=4)
    for i in range(11):
        s.append(i, i * 0.5)
    assert len(s) == 4 and s.total == 11 and s.dropped == 7
    assert s.rows()["n"].tolist() == [7, 8, 9, 10]
    assert s.rows()["x"].tolist() == [3.5, 4.0, 4.5, 5.0]


@pytest.mark.parametrize("since,want", [
    (0, [7, 8, 9, 10]),   # rows 0-6 are gone: what is held of them
    (8, [8, 9, 10]),
    (10, [10]),
    (11, []),             # nothing newer yet
    (50, []),
])
def test_series_rows_since_a_sequence_number(since, want):
    s = MetricsRegistry().series("t/since", ("n",), capacity=4)
    for i in range(11):
        s.append(i)
    got = s.rows(since=since)["n"]
    assert got.tolist() == want and got.dtype == np.float64


def test_series_append_allocates_nothing_a_row():
    s = MetricsRegistry().series("t/alloc", POLL_COLUMNS, capacity=4096)
    for i in range(100):  # whatever the first calls cache is there
        s.append(i, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 1, 0, 8)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(10_000):
            s.append(i, time.perf_counter(), 2.0, 3.0, 4.0, 5.0, 6.0, 1, 0, 8)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(d.size_diff for d in after.compare_to(before, "filename")
                if "tracemalloc" not in d.traceback[0].filename)
    # 10,000 rows of ten float64 would be 800,000 bytes if a row were kept
    assert grown < 4096, grown
    assert s.total == 10_100 and s.dropped == 10_100 - 4096


def test_series_in_the_registry_like_the_other_instruments():
    reg = MetricsRegistry()
    assert reg.series("t/none") is None, "a reader asks; nothing is made"
    s = reg.series("t/reg", ("a",), capacity=4)
    assert reg.series("t/reg", ("a",)) is s and reg.series("t/reg") is s
    with pytest.raises(ValueError):
        reg.series("t/reg", ("a", "b"))
    reg.counter("t/count")
    with pytest.raises(TypeError):
        reg.series("t/count", ("a",))
    with pytest.raises(TypeError):
        reg.gauge("t/reg")
    for i in range(6):
        s.append(i)
    snap = reg.snapshot()
    assert snap["t/reg"] == {"rows": 4, "dropped": 2, "kind": "series"}
    json.dumps(snap)  # a flush writes it as it is
    # a writer that owns its series takes the name over, empty
    fresh = reg.series("t/reg", ("a",), capacity=4, fresh=True)
    assert fresh is not s and len(fresh) == 0 and reg.series("t/reg") is fresh
    reg.reset()
    assert reg.series("t/reg") is None
    assert isinstance(obs_metrics.series("t/module", ("a",), 2), Series)
    assert obs_metrics.REGISTRY.series("t/module").capacity == 2


# --------------------------------------------------------------------------
# the engine's rows


@pytest.fixture(scope="module")
def served(base, tmp_path_factory):  # noqa: F811
    """Three requests through two slots with telemetry on, a window event
    every four polls and a status file."""
    cfg, params, text = base
    out = tmp_path_factory.mktemp("polls")
    tele = telemetry.configure(str(out), run_name="serve", heartbeat_s=None,
                               watch_compiles=False)
    try:
        eng = GenerationEngine(params, cfg,
                               engine_cfg=EngineConfig(num_slots=2, block_size=4,
                                                       telemetry_every=4))
        eng.attach_slo(SloMonitor(SloTargets(ttft_p99_s=10.0)),
                       status_path=str(out / "status.json"))
        reqs = eng.generate(text[:3], keys=[jax.random.PRNGKey(90 + i) for i in range(3)])
        rows = eng.polls.rows()
        eng.close()
    finally:
        tele.flush(fleet=False)
        tele.close()
    records = [json.loads(line) for line in open(out / "serve.spans.jsonl")]
    return {"cfg": cfg, "engine": eng, "requests": reqs, "rows": rows, "records": records,
            "status": json.loads((out / "status.json").read_text())}


def test_engine_writes_one_row_a_poll_under_its_name(served):
    eng, rows = served["engine"], served["rows"]
    assert obs_metrics.series(POLL_SERIES) is eng.polls
    assert eng.polls.columns == POLL_COLUMNS and eng.polls.capacity == 65536
    assert tuple(rows) == POLL_COLUMNS
    assert len(rows["iter"]) == eng._iter == eng.polls.total
    assert rows["iter"].tolist() == list(range(1, eng._iter + 1)), "contiguous, as `_iter` counts"
    # one clock, forward only: a poll starts after the one before it ended
    ends = rows["t0_s"] + rows["dur_s"]
    assert (rows["dur_s"] > 0).all() and (rows["t0_s"][1:] >= ends[:-1]).all()


def test_rows_count_what_the_run_did(served):
    cfg, rows, reqs = served["cfg"], served["rows"], served["requests"]
    assert rows["admitted"].sum() == 3 and rows["evicted"].sum() == 3
    # two slots: two admitted in the first poll, the third when a lane is free
    assert rows["admitted"][0] == 2 and rows["lanes"].max() == 2
    # a request decodes n_gen - 1 lane-tokens after the one its prefill made
    assert rows["lanes"].sum() == 3 * (cfg.image_seq_len - 1)
    assert all(r.codes_done == cfg.image_seq_len for r in reqs)
    # time is booked where the work was: admission in the polls that admitted ...
    assert ((rows["admit_s"] > 0) == (rows["admitted"] > 0)).all()
    assert ((rows["block_s"] > 0) == (rows["evicted"] > 0)).all()
    assert ((rows["evict_s"] > 0) == (rows["evicted"] > 0)).all()
    assert ((rows["dispatch_s"] > 0) == (rows["lanes"] > 0)).all()


def test_a_rows_parts_never_exceed_its_duration(served):
    rows = served["rows"]
    parts = sum(rows[f"{p}_s"] for p in POLL_PHASES)
    assert (parts <= rows["dur_s"]).all()
    assert (parts > 0.5 * rows["dur_s"]).all(), "the spans cover most of a poll"


def test_window_events_are_sums_of_their_rows(served):
    rows = served["rows"]
    windows = [r for r in served["records"] if r.get("kind") == "serving_window"]
    assert len(windows) >= 3
    lo = 0
    for w in windows:
        sel = (rows["iter"] > lo) & (rows["iter"] <= w["iter"])
        lo = w["iter"]
        assert set(w["phase_s"]) == set(POLL_PHASES)
        for p in POLL_PHASES:
            assert w["phase_s"][p] == pytest.approx(rows[f"{p}_s"][sel].sum(), abs=1e-6)
        steps = int((rows["dispatch_s"][sel] > 0).sum())
        assert w["decode_steps"] == steps
        lane_tokens = rows["lanes"][sel].sum()
        if steps:
            assert w["goodput_frac"] == pytest.approx(lane_tokens / (2 * steps))
            span = (rows["t0_s"][sel] + rows["dur_s"][sel])[-1] - rows["t0_s"][sel][0]
            assert w["lane_tokens_per_s"] == pytest.approx(lane_tokens / span)
    # every row is in one window: close() flushed what followed the last event
    assert lo == rows["iter"][-1]
    assert sum(w["decode_steps"] for w in windows) == (rows["dispatch_s"] > 0).sum()


def test_status_file_names_the_last_windows_worst_poll(served):
    rows = served["rows"]
    # the file was last written by close(): the polls since the last event
    worst = served["status"]["serving"]["worst_poll"]
    assert worst["iter"] > rows["iter"][-1] - 4
    i = int(np.flatnonzero(rows["iter"] == worst["iter"])[0])
    assert worst["dur_s"] == pytest.approx(rows["dur_s"][i], abs=1e-6)
    parts = {p: rows[f"{p}_s"][i] for p in POLL_PHASES}
    parts["other"] = rows["dur_s"][i] - sum(parts.values())
    assert worst["phase"] == max(parts, key=parts.get)
    assert worst["phase_s"] == pytest.approx(parts[worst["phase"]], abs=1e-6)


def test_worst_poll_of_a_window_is_its_longest(base):  # noqa: F811
    cfg, params, text = base
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
        num_slots=2, block_size=4, telemetry_every=0))
    eng.generate(text[:1], keys=[jax.random.PRNGKey(3)])
    rows = eng.polls.rows()
    eng._window_event()
    i = int(rows["dur_s"].argmax())
    assert eng._worst_poll["iter"] == rows["iter"][i]
    assert eng._worst_poll["dur_s"] == pytest.approx(rows["dur_s"][i], abs=1e-6)
    assert eng._worst_poll["phase"] in POLL_PHASES + ("other",)
    eng._window_event()
    assert eng._worst_poll is None, "an empty window has no worst poll"


def test_a_wedged_poll_writes_no_row(base):  # noqa: F811
    cfg, params, text = base
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=2, block_size=4))
    eng.submit(text[0], key=jax.random.PRNGKey(5))
    eng.poll()
    assert eng.polls.total == 1 and eng._iter == 1
    eng.wedge(60.0)
    for _ in range(5):
        assert eng.poll() == []
    assert eng.polls.total == 1 and eng._iter == 1
    eng._stall_until = time.monotonic() - 1.0  # the wedge has run out
    eng.poll()
    assert eng.polls.rows()["iter"].tolist() == [1, 2]


def test_a_later_engine_takes_the_name_and_a_replica_has_its_own(base):  # noqa: F811
    cfg, params, text = base
    ecfg = EngineConfig(num_slots=2, block_size=4)
    first = GenerationEngine(params, cfg, engine_cfg=ecfg)
    first.submit(text[0], key=jax.random.PRNGKey(6))
    first.poll()
    second = GenerationEngine(params, cfg, engine_cfg=ecfg)
    assert obs_metrics.series(POLL_SERIES) is second.polls and len(second.polls) == 0
    assert first.polls.total == 1, "the first engine keeps its own rows"
    second.replica_id = 3  # what a router does once its replicas are built
    assert obs_metrics.series(f"{POLL_SERIES}.r3") is second.polls
    assert second.replica_id == 3 and len(second.polls) == 0


def test_no_switch_turns_the_series_off():
    fields = {f.name for f in engine_mod.dataclasses.fields(EngineConfig)}
    assert not {f for f in fields if "poll" in f or "series" in f}
    src = open(engine_mod.__file__).read()
    assert "os.environ" not in src and "getenv" not in src
    for gone in ("_phase_acc", "_win_decode_steps", "_win_lane_tokens", "_win_t"):
        assert gone + " " not in src and gone + "[" not in src and gone + "\n" not in src, gone
