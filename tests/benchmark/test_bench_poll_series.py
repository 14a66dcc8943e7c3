"""The engine's poll series reduced to completion gaps (PR 35): the four
readers on hand-made series, the join of the harness's poll count with the
engine's `iter` on a rehearsal run, and `stall_report.py` on a hand-made run
and, for the join by `iter` with a trace, on the `serve/poll` spans of the
chip fixture."""
import gc
import io
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import bench_rules  # noqa: E402
from benchmark.harness import device, manifest, tracer  # noqa: E402
from benchmark.harness import poll_series as ps  # noqa: E402
from benchmark.harness import program_trace as pt  # noqa: E402
from benchmark.harness import trace_reduce as tr  # noqa: E402
from benchmark.kinds import closed_loop  # noqa: E402
from benchmark.tools import stall_report  # noqa: E402

READERS = ["completion_gap_excess_pct", "gap_excess_blocked_ms", "gap_excess_host_ms",
           "between_polls_pct"]
# not `serve_olmoh_s32`: an accepted test holds that cell's per-layer set equal to its accepted
# rehearsal manifest's, and this PR may edit neither (PERF.md section 7)
SERVING = ["serve_batch", "serve_guided"]
COLUMNS = ("iter", "t0_s", "dur_s", "admit_s", "dispatch_s", "block_s", "evict_s",
           "admitted", "evicted", "lanes")
MAN = manifest.load()


# ---- hand-made series ---------------------------------------------------------
def synth(gaps=10, polls=16, dur=None, between=2e-5, first_iter=1):
    """`gaps` completion gaps of `polls` polls: rows as the engine writes them
    and the polls that returned completions.  A poll is `dur[j]` long (4 ms
    where none is given), nine tenths of it under `serve/decode.dispatch`; the
    last poll of a gap evicts (20 ms more: 12 waiting for the device, 8 of the
    eviction's own) and the first admits (5 ms more)."""
    n = (gaps + 1) * polls
    place = np.arange(n) % polls
    d = np.full(n, 0.004) if dur is None else np.asarray(dur, float).copy()
    rows = {c: np.zeros(n) for c in COLUMNS}
    rows["iter"] = np.arange(first_iter, first_iter + n, dtype=float)
    rows["dispatch_s"] = 0.9 * d
    evicts, admits = place == polls - 1, place == 0
    rows["block_s"][evicts], rows["evict_s"][evicts] = 0.012, 0.008
    rows["admit_s"][admits] = 0.005
    rows["dur_s"] = d + 0.020 * evicts + 0.005 * admits
    rows["admitted"], rows["evicted"] = admits.astype(float), evicts.astype(float)
    rows["lanes"][:] = 8
    step = rows["dur_s"] + between
    rows["t0_s"] = 1000.0 + np.concatenate([[0.0], np.cumsum(step)[:-1]])
    return rows, rows["iter"][evicts].astype(int).tolist()


def stall_in(rows, row, column, seconds):
    """`seconds` more in one poll's `column` (and so in its `dur_s`), or, for
    `between`, before that poll; every later poll moves back by as much."""
    rows = {c: v.copy() for c, v in rows.items()}
    if column != "between":
        rows[column][row] += seconds
        rows["dur_s"][row] += seconds
        row += 1
    rows["t0_s"][row:] += seconds
    return rows


def four(gaps):
    return {name: getattr(ps, name)(gaps) for name in READERS}


def test_a_clean_window_reads_zero():
    rows, polls = synth()
    g = ps.window_gaps(rows, polls)
    assert len(g) == 10 and set(g.n_polls.tolist()) == {16}
    got = four(g)
    assert got["completion_gap_excess_pct"] == pytest.approx(0.0, abs=1e-9)
    assert got["gap_excess_blocked_ms"] == pytest.approx(0.0, abs=1e-9)
    assert got["gap_excess_host_ms"] == pytest.approx(0.0, abs=1e-9)
    assert got["between_polls_pct"] == pytest.approx(100 * 16 * 2e-5 / g.wall_s[0], rel=1e-6)
    # the six parts of a gap are all of it
    assert sum(g.parts[p] for p in ps.PARTS) == pytest.approx(g.wall_s, abs=1e-12)
    assert g.marked() == []


def test_a_stall_in_dispatch_is_the_runtimes_and_not_the_hosts():
    rows, polls = synth()
    clean = ps.window_gaps(rows, polls)
    g = ps.window_gaps(stall_in(rows, 16 * 5 + 7, "dispatch_s", 0.3), polls)
    got = four(g)
    assert got["completion_gap_excess_pct"] == pytest.approx(100 * 0.3 / (clean.wall_s.sum() + 0.3))
    assert got["gap_excess_blocked_ms"] == pytest.approx(300.0 / 10)
    assert got["gap_excess_host_ms"] == pytest.approx(0.0, abs=1e-6)
    assert got["between_polls_pct"] < four(clean)["between_polls_pct"]
    assert g.marked() == [4]  # rows 80-95 close at the sixth completion: the fifth gap
    (it, dur, part, secs), *_ = g.longest_polls(4)
    assert it == rows["iter"][87] and part == "dispatch_s" and secs == pytest.approx(0.3036)


def test_a_stall_between_two_polls_is_the_hosts():
    rows, polls = synth()
    clean = four(ps.window_gaps(rows, polls))
    g = ps.window_gaps(stall_in(rows, 16 * 3 + 2, "between", 0.3), polls)
    got = four(g)
    assert got["completion_gap_excess_pct"] == pytest.approx(100 * 0.3 / g.wall_s.sum())
    assert got["gap_excess_host_ms"] == pytest.approx(30.0)
    assert got["gap_excess_blocked_ms"] == pytest.approx(0.0, abs=1e-6)
    assert got["between_polls_pct"] == pytest.approx(
        100 * (10 * 16 * 2e-5 + 0.3) / g.wall_s.sum(), rel=1e-6)
    assert got["between_polls_pct"] > 10 * clean["between_polls_pct"]
    assert g.parts["between_s"][2] == pytest.approx(16 * 2e-5 + 0.3)


def test_a_bursty_host_with_equal_gap_sums_reads_zero():
    """The host runs ahead of the device: polls of 1 ms, then one held 130 ms
    by the steps in flight, at another place in every gap."""
    polls_a_gap, gaps = 32, 9
    dur = np.full((gaps + 1) * polls_a_gap, 0.001)
    for k in range(gaps + 1):
        dur[k * polls_a_gap + (5 * k + 3) % (polls_a_gap - 2) + 1] = 0.130
    rows, polls = synth(gaps, polls_a_gap, dur=dur)
    g = ps.window_gaps(rows, polls)
    assert rows["dur_s"].max() > 100 * np.median(rows["dur_s"])
    for name, value in four(g).items():
        if name != "between_polls_pct":
            assert value == pytest.approx(0.0, abs=1e-9), name


@pytest.mark.parametrize("case", ["dropped", "too_few", "no_eviction", "no_series"])
def test_what_cannot_be_told_reads_null_and_says_why(case):
    rows, polls = synth()
    said = []
    if case == "dropped":  # the series wrapped: the window's first rows are gone
        rows = {c: v[40:] for c, v in rows.items()}
        rows["dropped"] = 40
    elif case == "too_few":
        polls = polls[:3]
    elif case == "no_eviction":  # the harness counted a poll the engine did not
        polls = [p - 1 for p in polls]
    else:
        rows = None
    assert ps.window_gaps(rows, polls, say=said.append) is None
    want = {"dropped": "dropped rows of the window", "too_few": "fewer than 3",
            "no_eviction": "count polls differently", "no_series": None}[case]
    assert (said == []) if want is None else (len(said) == 1 and want in said[0])


class _Ctx:
    def __init__(self, records, peaks):
        self.records, self.peaks = records, peaks


def _with_series(monkeypatch, rows):
    from dalle_pytorch_tpu.observability import metrics

    reg = metrics.MetricsRegistry()
    series = reg.series(ps.SERIES, COLUMNS, capacity=len(rows["iter"]) + 8)
    for row in zip(*(rows[c] for c in COLUMNS)):
        series.append(*row)
    monkeypatch.setattr(metrics, "REGISTRY", reg)
    return reg


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_window_from_the_registry(monkeypatch, name):
    rows, polls = synth()
    _with_series(monkeypatch, stall_in(rows, 100, "dispatch_s", 0.3))
    records = {"completions": [{"poll": p} for p in polls]}
    value = manifest.reader(name)(_Ctx(records, peaks={"hbm_bytes_per_s": 819e9}))
    assert isinstance(value, float) and value >= 0.0
    assert (value > 0.0) == (name != "gap_excess_host_ms") or value < 1e-6
    # a rehearsal on the CPU reports none of them: they are times
    assert manifest.reader(name)(_Ctx(records, peaks=None)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_on_a_program_without_the_series(monkeypatch, name):
    from dalle_pytorch_tpu.observability import metrics

    class ParentsRegistry:  # what a parent commit's registry offers: no series
        counter = gauge = histogram = None

    records = {"completions": [{"poll": p} for p in synth()[1]]}
    monkeypatch.setattr(metrics, "REGISTRY", ParentsRegistry())
    assert manifest.reader(name)(_Ctx(records, peaks={"x": 1})) is None
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())  # built, never served
    assert manifest.reader(name)(_Ctx(records, peaks={"x": 1})) is None


# ---- the harness's poll count is the engine's `iter` ------------------------------
REHEARSAL = manifest.load(ROOT / "benchmark" / "rehearsal" / "manifest.json")


@pytest.fixture(scope="module", params=["tiny_serve", "tiny_guided"])
def rehearsed(request):
    cell = manifest.cell(REHEARSAL, request.param)
    sizes = manifest.config_sizes(REHEARSAL, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    try:
        out = closed_loop.run(sizes, traffic, 11, 1e9, tracer.Tracer(False, ROOT / ".bench_trace" / "t"),
                              device.CompileCounter(), max_polls=90)
    finally:
        gc.unfreeze()
    return sizes, traffic, out, ps.engine_rows()


def test_completions_land_on_rows_that_evicted(rehearsed):
    sizes, traffic, out, rows = rehearsed
    records = out["records"]
    assert rows["iter"].tolist() == list(range(1, records["polls"] + 1))
    every = [c["poll"] for c in records["all_completions"]]
    assert every == ps.completion_polls(rows), "every eviction of the run is a completion of the harness"
    polls = [c["poll"] for c in records["completions"]]  # the window's: after the one that opened it
    assert bench_rules.run_of(polls, every) and polls[0] > records["window_open_polls"] == every[0]
    g = ps.window_gaps(rows, polls)
    stagger = sizes["image_fmap_size"] ** 2 // traffic["clients"]
    assert len(g) == len(polls) - 1 >= 3 and set(g.n_polls.tolist()) <= {stagger, stagger - 1}
    assert sum(g.parts[p] for p in ps.PARTS) == pytest.approx(g.wall_s, abs=1e-9)
    assert all((g.parts[p] >= -1e-12).all() for p in ps.PARTS)
    # the interval the gaps tile is the one gen_img_tok_per_s is taken over, on another clock
    times = [c["t"] for c in records["completions"]]
    assert g.wall_s.sum() == pytest.approx(times[-1] - times[0], abs=5e-3)


def test_readers_give_numbers_on_a_run_where_a_device_is_named(rehearsed):
    _, _, out, _ = rehearsed
    ctx = manifest.Context(sizes={}, traffic={}, records=out["records"], trace=None,
                           peaks={"named": "as on the chip"}, end_to_end={})
    values = {name: manifest.reader(name)(ctx) for name in READERS}
    assert all(isinstance(v, float) and np.isfinite(v) and v >= 0 for v in values.values()), values
    assert 0 < values["between_polls_pct"] < 100 and values["completion_gap_excess_pct"] < 100
    ctx = manifest.Context(sizes={}, traffic={}, records=out["records"], trace=None,
                           peaks=None, end_to_end={})
    assert [manifest.reader(name)(ctx) for name in READERS] == [None] * 4


# ---- stall_report.py -----------------------------------------------------------
def test_stall_report_marks_the_gap_and_names_its_part(tmp_path):
    rows, polls = synth()
    rows = stall_in(stall_in(rows, 16 * 5 + 7, "dispatch_s", 0.3), 16 * 8 + 3, "between", 0.05)
    line = {"detail": {"window": {"open_poll": polls[0], "completions": [[0, 0, 0, 0]] * 8}}}
    path = tmp_path / "run.json.gz"
    stall_report.keep(path, dict(rows, dropped=0), line)
    kept = stall_report.load(path)
    assert kept["line"] == line and kept["rows"]["dropped"] == 0
    for c in COLUMNS:
        assert kept["rows"][c].tolist() == rows[c].tolist()
    text = io.StringIO()
    shown = stall_report.report(kept["rows"], kept["line"], out=text)
    assert [m["gap"] for m in shown["marked"]] == [4, 7] and shown["clock"] is None
    first, second = shown["marked"]
    assert first["grew"] == "dispatch_s" and first["excess_s"] == pytest.approx(0.3, rel=1e-3)
    assert first["longest"][0]["iter"] == rows["iter"][87] and first["longest"][0]["part"] == "dispatch_s"
    assert len(first["longest"]) == 3
    assert second["grew"] == "between_s" and second["excess_by_part_s"]["between_s"] == pytest.approx(0.05)
    # of the eight completions the line lists seven gaps are the window's: those after the opening poll
    assert [g["window"] for g in shown["gaps"]] == [False] + [True] * 7 + [False] * 2
    body = text.getvalue().splitlines()
    assert sum(line.startswith("*") for line in body) == 2
    assert any("dispatch grew most" in line for line in body)
    assert any(f"poll iter {int(rows['iter'][87])}:" in line for line in body)
    assert stall_report.main([str(path)]) == 0


def test_stall_report_says_so_where_there_is_nothing_to_tell():
    rows, _ = synth(gaps=1)
    text = io.StringIO()
    assert stall_report.report(rows, out=text) is None and "fewer than 3" in text.getvalue()
    rows, _ = synth()
    text = io.StringIO()
    assert stall_report.report(rows, out=text)["marked"] == []
    assert "no gap is over the median" in text.getvalue()


OFFSET = 1_725_000_000.25  # the profiler's clock against time.perf_counter, seconds


def test_stall_report_joins_rows_and_spans_by_iter_on_the_chip_fixture():
    """The `serve/poll` spans of the fixture's serve part (iter 3069 on) beside
    rows made by hand from the spans' own times less a fixed offset; before
    them, 1 ms polls with an eviction every 20, so that the gap the fixture's
    admission falls into is the stalled one."""
    fixture = json.loads((ROOT / "benchmark" / "fixtures" / "program_trace_v5e.json").read_text())
    trace = pt.ProgramTrace(fixture["serve"]["events"])
    spans = sorted((s for s in trace.spans if s.name == "serve/poll"), key=lambda s: s.start)
    iters = [int(s.stats["iter"]) for s in spans]
    assert iters[0] == 3069 and iters == list(range(3069, 3069 + len(spans)))
    before = 80
    n = before + len(spans)
    rows = {c: np.zeros(n) for c in COLUMNS}
    rows["iter"] = np.arange(3069 - before, 3069 + len(spans), dtype=float)
    rows["dur_s"][:before] = 0.001
    rows["dispatch_s"][:before] = 0.0009
    first_t0 = spans[0].start * 1e-9 - OFFSET
    rows["t0_s"][:before] = first_t0 - 0.00102 * np.arange(before, 0, -1)
    for j, s in enumerate(spans, start=before):
        rows["t0_s"][j], rows["dur_s"][j] = s.start * 1e-9 - OFFSET, s.dur * 1e-9
        for child, column in (("serve/admit", "admit_s"), ("serve/decode.dispatch", "dispatch_s"),
                              ("serve/evict", "evict_s")):
            if s.child(child) is not None:
                rows[column][j] = s.child(child).dur * 1e-9
    rows["evicted"][[before - 60, before - 40, before - 20, before, before + 20]] = 1
    text = io.StringIO()
    shown = stall_report.report(rows, trace=trace, out=text)
    clock = shown["clock"]
    assert clock["polls"] == len(spans) and (clock["from_iter"], clock["to_iter"]) == (3069, iters[-1])
    assert clock["offset_median_s"] == pytest.approx(OFFSET, abs=1e-6)
    assert clock["offset_range_s"] < 1e-6 and clock["dur_diff_range_s"] < 1e-9
    # the gap to the eviction poll 3069 (its drain) and the one after it (the admission)
    assert [m["to_iter"] for m in shown["marked"]] == [3069, 3089]
    admit_gap = shown["marked"][1]
    assert admit_gap["grew"] == "admit_s"
    worst = admit_gap["longest"][0]
    assert worst["iter"] == 3070 and worst["part"] == "admit_s"
    span = spans[1]
    busy = sum(b - a for a, b in tr.busy_intervals([o[:3] for o in trace.ops], span.start, span.end))
    assert worst["device_busy_s"] == pytest.approx(busy * 1e-9) and busy > 0
    assert worst["device_busy_s"] + worst["device_idle_s"] == pytest.approx(span.dur * 1e-9)
    assert "device busy" in text.getvalue() and "span.start - t0_s median" in text.getvalue()
    # the eviction poll's drain is device-busy time: the device ran the queued steps
    drain = shown["marked"][0]["longest"][0]
    assert drain["iter"] == 3069 and drain["device_busy_s"] > 0.5 * drain["dur_s"]


# ---- the manifest ----------------------------------------------------------------
def pr35s_readers_are_one_run_on_the_serving_cells(man):
    assert bench_rules.run_of(READERS, bench_rules.names(man["per_layer"]))
    for name in READERS:
        m = bench_rules.entry(man, "per_layer", name)
        assert m["workloads"][:2] == SERVING
        assert (m["layer"], m["source"], m["moves"], m["better"]) == (
            "scheduler", "program_span", "gen_img_tok_per_s", "lower")
        assert m["unit"] == ("%" if name.endswith("_pct") else "ms")
    for cell in SERVING:
        assert set(READERS) <= bench_rules.per_layer_of(man, cell)


MANIFEST_RULES = [pr35s_readers_are_one_run_on_the_serving_cells]


def test_pr35s_readers_are_one_run_on_the_serving_cells():
    pr35s_readers_are_one_run_on_the_serving_cells(MAN)


def test_the_rules_take_the_four_entries_as_data():
    assert bench_rules.broken_by(MAN) == []
    assert bench_rules.broken_by(bench_rules.extended(MAN)) == []
    cut = json.loads(json.dumps(MAN))
    cut["per_layer"] = [m for m in cut["per_layer"] if m["name"] != "gap_excess_host_ms"]
    assert "test_bench_poll_series.py::pr35s_readers_are_one_run_on_the_serving_cells" in \
        bench_rules.broken_by(cut)
