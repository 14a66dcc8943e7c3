"""The reduction from a trace to numbers: on hand-made events whose answers can
be worked out by hand, and on a small trace recorded on the chip (fixture)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import json  # noqa: E402

from benchmark.harness import manifest, trace_reduce as tr  # noqa: E402

MS = 1e6  # ns


def _events():
    """10 ms window.  Device busy 0-2, 3-4 (two overlapping ops), 6-9 ms."""
    ops = [
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0 * MS, 2 * MS),
        ("%copy.7 = f32[8]{0} copy(f32[8]{0} %x)", 3 * MS, 1 * MS),
        ("%copy.8 = f32[8]{0} copy(f32[8]{0} %y)", 3.5 * MS, 0.5 * MS),
        ("%flash_fwd.2 = bf16[8]{0} custom-call(...), custom_call_target=\"tpu_custom_call\"",
         6 * MS, 3 * MS),
        ("%fusion.9 = f32[8]{0} fusion(...)", 20 * MS, 1 * MS),  # outside the window
    ]
    modules = [("jit_step(1)", 0 * MS, 2 * MS), ("jit_small(2)", 3 * MS, 1 * MS),
               ("jit_step(1)", 6 * MS, 3 * MS), ("jit_step(1)", 9.5 * MS, 2 * MS)]
    spans = [("bench/poll", 0 * MS, 2.5 * MS), ("bench/poll.admit", 2.5 * MS, 3 * MS),
             ("bench/submit", 4.2 * MS, 0.3 * MS), ("bench/poll", 6 * MS, 4 * MS)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "spans": spans}


@pytest.mark.parametrize("text,want", [
    ("%copy_select_fusion.3 = f32[2]{0} fusion(...)", "copy_select_fusion"),
    ("%copy-done = bf16[4]{0} copy-done(...)", "copy-done"),
    ("%fusion = f32[1] fusion()", "fusion"),
    ("%convolution_add_fusion.12.1 = ...", "convolution_add_fusion"),
    ("flash_fwd", "flash_fwd"),
])
def test_op_group_strips_the_instruction_number(text, want):
    assert tr.op_group(text) == want


def test_merge_unions_overlapping_and_touching_intervals():
    assert tr.merge([(5, 6), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 6)]


def test_window_is_what_the_harness_spans_cover():
    v = tr.TraceView(_events())
    assert (v.lo, v.hi) == (0.0, 10 * MS)
    assert v.window_s == pytest.approx(0.010)


def test_busy_is_the_union_not_the_sum():
    v = tr.TraceView(_events())
    assert v.busy_s() == pytest.approx(0.006)  # 2 + 1 + 3 ms; the overlap counts once


def test_time_by_name_sums_per_group_inside_the_window():
    b = dict(map(tuple, tr.TraceView(_events()).breakdown()["device_ops"]))
    # the second copy lies inside the first: self time, so the groups sum to the busy time
    assert b == pytest.approx({"flash_fwd": 0.003, "fusion": 0.002, "copy": 0.001})
    assert sum(b.values()) == pytest.approx(tr.TraceView(_events()).busy_s())


def test_time_by_name_is_self_time_under_a_while_or_conditional():
    ops = [("%while.3 = (...) while(...), body=%b", 0, 10 * MS),
           ("%fusion.1 = f32[] fusion()", 1 * MS, 3 * MS),
           ("%conditional.2 = f32[] conditional(...)", 5 * MS, 4 * MS),
           ("%copy.4 = f32[] copy()", 6 * MS, 2 * MS),
           ("%fusion.5 = f32[] fusion()", 12 * MS, 1 * MS)]
    got = tr.time_by_name(ops, 0, 20 * MS)
    assert got == pytest.approx({"while": 0.003, "fusion": 0.004, "conditional": 0.002,
                                 "copy": 0.002})
    assert sum(got.values()) == pytest.approx(0.011)  # the union: nothing counted twice


def test_idle_gaps_go_to_the_span_the_host_was_in():
    g = dict(map(tuple, tr.TraceView(_events()).breakdown()["idle_gaps"]))
    # idle: 2-3 ms (poll 2-2.5, poll.admit 2.5-3), 4-6 ms (submit 4.2-4.5 started
    # last inside poll.admit 4-5.5, nothing 5.5-6), 9-10 ms (poll)
    assert g == pytest.approx({"poll": 0.0015, "poll.admit": 0.0017, "submit": 0.0003,
                               "unattributed": 0.0005})
    assert sum(g.values()) == pytest.approx(0.010 - 0.006)


def test_program_views():
    v = tr.TraceView(_events())
    assert [m[0] for m in v.modules_inside()] == ["jit_step(1)", "jit_small(2)", "jit_step(1)"]
    assert v.heaviest_module() == "jit_step(1)"
    assert len(v.spans_named("poll")) == 2


def test_readers_on_the_handmade_trace():
    v = tr.TraceView(_events())
    ctx = manifest.Context(sizes={}, traffic={}, records={}, trace=v, peaks=None, end_to_end={})
    assert manifest.reader("decode_step_device_ms")(ctx) == pytest.approx(2.5)  # median of 2, 3 ms
    assert manifest.reader("prefill_device_ms")(ctx) == pytest.approx(1.0)  # jit_small in poll.admit
    # the flash reader goes by the program's NAME (harness/program_trace.py): the same events with
    # the step named, and a kernel call inside the execution the stretch cuts, which counts nowhere
    from benchmark.harness import program_trace

    ev = _events()["devices"]["/device:TPU:0"]
    named = {"devices": {"/device:TPU:0": {
        "ops": [[n.split(" ")[0], s, d, ""] for n, s, d in ev["ops"]]
        + [["%flash_dq.5", 9.6 * MS, 0.3 * MS, ""]],
        "modules": [[n.replace("jit_step", "jit_train_step"), s, d] for n, s, d in ev["modules"]]}},
        "host": [[n, s, d, 0, {}] for n, s, d in _events()["spans"]]}
    ctx.program_trace = program_trace.ProgramTrace(named)
    assert len(ctx.program_trace.executions("train_step")) == 2  # the third is cut at 10 ms
    assert manifest.reader("flash_device_ms")(ctx) == pytest.approx(1.5)  # median of 0 and 3 ms
    empty = manifest.Context(sizes={}, traffic={}, records={}, trace=None, peaks=None, end_to_end={})
    for name in ("decode_step_device_ms", "prefill_device_ms", "flash_device_ms",
                 "decode_step_roofline", "mfu_pct", "lane_occupancy_pct", "gen_tok_per_s_median"):
        assert manifest.reader(name)(empty) is None, "nothing to read, nothing returned"


def test_a_trace_without_harness_spans_is_refused():
    with pytest.raises(ValueError):
        tr.TraceView({"devices": {}, "spans": []})


FIXTURE = ROOT / "benchmark" / "fixtures" / "serve_trace_v5e.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def test_recorded_trace_reduces_to_the_numbers_worked_out_when_it_was_cut(recorded):
    v = tr.TraceView(recorded["events"])
    want = recorded["expected"]
    assert v.window_s == pytest.approx(want["window_s"])
    assert v.busy_s() == pytest.approx(want["busy_s"])
    assert 0 < v.busy_s() < v.window_s
    b = v.breakdown()
    assert [k for k, _ in b["device_ops"]] == [k for k, _ in want["device_ops"]]
    assert dict(map(tuple, b["idle_gaps"])) == pytest.approx(dict(map(tuple, want["idle_gaps"])))
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(v.window_s - v.busy_s(), rel=1e-6)
    assert v.heaviest_module() == want["decode_program"]
    assert v.heaviest_module().startswith("jit__decode_step_impl(")


def test_recorded_trace_has_the_shape_the_reducer_assumes(recorded):
    ev = recorded["events"]
    assert all(p.startswith("/device:TPU:") for p in ev["devices"])
    names = {s[0] for s in ev["spans"]}
    assert names <= {"bench/poll", "bench/poll.admit", "bench/poll.evict", "bench/submit"}
    assert any(m[0].startswith("jit_") for d in ev["devices"].values() for m in d["modules"])
