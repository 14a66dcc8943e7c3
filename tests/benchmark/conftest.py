"""One stale pin, held to exactly what is stale in it.

`test_bench_rehearsal_q3n.py` (PR 26) ends its last test with `... and
len(BENCH["workloads"]) == 5`: true of the benchmark it was written for, false
the moment any later PR adds a cell, as PR 30 did (`train_glm47_ep8`).  A PR
that adds a cell may not edit a file the benchmark already has, so the line
cannot be repaired here.

The test still RUNS, every assertion of it.  Its count is the last statement of
the function, so a failure raised from that statement means every assertion
before it held (the cell's names, its traffic, its per-layer metrics and the
ones it must not carry, the order of the first four cells).  Only that one
failure is excused (reported xfailed), and only while the rest of that line is
true and the count is the one thing wrong with it; a failure on any other line
fails the test as it always did, and so does a PASS (strict: the pin is gone or
the benchmark shrank back, and this file has to go with it).
`test_bench_rehearsal_glm.py` states the same facts about the benchmark as it
now is.  A `benchmark` PR deletes the count from that line and this file with
it (PERF.md section 7).
"""
import json
import traceback
from pathlib import Path

import pytest

STALE = "test_bench_rehearsal_q3n.py::test_the_new_cell_and_its_metrics_are_in_the_manifest_as_the_issue_names_them"
PIN = 'assert all(w["chips"] == 1 for w in BENCH["workloads"]) and len(BENCH["workloads"]) == 5'


@pytest.hookimpl(wrapper=True)
def pytest_pyfunc_call(pyfuncitem):
    if not pyfuncitem.nodeid.endswith(STALE):
        return (yield)
    try:
        yield
    except AssertionError as e:
        frame = traceback.extract_tb(e.__traceback__)[-1]
        cells = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())["workloads"]
        if (frame.line or "").strip() == PIN and len(cells) > 5 and all(w["chips"] == 1 for w in cells):
            pytest.xfail("its last line pins the benchmark to the five cells of PR 26; every assertion "
                         f"before it held, and the benchmark has {len(cells)} one-chip cells")
        raise
    pytest.fail(f"{STALE} passes: its count of cells no longer bites, so delete tests/benchmark/conftest.py")
