"""The staggered closed loop is a function of poll counts, not of the clock:
for any seed the completions fall at the same polls, evenly spread."""
import gc
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import device, manifest, tracer  # noqa: E402
from benchmark.kinds import closed_loop  # noqa: E402

MAN = manifest.load(ROOT / "benchmark" / "rehearsal" / "manifest.json")


def _run(workload: str, seed: int, max_polls: int):
    cell = manifest.cell(MAN, workload)
    sizes = manifest.config_sizes(MAN, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    try:
        out = closed_loop.run(sizes, traffic, seed, 1e9, tracer.Tracer(False, ROOT / ".bench_trace" / "t"),
                        device.CompileCounter(), max_polls=max_polls)
    finally:
        gc.unfreeze()
    return sizes, traffic, out


@pytest.fixture(scope="module")
def runs():
    return {(w, s): _run(w, s, 70) for w in ("tiny_serve", "tiny_guided") for s in (1, 2**31 + 11)}


@pytest.mark.parametrize("workload", ["tiny_serve", "tiny_guided"])
def test_completions_evenly_spread_in_polls(runs, workload):
    sizes, traffic, out = runs[(workload, 1)]
    n_gen = sizes["image_fmap_size"] ** 2
    stagger = n_gen // traffic["clients"]
    polls = [c["poll"] for c in out["records"]["all_completions"]]
    assert len(polls) >= 3 * traffic["clients"]
    gaps = [b - a for a, b in zip(polls, polls[1:])]
    # a life is n_gen - 1 polls, so one gap in every C is a poll short
    assert set(gaps) <= {stagger, stagger - 1}, gaps
    assert gaps.count(stagger - 1) <= len(gaps) // traffic["clients"] + 1
    # client i's first completion: sent before poll i * stagger, done n_gen - 1 polls later
    first = {}
    for c in out["records"]["all_completions"]:
        first.setdefault(c["client"], c["poll"])
    assert first == {i: i * stagger + n_gen - 1 for i in range(traffic["clients"])}


@pytest.mark.parametrize("workload", ["tiny_serve", "tiny_guided"])
def test_same_polls_for_every_seed(runs, workload):
    a = [(c["poll"], c["client"]) for c in runs[(workload, 1)][2]["records"]["all_completions"]]
    b = [(c["poll"], c["client"]) for c in runs[(workload, 2**31 + 11)][2]["records"]["all_completions"]]
    assert a == b


@pytest.mark.parametrize("workload", ["tiny_serve", "tiny_guided"])
def test_window_opens_after_one_whole_life_with_every_lane_busy(runs, workload):
    sizes, traffic, out = runs[(workload, 1)]
    n_gen = sizes["image_fmap_size"] ** 2
    rec = out["records"]
    # client 0's first request is done after n_gen - 1 polls, and the window opens there
    assert rec["window_open_polls"] == n_gen - 1
    sent_inside = rec["sent_inside"]
    assert sent_inside and sent_inside[0]["client"] == 0, "client 0's second request is the first"
    assert all(c["sent_t"] >= out["t_open"] for c in sent_inside)
    assert len(sent_inside) == len(rec["completions"]) - (traffic["clients"] - 1)
    busy, offered = rec["occupancy"]
    assert offered == (rec["polls"] - rec["window_open_polls"]) * traffic["slots"]
    assert busy == offered  # every lane decoded in every poll of the window
    assert rec["window_compiles"] == 0
    assert out["failed"] == 0 and out["correct"]
