"""The engine's series `serving/polls` (one row a `poll()`, `serving/engine.py`
has the columns) reduced to completion gaps: what four per-layer readers and
`benchmark/tools/stall_report.py` share.

A GAP is the stretch from the end of one poll that returned a completion to
the end of the next such poll, on the series' own clock (`t0_s + dur_s`,
`time.perf_counter`).  Every gap holds the same work (the polls of one client
stagger: 128, 256 in the guided cell; one gap in every C is a poll short), and
the gaps of a window tile the interval `gen_img_tok_per_s` is taken over.  A
gap's wall time is the sum of six parts over its polls: `admit_s`,
`dispatch_s`, `block_s`, `evict_s` as the engine booked them, `other_s` (the
rest of `dur_s`: what no span covers) and `between_s` (from the end of the
poll before to this poll's entry: the caller's loop).  `dispatch_s + block_s`
is time the host waited on the runtime (a dispatch blocks once the device's
queue is full, an eviction drains it); the rest is the host's own.

What counts as a stall is measured per GAP, never per poll: a poll that finds
room in the device's queue returns in under a millisecond, one that does not
waits a step, and an eviction's drain takes 140 ms, so single polls differ by
two orders of magnitude in a window whose gaps are equal to 0.1 %.

Everything here returns nothing, and does not raise, on a program that keeps
no such series (a parent commit from before PR 35).
"""
from __future__ import annotations

import sys
from typing import Dict, Optional, Sequence

import numpy as np

SERIES = "serving/polls"
BOOKED = ("admit_s", "dispatch_s", "block_s", "evict_s")
PARTS = BOOKED + ("other_s", "between_s")
BLOCKED = ("dispatch_s", "block_s")  # waiting on the runtime; the other four are the host's
MARK = 0.01  # a gap over the median by more than this share is marked
MIN_GAPS = 3


def engine_rows() -> Optional[Dict[str, np.ndarray]]:
    """The rows the process's engine has written, with `dropped` beside the
    columns; None where the program keeps no series."""
    from dalle_pytorch_tpu.observability import metrics

    find = getattr(metrics.REGISTRY, "series", None)
    series = find(SERIES) if find is not None else None
    if series is None:
        return None
    return dict(series.rows(), dropped=series.dropped)


def completion_polls(rows: Dict[str, np.ndarray]) -> list:
    """The `iter` of every held poll that evicted a request, in order."""
    return rows["iter"][rows["evicted"] >= 1].astype(int).tolist()


class Gaps:
    """The completion gaps between the polls `polls` (engine `iter`s, at least
    two), by part.  `wall_s`, `n_polls` and `parts[name]` hold one entry a
    gap; `first[k]:last[k]` are gap k's rows in `rows`."""

    def __init__(self, rows: Dict[str, np.ndarray], polls: Sequence[int]):
        at = np.searchsorted(rows["iter"], polls)
        end = rows["t0_s"] + rows["dur_s"]
        per_poll = {p: rows[p] for p in BOOKED}
        per_poll["other_s"] = rows["dur_s"] - sum(per_poll.values())
        per_poll["between_s"] = np.concatenate([[0.0], rows["t0_s"][1:] - end[:-1]])
        self.rows, self.per_poll, self.polls = rows, per_poll, list(polls)
        self.first, self.last = at[:-1] + 1, at[1:] + 1
        self.n_polls = self.last - self.first
        self.wall_s = end[at[1:]] - end[at[:-1]]
        self.parts = {}
        for name, values in per_poll.items():
            total = np.concatenate([[0.0], np.cumsum(values)])
            self.parts[name] = total[self.last] - total[self.first]

    def __len__(self) -> int:
        return len(self.wall_s)

    @property
    def blocked_s(self) -> np.ndarray:
        return sum(self.parts[p] for p in BLOCKED)

    @property
    def host_s(self) -> np.ndarray:
        return self.wall_s - self.blocked_s

    def marked(self) -> list:
        """The gaps over the median by more than MARK of it."""
        return np.flatnonzero(self.wall_s > (1.0 + MARK) * np.median(self.wall_s)).tolist()

    def longest_polls(self, k: int, n: int = 3) -> list:
        """Gap k's n longest polls: (iter, dur_s, largest part, its seconds)."""
        lo, hi = self.first[k], self.last[k]
        out = []
        for j in lo + np.argsort(-self.rows["dur_s"][lo:hi], kind="stable")[:n]:
            inside = {p: self.per_poll[p][j] for p in PARTS if p != "between_s"}
            part = max(inside, key=inside.get)
            out.append((int(self.rows["iter"][j]), float(self.rows["dur_s"][j]), part,
                        float(inside[part])))
        return out


def window_gaps(rows: Optional[Dict[str, np.ndarray]], polls: Sequence[int],
                say=lambda text: print(f"poll_series: {text}", file=sys.stderr)) -> Optional[Gaps]:
    """The gaps between the polls that returned the window's completions, or
    None, with a line on stderr, where they cannot be told: fewer than three,
    rows of the window dropped, or a poll named that evicted nothing (then the
    harness and the engine count polls differently)."""
    if rows is None:
        return None
    polls = [int(p) for p in polls]
    if len(polls) < MIN_GAPS + 1:
        say(f"{max(len(polls) - 1, 0)} completion gaps in the window, fewer than {MIN_GAPS}")
        return None
    held = rows["iter"]
    if len(held) == 0 or polls[0] < held[0] or polls[-1] > held[-1]:
        say(f"the series dropped rows of the window ({int(rows.get('dropped', 0))} dropped; "
            f"holds iter {int(held[0]) if len(held) else '-'} on, the window begins at {polls[0]})")
        return None
    at = np.searchsorted(held, polls)
    wrong = [p for p, i in zip(polls, at) if held[i] != p or rows["evicted"][i] < 1]
    if wrong:
        say(f"polls {wrong[:5]} returned completions to the harness and evicted nothing "
            f"in the engine's rows: the two count polls differently")
        return None
    return Gaps(rows, polls)


def of(ctx) -> Optional[Gaps]:
    """The window's gaps of the run a reader is called for, told once a run
    and kept on its Context.  None on the CPU (these are times), on a program
    without the series, and where `window_gaps` says so."""
    if ctx.peaks is None:
        return None
    if not hasattr(ctx, "poll_gaps"):
        polls = [c["poll"] for c in ctx.records.get("completions", [])]
        ctx.poll_gaps = window_gaps(engine_rows(), polls)
    return ctx.poll_gaps


# ---- the four numbers ----------------------------------------------------------
def excess(values: np.ndarray) -> np.ndarray:
    """What each gap holds of `values` beyond the median gap's."""
    return np.maximum(values - np.median(values), 0.0)


def completion_gap_excess_pct(g: Gaps) -> float:
    return float(100.0 * excess(g.wall_s).sum() / g.wall_s.sum())


def gap_excess_blocked_ms(g: Gaps) -> float:
    return float(1e3 * excess(g.blocked_s).mean())


def gap_excess_host_ms(g: Gaps) -> float:
    return float(1e3 * excess(g.host_s).mean())


def between_polls_pct(g: Gaps) -> float:
    return float(100.0 * g.parts["between_s"].sum() / g.wall_s.sum())
