"""Process-wide runtime metrics registry.

Counters (monotonic: steps, loss-scale skips, host→device bytes), gauges
(point-in-time: data-queue depth, tokens/sec, device memory peak),
histograms (distributions: checkpoint save latency, per-sample decode time)
and series (the last N rows of named columns: one row a serving poll).
Instrumented code calls the module-level
`counter()/gauge()/histogram()/series()` helpers — no plumbing through call stacks — and the training loop flushes a
snapshot through the existing `MetricLogger` JSONL sink (and/or the
telemetry directory) at its logging cadence.

Thread-safe; the data-loader worker threads and the prefetch producer update
the same registry the step loop flushes.  All operations are a dict lookup +
float add under a lock — cheap enough for per-sample instrumentation.
"""
from __future__ import annotations

import math
import threading
from typing import Any, Dict, Optional, Sequence

import numpy as np


class Counter:
    """Monotonic counter.  `.inc(n)`; snapshot reports the running total and
    the delta since the previous flush (rates without external bookkeeping)."""

    __slots__ = ("name", "_value", "_last_flush", "_lock")

    def __init__(self, name: str, lock: threading.Lock):
        self.name = name
        self._value = 0.0
        self._last_flush = 0.0
        self._lock = lock

    def inc(self, n: float = 1.0):
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def _snapshot(self, reset_window: bool) -> Dict[str, float]:
        delta = self._value - self._last_flush
        if reset_window:
            self._last_flush = self._value
        return {"total": self._value, "delta": delta}


class Gauge:
    """Point-in-time value; snapshot reports last + the window max (peaks
    like queue depth survive a coarse flush cadence)."""

    __slots__ = ("name", "_value", "_max", "_lock")

    def __init__(self, name: str, lock: threading.Lock):
        self.name = name
        self._value = None
        self._max = None
        self._lock = lock

    def set(self, v: float):
        with self._lock:
            self._value = float(v)
            if self._max is None or v > self._max:
                self._max = float(v)

    @property
    def value(self):
        return self._value

    def _snapshot(self, reset_window: bool) -> Dict[str, Any]:
        out = {"last": self._value, "max": self._max}
        if reset_window:
            self._max = self._value
        return out


def bucket_percentile(buckets: Dict[int, int], count: float, q: float,
                      lo_clamp: Optional[float] = None,
                      hi_clamp: Optional[float] = None) -> Optional[float]:
    """Approximate q-quantile (q in [0, 1]) from log2 buckets (bucket i
    holds values in [2^(i-1), 2^i)): find the bucket holding the q·count-th
    sample and interpolate linearly inside its range, clamped to the
    observed min/max when given.  Worst-case error is the bucket width (a
    factor of 2).  Shared by the cumulative Histogram percentiles and the
    sliding-window view HistogramWindow computes over bucket DELTAS."""
    if not count:
        return None
    target = q * count
    cum = 0
    for b, c in sorted(buckets.items()):
        if cum + c >= target:
            lo = 0.0 if b <= -1074 else 2.0 ** (b - 1)
            hi = 2.0 ** b
            frac = (target - cum) / c
            val = lo + (hi - lo) * frac
            if lo_clamp is not None:
                val = max(val, lo_clamp)
            if hi_clamp is not None:
                val = min(val, hi_clamp)
            return val
        cum += c
    return hi_clamp


class Histogram:
    """Streaming distribution: count/total/min/max plus log2-bucket counts
    (bucket i holds values in [2^(i-1), 2^i) seconds/units) — enough for a
    latency report without storing samples."""

    __slots__ = ("name", "count", "total", "min", "max", "_buckets", "_lock")

    def __init__(self, name: str, lock: threading.Lock):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._buckets: Dict[int, int] = {}
        self._lock = lock

    def observe(self, v: float):
        v = float(v)
        with self._lock:
            self.count += 1
            self.total += v
            if self.min is None or v < self.min:
                self.min = v
            if self.max is None or v > self.max:
                self.max = v
            b = -1074 if v <= 0 else int(math.ceil(math.log2(v)))
            self._buckets[b] = self._buckets.get(b, 0) + 1

    @property
    def mean(self) -> Optional[float]:
        return self.total / self.count if self.count else None

    def percentile(self, q: float) -> Optional[float]:
        """Approximate q-quantile over ALL observations so far (see
        bucket_percentile); the latency tables health_report /
        telemetry_report render use this."""
        with self._lock:
            return bucket_percentile(self._buckets, self.count, q,
                                     lo_clamp=self.min, hi_clamp=self.max)

    def state(self) -> Dict[str, Any]:
        """Cumulative snapshot a HistogramWindow diffs against: monotone
        count/total and a copy of the bucket counts."""
        with self._lock:
            return {"count": self.count, "total": self.total,
                    "min": self.min, "max": self.max,
                    "buckets": dict(self._buckets)}

    def _snapshot(self, reset_window: bool) -> Dict[str, Any]:
        # registry.snapshot() already holds the shared (non-reentrant)
        # instrument lock — go straight to the unlocked percentile core,
        # NOT self.percentile(), which would self-deadlock
        def pct(q):
            return bucket_percentile(self._buckets, self.count, q,
                                     lo_clamp=self.min, hi_clamp=self.max)

        out = {"count": self.count, "total": self.total, "mean": self.mean,
               "min": self.min, "max": self.max,
               "p50": pct(0.5), "p95": pct(0.95), "p99": pct(0.99),
               "log2_buckets": {str(k): v for k, v in sorted(self._buckets.items())}}
        return out


class HistogramWindow:
    """Sliding-window percentile view over a Histogram, independent of the
    registry's flush cadence.

    `registry.flush_to` resets the Counter/Gauge windows, so anything that
    wants its OWN window (the SLO monitor's burn-rate math) cannot piggyback
    on snapshot deltas.  This helper keeps a private cumulative snapshot and,
    on each `advance()`, diffs the histogram's monotone bucket counts against
    it — yielding count/mean/percentiles of exactly the observations that
    landed since the previous `advance()`.  Bucket counts only ever grow, so
    the diff is race-free against concurrent `observe()` calls (an
    observation lands in either this window or the next, never neither)."""

    __slots__ = ("hist", "_prev")

    def __init__(self, hist: Histogram):
        self.hist = hist
        self._prev = hist.state()

    def advance(self) -> Dict[str, Any]:
        cur = self.hist.state()
        prev, self._prev = self._prev, cur
        count = cur["count"] - prev["count"]
        total = cur["total"] - prev["total"]
        buckets = {}
        for b, c in cur["buckets"].items():
            d = c - prev["buckets"].get(b, 0)
            if d > 0:
                buckets[b] = d
        # cumulative min/max bound (not equal) the window extrema; still
        # valid clamps since window observations are a subset of all
        out = {"count": count, "total": total,
               "mean": total / count if count else None}
        for label, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
            out[label] = bucket_percentile(buckets, count, q,
                                           lo_clamp=cur["min"],
                                           hi_clamp=cur["max"])
        return out


class Series:
    """The last `capacity` rows of named float64 columns, in one block
    allocated when the series is made.  `append(*row)` writes a row in place
    (the block never grows and nothing is kept per row, so a recorder on a
    latency path is no suspect of its own); once full it overwrites the oldest
    row and counts it in `dropped`, as `PoolFlightRecorder` does.  A row's
    sequence number is its place among all rows ever appended: `total` is the
    next one, `rows(since=n)` hands back, oldest first and as copies, the held
    rows numbered n and later.  The snapshot carries the length and `dropped`
    only: the rows are read from the object, not flushed."""

    __slots__ = ("name", "columns", "capacity", "total", "_data", "_lock")

    def __init__(self, name: str, lock: threading.Lock, columns: Sequence[str],
                 capacity: int = 65536):
        if capacity <= 0 or not columns or len(set(columns)) != len(columns):
            raise ValueError(f"series {name!r}: capacity {capacity}, columns {columns}")
        self.name = name
        self.columns = tuple(columns)
        self.capacity = int(capacity)
        self.total = 0
        self._data = np.zeros((self.capacity, len(self.columns)), np.float64)
        self._lock = lock

    def append(self, *row: float):
        with self._lock:
            self._data[self.total % self.capacity] = row
            self.total += 1

    def __len__(self) -> int:
        return min(self.total, self.capacity)

    @property
    def dropped(self) -> int:
        return max(self.total - self.capacity, 0)

    def rows(self, since: int = 0) -> Dict[str, np.ndarray]:
        """{column: values} of the held rows numbered `since` and later."""
        with self._lock:
            first = max(int(since), self.dropped)
            at = np.arange(first, max(self.total, first)) % self.capacity
            block = self._data[at]
        return {c: block[:, j] for j, c in enumerate(self.columns)}

    def _snapshot(self, reset_window: bool) -> Dict[str, Any]:
        return {"rows": len(self), "dropped": self.dropped}


class MetricsRegistry:
    """Create-or-get named instruments.  A name is bound to one instrument
    kind for the life of the process; asking for the same name with a
    different kind raises (silent shadowing hides bugs)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: Dict[str, Any] = {}

    def _get(self, name: str, cls):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = self._instruments[name] = cls(name, self._lock)
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(inst).__name__}, requested {cls.__name__}"
                )
            return inst

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def series(self, name: str, columns: Optional[Sequence[str]] = None,
               capacity: int = 65536, fresh: bool = False) -> Optional[Series]:
        """The series bound to `name`; made from `columns` and `capacity` where
        there is none (None where no columns are given: a reader asks for what
        a writer may have left).  `fresh` binds a new, empty one whatever was
        there: a writer that owns its series takes the name over."""
        with self._lock:
            inst = self._instruments.get(name)
            if inst is not None and not isinstance(inst, Series):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(inst).__name__}, requested Series")
            if columns is None:
                return inst
            if inst is None or fresh:
                inst = self._instruments[name] = Series(
                    name, self._lock, columns, capacity)
            elif inst.columns != tuple(columns):
                raise ValueError(
                    f"series {name!r} holds columns {inst.columns}, "
                    f"requested {tuple(columns)}")
            return inst

    def snapshot(self, reset_window: bool = True) -> Dict[str, Dict[str, Any]]:
        """{name: {kind, ...stats}} for every registered instrument.

        Runs under the shared instrument lock: `_snapshot` does unlocked
        read-modify-writes (window delta/max resets), and an `inc()` landing
        between its two reads would otherwise vanish from every window."""
        out = {}
        with self._lock:
            for name, inst in self._instruments.items():
                rec = inst._snapshot(reset_window)
                rec["kind"] = type(inst).__name__.lower()
                out[name] = rec
        return out

    def flush_to(self, logger, step: Optional[int] = None,
                 reset_window: bool = True) -> Dict[str, Any]:
        """Push a snapshot through a `MetricLogger` (JSONL + wandb when
        active) as one quiet record under the 'telemetry' key."""
        snap = self.snapshot(reset_window=reset_window)
        if logger is not None and snap:
            logger.log({"telemetry": snap}, step=step, quiet=True)
        return snap

    def reset(self):
        """Drop every instrument (tests only — production metrics are
        process-lifetime)."""
        with self._lock:
            self._instruments.clear()


# process-wide default registry: instrumented code uses these module-level
# helpers; the telemetry flusher reads the same object
REGISTRY = MetricsRegistry()


def counter(name: str) -> Counter:
    return REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return REGISTRY.gauge(name)


def histogram(name: str) -> Histogram:
    return REGISTRY.histogram(name)


def series(name: str, columns: Optional[Sequence[str]] = None,
           capacity: int = 65536, fresh: bool = False) -> Optional[Series]:
    return REGISTRY.series(name, columns, capacity, fresh)
