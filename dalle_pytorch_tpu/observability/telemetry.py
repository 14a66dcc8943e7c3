"""Telemetry facade: one object wiring spans + metrics + XLA introspection +
heartbeat, and the module-level `span()` the instrumented code calls.

Lifecycle (what the CLIs do):

    tele = telemetry.configure(dir=args.telemetry, run_name=...)
    tele.crosscheck_flops(step_fn, (state, batch, key), analytic_flops)
    for step:
        with tele.step(i):
            with telemetry.span("data_wait"): batch = next(it)
            with telemetry.span("dispatch"): state, m = step_fn(...)
            with telemetry.span("block"):    jax.block_until_ready(m["loss"])
        # tele.step() exit stamps the heartbeat + flushes the step record
    tele.flush(logger, step=i)   # at the logging cadence
    tele.close()

Everything degrades gracefully: with no directory the spans stay in memory
(bench mode), with no active Telemetry the module-level `span()` is a bare
`jax.profiler.TraceAnnotation` (inert unless a profiler session runs, and
then an event on the profiler's own host plane, on the device trace's
clock), and instrumented library code (data loader, prefetch) only ever
touches `span()` + the metrics registry — it keeps working unconfigured."""
from __future__ import annotations

import contextlib
import time
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

from jax.profiler import TraceAnnotation

from dalle_pytorch_tpu.observability import metrics as metrics_mod
from dalle_pytorch_tpu.observability.heartbeat import Heartbeat
from dalle_pytorch_tpu.observability.spans import SpanRecorder
from dalle_pytorch_tpu.observability.xla import (
    CompileWatcher,
    FlopsCrosscheck,
    record_memory_gauges,
    step_cost_analysis,
)

_NULL = contextlib.nullcontext()
_ACTIVE: Optional["Telemetry"] = None


class Telemetry:
    def __init__(
        self,
        dir: Optional[str] = None,
        run_name: str = "run",
        mirror_profiler: bool = True,
        heartbeat_s: Optional[float] = None,
        watch_compiles: bool = True,
        process_index: int = 0,
        flops_rtol: float = 0.5,
    ):
        self.dir = Path(dir) if dir is not None else None
        self.run_name = run_name
        self.process_index = process_index
        suffix = "" if process_index == 0 else f".p{process_index}"
        spans_path = (
            str(self.dir / f"{run_name}{suffix}.spans.jsonl")
            if self.dir is not None else None
        )
        self.spans = SpanRecorder(spans_path, mirror_profiler=mirror_profiler)
        self.registry = metrics_mod.REGISTRY
        # the alarm hub: every alarm (recompile, flops/comms divergence,
        # health, straggler, hang) flows through alarm() — one JSONL stream,
        # and one place for reactive listeners (the on-alarm TraceTrigger)
        self._alarm_listeners: list = []
        self.compile_watcher: Optional[CompileWatcher] = None
        if watch_compiles:
            self.compile_watcher = CompileWatcher(
                on_recompile=lambda ev: self.alarm(
                    "recompile", **{k: v for k, v in ev.items() if k != "ts"}
                )
            ).start()
        self.heartbeat: Optional[Heartbeat] = None
        if heartbeat_s is not None and heartbeat_s > 0:
            self.heartbeat = Heartbeat(
                heartbeat_s,
                dir=str(self.dir) if self.dir is not None else None,
                recorder=self.spans,
                registry=self.registry,
                process_index=process_index,
                # the hang event is already written by the monitor; notify
                # the listeners only (a resolved hang captures the next steps)
                on_hang=lambda report, info: self._notify_alarm("hang", info),
            ).start()
        self._flops_check = FlopsCrosscheck(
            1.0, rtol=flops_rtol,
            on_alarm=lambda ev: self.alarm("flops_divergence", **ev),
        )
        self._comms_check = None  # comms.CommsCrosscheck, built on first use
        self._mem_check = None    # memory.MemoryCrosscheck, built on first use
        self.last_memory_analysis = None  # latest memory_analysis() dict —
        # kept for the OOM forensic report (re-lowering at OOM time would
        # just OOM again)
        # fleet aggregation (observability/fleet.py): per-step phase times
        # accumulate here and are gathered across hosts at the flush cadence
        self.fleet = None
        # live HBM tracking (observability/memory.HbmMonitor): fed the
        # allocator maxes record_memory_gauges samples inside flush()
        self.memory = None
        self._window_steps = 0
        self._window_total_s = 0.0
        self._window_phases: Dict[str, float] = {}
        self._steps_seen = 0
        self._closed = False

    # -- alarms --------------------------------------------------------------
    def alarm(self, type: str, **fields):
        """Write one `kind: "alarm"` record and notify listeners.  Every
        alarm source routes through here so reactive consumers (the
        TraceTrigger) see the same stream the JSONL keeps."""
        self.spans.write_event("alarm", type=type, **fields)
        self._notify_alarm(type, fields)

    def _notify_alarm(self, type: str, fields):
        for fn in self._alarm_listeners:
            try:
                fn(type, fields)
            except Exception:  # listeners must never break the alarm path
                pass

    def add_alarm_listener(self, fn):
        """`fn(type: str, fields: dict)` on every alarm (any thread)."""
        self._alarm_listeners.append(fn)

    def attach_fleet(self, aggregator):
        """Wire a fleet.FleetAggregator: its window feeds from finish_step,
        its gather runs inside flush(), and its straggler alarms join the
        alarm stream (unless the aggregator already has its own sink)."""
        if aggregator.on_alarm is None:
            aggregator.on_alarm = lambda a: self.alarm(
                a.get("type", "straggler"),
                **{k: v for k, v in a.items() if k != "type"},
            )
        self.fleet = aggregator
        return aggregator

    def attach_memory(self, monitor):
        """Wire a memory.HbmMonitor: flush() feeds it the live allocator
        maxes, and its headroom alarms join the alarm stream (and so the
        on-alarm TraceTrigger) unless the monitor has its own sink."""
        if monitor.on_alarm is None:
            monitor.on_alarm = lambda a: self.alarm(
                a.get("type", "hbm_headroom"),
                **{k: v for k, v in a.items() if k != "type"},
            )
        self.memory = monitor
        return monitor

    # -- spans --------------------------------------------------------------
    def span(self, name: str, aggregate: bool = False, **attrs):
        return self.spans.span(name, aggregate=aggregate, **attrs)

    def begin_step(self, n: int):
        self.spans.start_step(n)

    def finish_step(self, n: int):
        """Flush the step record, stamp the heartbeat, feed the fleet
        window, and arm the recompile counter once the first step has
        completed (steady state)."""
        summary = self.spans.end_step()
        self._window_steps += 1
        self._window_total_s += summary.get("dur_s") or 0.0
        for name, v in (summary.get("spans") or {}).items():
            self._window_phases[name] = self._window_phases.get(name, 0.0) + v
        self._steps_seen += 1
        if self.heartbeat is not None:
            self.heartbeat.beat(n)
        if self._steps_seen == 1 and self.compile_watcher is not None:
            # steady state: later compiles are recompilations
            self.compile_watcher.arm()

    def abort_step(self):
        """Discard a step begun but never executed (empty data iterator)."""
        self.spans.abort_step()

    def step(self, n: int):
        """Per-step context: groups this step's spans, stamps the heartbeat,
        arms the recompile counter once the first step has completed."""
        tele = self

        class _StepCtx:
            def __enter__(self):
                tele.begin_step(n)
                return tele

            def __exit__(self, exc_type, *exc):
                if exc_type is None:
                    tele.finish_step(n)
                else:
                    tele.spans.end_step()
                return False

        return _StepCtx()

    # -- metrics ------------------------------------------------------------
    def flush(self, logger=None, step: Optional[int] = None,
              fleet: bool = True) -> Dict[str, Any]:
        """Sample memory gauges, run the fleet gather (when attached),
        snapshot the registry, and push it through the MetricLogger (when
        given) + the telemetry JSONL.  COLLECTIVE when a fleet aggregator is
        attached on a multi-process run: every process must flush at the
        same step cadence.  Pass fleet=False from paths the OTHER processes
        may not be taking — preemption, rollback-abort, end-of-run — or the
        lone flusher blocks forever in the all-gather."""
        mem_stats = record_memory_gauges()
        if self.memory is not None:
            try:
                rec = self.memory.observe(step, mem_stats)
            except Exception:  # live tracking must never kill training
                rec = None
            if rec:
                self.spans.write_event("mem_window", **rec)
        if fleet and self.fleet is not None and self._window_steps:
            phases = self._window_phases
            total_s, n_steps = self._window_total_s, self._window_steps
            self._window_phases, self._window_total_s, self._window_steps = {}, 0.0, 0
            # the gather's own (one-off) allgather compile is telemetry's,
            # not a training recompile
            suspend = (self.compile_watcher.suspended()
                       if self.compile_watcher is not None
                       else contextlib.nullcontext())
            try:
                with suspend:
                    rec = self.fleet.observe_window(step, phases, total_s, n_steps)
            except Exception:  # the fleet gather must never kill training
                rec = None
            if rec:
                self.spans.write_event("fleet", step=step, **rec)
        snap = self.registry.flush_to(logger, step=step)
        if snap:
            self.spans.write_event("metrics", step=step, metrics=snap)
        return snap

    # -- XLA ----------------------------------------------------------------
    def crosscheck_flops(self, step_fn, args: Tuple, analytic_flops: float,
                         label: str = "train_step",
                         analytic_comms_bytes: Optional[float] = None
                         ) -> Optional[float]:
        """Record XLA's FLOPs estimate for the step vs the analytic model;
        feeds the persistent-divergence alarm.  With `analytic_comms_bytes`
        (the comms ledger total), the same cost analysis additionally feeds
        the comms cross-check: bytes-accessed over analytic wire bytes, with
        its own drift alarm (observability/comms.CommsCrosscheck).  Never
        raises."""
        import contextlib as _ctx

        suspend = (self.compile_watcher.suspended()
                   if self.compile_watcher is not None else _ctx.nullcontext())
        with suspend:  # the crosscheck's own lowering/compile is not a recompile
            ca = step_cost_analysis(step_fn, *args)
        if ca is None or "flops" not in ca:
            return None
        self._flops_check.analytic_flops = float(analytic_flops)
        ratio = self._flops_check.check(ca["flops"])
        self.spans.write_event(
            "flops_crosscheck", label=label, analytic_flops=float(analytic_flops),
            compiled_flops=ca["flops"], ratio=ratio,
            bytes_accessed=ca.get("bytes accessed"),
        )
        bytes_accessed = ca.get("bytes accessed")
        if analytic_comms_bytes and bytes_accessed:
            from dalle_pytorch_tpu.observability.comms import CommsCrosscheck

            if self._comms_check is None:
                self._comms_check = CommsCrosscheck(
                    float(analytic_comms_bytes), rtol=self._flops_check.rtol,
                    on_alarm=lambda ev: self.alarm("comms_divergence", **ev),
                )
            self._comms_check.analytic_flops = float(analytic_comms_bytes)
            comms_ratio = self._comms_check.check(bytes_accessed)
            self.spans.write_event(
                "comms_crosscheck", label=label,
                analytic_comms_bytes=float(analytic_comms_bytes),
                bytes_accessed=bytes_accessed, ratio=comms_ratio,
            )
        return ratio

    def crosscheck_memory(self, step_fn, args: Tuple, ledger,
                          label: str = "train_step",
                          expected_donation_bytes: Optional[float] = None
                          ) -> Optional[float]:
        """Record XLA's `memory_analysis()` for the step vs the analytic
        HBM ledger; feeds the persistent-drift alarm
        (memory.MemoryCrosscheck) and — when the step declares
        `donate_argnums` (or `expected_donation_bytes` is given) — the
        donation audit, alarming `donation_dropped` through the hub when
        the train state was not actually aliased.  COMPILES the step once
        (shielded from the recompile counter); run at the crosscheck
        cadence, not per step.  Never raises."""
        import contextlib as _ctx

        from dalle_pytorch_tpu.observability import memory as memory_mod

        suspend = (self.compile_watcher.suspended()
                   if self.compile_watcher is not None else _ctx.nullcontext())
        with suspend:  # the crosscheck's own compile is not a recompile
            analysis = memory_mod.step_memory_analysis(step_fn, *args)
        if analysis is None:
            return None
        self.last_memory_analysis = analysis
        analytic_total = (ledger or {}).get("total_bytes") or 0.0
        ratio = None
        if analytic_total > 0:
            if self._mem_check is None:
                self._mem_check = memory_mod.MemoryCrosscheck(
                    analytic_total, rtol=self._flops_check.rtol,
                    on_alarm=lambda ev: self.alarm("mem_divergence", **ev),
                )
            self._mem_check.analytic_flops = analytic_total
            ratio = self._mem_check.check(analysis["total_bytes"])
        event: Dict[str, Any] = {
            "label": label, "analytic_total_bytes": analytic_total,
            "ratio": ratio, **analysis,
        }
        if expected_donation_bytes is None and getattr(
                step_fn, "donate_argnums", None):
            # the step donates its TrainState (argument 0): expect the
            # ledger's at-rest state rows (params + opt moments) aliased
            rows = {r["name"]: r["bytes"] for r in (ledger or {}).get("rows", [])}
            expected_donation_bytes = rows.get("params", 0.0) + rows.get(
                "opt_state", 0.0)
        if expected_donation_bytes:
            audit = memory_mod.audit_donation(analysis, expected_donation_bytes)
            event["donation"] = audit
            if not audit["ok"]:
                self.alarm("donation_dropped", label=label, **audit)
        self.spans.write_event("memory_crosscheck", **event)
        return ratio

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"steps": self._steps_seen}
        if self.compile_watcher is not None:
            out.update(self.compile_watcher.summary())
        if self._flops_check.last_ratio is not None:
            out["flops_ratio"] = round(self._flops_check.last_ratio, 4)
        return out

    def close(self):
        if self._closed:
            return
        self._closed = True
        if self.heartbeat is not None:
            self.heartbeat.stop()
        if self.compile_watcher is not None:
            self.spans.write_event("compile_summary", **self.compile_watcher.summary())
            self.compile_watcher.stop()
        self.spans.write_event("run_end", ts_end=time.time())
        self.spans.close()
        global _ACTIVE
        if _ACTIVE is self:
            _ACTIVE = None


# --- module-level plumbing ---------------------------------------------------

def configure(dir: Optional[str] = None, run_name: str = "run", **kwargs) -> Telemetry:
    """Create + install the process-wide Telemetry (closing any previous)."""
    global _ACTIVE
    if _ACTIVE is not None:
        _ACTIVE.close()
    _ACTIVE = Telemetry(dir=dir, run_name=run_name, **kwargs)
    return _ACTIVE


def active() -> Optional[Telemetry]:
    return _ACTIVE


def span(name: str, aggregate: bool = False, **attrs):
    """Span on the active Telemetry (a JSONL record, mirrored into the
    profiler).  With none configured it is the profiler annotation alone:
    a running `jax.profiler` session is the one switch that makes it an
    event, so library code can instrument unconditionally.  `aggregate`
    spans (per-sample loader work) stay no-ops there."""
    tele = _ACTIVE
    if tele is None:
        return _NULL if aggregate else TraceAnnotation(name, **attrs)
    return tele.spans.span(name, aggregate=aggregate, **attrs)


class _TimedSpan:
    """`span()` that also hands back its own duration: `with timed_span(..)
    as t: ...` then `t.s`.  The one reading the engine's phase accounting,
    its histograms and the profiler event of the same name all come from."""

    __slots__ = ("_cm", "_t0", "s")

    def __init__(self, cm):
        self._cm = cm
        self.s = 0.0

    def __enter__(self):
        self._cm.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self._t0
        return self._cm.__exit__(*exc)


def timed_span(name: str, **attrs) -> _TimedSpan:
    return _TimedSpan(span(name, **attrs))
