"""Unified telemetry: structured spans, a process-wide metrics registry,
XLA-level introspection (recompile counting, memory peaks, FLOPs
cross-checks), and a heartbeat/hang monitor.

Instrumented code imports the cheap module-level helpers:

    from dalle_pytorch_tpu.observability import span, counter, gauge, histogram

which are registry updates and inert profiler annotations until something
switches them on.

The switch: `span(name, **attrs)` is always a `jax.profiler.TraceAnnotation`
of that name, which costs a fraction of a microsecond and records nothing
unless a `jax.profiler` session runs; while one does (a `--profile_steps`
capture, the on-alarm `TraceTrigger`, the benchmark's `--trace 1`) every span
is an event on the profiler's own host plane, on the same clock as the
device's operations, with its attributes as stats.  No flag, no environment
variable, no second recorder.  `telemetry.configure(dir=...)` adds the JSONL:
the same call then also writes a `kind:"span"` record (and mirrors into the
profiler as before).  `timed_span` is `span` that hands back its duration, so
a phase's accounting and its trace event are one reading.  See
tools/telemetry_report.py for turning a run's spans JSONL into a per-step
time-attribution table."""
from dalle_pytorch_tpu.observability.capture import TraceTrigger, parse_profile_steps
from dalle_pytorch_tpu.observability.comms import (
    CommsCrosscheck,
    comms_roofline,
    dalle_step_comms,
    step_comms_ledger,
)
from dalle_pytorch_tpu.observability.fleet import FleetAggregator, merge_step_records
from dalle_pytorch_tpu.observability.health import (
    capture_taps,
    leaf_paths,
    tap,
    tap_attention,
    taps_active,
    tree_health,
)
from dalle_pytorch_tpu.observability.health_host import DivergenceMonitor
from dalle_pytorch_tpu.observability.memory import (
    HbmMonitor,
    MemoryCrosscheck,
    audit_donation,
    dalle_step_memory,
    device_hbm_capacity,
    is_oom_error,
    oom_suggestions,
    sampling_memory_ledger,
    step_memory_analysis,
    step_memory_ledger,
    write_oom_report,
)
from dalle_pytorch_tpu.observability.heartbeat import Heartbeat, thread_stacks
from dalle_pytorch_tpu.observability.metrics import (
    REGISTRY,
    HistogramWindow,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    series,
)
from dalle_pytorch_tpu.observability.slo import (
    SloMonitor,
    SloTargets,
    write_status_json,
)
from dalle_pytorch_tpu.observability.spans import SpanRecorder
from dalle_pytorch_tpu.observability.telemetry import (
    Telemetry,
    active,
    configure,
    span,
    timed_span,
)
from dalle_pytorch_tpu.observability.xla import (
    CompileWatcher,
    FlopsCrosscheck,
    device_memory_stats,
    record_memory_gauges,
    step_cost_analysis,
)

__all__ = [
    "REGISTRY",
    "CommsCrosscheck",
    "CompileWatcher",
    "DivergenceMonitor",
    "FleetAggregator",
    "FlopsCrosscheck",
    "HbmMonitor",
    "Heartbeat",
    "HistogramWindow",
    "MemoryCrosscheck",
    "MetricsRegistry",
    "SloMonitor",
    "SloTargets",
    "SpanRecorder",
    "Telemetry",
    "TraceTrigger",
    "active",
    "audit_donation",
    "capture_taps",
    "comms_roofline",
    "configure",
    "counter",
    "dalle_step_comms",
    "dalle_step_memory",
    "device_hbm_capacity",
    "device_memory_stats",
    "gauge",
    "histogram",
    "is_oom_error",
    "leaf_paths",
    "merge_step_records",
    "oom_suggestions",
    "parse_profile_steps",
    "record_memory_gauges",
    "sampling_memory_ledger",
    "series",
    "span",
    "step_comms_ledger",
    "step_cost_analysis",
    "step_memory_analysis",
    "step_memory_ledger",
    "write_oom_report",
    "write_status_json",
    "tap",
    "tap_attention",
    "taps_active",
    "thread_stacks",
    "timed_span",
    "tree_health",
]
