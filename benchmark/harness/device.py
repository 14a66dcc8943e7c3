"""What the run is on: the accelerator check, the published peaks, the
`device` key of the result line, and the count of compiles in a window."""
from __future__ import annotations

import json
from pathlib import Path

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


def require_devices(chips: int, rehearse: bool):
    """The first `chips` local devices.  A measurement run needs that many
    accelerator chips; only `--rehearse` (tests, CPU) may go without."""
    devs = jax.local_devices()
    if devs[0].platform == "cpu" and not rehearse:
        raise NoAccelerator("JAX found no accelerator (platform cpu): the benchmark "
                            "measures the chip and has no CPU mode (--rehearse is for tests)")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chip(s), JAX found {len(devs)}")
    return devs[:chips]


def peaks(device) -> dict:
    """Published peaks of `device`; None on the CPU (no device metric is
    computed there); an unknown accelerator raises."""
    if device.platform == "cpu":
        return None
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device.device_kind not in table:
        raise NoAccelerator(f"no published peaks for device_kind {device.device_kind!r} "
                            "in benchmark/harness/peaks.json")
    return table[device.device_kind]


def memory_snapshot() -> list:
    """Per local device, what was resident and the most any program had
    reserved, taken by a generator when its window closes."""
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append({"bytes_in_use": int(stats.get("bytes_in_use", 0)),
                    "peak_bytes_reserved": int(stats.get("peak_bytes_reserved", 0))})
    return out


def describe(devices, at_close: list) -> dict:
    """The `device` key.  `memory_peak_bytes` is the peak on the fullest chip.
    This runtime's allocator keeps a program's temporaries apart: on the v5e
    `peak_bytes_in_use` read 7.0e9 for a train step whose compiled program
    asks for 8.8e9 of temporaries on top (PERF.md 21.5), and those show as
    `peak_bytes_reserved` (8.77e9 in the same cell, PR 23).  So the peak is
    the larger of the allocator's own peak and what was resident when the
    window closed plus the most a program had reserved up to then: the
    window's programs ran with that much resident."""
    peak = 0
    for d, snap in zip(devices, at_close):
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   snap["bytes_in_use"] + snap["peak_bytes_reserved"])
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts jax.monitoring backend-compile events while armed.  JAX times
    `compile_or_get_cached` under this event, so a program served from the
    persistent cache fires it too: the count is of programs that reached the
    backend for the first time in this process, compiled or fetched."""

    def __init__(self):
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == _COMPILE_EVENT:
            self.count += 1
