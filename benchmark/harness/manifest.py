"""BENCHMARK.json and the files it names.  Whatever belongs to one
configuration, one traffic mix or one per-layer metric is a file of its own
that is found by the name in the manifest; nothing here lists them."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read: the configuration's sizes, the
    traffic's parameters, the generator's host-side records of the window,
    the reduced trace (None where none was taken), the device's published
    peaks (None on the CPU) and the run's end-to-end values."""

    sizes: dict
    traffic: dict
    records: dict
    trace: object
    peaks: object
    end_to_end: dict


def load(path=None) -> dict:
    return json.loads(Path(path or ROOT / "BENCHMARK.json").read_text())


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in the manifest "
                   f"(has: {[w['name'] for w in manifest['workloads']]})")


def config_sizes(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return json.loads((ROOT / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in the manifest")


def traffic(name: str) -> dict:
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def _load(path: Path, label: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(ch if ch.isalnum() else "_" for ch in label), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(sizes: dict):
    """The plain reference the configuration's file names under `reference`."""
    return _load(ROOT / sizes["reference"], "reference_" + sizes["name"])


def metrics_for(manifest: dict, group: str, workload: str) -> List[dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def reader(metric_name: str) -> Callable:
    """The `read(ctx)` of benchmark/metrics/<metric name>.py.  A quantity split
    by the end-to-end metric it moves (`<quantity>.<group>`) may share the
    reader benchmark/metrics/<quantity>.py."""
    for stem in (metric_name, metric_name.rsplit(".", 1)[0]):
        path = BENCH_DIR / "metrics" / f"{stem}.py"
        if path.exists():
            return _load(path, "metric_" + stem).read
    raise FileNotFoundError(f"no reader for the per-layer metric {metric_name!r}")


def read_per_layer(manifest: dict, workload: str, ctx) -> List[tuple]:
    """(metric entry, value) for every per-layer metric of the cell whose
    reader found something to read; the others are left out of the line."""
    out = []
    for m in metrics_for(manifest, "per_layer", workload):
        value = reader(m["name"])(ctx)
        if value is not None:
            out.append((m, value))
    return out
