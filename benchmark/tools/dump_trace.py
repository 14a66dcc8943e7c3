"""Look at one profiler trace by hand: planes, lines, and the first events of
each line with their stats.  `python benchmark/tools/dump_trace.py <dir-or-xplane.pb> [n]`.
With `--probe OUT` it first records a one-second trace of a small jitted loop
on whatever device JAX finds (how the reducer's assumptions about plane and
line names were checked on the chip)."""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness.trace_reduce import find_xplane  # noqa: E402


def dump(path: str, n: int = 8, out=sys.stdout) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}", file=out)
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}", file=out)
            for ev in events[:n]:
                stats = {k: (v if not isinstance(v, (bytes, str)) or len(v) < 60 else v[:60])
                         for k, v in ev.stats}
                print(f"    {ev.name!r} start_ns={ev.start_ns} dur_ns={ev.duration_ns} {stats}", file=out)


def probe(out_dir: str) -> None:
    import time

    import jax
    import jax.numpy as jnp

    print("devices", jax.devices(), "cache env", os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    print("memory_stats", jax.local_devices()[0].memory_stats())
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.5:
        with jax.profiler.TraceAnnotation("bench/poll"):
            y = f(x)
            y = y.at[0, 0].set(1.0)
        with jax.profiler.TraceAnnotation("bench/sync"):
            y.block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0] == "--probe":
        probe(args[1])
        args = args[1:]
    dump(args[0], int(args[1]) if len(args) > 1 else 8)
