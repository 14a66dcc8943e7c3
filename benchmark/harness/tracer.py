"""The profiler around a stretch of the window, and the harness's own spans.
Off (`--trace 0`), `span()` is an empty context and nothing else happens."""
from __future__ import annotations

import contextlib
import shutil
from pathlib import Path

import jax

from benchmark.harness import trace_reduce

_NULL = contextlib.nullcontext()


class Tracer:
    def __init__(self, enabled: bool, out_dir: Path):
        self.enabled = enabled
        self.active = False
        self.done = False
        self.out_dir = Path(out_dir)

    def start(self) -> None:
        shutil.rmtree(self.out_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the TraceAnnotation spans are enough
        jax.profiler.start_trace(str(self.out_dir), profiler_options=options)
        self.active = True

    def stop(self) -> None:
        jax.profiler.stop_trace()
        self.active, self.done = False, True

    def span(self, what: str):
        if not self.active:
            return _NULL
        return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + what)

    def view(self):
        """The reduced trace, or None where none was taken."""
        if not self.done:
            return None
        return trace_reduce.TraceView(trace_reduce.load_xplane(str(self.out_dir)))
