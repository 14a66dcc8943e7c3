"""Traffic kind `closed_loop_state`: `closed_loop` for a trunk that keeps a
per-slot recurrent state, whose `correct` also holds that state.

The generator, the window, the stagger, the rates and the replay of the
window's deliveries are `closed_loop.run`'s: it is called, not copied.  What is
added follows it, outside the window: the engine that served the window is
asked for its in-flight requests' states as the timed program left them
(`GenerationEngine.recurrent_snapshot`) and `harness/correct_state.py` holds
them to the reference's recurrence and to the float32 the configuration
states; both verdicts decide `correct`, and the state's numbers stand in the
line's `detail.correct` beside the replay's.

`closed_loop.run` builds its engine itself and hands back records alone, and
is not this PR's to edit: the engine is kept by the class it is built from,
for the length of the call.  A `benchmark` issue that lets `closed_loop` hand
its engine to a check the configuration names folds this file into it
(PERF.md section 7).
"""
from __future__ import annotations

from benchmark.harness import correct_state
from benchmark.kinds import closed_loop


def run(sizes: dict, traffic: dict, seed: int, seconds: float, tracer, compiles,
        max_polls: int = None) -> dict:
    from dalle_pytorch_tpu.serving import engine as engine_mod

    built = []

    class KeptEngine(engine_mod.GenerationEngine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            built.append(self)

    plain = engine_mod.GenerationEngine
    engine_mod.GenerationEngine = KeptEngine
    try:
        result = closed_loop.run(sizes, traffic, seed, seconds, tracer, compiles, max_polls=max_polls)
    finally:
        engine_mod.GenerationEngine = plain
    (engine,) = built
    ok, detail = correct_state.state_agrees(engine.params, sizes, engine.recurrent_snapshot())
    detail.update(engine.recurrent_state_info())
    result["records"]["correct_detail"] = dict(result["records"]["correct_detail"], **detail)
    result["correct"] = bool(result["correct"] and ok)
    return result
