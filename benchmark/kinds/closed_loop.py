"""Traffic kind `closed_loop`: C clients against one `GenerationEngine`, each
sending its next request in the iteration its last one completes.

The stagger is in poll iterations, not seconds: client i sends its first
request when the harness has made i * (image tokens // C) polls.  A request
lives the same number of polls whichever run it is in, so the clients stay
evenly spread for as long as the engine runs, one completion and one
admission falling every (image tokens // C) polls.  The pre-roll (set-up) runs
until client 0's first request has completed: by then every lane is busy at
its own offset and every path (admit, decode, evict, codes pull, VAE decode)
has run at the window's shapes.  Throughput is all the tokens over all the
time between the window's first and last completion (every gap between two
completions holds the same work, so no partial image enters, and a stall
anywhere between them shows); latency is read from the requests that were
also SENT inside the window, which are the ones that lived wholly in the
steady state (a request of the pre-roll was admitted while the host ran a
queue of steps ahead of the device, and lived through the first eviction's
one-off costs).  The population is censored at the window's end: a request
that a stall pushes past the close is not in it, but the stall is in the
latency of every request that was in flight with it.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.harness import build, correct, device, stats


class Client:
    def __init__(self, index: int, first_due_poll: int):
        self.index = index
        self.due_poll = first_due_poll
        self.request = None
        self.submit_poll = None


def run(sizes: dict, traffic: dict, seed: int, seconds: float, tracer, compiles,
        max_polls: int = None) -> dict:
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine
    from dalle_pytorch_tpu.serving.scheduler import AdmissionRefused

    cfg = build.dalle_config(sizes, execution="sequential", scan_layers=False)
    params = build.make_weights(cfg, seed, build.dtype(sizes["serve_recipe"]["param_dtype"]))
    vae_cfg = build.vae_config(sizes)
    vae_params = build.make_vae(vae_cfg, seed)
    engine = GenerationEngine(
        params, cfg, vae_params=vae_params, vae_cfg=vae_cfg,
        engine_cfg=EngineConfig(num_slots=int(traffic["slots"]),
                                block_size=int(traffic["block_size"]),
                                filter_thres=float(traffic["filter_thres"])))
    n_gen = cfg.image_seq_len
    n_clients = int(traffic["clients"])
    stagger = n_gen // n_clients
    cond_scale = float(traffic["cond_scale"])
    lanes_per_request = 2 if cond_scale != 1.0 else 1
    assert n_clients * lanes_per_request <= int(traffic["slots"]), "more client lanes than slots"
    clients = [Client(i, i * stagger) for i in range(n_clients)]
    rng = np.random.default_rng([seed, 3])
    serial = 0

    def submit(client: Client, poll: int) -> bool:
        nonlocal serial
        text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,), dtype=np.int64)
        key = build.raw_key(seed, serial)
        serial += 1
        try:
            client.request = engine.submit(text.astype(np.int32), key=key,
                                           temperature=float(traffic["temperature"]),
                                           cond_scale=cond_scale)
        except AdmissionRefused:
            client.request = None
            return False
        client.submit_poll = poll
        return True

    completions, refused = [], []     # dicts; "t" = pixels on the host (monotonic)
    occupancy = [0, 0]                # lanes that decoded, lane slots offered
    trace_positions = []              # per traced poll: each active lane's query position
    polls = 0
    last_poll = n_gen - 2             # a request sent before poll p is admitted in poll p (its
    #                                   first code, and its second: the step runs too) and is
    #                                   evicted in poll p + n_gen - 2: a life of n_gen - 1 polls
    slots = int(traffic["slots"])
    trace_pad = int(traffic["trace_polls_each_side"])
    trace_from = trace_until = None
    t_open = t_close = window_open_polls = at_close = None
    closed = False

    # collect before the first request: a collection while requests are in
    # flight stalls every one of them, and their latencies are the window's
    gc.collect()
    gc.freeze()
    while True:
        now = time.monotonic()
        if t_open is not None and now >= t_close and not closed:
            # the window closes on the clock; a traced run polls on, in the
            # same steady state, through the stretch it traces: starting and
            # stopping the profiler stalls the host for seconds, and inside
            # the window that would be read as the system's own stall
            closed = True
            compiles.armed = False
            at_close = device.memory_snapshot()
            t_close = now
            if tracer.enabled:
                nxt = min(c.submit_poll + last_poll for c in clients if c.request is not None)
                if nxt - trace_pad <= polls:
                    nxt += stagger
                trace_from, trace_until = nxt - trace_pad, nxt + trace_pad
        if closed and (tracer.done or not tracer.enabled):
            break
        if max_polls is not None and polls >= max_polls:
            break
        admits = False
        for c in clients:
            if c.request is not None and c.request.outcome in ("shed", "poisoned"):
                refused.append({"t": now, "client": c.index, "outcome": c.request.outcome})
                c.request, c.due_poll = None, polls
            if c.request is None and polls >= c.due_poll:
                with tracer.span("submit"):
                    if submit(c, polls):
                        admits = True
                    else:
                        refused.append({"t": now, "client": c.index, "outcome": "refused"})
        # the poll in which a request ends is known from the one it was sent in
        evicts = any(c.request is not None and c.submit_poll + last_poll == polls for c in clients)
        if closed:
            if polls == trace_from:
                tracer.start()
            elif tracer.active and polls >= trace_until:
                tracer.stop()
                continue
        if tracer.active:
            trace_positions.append([engine.n_pre + (polls - c.submit_poll)
                                    for c in clients if c.request is not None
                                    for _ in range(lanes_per_request)])
        with tracer.span("poll.evict" if evicts else "poll.admit" if admits else "poll"):
            done = engine.poll()
        polls += 1
        busy_after = slots - engine.free_slots
        for req in done:
            client = next(c for c in clients if c.request is req)
            completions.append({
                "t": req.arrival_t + req.latency_s, "poll": polls, "client": client.index,
                "sent_t": req.arrival_t, "latency_s": req.latency_s, "ttft_s": req.ttft_s,
                "queue_wait_s": req.phases.get("queue_wait"),
                "codes_ok": bool(req.codes is not None and req.codes.min() >= 0
                                 and req.codes.max() < cfg.num_image_tokens),
                "text": req.text, "codes": req.codes, "image": req.images,
            })
            busy_after += len(req.lanes or ())
            client.request, client.due_poll = None, polls
        if closed:
            continue
        if t_open is not None:
            occupancy[0] += busy_after
            occupancy[1] += slots
        elif any(r["client"] == 0 for r in completions):
            # client 0's first request has just completed: every lane is busy
            # at its own offset and every path has run once.  Open before its
            # replacement is sent, so that the requests SENT inside the window
            # are exactly those that never met a first-time cost of the
            # pre-roll (their latencies are the window's).  What the pre-roll
            # allocated is frozen, not collected: no stall
            gc.freeze()
            compiles.armed = True
            window_open_polls = polls
            t_open = time.monotonic()
            t_close = t_open + seconds
    if tracer.active:
        tracer.stop()
    if not closed:  # a rehearsal cut short by max_polls
        compiles.armed = False
        at_close = device.memory_snapshot()
        t_close = time.monotonic()
    if t_open is None:  # ... before the window opened: everything counts
        t_open, inside_from = t_close, float("-inf")
    else:
        inside_from = t_open

    inside = stats.in_window(completions, "t", inside_from, t_close)
    failed = stats.in_window(refused, "t", inside_from, t_close)
    times = [r["t"] for r in inside]
    # latency, TTFT and queue wait: of the requests sent AND completed inside
    sent_inside = [r for r in inside if r["sent_t"] >= inside_from]
    images_ok = all(r["codes_ok"] and r["image"] is not None
                    and bool(np.isfinite(r["image"]).all()) for r in inside)
    # replay what the window delivered: its first and its last completion
    ok, detail = correct.serve_replay_agrees(
        params, sizes, vae_params, vae_cfg, float(traffic["filter_thres"]), cond_scale,
        inside[:1] + inside[1:][-1:] if images_ok else [])
    for r in completions:
        for key in ("text", "codes", "image"):
            r.pop(key)
    return {
        "end_to_end": {
            "gen_img_tok_per_s": stats.rate_from_mean_gap(times, n_gen),
            "image_latency_p50_s": stats.median([r["latency_s"] for r in sent_inside]),
        },
        "records": {
            "completions": inside, "sent_inside": sent_inside, "all_completions": completions,
            "image_tokens": n_gen,
            "occupancy": occupancy, "window_compiles": compiles.count,
            "window_open_polls": window_open_polls, "polls": polls,
            "trace_positions": trace_positions, "correct_detail": detail,
            "memory_at_close": at_close,
            "window_detail": {"open_poll": window_open_polls,
                              "completions": [[r["client"], round(r["t"] - t_open, 4),
                                               round(r["latency_s"], 4),
                                               round(r["ttft_s"], 4)] for r in inside]},
            "weight_itemsize": np.dtype(build.dtype(sizes["serve_recipe"]["param_dtype"])).itemsize,
            "kv_itemsize": np.dtype(engine.pool.dtype).itemsize,
        },
        "attempted": len(inside) + len(failed), "failed": len(failed),
        "correct": bool(ok and images_ok and len(inside) > 0), "t_open": t_open,
    }
