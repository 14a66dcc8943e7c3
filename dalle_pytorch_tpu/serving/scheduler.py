"""Host-side request scheduling and admission control for the serving engine.

Pure host logic (no jax imports at module scope beyond typing): a FIFO
request queue with a hard depth cap, and an `AdmissionController` that
decides per engine iteration whether the next queued request may enter a
decode slot.  Three gates, in order:

  1. **lanes** — a free engine slot (two for a guided request: its [cond]
     and [null] lanes are separate sequences with separate KV).
  2. **pool** — enough free blocks for the FULL sequence (kv_pool
     reservation-at-admission semantics: refusal up front is what turns
     pool exhaustion into backpressure instead of an OOM).
  3. **HBM headroom** — the live allocator usage fraction (PR 5's
     HbmMonitor capacity basis) must sit below `headroom_frac`; above it
     the controller defers admissions until the allocator recedes.

`submit` refuses (AdmissionRefused) rather than queues when the request can
NEVER be admitted (pool smaller than one sequence) or the queue is at its
cap — the flood-fault drill (`--inject_fault flood@STEP`) asserts exactly
this degradation mode.  Every refusal/deferral is counted in the metrics
registry and surfaces as a `serving_backpressure` alarm (once per episode,
re-armed when the queue drains) through the telemetry alarm hub.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Deque, Dict, List, Optional

import numpy as np

from dalle_pytorch_tpu.observability import metrics as obs_metrics


class AdmissionRefused(RuntimeError):
    """The service refused a request outright (queue full / can never fit).

    `kind` is the machine-readable refusal class (`queue_overflow`,
    `never_fits`, `fleet_saturated`) — `AdmissionController.note_refusal`
    counts a `serving/refused_<kind>` counter per class, so dashboards and
    the chaos drills can distinguish "the queue was full" from "this request
    can never be served" without parsing the human-readable reason."""

    def __init__(self, reason: str, kind: str = "other"):
        super().__init__(reason)
        self.reason = reason
        self.kind = kind


@dataclasses.dataclass
class Request:
    """One generation request.  `text`: (text_seq_len,) raw token ids;
    `key`: the request's PRNG key (raw uint32 (2,)) — the engine derives the
    exact key stream `sample_image_codes` would, so a request is bit-
    reproducible against the fused sampler.

    Lifecycle trace: the engine stamps `phases` (queue_wait / admission /
    prefill / decode / evict_sync / codes_pull / vae_decode / evict
    wall-seconds, read off its `serve/` spans) as the request moves
    through it and sets `outcome` exactly once — "completed", "shed"
    (refused at submit), or "deferred" (still queued/in-flight when the
    engine closed) — then emits one `kind:"request"` telemetry record."""

    id: int
    text: np.ndarray
    key: np.ndarray
    temperature: float = 1.0
    cond_scale: float = 1.0
    arrival_t: float = dataclasses.field(default_factory=time.monotonic)
    # durability budget (router/journal-owned): `deadline_s` is seconds from
    # arrival before the request is hedge-eligible/late (None = no deadline);
    # `retries_left` bounds how many requeue/poison-retry hops remain before
    # the terminal requeue_exhausted / poisoned record.  Both are carried
    # through drain() exports and journal `accepted` records so the budget
    # survives requeue hops and process crashes.
    deadline_s: Optional[float] = None
    retries_left: int = 3
    # runtime (engine-owned)
    lanes: Optional[List[int]] = None
    codes_done: int = 0
    admitted_t: Optional[float] = None
    ttft_s: Optional[float] = None
    latency_s: Optional[float] = None
    synthetic: bool = False
    # durability trace: journal content-uid, poison retry count, hedge links
    journal_uid: Optional[str] = None
    # journey trace context (observability/tracing.py): `trace_uid` is the
    # same content uid computed even when no journal is attached — every
    # hop of one logical request (requeue copy, hedged duplicate, crash
    # replay) derives the identical uid, which is what stitches its spans
    # into ONE journey; `replica` is the engine that created this hop (the
    # router reads it to label requeue/hedge edge events)
    trace_uid: Optional[str] = None
    replica: Optional[int] = None
    poison_retries: int = 0
    poison_victim: bool = False  # chaos poison-request fault: re-NaN this
    #                              request every hop until it quarantines
    hedged: bool = False
    hedge_uid: Optional[str] = None
    degrade_rung: int = 0
    replayed: bool = False
    # lifecycle trace (engine-owned)
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    deferrals: int = 0
    outcome: Optional[str] = None
    # speculative decode: verify rounds this request sat through (0 when the
    # engine ran the sequential path)
    spec_rounds: int = 0
    # results
    codes: Optional[np.ndarray] = None
    images: Optional[np.ndarray] = None

    @property
    def guided(self) -> bool:
        return self.cond_scale != 1.0

    @property
    def lanes_needed(self) -> int:
        return 2 if self.guided else 1

    @property
    def accepted_tokens_per_step(self) -> Optional[float]:
        """Mean tokens committed per speculative round for THIS request —
        the per-request acceptance-rate number the telemetry record and the
        bench percentiles report.  None when the request never ran under
        speculation.  `codes_done - 1` because the first code comes from
        prefill, not a decode round."""
        if self.spec_rounds <= 0:
            return None
        return (self.codes_done - 1) / self.spec_rounds

    @property
    def deadline_t(self) -> Optional[float]:
        """Absolute monotonic deadline (None = no deadline)."""
        if self.deadline_s is None:
            return None
        return self.arrival_t + self.deadline_s

    def deadline_frac(self, now: Optional[float] = None) -> Optional[float]:
        """Fraction of the deadline budget consumed (can exceed 1.0).  The
        router hedges a request on a stalled replica once this crosses its
        hedge threshold."""
        if self.deadline_s is None or self.deadline_s <= 0:
            return None
        now = time.monotonic() if now is None else now
        return (now - self.arrival_t) / self.deadline_s


class RequestQueue:
    """Bounded FIFO.  `push` raises AdmissionRefused at the cap — the
    caller (engine.submit) converts that into a refused-request metric."""

    def __init__(self, max_depth: int = 64):
        self.max_depth = max_depth
        self._q: Deque[Request] = collections.deque()

    def __len__(self) -> int:
        return len(self._q)

    def push(self, req: Request) -> None:
        if len(self._q) >= self.max_depth:
            raise AdmissionRefused(
                f"queue full ({self.max_depth} requests waiting)",
                kind="queue_overflow",
            )
        self._q.append(req)
        obs_metrics.gauge("serving/queue_depth").set(len(self._q))

    def requeue(self, req: Request) -> None:
        """Head-of-queue reinsertion for a request the engine already held
        capacity for (a poison retry): exempt from the depth cap — refusing
        a request the service ACCEPTED would be a silent drop."""
        self._q.appendleft(req)
        obs_metrics.gauge("serving/queue_depth").set(len(self._q))

    def peek(self) -> Optional[Request]:
        return self._q[0] if self._q else None

    def pop(self) -> Request:
        req = self._q.popleft()
        obs_metrics.gauge("serving/queue_depth").set(len(self._q))
        return req


class AdmissionController:
    """Decides whether the head-of-queue request may be admitted now.

    `usage_fn` returns the live HBM usage fraction (None where the backend
    exposes no allocator stats — CPU tests inject a fake).  `on_alarm` is
    the telemetry hub sink for `serving_backpressure` (fired once per
    episode: the first deferral/refusal after a period of free flow)."""

    def __init__(
        self,
        pool,
        *,
        headroom_frac: float = 0.92,
        usage_fn: Optional[Callable[[], Optional[float]]] = None,
        on_alarm: Optional[Callable[[Dict[str, Any]], None]] = None,
    ):
        self.pool = pool
        self.headroom_frac = headroom_frac
        self.usage_fn = usage_fn if usage_fn is not None else _default_usage_fn
        self.on_alarm = on_alarm
        self._alarmed = False

    def screen_submit(self, req: Request) -> None:
        """Refuse a request that can NEVER be admitted (satisfying it would
        require more pool than exists) — queueing it would hang the client."""
        if not self.pool.fits_ever() or (
            req.lanes_needed * self.pool.blocks_per_seq > self.pool.num_blocks
        ):
            raise AdmissionRefused(
                f"request needs {req.lanes_needed} x {self.pool.blocks_per_seq} "
                f"blocks but the pool only has {self.pool.num_blocks} — "
                "grow --num_blocks or shrink --block_size",
                kind="never_fits",
            )

    def may_admit(self, req: Request, free_lanes: int,
                  in_flight: int = 0) -> Optional[str]:
        """None when the request may enter now, else the deferral reason.
        The headroom gate only applies while something is IN FLIGHT: with
        zero active lanes the engine's footprint is already at its floor,
        so deferring can never lower usage — it would just livelock the
        service (the override is counted, and external memory pressure
        still shows up through the HbmMonitor alarm)."""
        return self.may_admit_ex(req, free_lanes, in_flight=in_flight)[0]

    def may_admit_ex(self, req: Request, free_lanes: int,
                     in_flight: int = 0) -> tuple:
        """(reason, kind) — the deferral reason plus its machine-readable
        class ("slots" / "pool" / "headroom"), or (None, None) when the
        request may enter now.  The kind is what the pool flight recorder
        logs per deferral, and the only classes the capacity simulator can
        re-derive from a trace: slots and pool deferrals are pure free-list
        arithmetic it replays exactly; headroom deferrals depend on live
        allocator stats and are reported as unmodeled."""
        if free_lanes < req.lanes_needed:
            return (f"no free slot ({free_lanes} free, "
                    f"{req.lanes_needed} needed)", "slots")
        if self.pool.free_blocks < req.lanes_needed * self.pool.blocks_per_seq:
            return (
                f"pool exhausted ({self.pool.free_blocks} blocks free, "
                f"{req.lanes_needed * self.pool.blocks_per_seq} needed)",
                "pool",
            )
        usage = None
        try:
            usage = self.usage_fn()
        except Exception:  # allocator stats must never kill the service
            usage = None
        if usage is not None and usage >= self.headroom_frac:
            if in_flight > 0:
                return (f"HBM headroom ({usage:.2f} >= "
                        f"{self.headroom_frac:.2f} usage fraction)",
                        "headroom")
            obs_metrics.counter("serving/headroom_overrides").inc()
        return (None, None)

    def _alarm_once(self, reason: str) -> None:
        if not self._alarmed:
            self._alarmed = True
            obs_metrics.counter("serving_backpressure_alarms").inc()
            if self.on_alarm is not None:
                self.on_alarm({"type": "serving_backpressure", "reason": reason})

    def note_deferral(self, reason: str) -> None:
        """A queued request waited this iteration (it will still be served)."""
        obs_metrics.counter("serving/admission_deferrals").inc()
        self._alarm_once(reason)

    def note_refusal(self, reason: str, kind: str = "other") -> None:
        """A request was shed outright — count the refusal under its
        machine-readable class (`serving/refused_queue_overflow`, ...) and
        alarm, but do NOT count a deferral (deferrals measure waiting,
        refusals measure dropped load; one event must not inflate both)."""
        obs_metrics.counter(f"serving/refused_{kind}").inc()
        self._alarm_once(reason)

    def note_flow(self) -> None:
        """An admission went through — the backpressure episode (if any)
        is over and the next deferral alarms again."""
        self._alarmed = False


def _default_usage_fn() -> Optional[float]:
    """Live allocator usage fraction from the PR 5 memory stack: the
    max-across-devices bytes_in_use over the device capacity.  None on
    backends without allocator stats (CPU)."""
    from dalle_pytorch_tpu.observability.memory import device_hbm_capacity
    from dalle_pytorch_tpu.observability.xla import record_memory_gauges

    try:
        stats = record_memory_gauges()
    except Exception:
        return None
    if not stats or "bytes_in_use" not in stats:
        return None
    cap = device_hbm_capacity()
    if not cap:
        return None
    return stats["bytes_in_use"] / cap
