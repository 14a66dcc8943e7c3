"""The continuous-batching generation engine.

A long-lived service loop over the `_prefill_phase`/`_decode_phase` seam:
each `poll()` iteration (1) admits queued prompts into free decode slots —
prefill runs per admission through the EXISTING `_prefill_phase` (identical
math to the fused sampler) and its dense cache is scattered into the shared
paged block pool; (2) runs ONE fused `paged_decode_step` for every active
slot — sequences at arbitrary positions advance together under one static
shape, so admissions and evictions never recompile; (3) evicts finished
sequences, frees their blocks, and decodes their codes through the VAE.

RNG is per-request: each request's key is split exactly the way
`sample_image_codes` splits a batch-1 call's key, so engine output is
BIT-IDENTICAL to the fused sampler for the same prompt + key
(tests/test_serving.py proves it, greedy and stochastic, guided and not).

Classifier-free guidance: a guided request occupies TWO lanes — its [cond]
and [null] sequences have different KV — and the per-lane `partner`/
`feed_src` index vectors implement `_cfg_combine` and the shared feed token
inside the one fused step.

Host work here is deliberate and synchronizes only at admission (TTFT needs
the first token to exist) and eviction (pulling a finished slot's codes);
the steady-state decode loop dispatches asynchronously.

Observability: every request leaves exactly one `kind:"request"` JSONL
record — outcome completed/shed/deferred plus per-phase wall-seconds
(queue_wait, admission, prefill, decode, evict_sync, codes_pull, vae_decode,
evict) that sum to its latency.  Every phase of the loop is a span through
`observability.telemetry.span`: an event on the profiler's host plane while a
`jax.profiler` session runs (so the engine's phases and the device's
operations share one clock), a JSONL record as well while a `Telemetry` is
configured, and otherwise inert.  Nested by time on the polling thread, each
carrying `iter=` and, where it belongs to one request, `req=<Request.id>`:

    serve/submit
    serve/poll
      serve/admit                 one per admitted request; lanes=
        serve/admit.alloc         block tables, lane indices
                                                           -> phases["admission"]
        serve/admit.dispatch      the admit / ingest jit call: it splits the
                                  request's key and writes the lanes' metadata
        serve/admit.lane_meta     host bookkeeping (the in-flight list)
        serve/admit.ttft_sync     block_until_ready; with dispatch and
                                  lane_meta                -> phases["prefill"]
      serve/decode.dispatch       around it serve/spec.draft and
                                  serve/spec.verify on the speculative path
      serve/evict                 only in a poll that has finished requests
        serve/evict.flag_sync     the poisoned-flag pull: the drain of the
                                  queued steps             -> phases["evict_sync"]
        serve/evict.codes_pull    per request              -> phases["codes_pull"]
        serve/evict.lane_reset    the dispatch of the reset program that
                                  frees the lanes
        serve/evict.vae_decode    per request, dispatch + block
                                                           -> phases["vae_decode"]
        serve/evict.pixels_pull   per request

The same readings, per POLL, are one row of the series `serving/polls`
(`engine.polls`: an `observability.metrics.Series`, the last 65,536 polls in
one block allocated at build; always on, a row being two clock reads and a
write in place beside a decode step of milliseconds; `serving/polls.r<id>` for
a fleet replica; an engine built later in a process takes the name over):

    iter                      the `iter=` of the spans above
    t0_s, dur_s               poll entry (`time.perf_counter`, the clock
                              `timed_span` reads) to the end of serve/poll: the
                              row and the span of one `iter` are one interval
                              on two clocks, so a poll is found in a trace
    admit_s, dispatch_s       the serve/admit spans; serve/decode.dispatch
    block_s, evict_s          the eviction's device waits (flag_sync,
                              codes_pull, vae_decode); the rest of serve/evict.
                              The four never exceed dur_s
    admitted, evicted, lanes  requests; lane-tokens decoded, as
                              `serving/decode_lane_tokens` counts them

`t0_s[i+1] - (t0_s[i] + dur_s[i])` is time outside the poll: the caller's loop
(and the window event, every `telemetry_every` polls).  A wedged poll writes
no row, as it advances no `iter`.  The `serving_window` event's admit/dispatch/
block/evict split, its `decode_steps` and its goodput figure (lane-tokens
actually decoded vs the ideal slots x steps) are sums over the rows since the
last event, and the status file's `serving.worst_poll` is that window's
longest poll (`iter`, `dur_s`, its largest part as `phase` and `phase_s`;
`other` is what no span covers): after a latency alarm look there first, then
at `engine.polls.rows()`.  Every span closes where the code already returns or
already blocks: telemetry-off poll() performs ZERO additional device syncs
(tools/lint_host_sync.py keeps that mechanical).  The jitted programs carry
stable names (`serve_decode_step`, `serve_admit`, `serve_ingest`,
`serve_lane_reset`, `serve_vae_decode`, `serve_spec_draft`,
`serve_spec_verify`), which is how a trace's `XLA Modules` line is read.

Every write to the lanes' device state runs inside one of those programs:
admission writes a request's lanes in `serve_admit` / `serve_ingest`, and
eviction and `drain()` free lanes through `serve_lane_reset`, one donated
program over a mask of all slots (so one compile serves one lane, a guided
pair or a whole drain; `serving/lane_reset_calls` and
`serving/lane_reset_lanes` count its dispatches and the lanes they free).
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import signal
import time
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models import sampling as sampling_mod
from dalle_pytorch_tpu.models import speculative as spec_mod
from dalle_pytorch_tpu.models.transformer import (
    init_slot_rings,
    paged_decode_step,
    refuse_hybrid,
    write_prefill_to_pool,
)
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.observability import telemetry
from dalle_pytorch_tpu.observability import tracing
from dalle_pytorch_tpu.ops.sampling import gumbel_sample, top_k_filter
from dalle_pytorch_tpu.serving.kv_pool import BlockPool, PoolFlightRecorder
from dalle_pytorch_tpu.serving.scheduler import (
    AdmissionController,
    AdmissionRefused,
    Request,
    RequestQueue,
)
from dalle_pytorch_tpu.training import resilience

# one row a poll() in the series `serving/polls` (module docstring)
POLL_SERIES = "serving/polls"
POLL_PHASES = ("admit", "dispatch", "block", "evict")
POLL_COLUMNS = ("iter", "t0_s", "dur_s") + tuple(f"{p}_s" for p in POLL_PHASES) + (
    "admitted", "evicted", "lanes")
POLL_CAPACITY = 65536  # a 48 s window at a 1 ms poll still fits; ~5 MB
# the lane fields a freed lane returns to zero (`serve_lane_reset`)
LANE_RESET_FIELDS = ("active", "block_tables", "offsets", "img_prev",
                     "poisoned", "cand_cap")
# the request key `serve_ingest` arms a lane with when it is given none
_NO_KEY = np.zeros((2,), np.uint32)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving knobs.  `num_blocks` defaults to exactly enough for
    `num_slots` full sequences (no refusals from the pool until slots run
    out); size it SMALLER to make the pool the admission bottleneck."""

    num_slots: int = 4
    block_size: int = 32
    num_blocks: Optional[int] = None
    max_queue: int = 64
    headroom_frac: float = 0.92
    filter_thres: float = 0.9
    telemetry_every: int = 32  # poll iterations between serving_window events
    quantize_kv: Optional[str] = None  # "int8" stores the KV pool quantized
    poison_max_retries: int = 2  # decode retries before a nonfinite lane is
    #                              quarantined with a terminal `poisoned` record
    degraded_filter_thres: float = 0.98  # top-k keep fraction for lanes
    #                              admitted under the cap-candidates rung
    spec_k: int = 0  # speculative decode: tokens drafted per round (0 = off,
    #                  the sequential path — same jit, same bits as before)
    spec_draft_layers: Optional[int] = None  # drafter depth d (layers [0, d)),
    #                  default depth // 2; the verify pass runs [d, depth)
    pool_recorder: bool = True  # KV-pool flight recorder: block-lifecycle
    #                  events into a bounded ring, flushed through telemetry
    #                  as kind:"pool" records (off = the hooks vanish to one
    #                  `is None` test; nothing is recorded or allocated)
    pool_recorder_capacity: int = 4096  # ring bound; overflow drops the
    #                  OLDEST events (counted — pool_report refuses to
    #                  self-validate a torn trace)


class GenerationEngine:
    def __init__(
        self,
        params: dict,
        cfg,
        vae_params: Optional[dict] = None,
        vae_cfg: Any = None,
        engine_cfg: EngineConfig = EngineConfig(),
        usage_fn=None,
    ):
        assert cfg.image_seq_len >= 2, "engine needs at least 2 image tokens"
        refuse_hybrid(cfg.transformer_config(), "GenerationEngine")
        if engine_cfg.quantize_kv not in (None, "none"):
            refuse_hybrid(cfg.transformer_config(),
                          f"GenerationEngine(quantize_kv={engine_cfg.quantize_kv!r})",
                          recurrent_state=False)
        self.params = params
        self.cfg = cfg
        self.tcfg = cfg.transformer_config()
        self.vae_params = vae_params
        self.vae_cfg = vae_cfg
        self.ecfg = engine_cfg
        self.n_pre = cfg.text_seq_len + 1  # bos + text (prime_len 0)
        self.n_gen = cfg.image_seq_len

        from dalle_pytorch_tpu.quantization import weight_dtype

        ldtype = weight_dtype(params)  # the init_cache convention
        kv_quant = engine_cfg.quantize_kv
        if kv_quant == "none":
            kv_quant = None
        self.pool = BlockPool(
            self.tcfg,
            engine_cfg.num_blocks
            if engine_cfg.num_blocks is not None
            else engine_cfg.num_slots * _blocks_per_seq(self.tcfg, engine_cfg.block_size),
            engine_cfg.block_size,
            dtype=ldtype,
            quant=kv_quant,
            num_slots=engine_cfg.num_slots,
        )
        obs_metrics.gauge("serving/state_bytes").set(self.pool.state_bytes())
        # KV-pool flight recorder + live gauges (observability/pool.py):
        # block-lifecycle events at the existing admission/eviction syncs,
        # flushed as kind:"pool" records at the telemetry-window cadence
        self._pool_gauges = None
        if engine_cfg.pool_recorder:
            from dalle_pytorch_tpu.observability.pool import PoolGauges

            rec = PoolFlightRecorder(
                capacity=engine_cfg.pool_recorder_capacity)
            itemsize = np.dtype(ldtype).itemsize
            rec.config = {
                "num_blocks": self.pool.num_blocks,
                "block_size": engine_cfg.block_size,
                "blocks_per_seq": self.pool.blocks_per_seq,
                "num_slots": engine_cfg.num_slots,
                "n_pre": self.n_pre,
                "n_gen": self.n_gen,
                "kv_quant": kv_quant,
                "bytes_per_block": round(
                    self.pool.bytes(itemsize) / (self.pool.num_blocks + 1), 1),
            }
            self.pool.recorder = rec
            self._pool_gauges = PoolGauges(
                num_blocks=self.pool.num_blocks,
                block_size=engine_cfg.block_size,
                blocks_per_seq=self.pool.blocks_per_seq)
            rec.on_event = self._pool_gauges.observe
        self.queue = RequestQueue(max_depth=engine_cfg.max_queue)
        self.admission = AdmissionController(
            self.pool,
            headroom_frac=engine_cfg.headroom_frac,
            usage_fn=usage_fn,
            on_alarm=self._alarm,
        )

        S = engine_cfg.num_slots
        nk = max(self.n_gen - 1, 1)
        # the decode programs' head and lookup table, laid out once from
        # weights that do not change under the engine (a fleet's reshard
        # re-places `params`, it does not change them).  It rides in
        # `_state` so that it reaches every program as an ARGUMENT beside
        # `params`, which stays the checkpoint's tree; the programs hand it
        # through, aliased where the state is donated
        head = jax.jit(lambda p: dalle_mod.image_head(p, cfg))(params)
        self._head_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(head))
        obs_metrics.gauge("serving/decode_head_bytes").set(self._head_bytes)
        self._state: Dict[str, Any] = {
            "head": head,
            "pool": self.pool.device_pool(ldtype),
            "rings": init_slot_rings(self.tcfg, S, ldtype),
            "block_tables": jnp.zeros((S, self.pool.blocks_per_seq), jnp.int32),
            "offsets": jnp.zeros((S,), jnp.int32),
            "prev_code": jnp.zeros((S,), jnp.int32),
            "img_prev": jnp.zeros((S,), jnp.int32),
            "codes": jnp.zeros((S, self.n_gen), jnp.int32),
            "keys": jnp.zeros((S, nk, 2), jnp.uint32),
            "temp": jnp.ones((S,), jnp.float32),
            "cscale": jnp.ones((S,), jnp.float32),
            "guided": jnp.zeros((S,), bool),
            "partner": jnp.arange(S, dtype=jnp.int32),
            "feed_src": jnp.arange(S, dtype=jnp.int32),
            "active": jnp.zeros((S,), bool),
            # durability lane state: per-lane nonfinite flag (accumulated
            # jit-pure, pulled only at the eviction sync), the lane the
            # poison-request fault's victim currently occupies (-1 = none;
            # tracked across retry hops by _track_poison_lane), and the
            # per-lane candidate-cap mask the degrade ladder sets at admit
            "poisoned": jnp.zeros((S,), bool),
            "poison_lane": jnp.asarray(-1, jnp.int32),
            "cand_cap": jnp.zeros((S,), bool),
        }
        self._free_lanes: List[int] = list(range(S))
        self._inflight: List[Request] = []
        self._next_id = 0
        self._iter = 0
        self._warm_decode = False
        self._flood_rng = np.random.RandomState(0)
        # fleet hooks: a router tags this engine's request records with its
        # replica id; a disaggregated fleet installs a prefill worker here
        # (serving/fleet.PrefillWorker), and _do_admit ingests its handoff
        # instead of running prefill in-engine
        self.replica_id = None  # also binds `self.polls` under its name
        self.prefill_backend = None
        # durability hooks: a RequestJournal (serving/journal.py) makes
        # accepted requests crash-replayable; a DegradeLadder
        # (serving/degrade.py) shapes/screens submits under pressure
        # (`degrade_observe` is False when a fleet drives the ladder so the
        # pressure signal is observed once, fleet-wide, not per engine);
        # `_stall_until` wedges poll() for the stall-replica fault — alive
        # but making no progress, the failure mode the circuit breaker trips
        # on
        self.journal = None
        self.degrade = None
        self.degrade_observe = True
        self._stall_until = 0.0
        self._poison_lane_host = -1
        # observability attachments (all optional; telemetry-off poll() runs
        # the identical device schedule with only time.monotonic bookkeeping)
        self._slo = None            # observability.slo.SloMonitor
        self._status_path: Optional[str] = None
        self._capture = None        # observability.capture.TraceTrigger
        self._phase = "idle"        # live poll phase, for hang-dump context
        self._worst_poll: Optional[Dict[str, Any]] = None  # of that window
        # prefix-redundancy profiler (the measured case for a prefix cache):
        # content-hash of each admitted prompt prefix plus byte accounting
        # for the two duplication sources — the CFG null lane (its prefix KV
        # is text-independent, so every guided admission prefills an
        # identical copy) and repeated prompts (hedged copies, requeues,
        # replays, genuinely repeated text).  Pure host arithmetic at the
        # admission sync; `prefix_redundancy()` summarizes for the bench row
        self._prefix_seen: Dict[str, int] = {}
        self._prefix_admissions = 0
        self._prefix_repeats = 0
        self._prefix_repeat_bytes = 0.0
        self._prefix_null_bytes = 0.0
        self._prefix_total_bytes = 0.0
        # speculative decode state: (k, d) when enabled, the draft/verify
        # jit pair (NO donation — verify needs the pre-round rings for its
        # rollback while the draft result is still live), warm-compile flag,
        # and the per-window accounting behind spec/accepted_tokens_per_step
        # and spec/draft_time_frac
        self._spec: Optional[tuple] = None
        self._warm_spec = False
        self._win_spec_rounds = 0
        self._win_spec_accepted = 0
        self._win_spec_draft_s = 0.0
        self._win_spec_total_s = 0.0
        if engine_cfg.spec_k:
            self._spec = spec_mod.validate_spec(
                self.tcfg, engine_cfg.spec_k, engine_cfg.spec_draft_layers)
            k, d = self._spec

            def serve_spec_draft(params, state):
                return spec_mod.engine_spec_draft(
                    params, self.cfg, self.tcfg, state, spec_k=k,
                    draft_layers=d, block_size=engine_cfg.block_size,
                    filter_thres=engine_cfg.filter_thres,
                    degraded_filter_thres=engine_cfg.degraded_filter_thres,
                )

            def serve_spec_verify(params, state, draft):
                new_state, a = spec_mod.engine_spec_verify(
                    params, self.cfg, self.tcfg, state, draft, spec_k=k,
                    draft_layers=d, block_size=engine_cfg.block_size,
                    n_gen=self.n_gen,
                    filter_thres=engine_cfg.filter_thres,
                    degraded_filter_thres=engine_cfg.degraded_filter_thres,
                )
                # this program's state is not donated, and an entry handed
                # through it is copied: the table stays with the caller
                del new_state["head"]
                return new_state, a

            self._spec_draft_fn = jax.jit(serve_spec_draft)
            self._spec_verify_fn = jax.jit(serve_spec_verify)

        # {"kernel": n, "fallback": n} once the decode program is traced
        self._paged_paths: Optional[Dict[str, int]] = None

        # the function's name is the program's in a trace (`jit_<name>`)
        def serve_decode_step(params, state):
            return self._decode_step_impl(params, state)

        self._decode_fn = jax.jit(serve_decode_step, donate_argnums=(1,))

        def serve_lane_reset(state, mask):
            # LANE_RESET_FIELDS back to zero on the lanes `mask` selects
            out = dict(state)
            for name in LANE_RESET_FIELDS:
                x = state[name]
                m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
                out[name] = jnp.where(m, jnp.zeros((), x.dtype), x)
            return out

        self._lane_reset_fn = jax.jit(serve_lane_reset, donate_argnums=(0,))
        self._warm_reset = False
        self._admit_fns: Dict[Any, Any] = {}
        self._vae_decode = None
        if vae_params is not None:
            from dalle_pytorch_tpu.models import vae_registry

            def serve_vae_decode(codes):
                return vae_registry.decode_indices(vae_params, vae_cfg, codes)

            self._vae_decode = jax.jit(serve_vae_decode)

    @property
    def replica_id(self) -> Optional[int]:
        return self._replica_id

    @replica_id.setter
    def replica_id(self, value: Optional[int]) -> None:
        """A router names its replicas after they are built: the poll series
        goes into the registry again under the replica's name, empty."""
        self._replica_id = value
        name = POLL_SERIES if value is None else f"{POLL_SERIES}.r{value}"
        self.polls = obs_metrics.series(name, POLL_COLUMNS, POLL_CAPACITY,
                                        fresh=True)
        self._win_row = 0  # `polls.total` at the last window event

    # ------------------------------------------------------------------ jits
    def _decode_step_impl(self, params, state):
        """One fused decode step for all slots.  The transformer output ->
        sampled code half (image logits, poison injection, CFG across lane
        pairs, nonfinite screen, degrade-capped top-k, per-lane step key,
        feed-source mirror) lives in `speculative.lane_sample_pipeline`, the
        single pipeline the speculative draft/verify round also runs — so
        the two decode modes cannot drift apart bit-wise."""
        cfg, tcfg = self.cfg, self.tcfg
        prev = state["prev_code"]

        emb = spec_mod._embed_prev(params, cfg, state["head"], prev,
                                   state["img_prev"])

        paths = {"kernel": 0, "fallback": 0}
        out, pool, rings = paged_decode_step(
            params["transformer"], tcfg, emb, state["pool"],
            state["block_tables"], state["offsets"], state["rings"],
            self.ecfg.block_size, path_tally=paths,
        )
        self._note_paged_paths(paths)

        # per-lane step key row = img_prev (the index of the token being made)
        code, bad = spec_mod.lane_sample_pipeline(
            params, cfg, out, state["img_prev"], state,
            self.ecfg.filter_thres, self.ecfg.degraded_filter_thres,
        )
        poisoned = state["poisoned"] | bad

        act = state["active"]
        S = self.ecfg.num_slots
        with jax.named_scope("codes_write"):
            img_new = jnp.where(act, state["img_prev"] + 1, state["img_prev"])
            widx = jnp.clip(img_new, 0, self.n_gen - 1)
            existing = jnp.take_along_axis(state["codes"], widx[:, None], axis=1)[:, 0]
            codes_buf = state["codes"].at[jnp.arange(S), widx].set(
                jnp.where(act, code, existing)
            )
            return dict(
                state,
                pool=pool,
                rings=rings,
                offsets=jnp.where(act, state["offsets"] + 1, state["offsets"]),
                prev_code=jnp.where(act, code, state["prev_code"]),
                img_prev=img_new,
                codes=codes_buf,
                poisoned=poisoned,
            )

    def _note_paged_paths(self, paths: Dict[str, int]) -> None:
        """Runs while the decode program is TRACED: how many of its attention
        layers took the Pallas paged kernel, how many the XLA gather and how
        many advance a recurrent state instead, and that its head and lookup
        read the table laid out at build, into the registry (and the status
        file).  A retrace counts nothing new."""
        if self._paged_paths is None:
            self._paged_paths = dict(paths)
            obs_metrics.counter("serving/paged_attn_kernel_layers").inc(paths["kernel"])
            obs_metrics.counter("serving/paged_attn_fallback_layers").inc(paths["fallback"])
            obs_metrics.counter("serving/gdn_state_layers").inc(paths.get("state", 0))
            obs_metrics.counter("serving/gdn_step_kernel_layers").inc(paths.get("state_kernel", 0))
            obs_metrics.counter("serving/decode_head_prepared").inc()

    def _prefill_sample_impl(self, params, text, k0, temperature,
                             cond_scale: float):
        return prefill_sample(params, self.cfg, self.ecfg.filter_thres,
                              text, k0, temperature, cond_scale)

    def _ingest_impl(self, state, cache_layers, code, bt_rows, lane_idx,
                     lanes: int, step_keys, temperature, cond_scale, cand_cap):
        """The other half of admission: scatter a prefilled KV prefix into
        the paged pool and arm the lanes — the request's step keys, its
        temperature, guidance scale and candidate cap, the active flag and,
        for a guided pair, the [cond] / [null] indices.  Shared verbatim by
        the fused admit jit and the disaggregated ingest jit, which is what
        makes the two paths bit-identical."""
        pool = write_prefill_to_pool(
            state["pool"], bt_rows, cache_layers,
            self.n_pre, self.ecfg.block_size, slots=lane_idx,
        )
        rings = state["rings"]
        if rings is not None:
            with jax.named_scope("token_shift"):
                new_layers = []
                for rl, cl in zip(rings["layers"], cache_layers):
                    new_layers.append({
                        "shift_attn": rl["shift_attn"].at[lane_idx].set(
                            cl["shift_attn"].astype(rl["shift_attn"].dtype)),
                        "shift_ff": rl["shift_ff"].at[lane_idx].set(
                            cl["shift_ff"].astype(rl["shift_ff"].dtype)),
                    })
                rings = {"layers": new_layers}

        with jax.named_scope("codes_write"):
            codeb = jnp.broadcast_to(code, (lanes,))
            cond = lane_idx[0]
            st = dict(
                state,
                pool=pool,
                rings=rings,
                block_tables=state["block_tables"].at[lane_idx].set(bt_rows),
                codes=state["codes"].at[lane_idx, 0].set(codeb),
                prev_code=state["prev_code"].at[lane_idx].set(codeb),
                offsets=state["offsets"].at[lane_idx].set(self.n_pre),
                img_prev=state["img_prev"].at[lane_idx].set(0),
                keys=state["keys"].at[cond].set(step_keys),
                temp=state["temp"].at[lane_idx].set(temperature),
                cscale=state["cscale"].at[lane_idx].set(cond_scale),
                active=state["active"].at[lane_idx].set(True),
                cand_cap=state["cand_cap"].at[lane_idx].set(cand_cap),
            )
            if lanes == 2:
                # the [null] lane takes its partner's logits and token
                null = lane_idx[1]
                return dict(
                    st,
                    guided=st["guided"].at[cond].set(True).at[null].set(False),
                    partner=st["partner"].at[cond].set(null).at[null].set(null),
                    feed_src=st["feed_src"].at[cond].set(cond).at[null].set(cond),
                )
            return dict(
                st,
                guided=st["guided"].at[cond].set(False),
                partner=st["partner"].at[cond].set(cond),
                feed_src=st["feed_src"].at[cond].set(cond),
            )

    def _admit_fn_for(self, cond_scale: float, lanes: int):
        fn_key = (float(cond_scale), lanes)  # host-sync-ok: python jit-cache key
        fn = self._admit_fns.get(fn_key)
        if fn is not None:
            return fn

        def serve_admit(params, state, text, key, temperature, bt_rows,
                        lane_idx, cand_cap=False):
            with jax.named_scope("sample"):
                step_keys, k0 = request_keys(key, state["keys"].shape[1])
            cache_layers, code = self._prefill_sample_impl(
                params, text, k0, temperature, cond_scale)
            return self._ingest_impl(
                state, cache_layers, code, bt_rows, lane_idx, lanes,
                step_keys, temperature, cond_scale, cand_cap)

        fn = jax.jit(serve_admit, donate_argnums=(1,))
        self._admit_fns[fn_key] = fn
        return fn

    def _ingest_fn_for(self, lanes: int):
        """Jitted pool-write for a handoff produced elsewhere (the decode
        side of prefill/decode disaggregation).  The request's key and
        sampling knobs arm its lanes as in `serve_admit`; left out, they are
        those of a request that asks for nothing (key zero)."""
        fn_key = ("ingest", lanes)
        fn = self._admit_fns.get(fn_key)
        if fn is not None:
            return fn

        def serve_ingest(state, cache_layers, code, bt_rows, lane_idx,
                         key=_NO_KEY, temperature=1.0, cond_scale=1.0,
                         cand_cap=False):
            with jax.named_scope("sample"):
                step_keys, _ = request_keys(key, state["keys"].shape[1])
            return self._ingest_impl(
                state, cache_layers, code, bt_rows, lane_idx, lanes,
                step_keys, temperature, cond_scale, cand_cap)

        fn = jax.jit(serve_ingest, donate_argnums=(0,))
        self._admit_fns[fn_key] = fn
        return fn

    # ------------------------------------------------------------- lifecycle
    def _make_request(self, text, key, temperature, cond_scale,
                      synthetic, deadline_s=None, retries_left=None,
                      replayed: bool = False) -> Request:
        if key is None:
            key = jax.random.PRNGKey(self._next_id)
        req = Request(
            id=self._next_id,
            text=np.asarray(text, np.int32).reshape(self.cfg.text_seq_len),  # host-sync-ok: host token ids
            key=np.asarray(key, np.uint32).reshape(2),  # host-sync-ok: host PRNG key
            temperature=float(temperature),  # host-sync-ok: CLI/host scalar
            cond_scale=float(cond_scale),  # host-sync-ok: CLI/host scalar
            synthetic=synthetic,
            replayed=replayed,
        )
        if deadline_s is not None:
            req.deadline_s = float(deadline_s)  # host-sync-ok: CLI/host scalar
        if retries_left is not None:
            req.retries_left = int(retries_left)  # host-sync-ok: CLI/host scalar
        # journey trace context: the content uid is computed at submit (one
        # sha1 over host ints — journal-attached submits would compute it
        # anyway) so every hop of a logical request carries its journey id
        # and loadgen can aggregate per-journey without telemetry
        req.replica = self.replica_id
        tracing.journey_uid(req)
        self._next_id += 1
        return req

    def submit(self, text, key=None, temperature: float = 1.0,
               cond_scale: float = 1.0, synthetic: bool = False,
               deadline_s=None, retries_left=None,
               replayed: bool = False) -> Request:
        """Enqueue one prompt.  `text`: (text_seq_len,) raw token ids;
        `key`: request PRNG key (defaults to PRNGKey(request id)).  Raises
        AdmissionRefused when the service must shed load (queue full, the
        request can never fit the pool, or the degrade ladder is screening).
        An accepted request is journaled (fsynced) before submit returns —
        the durability point: after this, a crash cannot silently lose it."""
        req = self._make_request(text, key, temperature, cond_scale,
                                 synthetic, deadline_s, retries_left,
                                 replayed)
        with telemetry.span("serve/submit", iter=self._iter, req=req.id):
            try:
                if self.degrade is not None:
                    self.degrade.shape_request(req)
                self.admission.screen_submit(req)
                self.queue.push(req)
            except AdmissionRefused as e:
                obs_metrics.counter("serving/refused").inc()
                self.admission.note_refusal(e.reason, kind=e.kind)
                req.phases["queue_wait"] = time.monotonic() - req.arrival_t
                self._finish_record(req, "shed", reason=e.reason)
                raise
            obs_metrics.counter("serving/submitted").inc()
            if self.journal is not None:
                self.journal.accepted(req)
        return req

    def submit_when_able(self, text, key=None, temperature: float = 1.0,
                         cond_scale: float = 1.0, synthetic: bool = False,
                         deadline_s=None, retries_left=None,
                         replayed: bool = False) -> Request:
        """Blocking submit for batch callers (generate.py --engine, the
        prompt-mode serve CLI) and router requeues: a full queue BLOCKS —
        the engine polls until a slot frees — instead of refusing, and the
        wait counts as no refusal (those counters measure shed load, which a
        waiting batch caller is not).  A request that can NEVER fit the pool
        still refuses outright."""
        req = self._make_request(text, key, temperature, cond_scale,
                                 synthetic, deadline_s, retries_left,
                                 replayed)
        try:
            if self.degrade is not None:
                self.degrade.shape_request(req)
            self.admission.screen_submit(req)
        except AdmissionRefused as e:
            obs_metrics.counter("serving/refused").inc()
            req.phases["queue_wait"] = time.monotonic() - req.arrival_t
            self._finish_record(req, "shed", reason=e.reason)
            raise
        while len(self.queue) >= self.queue.max_depth:
            self.poll()  # a full queue implies busy, so this makes progress
        with telemetry.span("serve/submit", iter=self._iter, req=req.id):
            self.queue.push(req)
            obs_metrics.counter("serving/submitted").inc()
            if self.journal is not None:
                self.journal.accepted(req)
        return req

    @property
    def busy(self) -> bool:
        """Work pending: queued or in-flight requests."""
        return bool(len(self.queue) or self._inflight)

    def wedge(self, seconds: float) -> None:
        """Stall-replica fault: make poll() a no-op for `seconds` — the
        process stays alive and the engine keeps its queue/in-flight state,
        but its iteration counter and heartbeat stop advancing."""
        self._stall_until = time.monotonic() + float(seconds)  # host-sync-ok: CLI/host scalar

    @property
    def stalled(self) -> bool:
        return bool(self._stall_until
                    and time.monotonic() < self._stall_until)

    def _track_poison_lane(self) -> None:
        """Pin the poison fault's NaN injection to its victim REQUEST, not a
        lane index: the victim is re-poisoned on every decode step and every
        retry hop (its lane changes across re-admissions) until it burns its
        retry budget and quarantines — a persistently-bad request, the case
        the quarantine exists for.  Transient nonfinites (no sticky victim)
        still retry clean and complete."""
        lane = -1
        for r in self._inflight:
            if getattr(r, "poison_victim", False) and r.lanes:
                lane = r.lanes[0]
                break
        if lane != self._poison_lane_host:
            self._poison_lane_host = lane
            self._state = dict(self._state,
                               poison_lane=jnp.asarray(lane, jnp.int32))

    @property
    def free_slots(self) -> int:
        """Decode lanes currently free (a router placement input)."""
        return len(self._free_lanes)

    def drain(self) -> List[Dict[str, Any]]:
        """Stop serving and EXPORT every unfinished request so a survivor
        can re-serve it exactly: for each queued and in-flight request,
        return the prompt, the ORIGINAL request key, sampling knobs, and the
        RNG stream position (`codes_done`) plus the codes accepted so far.

        Per-request RNG streams make the re-decode exact — a fresh engine
        given the same (text, key, temperature, cond_scale) derives the
        identical key stream, so its output is bit-identical and the
        exported `codes` prefix must match the resubmission's first
        `codes_done` codes (tests/test_fleet_serving.py proves it).

        Each drained request still leaves its single terminal record on THIS
        engine — outcome "deferred" with `requeued: true` — and its lanes
        and pool blocks are freed, leaving the engine empty but usable."""
        now = time.monotonic()
        exports: List[Dict[str, Any]] = []

        def _export(req: Request, codes: Optional[np.ndarray]) -> Dict[str, Any]:
            return {
                "text": np.asarray(req.text, np.int32),  # host-sync-ok: drain exports live on host
                "key": np.asarray(req.key, np.uint32),  # host-sync-ok: drain exports live on host
                "temperature": req.temperature,
                "cond_scale": req.cond_scale,
                "synthetic": req.synthetic,
                "codes_done": req.codes_done,  # RNG stream position
                "codes": codes,                # accepted prefix (None if queued)
                "origin_id": req.id,
                "origin_replica": self.replica_id,
                # durability budget rides the requeue hop: the router
                # decrements retries_left and sheds (requeue_exhausted)
                # when it hits zero
                "deadline_s": req.deadline_s,
                "retries_left": req.retries_left,
            }

        while True:
            req = self.queue.peek()
            if req is None:
                break
            self.queue.pop()
            req.phases["queue_wait"] = now - req.arrival_t
            exports.append(_export(req, None))
            self._finish_record(req, "deferred", requeued=True)
        all_lanes: List[int] = []
        for req in self._inflight:
            if req.admitted_t is not None:
                req.phases["decode"] = now - req.admitted_t
            codes = np.asarray(  # host-sync-ok: exporting the drained slot's accepted codes
                self._state["codes"][req.lanes[0], :req.codes_done]
            )
            exports.append(_export(req, codes))
            self._finish_record(req, "deferred", requeued=True)
            for i in range(len(req.lanes)):
                # KV actually written by a drained lane: prefill's n_pre
                # tokens plus one per decode step fed (the last sampled
                # code was never fed back) — the recorder's reserved-vs-
                # written gap is the waste expected-block admission reclaims
                self.pool.free_table(
                    (req.id << 1) | i,
                    written_tokens=self.n_pre + max(req.codes_done - 1, 0))
            all_lanes.extend(req.lanes)
            self._free_lanes.extend(req.lanes)
        self._inflight = []
        if all_lanes:
            self._reset_lanes(all_lanes)
        obs_metrics.counter("serving/drained").inc(len(exports))
        self._window_event()
        return exports

    def poll(self) -> List[Request]:
        """One engine iteration: flood-fault poll, admissions, one fused
        decode step, evictions.  Returns the requests completed this
        iteration (codes — and images when a VAE is attached — populated).

        Phase attribution: every phase is a `serve/...` span (the tree is in
        the module docstring), and the poll's row in `self.polls` is written
        from the same readings: admit (the `serve/admit` spans, which contain
        the deliberate TTFT sync), dispatch (`serve/decode.dispatch`), block
        (the eviction's device waits: flag sync, codes pulls, VAE decodes) and
        evict (the rest of `serve/evict`).  No device syncs added."""
        if self._stall_until:
            if time.monotonic() < self._stall_until:
                # wedged (stall-replica fault): alive but making no progress
                # — no iteration advance, no heartbeat, no decode.  This is
                # the failure mode the router's circuit breaker must detect
                # without the replica ever dying.
                return []
            self._stall_until = 0.0
        t0 = time.perf_counter()
        self._iter += 1
        if self._capture is not None:
            self._capture.on_step_start(self._iter)
        self._poll_flood()
        if (self.replica_id is None
                and resilience.take_kill_fleet_fault(self._iter)):
            # single-engine serve: the crash-replay drill dies HERE with no
            # cleanup (a fleet fires the same fault from fleet.poll first)
            print(f"[chaos] kill-fleet: SIGKILL whole process at engine "
                  f"iteration {self._iter}", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
        if self.degrade is not None and self.degrade_observe:
            self.degrade.observe(
                len(self.queue) / max(self.queue.max_depth, 1),
                slo=self._slo)
        if self._inflight and resilience.take_poison_fault(self._iter):
            victim = self._inflight[0]
            victim.poison_victim = True
            print(f"[chaos] poison-request: request {victim.id} poisoned — "
                  "NaN decode logits until its retry budget burns", flush=True)
        with telemetry.span("serve/poll", iter=self._iter):
            self._phase = "admit"
            admit_s, admitted = self._admit_ready()
            self._track_poison_lane()
            dispatch_s, lanes = 0.0, 0
            if self._inflight:
                self._phase = "dispatch"
                with telemetry.timed_span("serve/decode.dispatch",
                                          iter=self._iter) as t:
                    lanes = self._decode_once()
                dispatch_s = t.s
            self._phase = "evict"
            done, evicted, block_s, evict_s = self._evict_finished()
            self._phase = "idle"
        self.polls.append(self._iter, t0, time.perf_counter() - t0, admit_s,
                          dispatch_s, block_s, evict_s, admitted, evicted,
                          lanes)
        if self.ecfg.telemetry_every and self._iter % self.ecfg.telemetry_every == 0:
            self._window_event()
        if self._capture is not None:
            self._capture.on_step_end(self._iter)
        tele = telemetry.active()
        if tele is not None and tele.heartbeat is not None:
            tele.heartbeat.beat(self._iter)
        return done

    def run_until_idle(self, max_iters: Optional[int] = None) -> List[Request]:
        """Drive poll() until queue and slots drain; returns all completions."""
        out: List[Request] = []
        iters = 0
        while len(self.queue) or self._inflight:
            out.extend(self.poll())
            iters += 1
            if max_iters is not None and iters >= max_iters:
                break
        return out

    def generate(self, texts, keys=None, temperature: float = 1.0,
                 cond_scale: float = 1.0) -> List[Request]:
        """Convenience batch API: submit every row of `texts` (b, ts) with
        its own key (row i of `keys`, default PRNGKey(i)) and run to
        completion.  Returns requests in submission order."""
        texts = np.asarray(texts)  # host-sync-ok: caller-provided host prompts
        reqs = []
        for i in range(texts.shape[0]):
            k = keys[i] if keys is not None else jax.random.PRNGKey(i)
            # blocking submit: a batch larger than the queue cap waits for
            # slots instead of being refused (shedding is for live traffic)
            reqs.append(self.submit_when_able(
                texts[i], key=k, temperature=temperature,
                cond_scale=cond_scale))
        self.run_until_idle()
        return reqs

    # ---------------------------------------------------------------- internals
    def _suspend_compiles(self):
        tele = telemetry.active()
        if tele is not None and tele.compile_watcher is not None:
            return tele.compile_watcher.suspended()
        return contextlib.nullcontext()

    def _alarm(self, fields: Dict[str, Any]) -> None:
        tele = telemetry.active()
        if tele is not None:
            f = dict(fields)
            tele.alarm(f.pop("type", "serving_backpressure"), **f)

    # ------------------------------------------------------- observability
    def attach_slo(self, monitor, status_path: Optional[str] = None) -> None:
        """Wire an `observability.slo.SloMonitor` (observed once per
        telemetry window) and/or a `--status_json` path that gets an atomic
        live snapshot at the same cadence."""
        self._slo = monitor
        self._status_path = status_path

    def attach_capture(self, trigger) -> None:
        """Wire an `observability.capture.TraceTrigger`: poll() becomes its
        step clock, so an alarm-requested profiler capture starts/stops on
        the engine thread at poll boundaries (the discipline the trigger
        requires)."""
        self._capture = trigger

    def phase_state(self) -> Dict[str, Any]:
        """Live request-phase snapshot for the heartbeat hang dump: which
        poll phase the engine died in, and every in-flight request's
        progress."""
        return {
            "iter": self._iter,
            "phase": self._phase,
            "queue_depth": len(self.queue),
            "free_lanes": len(self._free_lanes),
            "inflight": [
                {"id": r.id, "codes_done": r.codes_done, "lanes": r.lanes,
                 "phases": {k: round(v, 3) for k, v in r.phases.items()}}
                for r in self._inflight
            ],
        }

    def _finish_record(self, req: Request, outcome: str, **extra) -> None:
        """The request's single terminal `kind:"request"` record.  Terminal
        outcomes acknowledge the journal entry (first ack wins — a hedged
        copy or a replay racing a pre-crash completion is tagged duplicate
        and never double-acknowledged)."""
        req.outcome = outcome
        if (self.journal is not None
                and outcome in ("completed", "shed", "poisoned",
                                "requeue_exhausted")):
            if not self.journal.ack(req, outcome):
                extra.setdefault("duplicate", True)
        tele = telemetry.active()
        if tele is None:
            return
        if self.replica_id is not None:
            extra.setdefault("replica", self.replica_id)
        if req.degrade_rung:
            extra.setdefault("degrade_rung", req.degrade_rung)
        if req.hedged:
            extra.setdefault("hedged", True)
        if req.replayed:
            extra.setdefault("replayed", True)
        if req.spec_rounds > 0:
            extra.setdefault("accepted_tokens_per_step",
                             round(req.accepted_tokens_per_step, 4))
        # journey stitching fields: the content uid links this hop's record
        # to every other hop of the same logical request; arrival_ts anchors
        # the hop on the wall clock so trace_report can lay phases out
        # (rounded identically to the admit span so the two join exactly)
        extra.setdefault("journey", tracing.journey_uid(req))
        extra.setdefault("arrival_ts", round(tracing.wall(req.arrival_t), 6))
        tele.spans.write_event(
            "request", request_id=req.id, outcome=outcome,
            guided=req.guided, synthetic=req.synthetic,
            ttft_s=req.ttft_s, latency_s=req.latency_s,
            decode_tokens=req.codes_done, deferrals=req.deferrals,
            phases={k: round(v, 6) for k, v in req.phases.items()},
            **extra,
        )

    def close(self) -> None:
        """Account for work the engine will not finish: still-queued and
        in-flight requests get a terminal outcome "deferred" record (a
        multi-replica router resubmits those elsewhere), and a final
        telemetry window is flushed so short runs still report."""
        now = time.monotonic()
        while True:
            req = self.queue.peek()
            if req is None:
                break
            self.queue.pop()
            req.phases["queue_wait"] = now - req.arrival_t
            self._finish_record(req, "deferred")
        for req in self._inflight:
            if req.admitted_t is not None:
                req.phases["decode"] = now - req.admitted_t
            self._finish_record(req, "deferred")
        self._inflight = []
        self._window_event()

    def _poll_flood(self) -> None:
        n = resilience.take_flood_fault(self._iter)
        if n:
            print(f"[chaos] flood: injecting {n} synthetic requests", flush=True)
            for _ in range(n):
                text = self._flood_rng.randint(
                    1, self.cfg.num_text_tokens, size=(self.cfg.text_seq_len,)
                )
                try:
                    self.submit(text, synthetic=True)
                    obs_metrics.counter("serving/flood_injected").inc()
                except AdmissionRefused:
                    pass  # refusal IS the drill's success mode (counted in submit)

    def _admit_ready(self) -> tuple:
        """Admit what the queue's head and the pool allow.  Returns (the
        seconds under `serve/admit` spans, the requests admitted)."""
        admit_s, admitted = 0.0, 0
        while True:
            req = self.queue.peek()
            if req is None:
                return admit_s, admitted
            reason, kind = self.admission.may_admit_ex(
                req, free_lanes=len(self._free_lanes),
                in_flight=len(self._inflight))
            if reason is not None:
                req.deferrals += 1  # head-of-queue waited this iteration
                self.admission.note_deferral(reason)
                rec = self.pool.recorder
                if rec is not None:
                    # the deferral decision, with the free-list state it was
                    # made against — what lets pool_report re-derive slots/
                    # pool deferrals exactly (headroom ones are unmodeled)
                    rec.record(
                        "defer", req=req.id, defer_kind=kind,
                        lanes_needed=req.lanes_needed,
                        blocks_needed=(req.lanes_needed
                                       * self.pool.blocks_per_seq),
                        free=self.pool.free_blocks,
                        free_lanes=len(self._free_lanes),
                        replica=self.replica_id)
                return admit_s, admitted
            admit_s += self._do_admit(self.queue.pop())
            admitted += 1
            self.admission.note_flow()

    def _do_admit(self, req: Request) -> float:
        """One admission, as the `serve/admit` span and its four children:
        alloc (block tables and lane indices, on the host), dispatch (the
        admit / ingest jit, which also splits the request's key and writes
        every lane field the decode step reads), lane_meta (the host's
        in-flight bookkeeping) and ttft_sync (the first token must exist).
        `Request.phases` and the poll's admit time (the span's seconds,
        returned) are fed from the spans' own readings."""
        req.phases["queue_wait"] = time.monotonic() - req.arrival_t
        ids = {"iter": self._iter, "req": req.id}
        with telemetry.timed_span("serve/admit", lanes=req.lanes_needed,
                                  **ids) as t_admit:
            with telemetry.timed_span("serve/admit.alloc", **ids) as t_alloc:
                lanes = [self._free_lanes.pop(0) for _ in range(req.lanes_needed)]
                req.lanes = lanes
                # prompt-prefix content hash: shared by the redundancy profiler
                # (_note_prefix) and the flight recorder's alloc context — the
                # key pool_report's prefix-sharing forecast refcounts on
                phash = hashlib.sha1(req.text.tobytes()).hexdigest()[:12]
                rec = self.pool.recorder
                if rec is not None:
                    rec.ctx = {
                        "req": req.id, "journey": tracing.journey_uid(req),
                        "lanes": req.lanes_needed, "guided": req.guided,
                        "prefix_hash": phash, "replica": self.replica_id,
                    }
                tables = np.stack([
                    self.pool.alloc_table(owner=(req.id << 1) | i)
                    for i in range(len(lanes))
                ])
                if rec is not None:
                    rec.ctx = None
                lane_idx = np.asarray(lanes, np.int32)  # host-sync-ok: host lane ids
                # the request's knobs go to the program that arms its lanes
                # as host scalars; its key is split there, on the device
                temperature = np.float32(req.temperature)
                cand_cap = np.bool_(req.degrade_rung >= 2)
            req.phases["admission"] = t_alloc.s
            with telemetry.timed_span("serve/admit.dispatch", **ids) as t_dispatch:
                if self.prefill_backend is not None:
                    # disaggregated: the prefill worker ran _prefill_sample_impl
                    # on ITS mesh (deriving the same k0 from req.key) and hands
                    # the KV prefix + first code over; this side scatters it
                    # into the pool and arms the lanes — the ingest jit is the
                    # identical graph the fused admit traces, so the two paths
                    # stay bit-identical
                    handoff = self.prefill_backend.prefill(req)
                    ingest_fn = self._ingest_fn_for(len(lanes))
                    with self._suspend_compiles():
                        self._state = ingest_fn(
                            self._state, handoff["layers"], handoff["code"],
                            tables, lane_idx, req.key, temperature,
                            np.float32(req.cond_scale), cand_cap,
                        )
                else:
                    admit_fn = self._admit_fn_for(req.cond_scale, len(lanes))
                    with self._suspend_compiles():
                        self._state = admit_fn(
                            self.params, self._state, req.text[None],
                            req.key, temperature, tables, lane_idx, cand_cap,
                        )
            with telemetry.timed_span("serve/admit.lane_meta", **ids) as t_meta:
                self._inflight.append(req)
                req.codes_done = 1  # the first image token came out of prefill
            with telemetry.timed_span("serve/admit.ttft_sync", **ids) as t_sync:
                # TTFT: the first token must actually exist
                jax.block_until_ready(self._state["prev_code"])  # host-sync-ok: TTFT measurement point
            now = time.monotonic()
            req.admitted_t = now
            req.ttft_s = now - req.arrival_t
            req.phases["prefill"] = t_dispatch.s + t_meta.s + t_sync.s
            obs_metrics.counter("serving/admitted").inc()
            obs_metrics.histogram("serving/ttft_s").observe(req.ttft_s)
            # prefix profiling + the hop's admit span: all inputs are host
            # values this method already holds — emitted AT the existing TTFT
            # sync, adding none
            prefix_hash, prefix_repeat = self._note_prefix(req, phash)
            if tracing.enabled():
                tracing.emit(
                    "admit", tracing.journey_uid(req), hop=req.id,
                    replica=self.replica_id,
                    arrival_ts=round(tracing.wall(req.arrival_t), 6),
                    queue_wait_s=round(req.phases["queue_wait"], 6),
                    admission_s=round(req.phases["admission"], 6),
                    prefill_s=round(req.phases["prefill"], 6),
                    ttft_s=round(req.ttft_s, 6), lanes=len(lanes),
                    mode=("handoff" if self.prefill_backend is not None
                          else "fused"),
                    prefix_hash=prefix_hash, prefix_repeat=prefix_repeat,
                )
        return t_admit.s

    def _note_prefix(self, req: Request, h: str) -> tuple:
        """Prefix-redundancy accounting for one admission: price the
        per-lane prefix KV bytes for the already-hashed prompt `h` (the
        admit path computes it once, shared with the flight recorder) and
        attribute duplicates to the null lane (text-independent by
        construction) and to repeated prompts.  Returns
        (prefix_hash, seen_before)."""
        per_lane = self.pool.prefix_bytes(self.n_pre)
        self._prefix_admissions += 1
        self._prefix_total_bytes += per_lane * req.lanes_needed
        if req.guided:
            self._prefix_null_bytes += per_lane
        repeat = h in self._prefix_seen
        if repeat:
            self._prefix_repeats += 1
            self._prefix_repeat_bytes += per_lane
        self._prefix_seen[h] = self._prefix_seen.get(h, 0) + 1
        obs_metrics.gauge("prefix/duplicate_bytes").set(
            self._prefix_null_bytes + self._prefix_repeat_bytes)
        obs_metrics.gauge("prefix/repeat_hit_frac").set(
            self._prefix_repeats / self._prefix_admissions)
        return h, repeat

    def prefix_redundancy(self) -> Dict[str, Any]:
        """The profiler's summary — how many prefill KV bytes a prefix cache
        would have saved.  `null_lane_bytes` alone is what sharing the
        (identical) null-conditioning prefix across guided lanes saves;
        `repeat_prefill_bytes` adds exact-repeat prompts (hedges, requeues,
        replays, repeated text).  The serving bench row publishes this."""
        dup = self._prefix_null_bytes + self._prefix_repeat_bytes
        total = self._prefix_total_bytes
        return {
            "admissions": self._prefix_admissions,
            "unique_prefixes": len(self._prefix_seen),
            "repeat_hits": self._prefix_repeats,
            "repeat_hit_frac": (self._prefix_repeats / self._prefix_admissions
                                if self._prefix_admissions else 0.0),
            "null_lane_bytes": self._prefix_null_bytes,
            "repeat_prefill_bytes": self._prefix_repeat_bytes,
            "duplicate_bytes": dup,
            "prefill_bytes": total,
            "duplicate_frac": dup / total if total else 0.0,
        }

    def _decode_once(self) -> int:
        """One decode dispatch.  Returns the lane-tokens it decoded."""
        if self._spec is not None and not (
                self.degrade is not None and self.degrade.suppress_spec):
            return self._spec_decode_once()
        with (self._suspend_compiles() if not self._warm_decode
              else contextlib.nullcontext()):
            self._state = self._decode_fn(self.params, self._state)
        self._warm_decode = True
        obs_metrics.counter("serving/decode_steps").inc()
        obs_metrics.counter("serving/decode_lane_tokens").inc(len(self._inflight))
        for req in self._inflight:
            req.codes_done += 1
            if (self.journal is not None
                    and req.codes_done % self.journal.progress_every == 0):
                # host-held counter only — journaling progress adds no sync
                self.journal.progress(req)
        return len(self._inflight)

    def _spec_decode_once(self) -> int:
        """One speculative round: draft k tokens through the shallow prefix,
        verify them all in one full-model dispatch, advance each lane by its
        accepted length.  The per-round host pull of the accepted-length
        vector is the price of per-request progress bookkeeping (eviction,
        journal progress, drain exactness) — the honest overhead the README
        documents; the sequential path keeps its zero-extra-sync property."""
        k = self._spec[0]
        with (self._suspend_compiles() if not self._warm_spec
              else contextlib.nullcontext()):
            with telemetry.timed_span("serve/spec.draft",
                                      iter=self._iter) as t_draft:
                draft = self._spec_draft_fn(self.params, self._state)
                # draft/verify wall attribution needs the boundary to exist
                jax.block_until_ready(draft["drafts"])  # host-sync-ok: spec/draft_time_frac attribution point
            with telemetry.timed_span("serve/spec.verify",
                                      iter=self._iter) as t_verify:
                new_state, acc = self._spec_verify_fn(
                    self.params, self._state, draft)
                self._state = dict(new_state, head=self._state["head"])
                acc_np = np.asarray(acc)  # host-sync-ok: accepted lengths drive codes_done/eviction
        self._warm_spec = True
        accepted = 0
        lane_tokens = 0
        round_hops: Dict[str, int] = {}
        for req in self._inflight:
            adv = int(acc_np[req.lanes[0]])  # host-sync-ok: acceptance bookkeeping on the already-pulled np vector
            round_hops[str(req.id)] = adv
            old_done = req.codes_done
            req.codes_done += adv
            req.spec_rounds += 1
            accepted += adv
            lane_tokens += adv * len(req.lanes)
            # host free-list commit point: the reservation keeps its blocks,
            # the ledger's live-token count snaps back to the verified prefix
            for i in range(len(req.lanes)):
                self.pool.truncate_slot((req.id << 1) | i,
                                        self.n_pre + req.codes_done - 1)
            if (self.journal is not None and adv
                    and (old_done // self.journal.progress_every
                         != req.codes_done // self.journal.progress_every)):
                # same cadence as the sequential path's % check, generalized
                # to multi-token advances: fire on every boundary crossing
                self.journal.progress(req)
        obs_metrics.counter("serving/decode_steps").inc()
        obs_metrics.counter("serving/decode_lane_tokens").inc(lane_tokens)
        obs_metrics.counter("serving/spec_rounds").inc()
        obs_metrics.counter("serving/spec_accepted_tokens").inc(accepted)
        obs_metrics.counter("serving/spec_rejected_tokens").inc(
            max((k + 1) * len(self._inflight) - accepted, 0))
        # request-rounds, so the window gauge is mean accepted/step/request
        self._win_spec_rounds += len(self._inflight)
        self._win_spec_accepted += accepted
        self._win_spec_draft_s += t_draft.s
        self._win_spec_total_s += t_draft.s + t_verify.s
        if tracing.enabled():
            # one event per round, not per request: draft/verify walls are
            # the two spans' readings (each ends in an existing waived sync),
            # and `hops` maps engine request id -> accepted tokens (joined to
            # journeys through each hop's admit span)
            tracing.emit(
                "spec_round", None, replica=self.replica_id,
                draft_s=round(t_draft.s, 6), verify_s=round(t_verify.s, 6),
                hops=round_hops,
            )
        return lane_tokens

    def _reset_lanes(self, lanes: List[int]) -> None:
        """Free `lanes` on the device: one dispatch of `serve_lane_reset`,
        whose mask spans every slot, so one lane, a guided pair and a whole
        drain share its one compile."""
        mask = np.zeros((self.ecfg.num_slots,), bool)
        mask[lanes] = True
        with (self._suspend_compiles() if not self._warm_reset
              else contextlib.nullcontext()):
            self._state = self._lane_reset_fn(self._state, mask)
        self._warm_reset = True
        obs_metrics.counter("serving/lane_reset_calls").inc()
        obs_metrics.counter("serving/lane_reset_lanes").inc(len(lanes))

    def _evict_finished(self) -> tuple:
        """Evict the requests whose last code is out.  Returns (the healthy
        completions, the requests evicted, the seconds of `serve/evict` spent
        waiting for the device, its other seconds)."""
        done = [r for r in self._inflight if r.codes_done >= self.n_gen]
        if not done:
            return done, 0, 0.0, 0.0
        with telemetry.timed_span("serve/evict", iter=self._iter,
                                  n=len(done)) as t_evict:
            healthy, blocked_s = self._evict(done)
        # evict = host bookkeeping only; the device waits inside the span
        # (flag sync, codes pulls, VAE decodes) are the "block" share
        return healthy, len(done), blocked_s, t_evict.s - blocked_s

    def _evict(self, done: List[Request]) -> tuple:
        """The eviction under its `serve/evict` span.  Returns (the healthy
        completions, the seconds spent waiting for the device)."""
        it = self._iter
        t_start = time.monotonic()
        self._inflight = [r for r in self._inflight if r.codes_done < self.n_gen]
        # the per-lane nonfinite flags, pulled at the EXISTING eviction sync
        # (the jit accumulated them; the steady-state decode loop never did):
        # the wait drains every decode step the host has queued ahead
        with telemetry.timed_span("serve/evict.flag_sync", iter=it) as t_flag:
            poisoned_flags = np.asarray(self._state["poisoned"])  # host-sync-ok: flag pull at the eviction sync
        blocked_s = t_flag.s
        retry: List[Request] = []
        quarantine: List[Request] = []
        healthy: List[Request] = []
        for req in done:
            if bool(poisoned_flags[req.lanes].any()):
                if req.poison_retries < self.ecfg.poison_max_retries:
                    retry.append(req)
                else:
                    quarantine.append(req)
            else:
                healthy.append(req)
        all_lanes: List[int] = []
        for req in done:
            req.phases["decode"] = t_start - req.admitted_t
            req.phases["evict_sync"] = t_flag.s
            if req in healthy:
                with telemetry.timed_span("serve/evict.codes_pull", iter=it,
                                          req=req.id) as t_pull:
                    req.codes = np.asarray(self._state["codes"][req.lanes[0]])  # host-sync-ok: pulling the finished slot's codes
                req.phases["codes_pull"] = t_pull.s
                blocked_s += t_pull.s
            for i in range(len(req.lanes)):
                # same written-KV arithmetic as drain(): offsets stop at
                # n_pre + codes_done - 1 (the final code is never fed back)
                self.pool.free_table(
                    (req.id << 1) | i,
                    written_tokens=self.n_pre + max(req.codes_done - 1, 0))
            all_lanes.extend(req.lanes)
            self._free_lanes.extend(req.lanes)
            req.latency_s = time.monotonic() - req.arrival_t
        with telemetry.span("serve/evict.lane_reset", iter=it):
            self._reset_lanes(all_lanes)
        for req in retry:
            # nonfinite lane: evict, free, and re-decode from scratch (same
            # key, same RNG stream) — a transient NaN won't recur; a truly
            # poisonous request burns its K retries and quarantines.  Not a
            # terminal outcome, so no record is written for the retry hop.
            req.poison_retries += 1
            req.codes_done = 0
            req.lanes = None
            req.admitted_t = None
            req.codes = None
            self.queue.requeue(req)
            obs_metrics.counter("serving/poison_retries").inc()
            # retry hops leave no terminal record; the edge event is what
            # lets trace_report attribute the burned attempt inside the
            # journey (the final record's evict residual absorbs its time)
            tracing.emit("poison_retry", tracing.journey_uid(req),
                         hop=req.id, replica=self.replica_id,
                         retry=req.poison_retries)
        for req in quarantine:
            obs_metrics.counter("serving/quarantined").inc()
            # same phases-sum-to-latency contract as completed requests:
            # the residual (earlier retry hops' decode time included) is
            # evict, so a poisoned journey's critical path still closes
            req.phases["evict"] = max(
                req.latency_s - sum(req.phases.values()), 0.0)
            self._finish_record(req, "poisoned",
                                reason="nonfinite decode logits",
                                retries=req.poison_retries)
        for req in healthy:
            if self._vae_decode is not None:
                with telemetry.timed_span("serve/evict.vae_decode", iter=it,
                                          req=req.id) as t_vae:
                    images = self._vae_decode(req.codes[None])
                    jax.block_until_ready(images)  # host-sync-ok: completion boundary
                obs_metrics.histogram("gen/vae_decode_s").observe(t_vae.s)
                with telemetry.span("serve/evict.pixels_pull", iter=it,
                                    req=req.id):
                    req.images = np.asarray(images)  # host-sync-ok: delivering the result
                req.phases["vae_decode"] = t_vae.s
                blocked_s += t_vae.s
                req.latency_s = time.monotonic() - req.arrival_t
            # phases must sum to the latency (reports and the flood drill
            # rely on it): the residual — lane reset, pixel pull, table
            # frees, waiting behind batch peers' eviction/VAE work — is
            # evict time
            req.phases["evict"] = max(
                req.latency_s - sum(req.phases.values()), 0.0)
            obs_metrics.counter("serving/completed").inc()
            obs_metrics.histogram("serving/request_s").observe(req.latency_s)
            self._finish_record(req, "completed")
        return healthy, blocked_s

    def _window_event(self) -> None:
        """Close one telemetry window: emit the serving_window event with the
        poll-phase split and the goodput figure and flush the window's spans
        (when telemetry is on), run the SLO monitor, and refresh the
        status_json scrape file.  The window is the rows `self.polls` took
        since the last call: its seconds run from its first poll's entry to
        its last poll's end."""
        rows = self.polls.rows(since=self._win_row)
        self._win_row = self.polls.total
        dur = rows["dur_s"]
        elapsed = (max(rows["t0_s"][-1] + dur[-1] - rows["t0_s"][0], 1e-9)
                   if len(dur) else 1e-9)
        steps = int(np.count_nonzero(rows["dispatch_s"]))
        lane_tokens = int(rows["lanes"].sum())
        ideal = steps * self.ecfg.num_slots
        # goodput: lane-tokens actually decoded vs every slot busy every step
        goodput = lane_tokens / ideal if ideal else None
        phases = {p: round(float(rows[f"{p}_s"].sum()), 6) for p in POLL_PHASES}
        self._worst_poll = None
        if len(dur):
            i = int(dur.argmax())
            row = {c: v[i].tolist() for c, v in rows.items()}
            parts = {p: row[f"{p}_s"] for p in POLL_PHASES}
            parts["other"] = row["dur_s"] - sum(parts.values())
            phase = max(parts, key=parts.get)
            self._worst_poll = {
                "iter": round(row["iter"]), "dur_s": round(row["dur_s"], 6),
                "phase": phase, "phase_s": round(parts[phase], 6)}
        spec_accept = None
        spec_draft_frac = None
        if self._win_spec_rounds:
            spec_accept = self._win_spec_accepted / self._win_spec_rounds
            obs_metrics.gauge("spec/accepted_tokens_per_step").set(spec_accept)
            if self._win_spec_total_s > 0:
                spec_draft_frac = self._win_spec_draft_s / self._win_spec_total_s
                obs_metrics.gauge("spec/draft_time_frac").set(spec_draft_frac)
        self._win_spec_rounds = 0
        self._win_spec_accepted = 0
        self._win_spec_draft_s = 0.0
        self._win_spec_total_s = 0.0
        tele = telemetry.active()
        if tele is not None:
            spec_fields = {}
            if spec_accept is not None:
                spec_fields["spec_accepted_tokens_per_step"] = round(
                    spec_accept, 4)
            if spec_draft_frac is not None:
                spec_fields["spec_draft_time_frac"] = round(spec_draft_frac, 4)
            tele.spans.write_event(
                "serving_window", iter=self._iter,
                queue_depth=len(self.queue),
                active_lanes=self.ecfg.num_slots - len(self._free_lanes),
                pool_occupancy_frac=self.pool.occupancy_frac,
                pool_free_blocks=self.pool.free_blocks,
                phase_s=phases, goodput_frac=goodput,
                lane_tokens_per_s=lane_tokens / elapsed,
                decode_steps=steps,
                **spec_fields,
                **self.quantization_state(),
                **self.paged_path_state(),
                **self.recurrent_state_info(),
                **self.decode_head_state(),
            )
        # flight-recorder drain rides the same cadence: pending block-
        # lifecycle events leave the ring as kind:"pool" records, and the
        # live gauges re-publish (all host work on already-recorded dicts)
        prec = self.pool.recorder
        if tele is not None:
            tele.spans.flush()  # the window's serve/ spans
            if prec is not None:
                prec.flush(tele.spans, replica=self.replica_id)
        if self._pool_gauges is not None:
            self._pool_gauges.publish(
                dropped=prec.dropped if prec is not None else 0)
        if self._slo is not None:
            rec = self._slo.observe(self._iter)
            if tele is not None and rec is not None:
                tele.spans.write_event("slo_window", **rec)
        if self._status_path:
            self._write_status()

    def _write_status(self) -> None:
        from dalle_pytorch_tpu.observability.slo import write_status_json

        payload: Dict[str, Any] = self._slo.status() if self._slo else {}
        payload["serving"] = {
            "iter": self._iter,
            "queue_depth": len(self.queue),
            "active_lanes": self.ecfg.num_slots - len(self._free_lanes),
            "inflight": len(self._inflight),
            "pool_occupancy_frac": self.pool.occupancy_frac,
            "pool_free_blocks": self.pool.free_blocks,
            "worst_poll": self._worst_poll,
            **self.paged_path_state(),
            **self.recurrent_state_info(),
            **self.decode_head_state(),
        }
        payload["pool"] = self.pool_observability()
        payload["quantization"] = self.quantization_state()
        write_status_json(self._status_path, payload)

    def paged_path_state(self) -> Dict[str, Optional[int]]:
        """How many attention layers of the decode program took the Pallas
        paged kernel and how many the XLA gather (None until it is traced):
        the registry's `serving/paged_attn_*_layers`, for status_json and
        the serving_window event."""
        paths = self._paged_paths or {}
        return {"paged_attn_kernel_layers": paths.get("kernel"),
                "paged_attn_fallback_layers": paths.get("fallback")}

    def recurrent_state_info(self) -> Dict[str, Optional[int]]:
        """How many layers of the decode program advance a per-slot recurrent
        state where the others read blocks, how many of those took the
        one-token rule's kernel (fewer: the XLA form ran, two reads and a write
        of the state; both None until the program is traced), and the bytes of
        that state and its taps: the registry's `serving/gdn_state_layers`,
        `serving/gdn_step_kernel_layers` and `serving/state_bytes`, carried
        beside the path counts."""
        paths = self._paged_paths
        return {"gdn_state_layers": None if paths is None else paths.get("state", 0),
                "gdn_step_kernel_layers": None if paths is None else paths.get("state_kernel", 0),
                "state_bytes": self.pool.state_bytes()}

    def recurrent_snapshot(self) -> List[Dict[str, Any]]:
        """What every in-flight request's first lane holds of a recurrent
        state, after every step dispatched so far (one sync): the request, the
        `positions` of [<bos>, text, codes] its state has taken in, the `codes`
        sampled so far (positions - n_pre + 1 of them: the last one is not fed
        yet) and `states`, one (heads, dk, dv) float32 array a `gated_delta`
        layer, in layer order.  Empty for a trunk that keeps none."""
        if self.tcfg.depth == self.tcfg.kv_layers or not self._inflight:
            return []
        lanes = jnp.asarray([req.lanes[0] for req in self._inflight], jnp.int32)
        st = self._state
        offsets, codes, states = jax.device_get((  # host-sync-ok: a snapshot is a sync
            st["offsets"][lanes], st["codes"][lanes],
            [layer["state"][lanes] for layer in st["pool"]["layers"] if "state" in layer]))
        return [{"request": req, "positions": at, "codes": codes[i, :at - self.n_pre + 1],
                 "states": [s[i] for s in states]}
                for i, (req, at) in enumerate(zip(self._inflight, offsets.tolist()))]

    def decode_head_state(self) -> Dict[str, Optional[int]]:
        """Whether the decode program was traced on the table laid out at
        build (None until it is traced) and that table's bytes: the
        registry's `serving/decode_head_prepared` and
        `serving/decode_head_bytes`, carried like the path counts."""
        return {"decode_head_prepared": None if self._paged_paths is None else 1,
                "decode_head_bytes": self._head_bytes}

    def pool_observability(self) -> Dict[str, Any]:
        """Live pool section for status_json and the serve report: the
        free-list state every run has, plus the flight-recorder gauge
        summary (block lifetimes, reserved-unused waste, footprint
        percentiles, overcommit forecast) when the recorder is on."""
        out: Dict[str, Any] = {
            "num_blocks": self.pool.num_blocks,
            "block_size": self.pool.block_size,
            "occupancy_frac": round(self.pool.occupancy_frac, 4),
            "free_blocks": self.pool.free_blocks,
            "high_water": self.pool.high_water,
            "fragmentation_frac": round(self.pool.fragmentation_frac, 4),
        }
        if self._pool_gauges is not None:
            out.update(self._pool_gauges.summary())
        rec = self.pool.recorder
        if rec is not None:
            out["recorder_dropped"] = rec.dropped
        return out

    def quantization_state(self) -> Dict[str, Any]:
        """Active weight/KV storage dtypes + the analytic per-step dequant
        overhead — what makes a quantized run distinguishable from a bf16
        run in status_json, serving_window events, and serving_report."""
        from dalle_pytorch_tpu import quantization as quant_mod

        wk = quant_mod.weight_quant_kind(self.params)
        kv = self.pool.quant
        over = quant_mod.dequant_overhead_flops(
            self.tcfg, kv, wk, self.ecfg.num_slots,
            emb_rows=self.cfg.total_tokens + self.cfg.num_image_tokens)
        return {
            "weight_dtype": wk or str(jnp.dtype(
                quant_mod.weight_dtype(self.params)).name),
            "kv_dtype": kv or str(jnp.dtype(self.pool.dtype).name),
            "dequant_flops_per_step": over["dequant_flops_per_step"],
            "dequant_frac_of_step": round(over["dequant_frac_of_step"], 6),
        }

    def memory_ledger(self, capacity_bytes: Optional[float] = None):
        """The serving path's HBM ledger: params + the paged pool + the
        transient per-layer gather working set (memory.sampling_memory_ledger
        with the paged rows)."""
        from dalle_pytorch_tpu.observability import memory as memory_mod
        from dalle_pytorch_tpu.serving.kv_pool import paged_ledger_entry

        return memory_mod.sampling_memory_ledger(
            self.cfg, self.ecfg.num_slots, self.params,
            capacity_bytes=capacity_bytes,
            paged_pool=paged_ledger_entry(
                self.cfg, self.pool.num_blocks + 1, self.ecfg.block_size,
                self.ecfg.num_slots, kv_quant=self.pool.quant,
            ),
        )


def _blocks_per_seq(tcfg, block_size: int) -> int:
    from dalle_pytorch_tpu.models.transformer import paged_blocks_per_seq

    return paged_blocks_per_seq(tcfg, block_size)


def request_keys(key, n_steps: int):
    """A request's RNG stream from its (2,) uint32 key, split exactly as
    `_decode_phase` splits a batch-1 call's: (the (n_steps, 2) step keys,
    the first token's key k0)."""
    key, k0 = jax.random.split(jnp.asarray(key, jnp.uint32))
    return jax.random.split(key, n_steps), k0


def prefill_sample(params, cfg, filter_thres: float, text, k0, temperature,
                   cond_scale: float):
    """Prefill + first-token sample — the half of admission that only needs
    params and the prompt.  Module-level so a disaggregated prefill worker
    (serving/fleet.PrefillWorker) traces the IDENTICAL graph on its own
    mesh; the returned (cache_layers, code) is the prefill→decode handoff
    payload the decode replica's ingest jit scatters into its pool."""
    guided = cond_scale != 1.0
    cache, last_logits = sampling_mod._prefill_phase(
        params, cfg, text, None, 0, cond_scale
    )
    with jax.named_scope("sample"):
        lg = (sampling_mod._cfg_combine(last_logits, cond_scale)
              if guided else last_logits)
        filtered = top_k_filter(lg, thres=filter_thres)
        # cast to the logits dtype: the fused path's python-float temperature
        # is WEAKLY typed (bf16 logits stay bf16 through the division); a
        # strong f32 scalar would promote and break parity
        tok = gumbel_sample(k0, filtered,
                            temperature=temperature.astype(filtered.dtype))
        code = jnp.clip(
            tok - cfg.num_text_tokens_padded, 0, cfg.num_image_tokens - 1
        ).astype(jnp.int32)  # (1,)
    return cache["layers"], code
