"""Serving fleet: N engine replicas behind a router, with optional
prefill/decode disaggregation and serve-through-preemption.

The PR 7 engine is one process on one mesh; this module multiplies it:

* **Replicas** — `ServingFleet` builds N `GenerationEngine`s (each tagging
  its request records with its replica id) and fronts them with
  `serving/router.Router`, which places admissions on live load (queue
  depth, free slots, free pool blocks, HBM headroom) and turns
  every-replica-refused into a counted router-level shed.  The fleet
  quacks like one engine (submit/poll/busy/run_until_idle), so
  tools/loadgen.py and cli/serve.py drive it unchanged.
* **Disaggregation** — `PrefillWorker` runs the prefill half of admission
  (`engine.prefill_sample`, the identical traced graph) on its OWN params —
  optionally placed on a different mesh through the PR 6 registry
  (`parallel/reshard.reshard_tree`) — and hands the KV prefix + first code
  to the decode replica, whose ingest jit scatters it into the paged pool
  via `write_prefill_to_pool`.  The handoff is priced as a comms-ledger row
  (`observability.comms.prefill_handoff_row`) and counted in
  `serving/handoff_bytes`; decode output is bit-identical to the fused
  single-engine path (tests/test_fleet_serving.py proves it).
* **Preemption** — `kill_replica(i)` (or an armed `kill-replica@ITER:IDX`
  fault, polled like the engine polls flood faults) drains the dead
  replica's per-slot state and the router requeues it onto survivors;
  per-request RNG streams make the re-decode exact.  With
  `reshard_on_kill`, survivors re-place their weights through
  `parallel/reshard.py` — the serving counterpart of elastic training
  resume.

Host work here is deliberate and identical in kind to the engine's own
(admission bookkeeping, handoff dispatch); the steady-state decode loops
stay async inside each replica.
"""
from __future__ import annotations

import dataclasses
import os
import signal
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu.models.transformer import refuse_hybrid
from dalle_pytorch_tpu.observability import comms as comms_mod
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.observability import tracing
from dalle_pytorch_tpu.serving.engine import (
    EngineConfig,
    GenerationEngine,
    prefill_sample,
)
from dalle_pytorch_tpu.serving.router import Router
from dalle_pytorch_tpu.serving.scheduler import Request
from dalle_pytorch_tpu.training import resilience


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Fleet knobs on top of one shared per-replica EngineConfig.

    `kill_at_iter`/`kill_replica_idx` are the in-process chaos hook bench
    and tests use directly; live runs arm the same drill with
    `--inject_fault kill-replica@ITER:IDX` instead."""

    replicas: int = 2
    disaggregate: bool = False
    engine: EngineConfig = EngineConfig()
    reshard_on_kill: bool = False
    kill_at_iter: Optional[int] = None
    kill_replica_idx: int = 0
    # durability knobs: how long a stall-replica fault wedges its victim,
    # and the router's circuit-breaker / hedging / bounded-requeue budgets
    stall_wedge_s: float = 3.0
    stall_after_s: float = 1.0
    probe_after_s: float = 1.0
    hedge_frac: float = 0.5
    requeue_budget_s: float = 30.0


class PrefillWorker:
    """The prefill half of admission as its own pool: runs
    `engine.prefill_sample` — the exact graph the fused admit traces — on
    its own params (optionally on its own mesh via `parallel/reshard.py`'s
    registry placement) and returns the handoff a decode replica ingests.

    One worker serves every replica: prefill is stateless (params + prompt
    in, KV prefix + first code out), so the pool "size" is just how many
    workers a deployment constructs."""

    def __init__(self, params: dict, cfg, filter_thres: float = 0.9,
                 mesh=None, quantize_kv: Optional[str] = None):
        from dalle_pytorch_tpu.quantization import weight_dtype

        if mesh is not None:
            from dalle_pytorch_tpu.parallel.reshard import reshard_tree

            params = reshard_tree(params, mesh)
        self.params = params
        self.cfg = cfg
        self.tcfg = cfg.transformer_config()
        # the handoff's wire format and its comms row price K/V columns alone
        refuse_hybrid(self.tcfg, "PrefillWorker (disaggregated prefill)", recurrent_state=False)
        self.filter_thres = filter_thres
        self.quantize_kv = None if quantize_kv == "none" else quantize_kv
        self.n_pre = cfg.text_seq_len + 1
        self.itemsize = np.dtype(weight_dtype(params)).itemsize
        self._fns: Dict[float, Any] = {}

    def _fn_for(self, cond_scale: float):
        key = float(cond_scale)  # host-sync-ok: python jit-cache key
        fn = self._fns.get(key)
        if fn is None:
            cfg, thres = self.cfg, self.filter_thres

            kv_quant = self.quantize_kv

            def serve_prefill(params, text, k0, temperature):
                layers, code = prefill_sample(params, cfg, thres, text, k0,
                                              temperature, cond_scale)
                if kv_quant:
                    # compress the handoff ON the prefill mesh: per-token
                    # scales make quantize-then-ship equal ship-then-quantize,
                    # so the decode replica's pool is bit-identical either way
                    from dalle_pytorch_tpu.quantization import (
                        quantize_cache_layers,
                    )

                    layers = quantize_cache_layers(layers)
                return layers, code

            fn = jax.jit(serve_prefill)
            self._fns[key] = fn
        return fn

    def handoff_row(self, lanes: int = 1) -> Dict[str, Any]:
        """The comms-ledger row pricing one admission's handoff."""
        ring = 0.0
        if self.tcfg.shift_tokens:
            # both token-shift ring tails (attn + ff), per layer:
            # (lanes, fmap, 2, dim//4) each — see transformer.init_cache
            ring = (2.0 * self.tcfg.depth * lanes * self.tcfg.image_fmap_size
                    * 2 * (self.tcfg.dim // 4) * self.itemsize)
        return comms_mod.prefill_handoff_row(
            self.tcfg, self.n_pre, lanes, self.itemsize, ring_bytes=ring,
            kv_quant=self.quantize_kv)

    def prefill(self, req: Request) -> Dict[str, Any]:
        """Run prefill + first-token sample for `req` and return the handoff
        package.  The RNG derivation mirrors the engine's `_do_admit` (and
        so `sample_image_codes`) exactly: k0 is the first split of the
        request key, which is what keeps disaggregated output bit-identical."""
        _, k0 = jax.random.split(jnp.asarray(req.key, jnp.uint32))
        fn = self._fn_for(req.cond_scale)
        layers, code = fn(
            self.params, jnp.asarray(req.text[None], jnp.int32), k0,
            jnp.asarray(req.temperature, jnp.float32),
        )
        lanes = 2 if req.cond_scale != 1.0 else 1
        row = self.handoff_row(lanes)
        obs_metrics.counter("serving/handoff_requests").inc()
        obs_metrics.counter("serving/handoff_bytes").inc(
            row["bytes_per_step"])
        # handoff edge: marks the hop's prefill as worker-produced and
        # prices the shipped bytes (the dispatch is async — no sync here;
        # the wall cost lands in the hop's prefill phase at the TTFT sync)
        tracing.emit("handoff", tracing.journey_uid(req), hop=req.id,
                     replica=req.replica, lanes=lanes,
                     bytes=row["bytes_per_step"])
        return {"layers": layers, "code": code, "lanes": lanes,
                "comms_row": row}


class ServingFleet:
    """N replicas + router with the single-engine serving surface."""

    def __init__(self, params: dict, cfg, vae_params: Optional[dict] = None,
                 vae_cfg: Any = None, fleet_cfg: FleetConfig = FleetConfig(),
                 usage_fn=None, on_alarm=None):
        assert fleet_cfg.replicas >= 1
        self.cfg = cfg
        self.fcfg = fleet_cfg
        self.engines: List[GenerationEngine] = [
            GenerationEngine(params, cfg, vae_params, vae_cfg,
                             engine_cfg=fleet_cfg.engine, usage_fn=usage_fn)
            for _ in range(fleet_cfg.replicas)
        ]
        self.router = Router(
            self.engines, on_alarm=on_alarm,
            stall_after_s=fleet_cfg.stall_after_s,
            probe_after_s=fleet_cfg.probe_after_s,
            hedge_frac=fleet_cfg.hedge_frac,
            requeue_budget_s=fleet_cfg.requeue_budget_s)
        self.prefill_worker: Optional[PrefillWorker] = None
        if fleet_cfg.disaggregate:
            self.prefill_worker = PrefillWorker(
                params, cfg, filter_thres=fleet_cfg.engine.filter_thres,
                quantize_kv=fleet_cfg.engine.quantize_kv)
            for eng in self.engines:
                eng.prefill_backend = self.prefill_worker
        self._iter = 0
        self._killed: List[int] = []
        self.journal = None
        self._degrade = None

    # ----------------------------------------------------------- durability
    def attach_journal(self, journal) -> None:
        """One shared RequestJournal for the whole fleet: every replica
        journals accepted/progress/ack against the same WAL, and the router
        acks its requeue_exhausted sheds there too."""
        self.journal = journal
        self.router.journal = journal
        for eng in self.engines:
            eng.journal = journal

    def attach_degrade(self, ladder) -> None:
        """One shared DegradeLadder: every replica shapes/screens submits
        with it, but only the FLEET observes pressure (max queue fraction
        across live replicas), so the rung timers see one signal."""
        self._degrade = ladder
        for eng in self.engines:
            eng.degrade = ladder
            eng.degrade_observe = False

    # ------------------------------------------------------ engine surface
    def submit(self, text, key=None, temperature: float = 1.0,
               cond_scale: float = 1.0, synthetic: bool = False,
               deadline_s=None, retries_left=None,
               replayed: bool = False) -> Request:
        return self.router.submit(text, key=key, temperature=temperature,
                                  cond_scale=cond_scale, synthetic=synthetic,
                                  deadline_s=deadline_s,
                                  retries_left=retries_left,
                                  replayed=replayed)

    def submit_when_able(self, text, key=None, temperature: float = 1.0,
                         cond_scale: float = 1.0, deadline_s=None,
                         retries_left=None, replayed: bool = False) -> Request:
        return self.router.submit_when_able(
            text, key=key, temperature=temperature, cond_scale=cond_scale,
            deadline_s=deadline_s, retries_left=retries_left,
            replayed=replayed)

    @property
    def busy(self) -> bool:
        return self.router.busy

    def poll(self) -> List[Request]:
        """One fleet iteration: arm/fire the chaos drills (kill-replica,
        kill-fleet, stall-replica), observe the degrade ladder, poll every
        live replica, refresh the fleet gauges."""
        self._iter += 1
        if resilience.take_kill_fleet_fault(self._iter):
            # the crash-replay drill: die with NO cleanup — no drain, no
            # terminal records, no journal acks.  Only the WAL survives.
            print(f"[chaos] kill-fleet: SIGKILL whole process at fleet "
                  f"iteration {self._iter}", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
        sidx = resilience.take_stall_replica_fault(self._iter)
        if sidx is not None and int(sidx) < len(self.engines):  # host-sync-ok: parsed CLI number
            print(f"[chaos] stall-replica: wedging replica {int(sidx)} for "  # host-sync-ok: parsed CLI number
                  f"{self.fcfg.stall_wedge_s}s at fleet iteration "
                  f"{self._iter}", flush=True)
            self.engines[int(sidx)].wedge(self.fcfg.stall_wedge_s)  # host-sync-ok: parsed CLI number
        idx = resilience.take_kill_replica_fault(self._iter)
        if (idx is None and self.fcfg.kill_at_iter is not None
                and self._iter >= self.fcfg.kill_at_iter
                and not self._killed):
            idx = self.fcfg.kill_replica_idx
        if idx is not None:
            self.kill_replica(int(idx))  # host-sync-ok: parsed CLI number
        if self._degrade is not None:
            live = self.router.alive()
            frac = max((len(r.engine.queue) / max(r.engine.queue.max_depth, 1)
                        for r in live), default=0.0)
            self._degrade.observe(frac, slo=self.engines[0]._slo)
        done = self.router.poll()
        self.router.publish_gauges()
        return done

    def run_until_idle(self, max_iters: Optional[int] = None) -> List[Request]:
        out: List[Request] = []
        iters = 0
        while self.busy:
            out.extend(self.poll())
            iters += 1
            if max_iters is not None and iters >= max_iters:
                break
        return out

    def generate(self, texts, keys=None, temperature: float = 1.0,
                 cond_scale: float = 1.0) -> List[Request]:
        texts = np.asarray(texts)  # host-sync-ok: caller-provided host prompts
        reqs = []
        for i in range(texts.shape[0]):
            k = keys[i] if keys is not None else jax.random.PRNGKey(i)
            reqs.append(self.submit_when_able(
                texts[i], key=k, temperature=temperature,
                cond_scale=cond_scale))
            # blocking submits only poll the CHOSEN replica; keep the whole
            # fleet advancing between submissions
            self.poll()
        self.run_until_idle()
        return reqs

    def close(self) -> None:
        for r in self.router.alive():
            r.engine.close()

    # ---------------------------------------------------------- preemption
    def kill_replica(self, idx: int, reason: str = "killed") -> List[Request]:
        """Simulated replica death: drain + requeue through the router;
        optionally reshard the survivors' weights (the elastic-serving
        counterpart of PR 6's shrink resume)."""
        if len(self.router.alive()) <= 1:
            print(f"[fleet] refusing to kill replica {idx}: it is the last "
                  "one alive", flush=True)
            return []
        print(f"[chaos] kill-replica: draining replica {idx} at fleet "
              f"iteration {self._iter}", flush=True)
        requeued = self.router.mark_lost(idx, reason=reason)
        self._killed.append(idx)
        if self.fcfg.reshard_on_kill:
            self._reshard_survivors()
        return requeued

    def _reshard_survivors(self) -> None:
        """Re-place every survivor's params onto its own (surviving) mesh
        through the partitioning registry — on one device this replicates
        in place; on a real submesh the same call moves the shards."""
        from jax.sharding import Mesh

        from dalle_pytorch_tpu.parallel.reshard import reshard_tree

        t0 = time.monotonic()
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))  # host-sync-ok: device handles, not array data
        for r in self.router.alive():
            r.engine.params = reshard_tree(r.engine.params, mesh)
        if self.prefill_worker is not None:
            self.prefill_worker.params = reshard_tree(
                self.prefill_worker.params, mesh)
        obs_metrics.gauge("fleet_serving/reshard_s").set(
            time.monotonic() - t0)

    # ------------------------------------------------------- observability
    @property
    def pool(self):
        """Replica 0's pool — the CLI report surface; per-replica pools stay
        reachable through `engines[i].pool`."""
        return self.engines[0].pool

    def attach_slo(self, monitor, status_path: Optional[str] = None) -> None:
        self.engines[0].attach_slo(monitor, status_path=status_path)

    def attach_capture(self, trigger) -> None:
        self.engines[0].attach_capture(trigger)

    def phase_state(self) -> Dict[str, Any]:
        return {
            "iter": self._iter,
            "replicas_alive": [r.id for r in self.router.alive()],
            "replicas": {r.id: r.engine.phase_state()
                         for r in self.router.alive()},
        }

    def memory_ledger(self, capacity_bytes: Optional[float] = None):
        return self.engines[0].memory_ledger(capacity_bytes=capacity_bytes)

    def prefix_redundancy(self) -> Dict[str, Any]:
        """Fleet-wide prefix-redundancy summary: sums the per-engine byte
        and admission counts (repeat hits stay per-engine — each engine
        hashes independently, so a cross-replica repeat is NOT counted; a
        shared prefix cache would save more than this reports, making the
        number conservative) and recomputes the fractions."""
        parts = [e.prefix_redundancy() for e in self.engines]
        out: Dict[str, Any] = {
            k: sum(p[k] for p in parts)
            for k in ("admissions", "unique_prefixes", "repeat_hits",
                      "null_lane_bytes", "repeat_prefill_bytes",
                      "duplicate_bytes", "prefill_bytes")
        }
        out["repeat_hit_frac"] = (out["repeat_hits"] / out["admissions"]
                                  if out["admissions"] else 0.0)
        out["duplicate_frac"] = (out["duplicate_bytes"] / out["prefill_bytes"]
                                 if out["prefill_bytes"] else 0.0)
        return out

    def pool_observability(self) -> Dict[str, Any]:
        """Fleet-wide pool section: each replica owns its OWN BlockPool, so
        per-replica summaries are reported verbatim (a forecast for one
        pool does not sum across pools) plus the additive fleet totals —
        reserved-unused waste and the recorder drop count — and the worst
        per-replica high-water fraction (the capacity-planning number)."""
        per = [e.pool_observability() for e in self.engines]
        out: Dict[str, Any] = {
            "replicas": per,
            "reserved_unused_blocks": sum(
                p.get("reserved_unused_blocks") or 0 for p in per),
            "recorder_dropped": sum(
                p.get("recorder_dropped") or 0 for p in per),
            "high_water_frac_max": max(
                (p["high_water"] / p["num_blocks"] if p["num_blocks"] else 0.0)
                for p in per),
        }
        return out

    def handoff_ledger(self) -> Optional[Dict[str, Any]]:
        """The disaggregation comms ledger (None when not disaggregated):
        one `prefill_to_decode` row, same shape as step_comms_ledger rows."""
        if self.prefill_worker is None:
            return None
        row = self.prefill_worker.handoff_row(lanes=1)
        return {
            "mesh": {"prefill": 1, "decode": len(self.router.alive())},
            "per_axis": [row],
            "total_bytes_per_step": row["bytes_per_step"],
        }
