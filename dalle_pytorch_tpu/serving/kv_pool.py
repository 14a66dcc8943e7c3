"""The paged KV block pool: device arrays + host-side free-list.

One preallocated pool (models/transformer.init_paged_pool) is shared by every
in-flight sequence; this module owns the HOST half — which physical blocks
are free, which belong to which request, and the occupancy numbers admission
control and the memory ledger price against.  The device half (gather /
scatter through block tables) lives in models/transformer's paged ops.

Allocation is whole-sequence at admission: generation length is fixed
(text + image_seq_len), so a reservation and an allocation are the same
thing — overcommit with mid-flight preemption is future work (vLLM-style
swapping), and admission control refusing up front is what turns "pool
exhausted" into backpressure instead of an OOM.

Block 0 is the TRASH block: inactive engine slots keep all-zero block
tables, so their masked decode lanes scatter into block 0 and can only
clobber garbage.  It is never handed out.

Two kinds of cache, one manager.  A `gated_delta` layer keeps no keys: per
SLOT it keeps the rule's float32 state and the convolution's last inputs
(`transformer.gated_delta_carried`), in the same device pool, as that layer's
entry.  A slot's state is admitted and freed with the slot: the ingest
program overwrites it whole at admission (nothing of the slot's last request
is read), so eviction has nothing to release and the free list counts blocks
of the layers that hold them (`cfg.kv_layers`) alone; `state_bytes` prices
the rest.

Flight recorder: attach a `PoolFlightRecorder` (`pool.recorder = ...`) and
every alloc_table / free_table / truncate_slot leaves a block-lifecycle
event — owner, block ids, occupancy/high-water at that instant, monotonic
timestamp — in a bounded in-memory ring the engine flushes through
telemetry as `kind:"pool"` JSONL records at its window cadence.  Every
field is a host int this ledger already holds and the hooks run inside
calls that already sit at the engine's admission/eviction host syncs, so
recording adds ZERO device syncs (tools/lint_host_sync.py keeps that
mechanical); with no recorder attached the hooks are a single `is None`
test — no event objects, no ring, nothing allocated.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Deque, Dict, List, Optional

import numpy as np

from dalle_pytorch_tpu.models.transformer import (
    TransformerConfig,
    gated_delta_carried,
    init_paged_pool,
    paged_blocks_per_seq,
)
from dalle_pytorch_tpu.observability import metrics as obs_metrics


class PoolExhausted(RuntimeError):
    """No free blocks for a whole-sequence allocation."""


class PoolFlightRecorder:
    """Bounded ring of block-lifecycle events (the KV-pool flight recorder).

    `record()` appends one host dict per pool operation — capped at
    `capacity`; under flood the OLDEST events drop (counted in `dropped`,
    surfaced so tools/pool_report.py refuses to validate a torn trace).
    The engine sets `ctx` to the admission context (request id, journey
    uid, lanes, guidance, prefix hash) for the per-lane allocs of one
    admission, and calls `flush()` at its telemetry-window cadence to
    drain the ring through `SpanRecorder.write_event` as `kind:"pool"`
    records.  `on_event` is the live-gauges tap
    (observability.pool.PoolGauges.observe) — fed at record time, so the
    gauges survive ring overflow and telemetry-off runs."""

    def __init__(self, capacity: int = 4096):
        assert capacity > 0
        self.capacity = capacity
        self._ring: Deque[Dict[str, Any]] = collections.deque(maxlen=capacity)
        self.dropped = 0
        self.config: Dict[str, Any] = {}
        self.ctx: Optional[Dict[str, Any]] = None
        self.on_event: Optional[Callable[[Dict[str, Any]], None]] = None
        self._config_flushed = False
        self._dropped_flushed = 0

    def __len__(self) -> int:
        return len(self._ring)

    def record(self, op: str, **fields) -> None:
        """One lifecycle event.  The timestamp is time.monotonic() — pure
        host clock, taken inside a pool call the engine already made at an
        existing sync point — and every field is a host value the caller
        already holds."""
        ev = {"op": op, "mono": time.monotonic(), **fields}
        if len(self._ring) == self.capacity:
            self.dropped += 1  # deque(maxlen) evicts the oldest silently
        self._ring.append(ev)
        cb = self.on_event
        if cb is not None:
            cb(ev)

    def flush(self, spans, replica: Optional[int] = None) -> int:
        """Drain pending events through `spans.write_event` as
        `kind:"pool"` JSONL records.  The pool-geometry config event goes
        out once (first flush); a drops marker follows any ring overflow
        since the previous flush.  Returns the number of lifecycle events
        written."""
        if not self._config_flushed and self.config:
            spans.write_event("pool", op="config", replica=replica,
                              **self.config)
            self._config_flushed = True
        if self.dropped != self._dropped_flushed:
            spans.write_event("pool", op="drops", replica=replica,
                              dropped=self.dropped)
            self._dropped_flushed = self.dropped
        n = 0
        while self._ring:
            ev = self._ring.popleft()
            ev.setdefault("replica", replica)
            spans.write_event("pool", **ev)
            n += 1
        return n


@dataclasses.dataclass
class BlockPool:
    """Host free-list over the shared device block pool.

    `num_blocks` counts usable blocks (the trash block is allocated on top),
    `block_size` is tokens per block.  `device_pool()` materializes the
    device arrays once; the engine threads them through its jits and keeps
    the latest version (this object never holds traced values).
    """

    cfg: TransformerConfig
    num_blocks: int
    block_size: int
    dtype: Any = None
    quant: Optional[str] = None  # "int8" for a quantized pool, else None
    num_slots: int = 0  # rows of the per-slot state of `gated_delta` layers

    def __post_init__(self):
        assert self.block_size > 0 and self.num_blocks > 0
        self.blocks_per_seq = paged_blocks_per_seq(self.cfg, self.block_size)
        # physical ids 1..num_blocks; 0 is the trash block
        self._free: List[int] = list(range(1, self.num_blocks + 1))
        self._owned: Dict[int, List[int]] = {}
        self._high_water = 0
        # flight recorder (None = recording off: the hooks below reduce to
        # one `is None` test — nothing allocated, nothing recorded)
        self.recorder: Optional[PoolFlightRecorder] = None

    # -- device side --------------------------------------------------------
    def device_pool(self, dtype=None) -> dict:
        """Fresh device arrays for this pool geometry (+1 for the trash
        block).  Called once at engine construction."""
        import jax.numpy as jnp

        dt = dtype if dtype is not None else (self.dtype or jnp.float32)
        return init_paged_pool(self.cfg, self.num_blocks + 1, self.block_size,
                               dt, quantize=self.quant, num_slots=self.num_slots)

    def bytes(self, itemsize: int = 4) -> float:
        """At-rest bytes of the device pool (k + v, every layer).  On a
        quantized pool `itemsize` is the dtype the pool WOULD have used —
        the quantized price (int8 payload + per-token scales) comes from
        the shared `kv_bytes_per_elem` formula."""
        from dalle_pytorch_tpu.quantization import kv_bytes_per_elem

        return (
            2.0 * self.cfg.kv_layers * (self.num_blocks + 1) * self.cfg.heads
            * self.block_size * self.cfg.dim_head
            * kv_bytes_per_elem(self.quant, itemsize, self.cfg.dim_head)
        )

    def state_bytes(self) -> int:
        """At-rest bytes of the per-slot recurrent state (float32) and
        convolution taps (the pool's type) of every `gated_delta` layer."""
        import jax

        state_layers = self.cfg.depth - self.cfg.kv_layers
        if not state_layers:
            return 0
        carried = jax.eval_shape(
            lambda: gated_delta_carried(self.cfg, self.num_slots, self.dtype or np.float32))
        return state_layers * sum(a.size * a.dtype.itemsize for a in carried.values())

    def prefix_bytes(self, n_tokens: int,
                     itemsize: Optional[int] = None) -> float:
        """At-rest KV bytes ONE lane's `n_tokens`-long prefix occupies in
        this pool (k + v, every layer, quantization priced by the shared
        formula).  The prefix-redundancy profiler prices duplicated prefill
        work with this — e.g. a guided request's null lane writes exactly
        this many bytes of KV that are byte-identical for every guided
        admission."""
        from dalle_pytorch_tpu.quantization import kv_bytes_per_elem

        if itemsize is None:
            itemsize = (np.dtype(self.dtype).itemsize
                        if self.dtype is not None else 4)
        return (2.0 * self.cfg.kv_layers * self.cfg.heads * n_tokens
                * self.cfg.dim_head
                * kv_bytes_per_elem(self.quant, itemsize, self.cfg.dim_head))

    # -- host free list -----------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - len(self._free)

    @property
    def occupancy_frac(self) -> float:
        return self.used_blocks / self.num_blocks

    @property
    def high_water(self) -> int:
        """Most blocks ever in use at once — the capacity-planning number a
        router and the flood drill size pools from ("how big did it get",
        not "how big is it now")."""
        return self._high_water

    @property
    def fragmentation_frac(self) -> float:
        """1 - (largest contiguous free run / free blocks).  Allocation is
        whole-sequence so fragmentation never blocks an admission here, but
        a quantized/compacted pool gathers faster from contiguous blocks —
        the gauge tracks how scattered the free list has become."""
        if not self._free:
            return 0.0
        ids = sorted(self._free)
        best = run = 1
        for a, b in zip(ids, ids[1:]):
            run = run + 1 if b == a + 1 else 1
            best = max(best, run)
        return 1.0 - best / len(self._free)

    def publish_gauges(self) -> None:
        """Mirror the free-list state into the metrics registry — the
        router's placement scores and the chaos drills read these instead of
        reaching into engine internals."""
        obs_metrics.gauge("serving/pool_blocks_free").set(self.free_blocks)
        obs_metrics.gauge("serving/pool_high_water").set(self._high_water)
        obs_metrics.gauge("serving/pool_fragmentation_frac").set(
            self.fragmentation_frac)

    def can_admit(self) -> bool:
        return len(self._free) >= self.blocks_per_seq

    def fits_ever(self) -> bool:
        """Could a request EVER be admitted (even on an idle pool)?  False
        means submit() must refuse outright instead of queueing forever."""
        return self.num_blocks >= self.blocks_per_seq

    def alloc_table(self, owner: int) -> np.ndarray:
        """Allocate a full sequence's blocks for request `owner`.  Returns
        the (blocks_per_seq,) int32 block table; raises PoolExhausted when
        the pool cannot cover it (admission control's job to pre-check)."""
        if len(self._free) < self.blocks_per_seq:
            raise PoolExhausted(
                f"need {self.blocks_per_seq} blocks, {len(self._free)} free"
            )
        blocks = [self._free.pop() for _ in range(self.blocks_per_seq)]
        self._owned[owner] = blocks
        self._high_water = max(self._high_water, self.used_blocks)
        rec = self.recorder
        if rec is not None:
            # host-ledger event emission: every field is a host int this
            # free-list already holds, stamped inside the admission call
            rec.record("alloc", owner=owner, blocks=list(blocks),
                       reserved=len(blocks), occupancy=self.used_blocks,
                       high_water=self._high_water, free=len(self._free),
                       **(rec.ctx or {}))
        self.publish_gauges()
        return np.asarray(blocks, np.int32)  # host-sync-ok: host free-list ids

    def free_table(self, owner: int,
                   written_tokens: Optional[int] = None) -> None:
        """Return a request's blocks to the free list (eviction).
        `written_tokens` is how many KV tokens the lane actually wrote —
        the engine knows it at its eviction sync; the recorder turns
        (reserved - ceil(written/block_size)) into the reserved-but-unused
        waste expected-block admission would reclaim."""
        blocks = self._owned.pop(owner, None)
        if blocks:
            self._free.extend(blocks)
            rec = self.recorder
            if rec is not None:
                rec.record("free", owner=owner, released=len(blocks),
                           written=written_tokens,
                           occupancy=self.used_blocks,
                           high_water=self._high_water,
                           free=len(self._free))
            self.publish_gauges()

    def truncate_slot(self, owner: int, n: int) -> int:
        """Roll `owner`'s sequence back to `n` valid tokens (speculative
        decode rejected everything past position n-1).  Allocation here is
        whole-sequence reservation — the blocks stay owned for the rest of
        the sequence the request WILL still generate — so rollback frees
        ZERO blocks; this is the host-side commit point that keeps the
        ledger's notion of live tokens consistent with the device offsets
        and re-publishes the gauges.  Returns the number of blocks holding
        live tokens (the device side needs no touch-up: rejected KV columns
        are masked out of every read and overwritten before reuse)."""
        blocks = self._owned.get(owner)
        if blocks is None:
            raise KeyError(f"truncate_slot: owner {owner} holds no blocks")
        if not (0 <= n <= self.blocks_per_seq * self.block_size):
            raise ValueError(
                f"truncate_slot: n={n} outside [0, "
                f"{self.blocks_per_seq * self.block_size}]")
        live = -(-n // self.block_size)
        rec = self.recorder
        if rec is not None:
            rec.record("truncate", owner=owner, tokens=n, live_blocks=live,
                       occupancy=self.used_blocks, free=len(self._free))
        self.publish_gauges()
        return live

    def owners(self) -> List[int]:
        return list(self._owned)


def blocks_within_bytes(cfg: TransformerConfig, budget_bytes: float,
                        block_size: int, itemsize: int = 2,
                        kv_quant: Optional[str] = None) -> int:
    """How many usable blocks fit an at-rest byte budget (trash block's cost
    included).  The capacity half of the 2x claim: quantizing the pool while
    holding the BYTE budget fixed roughly doubles the block count, which is
    what lets admission pass at 2x the slot count."""
    from dalle_pytorch_tpu.quantization import kv_bytes_per_elem

    per_block = (2.0 * cfg.kv_layers * cfg.heads * block_size * cfg.dim_head
                 * kv_bytes_per_elem(kv_quant, itemsize, cfg.dim_head))
    return max(int(budget_bytes // per_block) - 1, 0)  # -1: the trash block


def paged_ledger_entry(cfg_geom: Any, num_blocks: int, block_size: int,
                       num_slots: int, itemsize: Optional[int] = None,
                       kv_quant: Optional[str] = None,
                       ) -> Optional[Dict[str, Any]]:
    """The dict `observability.memory.sampling_memory_ledger` prices its
    paged-pool rows from (geometry comes from the DALLEConfig).  Leave
    `itemsize` None unless the pool dtype differs from the params' — the
    ledger's params-derived itemsize is the default, so a bf16 pool is not
    silently priced at 4 bytes."""
    entry = {
        "num_blocks": num_blocks,
        "block_size": block_size,
        "num_slots": num_slots,
    }
    if itemsize is not None:
        entry["itemsize"] = itemsize
    if kv_quant:
        entry["kv_quant"] = kv_quant
    return entry
