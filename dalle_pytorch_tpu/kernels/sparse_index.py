"""Index tables for the compacted-grid block-sparse flash kernels.

The dense-grid kernels in flash_attention.py schedule every (query-tile,
key-tile) pair and merely `pl.when`-skip the dead ones — dead tiles still
occupy grid slots and still DMA their K/V blocks into VMEM.  This module
turns a pattern's STATIC block-liveness table into flat per-grid-step index
arrays that are fed through `num_scalar_prefetch`, so the compacted kernels
iterate ONLY live tiles and their BlockSpec index maps fetch only live
blocks (splash-attention style).

Everything here runs on host numpy at trace time over static masks — the
tables are compile-time constants (or, under scan_layers, stacked constants
selected by a traced layer index).  Nothing in this module may touch traced
values; it is covered by tools/lint_host_sync.py like the rest of kernels/.

Table layout (all int32):

  row-major ("fwd"/"dq" traversal, query tiles outer, live key tiles inner,
  ascending j — the SAME visit order as the dense grid, which is what makes
  the compacted kernels bit-exact):
    qrow[H, T]   query-tile index i of grid step t
    kcol[H, T]   key-tile index j of grid step t
    first[H, T]  1 on the first live entry of a query row (init accumulators)
    last[H, T]   1 on the last live entry of a query row (finalize/write out)
    valid[H, T]  1 on real entries, 0 on padding/placeholders (skip compute)

  column-major ("dkv" traversal, key tiles outer, live query tiles inner,
  ascending i — the dk/dv kernel accumulates per KEY tile):
    qrowT/kcolT/firstT/lastT/validT[H, T2], same roles with row<->column
    swapped (firstT/lastT mark a key COLUMN's first/last live entry).

H is 1 for a shared mask and `heads` for per-head ('sparse' per-head) masks.
A query row (or key column) with no live tiles gets one placeholder entry
with first=last=1, valid=0: the kernel then runs init + finalize without
compute and writes the exact zeros the dense grid writes for fully-dead
rows.  Padding entries (to equalize T across heads, or across patterns for
scan stacking) replicate the previous entry's qrow/kcol with
first=last=valid=0 — the out-block index map keeps pointing at the
already-finalized block, so Pallas's end-of-grid flush rewrites values that
are already correct.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

# keys of the table dict, in the fixed order the kernels consume them
TABLE_KEYS = (
    "qrow", "kcol", "first", "last", "valid",
    "qrowT", "kcolT", "firstT", "lastT", "validT",
)


def block_causal_live_np(nq: int, nk: int, block_q: int, block_k: int) -> np.ndarray:
    """(nq, nk) bool: tiles with at least one causally-allowed (j <= i)
    element — the tile-granular causal triangle the dense kernels skip by."""
    i = np.arange(nq)[:, None]
    j = np.arange(nk)[None, :]
    return j * block_k <= i * block_q + block_q - 1


def grid_has_dead_step(block_live: np.ndarray, block_q: int, block_k: int, *,
                       causal: bool) -> bool:
    """`grid="auto"`'s one rule: compact when some step of the (nq, nk) tile
    grid is dead, whether causality or the pattern kills it.  block_live: the
    pattern's (nq, nk) — or per-head (h, nq, nk) — tile liveness
    (ops.masks.block_live_np), all-true without a mask.  On the dense grid a
    dead step still takes its grid slot and fetches its K/V (and mask) tiles;
    the compacted grid runs none of them, and is bit-exact against it.
    Measured at 384-tiles, kernel-only (PERF.md section 6, PR 34): causal
    1,152 (3 of 9 steps dead) -16 %, 4,224 (55 of 121) -19 to -21 %."""
    live = np.asarray(block_live, bool)  # host-sync-ok: static trace-time table
    if causal:
        live = live & block_causal_live_np(*live.shape[-2:], block_q, block_k)
    return not bool(live.all())  # host-sync-ok: static trace-time table


def _compact_axis(live: np.ndarray, transpose: bool) -> Tuple[list, list, list, list, list]:
    """Flatten one head's (nq, nk) liveness into entry lists.  Row-major when
    transpose=False (query rows outer); column-major when True."""
    E = live.T if transpose else live
    qi, ki, first, last, valid = [], [], [], [], []
    for a in range(E.shape[0]):
        hits = np.flatnonzero(E[a])
        if hits.size == 0:
            # placeholder: init + finalize fire with no compute, writing the
            # same zeros the dense grid writes for a fully-dead row/column
            qi.append(a)
            ki.append(0)
            first.append(1)
            last.append(1)
            valid.append(0)
            continue
        for s, b in enumerate(hits):
            qi.append(a)
            ki.append(int(b))  # host-sync-ok: static trace-time table build
            first.append(1 if s == 0 else 0)
            last.append(1 if s == hits.size - 1 else 0)
            valid.append(1)
    if transpose:  # entries are (column, row): swap back to (qrow, kcol)
        qi, ki = ki, qi
    return qi, ki, first, last, valid


def _pad_entries(cols, length: int):
    qi, ki, first, last, valid = cols
    assert len(qi) <= length, (len(qi), length)
    while len(qi) < length:
        qi.append(qi[-1])
        ki.append(ki[-1])
        first.append(0)
        last.append(0)
        valid.append(0)
    return cols


def build_compacted_tables(
    block_live: np.ndarray,
    block_q: int,
    block_k: int,
    *,
    causal: bool = True,
    pad_to: Optional[Tuple[int, int]] = None,
) -> Dict[str, np.ndarray]:
    """Compacted grid tables from a pattern's block-liveness.

    block_live: (nq, nk) — or per-head (h, nq, nk) — nonzero = some element
    of the tile is pattern-allowed (ops.masks.block_live_np output, at
    resolve_block granularity).  Causality is folded in HERE (tile-granular,
    matching `_tile_live` in the dense kernels), so callers pass the
    pattern-only table.  pad_to=(T, T2) pads the row-major/column-major
    lengths (scan_layers stacks tables for every distinct pattern, and the
    grid size must be the same traced-select-invariant constant for all)."""
    bl = np.asarray(block_live)  # host-sync-ok: static trace-time table
    if bl.ndim == 2:
        bl = bl[None]
    heads, nq, nk = bl.shape
    live = bl.astype(bool)
    if causal:
        live = live & block_causal_live_np(nq, nk, block_q, block_k)[None]

    per_head = [
        (_compact_axis(live[h], False), _compact_axis(live[h], True))
        for h in range(heads)
    ]
    T = max(len(row[0][0]) for row in per_head)
    T2 = max(len(row[1][0]) for row in per_head)
    if pad_to is not None:
        assert pad_to[0] >= T and pad_to[1] >= T2, (pad_to, T, T2)
        T, T2 = pad_to

    out = {k: [] for k in TABLE_KEYS}
    for fwd_cols, bwd_cols in per_head:
        qi, ki, first, last, valid = _pad_entries(fwd_cols, T)
        out["qrow"].append(qi)
        out["kcol"].append(ki)
        out["first"].append(first)
        out["last"].append(last)
        out["valid"].append(valid)
        qi, ki, first, last, valid = _pad_entries(bwd_cols, T2)
        out["qrowT"].append(qi)
        out["kcolT"].append(ki)
        out["firstT"].append(first)
        out["lastT"].append(last)
        out["validT"].append(valid)
    return {k: np.asarray(v, np.int32) for k, v in out.items()}  # host-sync-ok: static tables


def table_grid_sizes(tables: Dict[str, np.ndarray]) -> Tuple[int, int]:
    """(T, T2): grid lengths of the row-major and column-major traversals —
    static from array shapes, so usable on traced (scan-selected) tables."""
    return tables["qrow"].shape[-1], tables["qrowT"].shape[-1]


def live_tile_counts(tables: Dict[str, np.ndarray]) -> Tuple[int, int]:
    """(live fwd entries, live dkv entries) — static tables only; the honest
    tile counts behind the bench's dense-vs-compacted ratio."""
    return (
        int(np.asarray(tables["valid"]).sum()),  # host-sync-ok: static table
        int(np.asarray(tables["validT"]).sum()),  # host-sync-ok: static table
    )


# ---------------------------------------------------------------------------
# sparse-aware decode
# ---------------------------------------------------------------------------

def decode_kv_counts(pattern: np.ndarray) -> np.ndarray:
    """Per-position permitted-key counts: counts[..., t] = |{j <= t :
    pattern[t, j]}|.  pattern: static (n, n) or (h, n, n) bool."""
    p = np.asarray(pattern, dtype=bool)  # host-sync-ok: static trace-time mask
    n = p.shape[-1]
    return (p & np.tril(np.ones((n, n), dtype=bool))).sum(axis=-1).astype(np.int32)


def decode_kv_span(pattern: Optional[np.ndarray], n: int) -> int:
    """Max keys any decode step reads under the pattern (the gather width
    Kmax).  None (a 'full' layer) reads the whole cache: returns n.  Shared
    with observability.memory's sampling ledger so the priced decode reads
    and the implemented gather agree by construction."""
    if pattern is None:
        return n
    return int(decode_kv_counts(pattern).max())


def build_decode_tables(pattern: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Gather tables for sparse-aware cached decode.

    Returns (idx, counts): idx[..., t, :] lists the ascending key positions
    {j <= t : pattern[t, j]} padded with 0 up to Kmax (padded entries are
    masked off by counts before the softmax — their exp is exactly 0.0, so
    parity with the full-cache row mask is exact); counts[..., t] is the
    live prefix length.  Shapes (n, Kmax)/(n,) for a shared pattern,
    (h, n, Kmax)/(h, n) per-head."""
    p = np.asarray(pattern, dtype=bool)  # host-sync-ok: static trace-time mask
    shared = p.ndim == 2
    if shared:
        p = p[None]
    heads, n, _ = p.shape
    counts = decode_kv_counts(p)
    kmax = int(counts.max())
    idx = np.zeros((heads, n, kmax), np.int32)
    for h in range(heads):
        for t in range(n):
            hits = np.flatnonzero(p[h, t, : t + 1])
            idx[h, t, : hits.size] = hits
    if shared:
        return idx[0], counts[0]
    return idx, counts
