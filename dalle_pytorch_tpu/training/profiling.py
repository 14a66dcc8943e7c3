"""Profiling & MFU accounting.

The reference exposes only DeepSpeed's FLOPS profiler and a hand-rolled
sample_per_sec counter (SURVEY.md §5).  TPU-native equivalents:

* analytic per-step FLOPs for a DALLE config (dalle_step_flops) and the MFU
  derived from wall-clock — the number the BASELINE targets are written in;
* jax.profiler trace capture (TensorBoard-compatible) around a step window;
* a StepTimer that measures correctly under async dispatch
  (block_until_ready on the full carried state, discarding the first
  overlapped measurement).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, Optional

import jax


def chip_peak_flops() -> Optional[float]:
    """Dense bf16 peak FLOP/s of the local chip (core/chips.py); None on CPU
    — there is no MFU to report there — and an error for an unknown TPU."""
    from dalle_pytorch_tpu.core.chips import chip_spec

    spec = chip_spec()
    return None if spec is None else spec.bf16_flops


_LOOKUP_TABLES = ("text_emb", "image_emb", "text_pos", "image_pos", "codebook", "visual_pos")


def matmul_param_count(params: Any) -> int:
    """Parameters that participate in matmuls (embedding *lookup* tables are
    excluded — counting them would inflate the FLOPs estimate and the MFU)."""
    total = 0
    for path, x in jax.tree_util.tree_leaves_with_path(params):
        if getattr(x, "ndim", 0) != 2:
            continue
        p = "/".join(str(getattr(k, "key", k)) for k in path)
        if any(t in p for t in _LOOKUP_TABLES):
            continue
        total += x.size
    return int(total)


def _attn_live_density(cfg) -> float:
    """Mean live fraction of the (s, s) score matrix across layers, counting
    only positions the attention may actually attend to (pattern AND causal).
    A full causal layer contributes ~0.5; axial/conv/block-sparse layers
    contribute their true (lower) density — pricing masked-out positions as
    useful FLOPs would inflate the MFU (the kernels skip dead tiles)."""
    import numpy as np

    from dalle_pytorch_tpu.models.transformer import (
        _pattern_for, _pattern_key, derive_layer_specs,
    )

    tcfg = cfg.transformer_config() if hasattr(cfg, "transformer_config") else cfg
    n = tcfg.seq_len
    tri_mean = (n + 1) / (2.0 * n)  # mean of the causal triangle
    cache: dict = {}
    dens = []
    for spec in derive_layer_specs(tcfg):
        key = _pattern_key(spec)
        if key not in cache:
            pm = _pattern_for(tcfg, key[0], key[1])
            if pm is None:
                cache[key] = tri_mean
            else:
                tri = np.tril(np.ones((n, n), dtype=bool))
                cache[key] = float((np.asarray(pm) & tri).mean())
        dens.append(cache[key])
    return sum(dens) / len(dens)


def _attn_tile_density(cfg) -> float:
    """Live fraction of the (s, s) score matrix at the flash kernels' TILE
    granularity: a (block_q, block_k) tile with a single live element is
    computed in full, so executed-FLOPs accounting must price whole live
    tiles — element-granular density understates kernel work for ragged
    patterns, overstating the remaining headroom.  Mirrors the block-liveness
    the kernels skip/compact by (ops.masks.block_live_np +
    sparse_index.block_causal_live_np at resolve_block granularity); falls
    back to element density when no kernel block divides the sequence (the
    dense-XLA path masks elementwise)."""
    import numpy as np

    from dalle_pytorch_tpu.kernels.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, resolve_block,
    )
    from dalle_pytorch_tpu.kernels.sparse_index import block_causal_live_np
    from dalle_pytorch_tpu.models.transformer import (
        _pattern_for, _pattern_key, derive_layer_specs,
    )
    from dalle_pytorch_tpu.ops.masks import block_live_np

    tcfg = cfg.transformer_config() if hasattr(cfg, "transformer_config") else cfg
    n = tcfg.seq_len
    try:
        bq = resolve_block(n, DEFAULT_BLOCK_Q)
        bk = resolve_block(n, DEFAULT_BLOCK_K)
    except ValueError:
        return _attn_live_density(cfg)
    cl = block_causal_live_np(n // bq, n // bk, bq, bk)
    cache: dict = {}
    dens = []
    for spec in derive_layer_specs(tcfg):
        key = _pattern_key(spec)
        if key not in cache:
            pm = _pattern_for(tcfg, key[0], key[1])
            if pm is None:
                cache[key] = float(cl.mean())
            else:
                bl = block_live_np(np.asarray(pm), bq, bk)
                cache[key] = float((bl & cl).mean())  # per-head bl broadcasts
        dens.append(cache[key])
    return sum(dens) / len(dens)


def dalle_step_flops(cfg, batch: int, n_matmul_params: int, with_backward: bool = True,
                     granularity: str = "element") -> float:
    """Analytic FLOPs for one (micro)step: 2*P*T matmul cost + attention
    scores/values priced at each layer's live (pattern & causal) density;
    backward ≈ 2x forward.

    granularity='element' prices the algorithmic density (what the math
    requires); 'tile' prices whole live kernel tiles — what the flash kernels
    actually execute, and therefore what the XLA cost crosscheck and the
    bench MFU must be compared against for sparse configs."""
    s = cfg.total_seq_len
    proj = 2.0 * n_matmul_params * batch * s
    density = (
        _attn_tile_density(cfg) if granularity == "tile"
        else _attn_live_density(cfg)
    )
    attn = 2.0 * 2.0 * batch * cfg.heads * s * s * cfg.dim_head * density * cfg.depth
    fwd = proj + attn
    return (3.0 if with_backward else 1.0) * fwd


def mfu(step_flops: float, step_time_s: float, n_chips: int = 1) -> Optional[float]:
    peak = chip_peak_flops()
    return None if peak is None else step_flops / step_time_s / (peak * n_chips)


@contextlib.contextmanager
def trace(log_dir: str = "./profile_trace") -> Iterator[None]:
    """Capture a TensorBoard trace of the enclosed block."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Times jitted steps under async dispatch: call observe(state) each step;
    per-step time = median of inter-block intervals after the first."""

    def __init__(self):
        self._times = []
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def observe(self, blockable: Any):
        jax.block_until_ready(blockable)
        now = time.perf_counter()
        if self._t0 is not None:
            self._times.append(now - self._t0)
        self._t0 = now

    @property
    def times(self):
        return list(self._times)

    def best(self) -> Optional[float]:
        return min(self._times) if self._times else None

    def summary(self) -> Dict[str, float]:
        ts = sorted(self._times)
        if not ts:
            return {}
        return {
            "best_s": ts[0],
            "median_s": ts[len(ts) // 2],
            "mean_s": sum(ts) / len(ts),
            "steps": float(len(ts)),
        }
