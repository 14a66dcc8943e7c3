"""The operations and bytes the algorithm REQUIRES, from the configuration's
sizes alone.  Kept with the benchmark so that no later PR can change what a
utilisation or a roofline share is measured against.  (The train count copies
the arithmetic of `training/profiling.dalle_step_flops` at element
granularity, with the masks of the benchmark's own reference.)"""
from __future__ import annotations

from functools import lru_cache
from typing import Sequence

import numpy as np

from benchmark.reference.dalle_reference import pattern_mask


def seq_len(sizes: dict) -> int:
    return int(sizes["text_seq_len"]) + int(sizes["image_fmap_size"]) ** 2


def vocabulary(sizes: dict) -> int:
    return int(sizes["num_text_tokens"]) + int(sizes["text_seq_len"]) + int(sizes["num_image_tokens"])


def matmul_params(sizes: dict) -> int:
    """Weights that take part in matrix multiplications (biases, norms and
    LayerScale vectors excluded; the shared embedding counts once, as the
    output projection)."""
    dim, inner = int(sizes["dim"]), int(sizes["heads"]) * int(sizes["dim_head"])
    ff = 4 * dim
    per_layer = dim * 3 * inner + inner * dim + 2 * dim * ff + ff * dim
    return int(sizes["depth"]) * per_layer + dim * vocabulary(sizes)


@lru_cache(maxsize=None)
def _mask(sizes_key: tuple, attn_type: str) -> np.ndarray:
    sizes = dict(sizes_key)
    return pattern_mask(sizes, attn_type, seq_len(sizes))


def _key(sizes: dict) -> tuple:
    keep = ("text_seq_len", "image_fmap_size", "conv_kernel_size", "conv_dilation")
    return tuple((k, sizes[k]) for k in keep if k in sizes)


def layer_types(sizes: dict) -> list:
    types = list(sizes["attn_types"])
    return [types[l % len(types)] for l in range(int(sizes["depth"]))]


def train_step_flops(sizes: dict, batch: int) -> float:
    """Forward + backward (3x forward) of `batch` sequences: 2 * weights per
    token, plus scores and values at each layer's live (pattern AND causal)
    share of the score matrix.  Recomputed operations (remat) do not count."""
    s = seq_len(sizes)
    proj = 2.0 * matmul_params(sizes) * batch * s
    live = sum(float(_mask(_key(sizes), t).mean()) for t in layer_types(sizes))
    attn = 4.0 * batch * int(sizes["heads"]) * s * s * int(sizes["dim_head"]) * live
    return 3.0 * (proj + attn)


def decode_step_bytes(sizes: dict, positions: Sequence[int], weight_itemsize: int,
                      kv_itemsize: int) -> float:
    """Bytes one fused decode step has to read: every layer's weights once;
    of the shared embedding and output table the rows a decode step can EMIT,
    `num_image_tokens` of them, once (the step's lookup and its head read the
    same image half: a step neither feeds nor draws a text or pad column);
    and for each active lane, whose query sits at sequence position p, the
    keys and values its layers' patterns let it see (causal: at most p + 1),
    in the pool's storage type.  Writes (one K/V column a lane and layer) and
    activations are left out: they are a thousandth of this."""
    never_read = int(sizes["dim"]) * (vocabulary(sizes) - int(sizes["num_image_tokens"]))
    weights = (matmul_params(sizes) - never_read) * weight_itemsize
    heads, dh = int(sizes["heads"]), int(sizes["dim_head"])
    kv = 0.0
    for t in layer_types(sizes):
        row_counts = _mask(_key(sizes), t).sum(axis=1)
        kv += sum(int(row_counts[min(p, len(row_counts) - 1)]) for p in positions)
    return weights + kv * 2 * heads * dh * kv_itemsize
