"""The operations a Laguna trunk (`gated_full` and `gated_window` layers with
their own head counts, per-head gates, a leading dense layer, softmax-routed
experts beside a gated shared expert) REQUIRES, from the configuration's sizes
alone, by `work_q3n.py`'s conventions, and the device time of the window
layers' flash kernels.  Kept with the benchmark so that no later PR can change
what `train_laguna_mfu_pct` and `train_window_flash_roofline` are measured
against.

Per token, forward: 2 x every matmul weight this chip applies (each layer's
q with its per-head gate, k, v and out at the layer's own head count; the
dense layer's SwiGLU; the router, the gated shared expert and the held experts
at the EXPECTED top_k * held / experts pairs a token, whatever the router did;
the head), plus each layer's scores and values over the (query, key) pairs its
mask permits: the causal half for the full kind, the band for the window kind
(2,031,872 pairs a head at 4,224 positions and a window of 512, where the
compacted grid's 30 live 384-tiles compute 4,423,680).  The backward is twice
the forward.  Recomputation does not count, nor do the pairs a tile computes
outside the band.
"""
from __future__ import annotations

from typing import Optional

from benchmark.harness import work_glm
from benchmark.harness.work_q3n import _words, layer_types, seq_len, vocabulary


def heads_of(sizes: dict, kind: str) -> int:
    return int(sizes.get("window_heads") or sizes["heads"]) if kind == "gated_window" else int(sizes["heads"])


def attention_weights(sizes: dict, kind: str) -> float:
    """q and its per-head gate, k, v and out of one layer of `kind`."""
    dim, dh, heads = int(sizes["dim"]), int(sizes["dim_head"]), heads_of(sizes, kind)
    kv = int(sizes.get("kv_heads") or sizes["heads"]) * dh
    return dim * (heads * dh + heads) + 2 * dim * kv + heads * dh * dim


def pairs(sizes: dict, kind: str, n: int) -> float:
    """(query, key) pairs a head's mask permits over n positions."""
    w = int(sizes["window"]) if kind == "gated_window" else n
    w = min(w, n)
    return w * (w + 1) / 2 + (n - w) * w


def attention_flops(sizes: dict, kind: str, n: int) -> float:
    """One layer's scores and values (2 x dim_head each a pair), all heads, forward."""
    return 4.0 * heads_of(sizes, kind) * int(sizes["dim_head"]) * pairs(sizes, kind, n)


def weights_per_token(sizes: dict) -> float:
    """Weights a token is multiplied by in the trunk and its head."""
    dense, dim = int(sizes.get("dense_layers", 0)), int(sizes["dim"])
    routed = work_glm.routed_weights(sizes) + dim  # + the shared expert's gate
    return (sum(attention_weights(sizes, kind) + (work_glm.dense_weights(sizes) if l < dense else routed)
                for l, kind in enumerate(layer_types(sizes)))
            + dim * vocabulary(sizes))


def train_step_flops(sizes: dict, batch: int) -> float:
    """Forward + backward (3 x forward) of `batch` sequences."""
    s = seq_len(sizes)
    forward = 2.0 * weights_per_token(sizes) * s + sum(
        attention_flops(sizes, kind, s) for kind in layer_types(sizes))
    return 3.0 * batch * forward


def window_flash_flops(sizes: dict, batch: int) -> float:
    """What the window layers' flash calls require, forward + backward, of
    `batch` sequences: the band's pairs only."""
    s = seq_len(sizes)
    return 3.0 * batch * sum(attention_flops(sizes, kind, s)
                             for kind in layer_types(sizes) if kind == "gated_window")


def flash_ms_under(ctx, scope: str, program: str = "train_step") -> Optional[float]:
    """Median over the traced stretch's whole executions of `program` of the
    device self ms of its `flash_*` operations (the Pallas calls, named after
    their kernels) under `scope`.  None where no trace was taken or none lies
    there."""
    import re

    from benchmark.harness import program_trace, stats

    trace = program_trace.of(ctx)
    if trace is None:
        return None
    named = re.compile("flash_")
    per = [sum(t for name, path, t in trace.op_self_times(start, dur)
               if named.match(name.lstrip("%")) and scope in _words(path))
           for start, dur in trace.executions(program)]
    if not any(per):
        return None
    return stats.median(per) * 1e-6
