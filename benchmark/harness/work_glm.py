"""The operations a latent-attention trunk with a leading dense layer, routed
experts and a prediction module REQUIRES, from the configuration's sizes alone,
by `work_q3n.py`'s conventions.  Kept with the benchmark so that no later PR
can change what `train_glm_mfu_pct` is measured against.

Per token, forward: 2 x every matmul weight this chip applies (the five
projections of each `mla` layer; the dense layer's SwiGLU; the router, the
shared expert and the held experts at the EXPECTED top_k * held / experts pairs
a token, whatever the router did; both heads; the module's merge and block),
plus the causal half of each `mla` layer's score matrix (scores over nope +
rope, values over v), the trunk's over s positions and the module's over s - 1.
The backward is twice the forward.  Recomputation does not count, nor does the
position the module's block carries for the kernel's sake and cuts.
"""
from __future__ import annotations

from benchmark.harness.work_q3n import seq_len, vocabulary


def mla_weights(sizes: dict) -> float:
    dim, heads = int(sizes["dim"]), int(sizes["heads"])
    q_rank, kv_rank = int(sizes["mla_q_rank"]), int(sizes["mla_kv_rank"])
    nope, rope, v = int(sizes["mla_nope_dim"]), int(sizes["mla_rope_dim"]), int(sizes["mla_v_dim"])
    return (dim * q_rank + q_rank * heads * (nope + rope) + dim * (kv_rank + rope)
            + kv_rank * heads * (nope + v) + heads * v * dim)


def routed_weights(sizes: dict) -> float:
    """Router, shared expert and the held experts at the expected pairs a token."""
    dim, experts = int(sizes["dim"]), int(sizes["moe_experts"])
    held = int(sizes.get("moe_experts_held") or experts)
    pairs = int(sizes["moe_top_k"]) * held / experts
    return (dim * experts + pairs * 3 * dim * int(sizes["moe_ff_dim"])
            + 3 * dim * int(sizes.get("moe_shared_ff_dim", 0)))


def dense_weights(sizes: dict) -> float:
    return 3 * int(sizes["dim"]) * int(sizes["dense_ff_dim"])


def trunk_weights_per_token(sizes: dict) -> float:
    """Weights a token is multiplied by in the trunk and its head."""
    depth, dense = int(sizes["depth"]), int(sizes.get("dense_layers", 0))
    return (depth * mla_weights(sizes) + dense * dense_weights(sizes)
            + (depth - dense) * routed_weights(sizes) + int(sizes["dim"]) * vocabulary(sizes))


def module_weights_per_token(sizes: dict) -> float:
    """The prediction module's: merge, one routed block, the shared head again."""
    if not int(sizes.get("mtp_depth", 0)):
        return 0.0
    dim = int(sizes["dim"])
    return 2 * dim * dim + mla_weights(sizes) + routed_weights(sizes) + dim * vocabulary(sizes)


def attention_flops(sizes: dict, positions: int) -> float:
    """One `mla` layer's scores and values over the causal half (diagonal included)."""
    width = int(sizes["mla_nope_dim"]) + int(sizes["mla_rope_dim"]) + int(sizes["mla_v_dim"])
    return 2.0 * int(sizes["heads"]) * width * positions * (positions + 1) / 2


def train_step_flops(sizes: dict, batch: int) -> float:
    """Forward + backward (3 x forward) of `batch` sequences."""
    s = seq_len(sizes)
    module = int(sizes.get("mtp_depth", 0))
    forward = (2.0 * trunk_weights_per_token(sizes) * s + int(sizes["depth"]) * attention_flops(sizes, s)
               + module * (2.0 * module_weights_per_token(sizes) * (s - 1)
                           + attention_flops(sizes, s - 1)))
    return 3.0 * batch * forward
