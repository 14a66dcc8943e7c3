"""The operations a hybrid trunk (`gated_delta` / `gated_full` layers, routed
experts) REQUIRES, from the configuration's sizes alone, and the device time
of its named scopes.  Kept with the benchmark so that no later PR can change
what `train_q3n_mfu_pct` is measured against.

Per token, forward: 2 x every matmul weight this chip applies (the held
experts at the EXPECTED top_k * held / experts pairs a token, whatever the
router did), plus the causal half of each `gated_full` layer's score matrix
(scores and values), plus the delta rule as its DEFINITION requires: decay
aside, three products of dk x dv a value head and token (S'^T k, the rank-1
write, S^T q), and not what a chunked algorithm spends to get there.  The
backward is twice the forward.  Recomputation does not count.
"""
from __future__ import annotations

from typing import Optional, Sequence


def seq_len(sizes: dict) -> int:
    return int(sizes["text_seq_len"]) + int(sizes["image_fmap_size"]) ** 2


def vocabulary(sizes: dict) -> int:
    return int(sizes["num_text_tokens"]) + int(sizes["text_seq_len"]) + int(sizes["num_image_tokens"])


def layer_types(sizes: dict) -> list:
    types = list(sizes["attn_types"])
    return [types[l % len(types)] for l in range(int(sizes["depth"]))]


def matmul_weights_per_token(sizes: dict) -> float:
    """Weights a token is multiplied by on this chip, all layers and the head."""
    dim = int(sizes["dim"])
    inner = int(sizes["heads"]) * int(sizes["dim_head"])
    kv = int(sizes.get("kv_heads") or sizes["heads"]) * int(sizes["dim_head"])
    hv = int(sizes["gdn_value_heads"])
    kd = int(sizes["gdn_key_heads"]) * int(sizes["gdn_key_dim"])
    vd = hv * int(sizes["gdn_value_dim"])
    mixers = {
        "gated_full": dim * 2 * inner + 2 * dim * kv + inner * dim,
        "gated_delta": (dim * (2 * kd + 2 * vd) + dim * 2 * hv + vd * dim
                        + int(sizes["gdn_conv_kernel"]) * (2 * kd + vd)),
    }
    experts = int(sizes["moe_experts"])
    held = int(sizes.get("moe_experts_held") or experts)
    pairs = int(sizes["moe_top_k"]) * held / experts  # expected pairs a token on this chip
    shared = int(sizes.get("moe_shared_ff_dim", 0))
    moe = (dim * experts + pairs * 3 * dim * int(sizes["moe_ff_dim"])
           + (3 * dim * shared + dim if shared else 0))
    return sum(mixers[t] + moe for t in layer_types(sizes)) + dim * vocabulary(sizes)


def train_step_flops(sizes: dict, batch: int) -> float:
    """Forward + backward (3 x forward) of `batch` sequences."""
    s = seq_len(sizes)
    types = layer_types(sizes)
    proj = 2.0 * matmul_weights_per_token(sizes) * batch * s
    # scores and values over the causal half (diagonal included) of s x s
    attn = types.count("gated_full") * 4.0 * batch * int(sizes["heads"]) \
        * int(sizes["dim_head"]) * s * (s + 1) / 2
    rule = types.count("gated_delta") * 3 * 2.0 * int(sizes["gdn_key_dim"]) \
        * int(sizes["gdn_value_dim"]) * int(sizes["gdn_value_heads"]) * batch * s
    return 3.0 * (proj + attn + rule)


def _words(path: str):
    """The scope names along an operation's path: `transpose(jvp(gdn_scan))`
    counts as `gdn_scan`, as in harness/program_trace.scope_of."""
    from benchmark.harness.program_trace import _WORD

    for part in path.split("/"):
        yield from _WORD.findall(part)


def scope_device_ms(ctx, scopes: Sequence[str], program: str = "train_step") -> Optional[float]:
    """Median over the traced stretch's whole executions of `program` of the
    device ms under any of `scopes` (forward, backward and recomputation
    alike).  None where no trace was taken or the program names none of them."""
    from benchmark.harness import program_trace, stats

    trace = program_trace.of(ctx)
    if trace is None:
        return None
    wanted = set(scopes)
    per = trace.time_by(program, lambda path: "in" if wanted.intersection(_words(path)) else "")
    if not any("in" in by for by in per):
        return None
    m = stats.median([by.get("in", 0.0) for by in per])
    return None if m is None else m * 1e-6
