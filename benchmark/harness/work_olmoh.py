"""The bytes a decode step of an Olmo-Hybrid trunk REQUIRES, from the
configuration's sizes alone.  Kept with the benchmark so that no later PR can
change what `decode_olmoh_step_roofline` and `decode_gdn_step_roofline` are
measured against.  A decode step is bandwidth-bound (one token a lane against
every weight and every lane's state), so its roofline is bytes over the chip's
HBM bandwidth.

Per step: every layer's matmul weights once, in the type they are stored in;
of the UNTIED vocabulary the head's image rows once (`num_image_tokens` x dim:
a decode step emits an image id only) and of the embedding the one row a lane
looks up; for every `gated_delta` layer the recurrent state of EVERY slot the
program updates, read once and written once (float32; an idle lane's slot is
advanced too: the step has one shape), and the convolution's taps the same
(the pool's type); for the `full` layers the keys and values each ACTIVE lane
sees at its position (causal: p + 1 of them), in the pool's type.  Activations
and the one K/V column a lane writes are left out: a thousandth of this.
"""
from __future__ import annotations

from typing import Sequence

from benchmark.harness.work_q3n import layer_types

STATE_ITEMSIZE = 4  # the delta rule's state is float32 whatever the weights are


def _gdn(sizes: dict):
    hv = int(sizes["gdn_value_heads"])
    kd = int(sizes["gdn_key_heads"]) * int(sizes["gdn_key_dim"])
    vd = hv * int(sizes["gdn_value_dim"])
    return hv, kd, vd


def layer_weights(sizes: dict) -> int:
    """Matmul and convolution weights of all layers (norm vectors, A_log and
    dt_bias excluded: a hundred-thousandth of these)."""
    dim, inner = int(sizes["dim"]), int(sizes["heads"]) * int(sizes["dim_head"])
    hv, kd, vd = _gdn(sizes)
    mixers = {
        "full": dim * 3 * inner + inner * dim,
        "gated_delta": (dim * (2 * kd + 2 * vd) + dim * 2 * hv + vd * dim
                        + int(sizes["gdn_conv_kernel"]) * (2 * kd + vd)),
    }
    swiglu = 3 * dim * int(sizes["dense_ff_dim"])
    return sum(mixers[t] + swiglu for t in layer_types(sizes))


def state_elements(sizes: dict) -> int:
    """Float32 elements of ONE slot's recurrent state, all `gated_delta` layers."""
    hv = int(sizes["gdn_value_heads"])
    return (layer_types(sizes).count("gated_delta") * hv
            * int(sizes["gdn_key_dim"]) * int(sizes["gdn_value_dim"]))


def taps_elements(sizes: dict) -> int:
    """Elements of ONE slot's convolution taps, all `gated_delta` layers."""
    _, kd, vd = _gdn(sizes)
    return (layer_types(sizes).count("gated_delta") * (int(sizes["gdn_conv_kernel"]) - 1)
            * (2 * kd + vd))


def gdn_step_bytes(sizes: dict, slots: int) -> float:
    """Bytes the one-token rule alone (scope `gdn_step`) has to move in a step:
    every slot's state read once and written once, and each slot's q, k (dk a
    head), v and output (dv a head) beside it, float32."""
    hv = int(sizes["gdn_value_heads"])
    vectors = (layer_types(sizes).count("gated_delta") * hv
               * 2 * (int(sizes["gdn_key_dim"]) + int(sizes["gdn_value_dim"])))
    return float(slots) * (2 * state_elements(sizes) + vectors) * STATE_ITEMSIZE


def decode_step_bytes(sizes: dict, positions: Sequence[int], slots: int, weight_itemsize: int,
                      kv_itemsize: int) -> float:
    """`positions`: the sequence position of each ACTIVE lane's query; `slots`:
    the lanes the program updates, active or not."""
    dim = int(sizes["dim"])
    weights = (layer_weights(sizes) + int(sizes["num_image_tokens"]) * dim
               + len(positions) * dim) * weight_itemsize
    state = slots * 2 * (state_elements(sizes) * STATE_ITEMSIZE + taps_elements(sizes) * kv_itemsize)
    seen = sum(p + 1 for p in positions)
    kv = (layer_types(sizes).count("full") * seen * 2
          * int(sizes["heads"]) * int(sizes["dim_head"]) * kv_itemsize)
    return float(weights + state + kv)
