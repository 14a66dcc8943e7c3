"""The transformer core.

Capability parity with /root/reference/dalle_pytorch/transformer.py (builder,
layer wrappers, weight sharing, rotary scheme) and attention.py (full + sparse
variants), redesigned TPU-first:

* Every attention variant — full, axial_row, axial_col, conv_like, and
  block-sparse — is ONE dense attention op with a static pattern mask
  (ops/masks.py).  The reference itself proves the equivalence with its
  `optimize_for_inference` static-mask path; on TPU this keeps all FLOPs on
  the MXU, and the Pallas kernels (kernels/) skip fully-masked tiles.
* Execution engines: 'sequential', 'remat' (jax.checkpoint per layer — the
  idiomatic activation-memory saver), and 'reversible' (true RevNet streams
  via custom_vjp, models/reversible.py) replacing reversible.py's autograd
  Function.
* KV-cached decoding uses fixed-shape preallocated buffers indexed by a
  traced offset (no growing tensors, no deques) — the cached token-shift ring
  buffer replaces the reference's deque (transformer.py:138-153), and cached
  *sparse* attention works directly via pattern-mask rows (the reference had
  to replay the full prefix through NonCached wrappers).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dalle_pytorch_tpu.core.module import dropout as apply_dropout
from dalle_pytorch_tpu.core.module import (
    layer_norm,
    layer_norm_init,
    linear,
    linear_init,
)
from dalle_pytorch_tpu.core.rng import KeyChain
from dalle_pytorch_tpu.models.reversible import make_reversible_runner
from dalle_pytorch_tpu.ops.attention import attend
from dalle_pytorch_tpu.ops.masks import build_block_sparse_mask, build_pattern_mask  # noqa: F401 (public re-export)
from dalle_pytorch_tpu.ops.rotary import apply_rotary, build_dalle_rotary
from dalle_pytorch_tpu.ops.shift import token_shift


PATTERN_ATTN_TYPES = ("full", "axial_row", "axial_col", "conv_like", "sparse")
HYBRID_ATTN_TYPES = ("gated_full", "gated_window", "gated_delta", "mla")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    dim: int
    depth: int
    seq_len: int
    causal: bool = True
    heads: int = 8
    dim_head: int = 64
    ff_mult: int = 4
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    attn_types: Tuple[str, ...] = ("full",)
    image_fmap_size: Optional[int] = None
    stable: bool = False
    sandwich_norm: bool = False
    shift_tokens: bool = False
    rotary_emb: bool = True
    shared_attn_ids: Optional[Tuple[int, ...]] = None
    shared_ff_ids: Optional[Tuple[int, ...]] = None
    execution: str = "sequential"  # 'sequential' | 'remat' | 'reversible'
    # Selective rematerialization policy for execution='remat':
    #   'full'      — save nothing, recompute the whole layer (jax.checkpoint
    #                 default; the round-2 behavior, which re-ran the flash
    #                 forward kernel in the backward for nothing — the Pallas
    #                 backward only needs q,k,v + the saved out/lse)
    #   'flash'     — save flash attention out + logsumexp
    #   'flash_qkv' — also save the qkv projection (the flash backward's other
    #                 input), leaving only the ff up-projection to recompute
    #   'flash_qkv_ff' — also save the ff pre-activation: backward recomputes
    #                 no matmuls at all (max memory; for chips with headroom)
    remat_policy: str = "full"
    # lax.scan over stacked layer params instead of an unrolled python loop:
    # near-constant compile time in depth (essential for depth-64 configs).
    # Requires unshared layers; composes with execution='remat'.
    scan_layers: bool = False
    # 'auto' | 'flash' (Pallas) | 'xla' (dense masked) | 'ring' (explicit
    # ring attention over seq_shard_axis — full-attention layers only)
    attn_kernel: str = "auto"
    # sequence parallelism: shard activations' sequence dim over this mesh
    # axis between layers.  GSPMD inserts the attention collectives by
    # default; attn_kernel='ring' instead runs the explicit ppermute ring
    # (parallel/ring.py, O(n/P) memory fwd AND bwd) for 'full' layers —
    # the hand-tuned path for very long sequences
    seq_shard_axis: Optional[str] = None
    # pipeline parallelism: shard the stacked-layer (depth) axis over this
    # mesh axis and run the GPipe schedule (parallel/pipeline.py).  Requires
    # scan_layers; composes with dp/fsdp/tp (they stay GSPMD-automatic inside
    # each stage).  Falls back to plain scan with a warning when no mesh with
    # the axis is installed.
    pipeline_axis: Optional[str] = None
    # microbatches per pipeline step (None = largest of 2P / P dividing batch)
    pp_num_micro: Optional[int] = None
    # circular/interleaved pipeline: each device holds pp_interleave chunks
    # of depth/(pp*v) layers and microbatches loop the ring v times — bubble
    # time drops ~v-fold (see parallel/pipeline.py).  Needs num_micro >= pp.
    pp_interleave: int = 1
    conv_kernel_size: int = 5
    conv_dilation: int = 1
    sparse_block_size: int = 16
    sparse_num_random_blocks: Optional[int] = None
    # per-HEAD random block layouts for 'sparse' layers (DeepSpeed's sparse
    # attention draws a layout per head, attention.py:349-365; the default
    # shares one layout across heads).  Mask memory is heads x seq^2 per
    # distinct layout, so this is opt-in; unsupported with scan_layers (the
    # scan stacks masks for EVERY layer — x heads would multiply that).
    sparse_per_head: bool = False
    # flash-kernel grid selection, forwarded to kernels.flash_attention:
    # 'auto' runs the compacted (live-tiles-only, scalar-prefetch) grid when a
    # layer's tile grid has a dead step (causality or the pattern kills it),
    # the dense pl.when-skipping grid otherwise; 'compact' / 'dense' force.
    # Compacted and dense grids are bit-exact, so this is purely a
    # scheduling/DMA-traffic choice.
    attn_grid: str = "auto"
    # VFA-style global-max forward on the compacted grid (precompute row
    # maxima in a max-only pass, skip the per-tile accumulator rescale).
    # allclose — not bit-identical — to the online-softmax forward, so opt-in.
    attn_vfa: bool = False
    # sparse-aware cached/paged decode: pattern layers gather only the keys
    # their pattern permits (Kmax per step) instead of attending over the full
    # seq_len cache — what makes seq-4096 (fmap 64) sampling tractable.
    sparse_decode: bool = True
    # ---- the block as a parameter (hybrid trunks; training path only) ----
    # 'layernorm' (LayerNorm, the DALL-E block) | 'rmsnorm_zc' (zero-centred
    # RMSNorm: x / sqrt(mean(x^2) + norm_eps) * (1 + w)) | 'rmsnorm' (plain:
    # ... * w, w initialised 1)
    norm: str = "layernorm"
    norm_eps: float = 1e-6  # RMSNorm only; LayerNorm keeps its 1e-5
    layer_scale: bool = True  # the per-channel LayerScale on each branch
    # `gated_full` layers: key/value heads (None = heads), the share of
    # dim_head that is rotated (rotate-half, by stream position) and its base
    kv_heads: Optional[int] = None
    partial_rotary_factor: float = 1.0
    rotary_theta: float = 10000.0
    # ... and its YaRN rotary [`rope_type` 'yarn']: the factor (0 = the default
    # rotary), `original_max_position_embeddings`, beta_fast and beta_slow;
    # cos and sin take the attention factor 0.1 ln factor + 1
    rope_yarn_factor: float = 0.0
    rope_yarn_original_max: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    # `gated_window` layers (a `gated_full` layer over a causal band): query
    # heads (None = heads), the base of their rotary (the default one, on the
    # whole of dim_head), and the keys a query sees, itself included [`sliding_window`]
    window_heads: Optional[int] = None
    window_rotary_theta: float = 10000.0
    window: int = 0
    # the output gate of both: 'elementwise' (heads x dim_head) | 'per_head'
    # (one scalar a head) [`gating`]
    attn_gate: str = "elementwise"
    # `gated_delta` layers (models/gated_layers.py): key heads, value heads,
    # their widths, and the causal depthwise convolution's taps
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv_kernel: int = 4
    # beta = 2 sigmoid(b) [`linear_allow_neg_eigval`]: the rule's transition
    # I - beta k k^T has eigenvalues in (-1, 1]
    gdn_neg_eigval: bool = False
    # the Olmo family's block: `pre_norm` False puts no norm on a branch's
    # INPUT (with `sandwich_norm` the one norm sits on its output, inside the
    # residual: h = x + N(mix(x))); `qk_norm`: the pattern layers' q and k
    # pass an RMSNorm over the WHOLE inner width before the split into heads;
    # `attn_bias` [`attention_bias`]: the pattern layers' out-projection bias
    pre_norm: bool = True
    qk_norm: bool = False
    attn_bias: bool = True
    # routed feed-forward (models/moe.py): 0 experts = the dense GEGLU.  The
    # router is `moe_experts` wide; the layer holds `moe_experts_held` of them
    # (None = all) from `moe_first_expert` on: one rank's share of an
    # expert-parallel deployment
    moe_experts: int = 0
    moe_top_k: int = 1
    moe_ff_dim: int = 0
    moe_shared_ff_dim: int = 0
    moe_experts_held: Optional[int] = None
    moe_first_expert: int = 0
    # the router's form: 'softmax' (probabilities, top-k renormalised) |
    # 'sigmoid_bias' (`topk_method` noaux_tc with one group: scores
    # sigmoid(W_r x), the top-k of score + a balancing bias that no gradient
    # trains, weights = the chosen scores over their sum x `moe_routed_scale`
    # [`routed_scaling_factor`]); the bias moves by +-`moe_bias_rate` a step
    # toward the under-loaded experts (models/moe.balance_bias)
    moe_router: str = "softmax"
    moe_routed_scale: float = 1.0
    moe_bias_rate: float = 0.001
    # the shared expert behind a sigmoid gate (True) or added as it is
    moe_shared_gated: bool = True
    # the first `dense_layers` [`first_k_dense_replace`] layers' feed-forward
    # is a dense SwiGLU of width `dense_ff_dim` [`intermediate_size`], no router
    dense_layers: int = 0
    dense_ff_dim: int = 0
    # `mla` layers (models/latent_attention.py): ranks of the query and the
    # key/value latents [`q_lora_rank`, `kv_lora_rank`], a head's key width
    # without and with rotary [`qk_nope_head_dim`, `qk_rope_head_dim`] and its
    # value width [`v_head_dim`]
    mla_q_rank: int = 0
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0

    @property
    def inner_dim(self) -> int:
        return self.heads * self.dim_head

    @property
    def kv_heads_resolved(self) -> int:
        return self.heads if self.kv_heads is None else self.kv_heads

    @property
    def moe_held(self) -> int:
        return self.moe_experts if self.moe_experts_held is None else self.moe_experts_held

    @property
    def hybrid(self) -> bool:
        """Anything of the block that only the full-sequence training path
        computes (see `refuse_hybrid`): `gated_delta` / `gated_full` /
        `gated_window` / `mla` layers, routed experts, leading dense SwiGLU
        layers, an RMSNorm."""
        return (self.moe_experts > 0 or self.norm != "layernorm" or self.dense_layers > 0
                or any(t in HYBRID_ATTN_TYPES for t in self.attn_types))

    @property
    def unserved(self) -> bool:
        """What of the block no cached or paged entry point computes (see
        `refuse_hybrid`): `gated_full` / `gated_window` / `mla` layers and
        routed experts.
        `gated_delta` layers (a recurrent state and convolution taps per
        sequence beside the K/V cache), dense SwiGLU layers and the RMSNorms
        are served."""
        return self.moe_experts > 0 or any(
            t in ("gated_full", "gated_window", "mla") for t in self.attn_types)

    @property
    def recurrent(self) -> bool:
        """Some layer keeps a per-sequence state where the others keep keys."""
        return "gated_delta" in self.attn_types

    @property
    def kv_layers(self) -> int:
        """Layers that keep keys and values (blocks of the paged pool)."""
        return sum(self.attn_types[i % len(self.attn_types)] != "gated_delta"
                   for i in range(self.depth))

    def ff_type(self, index: int) -> str:
        """What layer `index`'s feed-forward IS: 'swiglu' (a leading dense
        layer), 'moe' (routed experts) or 'geglu' (the DALL-E block's)."""
        if index < self.dense_layers:
            return "swiglu"
        return "moe" if self.moe_experts else "geglu"

    @property
    def text_len(self) -> int:
        """Layout text length (bos + text) = seq_len + 1 - fmap**2."""
        assert self.image_fmap_size is not None
        return self.seq_len + 1 - self.image_fmap_size ** 2


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    index: int
    attn_type: str
    attn_id: str
    ff_id: str


def derive_layer_specs(cfg: TransformerConfig) -> List[LayerSpec]:
    """Cycle attn_types over depth and resolve weight-sharing ids, mirroring
    the reference builder (transformer.py:236-277) including its
    type-consistency check for shared layers."""
    attn_ids = cfg.shared_attn_ids or tuple(range(cfg.depth))
    ff_ids = cfg.shared_ff_ids or tuple(range(cfg.depth))
    specs = []
    seen_attn_types: Dict[str, str] = {}
    for i in range(cfg.depth):
        attn_type = cfg.attn_types[i % len(cfg.attn_types)]
        if attn_type not in PATTERN_ATTN_TYPES + HYBRID_ATTN_TYPES:
            raise ValueError(f'attention type "{attn_type}" is not valid')
        attn_id = str(attn_ids[i % len(attn_ids)])
        ff_id = str(ff_ids[i % len(ff_ids)])
        if attn_id in seen_attn_types and seen_attn_types[attn_id] != attn_type:
            raise ValueError(
                f"attn_types do not match shared_attn_ids (ind = {i}, "
                f'attn_type = "{attn_type}", reused = "{seen_attn_types[attn_id]}")'
            )
        seen_attn_types[attn_id] = attn_type
        specs.append(LayerSpec(i, attn_type, attn_id, ff_id))
    return specs


def hybrid_refusal(cfg: TransformerConfig, what: str) -> NotImplementedError:
    """The one error of every entry point that cannot run a hybrid block."""
    return NotImplementedError(
        f"{what} does not support this block (attn_types {cfg.attn_types}, "
        f"norm {cfg.norm!r}, {cfg.moe_experts} routed experts, {cfg.dense_layers} "
        "leading dense layers): gated_full / gated_window / mla layers and routed experts run on "
        "the training path only (execution 'sequential' or 'remat', scan_layers off, "
        "no pipeline); gated_delta layers, dense SwiGLU layers and RMSNorm are also "
        "served (prefill, decode_step, the paged pool, GenerationEngine), with no "
        "speculation, int8 pool or handed-over prefill of a recurrent state")


def refuse_hybrid(cfg: TransformerConfig, what: str, recurrent_state: bool = True) -> None:
    """Raise `hybrid_refusal` from a cached or paged entry point for what it
    cannot serve: `gated_full` / `gated_window` / `mla` layers (a gated,
    windowed or latent cache and the absorbed decode form of `mla`) and
    routed experts (ROADMAP.md, Queue 2); a wrong picture is worse than
    none.  `recurrent_state` False: the
    caller cannot carry `gated_delta`'s per-sequence state either (it rolls
    tokens back, quantizes the pool or hands a prefill over)."""
    if cfg.unserved or (cfg.recurrent and not recurrent_state):
        raise hybrid_refusal(cfg, what)


def _note_hybrid_layers(cfg: TransformerConfig, specs, gmm_paths: Dict[str, int],
                        tokens: int) -> None:
    """Runs while a forward is TRACED: what of the hybrid block the program
    holds, into the metrics registry (one count per traced forward).  The
    grouped products are counted as `moe.grouped_matmul` is traced: three a
    layer, in the loop body that walks the pairs' chunks."""
    from dalle_pytorch_tpu.observability import metrics as obs_metrics

    obs_metrics.counter("train/gdn_layers").inc(
        sum(s.attn_type == "gated_delta" for s in specs))
    obs_metrics.counter("train/mla_layers").inc(sum(s.attn_type == "mla" for s in specs))
    obs_metrics.counter("train/window_layers").inc(sum(s.attn_type == "gated_window" for s in specs))
    obs_metrics.counter("train/full_attn_layers").inc(sum(s.attn_type == "gated_full" for s in specs))
    obs_metrics.counter("train/dense_ff_layers").inc(
        sum(cfg.ff_type(s.index) == "swiglu" for s in specs))
    routed = sum(cfg.ff_type(s.index) == "moe" for s in specs)  # by what each layer IS
    if routed:
        obs_metrics.counter("train/moe_layers").inc(routed)
        obs_metrics.counter("train/moe_experts_held").inc(cfg.moe_held * routed)
        from dalle_pytorch_tpu.models.moe import pair_rows

        obs_metrics.counter("train/moe_pair_rows").inc(pair_rows(cfg, tokens) * routed)
        obs_metrics.counter("train/moe_gmm_kernel_calls").inc(gmm_paths["kernel"])
        obs_metrics.counter("train/moe_gmm_fallback_calls").inc(gmm_paths["fallback"])


_REMAT_SAVE_NAMES = {
    "flash": ("flash_out", "flash_lse"),
    "flash_qkv": ("flash_out", "flash_lse", "attn_qkv"),
    "flash_qkv_ff": ("flash_out", "flash_lse", "attn_qkv", "ff_pre"),
}


def _remat_wrap(fn, cfg: "TransformerConfig"):
    """jax.checkpoint with the config's selective save policy (see
    TransformerConfig.remat_policy)."""
    if cfg.remat_policy in (None, "full"):
        return jax.checkpoint(fn)
    if cfg.remat_policy not in _REMAT_SAVE_NAMES:
        raise ValueError(
            f"remat_policy {cfg.remat_policy!r} is not valid; choose from "
            f"'full', {', '.join(map(repr, _REMAT_SAVE_NAMES))}"
        )
    names = _REMAT_SAVE_NAMES[cfg.remat_policy]
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*names)
    )


def _layerscale_eps(layer_one_indexed: int) -> float:
    if layer_one_indexed <= 18:
        return 0.1
    if layer_one_indexed <= 24:
        return 1e-5
    return 1e-6


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def norm_init(cfg: TransformerConfig) -> dict:
    """The block's norm over `dim` (cfg.norm)."""
    if cfg.norm == "layernorm":
        return layer_norm_init(cfg.dim)
    if cfg.norm not in ("rmsnorm_zc", "rmsnorm"):
        raise ValueError(
            f"norm {cfg.norm!r} is not valid; choose 'layernorm', 'rmsnorm_zc' or 'rmsnorm'")
    from dalle_pytorch_tpu.models.gated_layers import rms_norm_init

    return rms_norm_init(cfg.dim, zero_centered=cfg.norm == "rmsnorm_zc")


def apply_norm(cfg: TransformerConfig, params: dict, x):
    if cfg.norm == "layernorm":
        return layer_norm(params, x)
    from dalle_pytorch_tpu.models.gated_layers import rms_norm

    return rms_norm(params, x, cfg.norm_eps, zero_centered=cfg.norm == "rmsnorm_zc")


def init_transformer(key: jax.Array, cfg: TransformerConfig) -> dict:
    keys = KeyChain(key)
    specs = derive_layer_specs(cfg)

    shared_attn: Dict[str, dict] = {}
    shared_ff: Dict[str, dict] = {}
    layers = []
    for spec in specs:
        if spec.attn_type in HYBRID_ATTN_TYPES and spec.attn_id not in shared_attn:
            from dalle_pytorch_tpu.models import gated_layers

            from dalle_pytorch_tpu.models.latent_attention import init_mla

            init = {"gated_delta": gated_layers.init_gated_delta,
                    "gated_full": gated_layers.init_gated_full,
                    "gated_window": functools.partial(gated_layers.init_gated_full, kind="gated_window"),
                    "mla": init_mla}[spec.attn_type]
            shared_attn[spec.attn_id] = init(keys.next(), cfg)
        ff_type = cfg.ff_type(spec.index)
        if ff_type == "moe" and spec.ff_id not in shared_ff:
            from dalle_pytorch_tpu.models.moe import init_moe

            shared_ff[spec.ff_id] = init_moe(keys.next(), cfg)
        if ff_type == "swiglu" and spec.ff_id not in shared_ff:
            shared_ff[spec.ff_id] = {
                "wg": linear_init(keys.next(), cfg.dim, cfg.dense_ff_dim, bias=False),
                "wu": linear_init(keys.next(), cfg.dim, cfg.dense_ff_dim, bias=False),
                "wd": linear_init(keys.next(), cfg.dense_ff_dim, cfg.dim, bias=False),
            }
        if spec.attn_id not in shared_attn:
            # qkv columns are HEAD-MAJOR: [h0:(q|k|v), h1:(q|k|v), ...] — the
            # head axis carries the tp sharding, so splitting into q/k/v is
            # shard-local (Megatron layout; a [q|k|v]-blocked layout makes the
            # partitioner exchange half-heads between tp shards with
            # collective-permutes on every layer)
            shared_attn[spec.attn_id] = {
                "qkv": linear_init(keys.next(), cfg.dim, cfg.inner_dim * 3, bias=False),
                "out": linear_init(keys.next(), cfg.inner_dim, cfg.dim, bias=cfg.attn_bias),
            }
            if cfg.qk_norm:
                from dalle_pytorch_tpu.models.gated_layers import rms_norm_init

                # over the whole inner width, head-major like the qkv columns
                for name in ("q_norm", "k_norm"):
                    shared_attn[spec.attn_id][name] = rms_norm_init(
                        cfg.inner_dim, zero_centered=False)
        if spec.ff_id not in shared_ff:
            # GEGLU as two column-parallel projections (values / gates) — the
            # fused [a|g] layout splits across tp shards (same exchange
            # problem as qkv); two matrices keep the split out of the graph
            shared_ff[spec.ff_id] = {
                "w1": linear_init(keys.next(), cfg.dim, cfg.dim * cfg.ff_mult),
                "w1g": linear_init(keys.next(), cfg.dim, cfg.dim * cfg.ff_mult),
                "w2": linear_init(keys.next(), cfg.dim * cfg.ff_mult, cfg.dim),
            }
        eps = _layerscale_eps(spec.index + 1)
        layer = {"attn_norm": norm_init(cfg), "ff_norm": norm_init(cfg)} if cfg.pre_norm else {}
        if cfg.layer_scale:
            layer["attn_scale"] = jnp.full((1, 1, cfg.dim), eps, jnp.float32)
            layer["ff_scale"] = jnp.full((1, 1, cfg.dim), eps, jnp.float32)
        if cfg.sandwich_norm:
            layer["attn_norm_out"] = norm_init(cfg)
            layer["ff_norm_out"] = norm_init(cfg)
        layers.append(layer)

    return {"shared_attn": shared_attn, "shared_ff": shared_ff, "layers": layers}


def migrate_transformer_layout(tparams: dict, heads: int, dim_head: int) -> dict:
    """Upgrade a pre-round-5 transformer param tree to the tp-local layouts
    (head-major qkv columns, two-matrix GEGLU — see init_transformer).

    Old trees are detected by the absence of 'w1g' in shared_ff; returns the
    input unchanged when already current.  Without this, resuming an old
    self-format checkpoint would crash with a bare KeyError('w1g') at trace
    time — or worse, a partial fix would silently scramble q/k/v across
    heads, since the qkv matrix has identical shape in both layouts."""
    shared_ff = tparams.get("shared_ff", {})
    if not shared_ff or all("w1g" in ff for ff in shared_ff.values()):
        return tparams
    import numpy as np

    out = dict(tparams)
    new_attn = {}
    for aid, attn in tparams["shared_attn"].items():
        attn = dict(attn)
        w = np.asarray(attn["qkv"]["w"])  # (dim, 3*h*dh), [q|k|v]-blocked
        w = w.reshape(w.shape[0], 3, heads, dim_head)
        w = w.transpose(0, 2, 1, 3).reshape(w.shape[0], -1)  # head-major
        attn["qkv"] = {**attn["qkv"], "w": jnp.asarray(w)}
        new_attn[aid] = attn
    out["shared_attn"] = new_attn
    new_ff = {}
    for fid, ff in shared_ff.items():
        ff = dict(ff)
        w1 = ff.pop("w1")
        half = np.asarray(w1["w"]).shape[-1] // 2
        new_w1 = {"w": jnp.asarray(np.asarray(w1["w"])[:, :half])}
        new_w1g = {"w": jnp.asarray(np.asarray(w1["w"])[:, half:])}
        if "b" in w1:
            new_w1["b"] = jnp.asarray(np.asarray(w1["b"])[:half])
            new_w1g["b"] = jnp.asarray(np.asarray(w1["b"])[half:])
        ff["w1"], ff["w1g"] = new_w1, new_w1g
        new_ff[fid] = ff
    out["shared_ff"] = new_ff
    return out


def transformer_rotary(cfg: TransformerConfig) -> Optional[jnp.ndarray]:
    """The DALL-E rotary table of the pattern layers (the hybrid layers rotate
    by stream position, or not at all, themselves)."""
    if not cfg.rotary_emb or all(t in HYBRID_ATTN_TYPES for t in cfg.attn_types):
        return None
    return build_dalle_rotary(cfg.dim_head, cfg.text_len, cfg.image_fmap_size)


def _pattern_for(cfg: TransformerConfig, attn_type: str, seed: int = 0):
    """(seq_len, seq_len) NUMPY pattern mask or None for 'full'.

    Kept as numpy (not jnp) deliberately: under jit, any jnp op on a constant
    yields a tracer, which would defeat the Pallas kernel's trace-time
    tile-liveness derivation.  Numpy slices stay concrete; conversion to a
    device constant happens at the op boundary.

    `seed` picks the random block layout for 'sparse' (see _pattern_seed)."""
    from dalle_pytorch_tpu.ops.masks import (
        _block_sparse_mask_np,
        _block_sparse_mask_np_heads,
        _pattern_mask_np,
    )

    if attn_type == "full" or attn_type in HYBRID_ATTN_TYPES:
        return None
    if attn_type == "sparse":
        nr = cfg.sparse_num_random_blocks
        if nr is None:
            nr = cfg.seq_len // cfg.sparse_block_size // 4
        if cfg.sparse_per_head:
            return _block_sparse_mask_np_heads(
                cfg.seq_len, cfg.image_fmap_size, cfg.sparse_block_size,
                nr, 4, seed, cfg.heads,
            )
        return _block_sparse_mask_np(
            cfg.seq_len, cfg.image_fmap_size, cfg.sparse_block_size, nr, 4, seed
        )
    return _pattern_mask_np(
        attn_type, cfg.seq_len, cfg.image_fmap_size, cfg.conv_kernel_size, cfg.conv_dilation
    )


def _pattern_seed(spec: LayerSpec) -> int:
    """Random-layout seed for a 'sparse' layer: keyed by the shared-attention
    id, so the layout is a property of the attention *module*.  This mirrors
    the reference, where each SparseSelfAttention instance draws its own
    random blocks at module init (attention.py:349-365) — distinct layers get
    distinct layouts (union coverage across depth), while weight-shared layers
    (shared_attn_ids) reuse the instance and hence its layout."""
    try:
        return int(spec.attn_id)
    except ValueError:
        import zlib

        # crc32, NOT hash(): str hashing is randomized per process
        # (PYTHONHASHSEED) — a per-process layout would silently diverge
        # across multi-host replicas and across checkpoint resumes
        return zlib.crc32(spec.attn_id.encode())


def _pattern_key(spec: LayerSpec) -> Tuple[str, int]:
    """Dict key identifying a layer's pattern (type + layout seed)."""
    return (spec.attn_type, _pattern_seed(spec) if spec.attn_type == "sparse" else 0)


def spec_patterns(cfg: TransformerConfig, specs: List[LayerSpec]) -> Dict[Tuple[str, int], object]:
    """One pattern mask per DISTINCT (attn_type, seed) across the given specs
    (a depth-64 model cycles 4 types — build 4 masks, not 64)."""
    return {
        key: _pattern_for(cfg, key[0], key[1])
        for key in dict.fromkeys(_pattern_key(s) for s in specs)
    }


# ---------------------------------------------------------------------------
# branch functions (full-sequence mode)
# ---------------------------------------------------------------------------

def _split_heads(x, heads):
    b, n, _ = x.shape
    return x.reshape(b, n, heads, -1).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)


def _qkv_heads(shared, cfg, x, ang, checkpoint: bool = False):
    """Project to qkv and split into (q, k, v), each (b, h, n_x, dh).

    Head-major column layout (see init_transformer): the reshape puts tp
    sharding on the head axis, so the split is shard-local, and the rotary
    rotation (`ang`: (n_x, rot) or None) runs as ONE pass over q,k,v."""
    b, n_x, _ = x.shape
    qkv = linear(shared["qkv"], x)
    if checkpoint:
        qkv = checkpoint_name(qkv, "attn_qkv")
    qkv = qkv.reshape(b, n_x, cfg.heads, 3, cfg.dim_head).transpose(0, 2, 3, 1, 4)
    if ang is not None:
        qkv = apply_rotary(ang, qkv)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q, k = _whole_width_norm(shared["q_norm"], cfg, q), _whole_width_norm(shared["k_norm"], cfg, k)
    return q, k, v


def _whole_width_norm(params, cfg, t):
    """RMSNorm of a token's q (or k) over ALL heads' channels at once: t
    (b, h, n, dh), the weight (h * dh,) head-major.  Float32 statistics."""
    t32 = t.astype(jnp.float32)
    ms = jnp.mean(t32 * t32, axis=(1, 3), keepdims=True)
    w = params["w"].astype(jnp.float32).reshape(1, cfg.heads, 1, cfg.dim_head)
    return (t32 * jax.lax.rsqrt(ms + cfg.norm_eps) * w).astype(t.dtype)


def _use_flash(cfg, n: int, key_mask) -> bool:
    # key_mask no longer forces the dense path: the Pallas kernel takes the
    # per-batch key-padding rows directly
    if cfg.attn_kernel in ("xla", "ring"):
        return False
    if cfg.seq_shard_axis is not None:
        return False  # GSPMD partitions the XLA attention; pallas_call can't split seq
    if n % 128 != 0:
        return False
    if cfg.attn_kernel == "flash":
        return True
    return jax.default_backend() == "tpu"  # 'auto'


def _ambient_mesh():
    """The mesh installed by the enclosing `with mesh:` block (the train step
    enters it), or None outside one.  Framework meshes are ContextMeshes that
    publish themselves on enter, so no jax-private state is read."""
    from dalle_pytorch_tpu.parallel.mesh import active_mesh

    return active_mesh()


def _kernel_mesh(cfg):
    """The ambient mesh the flash kernels must be shard_mapped over, or None.
    Inside the pipeline's own (manual-pp) shard_map the kernel is left as it
    is — nesting a second manual region there is not supported."""
    return None if cfg.pipeline_axis is not None else _ambient_mesh()


def _use_ring(cfg, pattern, key_mask) -> bool:
    return (
        cfg.attn_kernel == "ring"
        and cfg.seq_shard_axis is not None
        # 2-D static patterns ride the ring (each device holds its row/col
        # mask blocks); per-head (3-D) patterns and padded-key masks fall
        # back to the GSPMD dense path
        and (pattern is None or getattr(pattern, "ndim", 2) == 2)
        and key_mask is None
    )


@jax.named_scope("attn")
def _attention_full(shared, cfg, x, pattern, rotary, key_mask, dkey, live=None,
                    tables=None):
    b, n, _ = x.shape
    q, k, v = _qkv_heads(
        shared, cfg, x, None if rotary is None else rotary[:n], checkpoint=True
    )

    if _use_ring(cfg, pattern, key_mask):
        mesh = _ambient_mesh()
        if mesh is None:
            # the user explicitly asked for the ring kernel; falling back to
            # the dense GSPMD path silently would be an O(n) memory surprise
            import warnings

            warnings.warn(
                "attn_kernel='ring' but no mesh is installed (forward called "
                "outside a `with mesh:` block) — falling back to dense GSPMD "
                "attention",
                stacklevel=2,
            )
        else:
            from dalle_pytorch_tpu.parallel.ring import ring_attention

            out = ring_attention(
                q, k, v, mesh, causal=cfg.causal,
                axis_name=cfg.seq_shard_axis, scale=cfg.dim_head ** -0.5,
                mask=None if pattern is None else jnp.asarray(pattern[:n, :n]),
            )
            out = linear(shared["out"], _merge_heads(out))
            return apply_dropout(dkey, out, cfg.attn_dropout)

    if _use_flash(cfg, n, key_mask):
        from dalle_pytorch_tpu.kernels.flash_attention import flash_attention

        pm = pattern[..., :n, :n] if pattern is not None else None
        km = key_mask[:, :n] if key_mask is not None else None
        out = flash_attention(
            q, k, v, mask=pm, causal=cfg.causal, scale=cfg.dim_head ** -0.5,
            live=live, key_mask=km, grid=cfg.attn_grid, tables=tables,
            vfa=cfg.attn_vfa, mesh=_kernel_mesh(cfg),
        )
        out = linear(shared["out"], _merge_heads(out))
        return apply_dropout(dkey, out, cfg.attn_dropout)

    q = q * (cfg.dim_head ** -0.5)

    mask = None
    if cfg.causal:
        i = jnp.arange(n)[:, None]
        j = jnp.arange(n)[None, :]
        mask = j <= i
    if pattern is not None:
        pm = pattern[..., :n, :n]  # (n, n) or per-head (h, n, n)
        mask = pm if mask is None else (mask & pm)
    if mask is not None:
        mask = mask[None] if mask.ndim == 3 else mask[None, None]
    if key_mask is not None:
        km = key_mask[:, None, None, :n]
        mask = km if mask is None else (mask & km)

    out = attend(q, k, v, mask=mask, stable=cfg.stable)
    out = linear(shared["out"], _merge_heads(out))
    return apply_dropout(dkey, out, cfg.attn_dropout)


@jax.named_scope("ff")
def _feed_forward(shared, cfg, x, dkey):
    # GEGLU via two column-parallel projections (see init_transformer) —
    # both carry the 'ff_pre' checkpoint name so the flash_qkv_ff remat
    # policy saves the full pre-activation as before
    a = checkpoint_name(linear(shared["w1"], x), "ff_pre")
    gates = checkpoint_name(linear(shared["w1g"], x), "ff_pre")
    h = a * jax.nn.gelu(gates, approximate=False)  # exact erf, as the reference's F.gelu
    h = apply_dropout(dkey, h, cfg.ff_dropout)
    return linear(shared["w2"], h)


@jax.named_scope("attn")
def _attention_prefill(shared, cfg, layer_cache, x, pattern, rotary, key_mask):
    """Length-n prefix attention that also fills the KV cache from offset 0.
    Mutates layer_cache['k'/'v'] (caller passes a fresh dict copy)."""
    b, n, _ = x.shape
    q, k, v = _qkv_heads(shared, cfg, x, None if rotary is None else rotary[:n])
    with jax.named_scope("kv_write"):
        layer_cache["k"] = jax.lax.dynamic_update_slice(
            layer_cache["k"], k.astype(layer_cache["k"].dtype), (0, 0, 0, 0)
        )
        layer_cache["v"] = jax.lax.dynamic_update_slice(
            layer_cache["v"], v.astype(layer_cache["v"].dtype), (0, 0, 0, 0)
        )
    if _use_flash(cfg, n, key_mask):
        # generation prefill on the kernel path: the dense fallback below
        # materializes a (b, h, n, n) mask — O(n^2) HBM per prefill at
        # sampling time, which the kernel's causal/pattern/key-mask inputs
        # make unnecessary
        from dalle_pytorch_tpu.kernels.flash_attention import flash_attention

        pm = pattern[..., :n, :n] if pattern is not None else None
        km = key_mask[:, :n] if key_mask is not None else None
        out = flash_attention(
            q, k, v, mask=pm, causal=True, scale=cfg.dim_head ** -0.5,
            key_mask=km, grid=cfg.attn_grid, vfa=cfg.attn_vfa,
            mesh=_kernel_mesh(cfg),
        )
        return linear(shared["out"], _merge_heads(out))
    q = q * (cfg.dim_head ** -0.5)
    i_idx = jnp.arange(n)[:, None]
    j_idx = jnp.arange(n)[None, :]
    mask = j_idx <= i_idx
    if pattern is not None:
        mask = mask & pattern[..., :n, :n]  # per-head patterns broadcast
    mask = mask[None] if mask.ndim == 3 else mask[None, None]
    if key_mask is not None:
        mask = mask & key_mask[:, None, None, :n]
    out = attend(q, k, v, mask=mask, stable=cfg.stable)
    return linear(shared["out"], _merge_heads(out))


def _residual_branch(
    cfg,
    wrap: dict,
    attn_params: dict,
    ff_params: dict,
    x: jnp.ndarray,
    kind: str,
    mode: str = "full",  # 'full' | 'prefill' | 'decode'
    rotary=None,
    pattern=None,
    key_mask=None,
    dkey=None,
    live=None,
    tables=None,
    decode_tab=None,
    layer_cache: Optional[dict] = None,
    offset=None,
    text_mode: bool = False,
    attn_type: str = "full",
    aux: Optional[dict] = None,
    ff_type: Optional[str] = None,  # TransformerConfig.ff_type of the layer; None = the trunk's one kind
):
    """THE residual branch — PreShiftToken? -> PreNorm -> attn/ff -> sandwich?
    -> LayerScale — shared by full-sequence apply (unrolled and scanned), prefill and
    single-token cached decode (the reference re-implements this composition
    per wrapper; here every mode runs the one definition).  Returns
    (branch output, updated layer cache or None)."""
    h = x
    if cfg.pre_norm:
        with jax.named_scope("norm"):
            h = apply_norm(cfg, wrap[f"{kind}_norm"], x)
    if cfg.shift_tokens:
        if mode == "decode":
            if text_mode:
                # token shift is the identity for text-only sequences
                # (ops/shift.py:45-47 — n < text_len passes through), so a
                # text-region decode step skips the cached shift entirely
                pass
            else:
                layer_cache = dict(layer_cache)
                h, layer_cache[f"shift_{kind}"] = _shift_cached_step(
                    cfg, layer_cache[f"shift_{kind}"], h, offset
                )
        else:
            if mode == "prefill":
                # raw (normed, pre-shift) values feed the ring buffer
                layer_cache = dict(layer_cache)
                layer_cache[f"shift_{kind}"] = _fill_ring(cfg, layer_cache[f"shift_{kind}"], h)
            with jax.named_scope("token_shift"):
                h = token_shift(h, cfg.seq_len, cfg.image_fmap_size)
    if kind == "attn" and attn_type == "gated_delta" and mode != "full":
        h, layer_cache = _gated_delta_cached(attn_params, cfg, layer_cache, h, mode)
    elif kind == "attn" and attn_type in HYBRID_ATTN_TYPES:
        h = _hybrid_mixer(attn_params, cfg, h, attn_type)
    elif kind == "ff" and ff_type == "swiglu":
        h = _dense_swiglu(ff_params, h)
    elif kind == "ff" and cfg.moe_experts:
        h = _routed_feed_forward(ff_params, cfg, h, aux)
    elif kind == "attn":
        if mode == "full":
            h = _attention_full(
                attn_params, cfg, h, pattern, rotary, key_mask, dkey, live=live,
                tables=tables,
            )
        elif mode == "prefill":
            layer_cache = dict(layer_cache)
            h = _attention_prefill(
                attn_params, cfg, layer_cache, h, pattern, rotary, key_mask)
        else:
            layer_cache = dict(layer_cache)
            h, (layer_cache["k"], layer_cache["v"]) = _attention_cached(
                attn_params, cfg, layer_cache, h, pattern, rotary, offset,
                decode_tab=decode_tab,
            )
    else:
        h = _feed_forward(ff_params, cfg, h, dkey)
    with jax.named_scope("norm"):
        if cfg.sandwich_norm:
            h = apply_norm(cfg, wrap[f"{kind}_norm_out"], h)
        if cfg.layer_scale:
            h = h * wrap[f"{kind}_scale"].astype(h.dtype)
        return h, layer_cache


@jax.named_scope("attn")
def _hybrid_mixer(shared, cfg, x, attn_type: str):
    """`gated_delta` / `gated_full` / `gated_window` (models/gated_layers.py),
    `mla` (models/latent_attention.py), full sequence."""
    from dalle_pytorch_tpu.models import gated_layers

    if attn_type == "gated_delta":
        return gated_layers.gated_delta_net(shared, cfg, x)
    use_flash, mesh = _use_flash(cfg, x.shape[1], None), _kernel_mesh(cfg)
    if attn_type == "mla":
        from dalle_pytorch_tpu.models.latent_attention import mla_attention

        return mla_attention(shared, cfg, x, use_flash=use_flash, mesh=mesh)
    if attn_type == "gated_window":
        with jax.named_scope("swa"):
            return gated_layers.gated_full_attention(shared, cfg, x, use_flash=use_flash, mesh=mesh,
                                                     kind="gated_window")
    return gated_layers.gated_full_attention(shared, cfg, x, use_flash=use_flash, mesh=mesh)


@jax.named_scope("attn")
def _gated_delta_cached(shared, cfg, layer_cache, x, mode: str):
    """`gated_delta` on a cache entry {"state", "taps"}: a prefill computes the
    full sequence and leaves both behind, a decode step advances them by one
    token of every row.  Returns (out, the new cache entry)."""
    from dalle_pytorch_tpu.models import gated_layers

    if mode == "prefill":
        out, carried = gated_layers.gated_delta_net(shared, cfg, x, return_state=True)
    else:
        out, carried = gated_layers.gated_delta_step(shared, cfg, x, layer_cache)
    return out, dict(layer_cache, state=carried["state"],
                     taps=carried["taps"].astype(layer_cache["taps"].dtype))


@jax.named_scope("ff")
def _dense_swiglu(shared, x):
    """A leading dense layer's feed-forward: W_d (silu(W_g x) * (W_u x))."""
    with jax.named_scope("dense_ff"):
        return linear(shared["wd"], jax.nn.silu(linear(shared["wg"], x)) * linear(shared["wu"], x))


@jax.named_scope("ff")
def _routed_feed_forward(shared, cfg, x, aux: Optional[dict]):
    """Routed experts (models/moe.py).  `aux`, the caller's dict, collects
    the layer's load scalars and the grouped products' path count."""
    from dalle_pytorch_tpu.models.moe import moe_feed_forward

    paths = None if aux is None else aux.setdefault("gmm_paths", {"kernel": 0, "fallback": 0})
    out, stats = moe_feed_forward(shared, cfg, x, path_tally=paths)
    if aux is not None:
        aux.setdefault("moe_stats", []).append(stats)
    return out


def _branch(params, cfg, spec, x, kind, rotary, pattern, key_mask, dkey, aux=None):
    """Full-sequence residual branch addressed by layer spec."""
    out, _ = _residual_branch(
        cfg,
        params["layers"][spec.index],
        params["shared_attn"][spec.attn_id],
        params["shared_ff"][spec.ff_id],
        x,
        kind,
        rotary=rotary,
        pattern=pattern,
        key_mask=key_mask,
        dkey=dkey,
        attn_type=spec.attn_type,
        aux=aux,
        ff_type=cfg.ff_type(spec.index),
    )
    return out


# ---------------------------------------------------------------------------
# full-sequence apply
# ---------------------------------------------------------------------------

def apply_transformer(
    params: dict,
    cfg: TransformerConfig,
    x: jnp.ndarray,
    key_mask: Optional[jnp.ndarray] = None,
    dropout_key: Optional[jax.Array] = None,
    return_stats: bool = False,
):
    """x: (batch, n, dim) with n <= seq_len.  Full-sequence (training) mode.
    `return_stats`: also return the routed layers' load scalars, averaged
    over the layers ({} for a dense feed-forward), and under a bias-balanced
    router `moe_choice_counts`: {ff_id: (moe_experts,) tokens that chose each
    expert in that layer}, what the bias's rule reads."""
    if cfg.hybrid and (cfg.scan_layers or cfg.pipeline_axis is not None
                       or cfg.seq_shard_axis is not None
                       or cfg.execution not in ("sequential", "remat")):
        raise hybrid_refusal(cfg, f"apply_transformer(execution={cfg.execution!r}, "
                                  f"scan_layers={cfg.scan_layers}, pipeline_axis={cfg.pipeline_axis!r}, "
                                  f"seq_shard_axis={cfg.seq_shard_axis!r})")
    if cfg.pipeline_axis is not None and not cfg.scan_layers:
        raise ValueError(
            "pipeline_axis requires scan_layers=True (pipeline stages shard "
            "the stacked layer params)"
        )
    if cfg.pipeline_axis is not None and cfg.execution == "reversible":
        # the reversible runner returns before the scan path, so pp would be
        # silently ignored and every stage would compute a full replica
        raise ValueError(
            "pipeline_axis is not supported with execution='reversible'; use "
            "execution='remat' (or 'sequential') with scan_layers=True"
        )
    specs = derive_layer_specs(cfg)
    rotary = transformer_rotary(cfg)
    patterns = spec_patterns(cfg, specs)

    has_dropout = (cfg.attn_dropout > 0 or cfg.ff_dropout > 0) and dropout_key is not None
    if has_dropout:
        layer_keys = jax.random.split(dropout_key, cfg.depth * 2).reshape(cfg.depth, 2, -1)
    else:
        layer_keys = None

    def seq_constraint(x):
        if cfg.seq_shard_axis is None:
            return x
        from jax.sharding import PartitionSpec

        return jax.lax.with_sharding_constraint(
            x, PartitionSpec(None, cfg.seq_shard_axis, None)
        )

    def branch(spec, x, kind, dkey, aux=None):
        return _branch(params, cfg, spec, x, kind, rotary, patterns[_pattern_key(spec)],
                       key_mask, dkey, aux=aux)

    if cfg.execution == "reversible":
        f_fns = []
        g_fns = []
        for spec in specs:
            f_fns.append(
                lambda p, h, k, s=spec: _branch(
                    p, cfg, s, h, "attn", rotary, patterns[_pattern_key(s)], key_mask,
                    k if has_dropout else None,
                )
            )
            g_fns.append(
                lambda p, h, k, s=spec: _branch(
                    p, cfg, s, h, "ff", rotary, patterns[_pattern_key(s)], key_mask,
                    k if has_dropout else None,
                )
            )
        runner = make_reversible_runner(f_fns, g_fns)
        keys = (
            layer_keys
            if layer_keys is not None
            else jnp.zeros((cfg.depth, 2, 2), jnp.uint32)
        )
        out = runner(params, x, keys)
        return (out, {}) if return_stats else out

    if cfg.scan_layers:
        out = _apply_scan(params, cfg, x, key_mask, layer_keys, seq_constraint, specs, rotary)
        return (out, {}) if return_stats else out

    x = seq_constraint(x)
    gmm_paths = {"kernel": 0, "fallback": 0}
    layer_stats, choice_counts = [], {}
    for spec in specs:
        akey = layer_keys[spec.index, 0] if has_dropout else None
        fkey = layer_keys[spec.index, 1] if has_dropout else None

        def block(x, akey=akey, fkey=fkey, spec=spec):
            # the routed layer's scalars leave the block as outputs: under
            # jax.checkpoint nothing traced inside may leave any other way
            aux = {"gmm_paths": gmm_paths}
            x = x + branch(spec, x, "attn", akey)
            x = seq_constraint(x)
            x = x + branch(spec, x, "ff", fkey, aux)
            return seq_constraint(x), aux.get("moe_stats", [])

        if cfg.execution == "remat":
            x, stats = _remat_wrap(block, cfg)(x)
        else:
            x, stats = block(x)
        for s in stats:
            if "moe_choice_counts" in s:  # a vector of this layer's own, not a scalar to average
                choice_counts[spec.ff_id] = choice_counts.get(spec.ff_id, 0) + s.pop("moe_choice_counts")
        layer_stats += stats
    if cfg.hybrid:
        _note_hybrid_layers(cfg, specs, gmm_paths, tokens=x.shape[0] * x.shape[1])
    if not return_stats:
        return x
    stats = {k: sum(s[k] for s in layer_stats) / len(layer_stats)
             for k in (layer_stats[0] if layer_stats else {})}
    if choice_counts:
        stats["moe_choice_counts"] = choice_counts
    return x, stats


def _assert_scannable(cfg, specs):
    assert cfg.execution in ("sequential", "remat"), "scan_layers: sequential/remat only"
    assert not cfg.sparse_per_head, (
        "sparse_per_head is not supported with scan_layers: the scan stacks a "
        "mask per layer, and per-head layouts would multiply that memory by "
        "`heads` for every layer — use the unrolled sequential/remat engines"
    )
    assert len({s.attn_id for s in specs}) == len(specs) and len({s.ff_id for s in specs}) == len(specs), (
        "scan_layers requires unshared layers (shared_attn_ids/shared_ff_ids unset)"
    )


def _stacked_bundles(params, specs):
    """Per-layer param bundles stacked along a leading depth axis (the
    lax.scan xs of `_apply_scan`)."""
    bundles = [
        {
            "attn": params["shared_attn"][s.attn_id],
            "ff": params["shared_ff"][s.ff_id],
            "wrap": params["layers"][s.index],
        }
        for s in specs
    ]
    with jax.named_scope("stack_layers"):
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *bundles)


def _stacked_masks(cfg, specs, n: int):
    """(masks (D, n, n) bool, midx (depth,) int32): one mask per DISTINCT
    pattern ('full' becomes all-ones), selected per layer by traced index."""
    import numpy as np

    distinct = list(dict.fromkeys(_pattern_key(s) for s in specs))
    masks_np = []
    for t, seed in distinct:
        pm = _pattern_for(cfg, t, seed)
        masks_np.append(np.ones((n, n), bool) if pm is None else np.asarray(pm)[:n, :n])
    midx = jnp.asarray([distinct.index(_pattern_key(s)) for s in specs], jnp.int32)
    return np.stack(masks_np), midx


def _stacked_flash_tables(cfg, masks_np, bq: int, bk: int, causal: bool):
    """Stacked compacted-grid index tables for the scan paths — one table set
    per DISTINCT pattern, padded to a common grid length (lax.scan selects a
    TRACED mask per layer, which defeats flash_attention's trace-time table
    build; the grid size must also be layer-invariant).  Returns a dict of
    (D, 1, T)/(D, 1, T2) jnp arrays keyed by sparse_index.TABLE_KEYS, or None
    when the dense grid is the right call (attn_grid='dense', or 'auto' with
    no dead step in any layer's grid: `flash_attention`'s own rule,
    `sparse_index.grid_has_dead_step`, so a causal stack compacts)."""
    import numpy as np

    if cfg.attn_grid == "dense":
        return None
    from dalle_pytorch_tpu.kernels.sparse_index import (
        TABLE_KEYS, build_compacted_tables, grid_has_dead_step,
    )
    from dalle_pytorch_tpu.ops.masks import block_live_np

    lives = [block_live_np(m, bq, bk) for m in masks_np]
    if cfg.attn_grid == "auto" and not any(
            grid_has_dead_step(lv, bq, bk, causal=causal) for lv in lives):
        return None
    per = [build_compacted_tables(lv, bq, bk, causal=causal) for lv in lives]
    pad = (
        max(t["qrow"].shape[-1] for t in per),
        max(t["qrowT"].shape[-1] for t in per),
    )
    per = [
        build_compacted_tables(lv, bq, bk, causal=causal, pad_to=pad)
        for lv in lives
    ]
    return {k: jnp.asarray(np.stack([t[k] for t in per])) for k in TABLE_KEYS}


def _select_flash_tables(tabstk, mi):
    """Per-layer table tuple (TABLE_KEYS order) from the stacked tables, by
    traced layer index."""
    if tabstk is None:
        return None
    from dalle_pytorch_tpu.kernels.sparse_index import TABLE_KEYS

    return tuple(jnp.take(tabstk[k], mi, axis=0, mode="clip") for k in TABLE_KEYS)


def _decode_tables_by_key(cfg, patterns):
    """Sparse-decode gather tables per pattern key ('full' layers stay on the
    dense cache read; pattern layers each get their own minimal Kmax)."""
    if not cfg.sparse_decode:
        return {}
    from dalle_pytorch_tpu.kernels.sparse_index import build_decode_tables

    out = {}
    for key, pm in patterns.items():
        if pm is not None:
            idx, counts = build_decode_tables(pm)
            out[key] = (jnp.asarray(idx), jnp.asarray(counts))
    return out


def _apply_scan(params, cfg, x, key_mask, layer_keys, seq_constraint, specs, rotary):
    """lax.scan over stacked per-layer params.  Per-layer attention patterns
    become a traced select from a stacked mask array (with stacked Pallas
    tile-liveness tables, so block skipping survives the scan)."""
    import numpy as np

    _assert_scannable(cfg, specs)
    n = x.shape[1]

    from dalle_pytorch_tpu.kernels.flash_attention import (
        DEFAULT_BLOCK_K,
        DEFAULT_BLOCK_Q,
        resolve_block,
    )

    masks_np, midx = _stacked_masks(cfg, specs, n)
    # liveness granularity must match the kernel's RESOLVED block sizes
    try:
        bq = resolve_block(n, DEFAULT_BLOCK_Q)
        bk = resolve_block(n, DEFAULT_BLOCK_K)
        lives = jnp.asarray(np.stack([
            m.reshape(n // bq, bq, n // bk, bk).any(axis=(1, 3)).astype(np.int32)
            for m in masks_np
        ]))
        tabstk = _stacked_flash_tables(cfg, masks_np, bq, bk, cfg.causal)
    except ValueError:  # no valid block: the flash path won't be taken anyway
        lives = None
        tabstk = None
    masks = jnp.asarray(masks_np)

    stacked = _stacked_bundles(params, specs)

    def run_branch(bundle, h, kind, mask, live, tabs, dkey):
        out, _ = _residual_branch(
            cfg, bundle["wrap"], bundle["attn"], bundle["ff"], h, kind,
            rotary=rotary, pattern=mask, key_mask=key_mask, dkey=dkey, live=live,
            tables=tabs,
        )
        return out

    def body(h, xs):
        if layer_keys is not None:
            bundle, mi, keys2 = xs
            akey, fkey = keys2[0], keys2[1]
        else:
            bundle, mi = xs
            akey = fkey = None
        mask = jnp.take(masks, mi, axis=0, mode="clip")
        live = jnp.take(lives, mi, axis=0, mode="clip") if lives is not None else None
        tabs = _select_flash_tables(tabstk, mi)
        h = h + run_branch(bundle, h, "attn", mask, live, tabs, akey)
        h = seq_constraint(h)
        h = h + run_branch(bundle, h, "ff", mask, live, tabs, fkey)
        return seq_constraint(h), None

    if cfg.execution == "remat":
        body = _remat_wrap(body, cfg)

    xs = (stacked, midx, layer_keys) if layer_keys is not None else (stacked, midx)

    if cfg.pipeline_axis is not None:
        mesh = _ambient_mesh()
        if (
            mesh is not None
            and cfg.pipeline_axis in mesh.shape
            and mesh.shape[cfg.pipeline_axis] > 1
        ):
            from dalle_pytorch_tpu.parallel.pipeline import pipeline_scan

            fold = None
            if layer_keys is not None:
                # each microbatch must draw its OWN dropout masks — fold the
                # microbatch id into the per-layer keys (a single-stage scan
                # draws one batch-wide mask; reusing it per microbatch would
                # correlate dropout across the batch)
                def fold(xs_local, micro_id):
                    bundle, mi, keys2 = xs_local
                    flat = keys2.reshape(-1, keys2.shape[-1])
                    folded = jax.vmap(
                        lambda k: jax.random.fold_in(k, micro_id)
                    )(flat).reshape(keys2.shape)
                    return (bundle, mi, folded)

            return pipeline_scan(
                body, seq_constraint(x), xs, mesh,
                axis=cfg.pipeline_axis, num_micro=cfg.pp_num_micro,
                fold_micro=fold,
                # seq sharding lowers token shifts / attention to GLOBAL halo
                # collectives inside the stage body; bubble stages must still
                # execute them (see pipeline_scan docstring)
                skip_bubble=cfg.seq_shard_axis is None,
                interleave=cfg.pp_interleave,
            )
        import warnings

        warnings.warn(
            f"pipeline_axis={cfg.pipeline_axis!r} but no mesh with that axis "
            ">1 is installed — falling back to single-stage lax.scan",
            stacklevel=2,
        )

    out, _ = jax.lax.scan(body, seq_constraint(x), xs)
    return out


# ---------------------------------------------------------------------------
# cached decoding
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, dtype=jnp.float32) -> dict:
    """Fixed-shape KV cache + token-shift ring buffers, one dict per layer;
    `offset` is the number of positions already consumed."""
    refuse_hybrid(cfg, "init_cache")

    def entry(spec):
        if spec.attn_type == "gated_delta":
            e = gated_delta_carried(cfg, batch, dtype)
        else:
            e = {
                "k": jnp.zeros((batch, cfg.heads, cfg.seq_len, cfg.dim_head), dtype),
                "v": jnp.zeros((batch, cfg.heads, cfg.seq_len, cfg.dim_head), dtype),
            }
        if cfg.shift_tokens:
            q = cfg.dim // 4
            fmap = cfg.image_fmap_size
            e["shift_attn"] = jnp.zeros((batch, fmap, 2, q), dtype)
            e["shift_ff"] = jnp.zeros((batch, fmap, 2, q), dtype)
        return e

    layers = [entry(spec) for spec in derive_layer_specs(cfg)]
    return {"offset": jnp.zeros((), jnp.int32), "layers": layers}


def gated_delta_carried(cfg: TransformerConfig, rows: int, dtype) -> dict:
    """What a `gated_delta` layer keeps per sequence (a batch row of the dense
    cache, a slot of the paged pool) where the other layers keep keys: the
    rule's state, float32 whatever `dtype` is, zero at a sequence's start, and
    the convolution's last taps - 1 inputs in `dtype`."""
    channels = 2 * cfg.gdn_key_heads * cfg.gdn_key_dim + cfg.gdn_value_heads * cfg.gdn_value_dim
    return {
        "state": jnp.zeros((rows, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim),
                           jnp.float32),
        "taps": jnp.zeros((rows, cfg.gdn_conv_kernel - 1, channels), dtype),
    }


@jax.named_scope("token_shift")
def _shift_cached_step(cfg, rb, x, offset):
    """Single-token cached token shift — the fixed-shape replacement for the
    reference's deque (transformer.py:138-153).  x: (b, 1, dim);
    rb: (b, fmap, 2, d//4) holds each past image token's raw first/second
    channel quarters in its raster-column slot.  Returns (shifted x, new rb)."""
    fmap = cfg.image_fmap_size
    q = cfg.dim // 4
    img_pos = offset - cfg.text_len  # >= 0: cached decode only runs in the image region
    slot = jnp.mod(img_pos, fmap)

    cur = x[:, 0]
    # the token one full row above lives in the slot we are about to overwrite
    top = jax.lax.dynamic_index_in_dim(rb, slot, axis=1, keepdims=False)[:, 0]
    prev = jax.lax.dynamic_index_in_dim(rb, jnp.mod(slot - 1, fmap), axis=1, keepdims=False)
    left = jnp.where(slot == 0, jnp.zeros_like(prev[:, 1]), prev[:, 1])

    shifted = jnp.concatenate([top, left, cur[:, 2 * q :]], axis=-1)[:, None]

    pair = jnp.stack([cur[:, :q], cur[:, q : 2 * q]], axis=1)  # (b, 2, q)
    rb = jax.lax.dynamic_update_index_in_dim(rb, pair[:, None].astype(rb.dtype), slot, axis=1)
    return shifted, rb


@jax.named_scope("attn")
def _attention_cached(shared, cfg, layer_cache, x, pattern, rotary, offset,
                      decode_tab=None):
    """Single-token cached attention.  x: (b, 1, dim).  Returns (out, (k, v)).

    `decode_tab`: optional sparse-decode gather tables (idx, counts) from
    sparse_index.build_decode_tables — idx[..., t, :] lists the pattern's
    permitted key positions {j <= t} and already folds in both causality and
    the pattern row, so the step gathers Kmax keys instead of attending over
    the full seq_len cache.  Padded gather slots are masked off by counts
    (their exp underflows to exactly 0.0, like the dense path's masked
    positions), so results match the full-cache row-mask path.

    A QUANTIZED cache (`k_scale`/`v_scale` present: int8 k/v + per-token
    scales — the serving pool's dense per-slot view) runs the same math on
    dequantized values.  The new column is quantized once on write, and the
    sparse-decode branch dequantizes ONLY the gathered Kmax keys, so the
    dtype win compounds with PR 8's pattern win instead of undoing it."""
    from dalle_pytorch_tpu.quantization import (
        dequantize_kv as _deq_kv,
        quantize_kv as _q_kv,
    )

    ang = (
        None if rotary is None
        else jax.lax.dynamic_slice(rotary, (offset, 0), (1, rotary.shape[1]))
    )
    q, k, v = _qkv_heads(shared, cfg, x, ang)  # (b, h, 1, dh)
    q = q * (cfg.dim_head ** -0.5)
    cdtype = q.dtype

    quantized = "k_scale" in layer_cache
    if quantized:
        kq, ks = _q_kv(k)
        vq, vs = _q_kv(v)
        k_buf = jax.lax.dynamic_update_slice(
            layer_cache["k"], kq, (0, 0, offset, 0))
        v_buf = jax.lax.dynamic_update_slice(
            layer_cache["v"], vq, (0, 0, offset, 0))
        ks_buf = jax.lax.dynamic_update_slice(
            layer_cache["k_scale"], ks.astype(layer_cache["k_scale"].dtype),
            (0, 0, offset))
        vs_buf = jax.lax.dynamic_update_slice(
            layer_cache["v_scale"], vs.astype(layer_cache["v_scale"].dtype),
            (0, 0, offset))
        new_cache = (k_buf, v_buf, ks_buf, vs_buf)
    else:
        k_buf = jax.lax.dynamic_update_slice(
            layer_cache["k"], k.astype(layer_cache["k"].dtype), (0, 0, offset, 0)
        )
        v_buf = jax.lax.dynamic_update_slice(
            layer_cache["v"], v.astype(layer_cache["v"].dtype), (0, 0, offset, 0)
        )
        new_cache = (k_buf, v_buf)

    if decode_tab is not None:
        idx, counts = decode_tab
        kmax = idx.shape[-1]
        if idx.ndim == 3:  # per-head (h, n, Kmax)
            sel = jax.lax.dynamic_slice(
                idx, (0, offset, 0), (idx.shape[0], 1, kmax))[:, 0]  # (h, Kmax)
            cnt = jax.lax.dynamic_slice(
                counts, (0, offset), (counts.shape[0], 1))[:, 0]  # (h,)
            k_sel = jnp.take_along_axis(k_buf, sel[None, :, :, None], axis=2)
            v_sel = jnp.take_along_axis(v_buf, sel[None, :, :, None], axis=2)
            if quantized:  # dequantize only the Kmax gathered keys
                k_sel = _deq_kv(k_sel, jnp.take_along_axis(
                    ks_buf, sel[None, :, :], axis=2), cdtype)
                v_sel = _deq_kv(v_sel, jnp.take_along_axis(
                    vs_buf, sel[None, :, :], axis=2), cdtype)
            amask = (jnp.arange(kmax)[None, :] < cnt[:, None])[None, :, None, :]
        else:  # shared (n, Kmax)
            sel = jax.lax.dynamic_slice(idx, (offset, 0), (1, kmax))[0]
            cnt = jax.lax.dynamic_slice(counts, (offset,), (1,))[0]
            k_sel = jnp.take(k_buf, sel, axis=2)
            v_sel = jnp.take(v_buf, sel, axis=2)
            if quantized:
                k_sel = _deq_kv(k_sel, jnp.take(ks_buf, sel, axis=2), cdtype)
                v_sel = _deq_kv(v_sel, jnp.take(vs_buf, sel, axis=2), cdtype)
            amask = (jnp.arange(kmax) < cnt)[None, None, None, :]
        out = attend(q, k_sel, v_sel, mask=amask, stable=cfg.stable)
        out = linear(shared["out"], _merge_heads(out))
        return out, new_cache

    j = jnp.arange(cfg.seq_len)
    mask = j <= offset
    if pattern is not None:
        if jnp.ndim(pattern) == 3:  # per-head (h, n, n): one row per head
            rows = jax.lax.dynamic_slice(
                pattern, (0, offset, 0), (pattern.shape[0], 1, cfg.seq_len)
            )[:, 0]
            mask = mask[None, :] & rows  # (h, seq)
        else:
            row = jax.lax.dynamic_slice(pattern, (offset, 0), (1, cfg.seq_len))[0]
            mask = mask & row
    amask = mask[None, :, None, :] if mask.ndim == 2 else mask[None, None, None, :]
    if quantized:
        k_att = _deq_kv(k_buf, ks_buf, cdtype)
        v_att = _deq_kv(v_buf, vs_buf, cdtype)
    else:
        k_att, v_att = k_buf, v_buf
    out = attend(q, k_att, v_att, mask=amask, stable=cfg.stable)
    out = linear(shared["out"], _merge_heads(out))
    return out, new_cache


def _run_cached_layers(cfg: TransformerConfig, specs, x, cache, branch):
    """Drive `branch(spec, x, kind, layer_cache) -> (out, layer_cache)` through
    the layer stack (sequential residual or reversible twin-stream), returning
    (output, new layer caches)."""
    new_layers = []
    if cfg.execution == "reversible":
        x1 = x2 = x
        for spec in specs:
            layer_cache = cache["layers"][spec.index]
            fa, layer_cache = branch(spec, x2, "attn", layer_cache)
            x1 = x1 + fa
            fb, layer_cache = branch(spec, x1, "ff", layer_cache)
            x2 = x2 + fb
            new_layers.append(layer_cache)
        return (x1 + x2) / 2, new_layers
    h = x
    for spec in specs:
        layer_cache = cache["layers"][spec.index]
        fa, layer_cache = branch(spec, h, "attn", layer_cache)
        h = h + fa
        fb, layer_cache = branch(spec, h, "ff", layer_cache)
        h = h + fb
        new_layers.append(layer_cache)
    return h, new_layers


def _resolve_layer_range(cfg, specs, layer_start, layer_stop):
    """Validate a [layer_start, layer_stop) slice of the stack (speculative
    drafting runs layers [0, d) then verification continues [d, depth)).
    Returns (sliced_specs, partial: bool).  Reversible execution interleaves
    the two residual streams across the whole stack, so a partial run has no
    well-defined hidden state to hand off — refuse it."""
    n = len(specs)
    stop = n if layer_stop is None else layer_stop
    if not (0 <= layer_start < stop <= n):
        raise ValueError(
            f"layer range [{layer_start}, {stop}) invalid for depth {n}")
    partial = layer_start != 0 or stop != n
    if partial and cfg.execution == "reversible":
        raise ValueError(
            "partial layer ranges (speculative drafting) require sequential "
            "execution; reversible twin-stream layers cannot be split")
    return specs[layer_start:stop], partial


def decode_step(
    params: dict,
    cfg: TransformerConfig,
    x: jnp.ndarray,
    cache: dict,
    text_only: bool = False,
    layer_start: int = 0,
    layer_stop: int = None,
) -> Tuple[jnp.ndarray, dict]:
    """Process ONE token (b, 1, dim) at position cache['offset'].  Sampling
    runs with dropout disabled (eval mode), matching the reference's
    eval_decorator.  text_only: the decode position is in the text region
    (generate_texts) — the token shift is skipped (identity there).

    layer_start/layer_stop run only layers [layer_start, layer_stop) — the
    speculative drafter's shallow prefix (layer_stop=d) and the verifier's
    continuation from a stored layer-d hidden (layer_start=d).  The returned
    cache keeps the untouched layers' entries verbatim, so a draft pass
    followed by a verify pass writes exactly what one full pass would."""
    refuse_hybrid(cfg, "decode_step")
    specs = derive_layer_specs(cfg)
    specs, partial = _resolve_layer_range(cfg, specs, layer_start, layer_stop)
    rotary = transformer_rotary(cfg)
    offset = cache["offset"]

    patterns = spec_patterns(cfg, specs)
    dec_tabs = _decode_tables_by_key(cfg, patterns)

    def branch(spec, x, kind, layer_cache):
        return _residual_branch(
            cfg, params["layers"][spec.index], params["shared_attn"][spec.attn_id],
            params["shared_ff"][spec.ff_id], x, kind, mode="decode",
            rotary=rotary, pattern=patterns[_pattern_key(spec)],
            layer_cache=layer_cache, offset=offset, text_mode=text_only,
            decode_tab=dec_tabs.get(_pattern_key(spec)),
            attn_type=spec.attn_type, ff_type=cfg.ff_type(spec.index),
        )

    out, new_layers = _run_cached_layers(cfg, specs, x, cache, branch)
    if partial:
        merged = list(cache["layers"])
        for spec, lc in zip(specs, new_layers):
            merged[spec.index] = lc
        new_layers = merged
    return out, {"offset": offset + 1, "layers": new_layers}


def prefill(
    params: dict,
    cfg: TransformerConfig,
    x: jnp.ndarray,
    cache: dict,
    key_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, dict]:
    """Consume a length-n prefix starting at offset 0, filling the KV cache and
    shift ring buffers, and return the transformer output for the prefix."""
    refuse_hybrid(cfg, "prefill")
    n = x.shape[1]
    specs = derive_layer_specs(cfg)
    rotary = transformer_rotary(cfg)

    patterns = spec_patterns(cfg, specs)

    def branch(spec, x, kind, layer_cache):
        return _residual_branch(
            cfg, params["layers"][spec.index], params["shared_attn"][spec.attn_id],
            params["shared_ff"][spec.ff_id], x, kind, mode="prefill",
            rotary=rotary, pattern=patterns[_pattern_key(spec)], key_mask=key_mask,
            layer_cache=layer_cache,
            attn_type=spec.attn_type, ff_type=cfg.ff_type(spec.index),
        )

    out, new_layers = _run_cached_layers(cfg, specs, x, cache, branch)
    return out, {"offset": jnp.asarray(n, jnp.int32), "layers": new_layers}


@jax.named_scope("token_shift")
def _fill_ring(cfg: TransformerConfig, rb: jnp.ndarray, pre_shift: jnp.ndarray) -> jnp.ndarray:
    """Populate the shift ring buffer from a length-n prefix ending at n-1.

    Stores the raw channel quarters of the last min(n - text_len, fmap) image
    tokens in their raster slots (positions before the image region contribute
    zeros, matching the reference's dummy entries)."""
    b, n, d = pre_shift.shape
    fmap = cfg.image_fmap_size
    q = d // 4
    text_len = cfg.text_len
    n_img = n - text_len  # may be <= 0 (text-only prefill)
    if n_img <= 0:
        return rb
    take = min(n_img, fmap)
    tail = pre_shift[:, n - take :]
    pairs = jnp.stack([tail[..., :q], tail[..., q : 2 * q]], axis=2)  # (b, take, 2, q)
    for t in range(take):
        img_pos = n_img - take + t
        slot = img_pos % fmap
        rb = rb.at[:, slot].set(pairs[:, t])
    return rb


# ---------------------------------------------------------------------------
# paged KV cache (serving/ continuous batching)
# ---------------------------------------------------------------------------
#
# The dense cache above allocates (b, h, seq_len, dh) per layer per request
# batch — one request's worth of HBM whether the sequence has generated 3
# tokens or 1000.  The serving engine instead shares ONE preallocated block
# pool across all in-flight sequences: per layer, (num_blocks, h, block_size,
# dh) k/v arrays addressed through per-slot int32 block tables.  Shapes stay
# static (XLA requirement); raggedness lives entirely in the block-table
# *values* and the per-slot `offsets` vector, so admitting or evicting a
# sequence never recompiles anything.
#
# Decode attention over the pool has two implementations of one operation,
# chosen per layer from what the code can observe (`_use_paged_kernel`):
#
# * the Pallas kernel (kernels/paged_attention.py) reads each slot's K/V
#   blocks where they lie, addressed through the block table by scalar
#   prefetch, and writes the new column into its block in place.  No
#   per-slot view exists, no XLA operation touches a pool array, and only
#   a slot's live blocks are fetched (none past its offset, none in which
#   the pattern permits no key): the decode working set is two block tiles.
#   Float32 online softmax: the same mathematics as the dense path in
#   another order of summation (float32 round-off, not bit parity).
# * the XLA path (`_paged_attention_step` + `_paged_scatter_cols`) is the
#   meaning of the operation and what runs where a tile cannot hold the
#   shape (dim_head not a multiple of 128, block_size not of 8), on int8
#   pools, per-head patterns, `stable` softmax and under a health tap.  Bit
#   parity with the dense cache is by construction: each slot's attention
#   runs the SAME `_attention_cached` math on a dense (h, seq_len, dh) view
#   gathered from its blocks (vmapped over slots with a per-slot offset).
#   That view is a transient of one layer, but on the chip the path is
#   mostly copies: the gather, and a relayout of the layer's whole pool on
#   entry and exit that XLA's scatter of the new column asks for (17 of 25
#   ms of a decode step at DALL-E width; PERF.md section 6, PR 25).
#
# Either way positions past a slot's offset hold stale bytes from evicted
# sequences, and the mask fills them with finfo.min BEFORE the softmax —
# exp underflows to exactly 0.0 — so they contribute exactly nothing, same
# as the dense cache's zeros.  The at-rest footprint is just the pool
# (priced by sampling_memory_ledger's paged rows).


def paged_blocks_per_seq(cfg: TransformerConfig, block_size: int) -> int:
    """Blocks a full sequence occupies (the admission-control unit)."""
    return -(-cfg.seq_len // block_size)


def init_paged_pool(
    cfg: TransformerConfig, num_blocks: int, block_size: int, dtype=jnp.float32,
    quantize: Optional[str] = None, num_slots: Optional[int] = None,
) -> dict:
    """One shared KV block pool: per layer, (num_blocks, heads, block_size,
    dim_head) k/v arrays.  Block 0 is conventionally reserved by the serving
    pool as the trash block inactive slots write into.

    A `gated_delta` layer keeps no keys and holds no blocks: its entry is
    `gated_delta_carried` for `num_slots` slots ({"state", "taps"}, indexed by
    SLOT, admitted and freed with it), beside the block tables that address
    the other layers' entries.

    `quantize="int8"` stores int8 k/v with PER-TOKEN bf16 scales beside the
    blocks (`k_scale`/`v_scale`, block shape minus dim_head) — per-token so
    the decode scatter of one new column never re-scales a block's existing
    tokens.  Every paged op downstream keys off the presence of the scale
    arrays, so the quantized pool threads through the same jits."""
    quantized = bool(quantize) and quantize != "none"
    refuse_hybrid(cfg, "init_paged_pool", recurrent_state=not quantized)
    from dalle_pytorch_tpu.quantization import KV_SCALE_DTYPE

    def entry(spec):
        if spec.attn_type == "gated_delta":
            if num_slots is None:
                raise ValueError("init_paged_pool: a gated_delta layer's state is per slot; "
                                 "pass num_slots")
            return gated_delta_carried(cfg, num_slots, dtype)
        shape = (num_blocks, cfg.heads, block_size, cfg.dim_head)
        if quantized:
            sshape = shape[:-1]
            return {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, KV_SCALE_DTYPE),
                "v_scale": jnp.zeros(sshape, KV_SCALE_DTYPE),
            }
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    return {"layers": [entry(spec) for spec in derive_layer_specs(cfg)]}


def init_slot_rings(
    cfg: TransformerConfig, num_slots: int, dtype=jnp.float32
) -> Optional[dict]:
    """Per-slot token-shift ring buffers (slot-resident, not paged — they are
    O(fmap * dim) per slot, dwarfed by the KV blocks).  None when the config
    has no token shift."""
    if not cfg.shift_tokens:
        return None
    q = cfg.dim // 4
    fmap = cfg.image_fmap_size

    def entry():
        return {
            "shift_attn": jnp.zeros((num_slots, fmap, 2, q), dtype),
            "shift_ff": jnp.zeros((num_slots, fmap, 2, q), dtype),
        }

    return {"layers": [entry() for _ in range(cfg.depth)]}


@jax.named_scope("kv_write")
def write_prefill_to_pool(
    pool: dict,
    block_tables: jnp.ndarray,
    cache_layers,
    n_pre: int,
    block_size: int,
    slots: Optional[jnp.ndarray] = None,
) -> dict:
    """Scatter a freshly prefilled DENSE cache's first `n_pre` positions into
    the block pool — prefill itself runs the existing `prefill` (identical
    math, so parity is free) and this is pure data movement.  `block_tables`:
    (b, max_blocks) physical block ids for the b newly admitted slots;
    `cache_layers`: the `layers` entry of the cache `prefill` returned;
    `slots`: (b,) the slots admitted, where a `gated_delta` layer's state and
    taps go WHOLE (scope `state_write`): whatever the slot's last request
    left there is overwritten, none of it read.

    Quantized pools (layer entries carrying `k_scale`) accept EITHER a
    dense float cache (the fused admit: quantize at scatter) or a
    pre-quantized handoff (the disaggregated worker compressed the wire
    bytes already) — per-token scales make the two orders bit-identical."""
    from dalle_pytorch_tpu.quantization import quantize_kv as _quantize_kv

    nb = -(-n_pre // block_size)
    pad = nb * block_size - n_pre

    def pack(k):
        # (b, h, seq, dh) -> (b, nb, h, block_size, dh)
        k = k[:, :, :n_pre]
        if pad:
            k = jnp.pad(k, [(0, 0), (0, 0), (0, pad), (0, 0)])
        b, h, _, dh = k.shape
        return jnp.swapaxes(k.reshape(b, h, nb, block_size, dh), 1, 2)

    def pack_scale(s):
        # (b, h, seq) -> (b, nb, h, block_size)
        s = s[:, :, :n_pre]
        if pad:
            s = jnp.pad(s, [(0, 0), (0, 0), (0, pad)])
        b, h, _ = s.shape
        return jnp.swapaxes(s.reshape(b, h, nb, block_size), 1, 2)

    def packed_kv(lp, lc):
        """(k, v[, k_scale, v_scale]) in pool layout for one layer."""
        if "k_scale" not in lp:
            return {"k": pack(lc["k"]), "v": pack(lc["v"])}
        if "k_scale" in lc:  # pre-quantized handoff: pure data movement
            return {"k": pack(lc["k"]), "v": pack(lc["v"]),
                    "k_scale": pack_scale(lc["k_scale"]),
                    "v_scale": pack_scale(lc["v_scale"])}
        kq, ks = _quantize_kv(pack(lc["k"]))
        vq, vs = _quantize_kv(pack(lc["v"]))
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}

    tbl = block_tables[:, :nb]
    new_layers = []
    for lp, lc in zip(pool["layers"], cache_layers):
        if "state" in lp:
            if slots is None:
                raise ValueError("write_prefill_to_pool: a gated_delta layer's state goes to a "
                                 "slot; pass slots")
            with jax.named_scope("state_write"):
                new_layers.append({name: lp[name].at[slots].set(lc[name].astype(lp[name].dtype))
                                   for name in ("state", "taps")})
            continue
        pk = packed_kv(lp, lc)
        new_layers.append(dict(lp, **{
            name: lp[name].at[tbl].set(arr.astype(lp[name].dtype))
            for name, arr in pk.items()
        }))
    return {"layers": new_layers}


def _paged_attention_step(shared, cfg, layer_pool, block_tables, offsets, x,
                          pattern, rotary, decode_tab=None):
    """Per-slot cached attention over the paged pool.  x: (S, 1, dim);
    block_tables: (S, max_blocks); offsets: (S,).  Each slot gathers its
    blocks into a dense (h, seq_len, dh) view and runs the SAME
    `_attention_cached` math (vmapped), so results are bit-identical to the
    dense cache.  Returns (out (S, 1, dim), (new_k, new_v) (S, h, dh)) —
    the new column, for the caller to scatter back into the pool.  On a
    quantized pool the gathered view stays int8 (+ per-token scales) —
    `_attention_cached` dequantizes on use — and the returned column tuple
    grows the quantized column's scales ((S, h) each)."""
    seq = cfg.seq_len
    quantized = "k_scale" in layer_pool

    def one(x_s, bt_s, off_s):
        with jax.named_scope("kv_gather"):
            k = jnp.take(layer_pool["k"], bt_s, axis=0)  # (B, h, bs, dh)
            v = jnp.take(layer_pool["v"], bt_s, axis=0)
            k = k.transpose(1, 0, 2, 3).reshape(cfg.heads, -1, cfg.dim_head)[None, :, :seq]
            v = v.transpose(1, 0, 2, 3).reshape(cfg.heads, -1, cfg.dim_head)[None, :, :seq]
            cache = {"k": k, "v": v}
            if quantized:
                ks = jnp.take(layer_pool["k_scale"], bt_s, axis=0)  # (B, h, bs)
                vs = jnp.take(layer_pool["v_scale"], bt_s, axis=0)
                cache["k_scale"] = ks.transpose(1, 0, 2).reshape(cfg.heads, -1)[None, :, :seq]
                cache["v_scale"] = vs.transpose(1, 0, 2).reshape(cfg.heads, -1)[None, :, :seq]
        out, new_cache = _attention_cached(
            shared, cfg, cache, x_s[None], pattern, rotary, off_s,
            decode_tab=decode_tab,
        )

        def col(buf):  # (1, h, seq[, dh]) -> the off_s column, batch removed
            if buf.ndim == 4:
                c = jax.lax.dynamic_slice(
                    buf, (0, 0, off_s, 0), (1, cfg.heads, 1, cfg.dim_head))
                return c[0, :, 0]
            c = jax.lax.dynamic_slice(buf, (0, 0, off_s), (1, cfg.heads, 1))
            return c[0, :, 0]

        return (out[0], *[col(b) for b in new_cache])

    res = jax.vmap(one)(x, block_tables, offsets)
    return res[0], tuple(res[1:])


@jax.named_scope("kv_write")
def _paged_scatter_cols(layer_pool, block_tables, offsets, cols, block_size: int):
    """Write each slot's new KV column into its pool block.  Inactive slots
    share the trash block (their tables are all-zero), so their duplicate
    scatter indices can only clobber garbage."""
    bids = jnp.take_along_axis(
        block_tables, (offsets // block_size)[:, None], axis=1)[:, 0]
    within = offsets % block_size
    nk, nv = cols[0], cols[1]
    new = dict(
        layer_pool,
        k=layer_pool["k"].at[bids, :, within, :].set(nk.astype(layer_pool["k"].dtype)),
        v=layer_pool["v"].at[bids, :, within, :].set(nv.astype(layer_pool["v"].dtype)),
    )
    if len(cols) == 4:  # quantized pool: scatter the column's per-token scales
        nks, nvs = cols[2], cols[3]
        new["k_scale"] = layer_pool["k_scale"].at[bids, :, within].set(
            nks.astype(layer_pool["k_scale"].dtype))
        new["v_scale"] = layer_pool["v_scale"].at[bids, :, within].set(
            nvs.astype(layer_pool["v_scale"].dtype))
    return new


def _use_paged_kernel(cfg, layer_pool, pattern, block_size: int) -> bool:
    """Whether a layer's decode attention takes the Pallas paged kernel, from
    what the input shows (as `_use_flash` does for training): an unquantized
    pool, a shared (2-D) pattern or none, the plain softmax, no health tap
    wanting the scores, and a block tile the chip's tiling holds.  Anything
    else runs `_paged_attention_step` + `_paged_scatter_cols`."""
    from dalle_pytorch_tpu.kernels import paged_attention
    from dalle_pytorch_tpu.observability import health as health_mod

    if "k_scale" in layer_pool or cfg.stable or health_mod.taps_active():
        return False
    if pattern is not None and jnp.ndim(pattern) != 2:
        return False
    if jax.default_backend() not in ("cpu", "tpu"):
        return False
    return paged_attention.supports(
        cfg.dim_head, block_size, layer_pool["k"].dtype)


def _note_paged_path(path_tally: Optional[Dict[str, int]], use_kernel: bool) -> None:
    """Trace-time count, into the caller's dict, of the attention layers that
    took the kernel and of those that fell back (see `paged_decode_step`)."""
    if path_tally is not None:
        key = "kernel" if use_kernel else "fallback"
        path_tally[key] = path_tally.get(key, 0) + 1


@jax.named_scope("attn")
def _paged_attention_kernel_step(shared, cfg, layer_pool, block_tables,
                                 offsets, x, pattern, rotary):
    """The kernel path of `_paged_attention_step`: x (S, 1, dim) -> (out
    (S, 1, dim), new layer pool).  The kernel reads the pool through the
    block table and writes the new column in place; what is left of the
    gather is each slot's rotary angles and mask row."""
    from dalle_pytorch_tpu.kernels.paged_attention import paged_decode_attention

    with jax.named_scope("kv_gather"):
        ang = None
        if rotary is not None:  # (S, rot), broadcast over (h, qkv, 1)
            ang = jnp.take(rotary, offsets, axis=0, mode="clip")[:, None, None, None, :]
        rows = jnp.arange(cfg.seq_len)[None, :] <= offsets[:, None]
        if pattern is not None:
            rows = rows & jnp.take(
                jnp.asarray(pattern), offsets, axis=0, mode="clip")[:, :cfg.seq_len]
    q, k, v = _qkv_heads(shared, cfg, x, ang)  # (S, h, 1, dh)
    q = q * (cfg.dim_head ** -0.5)
    out, k_pool, v_pool = paged_decode_attention(
        q[:, :, 0], k[:, :, 0], v[:, :, 0], layer_pool["k"], layer_pool["v"],
        block_tables, offsets, rows,
    )
    out = linear(shared["out"], out.reshape(x.shape[0], 1, -1))
    return out, dict(layer_pool, k=k_pool, v=v_pool)


@jax.named_scope("token_shift")
def _paged_shift_step(cfg, ring, x, offsets):
    """Per-slot cached token shift: vmap of `_shift_cached_step` with a
    per-slot offset.  ring: (S, fmap, 2, q); x: (S, 1, dim)."""

    def one(rb, x_s, off_s):
        shifted, rb2 = _shift_cached_step(cfg, rb[None], x_s[None], off_s)
        return shifted[0], rb2[0]

    return jax.vmap(one)(ring, x, offsets)


def _paged_branch(cfg, wrap, attn_params, ff_params, x, kind, layer_pool,
                  block_tables, offsets, ring, pattern, rotary, block_size,
                  decode_tab=None, use_kernel=False, attn_type="full", ff_type="geglu"):
    """Decode-mode residual branch over paged per-slot state — the same
    composition as `_residual_branch(mode='decode')` with vectors where that
    path has scalars.  Returns (branch out, new ring, layer pool): an attn
    branch hands back the pool with the new K/V column written (in the
    kernel, or by `_paged_scatter_cols`) or, from a `gated_delta` layer, with
    every slot's state and taps advanced; an ff branch the pool it got."""
    h = x
    if cfg.pre_norm:
        with jax.named_scope("norm"):
            h = apply_norm(cfg, wrap[f"{kind}_norm"], x)
    new_ring = ring
    if cfg.shift_tokens:
        h, new_ring = _paged_shift_step(cfg, ring, h, offsets)
    if kind == "attn" and attn_type == "gated_delta":
        h, layer_pool = _gated_delta_cached(attn_params, cfg, layer_pool, h, "decode")
    elif kind == "attn" and use_kernel:
        h, layer_pool = _paged_attention_kernel_step(
            attn_params, cfg, layer_pool, block_tables, offsets, h, pattern,
            rotary,
        )
    elif kind == "attn":
        h, cols = _paged_attention_step(
            attn_params, cfg, layer_pool, block_tables, offsets, h, pattern,
            rotary, decode_tab=decode_tab,
        )
        layer_pool = _paged_scatter_cols(
            layer_pool, block_tables, offsets, cols, block_size)
    elif ff_type == "swiglu":
        h = _dense_swiglu(ff_params, h)
    else:
        h = _feed_forward(ff_params, cfg, h, None)
    with jax.named_scope("norm"):
        if cfg.sandwich_norm:
            h = apply_norm(cfg, wrap[f"{kind}_norm_out"], h)
        if cfg.layer_scale:
            h = h * wrap[f"{kind}_scale"].astype(h.dtype)
        return h, new_ring, layer_pool


def paged_decode_step(
    params: dict,
    cfg: TransformerConfig,
    x: jnp.ndarray,
    pool: dict,
    block_tables: jnp.ndarray,
    offsets: jnp.ndarray,
    rings: Optional[dict],
    block_size: int,
    layer_start: int = 0,
    layer_stop: int = None,
    path_tally: Optional[Dict[str, int]] = None,
) -> Tuple[jnp.ndarray, dict, Optional[dict]]:
    """One decode step for a whole SLOT BATCH of independent sequences at
    per-slot positions.  x: (S, 1, dim) embedded tokens; `offsets`: (S,)
    per-slot cache offsets (the position each slot's token occupies);
    `rings`: init_slot_rings state or None.  Returns (out (S, 1, dim),
    new pool, new rings).  The serving engine's fused per-iteration decode.

    layer_start/layer_stop restrict the pass to layers [layer_start,
    layer_stop) — the speculative draft (prefix) and verify (continuation)
    halves.  The returned pool/rings keep untouched layers' state verbatim.

    `path_tally`: a dict of the caller's; while the step is TRACED it gains
    the number of attention layers that took the Pallas paged kernel
    ("kernel"), that ran the gather path ("fallback") and that advanced a
    recurrent state instead ("state": the `gated_delta` layers, which hold no
    blocks and read neither `block_tables` nor `offsets`; "state_kernel": those
    of them whose one-token rule took `kernels/delta_step.py`)."""
    refuse_hybrid(cfg, "paged_decode_step")
    specs = derive_layer_specs(cfg)
    specs, partial = _resolve_layer_range(cfg, specs, layer_start, layer_stop)
    rotary = transformer_rotary(cfg)
    assert block_tables.shape[1] * block_size >= cfg.seq_len, (
        "block tables must cover a full sequence: "
        f"{block_tables.shape[1]} x {block_size} < {cfg.seq_len}"
    )

    patterns = spec_patterns(cfg, specs)
    use_kernel = {}
    for spec in specs:
        if spec.attn_type == "gated_delta":  # no keys: neither path, and no gather table (its pattern is None)
            use_kernel[spec.index] = False
            if path_tally is not None:
                from dalle_pytorch_tpu.models import gated_layers

                path_tally["state"] = path_tally.get("state", 0) + 1
                path_tally["state_kernel"] = path_tally.get("state_kernel", 0) + int(
                    gated_layers._use_delta_kernel(cfg))
            continue
        use_kernel[spec.index] = _use_paged_kernel(
            cfg, pool["layers"][spec.index], patterns[_pattern_key(spec)],
            block_size)
        _note_paged_path(path_tally, use_kernel[spec.index])
    # gather tables only for the patterns of layers that gather
    dec_tabs = _decode_tables_by_key(cfg, {
        _pattern_key(spec): patterns[_pattern_key(spec)]
        for spec in specs if not use_kernel[spec.index]
    })

    def branch(spec, h, kind, layer_pool, ring):
        return _paged_branch(
            cfg, params["layers"][spec.index], params["shared_attn"][spec.attn_id],
            params["shared_ff"][spec.ff_id], h, kind, layer_pool, block_tables,
            offsets, ring, patterns[_pattern_key(spec)], rotary, block_size,
            decode_tab=dec_tabs.get(_pattern_key(spec)),
            use_kernel=use_kernel[spec.index],
            attn_type=spec.attn_type, ff_type=cfg.ff_type(spec.index),
        )

    new_pool_layers, new_ring_layers = [], []

    def run_layer(spec, h):
        """One layer's decode-mode residual pair on the paged state: returns
        (fa, fb, new layer pool, new ring layer) with fb computed on h + fa."""
        lp = pool["layers"][spec.index]
        ring_layer = rings["layers"][spec.index] if cfg.shift_tokens else None
        r_attn = ring_layer["shift_attn"] if cfg.shift_tokens else None
        fa, r_attn, lp = branch(spec, h, "attn", lp, r_attn)
        r_ff = ring_layer["shift_ff"] if cfg.shift_tokens else None
        fb, r_ff, _ = branch(spec, h + fa, "ff", lp, r_ff)
        new_ring = (
            {"shift_attn": r_attn, "shift_ff": r_ff} if cfg.shift_tokens else None
        )
        return fa, fb, lp, new_ring

    if cfg.execution == "reversible":
        x1 = x2 = x
        for spec in specs:
            lp0 = pool["layers"][spec.index]
            ring_layer = rings["layers"][spec.index] if cfg.shift_tokens else None
            r_attn = ring_layer["shift_attn"] if cfg.shift_tokens else None
            fa, r_attn, lp = branch(spec, x2, "attn", lp0, r_attn)
            x1 = x1 + fa
            r_ff = ring_layer["shift_ff"] if cfg.shift_tokens else None
            fb, r_ff, _ = branch(spec, x1, "ff", lp, r_ff)
            x2 = x2 + fb
            new_pool_layers.append(lp)
            if cfg.shift_tokens:
                new_ring_layers.append({"shift_attn": r_attn, "shift_ff": r_ff})
        out = (x1 + x2) / 2
    else:
        h = x
        for spec in specs:
            fa, fb, lp, new_ring = run_layer(spec, h)
            h = h + fa + fb
            new_pool_layers.append(lp)
            if cfg.shift_tokens:
                new_ring_layers.append(new_ring)
        out = h

    if partial:
        merged_pool = list(pool["layers"])
        for spec, lp in zip(specs, new_pool_layers):
            merged_pool[spec.index] = lp
        new_pool_layers = merged_pool
        if cfg.shift_tokens:
            merged_rings = list(rings["layers"])
            for spec, rl in zip(specs, new_ring_layers):
                merged_rings[spec.index] = rl
            new_ring_layers = merged_rings
    new_rings = {"layers": new_ring_layers} if cfg.shift_tokens else None
    return out, {"layers": new_pool_layers}, new_rings
