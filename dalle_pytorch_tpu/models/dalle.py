"""The text→image autoregressive DALL-E model.

Capability parity with /root/reference/dalle_pytorch/dalle_pytorch.py:352-671:
joint text+image vocabulary with per-position unique padding tokens, <bos>
prepend, axial/learned or rotary positions, logits masking so text positions
predict text and image positions predict image, the (text + 7*img)/8 weighted
CE loss, the `stable` embedding-blend + DivideMax tricks, and optional tied
input/output embeddings.

The model is a pure function over a parameter pytree and operates on image
*codes* — the frozen VAE that turns pixels into codes is composed by the
caller (training/api layers), removing the reference's model→distributed
coupling (SURVEY.md §1)."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.core.module import embedding_init, linear, linear_init
from dalle_pytorch_tpu.core.rng import KeyChain
from dalle_pytorch_tpu.models.transformer import (
    TransformerConfig, apply_norm, apply_transformer, init_transformer, norm_init,
)
from dalle_pytorch_tpu.observability import health as health_mod
from dalle_pytorch_tpu.ops.sampling import prob_mask_like
from dalle_pytorch_tpu.ops.stable import divide_max


# the fields that describe the block, handed to TransformerConfig as they are
_BLOCK_FIELDS = (
    "norm", "norm_eps", "layer_scale", "kv_heads", "partial_rotary_factor", "rotary_theta",
    "rope_yarn_factor", "rope_yarn_original_max", "rope_yarn_beta_fast", "rope_yarn_beta_slow",
    "window_heads", "window_rotary_theta", "window", "attn_gate",
    "gdn_key_heads", "gdn_value_heads", "gdn_key_dim", "gdn_value_dim", "gdn_conv_kernel",
    "gdn_neg_eigval", "pre_norm", "qk_norm", "attn_bias",
    "moe_experts", "moe_top_k", "moe_ff_dim", "moe_shared_ff_dim",
    "moe_experts_held", "moe_first_expert",
    "moe_router", "moe_routed_scale", "moe_bias_rate", "moe_shared_gated",
    "dense_layers", "dense_ff_dim",
    "mla_q_rank", "mla_kv_rank", "mla_nope_dim", "mla_rope_dim", "mla_v_dim",
)


@dataclasses.dataclass(frozen=True)
class DALLEConfig:
    dim: int
    depth: int
    num_text_tokens: int = 10000  # raw text vocab; per-position pad ids are reserved on top
    text_seq_len: int = 256
    heads: int = 8
    dim_head: int = 64
    reversible: bool = False
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    attn_types: Tuple[str, ...] = ("full",)
    loss_img_weight: float = 7.0
    stable: bool = False
    sandwich_norm: bool = False
    shift_tokens: bool = True
    rotary_emb: bool = True
    shared_attn_ids: Optional[Tuple[int, ...]] = None
    shared_ff_ids: Optional[Tuple[int, ...]] = None
    share_input_output_emb: bool = False
    execution: Optional[str] = None  # None -> 'reversible' if reversible else 'sequential'
    scan_layers: bool = False  # lax.scan over layers (fast compiles at high depth)
    # selective remat save policy for execution='remat'
    # ('full' | 'flash' | 'flash_qkv' | 'flash_qkv_ff' — TransformerConfig.remat_policy)
    remat_policy: str = "full"
    # image side, derived from the VAE that produced the codes
    num_image_tokens: int = 512
    image_fmap_size: int = 32
    # sparse pattern knobs
    conv_kernel_size: int = 5
    conv_dilation: int = 1
    sparse_block_size: int = 16
    sparse_per_head: bool = False  # per-head random block layouts (DeepSpeed parity)
    attn_kernel: str = "auto"  # 'auto' | 'flash' | 'xla'
    # flash-kernel grid: 'auto' compacts when the tile grid has a dead step
    # (causal or pattern); 'dense' | 'compact' force (TransformerConfig docs)
    attn_grid: str = "auto"
    attn_vfa: bool = False  # VFA global-max forward pass (allclose, not bitwise)
    # cached/paged decode gathers only pattern-permitted keys (Kmax reads per
    # step instead of the full cache).  Off: full-cache reads — bit-stable vs
    # pre-sparse-decode sampling (the gather is reduction-order-ulp close)
    sparse_decode: bool = True
    seq_shard_axis: Optional[str] = None  # sequence-parallel mesh axis (e.g. 'sp')
    pipeline_axis: Optional[str] = None  # pipeline-parallel mesh axis (e.g. 'pp')
    pp_interleave: int = 1  # circular pipeline chunks per device (bubble / v)
    pp_num_micro: Optional[int] = None  # GPipe microbatches (None = auto)
    # the block as a parameter (TransformerConfig has each field's meaning):
    # `attn_types` may cycle `gated_delta` / `gated_full` / `gated_window` /
    # `mla`; a hybrid trunk is trained through forward(), and served where
    # `transformer.refuse_hybrid` lets it (`gated_delta` beside pattern layers:
    # yes; `gated_full`, `gated_window`, `mla`, routed experts: no)
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    layer_scale: bool = True
    kv_heads: Optional[int] = None
    partial_rotary_factor: float = 1.0
    rotary_theta: float = 10000.0
    rope_yarn_factor: float = 0.0
    rope_yarn_original_max: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    window_heads: Optional[int] = None
    window_rotary_theta: float = 10000.0
    window: int = 0
    attn_gate: str = "elementwise"
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv_kernel: int = 4
    gdn_neg_eigval: bool = False
    pre_norm: bool = True
    qk_norm: bool = False
    attn_bias: bool = True
    # with `rotary_emb` off: the learned text and axial image position tables
    # (True, the DALL-E stream's), or no position signal beside what the
    # layers carry themselves (False: a trunk whose recurrent layers do)
    axial_pos_emb: bool = True
    moe_experts: int = 0
    moe_top_k: int = 1
    moe_ff_dim: int = 0
    moe_shared_ff_dim: int = 0
    moe_experts_held: Optional[int] = None
    moe_first_expert: int = 0
    moe_router: str = "softmax"
    moe_routed_scale: float = 1.0
    moe_bias_rate: float = 0.001
    moe_shared_gated: bool = True
    dense_layers: int = 0
    dense_ff_dim: int = 0
    mla_q_rank: int = 0
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0
    # the multi-token-prediction module [`num_nextn_predict_layers`]: after the
    # trunk, one more block of the trunk's LAST layer's kind over
    # W_m [N(h_i) ; N(e_{i+1})], its own final norm, the trunk's own embedding
    # and head; its logits at i predict token i + 2 and its loss is added
    # `mtp_loss_weight` times (DeepSeek-V3 report, section 2.2).  0 = none.
    mtp_depth: int = 0
    mtp_loss_weight: float = 0.3

    # -- derived ----------------------------------------------------------
    @property
    def num_text_tokens_padded(self) -> int:
        return self.num_text_tokens + self.text_seq_len

    @property
    def image_seq_len(self) -> int:
        return self.image_fmap_size ** 2

    @property
    def total_seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def total_tokens(self) -> int:
        return self.num_text_tokens_padded + self.num_image_tokens

    @property
    def learned_positions(self) -> bool:
        return not self.rotary_emb and self.axial_pos_emb

    @property
    def resolved_execution(self) -> str:
        if self.execution is not None:
            return self.execution
        return "reversible" if self.reversible else "sequential"

    def transformer_config(self) -> TransformerConfig:
        return TransformerConfig(
            dim=self.dim,
            depth=self.depth,
            seq_len=self.total_seq_len,
            causal=True,
            heads=self.heads,
            dim_head=self.dim_head,
            attn_dropout=self.attn_dropout,
            ff_dropout=self.ff_dropout,
            attn_types=self.attn_types,
            image_fmap_size=self.image_fmap_size,
            stable=self.stable,
            sandwich_norm=self.sandwich_norm,
            shift_tokens=self.shift_tokens,
            rotary_emb=self.rotary_emb,
            shared_attn_ids=self.shared_attn_ids,
            shared_ff_ids=self.shared_ff_ids,
            execution=self.resolved_execution,
            scan_layers=self.scan_layers,
            remat_policy=self.remat_policy,
            conv_kernel_size=self.conv_kernel_size,
            conv_dilation=self.conv_dilation,
            sparse_block_size=self.sparse_block_size,
            sparse_per_head=self.sparse_per_head,
            attn_kernel=self.attn_kernel,
            attn_grid=self.attn_grid,
            attn_vfa=self.attn_vfa,
            sparse_decode=self.sparse_decode,
            seq_shard_axis=self.seq_shard_axis,
            pipeline_axis=self.pipeline_axis,
            pp_num_micro=self.pp_num_micro,
            pp_interleave=self.pp_interleave,
            **{k: getattr(self, k) for k in _BLOCK_FIELDS},
        )

    def mtp_block_config(self) -> TransformerConfig:
        """The prediction module's one block: the trunk's last layer's kind
        (its mixer, a routed feed-forward where the trunk has one)."""
        if self.mtp_depth != 1:
            raise ValueError(f"mtp_depth {self.mtp_depth} is not supported; 0 (none) or 1")
        last = self.attn_types[(self.depth - 1) % len(self.attn_types)]
        return dataclasses.replace(self.transformer_config(), depth=1, attn_types=(last,),
                                   dense_layers=0, shared_attn_ids=None, shared_ff_ids=None)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, hparams: dict) -> "DALLEConfig":
        """Rebuild from a serialized to_dict (tuple fields round-trip json as
        lists)."""
        return cls(**tupled_hparams(hparams))

    @classmethod
    def from_vae(cls, vae_cfg, **kwargs) -> "DALLEConfig":
        """Derive the image-side fields from a DiscreteVAEConfig (or any object
        with num_tokens / image_size / num_layers)."""
        fmap = vae_cfg.image_size // (2 ** vae_cfg.num_layers)
        return cls(num_image_tokens=vae_cfg.num_tokens, image_fmap_size=fmap, **kwargs)


def tupled_hparams(hparams: dict) -> dict:
    """Coerce the tuple-typed config keys back from json-round-tripped lists."""
    out = dict(hparams)
    for k in ("attn_types", "shared_attn_ids", "shared_ff_ids"):
        if out.get(k) is not None:
            out[k] = tuple(out[k])
    return out


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def migrate_param_layout(params: dict, cfg: DALLEConfig) -> dict:
    """Upgrade pre-round-5 DALLE checkpoints to the tp-local transformer
    layouts (no-op when already current) — see
    transformer.migrate_transformer_layout."""
    from dalle_pytorch_tpu.models.transformer import migrate_transformer_layout

    migrated = migrate_transformer_layout(
        params.get("transformer", {}), cfg.heads, cfg.dim_head
    )
    if migrated is params.get("transformer"):
        return params
    return {**params, "transformer": migrated}


def init_dalle(key: jax.Array, cfg: DALLEConfig) -> dict:
    keys = KeyChain(key)
    params = {
        "transformer": init_transformer(keys.next(), cfg.transformer_config()),
        "logits_norm": norm_init(cfg.transformer_config()),
        "logits_linear": linear_init(keys.next(), cfg.dim, cfg.total_tokens),
    }
    if not cfg.share_input_output_emb:
        params["text_emb"] = embedding_init(keys.next(), cfg.num_text_tokens_padded, cfg.dim)
        params["image_emb"] = embedding_init(keys.next(), cfg.num_image_tokens, cfg.dim)
    if cfg.learned_positions:
        params["text_pos"] = embedding_init(keys.next(), cfg.text_seq_len + 1, cfg.dim)
        # axial positional embedding: summed per-row and per-column tables
        params["image_pos_h"] = embedding_init(keys.next(), cfg.image_fmap_size, cfg.dim)
        params["image_pos_w"] = embedding_init(keys.next(), cfg.image_fmap_size, cfg.dim)
    if cfg.mtp_depth:
        block_cfg = cfg.mtp_block_config()
        params["mtp"] = {
            "h_norm": norm_init(block_cfg),
            "e_norm": norm_init(block_cfg),
            "merge": linear_init(keys.next(), 2 * cfg.dim, cfg.dim, bias=False),
            "block": init_transformer(keys.next(), block_cfg),
            "norm": norm_init(block_cfg),
        }
    return params


# ---------------------------------------------------------------------------
# embedding helpers (shared with the sampler)
# ---------------------------------------------------------------------------

def _logits_w(params: dict) -> jnp.ndarray:
    from dalle_pytorch_tpu.quantization import maybe_dequant_weight

    return maybe_dequant_weight(params["logits_linear"]["w"])


def _text_table(params: dict, cfg: DALLEConfig) -> jnp.ndarray:
    if cfg.share_input_output_emb:
        return _logits_w(params)[:, : cfg.num_text_tokens_padded].T
    from dalle_pytorch_tpu.quantization import maybe_dequant_weight

    return maybe_dequant_weight(params["text_emb"]["table"])


def _image_table(params: dict, cfg: DALLEConfig) -> jnp.ndarray:
    if cfg.share_input_output_emb:
        return _logits_w(params)[:, cfg.num_text_tokens_padded :].T
    from dalle_pytorch_tpu.quantization import maybe_dequant_weight

    return maybe_dequant_weight(params["image_emb"]["table"])


def image_head(params: dict, cfg: DALLEConfig) -> dict:
    """The image half of the output head in LOOKUP layout: {"table":
    (num_image_tokens, dim), "b": its (num_image_tokens,) bias entries when
    the head has a bias}.  A decode step only ever produces an image position,
    whose text columns `logits_mask_slice` forbids, and under a shared
    embedding looks its input up in the same rows — so a serving engine
    derives this ONCE from weights that do not change under it, where
    `_image_table` re-derives the slice and the transpose on every call.  A
    quantized `w` stays as stored: its per-column scales are the table's
    per-row ones (`quantization.quantize_table`'s format)."""
    lin = params["logits_linear"]
    w, ntp = lin["w"], cfg.num_text_tokens_padded
    if isinstance(w, dict):
        table = {"qvalue": w["qvalue"][:, ntp:].T, "scale": w["scale"][ntp:, None]}
    else:
        table = w[:, ntp:].T
    head = {"table": table}
    if "b" in lin:
        head["b"] = lin["b"][ntp:]
    return head


def remap_and_bos(cfg: DALLEConfig, text: jnp.ndarray) -> jnp.ndarray:
    """Give padding (id 0) a unique per-position id, then prepend <bos>=0.

    Ids are clamped into the raw text vocab first (before the pad remap):
    out-of-range ids (e.g. a tokenizer whose vocab exceeds num_text_tokens)
    would otherwise hit jnp.take's default out-of-bounds FILL behavior and
    silently produce NaN embeddings (on every backend)."""
    b = text.shape[0]
    text = jnp.clip(text, 0, cfg.num_text_tokens - 1)
    text_range = jnp.arange(cfg.text_seq_len) + (cfg.num_text_tokens_padded - cfg.text_seq_len)
    text = jnp.where(text == 0, text_range, text)
    return jnp.concatenate([jnp.zeros((b, 1), text.dtype), text], axis=1)


@jax.named_scope("embed")
def embed_text_ids(params: dict, cfg: DALLEConfig, text_ids: jnp.ndarray) -> jnp.ndarray:
    """text_ids: (b, n) post-remap ids incl. bos, positions 0..n-1."""
    emb = jnp.take(_text_table(params, cfg), text_ids, axis=0)
    if cfg.learned_positions:
        pos = jnp.take(params["text_pos"]["table"], jnp.arange(text_ids.shape[1]), axis=0)
        emb = emb + pos
    return emb


def image_pos_table(params: dict, cfg: DALLEConfig) -> Optional[jnp.ndarray]:
    """(image_seq_len, dim) axial positional embeddings, or None under rotary."""
    if not cfg.learned_positions:
        return None
    fmap = cfg.image_fmap_size
    h = jnp.repeat(params["image_pos_h"]["table"], fmap, axis=0)
    w = jnp.tile(params["image_pos_w"]["table"], (fmap, 1))
    return h + w


@jax.named_scope("embed")
def embed_image_codes(params: dict, cfg: DALLEConfig, codes: jnp.ndarray, start: int = 0) -> jnp.ndarray:
    """codes: (b, m) image code ids occupying raster positions start..start+m-1."""
    emb = jnp.take(_image_table(params, cfg), codes, axis=0, mode="clip")
    pos = image_pos_table(params, cfg)
    if pos is not None:
        emb = emb + jax.lax.dynamic_slice(pos, (start, 0), (codes.shape[1], pos.shape[1]))
    return emb


def logits_mask_slice(cfg: DALLEConfig, n: int) -> jnp.ndarray:
    """(n, total_tokens) bool; True = FORBIDDEN (matches the reference's
    masked_fill semantics at dalle_pytorch.py:450-455)."""
    seq_range = jnp.arange(n)[:, None]
    logits_range = jnp.arange(cfg.total_tokens)[None, :]
    return ((seq_range >= cfg.text_seq_len) & (logits_range < cfg.num_text_tokens_padded)) | (
        (seq_range < cfg.text_seq_len) & (logits_range >= cfg.num_text_tokens_padded)
    )


def to_logits(params: dict, cfg: DALLEConfig, x: jnp.ndarray) -> jnp.ndarray:
    return linear(params["logits_linear"],
                  apply_norm(cfg.transformer_config(), params["logits_norm"], x))


def to_image_logits(params: dict, cfg: DALLEConfig, head: dict, x: jnp.ndarray) -> jnp.ndarray:
    """`to_logits(...)[..., num_text_tokens_padded:]` from `image_head`'s
    table: per column the same sum of the same `dim` products, contracted
    against the table's rows."""
    from dalle_pytorch_tpu.quantization import maybe_dequant_weight

    x = apply_norm(cfg.transformer_config(), params["logits_norm"], x)
    y = jax.lax.dot_general(
        x, maybe_dequant_weight(head["table"], x.dtype),
        (((x.ndim - 1,), (1,)), ((), ())), preferred_element_type=x.dtype)
    if "b" in head:
        y = y + head["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def forward(
    params: dict,
    cfg: DALLEConfig,
    text: jnp.ndarray,
    image_codes: Optional[jnp.ndarray] = None,
    return_loss: bool = False,
    null_cond_prob: float = 0.0,
    key: Optional[jax.Array] = None,
    return_aux: bool = False,
    with_mtp_logits: bool = False,
):
    """Training/scoring forward.

    text: (b, text_seq_len) token ids with 0 = padding.
    image_codes: (b, image_seq_len) VAE code indices (callers with raw pixels
    tokenize through the frozen VAE first).
    Returns logits (b, n, total_tokens) or the weighted CE loss; with
    `return_aux`, a pair of that and a dict of device scalars beside it (a
    routed trunk's `moe_load_max_over_mean`, `moe_pairs_here` and
    `moe_overflow_share`, with a prediction module the `main_loss` and
    `mtp_loss` the loss is the sum of; {} for a dense trunk), and under
    `rule_inputs` what `param_rule` reads, keyed by the parameter's path.
    `with_mtp_logits` (not with `return_loss`): the first of the pair is
    (logits, the prediction module's (b, n - 1, total_tokens) logits)."""
    assert text.shape[-1] == cfg.text_seq_len, (
        f"text length {text.shape[-1]} != text_seq_len {cfg.text_seq_len}"
    )
    drop_key = None
    if null_cond_prob > 0.0:
        assert key is not None, "null_cond_prob requires a PRNG key"
        key, null_key = jax.random.split(key)
        null_mask = prob_mask_like(null_key, (text.shape[0],), null_cond_prob)
        text = text * (~null_mask)[:, None]
    if key is not None:
        drop_key = key

    text_ids = remap_and_bos(cfg, text)
    tokens = embed_text_ids(params, cfg, text_ids)

    if image_codes is not None and image_codes.size > 0:
        img_emb = embed_image_codes(params, cfg, image_codes)
        tokens = jnp.concatenate([tokens, img_emb], axis=1)

    # drop the final token when the sequence overruns total_seq_len (it has
    # nothing left to predict)
    if tokens.shape[1] > cfg.total_seq_len:
        tokens = tokens[:, : cfg.total_seq_len]
    n = tokens.shape[1]

    if cfg.stable:
        alpha = 0.1
        tokens = tokens * alpha + jax.lax.stop_gradient(tokens) * (1 - alpha)

    out, aux = apply_transformer(params["transformer"], cfg.transformer_config(), tokens,
                                 dropout_key=drop_key, return_stats=True)

    if cfg.stable:
        out = divide_max(out)

    # a bias-balanced router's choice counts, by the path of the bias they move
    rule_inputs = {f"transformer/shared_ff/{ff_id}/router/bias": counts
                   for ff_id, counts in aux.pop("moe_choice_counts", {}).items()}
    mtp_logits = None
    if cfg.mtp_depth:
        mtp_logits, block_aux = _mtp_logits(params, cfg, tokens, out, drop_key)
        rule_inputs.update({f"mtp/block/shared_ff/{ff_id}/router/bias": counts
                            for ff_id, counts in block_aux.pop("moe_choice_counts", {}).items()})
        # the load scalars are means over routed layers: the trunk's and the module's one
        routed = sum(cfg.transformer_config().ff_type(i) == "moe" for i in range(cfg.depth))
        aux = {k: (aux[k] * routed + v) / (routed + 1) if k in aux else v
               for k, v in block_aux.items()} if block_aux else aux
    if rule_inputs:
        aux["rule_inputs"] = rule_inputs
        aux["moe_bias_abs_max"] = jnp.max(jnp.stack([
            jnp.max(jnp.abs(_leaf_at(params, path).astype(jnp.float32))) for path in rule_inputs]))

    with jax.named_scope("logits_loss"):
        logits = to_logits(params, cfg, out)
        logits = jnp.where(
            logits_mask_slice(cfg, n)[None], jnp.finfo(logits.dtype).min, logits
        )

    if health_mod.taps_active():
        # output-head numerics for the diagnostic probe: vocab-logit max and
        # mean predictive entropy (H = lse - E_p[logit]; the masked fills
        # carry zero probability, so the streamed identity stays exact)
        lg32 = logits.astype(jnp.float32)
        lse_h = jax.scipy.special.logsumexp(lg32, axis=-1)
        ent_h = lse_h - jnp.sum(jax.nn.softmax(lg32, axis=-1) * lg32, axis=-1)
        health_mod.tap(
            "dalle_logits",
            logit_max=jnp.max(lg32),
            entropy_mean=jnp.mean(ent_h),
        )

    if not return_loss:
        if with_mtp_logits:
            logits = (logits, mtp_logits)
        return (logits, aux) if return_aux else logits

    assert image_codes is not None, "when training, image codes must be supplied"
    with jax.named_scope("logits_loss"):
        labels = jnp.concatenate(
            [text_ids[:, 1:], image_codes + cfg.num_text_tokens_padded], axis=1
        )
        assert labels.shape[1] == cfg.total_seq_len
        loss = _weighted_ce(cfg, logits, labels, cfg.text_seq_len)
    if mtp_logits is not None:
        with jax.named_scope("mtp"), jax.named_scope("mtp_head"):
            # position i's target is token i + 2: the main labels one further on
            mtp_loss = _weighted_ce(cfg, mtp_logits, labels[:, 1:], cfg.text_seq_len - 1)
        aux = dict(aux, main_loss=loss, mtp_loss=mtp_loss)
        loss = loss + cfg.mtp_loss_weight * mtp_loss
    return (loss, aux) if return_aux else loss


def _leaf_at(params: dict, path: str):
    for key in path.split("/"):
        params = params[key]
    return params


def param_rule(cfg: DALLEConfig):
    """What `make_train_step(param_rule=...)` takes for this configuration: the
    rule that moves each parameter `forward`'s `rule_inputs` names (a
    bias-balanced router's bias, by its layer's choice counts of the step),
    or None where the loss names none."""
    if cfg.moe_router != "sigmoid_bias":
        return None
    from dalle_pytorch_tpu.models.moe import balance_bias

    def rule(path: str, bias, counts):
        with jax.named_scope("moe_bias_update"):
            return balance_bias(cfg, bias, counts)

    return rule


def _weighted_ce(cfg: DALLEConfig, logits, labels, n_text: int):
    """(CE over the first `n_text` positions, whose targets are text, +
    loss_img_weight x CE over the rest) / (loss_img_weight + 1)."""
    # CE as gather - logsumexp: same math as log_softmax+gather but never
    # materializes a second (b, n, vocab) f32 tensor (XLA streams the
    # reduction over the bf16 logits)
    logits32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)
    label_logit = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    token_ll = label_logit - lse
    loss_text = -jnp.mean(token_ll[:, :n_text])
    loss_img = -jnp.mean(token_ll[:, n_text:])
    return (loss_text + cfg.loss_img_weight * loss_img) / (cfg.loss_img_weight + 1)


@jax.named_scope("mtp")
def _mtp_logits(params: dict, cfg: DALLEConfig, tokens, out, drop_key):
    """The prediction module over the trunk's output `out` (before the final
    norm) and the input embeddings `tokens`, both (b, n, dim).  Returns (its
    (b, n - 1, total_tokens) logits, whose row i predicts token i + 2 and is
    masked by that target's position; its block's `apply_transformer`
    stats).  The block runs over all n positions, so that
    the sequence keeps the length the attention kernel takes: the last has no
    next embedding (zeros) and no target, is routed like any token, reaches
    no earlier position (causal) and is cut from the logits."""
    from dalle_pytorch_tpu.observability import metrics as obs_metrics

    mtp, block_cfg = params["mtp"], cfg.mtp_block_config()
    obs_metrics.counter("train/mtp_layers").inc(cfg.mtp_depth)
    n = tokens.shape[1]
    with jax.named_scope("mtp_merge"):
        e_next = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        merged = linear(mtp["merge"], jnp.concatenate(
            [apply_norm(block_cfg, mtp["h_norm"], out), apply_norm(block_cfg, mtp["e_norm"], e_next)],
            axis=-1))
    h, block_aux = apply_transformer(mtp["block"], block_cfg, merged, dropout_key=drop_key,
                                     return_stats=True)
    with jax.named_scope("mtp_head"):
        logits = linear(params["logits_linear"], apply_norm(block_cfg, mtp["norm"], h[:, : n - 1]))
        logits = jnp.where(logits_mask_slice(cfg, n)[None, 1:], jnp.finfo(logits.dtype).min, logits)
    return logits, block_aux
