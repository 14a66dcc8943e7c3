"""Post-training quantization for serving: int8/fp8 weights + int8 paged KV.

Two independent levers, both dequant-on-use (the matmuls and the attention
math run in the compute dtype; only *storage* shrinks):

* **Weights** — `quantize_tree` replaces every 2-D matmul leaf keyed ``w``
  with ``{"qvalue": int8 (in, out), "scale": (out,) original-float}``:
  symmetric per-output-channel quantization (scale = amax/127 along the
  input axis).  The scale keeps the ORIGINAL float dtype, so it doubles as
  the tree's compute-dtype record (`weight_dtype`).  The text/image
  embedding TABLES are quantized too, per row (scale (N, 1) so the same
  dequant hook broadcasts) — at mid-size geometry the tables are ~15-30%
  of the footprint, and leaving them float would honestly miss the 1.9x
  at-rest bar.  ``fp8`` stores float8_e4m3 qvalues (scale = amax/448)
  where the dtype exists — gated, never required.  Positional tables,
  norms, biases, and conv kernels are left alone (those ARE a rounding
  error, and some are sliced positionally).  The sub-dict flows through
  the v3 checkpoint
  format's nested paths unchanged, and through the PR 6 registry: ``re``
  search rules match ``.../qkv/w/qvalue`` exactly like ``.../qkv/w``, so
  int8 blocks inherit their parent's placement; the 1-D scales get their
  own rules (column-parallel scales shard with their out axis, row-parallel
  scales replicate).

* **Paged KV** — `init_paged_pool(..., quantize="int8")` stores int8 k/v
  blocks with PER-TOKEN bf16 scales beside them (shape = block shape minus
  dim_head).  Per-token (not per-block) scales are what make the
  incremental decode scatter exact: writing one new column never re-scales
  a block's existing tokens, so there is no accumulation drift beyond the
  rounding of each token once.  bf16 scales cost 2/dim_head bytes per
  element — at dim_head 64 the pool lands at 1.03 bytes/elem, a 1.94x
  reduction vs bf16 (f32 scales would miss the 1.9x bar at 1.88x).

Honesty layer: `kv_bytes_per_elem` is the ONE pricing formula shared by the
memory ledger, the comms handoff row, and the pool byte budget, so every
claimed byte is the same byte.  `assert_quantized_reduction` is the >=1.9x
gate — it lives here (called by tests/bench/tools at REALISTIC geometry)
rather than inside the ledger, because at tiny test geometry (dim_head 8)
the scale overhead honestly eats the win (1.6x, see DESIGN.md round 16).

Everything in this module is jit-pure (tools/lint_host_sync.py covers it):
quantize/dequantize trace inside the serving jits.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

# per-token KV scale storage dtype: bf16 keeps bytes/elem at 1 + 2/dim_head
# (f32 would be 1 + 4/dim_head = 1.88x at dh 64, under the 1.9x bar)
KV_SCALE_DTYPE = jnp.bfloat16
KV_SCALE_ITEMSIZE = 2

# declared numerics budgets for the quantized_parity gate: greedy logit
# drift is measured RELATIVE to the baseline logits' std (absolute drift on
# a random-init net means nothing), asserted in tests/test_quantization.py
# and gated as a bench row.  Measured on the f32 CPU smoke configs: kv-only
# ~3e-4, weights+kv ~1e-2 rel drift — the budgets leave room for bf16
# compute and trained (less uniform) weight distributions on real params.
KV_PARITY_REL_BUDGET = 0.05        # int8 KV only, weights untouched
FULL_PARITY_REL_BUDGET = 0.20      # int8 weights + int8 KV together

WEIGHT_DTYPES = ("int8", "fp8")
KV_DTYPES = ("int8",)


def fp8_dtype():
    """float8_e4m3 if this jax build ships it, else None (callers gate)."""
    return getattr(jnp, "float8_e4m3fn", None)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def is_quantized_weight(w: Any) -> bool:
    return isinstance(w, dict) and "qvalue" in w and "scale" in w


def quantize_weight(w: jnp.ndarray, dtype: str = "int8") -> Dict[str, Any]:
    """Symmetric per-output-channel quantization of one (in, out) matmul
    weight.  scale keeps w's float dtype (it is also the compute-dtype
    record); zero columns get scale 0 and qvalue 0 (dequant is exact)."""
    assert w.ndim == 2, f"quantize_weight wants (in, out), got {w.shape}"
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)  # (out,)
    if dtype == "int8":
        scale = amax / 127.0
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(w.astype(jnp.float32) / safe), -127, 127)
        q = q.astype(jnp.int8)
    elif dtype == "fp8":
        f8 = fp8_dtype()
        if f8 is None:
            raise ValueError(
                "fp8 weights need jnp.float8_e4m3fn, which this jax build "
                "does not ship — use int8")
        scale = amax / 448.0  # e4m3 finite max
        safe = jnp.where(scale > 0, scale, 1.0)
        q = (w.astype(jnp.float32) / safe).astype(f8)
    else:
        raise ValueError(f"unknown weight quant dtype {dtype!r}")
    return {"qvalue": q, "scale": scale.astype(w.dtype)}


def quantize_table(t: jnp.ndarray, dtype: str = "int8") -> Dict[str, Any]:
    """Per-ROW symmetric quantization of an (N, dim) embedding table: scale
    is (N, 1) — kept 2-D so `maybe_dequant_weight`'s qvalue * scale
    broadcast serves weights ((in,out)*(out,)) and tables alike, and so the
    registry's LARGEST default shards the scale rows with the table rows."""
    assert t.ndim == 2, f"quantize_table wants (N, dim), got {t.shape}"
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=1, keepdims=True)
    if dtype == "int8":
        scale = amax / 127.0
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(t.astype(jnp.float32) / safe), -127, 127)
        q = q.astype(jnp.int8)
    elif dtype == "fp8":
        f8 = fp8_dtype()
        if f8 is None:
            raise ValueError(
                "fp8 tables need jnp.float8_e4m3fn, which this jax build "
                "does not ship — use int8")
        scale = amax / 448.0
        safe = jnp.where(scale > 0, scale, 1.0)
        q = (t.astype(jnp.float32) / safe).astype(f8)
    else:
        raise ValueError(f"unknown table quant dtype {dtype!r}")
    return {"qvalue": q, "scale": scale.astype(t.dtype)}


def maybe_dequant_weight(w: Any, dtype: Optional[Any] = None) -> jnp.ndarray:
    """Dequantize a {"qvalue","scale"} weight (or pass a plain array
    through), optionally cast to `dtype`.  THE dequant-on-use hook: every
    matmul/emb-table consumer routes through here, so quantized and plain
    trees run the same forward."""
    if is_quantized_weight(w):
        scale = w["scale"]
        out = w["qvalue"].astype(scale.dtype) * scale
    else:
        out = w
    return out if dtype is None else out.astype(dtype)


# embedding tables quantize_tree converts (per row); positional tables are
# excluded — they are tiny, summed (never matmul'd), and pos_h/pos_w add
# BEFORE the take so per-row scales would not commute with the sum
QUANTIZED_TABLES = ("text_emb", "image_emb")


def quantize_tree(params: Any, dtype: str = "int8") -> Any:
    """Post-training quantization pass over a param tree: every 2-D float
    matmul leaf keyed "w" (qkv, out, w1, w1g, w2, logits_linear) becomes a
    per-output-channel {"qvalue", "scale"} sub-dict, and the text/image
    embedding tables become per-row ones.  Conv kernels are 4-D, positional
    tables, norms and biases stay float.  Idempotent (already-quantized
    leaves pass through); structure otherwise unchanged, so checkpoints,
    the registry, and reshard all see ordinary nested dict paths
    (.../w/qvalue, .../w/scale)."""
    if dtype not in WEIGHT_DTYPES:
        raise ValueError(f"dtype must be one of {WEIGHT_DTYPES}, got {dtype!r}")

    def is_plain_2d(v):
        return (not is_quantized_weight(v) and hasattr(v, "ndim")
                and v.ndim == 2
                and jnp.issubdtype(jnp.result_type(v), jnp.floating))

    def walk(node, path):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k == "w" and is_plain_2d(v):
                    out[k] = quantize_weight(v, dtype)
                elif (k == "table" and path and path[-1] in QUANTIZED_TABLES
                        and is_plain_2d(v)):
                    out[k] = quantize_table(v, dtype)
                else:
                    out[k] = walk(v, path + (k,))
            return out
        if isinstance(node, (list, tuple)):
            seq = [walk(v, path + (i,)) for i, v in enumerate(node)]
            return type(node)(seq) if isinstance(node, tuple) else seq
        return node

    return walk(params, ())


def dequantize_tree(params: Any) -> Any:
    """Inverse pass (up to rounding): every quantized weight back to a
    dense float array — the round-trip half of tools/quantize.py's test."""

    def walk(node):
        if is_quantized_weight(node):
            return maybe_dequant_weight(node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            seq = [walk(v) for v in node]
            return type(node)(seq) if isinstance(node, tuple) else seq
        return node

    return walk(params)


def tree_is_quantized(params: Any) -> bool:
    found = []

    def walk(node):
        if is_quantized_weight(node):
            found.append(True)
            return
        if isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    return bool(found)


def weight_dtype(params: dict) -> Any:
    """The tree's float compute dtype — `params["logits_linear"]["w"].dtype`
    made quantization-aware (the scale carries the original dtype)."""
    w = params["logits_linear"]["w"]
    if is_quantized_weight(w):
        return w["scale"].dtype
    return w.dtype


def weight_quant_kind(params: dict) -> Optional[str]:
    """"int8"/"fp8" when the tree's matmul weights are quantized, else None."""
    w = params["logits_linear"]["w"]
    if not is_quantized_weight(w):
        return None
    f8 = fp8_dtype()
    if f8 is not None and jnp.result_type(w["qvalue"]) == jnp.dtype(f8):
        return "fp8"
    return "int8"


# ---------------------------------------------------------------------------
# paged KV
# ---------------------------------------------------------------------------

def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-token int8: x (..., dim_head) -> (int8 (..., dim_head),
    bf16 scale (...,)).  Per-token granularity is load-bearing: the decode
    scatter writes ONE new token per step, and a per-token scale means that
    write never re-quantizes neighbors already in the block."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)[..., None]
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / safe), -127, 127)
    return q.astype(jnp.int8), scale.astype(KV_SCALE_DTYPE)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """int8 (..., dim_head) + scale (...,) -> float (..., dim_head)."""
    return q.astype(dtype) * scale.astype(dtype)[..., None]


def quantize_cache_layers(layers: Any) -> Any:
    """Quantize a dense prefill cache's k/v (handoff compression for the
    disaggregated prefill worker).  Shift rings stay float — they are
    O(fmap*dim) per lane, noise next to the KV prefix.  Because the scale
    is per-token, quantize-then-pack here equals pack-then-quantize on the
    decode side, so the wire format does not perturb parity between the
    fused and disaggregated paths."""

    def qentry(e):
        kq, ks = quantize_kv(e["k"])
        vq, vs = quantize_kv(e["v"])
        return dict(e, k=kq, v=vq, k_scale=ks, v_scale=vs)

    return [qentry(e) for e in layers]


# ---------------------------------------------------------------------------
# pricing (the single source every ledger row quotes)
# ---------------------------------------------------------------------------

def kv_bytes_per_elem(kv_quant: Optional[str], itemsize: float,
                      dim_head: int) -> float:
    """Bytes per stored KV element: the dtype's itemsize, or for int8 the
    payload byte plus the per-token scale amortized over dim_head."""
    if not kv_quant or kv_quant == "none":
        return float(itemsize)  # host-sync-ok: static python int
    if kv_quant not in KV_DTYPES:
        raise ValueError(f"kv quant must be one of {KV_DTYPES}, got {kv_quant!r}")
    return 1.0 + KV_SCALE_ITEMSIZE / float(dim_head)  # host-sync-ok: static


def kv_pool_reduction(dim_head: int, itemsize: float = 2.0) -> float:
    """At-rest reduction of an int8 KV pool vs an `itemsize`-byte pool
    (default bf16).  1.94x at dim_head 64; honestly only 1.6x at the test
    suite's dim_head 8."""
    # host-sync-ok: static config arithmetic
    return float(itemsize) / kv_bytes_per_elem("int8", itemsize, dim_head)


def tree_weight_bytes(params: Any, itemsize: Optional[int] = None) -> float:
    """Storage bytes of a (possibly quantized) param tree: float leaves at
    their dtype (or repriced at `itemsize`) PLUS int8/fp8 qvalue payloads at
    1 byte — the quantization-aware replacement for comms.tree_float_bytes
    on trees that may hold integer weight blocks."""
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(params):
        size = getattr(leaf, "size", None)
        if size is None:
            continue
        dt = jnp.result_type(leaf)
        if jnp.issubdtype(dt, jnp.floating):
            total += size * (itemsize if itemsize is not None
                             else jnp.dtype(dt).itemsize)
        elif dt == jnp.dtype(jnp.int8):
            total += size * 1.0
    return total


def weight_reduction(params_plain: Any, params_quant: Any,
                     baseline_itemsize: int = 2) -> float:
    """At-rest weight reduction of the quantized tree vs the plain tree,
    BOTH repriced at bf16 float storage (the serving baseline): an f32-init
    tree would otherwise flatter int8 with a free 4x on the numerator, and
    f32 residual floats (norms, scales) would unfairly tax it on the
    denominator."""
    base = tree_weight_bytes(params_plain, itemsize=baseline_itemsize)
    quant = tree_weight_bytes(params_quant, itemsize=baseline_itemsize)
    return base / quant if quant else float("inf")


def assert_quantized_reduction(name: str, reduction: float,
                               floor: float = 1.9) -> float:
    """The >=1.9x acceptance gate, invoked by tests/bench/tools at realistic
    geometry.  Deliberately NOT called inside the ledger: tiny test
    geometries (dim_head 8) honestly miss the bar and must still ledger
    truthfully."""
    assert reduction >= floor, (
        f"{name}: quantized at-rest reduction {reduction:.3f}x is under the "
        f"{floor}x bar — scale overhead is eating the byte savings")
    return reduction


def dequant_overhead_flops(tcfg: Any, kv_quant: Optional[str],
                           weights: Optional[str], slots: int,
                           emb_rows: int = 0) -> Dict[str, float]:
    """Analytic extra work one fused decode step pays for dequant-on-use:
    one multiply per dequantized element.  KV: each layer rematerializes its
    (slots, heads, seq, dim_head) k+v view; weights: every quantized matmul
    leaf is expanded once per step, plus `emb_rows` vocab-sized rows
    (logits projection + embedding-table gathers) at dim each.  Reported
    next to the step's matmul FLOPs so reports can show the overhead
    fraction — this is the honest negative (DESIGN round 16): at tiny batch
    the byte savings do not buy wall-clock back, they buy CAPACITY (more
    slots per chip)."""
    kv = 0.0
    if kv_quant and kv_quant != "none":
        kv = 2.0 * tcfg.depth * slots * tcfg.heads * tcfg.seq_len * tcfg.dim_head
    w = 0.0
    if weights and weights != "none":
        # qkv + out + w1 (+w1g) + w2 per layer: ~12*dim^2 per layer, plus
        # the vocab-row matrices (logits w, embedding tables)
        # host-sync-ok: static config arithmetic
        w = 12.0 * tcfg.depth * tcfg.dim * tcfg.dim + float(emb_rows) * tcfg.dim
    # decode-step matmul flops ~ 2 * params_matmul * slots (one token/slot)
    step = 2.0 * (12.0 * tcfg.depth * tcfg.dim * tcfg.dim) * max(slots, 1)
    total = kv + w
    return {
        "kv_dequant_flops": kv,
        "weight_dequant_flops": w,
        "dequant_flops_per_step": total,
        "dequant_frac_of_step": total / step if step else 0.0,
    }


# ---------------------------------------------------------------------------
# numerics parity harness (greedy, teacher-forced by construction)
# ---------------------------------------------------------------------------

def paged_greedy_logits(params: dict, cfg: Any, text,
                        quantize_kv_mode: Optional[str] = None,
                        steps: Optional[int] = None,
                        block_size: int = 8) -> Dict[str, Any]:
    """Greedy paged decode collecting per-step logits — the measurement half
    of the `quantized_parity` gate.  Runs the REAL serving path (dense
    prefill -> write_prefill_to_pool -> paged_decode_step loop) for one
    sequence, greedy argmax feeding, and returns the (steps, V) logits plus
    the chosen codes.  Compare a quantized run against a plain run of the
    same params/text to measure drift."""
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models import transformer as tr

    tcfg = cfg.transformer_config()
    n_pre = cfg.text_seq_len + 1
    n_steps = cfg.image_seq_len if steps is None else min(steps, cfg.image_seq_len)
    dt = weight_dtype(params)

    text = jnp.asarray(text, jnp.int32).reshape(1, cfg.text_seq_len)
    ids = dalle_mod.remap_and_bos(cfg, text)
    emb = dalle_mod.embed_text_ids(params, cfg, ids)
    cache = tr.init_cache(tcfg, 1, dtype=dt)
    out, cache = tr.prefill(params["transformer"], tcfg, emb, cache)

    vmask = dalle_mod.logits_mask_slice(cfg, cfg.total_seq_len)

    def logits_at(x_last, offset):
        lg = dalle_mod.to_logits(params, cfg, x_last)[:, 0]
        row = jnp.take(vmask, jnp.asarray(offset)[None], axis=0)[0]
        return jnp.where(row, jnp.finfo(lg.dtype).min, lg)

    lg0 = logits_at(out[:, -1:], n_pre - 1)
    code = jnp.clip(jnp.argmax(lg0, axis=-1) - cfg.num_text_tokens_padded,
                    0, cfg.num_image_tokens - 1).astype(jnp.int32)

    bps = tr.paged_blocks_per_seq(tcfg, block_size)
    pool = tr.init_paged_pool(tcfg, bps + 1, block_size, dt,
                              quantize=quantize_kv_mode)
    bt = jnp.arange(1, bps + 1, dtype=jnp.int32)[None]
    pool = tr.write_prefill_to_pool(pool, bt, cache["layers"],
                                    n_pre, block_size)
    rings = tr.init_slot_rings(tcfg, 1, dt)
    if rings is not None:
        rings = {"layers": [
            {"shift_attn": c["shift_attn"].astype(r["shift_attn"].dtype),
             "shift_ff": c["shift_ff"].astype(r["shift_ff"].dtype)}
            for r, c in zip(rings["layers"], cache["layers"])
        ]}

    def step(pool, rings, code, offset, img_prev):
        e = jnp.take(dalle_mod._image_table(params, cfg), code[:, None],
                     axis=0, mode="clip")
        pos = dalle_mod.image_pos_table(params, cfg)
        if pos is not None:
            e = e + jnp.take(pos, jnp.asarray(img_prev)[None], axis=0,
                             mode="clip")[:, None]
        out, pool, rings = tr.paged_decode_step(
            params["transformer"], tcfg, e, pool, bt,
            jnp.asarray([offset], jnp.int32), rings, block_size)
        lg = logits_at(out, offset)
        nxt = jnp.clip(jnp.argmax(lg, axis=-1) - cfg.num_text_tokens_padded,
                       0, cfg.num_image_tokens - 1).astype(jnp.int32)
        return pool, rings, lg, nxt

    step_fn = jax.jit(step, static_argnums=(3, 4))

    logits: List[Any] = [lg0]
    codes: List[Any] = [code]
    for t in range(n_steps - 1):
        pool, rings, lg, code = step_fn(pool, rings, code, n_pre + t, t)
        logits.append(lg)
        codes.append(code)
    return {
        "logits": jnp.concatenate(logits, axis=0),   # (steps, V)
        "codes": jnp.concatenate(codes, axis=0),     # (steps,)
    }


def greedy_parity_metrics(base: Dict[str, Any], quant: Dict[str, Any]
                          ) -> Dict[str, float]:
    """Drift between two paged_greedy_logits runs: max |delta logit| scaled
    by the baseline logits' std (finite entries only — the vocab mask pins
    both runs to -inf on forbidden rows), plus the greedy token match
    fraction (reported, not gated: on random-init nets argmax margins are
    noise).  Host-side: pulls the two small logit mats once, at the end."""
    import numpy as np

    lb = np.asarray(base["logits"], np.float32)  # host-sync-ok: parity report, after the run
    lq = np.asarray(quant["logits"], np.float32)  # host-sync-ok: parity report, after the run
    finite = np.isfinite(lb) & np.isfinite(lq) & (lb > np.finfo(np.float32).min / 2)
    drift = float(np.max(np.abs(np.where(finite, lb - lq, 0.0))))  # host-sync-ok: report scalar
    spread = float(max(np.std(lb[finite]), 1e-6))  # host-sync-ok: report scalar
    match = float(np.mean(np.asarray(base["codes"]) == np.asarray(quant["codes"])))  # host-sync-ok: report scalar
    return {
        "greedy_logit_drift_abs": drift,
        "greedy_logit_drift_rel": drift / spread,
        "logit_spread": spread,
        "token_match_frac": match,
    }
