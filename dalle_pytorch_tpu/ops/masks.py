"""Static attention-pattern masks.

The reference implements its sparse attention variants as gather/unfold-based
torch modules (/root/reference/dalle_pytorch/attention.py:103-335).  On TPU the
idiomatic design is the one the reference itself uses for
`optimize_for_inference` (/root/reference/dalle_pytorch/transformer.py:333-350):
express every pattern as a static boolean mask over one dense attention — XLA
keeps the matmuls on the MXU, and Pallas kernels can later skip fully-masked
blocks.  Masks are built in numpy at trace time (static shapes) and are
combined with the causal triangle inside the attention op.

Layout convention: position 0..text_len-1 is [<bos> + text], positions
text_len..text_len+fmap**2-1 are the raster-ordered image grid, where
text_len = seq_len + 1 - fmap**2.  Masks are returned at (seq_len, seq_len),
i.e. the layout truncated by its final position, matching the reference's
`seq_len`-sized static masks.
"""
from __future__ import annotations

from functools import lru_cache

import jax.numpy as jnp
import numpy as np

ATTN_TYPES = ("full", "axial_row", "axial_col", "conv_like", "sparse")


def causal_mask(n: int) -> jnp.ndarray:
    """(n, n) bool, True = may attend (j <= i)."""
    return jnp.asarray(np.tril(np.ones((n, n), dtype=bool)))


@lru_cache(maxsize=64)
def _pattern_mask_np(
    attn_type: str,
    seq_len: int,
    image_fmap_size: int,
    kernel_size: int,
    dilation: int,
) -> np.ndarray:
    fmap = image_fmap_size
    img_seq_len = fmap * fmap
    text_len = seq_len + 1 - img_seq_len
    layout = text_len + img_seq_len  # == seq_len + 1

    mask = np.zeros((layout, layout), dtype=bool)
    mask[:, :text_len] = True  # everything attends to text (causality added later)

    if attn_type == "full":
        mask[:, :] = True
    elif attn_type == "axial_row":
        h = np.arange(img_seq_len) // fmap
        same_row = h[:, None] == h[None, :]
        mask[text_len:, text_len:] = same_row
    elif attn_type == "axial_col":
        w = np.arange(img_seq_len) % fmap
        same_col = w[:, None] == w[None, :]
        mask[text_len:, text_len:] = same_col
    elif attn_type == "conv_like":
        h = np.arange(img_seq_len) // fmap
        w = np.arange(img_seq_len) % fmap
        dh = h[:, None] - h[None, :]  # query minus key
        dw = w[:, None] - w[None, :]
        max_off = (kernel_size - 1) * dilation
        ok_h = (dh >= 0) & (dh <= max_off) & (dh % dilation == 0)
        ok_w = (dw >= 0) & (dw <= max_off) & (dw % dilation == 0)
        mask[text_len:, text_len:] = ok_h & ok_w
    else:
        raise ValueError(f'attention type "{attn_type}" has no static mask')

    return mask[:seq_len, :seq_len]


@lru_cache(maxsize=16)
def _block_sparse_mask_np(
    seq_len: int,
    image_fmap_size: int,
    block_size: int,
    num_random_blocks: int,
    local_window_blocks: int,
    seed: int,
) -> np.ndarray:
    """Block-sparse layout with the semantics of DeepSpeed's
    VariableSparsityConfig as used by the reference
    (/root/reference/dalle_pytorch/attention.py:349-365): fixed block size,
    a local window of preceding blocks, text-covering global blocks (global in
    both row and column), and per-query-block random blocks; unidirectional
    (lower-triangular at block granularity).  The random choices are seeded
    for reproducibility (the reference's are not — layouts are drawn once per
    module instantiation)."""
    img_seq_len = image_fmap_size ** 2
    text_len = seq_len + 1 - img_seq_len
    nb = -(-seq_len // block_size)
    num_global = -(-text_len // block_size)

    layout = np.zeros((nb, nb), dtype=bool)
    for qb in range(nb):
        lo = max(0, qb - local_window_blocks + 1)
        layout[qb, lo : qb + 1] = True  # local window
    layout[:, :num_global] = True  # global text blocks as keys
    layout[:num_global, :] = True  # global text blocks as queries
    rng = np.random.RandomState(seed)
    for qb in range(nb):
        if qb > 0 and num_random_blocks > 0:
            picks = rng.randint(0, qb + 1, size=num_random_blocks)
            layout[qb, picks] = True
    # unidirectional: no block above the diagonal
    layout &= np.tril(np.ones((nb, nb), dtype=bool))

    mask = np.kron(layout, np.ones((block_size, block_size), dtype=bool))
    return mask[:seq_len, :seq_len]


def _block_sparse_mask_np_heads(
    seq_len: int,
    image_fmap_size: int,
    block_size: int,
    num_random_blocks: int,
    local_window_blocks: int,
    seed: int,
    heads: int,
) -> np.ndarray:
    """(heads, seq_len, seq_len) — one random-block stream per head (the
    7919 stride keeps per-head seeds disjoint across layer seeds).  The
    SINGLE source of the per-head scheme: the transformer's pattern builder
    and the public helper below must agree or a checkpointed model's layout
    stops being reproducible."""
    return np.stack([
        _block_sparse_mask_np(
            seq_len, image_fmap_size, block_size, num_random_blocks,
            local_window_blocks, seed + 7919 * h,
        )
        for h in range(heads)
    ])


def build_block_sparse_mask(
    seq_len: int,
    image_fmap_size: int,
    block_size: int = 16,
    num_random_blocks: int | None = None,
    local_window_blocks: int = 4,
    seed: int = 0,
    heads: int | None = None,
) -> jnp.ndarray:
    """(seq_len, seq_len) layout, or (heads, seq_len, seq_len) when `heads`
    is given — each head draws its own random blocks (DeepSpeed's sparse
    attention varies the layout per head,
    /root/reference/dalle_pytorch/attention.py:349-365); the local window and
    global text blocks are head-invariant."""
    if num_random_blocks is None:
        num_random_blocks = seq_len // block_size // 4
    if heads is None:
        return jnp.asarray(
            _block_sparse_mask_np(
                seq_len, image_fmap_size, block_size, num_random_blocks, local_window_blocks, seed
            )
        )
    return jnp.asarray(
        _block_sparse_mask_np_heads(
            seq_len, image_fmap_size, block_size, num_random_blocks,
            local_window_blocks, seed, heads,
        )
    )


@lru_cache(maxsize=8)
def window_mask_np(n: int, window: int) -> np.ndarray:
    """(n, n) bool causal band of a sliding-window layer: query i sees key j
    where 0 <= i - j < window, itself included [`sliding_window`].  Static, so
    the flash kernels derive the band's tile liveness from it at trace time
    and the compacted grid runs the live tiles only: at 4,224 positions and
    384-tiles a window of 512 leaves 30 of the 121 steps, causality alone 66."""
    d = np.arange(n)[:, None] - np.arange(n)[None, :]
    return (d >= 0) & (d < window)


def block_live_np(mask: np.ndarray, block_q: int, block_k: int) -> np.ndarray:
    """Tile-granular liveness of a static pattern mask: (nq, nk) bool — or
    (h, nq, nk) for per-head masks — True where the (block_q, block_k) tile
    has at least one allowed element.  THE block-liveness table the flash
    kernels skip dead tiles by and the compacted-grid index builder
    (kernels/sparse_index.py) flattens; must be built at resolve_block()
    granularity."""
    m = np.asarray(mask, dtype=bool)  # host-sync-ok: static trace-time mask
    n = m.shape[-1]
    assert n % block_q == 0 and n % block_k == 0, (n, block_q, block_k)
    nq, nk = n // block_q, n // block_k
    if m.ndim == 3:
        return m.reshape(m.shape[0], nq, block_q, nk, block_k).any(axis=(2, 4))
    return m.reshape(nq, block_q, nk, block_k).any(axis=(1, 3))


def build_pattern_mask(
    attn_type: str,
    seq_len: int,
    image_fmap_size: int,
    kernel_size: int = 5,
    dilation: int = 1,
) -> jnp.ndarray:
    """(seq_len, seq_len) bool pattern mask, True = may attend.  Must be
    AND-ed with the causal triangle by the caller."""
    return jnp.asarray(
        _pattern_mask_np(attn_type, seq_len, image_fmap_size, kernel_size, dilation)
    )
