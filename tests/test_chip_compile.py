"""The kernels of the main path, compiled for the chip without the chip.

The TPU compiler is installed here and compiles for a DESCRIBED `v5e:2x2`
topology (on-chip-measurement guide §2, rehearsal 3): interpret mode cannot
see what it refuses — a block not aligned to the tiling, a kernel over its
VMEM budget.  Shapes are chip_smoke.py's real ones (b4, h16, n1280, d128,
bf16, 256-tiles) and the train cells' (1,152 and 4,224 positions, widths 128
and 256, 384-tiles, every kernel body with a mask tile and without).  Nothing runs, so nothing here is a result or a time; a
compile that passes is not a chip run.  Every test that compiles skips where
the topology cannot be described.  Plus: each train cell's step traced at its
real sizes for the operands its flash calls take, and the hardware table,
which finds the kind the v5e reports and refuses a kind it does not know."""
import base64
import functools
import json
import os
import re
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dalle_pytorch_tpu.core import chips
from dalle_pytorch_tpu.kernels import flash_attention as fa
from dalle_pytorch_tpu.kernels import paged_attention as pa
from dalle_pytorch_tpu.models import moe
from dalle_pytorch_tpu.models.transformer import TransformerConfig, _pattern_for

B, H, N, D = 4, 16, 1280, 128  # the smoke's attention shape (fmap 32, text 256)
ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on a described v5e chip; kernels forced off
    interpret mode for the module (steering `_interpret` belongs in the test,
    not in an option of the program)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compiler for a described chip here
        pytest.skip(f"cannot describe a v5e topology: {e!r}")
    mp = pytest.MonkeyPatch()
    mp.setattr(fa, "_interpret", lambda: False)
    mp.setattr(moe, "_use_gmm_kernel", lambda: True)  # what a TPU backend answers
    yield SingleDeviceSharding(topo.devices[0])
    mp.undo()


@functools.lru_cache(maxsize=None)
def _pattern(kind, heads=H, per_head=False, seq_len=N, fmap=32):
    cfg = TransformerConfig(dim=heads * D, depth=1, seq_len=seq_len, heads=heads,
                            dim_head=D, image_fmap_size=fmap,
                            sparse_per_head=per_head)
    return np.asarray(_pattern_for(cfg, kind), bool)


def _compile(fn, sharding, *shapes_dtypes, precision="default"):
    """Compiled under the matmul precision the cells run with, the default:
    tests/conftest.py's "highest" is for comparisons on the CPU, and a kernel
    compiled under it (`contract_precision<fp32>` on every float32 product;
    refused outright by a bfloat16 one that does not state its own) is not the
    program that runs."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes_dtypes]
    with jax.default_matmul_precision(precision):
        return jax.jit(fn).lower(*args).compile().as_text()


def _flash_bodies(text):
    """[(kernel name, its Mosaic body as generic MLIR text)] for every flash
    kernel of a compiled program: the custom call carries the body as base64
    of MLIR bytecode in the `stable_mosaic` dialect, which parses as
    unregistered operations."""
    from jax.extend.mlir import ir

    out = []
    for name, body in re.findall(r'%(flash_\w+?)[.\d]* = [^\n]*?"body":"([A-Za-z0-9+/=]+)"', text):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            out.append((name, str(ir.Module.parse(base64.b64decode(body)))))
    return out


def _check_16bit_products(text, n_kernels):
    """What tests/test_flash.py checks on the jaxpr, on the program the chip's
    compiler was handed: every `tpu.matmul` of every flash kernel takes
    bfloat16 operands and gives float32, with no precision attribute, and no
    bfloat16 vector is widened (`arith.extf`) anywhere in a body."""
    bodies = _flash_bodies(text)
    assert len(bodies) >= n_kernels, [n for n, _ in bodies]
    for name, mlir in bodies:
        products = [ln for ln in mlir.splitlines() if '"stable_mosaic.tpu.matmul"' in ln]
        assert products, name
        for ln in products:
            lhs, rhs, acc, res = re.findall(r"vector<[\dx]+x(\w+)>", ln[ln.rindex(" : "):])
            assert (lhs, rhs, acc, res) == ("bf16", "bf16", "f32", "f32"), (name, ln[-160:])
            assert "precision" not in ln, (name, ln[:200])
        assert [ln for ln in mlir.splitlines() if "arith.extf" in ln and "bf16" in ln] == [], name


def _grad_of(**kw):
    """fwd + dq + dk/dv in one program: the gradient of a scalar of the output."""
    def loss(q, k, v, key_mask=None):
        out = fa.flash_attention(q, k, v, key_mask=key_mask, **kw)
        return out.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


QKV = [((B, H, N, D), jnp.bfloat16)] * 3

CASES = {
    # dense grid, fwd + dq + dk/dv: the `full` layers and one axial pattern
    "dense_full": (lambda: _grad_of(grid="dense"), QKV, 3),
    "dense_axial_row": (
        lambda: _grad_of(mask=_pattern("axial_row"), grid="dense"), QKV, 3),
    # compacted (scalar-prefetch) grid, fwd + bwd
    "compact_conv_like": (
        lambda: _grad_of(mask=_pattern("conv_like"), grid="compact"), QKV, 3),
    "compact_conv_like_vfa_fwd": (
        lambda: lambda q, k, v: fa.flash_attention(
            q, k, v, mask=_pattern("conv_like"), grid="compact", vfa=True),
        QKV, 2),
    # the CLIP path: per-batch key-padding rows, not causal, f32, dim_head 64
    "key_mask_f32_d64": (
        lambda: _grad_of(causal=False),
        [((B, 8, 256, 64), jnp.float32)] * 3 + [((B, 256), jnp.bool_)], 3),
    # per-head block-sparse layouts need per-head tables
    "compact_per_head_sparse": (
        lambda: _grad_of(mask=_pattern("sparse", heads=4, per_head=True),
                         grid="compact"),
        [((2, 4, N, D), jnp.bfloat16)] * 3, 3),
}


def _paged_shapes(pool_dtype, block_size):
    """serve_batch's decode attention: 8 slots, 16 heads x 128, sequence
    1,152, a pool of 8 x 18 + 1 blocks."""
    seq, slots = 1152, 8
    nblk = -(-seq // block_size)
    row = ((slots, H, D), jnp.float32)
    pool = ((slots * nblk + 1, H, block_size, D), pool_dtype)
    return [row, row, row, pool, pool, ((slots, nblk), jnp.int32),
            ((slots,), jnp.int32), ((slots, seq), jnp.bool_)]


# the serving kernel: the float32 pool of both serve cells, and a bf16 pool
# (whose tile is 16 rows)
CASES["paged_decode_f32_block64"] = (
    lambda: pa.paged_decode_attention, _paged_shapes(jnp.float32, 64), 1)
CASES["paged_decode_bf16_block16"] = (
    lambda: pa.paged_decode_attention, _paged_shapes(jnp.bfloat16, 16), 1)


# the hybrid trunk's cell (train_q3n_ep16), one microbatch: a gated_full layer's
# attention (16 heads once the 2 key/value heads are spread, width 256) ...
CASES["dense_full_d256_seq4224"] = (
    lambda: _grad_of(grid="dense"), [((1, 16, 4224, 256), jnp.bfloat16)] * 3, 3)


# ... and the held experts' grouped products over the pair buffer (4,224 x 10
# rows, 32 experts): jax's megablox kernels of the backward (the rows'
# gradient is the forward kernel on the transposed weights; the weights'
# gradient is its transposed twin); a sum's gradient needs no forward value
def _grouped_grad():
    def loss(lhs, rhs, sizes):
        return moe.grouped_matmul(lhs, rhs, sizes).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1))


CASES["moe_grouped_up_proj"] = (
    _grouped_grad, [((42240, 2048), jnp.bfloat16), ((32, 2048, 512), jnp.bfloat16),
                    ((32,), jnp.int32)], 2)
CASES["moe_grouped_down_proj"] = (
    _grouped_grad, [((42240, 512), jnp.bfloat16), ((32, 512, 2048), jnp.bfloat16),
                    ((32,), jnp.int32)], 2)
# those are every pair's rows at once (a rank that holds every expert); the
# cell's program walks chunks of `moe.pair_rows` rows (twice the 2,640 pairs
# expected at 32 of 512 experts, in row tiles: 5,376)
CASES["moe_grouped_up_proj_pair_rows"] = (
    _grouped_grad, [((5376, 2048), jnp.bfloat16), ((32, 2048, 512), jnp.bfloat16),
                    ((32,), jnp.int32)], 2)
CASES["moe_grouped_down_proj_pair_rows"] = (
    _grouped_grad, [((5376, 512), jnp.bfloat16), ((32, 512, 2048), jnp.bfloat16),
                    ((32,), jnp.int32)], 2)


# the latent-attention trunk's cell (train_glm47_ep8), one microbatch of one
# sequence: an `mla` layer's core, 20 heads of key width 192 + 64 = value width
# 256 (the kernel takes one width for both) over 4,224 positions ...
CASES["dense_full_d256_h20_seq4224"] = (
    lambda: _grad_of(grid="dense"), [((1, 20, 4224, 256), jnp.bfloat16)] * 3, 3)
# ... and the 8 held experts' grouped products over one chunk of the ranking
# (`moe.pair_rows`: twice the 2,112 pairs expected at 8 of 64 experts top-4,
# in row tiles: 4,224), width 1,536: the down projection's 1,536 inner columns
# are no multiple of the kernel's 1,024-wide tile
CASES["moe_grouped_up_proj_glm"] = (
    _grouped_grad, [((4224, 2048), jnp.bfloat16), ((8, 2048, 1536), jnp.bfloat16),
                    ((8,), jnp.int32)], 2)
CASES["moe_grouped_down_proj_glm"] = (
    _grouped_grad, [((4224, 1536), jnp.bfloat16), ((8, 1536, 2048), jnp.bfloat16),
                    ((8,), jnp.int32)], 2)


# The train cells' attention at the tile `resolve_block` gives their sequences
# (384 x 384: 1,152 = 3 x 384, 4,224 = 11 x 384), all seven kernel bodies at
# both head widths, with a (384, 384) mask tile and without: the three dense
# bodies, the three compacted ones, and the compacted forward behind its
# max-only first pass.  d8 runs (8, 16, 1152, 128), d24 (4, 16, 1152, 128) with
# the mask its scan selects (traced, with its liveness table), the hybrid
# trunks (1, 16, 4224, 256) and (1, 20, 4224, 256) without a pattern; a
# pattern at 4,224 is the fmap-64 layout's (text 128), which no cell trains
# yet.  Kernels only: no whole step compiles here.
def _vfa_fwd(**kw):
    return lambda q, k, v: fa.flash_attention(q, k, v, grid="compact", vfa=True, **kw)


def _scan_selected_mask():
    """What a `scan_layers` body hands the kernel: a TRACED (n, n) mask with
    its liveness table at the resolved granularity."""
    def loss(q, k, v, mask, live):
        return fa.flash_attention(q, k, v, mask=mask, live=live).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


TILE_384 = {
    "d128_b8_seq1152": ([((8, 16, 1152, 128), jnp.bfloat16)] * 3,
                        lambda: _pattern("conv_like", seq_len=1152)),
    "d256_h20_seq4224": ([((1, 20, 4224, 256), jnp.bfloat16)] * 3,
                         lambda: _pattern("axial_row", heads=20, seq_len=4224, fmap=64)),
}
for _shape, (_qkv, _mask) in TILE_384.items():
    CASES[f"dense_full_{_shape}"] = (lambda: _grad_of(grid="dense"), _qkv, 3)
    CASES[f"compact_full_{_shape}"] = (lambda: _grad_of(grid="compact"), _qkv, 3)
    CASES[f"compact_full_vfa_fwd_{_shape}"] = (lambda: _vfa_fwd(), _qkv, 2)
    CASES[f"dense_pattern_{_shape}"] = (
        lambda _mask=_mask: _grad_of(mask=_mask(), grid="dense"), _qkv, 3)
    CASES[f"compact_pattern_{_shape}"] = (
        lambda _mask=_mask: _grad_of(mask=_mask(), grid="compact"), _qkv, 3)
    CASES[f"compact_pattern_vfa_fwd_{_shape}"] = (
        lambda _mask=_mask: _vfa_fwd(mask=_mask()), _qkv, 2)
CASES["compact_full_d256_seq4224"] = (
    lambda: _grad_of(grid="compact"), [((1, 16, 4224, 256), jnp.bfloat16)] * 3, 3)
CASES["dense_scan_mask_d128_b4_seq1152"] = (
    _scan_selected_mask,
    [((4, 16, 1152, 128), jnp.bfloat16)] * 3 + [((1152, 1152), jnp.bool_), ((3, 3), jnp.int32)], 3)


def _scan_selected_tables():
    """What `train_d24`'s scanned body hands the kernel under `grid="auto"`: the
    traced mask and liveness table, and the layer's compacted tables selected
    out of the stacked ones (TRACED, in `sparse_index.TABLE_KEYS` order)."""
    def loss(q, k, v, mask, live, *tabs):
        out = fa.flash_attention(q, k, v, mask=mask, live=live, tables=tabs)
        return out.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


# the 6 causal steps of the 3 x 3 grid of 384-tiles, in both traversals
CASES["compact_scan_tables_d128_b4_seq1152"] = (
    _scan_selected_tables,
    [((4, 16, 1152, 128), jnp.bfloat16)] * 3 + [((1152, 1152), jnp.bool_), ((3, 3), jnp.int32)]
    + [((1, 6), jnp.int32)] * 10, 3)

# the tile each flash case's sequence resolves under the default cap
TILE_OF_SEQ = {1280: "256x256", 1152: "384x384", 4224: "384x384"}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    from dalle_pytorch_tpu.observability import metrics as obs_metrics

    build, shapes, n_kernels = CASES[name]
    flash = name.startswith(("dense_", "compact_"))  # the flash cases on bfloat16 inputs
    if flash:
        tile = obs_metrics.counter(f"kernels/flash_tile_{TILE_OF_SEQ[shapes[0][0][2]]}")
        before = tile.value
    text = _compile(build(), one_chip, *shapes)
    assert text.count("tpu_custom_call") >= n_kernels, (
        f"{name}: expected >= {n_kernels} Pallas custom calls in the compiled program")
    if flash:
        assert shapes[0][1] == jnp.bfloat16
        assert tile.value == before + 1, name
        _check_16bit_products(text, n_kernels)


def test_flash_16bit_products_state_their_own_precision(one_chip):
    """Under this suite's ambient "highest" the bfloat16 kernels still compile
    (Mosaic refuses a 16-bit product asked for fp32 contraction: "Bad lhs
    type"), because `_dot` states DEFAULT on 16-bit operands: the program does
    not depend on the ambient setting.  The float32 kernels follow it, as
    they always did."""
    _check_16bit_products(_compile(_grad_of(grid="dense"), one_chip, *QKV, precision="highest"), 3)
    f32 = [((B, 8, 256, 64), jnp.float32)] * 3
    text = _compile(_grad_of(grid="dense"), one_chip, *f32, precision="highest")
    assert all("contract_precision<fp32>" in mlir for _, mlir in _flash_bodies(text))


def _cell_step(workload, described, microbatch=None):
    """(step_fn, state, batch, key) of a train cell of BENCHMARK.json: the
    program's own `make_train_step` on the cell's configuration file, recipe,
    traffic and `param_rule`, as `benchmark/kinds/train_steps*.py` build it,
    over an `eval_shape`'d state whose leaves `described(shape, dtype)` makes.
    `attn_kernel` "flash" is what "auto" answers on a TPU backend."""
    import sys

    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from benchmark.harness import build
    from benchmark.kinds.train_steps import _optimizer
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    sizes = json.loads((ROOT / config["file"]).read_text())
    traffic = json.loads((ROOT / "benchmark" / "traffic" / (cell["traffic"] + ".json")).read_text())
    recipe = sizes["train_recipe"]
    cfg = build.dalle_config(sizes, execution=recipe["execution"], scan_layers=recipe["scan_layers"],
                             remat_policy=recipe.get("remat_policy", "full"), attn_kernel="flash")
    batch_size = int(traffic["microbatch"]) * int(traffic["grad_accum"])
    microbatch = microbatch or int(traffic["microbatch"])
    with_aux = traffic["kind"] == "train_steps_mtp"

    def loss_fn(p, b, key):
        return dalle_mod.forward(p, cfg, b["text"], b["image_codes"], return_loss=True,
                                 return_aux=with_aux)

    param_dtype = build.dtype(recipe["param_dtype"])
    settings = StepSettings(compute_dtype=build.dtype(recipe["compute_dtype"]),
                            grad_dtype=build.dtype(recipe["grad_dtype"]),
                            grad_accum=batch_size // microbatch,
                            param_dtype=param_dtype if param_dtype != jnp.float32 else None)
    init_fn, step_fn = make_train_step(loss_fn, _optimizer(recipe), settings=settings,
                                       param_rule=dalle_mod.param_rule(cfg) if with_aux else None)
    state = jax.tree_util.tree_map(
        lambda a: described(a.shape, a.dtype),
        jax.eval_shape(lambda k: init_fn(dalle_mod.init_dalle(k, cfg)), jax.random.PRNGKey(0)))
    batch = {"text": described((batch_size, cfg.text_seq_len), jnp.int32),
             "image_codes": described((batch_size, cfg.image_seq_len), jnp.int32)}
    return cfg, step_fn, state, batch, described((2,), jnp.uint32)


@pytest.mark.parametrize("microbatch", [1, 2])
def test_glm_cell_step_fits_the_chip_at_microbatch_1_and_not_at_2(one_chip, microbatch):
    """`train_glm47_ep8`'s own optimizer step over an `eval_shape`'d state: the
    compiler takes it at the cell's microbatch, 1 x 4 (15.86e9 bytes of the
    chip's 16.9e9: a later change to the block that tips it over fails HERE
    and not on the chip), and refuses microbatch 2 x 2 for the chip's memory,
    which is why the traffic file says 1 (the largest of 4, 2, 1 that
    compiles; when 2 starts to fit, that file is due a change).  Nothing runs
    and nothing is allocated."""
    cfg, step_fn, state, batch, key = _cell_step(
        "train_glm47_ep8", lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip),
        microbatch=microbatch)
    assert cfg.execution == "sequential" and batch["text"].shape[0] == 4
    with jax.default_matmul_precision("default"):  # the suite's "highest" is for CPU comparisons
        lowered = step_fn.lower(state, batch, key)
        if microbatch == 1:
            text = lowered.compile().as_text()
            # six latent-attention blocks x (forward, dq, dkv) + five routed layers' grouped products
            assert text.count("tpu_custom_call") >= 18 + 15
            _check_16bit_products(text, 18)
        else:
            with pytest.raises(Exception, match="Ran out of memory in memory space hbm"):
                lowered.compile()


def _flash_counts():
    """{counter: total} of every `kernels/flash_*` counter the registry holds."""
    from dalle_pytorch_tpu.observability import metrics as obs_metrics

    return {name[len("kernels/"):]: rec["total"]
            for name, rec in obs_metrics.REGISTRY.snapshot(reset_window=False).items()
            if name.startswith("kernels/flash_")}


def _flash_counted(fn, *args):
    """What tracing `fn(*args)` added to the `kernels/flash_*` counters, as
    a function of a name's prefix: `counted("flash_tile_")`."""
    before = _flash_counts()
    fn(*args)
    grew = {n: c - before.get(n, 0) for n, c in _flash_counts().items() if c != before.get(n, 0)}
    return lambda prefix: {n: c for n, c in grew.items() if n.startswith(prefix)}


@functools.lru_cache(maxsize=None)
def _cell_step_flash_counts(workload):
    """Each train cell's step, TRACED once at its real sizes (`eval_shape`:
    nothing compiles, runs or is allocated, and no chip is described)."""
    cfg, step_fn, state, batch, key = _cell_step(workload, jax.ShapeDtypeStruct)
    return _flash_counted(jax.eval_shape, step_fn, state, batch, key)


# flash_attention calls a traced step holds: d8's eight layers, d24's one
# scanned layer body, the hybrid period's one `gated_full` layer, the latent
# trunk's five blocks and its prediction module's
CELL_FLASH_CALLS = [("train_d8", 8), ("train_d24", 1), ("train_q3n_ep16", 1), ("train_glm47_ep8", 6)]


@pytest.mark.parametrize("workload,calls", CELL_FLASH_CALLS)
def test_train_cells_feed_the_flash_kernels_16bit_operands(workload, calls):
    """Every `flash_attention` call a train cell's traced step holds takes
    16-bit operands: `kernels/flash_calls_32bit_operands` stays where it was."""
    assert _cell_step_flash_counts(workload)("flash_calls_") == {"flash_calls_16bit_operands": calls}


@pytest.mark.parametrize("workload,calls", CELL_FLASH_CALLS)
def test_train_cells_run_the_flash_kernels_at_384_tiles(workload, calls):
    """The same traced steps, read for the tile: every call of every train
    cell resolves 384 x 384 (1,152 = 3 x 384, 4,224 = 11 x 384) and no other
    tile is counted; under `scan_layers` the one scanned body carries the one
    tile all 24 layers run."""
    assert _cell_step_flash_counts(workload)("flash_tile_") == {"flash_tile_384x384": calls}


@pytest.mark.parametrize("workload,calls", CELL_FLASH_CALLS)
def test_train_cells_run_the_flash_kernels_on_the_compacted_grid(workload, calls):
    """The same traced steps, read for the grid: causality kills 3 of the 9
    steps of a 3 x 3 grid of 384-tiles (1,152) and 55 of 121 (4,224), so
    `grid="auto"` compacts every call of every train cell, the scanned body
    on its stacked tables included, and none takes the dense grid."""
    assert _cell_step_flash_counts(workload)("flash_grid_") == {"flash_grid_compact": calls}


@functools.lru_cache(maxsize=None)
def _prefill_flash_counts(positions):
    """Admission's prefill at the DALL-E serving widths, two layers, TRACED
    (nothing runs), read for its `kernels/flash_*` counters."""
    from dalle_pytorch_tpu.models import transformer as tr

    cfg = TransformerConfig(dim=2 * D, depth=2, seq_len=positions - 1 + 32 * 32, heads=2,
                            dim_head=D, image_fmap_size=32, attn_kernel="flash",
                            attn_types=("full", "axial_row"))
    params = jax.eval_shape(lambda k: tr.init_transformer(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: tr.init_cache(cfg, 1))
    x = jax.ShapeDtypeStruct((1, positions, cfg.dim), jnp.float32)
    return _flash_counted(jax.eval_shape, lambda p, x, c: tr.prefill(p, cfg, x, c),
                          params, x, cache)


@pytest.mark.parametrize("positions,tiles", [(128, {"flash_tile_128x128": 2}), (129, {})])
def test_serving_prefill_counts_the_tile_it_counted_before(positions, tiles):
    """Admission's prefill is not moved by the rule: at 128 positions (the
    DALL-E serving cells) one 128-tile a layer, as under the old default; at
    129 (`serve_olmoh_s32`) no multiple of 128 divides the sequence and
    `_use_flash` keeps the dense path, so no kernel and no tile is counted."""
    assert _prefill_flash_counts(positions)("flash_tile_") == tiles


@pytest.mark.parametrize("positions,grids", [(128, {"flash_grid_dense": 2}), (129, {})])
def test_serving_prefill_keeps_the_dense_grid(positions, grids):
    """Nor by the grid rule: a 128-position prefill is a 1 x 1 grid of
    128-tiles, whose one step is live, so each layer's call stays on the
    dense grid (its program lowers to the parent's text)."""
    assert _prefill_flash_counts(positions)("flash_grid_") == grids


@pytest.fixture(scope="module")
def decode_program(one_chip):
    """(engine, text of its decode program compiled for the chip) at
    serve_batch's widths and flags, depth cut to 2.  Nothing runs."""
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models.dalle import DALLEConfig
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    cfg = DALLEConfig(dim=H * D, depth=2, heads=H, dim_head=D, num_text_tokens=16384,
                      text_seq_len=128, num_image_tokens=8192, image_fmap_size=32,
                      attn_types=("full", "axial_row"), shift_tokens=True,
                      rotary_emb=True, share_input_output_emb=True)
    params = jax.jit(lambda k: dalle_mod.init_dalle(k, cfg))(jax.random.PRNGKey(0))
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=8, block_size=64))

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    text = eng._decode_fn.lower(described(eng.params), described(eng._state)).compile().as_text()
    return eng, text


def _op_of(line):
    m = re.search(r"= .*? ([a-z][a-z\-]*)\(", line)
    return m.group(1) if m else None


def test_decode_program_touches_the_pool_only_through_the_kernel(decode_program):
    """The engine's decode program compiled for the chip: both layers take
    the kernel, and no operation but the kernel has a pool array for an
    operand or a result — no gather, no relayout `copy` (XLA's scatter wants
    the pool in another layout: two copies of each pool array a step), and no
    staging of a pool through VMEM by XLA's memory-space assignment
    (`slice-start` / `copy-start`), which a cost estimate on the kernel
    brings on.  The kernel cases above compile WITHOUT donating the pool,
    where pinning it to HBM aborted the compiler."""
    eng, text = decode_program
    assert eng._paged_paths == {"kernel": 2, "fallback": 0}
    pool = eng._state["pool"]["layers"][0]["k"]
    shape = "f32[" + ",".join(map(str, pool.shape)) + "]"
    ops = {}
    for line in text[text.index("ENTRY "):].splitlines()[1:]:
        if shape in line:
            ops[_op_of(line)] = ops.get(_op_of(line), 0) + 1
    assert ops == {"parameter": 4, "custom-call": 2, "get-tuple-element": 4, "tuple": 1}, ops


def test_decode_program_reads_the_image_table_where_it_lies(decode_program):
    """The same program: the whole head (`f32[2048,24704]`, 202 MB) is no
    operand and no result of any instruction — jit does not even pass it in —
    there is no `transpose`, `copy` or `slice` of a table-sized array (the 67
    MB slice-and-transpose a step that the shared embedding's lookup cost),
    `top_k`'s sort is 8,192 wide, and the table laid out at engine build goes
    in as a parameter and comes out aliased to it."""
    eng, text = decode_program
    cfg = eng.cfg
    whole = f"f32[{cfg.dim},{cfg.total_tokens}]"
    assert [ln for ln in text.splitlines() if whole in ln and _op_of(ln) != "parameter"] == []
    table = (f"[{cfg.num_image_tokens},{cfg.dim}]", f"[{cfg.dim},{cfg.num_image_tokens}]")
    moved = [ln[:160] for ln in text.splitlines()
             if _op_of(ln) in ("transpose", "copy", "slice") and any(t in ln for t in table)]
    assert moved == [], moved
    sorts = [ln for ln in text.splitlines() if _op_of(ln) == "sort"]
    widths = [max(int(n) for dims in re.findall(r"\[([\d,]+)\]", ln.split(" sort(")[0])
                  for n in dims.split(",")) for ln in sorts]
    assert sorts and max(widths) == cfg.num_image_tokens, widths
    (param,) = re.findall(r"parameter\((\d+)\)[^\n]*op_name=\"state\[\\'head\\'\]\[\\'table\\'\]\"", text)
    assert re.search(r"\{\d+\}: \(%s, \{\}, may-alias\)" % param, text.splitlines()[0]), param


@pytest.fixture(scope="module")
def olmoh_decode_program(one_chip):
    """(engine, text of its decode program compiled for the chip) of the cell
    `serve_olmoh_s32` as it is: the configuration's file, 32 slots, bfloat16.
    Nothing runs and nothing of that size is allocated: the engine is built
    under `jax.eval_shape` (its weights, pool and state are shapes) and only
    its decode function and the shapes of its state are used."""
    import json
    from pathlib import Path

    from benchmark.harness import build
    from dalle_pytorch_tpu.core.pytree import cast_floating
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models import gated_layers
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    root = Path(__file__).resolve().parents[1]
    sizes = json.loads((root / "benchmark" / "configs" / "olmo_hybrid_7b_p1.json").read_text())
    traffic = json.loads((root / "benchmark" / "traffic" / "closed_batch_s32_c32.json").read_text())
    cfg = build.dalle_config(sizes, execution="sequential", scan_layers=False)
    params = jax.eval_shape(lambda k: cast_floating(dalle_mod.init_dalle(k, cfg), jnp.bfloat16),
                            jax.random.PRNGKey(0))
    held = {}

    def make(p):
        held["engine"] = GenerationEngine(p, cfg, engine_cfg=EngineConfig(
            num_slots=traffic["slots"], block_size=traffic["block_size"],
            filter_thres=traffic["filter_thres"]))
        return held["engine"]._state

    state = jax.eval_shape(make, params)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    # the layer asks jax.default_backend(), which is the CPU here: its TPU answer
    steered = gated_layers._use_delta_kernel
    gated_layers._use_delta_kernel = lambda cfg: True
    try:
        text = held["engine"]._decode_fn.lower(described(params), described(state)).compile().as_text()
    finally:
        gated_layers._use_delta_kernel = steered
    return held["engine"], state, text


def test_olmoh_decode_program_holds_one_paged_kernel_and_three_state_kernels(olmoh_decode_program):
    """`serve_decode_step` of `serve_olmoh_s32` compiled for the chip: the one
    `full` layer takes `paged_decode_attn` on the bfloat16 pool (no gather-path
    layer), the three `gated_delta` layers one `gdn_step` each, and the
    counters say 3 state layers, 1 kernel layer."""
    eng, _, text = olmoh_decode_program
    assert eng._paged_paths == {"kernel": 1, "fallback": 0, "state": 3, "state_kernel": 3}
    # every state layer took the kernel: a silent fall to the XLA form would read fewer
    info = eng.recurrent_state_info()
    assert info["gdn_state_layers"] == info["gdn_step_kernel_layers"] == 3
    assert eng.recurrent_state_info()["state_bytes"] == 32 * 3 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    entry = text[text.index("ENTRY "):]
    calls = [ln for ln in entry.splitlines() if _op_of(ln) == "custom-call" and "tpu_custom_call" in ln]
    named = lambda name: [ln for ln in calls if f'kernel_name = \\"{name}\\"' in ln or f"/{name}" in ln]
    assert len(named("paged_decode_attn")) == 1 and len(named("gdn_step")) == 3, len(calls)


def test_olmoh_decode_program_moves_no_state_pool_or_table_sized_array(olmoh_decode_program):
    """The state goes in as a parameter, through ONE operation (its layer's
    kernel, which reads it once and writes it once) and out aliased to the
    parameter: no XLA pass over it, no `copy` or `transpose` of a state-, pool-
    or table-sized array, no staging through VMEM (`slice-start`,
    `copy-start`).  The XLA form of the rule, which this replaced, showed two
    reads and a write a layer here."""
    _, state, text = olmoh_decode_program
    entry = text[text.index("ENTRY "):]
    layers = state["pool"]["layers"]

    def shape_of(a):
        return {"float32": "f32", "bfloat16": "bf16"}[a.dtype.name] + "[" + ",".join(map(str, a.shape)) + "]"

    ops = {}
    for line in entry.splitlines()[1:]:
        if shape_of(layers[0]["state"]) in line:
            ops[_op_of(line)] = ops.get(_op_of(line), 0) + 1
    assert ops == {"parameter": 3, "custom-call": 3, "get-tuple-element": 3, "tuple": 1}, ops
    big = [shape_of(layers[0]["state"]), shape_of(layers[3]["k"]), shape_of(state["head"]["table"]),
           "bf16[8192,3840]", "bf16[3840,8192]"]
    moved = [ln[:160] for ln in entry.splitlines()
             if _op_of(ln) in ("copy", "transpose", "copy-start", "slice-start") and any(b in ln for b in big)]
    assert moved == [], moved
    head = entry.splitlines()[0] if "may-alias" in entry.splitlines()[0] else text.splitlines()[0]
    for i in range(3):
        (param,) = re.findall(
            r"parameter\((\d+)\)[^\n]*op_name=\"state\[\\'pool\\'\]\[\\'layers\\'\]\[%d\]\[\\'state\\'\]\"" % i, text)
        assert re.search(r"\{\d+\}: \(%s, \{\}, may-alias\)" % param, head), (i, param)


@pytest.mark.parametrize("lookup", ["flops", "hbm", "ici"])
def test_chip_table_knows_v5e_and_refuses_unknown(lookup):
    """Every consumer of the one hardware table (MFU peak, HBM capacity, ICI
    roofline) finds the kind a v5e reports and raises on a kind it does not
    know; on the CPU it gets None — never a default."""
    from dalle_pytorch_tpu.observability import comms, memory
    from dalle_pytorch_tpu.training import profiling

    class Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

        def memory_stats(self):
            return None

    v5e = chips.chip_spec(Dev("TPU v5 lite"))
    assert (v5e.bf16_flops, v5e.hbm_bytes, v5e.ici_bytes_per_s) == (197e12, 16e9, 200e9)
    with pytest.raises(ValueError, match="TPU v99"):
        chips.chip_spec(Dev("TPU v99"))
    assert chips.chip_spec() is None  # tests run on the CPU
    if lookup == "flops":
        assert profiling.chip_peak_flops() is None
        assert profiling.mfu(1e12, 1.0) is None
    elif lookup == "hbm":
        assert memory.device_hbm_capacity(Dev("TPU v5 lite")) == 16e9
        with pytest.raises(ValueError):
            memory.device_hbm_capacity(Dev("TPU v99"))
        assert memory.device_hbm_capacity() is None
    else:
        assert comms.comms_roofline(1e9, 1e12) is None
        roof = comms.comms_roofline(1e10, 1e12, peak_flops=v5e.bf16_flops,
                                    ici_bytes_per_s=v5e.ici_bytes_per_s)
        assert roof["bound"] == "comms"
