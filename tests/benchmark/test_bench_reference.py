"""The plain float32 reference against the program, at a tiny size on the CPU,
and its masks, rotary table and token shift against the program's own."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import build, correct, manifest, work  # noqa: E402
from benchmark.reference import dalle_reference as ref  # noqa: E402

MAN = manifest.load(ROOT / "benchmark" / "rehearsal" / "manifest.json")
SIZES = manifest.config_sizes(MAN, "tiny")
PATTERNS = ["full", "axial_row", "axial_col", "conv_like"]


@pytest.fixture(scope="module")
def model():
    cfg = build.dalle_config(SIZES)
    return cfg, build.make_weights(cfg, 2**31 + 5, jnp.float32)


def _sequence(cfg, n_codes, pad_tail=3):
    rng = np.random.default_rng(1)
    text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,))
    text[cfg.text_seq_len - pad_tail:] = 0
    return text.astype(np.int32), rng.integers(0, cfg.num_image_tokens, (n_codes,)).astype(np.int32)


@pytest.mark.parametrize("n_codes", [16, 5, 0])
def test_logits_match_models_dalle_forward(model, n_codes):
    from dalle_pytorch_tpu.models import dalle as dalle_mod

    cfg, params = model
    text, codes = _sequence(cfg, n_codes)
    want = np.asarray(ref.forward_logits(params, SIZES, text, codes))
    got = np.asarray(dalle_mod.forward(params, cfg, jnp.asarray(text)[None],
                                       jnp.asarray(codes)[None] if n_codes else None)[0])
    ok = np.isfinite(want)
    assert got.shape == want.shape
    np.testing.assert_allclose(got[ok], want[ok], atol=2e-5)
    assert (got[~ok] < -1e30).all(), "the program forbids what the reference forbids"


def test_loss_matches_models_dalle_forward(model):
    from dalle_pytorch_tpu.models import dalle as dalle_mod

    cfg, params = model
    text, codes = _sequence(cfg, cfg.image_seq_len)
    got = float(dalle_mod.forward(params, cfg, jnp.asarray(text)[None], jnp.asarray(codes)[None],
                                  return_loss=True))
    assert got == pytest.approx(float(ref.loss(params, SIZES, text, codes)), rel=1e-5)


def _served(model, cond_scale, n_requests=3, slots=4):
    """Requests through the engine itself (prefill, ingest, paged decode,
    sampler), as `kinds/closed_loop.py` drives it."""
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    cfg, params = model
    vae_cfg = build.vae_config(SIZES)
    vae_params = build.make_vae(vae_cfg, 11)
    engine = GenerationEngine(params, cfg, vae_params=vae_params, vae_cfg=vae_cfg,
                              engine_cfg=EngineConfig(num_slots=slots, block_size=8,
                                                      filter_thres=0.9))
    rng = np.random.default_rng(4)
    reqs = [engine.submit(rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,),
                                       dtype=np.int64).astype(np.int32),
                          key=build.raw_key(9, i), temperature=1.0, cond_scale=cond_scale)
            for i in range(n_requests if cond_scale == 1.0 else 2)]
    done = []
    for _ in range(200):
        done += engine.poll()
        if len(done) == len(reqs):
            break
    assert len(done) == len(reqs)
    return vae_params, vae_cfg, [{"text": r.text, "codes": r.codes, "image": r.images}
                                 for r in done]


@pytest.mark.parametrize("cond_scale", [1.0, 3.0])
def test_prefill_and_paged_decode_deliver_what_the_reference_permits(model, cond_scale):
    cfg, params = model
    vae_params, vae_cfg, delivered = _served(model, cond_scale)
    ok, detail = correct.serve_replay_agrees(params, SIZES, vae_params, vae_cfg, 0.9,
                                             cond_scale, delivered)
    assert ok and detail["codes"] == len(delivered) * cfg.image_seq_len
    assert detail["outside_top_k_share"] == 0 and detail["pixels_rms_err"] < 1e-5


@pytest.mark.parametrize("fault", ["other_scale", "other_text", "shifted_codes", "dimmed_pixels"])
def test_replay_refuses_what_the_engine_did_not_serve(model, fault):
    cfg, params = model
    vae_params, vae_cfg, delivered = _served(model, 3.0)
    scale = 3.0
    if fault == "other_scale":     # guidance applied at another scale than asked
        scale = -1.0
    elif fault == "other_text":    # a lane that read another request's K/V
        for d in delivered:
            d["text"] = np.roll(d["text"], 3)
    elif fault == "shifted_codes":  # codes written one position off
        for d in delivered:
            d["codes"] = np.roll(d["codes"], 1)
    else:                          # pixels that are not the VAE's decode
        delivered[0]["image"] = 0.9 * delivered[0]["image"]
    ok, _ = correct.serve_replay_agrees(params, SIZES, vae_params, vae_cfg, 0.9, scale, delivered)
    assert not ok


def test_train_forward_check_passes_in_float32_and_fails_on_a_wrong_model(model):
    cfg, params = model
    ok, detail = correct.train_forward_agrees(params, cfg, SIZES, jnp.float32, seed=3)
    assert ok and detail["logits_rms_err"] < 1e-5 and detail["loss_rel_err"] < 1e-5
    wrong = dict(SIZES, attn_types=["full"])  # the reference of another model
    ok, detail = correct.train_forward_agrees(params, cfg, wrong, jnp.float32, seed=3)
    assert not ok


@pytest.mark.parametrize("rel_noise,passes", [(0.004, True), (0.06, False)])
def test_tolerance_takes_bf16_rounding_and_refuses_an_8bit_floats(rel_noise, passes):
    rng = np.random.default_rng(0)
    want = rng.normal(size=(32, 200)).astype(np.float32)
    want[:, 150:] = -np.inf
    got = np.where(np.isfinite(want), want * (1 + rel_noise * rng.normal(size=want.shape)), 0.0)
    ok, _ = correct._verdict(*correct.logits_error(jnp.asarray(got), jnp.asarray(want)), {})
    assert ok == passes


def test_one_wrong_row_fails_even_if_the_mean_is_small():
    want = np.random.default_rng(0).normal(size=(1000, 64)).astype(np.float32)
    got = want.copy()
    got[17] = -got[17]
    ok, detail = correct._verdict(*correct.logits_error(jnp.asarray(got), jnp.asarray(want)), {})
    assert not ok and detail["logits_worst_row_err"] > 3 * correct.TOLERANCE


@pytest.mark.parametrize("pattern", PATTERNS)
def test_pattern_mask_is_the_programs_pattern_and_causal(pattern):
    from dalle_pytorch_tpu.models.transformer import _pattern_for

    cfg = build.dalle_config(SIZES)
    n = cfg.total_seq_len
    theirs = _pattern_for(cfg.transformer_config(), pattern)
    causal = np.tril(np.ones((n, n), bool))
    want = causal if theirs is None else (np.asarray(theirs, bool)[:n, :n] & causal)
    np.testing.assert_array_equal(ref.pattern_mask(SIZES, pattern, n), want)


def test_rotary_table_is_the_programs():
    from dalle_pytorch_tpu.models.transformer import transformer_rotary

    cfg = build.dalle_config(SIZES)
    theirs = np.asarray(transformer_rotary(cfg.transformer_config()))
    mine = ref.rotary_angles(SIZES, cfg.total_seq_len)
    np.testing.assert_allclose(mine[:, :theirs.shape[1]], theirs[:cfg.total_seq_len], atol=1e-3)
    assert not mine[:, theirs.shape[1]:].any()


def test_token_shift_is_the_programs():
    from dalle_pytorch_tpu.ops.shift import token_shift

    cfg = build.dalle_config(SIZES)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(cfg.total_seq_len, cfg.dim)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(ref._token_shift(SIZES, x)),
                                  np.asarray(token_shift(x[None], cfg.total_seq_len,
                                                         cfg.image_fmap_size)[0]))


def test_reference_refuses_what_it_does_not_cover():
    with pytest.raises(ValueError):
        ref.forward_logits({}, dict(SIZES, sandwich_norm=True), np.zeros(8), np.zeros(0))


def test_required_work_counts():
    from dalle_pytorch_tpu.training.profiling import dalle_step_flops, matmul_param_count

    cfg = build.dalle_config(SIZES)
    params = jax.eval_shape(lambda k: __import__("dalle_pytorch_tpu.models.dalle", fromlist=["x"])
                            .init_dalle(k, cfg), jax.random.PRNGKey(0))
    assert work.matmul_params(SIZES) == matmul_param_count(params)
    assert work.train_step_flops(SIZES, 4) == pytest.approx(
        dalle_step_flops(cfg, 4, work.matmul_params(SIZES), granularity="element"), rel=1e-6)
    # decode bytes: weights once, plus what each lane's patterns let it see
    base = work.decode_step_bytes(SIZES, [], 4, 4)
    # of the shared table, the rows a decode step can emit: its lookup and its head read the image half
    text_and_pad_rows = cfg.num_text_tokens + cfg.text_seq_len
    assert work.vocabulary(SIZES) == text_and_pad_rows + cfg.num_image_tokens
    assert base == (work.matmul_params(SIZES) - cfg.dim * text_and_pad_rows) * 4
    n_pre = cfg.text_seq_len + 1
    one = work.decode_step_bytes(SIZES, [n_pre], 4, 4) - base
    # the first image position sees all text and itself, in every layer's pattern
    assert one == cfg.depth * (n_pre + 1) * 2 * cfg.heads * cfg.dim_head * 4
    later = work.decode_step_bytes(SIZES, [n_pre + 9], 4, 4) - base
    full_rows = (n_pre + 10) * cfg.depth
    assert one < later < full_rows * 2 * cfg.heads * cfg.dim_head * 4, "sparse layers read less"


def test_real_sizes_are_the_sources_and_d8_cuts_only_depth():
    man = manifest.load()
    d8 = manifest.config_sizes(man, "dalle_2048_d8")
    d24 = manifest.config_sizes(man, "dalle_2048_d24")
    assert work.seq_len(d8) == work.seq_len(d24) == 1152
    differ = {k for k in d24 if not isinstance(d24[k], (dict, list, str)) and d8[k] != d24[k]}
    assert differ == {"depth"} == set(d8["reduced"]) and d8["depth"] == 8
    assert round(work.matmul_params(d8) / 1e6) == 587
    assert round(work.matmul_params(d24) / 1e6) == 1661
