"""End-to-end CLI tests on a synthetic colored-shapes dataset (the JAX-native
version of the reference's rainbow_dalle.ipynb fixture, SURVEY.md §4)."""
import numpy as np
import pytest
from PIL import Image, ImageDraw

from dalle_pytorch_tpu.cli import generate as generate_cli
from dalle_pytorch_tpu.cli import train_dalle as train_dalle_cli
from dalle_pytorch_tpu.cli import train_vae as train_vae_cli

COLORS = {"red": (220, 40, 40), "green": (40, 200, 60), "blue": (50, 80, 220)}
SHAPES = ("circle", "square")


def make_rainbow_dataset(folder, n=24, size=16):
    folder.mkdir(parents=True, exist_ok=True)
    rng = np.random.RandomState(0)
    for i in range(n):
        color = list(COLORS)[i % len(COLORS)]
        shape = SHAPES[(i // len(COLORS)) % len(SHAPES)]
        img = Image.new("RGB", (size, size), (250, 250, 250))
        d = ImageDraw.Draw(img)
        x0, y0 = rng.randint(1, 6), rng.randint(1, 6)
        x1, y1 = x0 + rng.randint(6, 9), y0 + rng.randint(6, 9)
        if shape == "circle":
            d.ellipse([x0, y0, x1, y1], fill=COLORS[color])
        else:
            d.rectangle([x0, y0, x1, y1], fill=COLORS[color])
        img.save(folder / f"img{i:03d}.png")
        (folder / f"img{i:03d}.txt").write_text(f"a {color} {shape}")


@pytest.fixture(scope="module")
def workspace(tmp_path_factory):
    ws = tmp_path_factory.mktemp("rainbow")
    make_rainbow_dataset(ws / "data")
    return ws


@pytest.fixture(scope="module")
def trained_vae(workspace):
    params, cfg = train_vae_cli.main([
        "--image_folder", str(workspace / "data"),
        "--image_size", "16",
        "--num_tokens", "32",
        "--num_layers", "2",
        "--emb_dim", "16",
        "--hidden_dim", "16",
        "--num_resnet_blocks", "0",
        "--epochs", "1",
        "--batch_size", "8",
        "--vae_output_file_name", str(workspace / "vae"),
        "--save_every_n_steps", "0",
    ])
    assert (workspace / "vae.pt").exists()
    return workspace / "vae.pt"


@pytest.fixture(scope="module")
def trained_dalle(workspace, trained_vae):
    state, cfg = train_dalle_cli.main([
        "--vae_path", str(trained_vae),
        "--image_text_folder", str(workspace / "data"),
        "--dim", "32",
        "--depth", "1",
        "--heads", "2",
        "--dim_head", "8",
        "--text_seq_len", "16",
        "--num_text_tokens", "64",
        "--epochs", "1",
        "--batch_size", "8",
        "--save_every_n_steps", "0",
        "--sample_every_n_steps", "0",
        "--dalle_output_file_name", str(workspace / "dalle"),
        "--truncate_captions",
        "--rotary_emb",
        "--shift_tokens",
    ])
    assert (workspace / "dalle.pt").exists()
    return workspace / "dalle.pt"


def test_out_of_vocab_ids_are_clamped_not_nan(trained_dalle):
    """Regression guard: feeding real-tokenizer ids (vocab 49408) into a
    num_text_tokens=64 model once hit jnp.take's out-of-bounds NaN fill;
    the model clamps ids into vocab instead."""
    import jax

    from dalle_pytorch_tpu.data.tokenizer import tokenizer as tok
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models.dalle import DALLEConfig
    from dalle_pytorch_tpu.training.checkpoint import load_checkpoint

    trees, meta = load_checkpoint(str(trained_dalle))
    hparams = dict(meta["hparams"])
    for k in ("attn_types", "shared_attn_ids", "shared_ff_ids"):
        if hparams.get(k) is not None:
            hparams[k] = tuple(hparams[k])
    cfg = DALLEConfig(**hparams)
    text = jax.numpy.asarray(tok.tokenize("a red circle", cfg.text_seq_len, truncate_text=True))
    codes = jax.numpy.zeros((1, cfg.image_seq_len), int)
    loss = dalle_mod.forward(trees["weights"], cfg, text, codes, return_loss=True)
    assert np.isfinite(float(loss)), "out-of-vocab ids produced non-finite loss"


def test_train_vae_cli(trained_vae):
    from dalle_pytorch_tpu.training.checkpoint import load_checkpoint

    trees, meta = load_checkpoint(str(trained_vae))
    assert "weights" in trees
    assert meta["hparams"]["num_tokens"] == 32
    assert "version" in meta


def test_train_dalle_cli_and_checkpoint_payload(trained_dalle):
    from dalle_pytorch_tpu.training.checkpoint import load_checkpoint

    trees, meta = load_checkpoint(str(trained_dalle))
    # reference checkpoint payload parity (train_dalle.py:535-582)
    for k in ("hparams", "vae_params", "epoch", "version", "vae_class_name", "scheduler_state"):
        assert k in meta, k
    assert "weights" in trees and "opt_state" in trees and "vae_weights" in trees
    assert meta["vae_class_name"] == "DiscreteVAE"


def test_train_dalle_resume(workspace, trained_dalle):
    import json

    from dalle_pytorch_tpu.training.checkpoint import load_checkpoint

    _, meta0 = load_checkpoint(str(trained_dalle))
    # 24 samples / batch 8 = 3 steps in the first 1-epoch run
    assert meta0["global_step"] == 3

    state, cfg = train_dalle_cli.main([
        "--dalle_path", str(trained_dalle),
        "--image_text_folder", str(workspace / "data"),
        "--epochs", "2",  # resumes from epoch 1
        "--batch_size", "8",
        "--save_every_n_steps", "0",
        "--sample_every_n_steps", "0",
        "--log_every_n_steps", "1",
        "--dalle_output_file_name", str(workspace / "dalle_resumed"),
        "--truncate_captions",
    ])
    assert (workspace / "dalle_resumed.pt").exists()
    # the step counter continues across resume (3 restored + 3 new), keeping
    # save/sample cadences and rotation continuous
    _, meta1 = load_checkpoint(str(workspace / "dalle_resumed.pt"))
    assert meta1["global_step"] == 6
    assert meta1["epoch"] == 2
    # throughput: the process's FIRST window spans jit compile, so its rate
    # is omitted (round 2 logged a bogus 0.0); later windows report real
    # positive rates
    records = [
        json.loads(line) for line in open(workspace / "dalle_resumed.metrics.jsonl")
        if "loss" in line
    ]
    assert records and "sample_per_sec" not in records[0]
    rates = [r["sample_per_sec"] for r in records[1:] if "sample_per_sec" in r]
    assert rates and all(r > 0 for r in rates)


@pytest.mark.slow  # tier-1 budget: the pieces stay fast via
#                    test_resharding's orbax validate/roundtrip tests and the
#                    npz train-resume CLI legs; this is the three-subprocess
#                    orbax end-to-end stitch
def test_sharded_checkpoint_train_resume_generate(workspace, trained_vae):
    """--sharded_checkpoint end to end: orbax directory save (no host
    gather), resume from the directory (weights restored after distribution),
    and generate.py inference straight off the directory."""
    pytest.importorskip("orbax.checkpoint")
    from dalle_pytorch_tpu.training.checkpoint import is_sharded_checkpoint

    common = [
        "--image_text_folder", str(workspace / "data"),
        "--dim", "32", "--depth", "1", "--heads", "2", "--dim_head", "8",
        "--text_seq_len", "16", "--num_text_tokens", "64",
        "--batch_size", "8", "--truncate_captions",
        "--save_every_n_steps", "0", "--sample_every_n_steps", "0",
        "--sharded_checkpoint",
    ]
    out = workspace / "dalle_sharded"
    state, cfg = train_dalle_cli.main([
        "--vae_path", str(trained_vae), "--epochs", "1",
        "--dalle_output_file_name", str(out), *common,
    ])
    ckpt = workspace / "dalle_sharded.pt"
    assert is_sharded_checkpoint(str(ckpt))
    assert (ckpt / "vae.npz").exists()

    out2 = workspace / "dalle_sharded_resumed"
    state2, cfg2 = train_dalle_cli.main([
        "--dalle_path", str(ckpt), "--epochs", "2",
        "--dalle_output_file_name", str(out2), *common,
    ])
    import json

    meta = json.loads((workspace / "dalle_sharded_resumed.pt" / "meta.json").read_text())
    assert meta["epoch"] == 2
    assert meta["global_step"] == 6  # 3 restored + 3 new

    paths = generate_cli.main([
        "--dalle_path", str(workspace / "dalle_sharded_resumed.pt"),
        "--text", "a red circle",
        "--num_images", "1", "--batch_size", "1",
        "--outputs_dir", str(workspace / "outputs_sharded"),
    ])
    assert len(paths) == 1


def test_rotation_glob_strips_step_suffix():
    """Regression: the rotation glob was built from the step file's own stem
    ('out_step100' -> 'out_step100_step*.npz'), which matched nothing, so
    --keep_n_checkpoints silently never deleted anything."""
    from dalle_pytorch_tpu.cli.train_dalle import _rotation_glob

    assert _rotation_glob("out_step100.npz") == "out_step*.npz"
    assert _rotation_glob("/a/b/my_run_step5.npz") == "my_run_step*.npz"


def test_keep_n_checkpoints_rotates(workspace, trained_vae):
    out = workspace / "dalle_rot"
    train_dalle_cli.main([
        "--vae_path", str(trained_vae),
        "--image_text_folder", str(workspace / "data"),
        "--dim", "32", "--depth", "1", "--heads", "2", "--dim_head", "8",
        "--text_seq_len", "16", "--num_text_tokens", "64",
        "--epochs", "1", "--batch_size", "8", "--truncate_captions",
        "--save_every_n_steps", "1", "--keep_n_checkpoints", "1",
        "--sample_every_n_steps", "0",
        "--dalle_output_file_name", str(out),
    ])
    # 3 steps -> saves at step 1 and 2; keep_n=1 leaves only the newest
    left = sorted(p.name for p in workspace.glob("dalle_rot_step*.npz"))
    assert left == ["dalle_rot_step2.npz"]


def test_loaded_inference_weights_are_on_the_device(trained_dalle):
    """generate.py and the serve CLI load weights through load_dalle_bundle;
    they must come back as device arrays, placed once.  Host numpy weights
    are re-uploaded by EVERY jitted call — the engine's decode step moved
    2.3 GB per token on the chip at dim 2048 (PR 21) and the CPU cannot see
    it."""
    import jax

    from dalle_pytorch_tpu.cli.common import load_dalle_bundle

    _, params, _, vae_params = load_dalle_bundle(trained_dalle)
    leaves = jax.tree_util.tree_leaves((params, vae_params))
    assert leaves and all(isinstance(x, jax.Array) for x in leaves)


def test_generate_cli(workspace, trained_dalle):
    paths = generate_cli.main([
        "--dalle_path", str(trained_dalle),
        "--text", "a red circle|a blue square",
        "--num_images", "2",
        "--batch_size", "2",
        "--outputs_dir", str(workspace / "outputs"),
    ])
    assert len(paths) == 4
    for p in paths:
        img = Image.open(p)
        assert img.size == (16, 16)


def test_generate_cli_engine(workspace, trained_dalle):
    """--engine routes the same checkpoint through the continuous-batching
    serving engine (ISSUE 8 satellite): per-image requests, same output
    surface (PNGs per prompt dir), VAE decode included."""
    paths = generate_cli.main([
        "--dalle_path", str(trained_dalle),
        "--text", "a red circle",
        "--num_images", "2",
        "--batch_size", "2",
        "--engine",
        "--engine_slots", "2",
        "--engine_block_size", "8",
        "--outputs_dir", str(workspace / "outputs_engine"),
    ])
    assert len(paths) == 2
    for p in paths:
        img = Image.open(p)
        assert img.size == (16, 16)


def test_generate_cli_gentxt(workspace, trained_dalle):
    paths = generate_cli.main([
        "--dalle_path", str(trained_dalle),
        "--text", "a red",
        "--gentxt",
        "--num_images", "1",
        "--batch_size", "1",
        "--outputs_dir", str(workspace / "outputs_gentxt"),
    ])
    assert len(paths) == 1


def test_train_clip_cli(workspace):
    from dalle_pytorch_tpu.cli import train_clip as train_clip_cli

    state, cfg = train_clip_cli.main([
        "--image_text_folder", str(workspace / "data"),
        "--dim_text", "32", "--dim_image", "32", "--dim_latent", "16",
        "--text_enc_depth", "1", "--text_seq_len", "16", "--text_heads", "2",
        "--visual_enc_depth", "1", "--visual_heads", "2",
        "--visual_image_size", "16", "--visual_patch_size", "8",
        "--epochs", "1", "--batch_size", "8",
        "--clip_output_file_name", str(workspace / "clip"),
        "--truncate_captions", "--save_every_n_steps", "0",
    ])
    assert (workspace / "clip.pt").exists()


def test_train_dalle_taming_and_generate(workspace):
    """Reference train_dalle.py:246-293 / generate.py:94-99: train on top of a
    pretrained taming VQGAN (--taming) and generate from the resulting
    checkpoint, whose vae_class_name dispatches the right decoder."""
    import torch
    import yaml
    from taming_fixture import make_taming_state_dict

    from dalle_pytorch_tpu.models.vqgan import VQGANConfig
    from dalle_pytorch_tpu.training.checkpoint import load_checkpoint

    # consistent geometry: 1 halving (ch_mult len 2) == f-factor 16/8
    cfg = VQGANConfig(
        ch=8, ch_mult=(1, 2), num_res_blocks=1, attn_resolutions=(8,),
        resolution=16, z_channels=8, n_embed=32, embed_dim=8,
    )
    ckpt_path = workspace / "vqgan_tiny.ckpt"
    torch.save({"state_dict": make_taming_state_dict(cfg)}, str(ckpt_path))
    config_path = workspace / "vqgan_tiny.yml"
    config_path.write_text(yaml.safe_dump({
        "model": {"params": {
            "n_embed": 32, "embed_dim": 8,
            "ddconfig": {
                "ch": 8, "ch_mult": [1, 2], "num_res_blocks": 1,
                "attn_resolutions": [8], "in_channels": 3, "out_ch": 3,
                "resolution": 16, "z_channels": 8,
            },
        }},
    }))

    state, dcfg = train_dalle_cli.main([
        "--taming",
        "--vqgan_model_path", str(ckpt_path),
        "--vqgan_config_path", str(config_path),
        "--image_text_folder", str(workspace / "data"),
        "--dim", "32",
        "--depth", "1",
        "--heads", "2",
        "--dim_head", "8",
        "--text_seq_len", "16",
        "--num_text_tokens", "64",
        "--epochs", "1",
        "--batch_size", "8",
        "--save_every_n_steps", "0",
        "--sample_every_n_steps", "0",
        "--dalle_output_file_name", str(workspace / "dalle_taming"),
        "--truncate_captions",
    ])
    assert dcfg.num_image_tokens == 32 and dcfg.image_fmap_size == 8

    ckpt = workspace / "dalle_taming.pt"
    _, meta = load_checkpoint(str(ckpt))
    assert meta["vae_class_name"] == "VQGanVAE"

    paths = generate_cli.main([
        "--dalle_path", str(ckpt),
        "--text", "a red circle",
        "--num_images", "1",
        "--batch_size", "1",
        "--outputs_dir", str(workspace / "outputs_taming"),
    ])
    assert len(paths) == 1
    assert Image.open(paths[0]).size == (16, 16)


def test_train_vae_image_and_histogram_logging(workspace, trained_vae):
    """Observability parity (reference train_vae.py:252-271): recon grids,
    hard recons, and the codebook-usage histogram land at the log cadence."""
    import json

    img_dir = workspace / "vae.images"
    for name in ("original_images", "reconstructions", "hard_reconstructions"):
        p = img_dir / f"step0_{name}.png"
        assert p.exists(), p
        assert Image.open(p).size[0] > 16  # a grid, not a single tile
    records = [json.loads(l) for l in open(workspace / "vae.metrics.jsonl")]
    hists = [r["codebook_indices_hist"] for r in records if "codebook_indices_hist" in r]
    assert hists and sum(hists[0]["counts"]) > 0


def test_train_dalle_sample_image_logging(workspace, trained_vae):
    """Generated-sample logging at the sampling cadence (reference
    train_dalle.py:639-649)."""
    import json

    train_dalle_cli.main([
        "--vae_path", str(trained_vae),
        "--image_text_folder", str(workspace / "data"),
        "--dim", "32", "--depth", "1", "--heads", "2", "--dim_head", "8",
        "--text_seq_len", "16", "--num_text_tokens", "64",
        "--epochs", "1", "--batch_size", "8",
        "--save_every_n_steps", "0",
        "--sample_every_n_steps", "2",
        "--dalle_output_file_name", str(workspace / "dalle_sampled"),
        "--truncate_captions",
    ])
    img_dir = workspace / "dalle_sampled.images"
    assert (img_dir / "step2_image.png").exists()
    records = [json.loads(l) for l in open(workspace / "dalle_sampled.metrics.jsonl")]
    caps = [r for r in records if "image_caption" in r]
    assert caps and isinstance(caps[0]["image_caption"], str)


def test_train_dalle_artifact_records(workspace, trained_dalle):
    """Model-artifact records at epoch end + final (reference
    train_dalle.py:584-587,667-675; JSONL fallback when wandb is absent)."""
    import json

    records = [json.loads(l) for l in open(workspace / "dalle.metrics.jsonl")]
    names = [r["artifact"]["name"] for r in records if "artifact" in r]
    assert "trained-dalle" in names and "trained-dalle-final" in names
