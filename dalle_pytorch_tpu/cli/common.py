"""Shared CLI helpers."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.
    Called once at the top of every entry point that jits (the CLIs,
    benchmark/run.py, chip_smoke.py's children, tools/), BEFORE the first compile.

    Where `JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself and this
    sets nothing.  Otherwise the cache lives at ONE fixed path inside the
    checkout (`<repo>/.jax_cache`, gitignored) — the path is part of the
    cache key, so a temporary name, a pid or a time would never hit.  Tests
    keep the cache off (`JAX_ENABLE_COMPILATION_CACHE=false`,
    tests/conftest.py)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def load_dalle_bundle(path, allow_legacy_pickle: bool = False,
                      vqgan_config_path: Optional[str] = None):
    """Load a trained DALL-E checkpoint of any supported flavor — self-format
    npz, orbax sharded directory, or torch-reference dalle.pt — returning
    (dalle_cfg, params, vae_cfg, vae_params).  Shared by cli/generate.py and
    cli/serve.py so the batch CLI and the long-lived service consume the
    exact same loading/migration path."""
    from dalle_pytorch_tpu.models import vae_registry
    from dalle_pytorch_tpu.models.dalle import DALLEConfig
    from dalle_pytorch_tpu.models.torch_port import (
        is_torch_checkpoint,
        load_reference_dalle_checkpoint,
    )
    from dalle_pytorch_tpu.training.checkpoint import (
        is_sharded_checkpoint,
        load_checkpoint,
    )
    from dalle_pytorch_tpu.version import __version__

    path = Path(path)
    assert path.exists(), f"trained DALL-E {path} does not exist"

    if is_sharded_checkpoint(str(path)):
        # orbax sharded training checkpoint (train_dalle --sharded_checkpoint):
        # template-free restore of the weights only — inference must never
        # materialize the optimizer moments (≈2× params of host memory)
        from dalle_pytorch_tpu.training.checkpoint import load_sharded

        restored, meta = load_sharded(str(path), only=("weights",))
        vae_trees, vae_side_meta = load_checkpoint(
            str(path / "vae.npz"), allow_legacy_pickle=allow_legacy_pickle
        )
        if meta.get("version") != __version__:
            print(f"note: checkpoint version {meta.get('version')} != library {__version__}")
        dalle_cfg = DALLEConfig.from_dict(meta["hparams"])
        vae_cfg = vae_registry.config_from_meta(
            vae_side_meta.get("vae_class_name", "DiscreteVAE"), vae_side_meta["vae_params"]
        )
        from dalle_pytorch_tpu.models import dalle as dalle_mod

        # template-free restore rebuilds the file's own (possibly
        # pre-round-5) structure — migrate like the npz branch does
        params = dalle_mod.migrate_param_layout(restored["weights"], dalle_cfg)
        vae_params = vae_trees["vae_weights"]
    elif is_torch_checkpoint(str(path)):
        # a dalle.pt trained with the torch reference — convert on load
        taming_config = None
        if vqgan_config_path:  # --taming is implied by the config path
            from dalle_pytorch_tpu.models.pretrained import parse_taming_yaml

            taming_config = parse_taming_yaml(vqgan_config_path)
        ref = load_reference_dalle_checkpoint(str(path), taming_config=taming_config)
        dalle_cfg, params = ref["config"], ref["params"]
        vae_cfg, vae_params = ref["vae_config"], ref["vae_params"]
        print(f"loaded reference-format checkpoint (version {ref.get('version')})")
    else:
        trees, meta = load_checkpoint(
            str(path), allow_legacy_pickle=allow_legacy_pickle
        )
        if meta.get("version") != __version__:
            print(f"note: checkpoint version {meta.get('version')} != library {__version__}")

        dalle_cfg = DALLEConfig.from_dict(meta["hparams"])
        # reference generate.py:94-101: reconstitute whichever VAE class the
        # checkpoint was trained with
        vae_cfg = vae_registry.config_from_meta(
            meta.get("vae_class_name", "DiscreteVAE"), meta["vae_params"]
        )
        from dalle_pytorch_tpu.models import dalle as dalle_mod

        params = dalle_mod.migrate_param_layout(trees["weights"], dalle_cfg)
        vae_params = trees["vae_weights"]
    # checkpoints load as HOST numpy arrays: place the weights on the device
    # ONCE here, or every jitted call of the sampler / the engine's decode
    # step uploads them again (2.3 GB per step at dim 2048 — invisible on the
    # CPU, where host and device memory are the same)
    return dalle_cfg, jax.device_put(params), vae_cfg, jax.device_put(vae_params)


def warn_vocab_mismatch(num_text_tokens: int, tokenizer, is_root: bool = True) -> None:
    """Out-of-vocab caption ids are clamped by the model (models/dalle.py);
    surface the misconfiguration at every entry point that pairs a tokenizer
    with a model."""
    vocab = getattr(tokenizer, "vocab_size", None)
    if is_root and vocab is not None and num_text_tokens < vocab:
        print(
            f"WARNING: model num_text_tokens {num_text_tokens} < tokenizer vocab "
            f"{vocab}; out-of-range caption ids will be clamped onto the last "
            f"vocab id — check --num_text_tokens / tokenizer choice"
        )
