"""From a configuration file to the program's objects: the DALLEConfig, the
weights (made on the device from the seed, in one jitted call, in the type
they are used in), the untrained seeded VAE, and the seeded inputs."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def dtype(name: str):
    return _DTYPES[name]


def dalle_config(sizes: dict, **overrides):
    """The program's DALLEConfig from the file's keys of the same name."""
    from dalle_pytorch_tpu.models.dalle import DALLEConfig

    names = {f.name for f in dataclasses.fields(DALLEConfig)}
    kw = {k: v for k, v in sizes.items() if k in names}
    kw.update(overrides)
    kw["attn_types"] = tuple(kw["attn_types"])
    return DALLEConfig(**kw)


def vae_config(sizes: dict):
    from dalle_pytorch_tpu.models.vae import DiscreteVAEConfig

    return DiscreteVAEConfig(**sizes["vae"])


def seed_key(seed: int, stream: int):
    """A PRNG key from any whole-number seed (the driver's pass 2**31) and a
    stream number, so weights, inputs and requests never share one."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF), stream)


def raw_key(seed: int, index: int) -> np.ndarray:
    """A raw uint32[2] PRNG key for step or request `index`, made on the host:
    handing it to a jitted call costs no device program of its own."""
    return np.array([((seed >> 32) ^ seed) & 0xFFFFFFFF, index], np.uint32)


def make_weights(cfg, seed: int, param_dtype):
    """DALL-E weights on the device in one jitted call, cast inside it."""
    from dalle_pytorch_tpu.core.pytree import cast_floating
    from dalle_pytorch_tpu.models import dalle as dalle_mod

    return jax.jit(lambda k: cast_floating(dalle_mod.init_dalle(k, cfg), param_dtype))(
        seed_key(seed, 0))


def make_vae(vae_cfg, seed: int):
    from dalle_pytorch_tpu.models.vae import init_discrete_vae

    return jax.jit(lambda k: init_discrete_vae(k, vae_cfg))(seed_key(seed, 1))
