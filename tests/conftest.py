"""Test harness: force a virtual 8-device CPU platform BEFORE the backend
initializes so multi-chip sharding logic is exercised without TPU hardware
(the JAX-native answer to testing multi-node without a cluster — see
SURVEY.md §4).  pytest plugins may import jax early, so both the env vars and
the live config are forced here.  The persistent compilation cache the CLIs
turn on (cli/common.enable_compile_cache) stays OFF under test — in this
process and in every CLI subprocess a test spawns."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    # tier-1 (ROADMAP.md) runs `-m 'not slow'`: multi-process / multi-minute
    # tests carry these markers so the fast suite stays fast
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 fast suite (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers", "multichip: exercises multi-device or multi-process topology"
    )
