"""Test configuration: simulate an 8-device TPU mesh on host CPU.

The reference fakes a cluster with ``mp.spawn`` + gloo (``assert.py:13-25``);
the JAX-native equivalent is a single process with
``--xla_force_host_platform_device_count=N`` so every ``Mesh``/``shard_map``
test runs the exact code that runs on a real TPU slice.
"""

import os

# Must run before jax initializes its backends (conftest imports first).
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)
jax.config.update("jax_default_matmul_precision", "highest")

# The suite is compile-dominated (tiny shapes, one host CPU, every parity
# test jits a fresh shard_map transformer); a persistent on-disk cache cuts
# repeat-run wall time without touching coverage.  Placed by
# JAX_COMPILATION_CACHE_DIR when set, else tests/.jax_cache.
from ring_attention_tpu.utils import enable_compile_cache  # noqa: E402

enable_compile_cache(os.path.join(os.path.dirname(__file__), ".jax_cache"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
