"""Test bootstrap: force a virtual 8-device CPU mesh before JAX initializes.

This is the analog of the reference's Spark `local[n]` test master
(reference ``dl4j-spark/src/test/.../BaseSparkTest.java:90``): the full
distributed code path exercised in a single process.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# The suite is compile-bound (thousands of tiny programs, each run a few
# times): skip LLVM's expensive passes, which buy run time it does not need.
if "xla_llvm_disable_expensive_passes" not in _flags:
    _flags += " --xla_llvm_disable_expensive_passes=true"
os.environ["XLA_FLAGS"] = _flags.strip()
# The suite is a CPU suite wherever it runs (a machine with a chip included:
# tests/test_backend_parity.py hands the chip to its own children).
os.environ["JAX_PLATFORMS"] = "cpu"
# One persistent XLA compile cache for the suite and every child it starts,
# every program cached: the suite compiles the same small programs over and
# over (each decode engine's ladder, each fleet host, each example). It is
# where the caller placed it, else at the checkout's fixed .jax_cache (the
# path is part of the cache key). On jax 0.9.0 executables loaded from it are
# bit-identical to fresh ones: the elastic digest-chain tests, which an
# earlier JAX broke on a warm cache, pass cold and warm (PR 21).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax  # noqa: E402

# Gradient checks run in float64 (parity with the reference's double-precision
# gradient checks, GradientCheckUtil.java); enable x64 support globally.
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _pallas_interpret_mode():
    """The suite asks for interpret mode: wherever a test routes attention
    to the Pallas kernels (``DL4JTPU_FLASH_ATTENTION=1``), they run
    interpreted on the CPU. Nothing infers this from the platform
    (``util.xla.kernel_mode``)."""
    from deeplearning4j_tpu.util.xla import interpret_kernels
    with interpret_kernels():
        yield


@pytest.fixture
def rng():
    return np.random.default_rng(12345)
