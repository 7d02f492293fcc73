"""Test harness: force an 8-device CPU platform so every SP/PP/GEMS schedule
runs as a real SPMD program in pytest (SURVEY §4: the harness the reference
lacks — its numerical validation needs a 4-5 GPU MPI launch)."""

import os

# Tests run on the CPU whatever the machine holds.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

from mpi4dl_tpu.compat import (  # noqa: E402
    ensure_compilation_cache,
    ensure_host_device_count,
)

jax.config.update("jax_platforms", "cpu")
# Read at backend init, which has not happened yet at conftest time.
ensure_host_device_count(8)
jax.config.update("jax_threefry_partitionable", True)

# Persistent compilation cache: the CPU-mesh programs here are compile-bound
# and identical across runs — cache them on disk so iterating on tests is
# fast.  Placed by the same helper as the runner (JAX_COMPILATION_CACHE_DIR,
# else <checkout>/.jax_cache).
ensure_compilation_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def rec():
    """A span recorder of the test's own, so that ``conv_paths`` and the
    other site counts hold what the test traced and nothing else."""
    from mpi4dl_tpu.obs import spans

    spans._reset_recorder()
    yield spans.recorder()
    spans._reset_recorder()
