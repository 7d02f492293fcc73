"""The Pallas kernels compiled by the chip's own compiler, without the chip.

Interpret mode (every other kernel test here) cannot show a block shape
Mosaic refuses, a slice off the tiling, or a VMEM overrun; the TPU compiler
installed in this container can, for a chip that is described and not
attached.  The shapes are the ones ``chip_smoke.py`` runs on the chip: the
D2-step tile (512², 208 channels) and a 4096-token, 128-wide head.

All in ONE file and the topology in a fixture, never at import: only one
process may load libtpu, and under xdist every worker imports every file.
Nothing runs here, so these say nothing about results or times.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chip_smoke import KERNEL_SHAPES, flash_attention  # noqa: E402

from mpi4dl_tpu.ops.pallas_conv import halo_conv2d  # noqa: E402

TILE, CHANNELS = KERNEL_SHAPES["tile"], KERNEL_SHAPES["channels"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; turn the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _conv_args(one_chip):
    x = jax.ShapeDtypeStruct((1, TILE + 2, TILE + 2, CHANNELS), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, CHANNELS, CHANNELS), jnp.bfloat16,
                             sharding=one_chip)
    return x, w


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_halo_conv2d_compiles_for_v5e(one_chip, no_persistent_cache):
    _assert_mosaic(
        halo_conv2d.lower(*_conv_args(one_chip), interpret=False).compile())


def test_fused_relu_conv_bn_stats_compiles_for_v5e(one_chip,
                                                   no_persistent_cache):
    _assert_mosaic(halo_conv2d.lower(
        *_conv_args(one_chip), interpret=False, fuse_relu=True,
        stat_window=(0, TILE, 0, TILE),
    ).compile())


def test_block_flash_fwd_bwd_compiles_for_v5e(one_chip, no_persistent_cache):
    qkv = jax.ShapeDtypeStruct(
        (KERNEL_SHAPES["heads"], KERNEL_SHAPES["seq"],
         KERNEL_SHAPES["head_dim"]), jnp.bfloat16, sharding=one_chip)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(flash_attention, q, k, v)
        return out, vjp(jnp.ones_like(out))

    _assert_mosaic(jax.jit(fwd_bwd).lower(qkv, qkv, qkv).compile())
