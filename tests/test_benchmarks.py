"""The L6 entry-point layer (benchmarks/common.run) driven in-process.

The engines have exact-match tests; this protects the runner glue — flag
parsing, level/junction derivation, mesh provisioning, dataset
dispatch, the epoch loop, and the summary contract — for the composite
families (smallest configs that still exercise the full path)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import run


def _argv(**over):
    base = {
        "--model": "resnet",
        "--image-size": "32",
        "--num-layers": "1",
        "--batch-size": "8",
        "--steps-per-epoch": "2",
    }
    base.update(over)
    out = []
    for k, v in base.items():
        out.append(k)
        if v is not None:
            out.append(v)
    return out


def _check(summary):
    assert set(summary) >= {"images_per_sec", "loss", "steps"}
    assert np.isfinite(summary["loss"]), summary
    assert summary["steps"] >= 1


def test_run_sp_multilevel_local_dp(devices8):
    """The most composite SP path: two spatial levels + LOCAL_DP_LP junction
    + pipeline tail, straight through the CLI glue."""
    _check(run("sp", "resnet", _argv(**{
        "--batch-size": "12",
        "--slice-method": "vertical",
        "--num-spatial-parts": "2,1",
        "--spatial-size": "2",
        "--split-size": "3",
        "--parts": "2",
        "--local-DP": "2",
    })))


def test_run_gems_sp(devices8):
    _check(run("gems_sp", "resnet", _argv(**{
        "--split-size": "2",
        "--parts": "2",
        "--num-spatial-parts": "4",
    })))


def test_run_lp_bf16_all(devices8):
    _check(run("lp", "resnet", _argv(**{
        "--split-size": "2",
        "--parts": "2",
        "--precision": "bf_16_all",
    })))


def test_pallas_conv_flag_tristate():
    """--pallas-conv / --no-pallas-conv / absent parse to True/False/None,
    and auto resolves OFF on every backend (XLA's fusion wins at the step
    level — resolve_pallas_conv's docstring); the flag is the opt-in."""
    from mpi4dl_tpu.config import (
        config_from_args, get_parser, resolve_pallas_conv,
    )

    p = get_parser()
    assert config_from_args(p.parse_args([])).pallas_conv is None
    assert config_from_args(p.parse_args(["--pallas-conv"])).pallas_conv is True
    assert config_from_args(
        p.parse_args(["--no-pallas-conv"])
    ).pallas_conv is False
    assert resolve_pallas_conv(True) is True
    assert resolve_pallas_conv(False) is False
    assert resolve_pallas_conv(None) is False


@pytest.mark.parametrize("platforms,provisioned", [
    ("cpu", [8]), ("tpu,cpu", [8]), ("", []), ("tpu", []),
])
def test_virtual_devices_only_where_jax_platforms_names_the_cpu(
        monkeypatch, platforms, provisioned):
    """On a chip machine whose TPU fails to initialise jax falls back to the
    CPU; the runner must not have provisioned virtual devices for it."""
    from benchmarks import common
    from jax._src import xla_bridge

    calls = []
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.delenv("MPI4DL_FLEET_SLICE_DEVICES", raising=False)
    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: False)
    monkeypatch.setattr("mpi4dl_tpu.compat.ensure_host_device_count",
                        calls.append)
    common._ensure_devices(4)
    assert calls == provisioned


def test_mesh_larger_than_the_devices_is_an_error(devices8):
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh

    with pytest.raises(ValueError, match="needs 16 devices, have 8"):
        build_mesh(MeshSpec(stage=4, sph=2, spw=2), devices8)
