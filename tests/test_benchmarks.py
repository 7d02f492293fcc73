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


_PERFBENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench")


@pytest.mark.parametrize("rel", sorted(
    os.path.join(d, f) for d in ("configs", "traffic")
    for f in os.listdir(os.path.join(_PERFBENCH, d)) if f.endswith(".json")))
def test_every_flag_a_benchmark_cell_passes_still_parses(rel):
    """The `argv` of each configuration and traffic file of the benchmark
    (read, never edited here) goes through the entry point's parser and
    `config_from_args` as `perfbench.harness.build` sends it.  Fails when a
    flag a cell passes is retired or renamed: argparse exits on it."""
    import json

    from mpi4dl_tpu.config import ParallelConfig, config_from_args, get_parser

    with open(os.path.join(_PERFBENCH, rel)) as f:
        argv = json.load(f)["argv"]
    assert any(a.startswith("--") for a in argv)
    cfg = config_from_args(get_parser().parse_args(argv))
    assert isinstance(cfg, ParallelConfig)


@pytest.mark.parametrize("flag", ["--pallas-conv", "--no-pallas-conv"])
def test_retired_convolution_flags_are_refused(flag, capsys):
    """The option that picked a convolution's path is gone with the path: a
    command line that still carries it stops at the parser, not silently on
    another path.  Fails if the parser takes the flag again (or abbreviates
    it to a live one)."""
    from mpi4dl_tpu.config import get_parser

    with pytest.raises(SystemExit):
        get_parser().parse_args([flag])
    assert "unrecognized arguments" in capsys.readouterr().err


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
