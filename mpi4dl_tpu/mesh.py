"""Device-mesh construction.

The reference derives a rank topology by hand from MPI world size
(``src/torchgems/comm.py:44-137``: split_rank math, spatial groups, GEMS rank
inversion).  On TPU all of that becomes a named :class:`jax.sharding.Mesh`:

- ``data``  — outer data parallelism (reference allreduce groups)
- ``stage`` — pipeline/layer-parallel stages (reference split_rank)
- ``sph``/``spw`` — spatial tile grid over image H/W (reference spatial ranks)

GEMS needs no axis: the mirror placement is a compile-time permutation of the
``stage`` axis (see parallel/gems.py), not a second set of processes.

Axis order is (data, stage, sph, spw) so that the *innermost* (fastest-moving,
most-bandwidth-coupled on ICI) axes are the spatial tile axes that exchange
halos every conv, and stage neighbours are contiguous blocks — the topological
analog of the reference pinning spatial ranks to one node's 4 GPUs
(``comm.py:34-41``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical mesh-axis names.  All collective calls and PartitionSpecs in the
# package reference these constants (not raw strings) so the static analyzer
# (mpi4dl_tpu/analysis, rule `collective-axis`) can verify every axis name
# against this single source of truth.
AXIS_DATA = "data"
AXIS_STAGE = "stage"
AXIS_SPH = "sph"
AXIS_SPW = "spw"

AXES = (AXIS_DATA, AXIS_STAGE, AXIS_SPH, AXIS_SPW)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    data: int = 1
    stage: int = 1
    sph: int = 1
    spw: int = 1

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.data, self.stage, self.sph, self.spw)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))

    @classmethod
    def from_config(cls, cfg) -> "MeshSpec":
        """Derive the mesh from a ParallelConfig, mirroring the reference's
        mp_size math (comm.py:59-67): the spatial region occupies
        num_spatial_parts devices which double as the first `spatial_size`
        pipeline stage(s)."""
        if cfg.spatial_size > 0 and cfg.spatial_part_size > 1:
            if cfg.slice_method == "square":
                g = int(np.sqrt(cfg.spatial_part_size))
                sph, spw = g, g
            elif cfg.slice_method == "vertical":
                sph, spw = 1, cfg.spatial_part_size
            else:  # horizontal
                sph, spw = cfg.spatial_part_size, 1
        else:
            sph, spw = 1, 1
        return cls(data=cfg.data_parallel, stage=cfg.split_size, sph=sph, spw=spw)


def build_mesh(
    spec: MeshSpec,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named Mesh of shape (data, stage, sph, spw).

    With fewer devices than ``spec.size`` this raises — tests use the
    8-device CPU fixture; ``chip_smoke.py --four-chips`` runs real meshes.
    """
    devices = list(devices if devices is not None else jax.devices())
    need = spec.size
    if len(devices) < need:
        raise ValueError(
            f"mesh {spec} needs {need} devices, have {len(devices)} "
            "(virtual CPU devices are provisioned only under "
            "JAX_PLATFORMS=cpu)"
        )
    arr = np.array(devices[:need]).reshape(spec.shape)
    return Mesh(arr, AXES)


def single_device_mesh() -> Mesh:
    return build_mesh(MeshSpec())


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> int:
    """Multi-host initialization — the TPU-native analog of the reference's
    ``dist.init_process_group("mpi")`` world init (``comm.py:154-159``).

    On TPU pods ``jax.distributed.initialize()`` auto-discovers the
    coordinator and peers from the TPU environment; elsewhere pass the
    coordinator address + process count/id (or set the standard
    ``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``).
    After this, ``jax.devices()`` spans every host and :func:`build_mesh`
    builds pod-wide meshes — with the default (data, stage, sph, spw) axis
    order, the outermost ``data`` axis lands across hosts (DCN) and the
    innermost spatial tile axes stay within a host's ICI domain, which is
    the right network mapping for gradient-allreduce-over-DCN /
    halo-exchange-over-ICI.  Returns the process index.  Idempotent: a
    second call is a no-op.
    """
    import os

    import jax

    # Probe WITHOUT touching the backend: jax.process_count() would
    # initialize XLA, after which distributed.initialize() always raises.
    try:
        from jax._src.distributed import global_state

        already = global_state.client is not None
    except Exception:  # noqa: BLE001 — internals moved; assume fresh
        already = False
    if not already:
        kwargs = {}
        if coordinator_address:
            kwargs = dict(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        # A failure is only benign when NO distributed environment was
        # configured — via args, the standard env vars, or a TPU pod
        # environment; swallowing it there would silently train N
        # unsynchronized single-process replicas.
        configured = bool(coordinator_address) or any(
            os.environ.get(v)
            for v in (
                "JAX_COORDINATOR_ADDRESS",
                "COORDINATOR_ADDRESS",
                "TPU_WORKER_HOSTNAMES",
                "MEGASCALE_COORDINATOR_ADDRESS",
            )
        )
        try:
            jax.distributed.initialize(**kwargs)
        except (RuntimeError, ValueError) as e:
            if configured:
                raise
            import logging

            logging.getLogger(__name__).warning(
                "single-process mode (%s)", e
            )
    return jax.process_index()
