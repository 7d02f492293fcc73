"""Static VMEM/DMA/grid certification for Pallas kernels (ISSUE 19).

``ircheck`` (ISSUE 16) verifies the engine wire but treats ``pallas_call``
as an opaque custom call; this package opens the box.  It traces every
kernel registered in :mod:`mpi4dl_tpu.ops.kernel_registry` on a CPU host
(``jax.make_jaxpr`` only — no TPU compile), enumerates the full grid, and
abstract-interprets the kernel jaxpr per grid point in the TPU's sequential
row-major order (last grid dimension innermost, scratch persisting across
steps).  It is the safety rail ROADMAP item 2's halo-RDMA conv is built
against: the invariants that were comments — a DMA write into a scratch
buffer that is still being read (a WAR hazard Mosaic does not fence), the
hand-maintained VMEM caps — are now checked, and an
inter-chip ``make_async_remote_copy`` kernel will be enrolled into the same
gate by one registry row.

Finding classification (every kind has an injected-violation fixture in
tests/test_pallascheck.py; keys are ``kernel:grid_point_class:kind`` with a
grid-point class like ``lo-mid-hi`` — one coordinate class per grid dim —
so baselines survive shape tweaks that keep the failure class):

grid/BlockSpec soundness (grid.py):

- ``oob-block`` — an index-map output places a block (partially) outside
  its operand array for some grid point;
- ``overlapping-output`` — an output block is revisited NON-consecutively:
  the pipeline emits it at the end of each visit run, so a later run
  silently clobbers data already written (consecutive revisits are the
  legal accumulation pattern and feed the ``uninit-accumulator`` check);
- ``untiled-output`` — grid-wide, the output blocks do not cover the
  output array (rows that no program ever writes reach HBM as garbage);
- ``misaligned-block`` — a block shape that violates the 128-lane /
  dtype-sublane tiling on its minor two dims (Mosaic would reject or pad);

VMEM budget certification (vmem.py):

- ``vmem-overbudget`` — scratch + double-buffered blocked operands exceed
  ``--require-vmem-frac`` x the 16 MiB per-core pool;

DMA/semaphore discipline (interp.py):

- ``unmatched-dma`` — a start with no wait on the same semaphore along
  some ``pl.when``/branch path (or still in flight at kernel end), a wait
  with no start, or a second start racing an in-flight copy;
- ``dma-race`` — a read of a DMA destination before its wait, or a write
  to a DMA source/destination while the copy is in flight (Mosaic does
  not fence a DMA write against in-flight reads of the same buffer: a
  scratch slot is written by one DMA a visit and waited before its read);
- ``nonbijective-device-map`` — a remote copy whose resolved ``device_id``
  map repeats a target (or leaves the declared ring) across the grid, or
  any remote copy in a kernel whose registry case declares no topology;

accumulator-init coverage (interp.py):

- ``uninit-accumulator`` — a scratch/output ref read before any write, or
  scratch read at the start of a revisited-output run while still holding
  the previous block's values (an ``@pl.when(k == 0)`` guard that does not
  cover every revisit).  A scratch's runs are those of the outputs its
  values are stored into: accumulators of outputs that move at two grains
  (a flash backward's dq beside its dk and dv) are each held to their own.

Entry points: :func:`check_spec`, :func:`check_case`,
:func:`check_registry`, :func:`pallas_contract` (the contract gate's
``pallas`` golden section), and the CLI
``python -m mpi4dl_tpu.analysis pallascheck``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

FINDING_KINDS = (
    "oob-block",
    "overlapping-output",
    "untiled-output",
    "misaligned-block",
    "vmem-overbudget",
    "unmatched-dma",
    "dma-race",
    "nonbijective-device-map",
    "uninit-accumulator",
)

#: per-core VMEM pool certified against: a TPU v5e core's 16 MiB
VMEM_BYTES = 16 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class Finding:
    """One kernel-verification failure, keyed ``kernel:grid_class:kind``."""

    kind: str         # one of FINDING_KINDS
    kernel: str       # registry case name (fixture name for unit runs)
    grid_class: str   # per-dim lo/mid/hi class, "" for whole-kernel findings
    message: str

    @property
    def key(self) -> str:
        return f"{self.kernel}:{self.grid_class or '*'}:{self.kind}"

    @property
    def baseline_key(self) -> Tuple[str, str, str, str]:
        return (self.kind, self.kernel, self.grid_class, self.message)

    def render(self) -> str:
        return f"{self.key}: {self.message}"


def check_spec(spec, case=None,
               require_vmem_frac: float = 1.0) -> List[Finding]:
    """All findings for one traced :class:`~.trace.KernelSpec`."""
    from mpi4dl_tpu.analysis.pallascheck.grid import grid_findings
    from mpi4dl_tpu.analysis.pallascheck.interp import interp_findings
    from mpi4dl_tpu.analysis.pallascheck.vmem import vmem_findings

    out = grid_findings(spec)
    out += vmem_findings(spec, require_vmem_frac=require_vmem_frac)
    out += interp_findings(spec, case=case)
    return _sorted(out)


def check_case(case, require_vmem_frac: float = 1.0) -> List[Finding]:
    """Trace one registry case and check every ``pallas_call`` in it."""
    from mpi4dl_tpu.analysis.pallascheck.trace import trace_case

    out: List[Finding] = []
    for spec in trace_case(case):
        out += check_spec(spec, case=case,
                          require_vmem_frac=require_vmem_frac)
    return _sorted(out)


def check_registry(kernels: Optional[Sequence[str]] = None,
                   require_vmem_frac: float = 1.0) -> List[Finding]:
    """Check every registered kernel case (optionally a name subset)."""
    from mpi4dl_tpu.ops.kernel_registry import REGISTRY, case_names

    wanted = set(case_names(kernels))
    out: List[Finding] = []
    for case in REGISTRY:
        if case.name in wanted:
            out += check_case(case, require_vmem_frac=require_vmem_frac)
    return _sorted(out)


def finding_counts(findings) -> Dict[str, int]:
    """``{kind: count}`` — the ``pallas`` contract section's golden
    material (zero-count kinds omitted so a clean kernel pins ``{}``)."""
    out: Dict[str, int] = {}
    for f in findings:
        out[f.kind] = out.get(f.kind, 0) + 1
    return dict(sorted(out.items()))


PALLAS_CONTRACT_SCHEMA = 1


def pallas_contract(require_vmem_frac: float = 1.0) -> dict:
    """The contract gate's ``pallas`` section: per registered case, the
    reviewable kernel shape — grid, per-operand block shapes, the
    re-derived per-grid-point VMEM total, static DMA-start count, and the
    finding counts (all zero on a clean tree).  Golden:
    ``contracts/pallas.json``."""
    import jax

    from mpi4dl_tpu.analysis.pallascheck.trace import trace_case
    from mpi4dl_tpu.analysis.pallascheck.vmem import vmem_breakdown
    from mpi4dl_tpu.ops.kernel_registry import REGISTRY

    kernels: Dict[str, dict] = {}
    for case in REGISTRY:
        for spec in trace_case(case):
            findings = check_spec(spec, case=case,
                                  require_vmem_frac=require_vmem_frac)
            dma_starts = _count_prim(spec.jaxpr, "dma_start")
            kernels[spec.case] = {
                "grid": list(spec.grid),
                "blocks": {
                    op.name: list(op.shape)
                    for op in spec.operands if op.role != "index"
                },
                "vmem_bytes": vmem_breakdown(spec)["total"],
                "dma_starts": dma_starts,
                "findings": finding_counts(findings),
            }
    return {
        "schema": PALLAS_CONTRACT_SCHEMA,
        "jax": jax.__version__,
        "vmem_frac": require_vmem_frac,
        "kernels": kernels,
    }


def _count_prim(jaxpr, name: str) -> int:
    from mpi4dl_tpu.analysis.pallascheck.trace import _sub_jaxprs

    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for sub in _sub_jaxprs(eqn.params):
            n += _count_prim(sub, name)
    return n


def _sorted(findings: List[Finding]) -> List[Finding]:
    return sorted(
        findings, key=lambda f: (f.kernel, f.kind, f.grid_class, f.message)
    )


def point_class(grid: Sequence[int], point: Sequence[int]) -> str:
    """Per-dim lo/mid/hi class of one grid point (size-1 dims are ``lo``):
    the ``grid_point_class`` segment of finding keys, chosen so a finding
    keyed at an edge/interior class survives shape tweaks."""
    parts = []
    for size, idx in zip(grid, point):
        if idx == 0:
            parts.append("lo")
        elif idx == int(size) - 1:
            parts.append("hi")
        else:
            parts.append("mid")
    return "-".join(parts)
