"""VMEM budget certification: the (b) check.

Re-derives the per-grid-point VMEM total from the traced specs — VMEM
scratch allocations at full size plus every blocked VMEM operand DOUBLE
(the Pallas pipeline keeps two buffers per blocked operand so the next
block's DMA overlaps compute) — and certifies it against
``--require-vmem-frac`` x the 16 MiB per-core pool.  The total is derived,
not declared: a kernel's own budget model is an a-priori formula, this one
is read back from what was actually traced, so the two cannot drift apart
silently.
"""

from __future__ import annotations

from typing import Dict, List

from mpi4dl_tpu.analysis.pallascheck import VMEM_BYTES, Finding
from mpi4dl_tpu.analysis.pallascheck.trace import VMEM, KernelSpec


def vmem_breakdown(spec: KernelSpec) -> Dict[str, int]:
    """Per-operand VMEM bytes (pipeline-doubled for blocked operands) plus
    the ``total`` — the contract section pins the total so a scratch-shape
    or tiling change is a reviewable drift, not a silent one."""
    out: Dict[str, int] = {}
    total = 0
    for op in spec.operands:
        if op.memory_space != VMEM:
            continue
        n = op.block_bytes() * (2 if op.blocked else 1)
        out[op.name] = n
        total += n
    out["total"] = total
    return out


def vmem_findings(spec: KernelSpec,
                  require_vmem_frac: float = 1.0) -> List[Finding]:
    breakdown = vmem_breakdown(spec)
    total = breakdown.pop("total")
    budget = int(VMEM_BYTES * require_vmem_frac)
    if total <= budget:
        return []
    parts = ", ".join(
        f"{name} {bytes_ / 1024 / 1024:.2f}"
        for name, bytes_ in sorted(breakdown.items(),
                                   key=lambda kv: -kv[1])
    )
    return [Finding(
        kind="vmem-overbudget",
        kernel=spec.case,
        grid_class="",
        message=(
            f"per-grid-point VMEM {total / 1024 / 1024:.2f} MiB exceeds "
            f"{require_vmem_frac:g} x {VMEM_BYTES // (1024 * 1024)} MiB "
            f"(blocked operands double-buffered; MiB by operand: {parts})"
        ),
    )]
