"""Derived cost metrics: FLOPs, bytes, MFU, arithmetic intensity.

The FLOP source is XLA's own cost model (``compiled.cost_analysis()``), the
same number bench.py's honesty instrumentation uses: a while/scan body is
counted ONCE (trip counts are not folded in — verified empirically in r4),
so for the scan-stacked step builders the reported figure is per optimizer
step.  MFU is achieved FLOP/s over the chip's published bf16 peak
(:data:`PEAK_BF16_FLOPS` — the single source of truth, imported by bench.py).

On CPU hosts there is no defensible peak, so :func:`peak_flops` returns
``(None, None)`` by default (bench.py's rule: never fake an MFU on the
host).  The report surface (obs/report.py) instead passes
``allow_cpu_nominal=True`` to get :data:`CPU_NOMINAL_PEAK_FLOPS` labeled
``"nominal-cpu"`` — a fixed reference point that makes CPU smoke-run MFU
lines comparable run-over-run while being explicit that it is NOT a
hardware utilization claim.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

# bf16 peak FLOP/s by TPU generation (public numbers); matched by substring
# of jax.devices()[0].device_kind.  Order matters: first match wins, so the
# more specific v5 spellings precede the bare "v5".
PEAK_BF16_FLOPS = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5litepod", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]

# Labeled reference peak for CPU smoke runs (see module docstring) — a
# nominal 100 GFLOP/s core, not a measured host capability.
CPU_NOMINAL_PEAK_FLOPS = 1e11

# Aggregate per-chip ICI bandwidth (public numbers, bytes/s): total
# inter-chip interconnect bandwidth per chip — the denominator of the
# analytical collective-time estimates in obs/timeline.py.  Matched like
# PEAK_BF16_FLOPS (first substring wins, specific v5 spellings first).
ICI_BYTES_PER_S = [
    ("v6", 4.48e11),      # 3,584 Gbps
    ("v5p", 6.0e11),      # 4,800 Gbps
    ("v5 lite", 2.0e11), ("v5e", 2.0e11), ("v5litepod", 2.0e11),  # 1,600 Gbps
    ("v5", 6.0e11),
    ("v4", 3.0e11),       # 2,400 Gbps
    ("v3", 8.2e10),
    ("v2", 6.2e10),
]

# Labeled nominal interconnect for CPU smoke runs — a fixed 10 GB/s
# reference so analytical timelines are comparable run-over-run on the
# virtual mesh (NOT a host measurement; same contract as the nominal peak).
DEFAULT_ICI_BYTES_PER_S = 1e10


def _known_kind(device, table, what: str) -> float:
    """First ``table`` row whose key is a substring of the device kind.  A
    device that is not in the table is an error, not a default: a guessed
    peak turns every utilization built on it into a guess."""
    kind = (getattr(device, "device_kind", "") or "").lower()
    for sub, value in table:
        if sub in kind:
            return value
    raise ValueError(
        f"no {what} known for device kind {kind!r} (platform "
        f"{device.platform!r}); add it to mpi4dl_tpu/obs/costs.py with its "
        "source"
    )


def ici_bytes_per_s(device) -> Tuple[float, str]:
    """(aggregate ICI bytes/s, source) for a jax device; source mirrors
    :func:`peak_flops`: ``"table"`` or ``"nominal-cpu"``.  Raises
    ValueError for an accelerator kind the table does not know."""
    if device is None or device.platform == "cpu":
        return DEFAULT_ICI_BYTES_PER_S, "nominal-cpu"
    return _known_kind(device, ICI_BYTES_PER_S, "ICI bandwidth"), "table"


def peak_flops(device, allow_cpu_nominal: bool = False
               ) -> Tuple[Optional[float], Optional[str]]:
    """(peak FLOP/s, source) for a jax device.

    source: ``"table"`` (known kind), ``"nominal-cpu"`` (only with
    ``allow_cpu_nominal``), or None on the CPU.  Raises ValueError for an
    accelerator kind the table does not know.
    """
    if device.platform == "cpu":
        if allow_cpu_nominal:
            return CPU_NOMINAL_PEAK_FLOPS, "nominal-cpu"
        return None, None
    return _known_kind(device, PEAK_BF16_FLOPS, "bf16 peak FLOP/s"), "table"


def compiled_cost(compiled) -> Dict[str, Optional[float]]:
    """{'flops', 'bytes_accessed'} from a jax.stages.Compiled's cost
    analysis (None where the backend reports nothing useful)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — any backend may lack cost_analysis
        return {"flops": None, "bytes_accessed": None}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0)) or None
    nbytes = float(ca.get("bytes accessed", 0.0)) or None
    return {"flops": flops, "bytes_accessed": nbytes}


def step_cost(step_fn, *args) -> Dict[str, Optional[float]]:
    """Lower + compile ``step_fn(*args)`` and return :func:`compiled_cost`.
    Prefer :func:`compiled_cost` on an existing Compiled to avoid a second
    compilation of the same program."""
    return compiled_cost(step_fn.lower(*args).compile())


def mfu(flops_per_step: Optional[float], step_ms: Optional[float],
        peak: Optional[float], n_devices: int = 1) -> Optional[float]:
    """Model FLOP utilization: (flops/step) / (step seconds) / (peak x N).

    ``cost_analysis`` on an SPMD program reports the PER-DEVICE module's
    FLOPs, so the usual call passes per-device flops with ``n_devices=1``;
    pass aggregate flops with the device count only when you summed shards
    yourself."""
    if not flops_per_step or not step_ms or not peak or step_ms <= 0:
        return None
    return (flops_per_step / (step_ms / 1e3)) / (peak * max(n_devices, 1))


def arithmetic_intensity(flops: Optional[float],
                         bytes_accessed: Optional[float]) -> Optional[float]:
    """FLOPs per HBM byte — the roofline abscissa; low values say the step
    is bandwidth-bound and more MFU needs fusion/layout work, not schedule
    work."""
    if not flops or not bytes_accessed:
        return None
    return flops / bytes_accessed
