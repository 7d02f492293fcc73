"""Structured telemetry: the library that replaces print-pile observability.

Every training step becomes self-describing through four pieces (ISSUE 2;
the reference's CUDA-event phase timing + MPI message accounting, SURVEY
§2a, re-expressed as compiler artifacts):

- **Trace scopes** (:mod:`~mpi4dl_tpu.obs.scopes`): ``obs.scope(name)``
  threads semantic names (``cell03``, ``halo_exchange_w``, ``stage1``)
  through the hot paths so XProf traces and compiled HLO carry phase
  attribution.  Disable with ``MPI4DL_NO_SCOPES=1``.
- **Host spans** (:mod:`~mpi4dl_tpu.obs.spans`): one in-memory recorder of
  where the host's time goes — set-up, the supervised loop, the loader and
  jax's own trace/lower/compile events — each span also a profiler
  annotation.  ``MPI4DL_NO_SCOPES=1`` turns it off with the scopes.
- **Run telemetry** (:mod:`~mpi4dl_tpu.obs.runlog`): :class:`RunLog` JSONL
  sink — run metadata (config, mesh, device, jax version, active hatches)
  plus per-step records (wall ms, images/sec, loss/acc, memory watermark,
  jit-cache retrace probe).
- **Derived metrics** (:mod:`~mpi4dl_tpu.obs.costs`,
  :mod:`~mpi4dl_tpu.obs.hlo_stats`): FLOPs/bytes from
  ``compiled.cost_analysis()`` → MFU + arithmetic intensity; per-class
  collective count/bytes parsed from compiled HLO.
- **Surfaces**: ``python -m mpi4dl_tpu.obs report run.jsonl``
  (:mod:`~mpi4dl_tpu.obs.report`), and ``--telemetry-dir`` on every
  benchmark entry point (benchmarks/common.py) and bench.py.

Forensics + fleet telemetry (ISSUE 17) ride on the same records:

- **Flight recorder** (:mod:`~mpi4dl_tpu.obs.flight`): bounded in-memory
  ring of the last N step records + checkpoint/anomaly/preempt events,
  dumped as ``flight.json`` on anomaly/escalation/preemption/crash — the
  supervisor's fourth evidence source.  ``MPI4DL_NO_FLIGHT=1`` disables.
- **Trace export** (:mod:`~mpi4dl_tpu.obs.trace`): Chrome/Perfetto
  trace-event JSON of the simulated wire schedule, analytical timeline,
  pipeline tick lanes, and measured RunLog walls.
- **Metrics** (:mod:`~mpi4dl_tpu.obs.metrics`): OpenMetrics/Prometheus
  text exposition (file snapshot + stdlib HTTP endpoint).
- **Trend** (:mod:`~mpi4dl_tpu.obs.trend`): directory-wide trajectory +
  newest-vs-previous regression gate (``obs report --trend DIR``).
"""

from __future__ import annotations

from mpi4dl_tpu.obs.scopes import scope, scopes_enabled
from mpi4dl_tpu.obs.runlog import (
    RunLog,
    active_hatches,
    device_memory_watermark,
    device_memory_watermarks,
    host_rss_peak_bytes,
    jit_cache_size,
    read_runlog,
)
from mpi4dl_tpu.obs.flight import (
    FlightRecorder,
    flight_summary,
    read_flight,
    watermark_growth,
)
from mpi4dl_tpu.obs.trace import (
    chrome_trace,
    hlo_trace_events,
    trace_from_runlog,
)
from mpi4dl_tpu.obs.metrics import (
    metrics_from_records,
    metrics_from_runlog,
    metrics_from_runlogs,
    serve_metrics,
    write_metrics_file,
)
from mpi4dl_tpu.obs.trend import (
    format_trend,
    read_bench_artifact,
    trend_report,
)
from mpi4dl_tpu.obs.costs import (
    arithmetic_intensity,
    compiled_cost,
    ici_bytes_per_s,
    mfu,
    peak_flops,
    step_cost,
)
from mpi4dl_tpu.obs.hbm import (
    attribute_compiled,
    attribute_hlo,
    compare_breakdowns,
    format_breakdown,
    format_delta,
    scope_group_bytes,
    top_scope,
)
from mpi4dl_tpu.obs.timeline import (
    analytical_timeline,
    bubble_fraction,
    collective_base,
    format_timeline,
    hlo_scope_costs,
    pipeline_ticks,
)
from mpi4dl_tpu.obs.overlap import (
    format_ledger,
    overlap_ledger,
    structural_overlap,
    wire_class,
)
from mpi4dl_tpu.obs.hlo_stats import (
    clean_scope_path,
    compiled_collective_stats,
    hlo_collective_stats,
    scope_coverage,
    scope_names,
    stablehlo_collectives,
    stablehlo_debug_text,
    stablehlo_sharding_annotations,
)

__all__ = [
    "FlightRecorder",
    "RunLog",
    "active_hatches",
    "analytical_timeline",
    "arithmetic_intensity",
    "attribute_compiled",
    "attribute_hlo",
    "bubble_fraction",
    "chrome_trace",
    "clean_scope_path",
    "collective_base",
    "compare_breakdowns",
    "compiled_collective_stats",
    "compiled_cost",
    "device_memory_watermark",
    "device_memory_watermarks",
    "flight_summary",
    "format_breakdown",
    "format_delta",
    "format_ledger",
    "format_timeline",
    "format_trend",
    "hlo_collective_stats",
    "hlo_scope_costs",
    "hlo_trace_events",
    "host_rss_peak_bytes",
    "ici_bytes_per_s",
    "jit_cache_size",
    "metrics_from_records",
    "metrics_from_runlog",
    "metrics_from_runlogs",
    "mfu",
    "overlap_ledger",
    "peak_flops",
    "pipeline_ticks",
    "read_bench_artifact",
    "read_flight",
    "read_runlog",
    "scope",
    "scope_coverage",
    "scope_group_bytes",
    "scope_names",
    "scopes_enabled",
    "serve_metrics",
    "stablehlo_collectives",
    "stablehlo_debug_text",
    "stablehlo_sharding_annotations",
    "step_cost",
    "structural_overlap",
    "top_scope",
    "trace_from_runlog",
    "trend_report",
    "watermark_growth",
    "wire_class",
]
