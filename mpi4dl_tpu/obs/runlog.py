"""RunLog: per-run JSONL telemetry sink.

One run = one ``.jsonl`` file; one line = one record, every record carrying
``kind`` (meta | cost | step | summary | hbm | timeline | overlap |
mem_probe | junction_sweep | xprof_ops | readiness | spans | anomaly | recovery |
preempt | checkpoint | restore | quarantine | drill | drill_summary |
supervisor | supervisor_summary | fleet | fleet_summary | <custom> — field
reference in docs/observability.md), ``t`` (unix
seconds) and ``schema``.  The first record is the run's metadata — full config, mesh spec,
device kind, jax version, active ``MPI4DL_*`` hatches — so a step file is
self-describing: no PERF_NOTES archaeology to learn what produced it
(VERDICT r4 weak-9, the bench ladder's rung_config lesson applied to every
training loop).

The sink is line-buffered and flushes per record, so a crash mid-epoch keeps
everything logged so far — same rationale as the try/finally around
``jax.profiler.stop_trace`` in benchmarks/common.py.

``python -m mpi4dl_tpu.obs report run.jsonl`` renders a file (obs/report.py);
:func:`read_runlog` is the programmatic reader.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

SCHEMA_VERSION = 1


def _jsonable(obj: Any) -> Any:
    """Best-effort conversion to JSON-serializable data (dataclasses, dtypes,
    numpy scalars, tuples); falls back to repr so telemetry never raises."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {k: _jsonable(v) for k, v in dataclasses.asdict(obj).items()}
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "item"):  # numpy / jax scalars
        try:
            return obj.item()
        except Exception:  # noqa: BLE001  # analysis: ok(swallow-except)
            pass  # deliberate: falls through to the repr() fallback below
    return repr(obj)


def active_hatches() -> Dict[str, str]:
    """Environment values of every declared ``MPI4DL_*`` hatch that is SET
    (config.HATCHES is the registry; unset hatches are omitted — their
    defaults are documented there)."""
    from mpi4dl_tpu.config import HATCHES

    out: Dict[str, str] = {}
    for name in HATCHES:
        val = os.environ.get(name)
        if val is not None:
            out[name] = val
    return out


def device_memory_watermark(device=None) -> Optional[int]:
    """``peak_bytes_in_use`` from ``device.memory_stats()``; None where the
    backend has no allocator stats (CPU)."""
    import jax

    dev = device if device is not None else jax.devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if not stats:
        return None
    return stats.get("peak_bytes_in_use") or stats.get("bytes_in_use")


def device_memory_watermarks(devices=None) -> Optional[Dict[str, Any]]:
    """Watermarks across ALL local devices — device 0 alone hides SP
    imbalance (an unevenly sliced grid OOMs on the hot tile while device 0
    reads healthy).  ``max``/``min``/``hbm_skew`` (max − min) plus the raw
    ``per_device`` list; None where no device reports allocator stats."""
    import jax

    devs = devices if devices is not None else jax.local_devices()
    peaks: List[int] = []
    for dev in devs:
        stats = getattr(dev, "memory_stats", lambda: None)()
        if not stats:
            continue
        peak = stats.get("peak_bytes_in_use") or stats.get("bytes_in_use")
        if peak:
            peaks.append(int(peak))
    if not peaks:
        return None
    return {
        "max": max(peaks),
        "min": min(peaks),
        "hbm_skew": max(peaks) - min(peaks),
        "devices": len(peaks),
        "per_device": peaks,
    }


def host_rss_peak_bytes() -> Optional[int]:
    """Process peak RSS — the memory watermark that exists on every host,
    including CPU backends whose devices report no allocator stats."""
    try:
        import resource
        import sys

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss is KB on Linux, bytes on macOS.
        return int(peak if sys.platform == "darwin" else peak * 1024)
    except Exception:  # noqa: BLE001 — non-POSIX host
        return None


def jit_cache_size(fn) -> Optional[int]:
    """Compiled-variant count of a jitted callable — the retrace probe.  A
    per-step record sequence where this GROWS past 1 is a retrace hazard
    (shape/dtype churn in the loop; analysis rule ``retrace`` finds the
    static cases, this catches the dynamic ones)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:  # noqa: BLE001
        return None


class RunLog:
    """Append-only JSONL writer for one run."""

    def __init__(self, path: str):
        self.path = path
        # Most recent record written (any kind) — the step watchdog dumps it
        # to stderr alongside live stacks when a step blows its budget.
        self.last_record: Optional[Dict[str, Any]] = None
        # Most recent record PER KIND: the watchdog pairs the last record
        # with the last `checkpoint` record so a stall inside a shard-
        # gather is distinguishable from a data stall.
        self.last_by_kind: Dict[str, Dict[str, Any]] = {}
        # The async checkpoint writer emits `checkpoint` records from its
        # worker thread while the training thread writes `step` records.
        self._lock = threading.Lock()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._fh = open(path, "a", encoding="utf-8")

    @classmethod
    def create(cls, directory: str, prefix: str = "run") -> "RunLog":
        """New uniquely-named run file under ``directory``."""
        os.makedirs(directory, exist_ok=True)
        stamp = time.strftime("%Y%m%d-%H%M%S")
        base = f"{prefix}-{stamp}-p{os.getpid()}"
        path = os.path.join(directory, base + ".jsonl")
        n = 0
        while os.path.exists(path):  # same second, same pid: suffix
            n += 1
            path = os.path.join(directory, f"{base}-{n}.jsonl")
        return cls(path)

    # -- records -----------------------------------------------------------

    def write(self, kind: str, **fields: Any) -> Dict[str, Any]:
        rec = {"kind": kind, "schema": SCHEMA_VERSION, "t": time.time()}
        rec.update({k: _jsonable(v) for k, v in fields.items()})
        with self._lock:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
            self.last_record = rec
            self.last_by_kind[kind] = rec
        return rec

    def write_meta(self, config: Any = None, mesh_spec: Any = None,
                   argv: Optional[List[str]] = None, **extra: Any) -> Dict[str, Any]:
        """The run's self-description record (always the file's first line)."""
        import jax

        devices = jax.devices()
        return self.write(
            "meta",
            config=config,
            mesh=mesh_spec,
            argv=argv,
            jax_version=jax.__version__,
            backend=jax.default_backend(),
            device_count=len(devices),
            device_kind=getattr(devices[0], "device_kind", None),
            platform=devices[0].platform,
            hatches=active_hatches(),
            **extra,
        )

    def write_step(self, *, epoch: int, step: int, ms: float,
                   images_per_sec: float, loss: float, accuracy: float,
                   step_fn=None, measured: bool = True,
                   **extra: Any) -> Dict[str, Any]:
        """One optimizer step.  ``measured=False`` marks warmup/compile steps
        (excluded from summary stats, kept in the record stream)."""
        wm = device_memory_watermarks()
        return self.write(
            "step",
            epoch=epoch,
            step=step,
            ms=round(float(ms), 3),
            images_per_sec=round(float(images_per_sec), 3),
            loss=float(loss),
            accuracy=float(accuracy),
            measured=bool(measured),
            memory_peak_bytes=None if wm is None else wm["max"],
            memory_peak_bytes_min=None if wm is None else wm["min"],
            hbm_skew=None if wm is None else wm["hbm_skew"],
            host_rss_peak_bytes=host_rss_peak_bytes(),
            jit_cache_size=jit_cache_size(step_fn) if step_fn is not None else None,
            **extra,
        )

    def close(self) -> None:
        """Close the file; a log whose step records carry ``spans_ms`` (the
        supervised loop's) first gets the span recorder's set-up spans and
        the programs built in the loop (one ``spans`` record)."""
        if not self._fh.closed:
            if (self.last_by_kind.get("step") or {}).get("spans_ms"):
                from mpi4dl_tpu.obs.spans import recorder

                self.write("spans", **recorder().summary())
            self._fh.close()

    def __enter__(self) -> "RunLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_runlog(path: str) -> List[Dict[str, Any]]:
    """Parse one run file back into records, skipping malformed lines with a
    stderr note — a crashed leg truncates its last line mid-write, and the
    report/trend tooling promises to render crashed-run files."""
    import sys

    out: List[Dict[str, Any]] = []
    with open(path, "r", encoding="utf-8") as fh:
        for lineno, line in enumerate(fh, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                sys.stderr.write(
                    f"[obs] {path}:{lineno}: skipping torn record "
                    f"({len(line)} bytes) — truncated mid-write?\n")
                continue
    return out
