"""Capture + analyze an XProf trace of the headline training step — the
measured-time member of the obs stack (docs/observability.md, "Composing
with the profilers").

Builds the exact bench.py headline step (AmoebaNet-D(18,416), bf16, donate,
configurable remat/batch/res), captures a ``jax.profiler`` trace of a few
hot steps, then parses the xplane protobuf with xprof's own converter and
prints the top-N ops by self time.  Because the hot paths are threaded with
``obs.scope`` names, the op rows read ``stage1/cell03/halo_exchange_spw``
instead of ``fusion.1234`` — this is the measured counterpart of the
*analytical* per-scope timeline (``mpi4dl_tpu/obs/timeline.py``) and the
per-scope HBM breakdown (``mpi4dl_tpu/obs/hbm.py``).

``--telemetry-dir`` writes the capture as a RunLog JSONL (meta + per-step
wall records + an ``xprof_ops`` record with the top-op table), so profiler
evidence shares the artifact format every other tool emits and renders via
``python -m mpi4dl_tpu.obs report``.

Usage:
    python benchmarks/profile_step.py --image-size 1024 --batch 1 \
        --remat none --steps 5 --out /tmp/xprof_1024 --telemetry-dir /tmp/t

The analysis step also runs standalone on an existing trace dir:
    python benchmarks/profile_step.py --analyze /tmp/xprof_1024
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import time

# Make `mpi4dl_tpu` importable when run by path (the benchmarks/common.py
# recipe; capture() needs it for bench imports, _open_runlog for obs).
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _open_runlog(args):
    """RunLog sink for ``--telemetry-dir`` (None when the flag is off)."""
    if not getattr(args, "telemetry_dir", None):
        return None
    from mpi4dl_tpu.obs import RunLog

    runlog = RunLog.create(args.telemetry_dir, prefix="profile")
    runlog.write_meta(config=vars(args), family="single",
                      argv=sys.argv[1:])
    return runlog


def capture(args, runlog=None) -> str:
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench import _build_step, _REMAT

    dev = jax.devices()[0]
    print(f"[profile] device={dev} kind={getattr(dev, 'device_kind', '?')}",
          file=sys.stderr)

    if args.sqrt_groups:
        # bench._build_step sets this for its ResNet rungs; the profiler
        # must be able to reproduce the exact frontier configuration.
        os.environ["MPI4DL_SQRT_GROUPS"] = str(args.sqrt_groups)
    step, state = _build_step(
        args.image_size, args.num_layers, args.num_filters, args.batch,
        remat=_REMAT[args.remat], arch=args.arch,
    )
    xs = [
        jax.random.normal(jax.random.key(100 + i),
                          (args.batch, args.image_size, args.image_size, 3),
                          jnp.bfloat16)
        for i in range(2)
    ]
    ys = [jnp.full((args.batch,), i % 1000, jnp.int32) for i in range(2)]

    t0 = time.perf_counter()
    for i in range(2):
        state, metrics = step(state, xs[i % 2], ys[i % 2])
    float(metrics["loss"])
    jax.block_until_ready(state)
    print(f"[profile] compile+warmup {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    from mpi4dl_tpu.obs.spans import recorder

    os.makedirs(args.out, exist_ok=True)
    jax.profiler.start_trace(args.out)
    t0 = time.perf_counter()
    try:
        for i in range(args.steps):
            # Scope-named trace: the step ops carry obs.scope paths; the
            # recorder's step span (a StepTraceAnnotation while the profiler
            # runs) lines the trace's step view up with the RunLog step
            # records (match on step number).
            with recorder().span("step", gstep=i):
                ts = time.perf_counter()
                state, metrics = step(state, xs[i % 2], ys[i % 2])
                if runlog is not None:
                    # Per-step wall records need a per-step sync.  Without
                    # the sink, keep the original free-running dispatch so
                    # the aggregate img/s figure stays comparable with
                    # pre-telemetry captures.
                    jax.block_until_ready(state)
            if runlog is not None:
                step_s = time.perf_counter() - ts
                runlog.write_step(
                    epoch=0, step=i, ms=step_s * 1e3,
                    images_per_sec=args.batch / step_s,
                    loss=float(metrics["loss"]),
                    accuracy=float(metrics.get("accuracy", 0.0)),
                )
        float(metrics["loss"])
        jax.block_until_ready(state)
    finally:
        dt = time.perf_counter() - t0
        jax.profiler.stop_trace()
    print(f"[profile] {args.steps} steps in {dt:.2f}s "
          f"({args.steps * args.batch / dt:.2f} img/s); trace -> {args.out}",
          file=sys.stderr)
    if runlog is not None:
        _record_overlap(step, (state, xs[0], ys[0]), runlog)
    return args.out


def _record_overlap(step, step_args, runlog) -> None:
    """The analytical exposed-wire ledger of the profiled step, written as
    an ``overlap`` RunLog record next to the measured ``xprof_ops`` table —
    the analytical and measured views of the same step land in the same
    JSONL for side-by-side reading (docs/observability.md).  Costs one AOT
    compile (the jit call cache doesn't expose the compiled module's text,
    and the persistent compilation cache is bypassed so the HLO keeps its
    obs.scope metadata)."""
    import time as _time

    import jax

    from mpi4dl_tpu.obs import overlap_ledger

    t0 = _time.perf_counter()
    try:
        cache_dir = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", None)
        try:
            compiled = step.lower(*step_args).compile()
        finally:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        ledger = overlap_ledger(compiled.as_text(),
                                device=jax.devices()[0])
    except Exception as e:  # noqa: BLE001 — telemetry never kills a capture
        print(f"[profile] overlap ledger unavailable ({e})", file=sys.stderr)
        return
    runlog.write("overlap", label="profile_step", **ledger)
    t = ledger["totals"]
    hf = ledger.get("hidden_frac")
    print(
        f"[profile] overlap ledger ({_time.perf_counter() - t0:.1f}s AOT "
        f"compile): wire {t['wire_ms']} ms, exposed {t['exposed_ms']} ms"
        + (f" (hidden {hf:.1%})" if hf is not None else ""),
        file=sys.stderr,
    )


def _find_xplane(trace_dir: str) -> str | None:
    pats = os.path.join(trace_dir, "**", "*.xplane.pb")
    files = sorted(glob.glob(pats, recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def analyze(trace_dir: str, top: int = 30, runlog=None) -> None:
    """Print per-op totals from the device plane of the xplane trace; with
    ``runlog``, also record them as an ``xprof_ops`` RunLog record."""
    xplane = _find_xplane(trace_dir)
    if xplane is None:
        print(f"[profile] no .xplane.pb under {trace_dir}", file=sys.stderr)
        return
    print(f"[profile] parsing {xplane}", file=sys.stderr)
    try:
        from xprof.convert import raw_to_tool_data as rtd
    except ImportError as e:
        # The capture (trace dir + RunLog records) is still useful without
        # the converter; say what is missing instead of dying on it.
        print(f"[profile] xprof converter unavailable ({e}); trace kept at "
              f"{trace_dir} — open it in TensorBoard/XProf instead",
              file=sys.stderr)
        return

    params = {"use_saved_result": False}
    data, _ = rtd.xspace_to_tool_data([xplane], "hlo_stats", params)
    if isinstance(data, bytes):
        data = data.decode("utf-8", "replace")
    obj = json.loads(data) if isinstance(data, str) else data
    # hlo_stats: list-of-dicts table ({p: columns, rows} varies by version).
    rows = obj.get("rows") if isinstance(obj, dict) else obj
    cols = [c.get("label") for c in obj.get("cols", [])] if isinstance(obj, dict) else None
    if not rows or not cols:
        out = os.path.join(trace_dir, "hlo_stats.json")
        with open(out, "w") as f:
            f.write(data if isinstance(data, str) else json.dumps(obj))
        print(f"[profile] unrecognized hlo_stats layout; raw dump -> {out}",
              file=sys.stderr)
        return
    idx = {c: i for i, c in enumerate(cols)}

    def val(r, c):
        return r["c"][idx[c]].get("v")

    key = "Total self time (us)"
    rows = sorted(rows, key=lambda r: -(val(r, key) or 0))
    total = sum(val(r, key) or 0 for r in rows)
    print(f"total device self time: {total / 1e3:.1f} ms")
    for r in rows[:top]:
        t = val(r, key) or 0
        print(
            f"{t / 1e3:8.2f} ms {100 * t / total:5.2f}% "
            f"x{int(val(r, '#Occurrences') or 0):<3d} "
            f"{val(r, 'HLO op category')}: {val(r, 'HLO op name')} "
            f"bound={val(r, 'Bound by')}"
        )
        print("          ", (val(r, "HLO op text") or "")[:160].replace("\n", " "))
    if runlog is not None:
        runlog.write(
            "xprof_ops",
            total_self_ms=round(total / 1e3, 3),
            ops=[
                {
                    "self_ms": round((val(r, key) or 0) / 1e3, 3),
                    "occurrences": int(val(r, "#Occurrences") or 0),
                    "category": val(r, "HLO op category"),
                    "name": val(r, "HLO op name"),
                    "bound_by": val(r, "Bound by"),
                }
                for r in rows[:top]
            ],
        )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--image-size", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--num-layers", type=int, default=18)
    ap.add_argument("--num-filters", type=int, default=416)
    ap.add_argument("--arch", default="amoeba", choices=["amoeba", "resnet"],
                    help="resnet: --num-layers carries the depth (110)")
    ap.add_argument("--remat", default="none",
                    choices=["none", "cell", "fine", "sqrt"])
    ap.add_argument("--sqrt-groups", type=int, default=0,
                    help="MPI4DL_SQRT_GROUPS for --remat sqrt (bench.py's "
                         "ResNet rungs use 16)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--out", default="/tmp/xprof_step")
    ap.add_argument("--analyze", default=None,
                    help="skip capture; analyze this existing trace dir")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--telemetry-dir", default=None,
                    help="write the capture as a RunLog JSONL (meta + "
                         "per-step records + xprof_ops top-op table); "
                         "render with `python -m mpi4dl_tpu.obs report` "
                         "(docs/observability.md)")
    args = ap.parse_args()

    runlog = _open_runlog(args)
    try:
        if args.analyze:
            analyze(args.analyze, args.top, runlog=runlog)
            return 0
        out = capture(args, runlog=runlog)
        analyze(out, args.top, runlog=runlog)
        return 0
    finally:
        if runlog is not None:
            runlog.close()
            print(f"[profile] telemetry written to {runlog.path}",
                  file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
