"""One benchmark configuration, one process — prints ONE JSON line.

Metric: training throughput (images/sec) of one model configuration on one
chip; the default is AmoebaNet-D(18, 416) at the reference's flagship
1024x1024, batch size 1, without remat (BASELINE.md: the reference's best
bs1 result at 1024² is ≈2.1 img/s for SP square + halo-D2 across FIVE GPUs).

    python bench.py                                   # the 1024² headline
    python bench.py --image-size 2048 --remat cell --scan 1 --iters 4
    python bench.py --arch resnet --num-layers 110 --remat sqrt

The process exits 3, printing nothing, when jax does not hand it the
platform it was asked for (``--platform``, default ``tpu``): a number from
the CPU is never printed under this metric's name.  The JSON names the
device it ran on.

Honesty instrumentation: the step's FLOPs are taken from XLA's own
``compiled.cost_analysis()`` and the JSON carries ``flops_per_step``,
``achieved_tflops`` and ``mfu`` against the chip's bf16 peak.  A measurement
with mfu > 1 is *physically impossible* and is treated as a failed
measurement: the run falls back to fetching the loss to the host every step
(which cannot overcount) with more iterations.  If even that lands above
peak, ``vs_baseline`` is null and an ``error`` explains.

``_build_step`` / ``build_probe_setup`` are shared with the diagnostic
probes (benchmarks/layout_probe.py, mem_probe.py, profile_step.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BASELINE_CLUSTER = 2.1   # reference: AmoebaNet-D 1024² bs1, SP square + D2, 5 GPUs
BASELINE_DEVICES = 5

_REMAT = {"none": False, "cell": True, "fine": "fine", "sqrt": "sqrt"}


def _build_step(image_size: int, num_layers: int, num_filters: int,
                batch: int = 1, remat=True, scan: int = 1,
                arch: str = "amoeba"):
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step

    if arch == "resnet":
        # Memory-tuned remat grouping for the deep-thin model (PERF_NOTES
        # r4: 16 groups beat sqrt(38)≈6 by ~2.2 GB at 2048²).
        os.environ.setdefault("MPI4DL_SQRT_GROUPS", "16")
        from mpi4dl_tpu.models.resnet import get_resnet_v2

        # num_layers carries the depth for the ResNet rungs (110 = the
        # reference's charted model, BASELINE.md).
        model = get_resnet_v2(
            (batch, image_size, image_size, 3),
            depth=num_layers, num_classes=1000,
        )
    else:
        from mpi4dl_tpu.models.amoebanet import amoebanetd

        model = amoebanetd(
            (batch, image_size, image_size, 3),
            num_classes=1000,
            num_layers=num_layers,
            num_filters=num_filters,
        )
    params, _ = model.init(jax.random.key(0))
    opt = Optimizer("sgd", lr=0.001)
    # bf16 compute + remat: per-cell (remat=True) for the throughput rungs;
    # per-op ("fine") for the max-resolution probes — backward temps bound
    # to one op at a time.  scan>1 packs k optimizer steps per dispatch
    # (the dispatch-overhead amortization, PERF_NOTES r4).
    step = make_train_step(
        model, opt, compute_dtype=jnp.bfloat16, remat=remat, donate=True,
        scan_steps=scan,
    )
    state = TrainState.create(params, opt)
    return step, state


def build_probe_setup(image_size, num_layers, num_filters, batch,
                      remat="none", scan=1, arch="amoeba"):
    """(step, state, x, y) for a rung config — shared by the diagnostic
    probes (benchmarks/layout_probe.py, benchmarks/mem_probe.py) so their
    input conventions (bf16 inputs, scan-stacked leading dim) cannot drift
    from the bench's own rungs."""
    import jax
    import jax.numpy as jnp

    step, state = _build_step(
        image_size, num_layers, num_filters, batch, remat=_REMAT[remat],
        scan=scan, arch=arch,
    )
    shp = (batch, image_size, image_size, 3)
    if scan > 1:
        shp = (scan,) + shp
    x = jax.random.normal(jax.random.key(0), shp, jnp.bfloat16)
    y = jnp.zeros((scan, batch) if scan > 1 else (batch,), jnp.int32)
    return step, state, x, y


def _step_flops(step, state, x, y) -> float | None:
    """FLOPs of one compiled training step from XLA's own cost model."""
    try:
        ca = step.lower(state, x, y).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = float(ca.get("flops", 0.0))
        return f if f > 0 else None
    except Exception as e:  # noqa: BLE001 — any backend may lack cost_analysis
        print(f"[bench] cost_analysis unavailable: {e}", file=sys.stderr)
        return None


def _measure(step, state, xs, ys, iters: int, blocked: bool):
    """Time `iters` steps cycling through fresh inputs.

    blocked=False: steps chain through state; one block_until_ready on the
    full final (state, metrics) plus a device-to-host fetch of the final loss
    — standard async JAX timing.
    blocked=True: fetch the loss scalar to the HOST every step.  A D2H copy
    cannot complete before the value exists, so this is immune to any
    dispatch/readiness artifact; it is a strict upper bound on step time.
    """
    import jax

    n = len(xs)
    t0 = time.perf_counter()
    metrics = None
    for i in range(iters):
        state, metrics = step(state, xs[i % n], ys[i % n])
        if blocked:
            float(metrics["loss"])
    float(metrics["loss"])
    jax.block_until_ready(state)
    return time.perf_counter() - t0, state


def _inner(platform: str, image_size: int, num_layers: int, num_filters: int,
           warmup: int, iters: int, comparable: bool,
           remat="cell", batch: int = 1, scan: int = 1,
           arch: str = "amoeba", telemetry_dir=None) -> None:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(f"[bench] platform={dev.platform} device={dev} "
          f"kind={getattr(dev, 'device_kind', '?')}", file=sys.stderr)
    if dev.platform != platform:
        print(f"[bench] wanted {platform!r}, got {dev.platform!r} — bail",
              file=sys.stderr)
        sys.exit(3)

    step, state = _build_step(
        image_size, num_layers, num_filters, batch, remat=_REMAT[remat],
        scan=scan, arch=arch,
    )
    # One timed "call" = `scan` optimizer steps compiled into one program
    # (scan=1: the plain per-step dispatch).  iters counts optimizer steps.
    calls = max(1, iters // scan)
    iters = calls * scan

    # Fresh inputs: a small pool of distinct images cycled through the loop so
    # no iteration can be satisfied by a cached/constant-folded result.
    n_inputs = min(4, max(2, calls))
    shp = (batch, image_size, image_size, 3)
    if scan > 1:
        shp = (scan,) + shp
    # bf16 input pool: the step casts to compute_dtype anyway, and fp32
    # scan-stacked pools cost real HBM at the memory-frontier rungs
    # (~300 MB at 2048² scan=3 — on rungs that miss fitting by ~250 MB).
    xs = [
        jax.random.normal(jax.random.key(100 + i), shp, jnp.bfloat16)
        for i in range(n_inputs)
    ]
    ys = [
        jnp.full(shp[:-3], i % 1000, jnp.int32).reshape(
            (scan, batch) if scan > 1 else (batch,)
        )
        for i in range(n_inputs)
    ]

    # XLA's HLO cost analysis counts a while/scan body ONCE (trip counts are
    # not folded in) — verified empirically: the scanned program reports the
    # same flops as the unscanned step (5.061e12 at 1024², r4).  So the
    # reported number IS per-step; a call executes `scan` times that.
    flops = _step_flops(step, state, xs[0], ys[0])
    from mpi4dl_tpu.obs.costs import peak_flops

    peak, _ = peak_flops(dev)

    t_c = time.perf_counter()
    for i in range(warmup):
        state, metrics = step(state, xs[i % n_inputs], ys[i % n_inputs])
    float(metrics["loss"])  # D2H: warmup really finished (see _measure)
    jax.block_until_ready(state)
    print(f"[bench] compile+warmup {time.perf_counter() - t_c:.1f}s; "
          f"flops/step={flops}", file=sys.stderr)

    def mfu_of(dt: float, n_calls: int):
        if flops is None or peak is None:
            return None
        return (flops * scan * n_calls / dt) / peak

    mode = "async_chain" if scan == 1 else f"scan{scan}_chain"
    dt, state = _measure(step, state, xs, ys, calls, blocked=False)
    mfu = mfu_of(dt, calls)
    error = None
    if mfu is not None and mfu > 1.0:
        # Physically impossible — the async timing did not capture the real
        # work.  Re-measure with per-call blocking on the full state and more
        # iterations; this cannot overcount.
        print(f"[bench] mfu={mfu:.2f} > 1 under async timing — "
              f"falling back to per-step blocking", file=sys.stderr)
        mode = "per_step_blocked"
        calls = calls * 2
        iters = calls * scan
        dt, state = _measure(step, state, xs, ys, calls, blocked=True)
        mfu = mfu_of(dt, calls)
        if mfu is not None and mfu > 1.0:
            error = (f"measurement failed: mfu={mfu:.2f} > 1 even with "
                     f"per-step block_until_ready on the full state")

    img_per_sec = batch * iters / dt
    achieved = (flops * scan * calls / dt) if flops else None
    ok = error is None
    model_tag = "resnet110v2" if arch == "resnet" else "amoebanetd"
    out = {
        "metric": f"{model_tag}_{image_size}px_bs{batch}_train_img_per_sec"
                  "_single_chip_vs_5gpu_cluster_baseline",
        "value": round(img_per_sec, 4),
        "unit": "images/sec",
        "vs_baseline": (
            round(img_per_sec / BASELINE_CLUSTER, 4) if (comparable and ok) else None
        ),
        "vs_baseline_per_device": (
            round(img_per_sec / (BASELINE_CLUSTER / BASELINE_DEVICES), 4)
            if (comparable and ok) else None
        ),
        "baseline_img_per_sec_cluster": BASELINE_CLUSTER,
        "baseline_devices": BASELINE_DEVICES,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", None),
        "timing_mode": mode,
        "iters": iters,
        "scan_steps_per_dispatch": scan,
        "flops_per_step": flops,
        "achieved_tflops": round(achieved / 1e12, 2) if achieved else None,
        "peak_tflops": round(peak / 1e12, 1) if peak else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
    }
    if error:
        out["error"] = error
    if telemetry_dir:
        # --telemetry-dir: mirror the rung result into a RunLog so bench
        # evidence and training-loop telemetry share one format/reader.
        try:
            from mpi4dl_tpu.obs import RunLog

            with RunLog.create(telemetry_dir, prefix=f"bench-{model_tag}") as rl:
                rl.write_meta(
                    config={
                        "image_size": image_size, "num_layers": num_layers,
                        "num_filters": num_filters, "batch": batch,
                        "remat": remat, "scan": scan, "arch": arch,
                        "platform": platform,
                    },
                    family="bench",
                )
                rl.write("summary", **out)
            from mpi4dl_tpu.obs.metrics import write_metrics_file
            from mpi4dl_tpu.obs.runlog import read_runlog

            prom = os.path.splitext(rl.path)[0] + ".prom"
            write_metrics_file(read_runlog(rl.path), prom)
            print(f"[bench] telemetry -> {rl.path} (+ {prom})",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — telemetry must not kill bench
            print(f"[bench] telemetry failed: {e}", file=sys.stderr)
    print(json.dumps(out))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--arch", default="amoeba", choices=("amoeba", "resnet"))
    ap.add_argument("--image-size", type=int, default=1024)
    ap.add_argument("--num-layers", type=int, default=18,
                    help="AmoebaNet cell count, or the ResNet depth")
    ap.add_argument("--num-filters", type=int, default=416)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=sorted(_REMAT))
    ap.add_argument("--scan", type=int, default=6,
                    help="optimizer steps per dispatch (memory-frontier "
                         "configurations use 1)")
    ap.add_argument("--warmup", type=int, default=1, help="warmup CALLS")
    ap.add_argument("--iters", type=int, default=18, help="timed steps")
    ap.add_argument("--telemetry-dir", default=None,
                    help="mirror the JSON result into a RunLog there")
    args = ap.parse_args(argv)
    _inner(
        args.platform, args.image_size, args.num_layers, args.num_filters,
        args.warmup, args.iters,
        comparable=(args.arch, args.image_size, args.batch) == ("amoeba", 1024, 1),
        remat=args.remat, batch=args.batch, scan=args.scan, arch=args.arch,
        telemetry_dir=args.telemetry_dir,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
