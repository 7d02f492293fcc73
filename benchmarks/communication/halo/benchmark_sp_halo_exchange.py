"""Halo-exchange latency microbenchmark + validation.

Reference: benchmarks/communication/halo/benchmark_sp_halo_exchange.py —
arange-image construction (:417-557), exact compare vs the globally
zero-padded image (:568-578), warmup + CUDA-event timed loop (:581-615).
Its published sample: ≈0.334 ms/iter at 1024², 4-way vertical, halo 3,
batch 1 on 4 GPUs (halo README:29-43).

``--with-compute`` adds the reference's `_with_compute` / `_conv` variants
(benchmark_sp_halo_exchange_with_compute.py:600-666): time exchange+conv
across the tile grid AGAINST the same convolution over the full image on one
device, and validate the gathered distributed conv output against the
single-device result (the `_with_compute_val` check).

This version runs the experiment as ONE jitted shard_map program whose
distributed body is the halo exchange (4 ppermutes max) [+ a VALID conv
consuming the margin], on whatever platform JAX offers: a TPU mesh when
multiple chips are attached, else a forced-host CPU mesh (functional
validation; CPU timing is not comparable).

Example:
  JAX_PLATFORMS=cpu \\
  python benchmark_sp_halo_exchange.py --image-size 256 --halo-len 3 \\
      --num-spatial-parts 4 --slice-method vertical --with-compute
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--image-size", type=int, default=256)
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--channels", type=int, default=3)
    p.add_argument("--halo-len", type=int, default=3)
    p.add_argument("--num-spatial-parts", type=int, default=4)
    p.add_argument("--slice-method", default="vertical",
                   help="square | vertical | horizontal")
    p.add_argument("--warmup", type=int, default=10)
    p.add_argument("--iterations", type=int, default=100)
    p.add_argument("--with-compute", action="store_true",
                   help="also time halo-exchange+conv vs a single-device conv "
                        "(reference _with_compute variant) and validate")
    p.add_argument("--num-filters", type=int, default=32,
                   help="conv output channels for --with-compute")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of the timed loop here")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from mpi4dl_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from mpi4dl_tpu.layer_ctx import spatial_ctx_for
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh
    from mpi4dl_tpu.ops.halo import HaloSpec, halo_exchange_2d

    sp = spatial_ctx_for(args.slice_method, args.num_spatial_parts)
    from benchmarks.common import _ensure_devices

    _ensure_devices(sp.grid_h * sp.grid_w)
    mesh = build_mesh(MeshSpec(sph=sp.grid_h, spw=sp.grid_w), jax.devices())
    h = args.halo_len
    size, b, c = args.image_size, args.batch_size, args.channels
    spec = P(None, sp.axis_h, sp.axis_w, None)
    halo_h = HaloSpec.symmetric(h if sp.grid_h > 1 else 0)
    halo_w = HaloSpec.symmetric(h if sp.grid_w > 1 else 0)

    fn = jax.jit(
        shard_map(
            lambda t: halo_exchange_2d(
                t, halo_h, halo_w, sp.axis_h, sp.axis_w, sp.grid_h, sp.grid_w
            ),
            mesh=mesh, in_specs=spec, out_specs=spec,
        )
    )

    # --- validation: arange image, exact compare against the matching window
    # of the globally zero-padded image (reference :417-461 per slice method).
    x = jnp.arange(b * size * size * c, dtype=jnp.float32).reshape(b, size, size, c)
    out = np.asarray(jax.block_until_ready(fn(x)))
    padded = np.pad(
        np.asarray(x), ((0, 0), (halo_h.lo, halo_h.hi), (halo_w.lo, halo_w.hi), (0, 0))
    )
    th, tw = size // sp.grid_h, size // sp.grid_w
    eth, etw = th + 2 * halo_h.lo, tw + 2 * halo_w.lo
    ok = True
    # shard_map concatenates per-tile outputs along the sharded dims.
    for r in range(sp.grid_h):
        for cc in range(sp.grid_w):
            got = out[:, r * eth : (r + 1) * eth, cc * etw : (cc + 1) * etw]
            want = padded[:, r * th : r * th + eth, cc * tw : cc * tw + etw]
            if not np.array_equal(got, want):
                ok = False
    print(f"validation: {'PASSED' if ok else 'FAILED'}")

    def timed_loop(f, arg):
        """warmup + per-iter timing (reference :598-613)."""
        out_d = f(arg)  # ensure compiled even with --warmup 0
        for _ in range(args.warmup):
            out_d = f(arg)
        jax.block_until_ready(out_d)
        ts = []
        for _ in range(args.iterations):
            t0 = time.perf_counter()
            jax.block_until_ready(f(arg))
            ts.append((time.perf_counter() - t0) * 1e3)
        return np.asarray(ts)

    # try/finally: a crash mid-measurement must still flush the trace
    # (start_trace only buffers; stop_trace writes the files).
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        times_np = timed_loop(fn, x)
        result = {
            "metric": "halo_exchange_ms_per_iter",
            "value": round(float(np.mean(times_np)), 4),
            "median_ms": round(float(np.median(times_np)), 4),
            "min_ms": round(float(np.min(times_np)), 4),
            "platform": jax.devices()[0].platform,
            "config": {
                "image_size": size, "batch": b, "channels": c, "halo_len": h,
                "parts": args.num_spatial_parts, "slice_method": args.slice_method,
            },
            "validation": "pass" if ok else "FAIL",
            "reference_ms": 0.334,  # 4xGPU MVAPICH2-GDR sample, halo README:29-43
        }

        if args.with_compute:
            # Reference _with_compute/_conv: a conv whose receptive field matches
            # the halo (k = 2*halo+1), run (a) distributed as exchange + VALID
            # conv consuming the margin, (b) on the full image on one device;
            # the gathered outputs must agree (_with_compute_val, ref
            # benchmark_sp_halo_exchange_conv.py:759-843) and both get timed
            # (ref benchmark_sp_halo_exchange_with_compute.py:600-666).
            kh = 2 * h + 1
            kernel = jax.random.normal(
                jax.random.key(0), (kh, kh, c, args.num_filters), jnp.float32
            ) / (kh * kh * c)
            sharded_h = sp.grid_h > 1
            sharded_w = sp.grid_w > 1

            def conv(t, pad_h, pad_w):
                return lax.conv_general_dilated(
                    t, kernel, (1, 1), (pad_h, pad_w),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )

            def dist_body(t):
                t = halo_exchange_2d(
                    t, halo_h, halo_w, sp.axis_h, sp.axis_w, sp.grid_h, sp.grid_w
                )
                return conv(
                    t,
                    (0, 0) if sharded_h else (h, h),
                    (0, 0) if sharded_w else (h, h),
                )

            dist_fn = jax.jit(
                shard_map(dist_body, mesh=mesh, in_specs=spec, out_specs=spec)
            )
            single_fn = jax.jit(lambda t: conv(t, (h, h), (h, h)))

            got = np.asarray(jax.block_until_ready(dist_fn(x)))
            want = np.asarray(jax.block_until_ready(single_fn(x)))
            cok = np.allclose(got, want, atol=1e-4)
            print(f"conv validation: {'PASSED' if cok else 'FAILED'}")
            ok = ok and cok

            t_dist = timed_loop(dist_fn, x)
            t_single = timed_loop(single_fn, x)
            result["with_compute"] = {
                "dist_exchange_conv_ms": round(float(np.mean(t_dist)), 4),
                "single_device_conv_ms": round(float(np.mean(t_single)), 4),
                "speedup_vs_single": round(
                    float(np.mean(t_single) / np.mean(t_dist)), 3
                ),
                "num_filters": args.num_filters,
                "kernel": kh,
                "conv_validation": "pass" if cok else "FAIL",
            }
            result["validation"] = "pass" if ok else "FAIL"

    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
    if args.profile_dir:
        result["profile_dir"] = args.profile_dir
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
