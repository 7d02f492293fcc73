"""Pallas halo-consuming conv vs XLA conv — the SURVEY §7 D2-endgame
measurement (VERDICT r3 task 9: measure, then decide).

Times the margin-consuming VALID conv (the hot op of fused halo-D2 runs,
ops/d2.py) three ways at D2-representative shapes:

  xla_valid   — lax.conv_general_dilated VALID on the margin-carrying input
                (the production path inside a fused run today)
  pallas      — ops/pallas_conv.halo_conv2d (implicit-GEMM Pallas kernel)
  xla_same    — lax.conv SAME on the unpadded input (the D1 cost for scale)

Prints one JSON line with ms + achieved TFLOPs per variant and the
pallas/xla speedup.  Run on real TPU hardware; on CPU it still runs (with
--interpret for the Pallas path) but timings are not meaningful.

Example:
  python benchmark_pallas_conv.py --height 512 --width 512 --cin 256 \\
      --cout 256 --kernel 3 --dtype bf16
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
    p.add_argument("--height", type=int, default=512)
    p.add_argument("--width", type=int, default=512)
    p.add_argument("--cin", type=int, default=256)
    p.add_argument("--cout", type=int, default=256)
    p.add_argument("--kernel", type=int, default=3)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    p.add_argument("--tile-h", type=int, default=64)
    p.add_argument("--tile-w", type=int, default=128)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--iterations", type=int, default=30)
    p.add_argument("--interpret", action="store_true",
                   help="run the Pallas kernel in interpreter mode (CPU)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4dl_tpu.ops.pallas_conv import conv_flops, halo_conv2d

    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    k, h, w = args.kernel, args.height, args.width
    m = k - 1
    kx, kw_ = jax.random.split(jax.random.key(0))
    x_pad = jax.random.normal(kx, (args.batch, h + m, w + m, args.cin), dtype)
    x_raw = x_pad[:, m // 2 : m // 2 + h, m // 2 : m // 2 + w, :]
    wk = (jax.random.normal(kw_, (k, k, args.cin, args.cout), dtype)
          / (k * k))

    def xla_valid(t):
        return jax.lax.conv_general_dilated(
            t, wk, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )

    def xla_same(t):
        return jax.lax.conv_general_dilated(
            t, wk, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )

    def pallas_fn(t):
        return halo_conv2d(
            t, wk, th=args.tile_h, tw=args.tile_w, interpret=args.interpret
        )

    variants = {
        "xla_valid": (jax.jit(xla_valid), x_pad),
        "pallas": (pallas_fn, x_pad),
        "xla_same": (jax.jit(xla_same), x_raw),
    }
    flops = conv_flops(args.batch, h, w, args.cin, args.cout, k, k)

    results = {}
    for name, (fn, arg) in variants.items():
        out = fn(arg)
        # D2H fetch of a scalar: cannot complete before the value exists.
        float(jnp.sum(out[..., 0].astype(jnp.float32)))
        for _ in range(args.warmup):
            out = fn(arg)
        float(jnp.sum(out[..., 0].astype(jnp.float32)))
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            out = fn(arg)
        float(jnp.sum(out[..., 0].astype(jnp.float32)))
        dt = (time.perf_counter() - t0) / args.iterations
        results[name] = {
            "ms": round(dt * 1e3, 4),
            "tflops": round(flops / dt / 1e12, 2),
        }

    # Correctness cross-check at benchmark shapes.
    a = np.asarray(variants["pallas"][0](x_pad), np.float32)
    b = np.asarray(variants["xla_valid"][0](x_pad), np.float32)
    ok = bool(np.allclose(a, b, rtol=0.05, atol=0.05))

    out = {
        "metric": "halo_valid_conv_ms",
        "value": results["pallas"]["ms"],
        "unit": "ms",
        "config": {
            "h": h, "w": w, "cin": args.cin, "cout": args.cout, "k": k,
            "batch": args.batch, "dtype": args.dtype,
            "tile": [args.tile_h, args.tile_w],
        },
        "variants": results,
        "pallas_speedup_vs_xla": round(
            results["xla_valid"]["ms"] / results["pallas"]["ms"], 3
        ),
        "flops_per_call": flops,
        "validation": "pass" if ok else "FAIL",
        "platform": jax.devices()[0].platform,
    }
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
