"""Hardware A/B of the Pallas-conv default in a D2-shaped END-TO-END step.

VERDICT r3 task 5: the kernel wins every op microbenchmark at D2 depths
(benchmark_pallas_conv.py), yet the same kernel measured 35% SLOWER in the
whole single-device SAME-conv program — XLA's conv+bias+BN+ReLU fusion died
at the pallas_call boundary.  The margin-consuming D2 path keeps it ON
based on op numbers only; this tool closes the gap with STEP-level timing.

Construction: the single-chip pad-once emulation of a fused margin-
consuming run (exactly what tests/test_d2.py uses for numerics) — the tile
carries the run's accumulated margin, and ``apply_layers_premargin`` drives
the SAME dispatch the distributed D2 path takes (SpatialCtx with
halo_pre_exchanged margins; bn_cross_tile=False so no collectives).  One
"step" = forward + grads + SGD update of a run of ``--fused`` relu-conv-bn
ops (the AmoebaNet op body, models/amoebanet.py _relu_conv_bn), timed with
a device-to-host scalar fetch.  A/B = SpatialCtx.use_pallas_conv.

Example (real chip):
  python benchmark_d2_step.py --tile 512 --channels 208 --fused 3
"""

from __future__ import annotations

import argparse
import dataclasses
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
    p.add_argument("--tile", type=int, default=512,
                   help="local tile extent (e.g. 512 = a 1024² image on a "
                        "2x2 grid)")
    p.add_argument("--channels", type=int, default=208)
    p.add_argument("--fused", type=int, default=3,
                   help="number of relu-conv-bn ops in the fused run")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iterations", type=int, default=20)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4dl_tpu.layer_ctx import ApplyCtx, SpatialCtx
    from mpi4dl_tpu.layers import BatchNorm, Conv2d, ReLU
    from mpi4dl_tpu.ops.d2 import accumulated_halo, apply_layers_premargin

    c, t, bs = args.channels, args.tile, args.batch
    layers = []
    for _ in range(args.fused):
        layers += [ReLU(), Conv2d(c, c, 3, bias=False), BatchNorm(c)]
    hh, hw = accumulated_halo(layers)

    key = jax.random.key(0)
    params = []
    shape = (bs, t, t, c)
    for i, l in enumerate(layers):
        pp, shape = l.init(jax.random.fold_in(key, i), shape)
        params.append(pp)

    # Margin-carrying tile (zero margin = a global-border tile of the
    # pad-once semantics — identical compute to any interior tile).
    x = jax.random.normal(
        jax.random.key(1), (bs, t + 2 * hh, t + 2 * hw, c), jnp.bfloat16
    )

    def make_step(use_pallas: bool):
        sp = SpatialCtx(
            axis_h="sph", axis_w="spw", grid_h=2, grid_w=2,
            bn_cross_tile=False, use_pallas_conv=use_pallas,
        )
        ctx = ApplyCtx(train=True, spatial=sp)

        def loss_fn(ps, x):
            y, mh, mw = apply_layers_premargin(layers, ps, x, ctx, hh, hw)
            assert mh == 0 and mw == 0, (mh, mw)
            return jnp.mean(jnp.square(y.astype(jnp.float32)))

        @jax.jit
        def step(ps, x):
            loss, grads = jax.value_and_grad(loss_fn)(ps, x)
            new = jax.tree.map(
                lambda p, g: (
                    p.astype(jnp.float32) - 0.001 * g.astype(jnp.float32)
                ).astype(p.dtype),
                ps, grads,
            )
            return new, loss

        return step

    def time_step(use_pallas: bool):
        # (the Pallas path auto-selects interpret mode on CPU hosts)
        step = make_step(use_pallas)
        ps = params
        t0 = time.perf_counter()
        for _ in range(args.warmup):
            ps, loss = step(ps, x)
        lval = float(loss)  # D2H sync
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            ps, loss = step(ps, x)
        lval = float(loss)
        dt = (time.perf_counter() - t0) / args.iterations
        return dt, lval, compile_s

    dt_off, loss_off, c_off = time_step(False)
    dt_on, loss_on, c_on = time_step(True)
    rel = abs(loss_on - loss_off) / max(abs(loss_off), 1e-9)
    out = {
        "metric": "d2_step_pallas_speedup",
        "value": round(dt_off / dt_on, 4),
        "unit": "x (xla_step_ms / pallas_step_ms)",
        "config": {
            "tile": t, "channels": c, "fused_convs": args.fused,
            "batch": bs, "margin": [hh, hw],
        },
        "xla_step_ms": round(dt_off * 1e3, 3),
        "pallas_step_ms": round(dt_on * 1e3, 3),
        "validation": "pass" if rel < 0.05 else f"FAIL rel={rel:.3g}",
        "platform": jax.devices()[0].platform,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
