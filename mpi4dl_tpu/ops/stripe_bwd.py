"""Stripe-wise backward through spatial-region blocks.

The 8K flagship's O(parts) memory lives in the SPATIAL phase: every extra
micro-batch widens the per-device chunk that flows through the SP region,
and during the region's backward each block's recompute holds its full
working set — the r5-era measurement was ~19.5 GB/device per extra
pipeline part, capping the flagship at parts=2 and a 33% 1F1B bubble
(PERF_NOTES "8K readiness re-run"; re-measured at HEAD the su=17 slope
is 4.05 GB/part, and the trail is the parts=8 blocker at the deep su=22
placement: 120.1 GB plain vs 81.6 striped — PERF_NOTES "stripe-wise
backward").

This module is the buy-back.  A block's stride-1 bottleneck branch runs —
forward AND backward — one H-stripe at a time:

- the run's accumulated halo (``ops/d2.accumulated_halo``) is realized
  ONCE up front: a real :func:`halo_exchange_2d` pull on spatially sharded
  dims (zeros at the global border), a zero-pad on an unsharded H — the
  halo-D2 pad-once border semantics in both cases;
- the margined tile is then processed by a ``lax.map`` over H stripes
  whose body is wrapped in ``jax.checkpoint``: the scan's transpose
  re-executes each stripe's forward and transposes it in place, so the
  BACKWARD working set is one stripe's internals plus the input-cotangent
  accumulator — not the full-size intermediate trail the plain per-cell
  remat holds.  The margined input is a scan constant (saved once, never
  stacked), which is what makes the residual cost O(stripe) instead of
  O(H);
- the scan additionally *serializes* the stripe recomputes, denying XLA's
  scheduler the concurrent-recompute pile-up measured behind the
  ``MPI4DL_1F1B_CELL_REMAT`` pathology (docs/pipeline.md).

Semantics are exactly the H-striped layer-run's (ops/hstripe_conv.py),
generalized to active spatial sharding: pad-once borders (the reference's
own D2 trade) and per-stripe train-mode BatchNorm statistics, with
``MPI4DL_HSTRIPE_EXACT=1`` buying bit-parity global statistics via the
stripewise stat cascade — here extended with cross-tile psum over the real
mesh axes and W-margin exclusion.  Everything is opt-in behind
``MPI4DL_STRIPE_BWD=1`` (config.HATCHES); default-off engines are
bit-identical.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.layer_ctx import SpatialCtx
from mpi4dl_tpu.mesh import AXIS_SPH
from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.ops.halo import HaloSpec, halo_exchange_2d

# Per-stripe working-set budget: the stripe count is sized so one stripe's
# widest intermediate stays under this many bytes (whole chunk, all batch
# rows).  MPI4DL_STRIPE_BUDGET overrides for tuning; the engagement gate is
# simply "more than one stripe would be needed", so small programs never
# change shape.
_STRIPE_BUDGET_DEFAULT = 64 * 1024 * 1024


def stripe_bwd_mode() -> str:
    """The ``MPI4DL_STRIPE_BWD`` hatch (config.HATCHES), read at dispatch
    (trace) time so A/B scripts can toggle it between step builds:

    - ``"0"``/unset — off (default; engines bit-identical);
    - ``"1"`` — stripe SPATIALLY SHARDED blocks only (the SP region — the
      production mode).  Pipeline-tail cells are deliberately excluded:
      striped scans inside the 1F1B backward branches inflate the fused
      stage-dispatch conditional's buffer union catastrophically (measured
      76.7 vs 8.3 GB/device on the 2048² flagship proxy — the same
      conditional-union pathology MPI4DL_1F1B_CELL_REMAT documents on deep
      stages), while the SP region runs OUTSIDE the tick loop and takes
      the full win;
    - ``"all"`` — stripe every eligible block including unsharded/tail
      cells (exactness testing and single-device capacity experiments).
    """
    return os.environ.get("MPI4DL_STRIPE_BWD", "0")


def stripe_bwd_enabled() -> bool:
    return stripe_bwd_mode() in ("1", "all")


def _stripe_budget() -> int:
    try:
        v = int(os.environ.get("MPI4DL_STRIPE_BUDGET", "0"))
    except ValueError:
        v = 0
    return v if v > 0 else _STRIPE_BUDGET_DEFAULT


def _exact_stats() -> bool:
    """Shared with the single-device striped run: MPI4DL_HSTRIPE_EXACT=1
    replaces per-stripe train-mode BN statistics with GLOBAL ones (stripe
    cascade + cross-tile psum) — bit-parity with the unstriped pad-once
    run at ~one extra prefix forward per BatchNorm."""
    return os.environ.get("MPI4DL_HSTRIPE_EXACT") == "1"


def _run_halo(layers) -> Optional[Tuple[int, int]]:
    """(hh, hw) accumulated halo of a stride-1 premargin-capable run, or
    None when any layer is unsupported or strided (striping needs the
    stripe grid to align with the global conv grid, which stride-1 runs
    guarantee for any stripe height).  Trivial runs — nothing but
    elementwise/identity layers — are rejected: their backward holds no
    intermediate trail worth bounding, so striping them is pure scan
    overhead."""
    from mpi4dl_tpu.layers import BatchNorm, Conv2d, Pool2d
    from mpi4dl_tpu.ops.d2 import accumulated_halo, layer_d2_geometry

    acc = accumulated_halo(layers)
    if acc is None:
        return None
    for layer in layers:
        g = layer_d2_geometry(layer)
        if g[2] != 1 or g[3] != 1:
            return None
    if not any(isinstance(l, (Conv2d, BatchNorm, Pool2d)) for l in layers):
        return None
    return acc


def _widest_row_bytes(layers, x_shape, itemsize: int) -> int:
    """Bytes of ONE H row of the run's widest intermediate (whole chunk):
    the unit the stripe budget divides."""
    n, h, w, c = x_shape
    cmax = c
    for layer in layers:
        cmax = max(
            cmax,
            getattr(layer, "out_channels", 0),
            getattr(layer, "num_features", 0),
            getattr(layer, "lane_pad_out", 0),
            getattr(layer, "lane_pad", 0),
        )
    return n * w * cmax * itemsize


def _pick_stripes(h: int, row_bytes: int) -> Optional[Tuple[int, int]]:
    """(stripes, stripe_height) for a local true H extent, or None when the
    run should stay on the plain path: one stripe suffices, or ``h`` has no
    reasonable divisor (a ragged stripe is not an option — zero rows would
    enter per-stripe BN statistics, the same constraint as
    hstripe_layer_run)."""
    from mpi4dl_tpu.ops.hstripe_conv import _smallest_divisor_at_least

    want = max(1, -(-(h * row_bytes) // _stripe_budget()))
    if want <= 1:
        return None
    stripes = _smallest_divisor_at_least(h, want)
    if stripes == 1 or stripes == h or stripes > 4 * want:
        return None
    return stripes, h // stripes


def _sharded(sp: Optional[SpatialCtx]) -> Tuple[bool, bool]:
    sharded_h = bool(sp and sp.active and sp.axis_h and sp.grid_h > 1)
    sharded_w = bool(sp and sp.active and sp.axis_w and sp.grid_w > 1)
    return sharded_h, sharded_w


def _has_lane_pad(layers) -> bool:
    return any(
        getattr(l, "lane_pad", 0) or getattr(l, "lane_pad_in", 0)
        or getattr(l, "lane_pad_out", 0)
        for l in layers
    )


def _stripe_plan(layers, x_shape, ctx, itemsize: int):
    """THE dispatch gate, shared by :func:`stripe_run_eligible` and
    :func:`maybe_stripe_run`: hatch on, a plain 4-D activation, a stride-1
    premargin-capable run, not already inside a margin-carrying or striped
    context, halo no wider than the tile, and a stripe plan that actually
    shrinks the working set.  Returns ``(acc_halo, (stripes, stripe_h))``
    or None."""
    if not stripe_bwd_enabled():
        return None
    sp = ctx.spatial
    if sp is not None and (sp.halo_pre_exchanged or sp.stat_local):
        return None
    if stripe_bwd_mode() != "all" and not (sp is not None and sp.active):
        return None
    if len(x_shape) != 4:
        return None
    acc = _run_halo(layers)
    if acc is None:
        return None
    sharded_h, sharded_w = _sharded(sp)
    if sharded_h and acc[0] > x_shape[1]:
        return None  # halo wider than the tile: single-neighbour limit
    if sharded_w and acc[1] > x_shape[2]:
        return None
    plan = _pick_stripes(
        x_shape[1], _widest_row_bytes(layers, x_shape, itemsize)
    )
    if plan is None:
        return None
    return acc, plan


def stripe_run_eligible(layers, x_shape, ctx, itemsize: int = 4) -> bool:
    """Shape-only predicate over :func:`_stripe_plan` (no activation in
    hand, so the caller supplies ``itemsize``; the real dispatch uses the
    activation's own dtype)."""
    return _stripe_plan(layers, x_shape, ctx, itemsize) is not None


def maybe_stripe_run(layers, params_seq, x, ctx):
    """Dispatch helper: run ``layers`` stripe-wise when eligible, else
    return None so the caller takes its normal path."""
    got = _stripe_plan(layers, x.shape, ctx, x.dtype.itemsize)
    if got is None:
        return None
    acc, plan = got
    return stripe_layer_run(layers, params_seq, x, ctx, acc, plan)


def _margins_at(layers, upto: int, mh: int, mw: int) -> Tuple[int, int]:
    """Remaining (H, W) margin at the input of ``layers[upto]`` for a
    stride-1 run.  W margin only decays when one was realized (mw > 0 —
    i.e. W is spatially sharded); an unsharded W carries no margin and the
    layers pad W themselves."""
    from mpi4dl_tpu.ops.d2 import layer_d2_geometry

    for layer in layers[:upto]:
        ph, pw, _, _ = layer_d2_geometry(layer)
        mh -= ph
        if mw:
            mw -= pw
    return mh, mw


def _deposit_axes(ctx) -> Tuple[str, ...]:
    """Mesh axes a striped run's BN running-stat deposits must pmean over so
    the written-back values are provably replicated: the caller's extra stat
    axes, the REAL tile axes (per-stripe statistics vary per tile; under the
    exact cascade the psum'd stats make this pmean an identity), and the
    data axis — the same set BatchNorm._deposit_running would use."""
    names = list(ctx.bn_stat_axes)
    sp = ctx.spatial
    if sp is not None and sp.active:
        names += [a for a in (sp.axis_h, sp.axis_w) if a]
    if ctx.data_axis:
        names.append(ctx.data_axis)
    return tuple(names)


def stripe_layer_run(layers, params_seq, x, ctx, acc=None, plan=None):
    """Run a stride-1 layer sequence stripe-by-stripe over H with a
    stripe-bounded backward.

    x: [N, H, W, C] — the LOCAL tile under spatial sharding (any of
    unsharded / H / W / HxW grids), unpadded.  The run's accumulated halo is
    realized once (exchange on sharded dims, zero-pad on an unsharded H),
    then ``lax.map`` over H stripes of a ``jax.checkpoint``-wrapped body
    computes the output; each stripe consumes the margin via
    :func:`mpi4dl_tpu.ops.d2.apply_layers_premargin`.  AD through the scan
    gives the stripe-wise backward: per stripe, re-execute + transpose.

    Train-mode BN uses per-stripe statistics (margins excluded), or GLOBAL
    statistics under ``MPI4DL_HSTRIPE_EXACT=1`` via one stripewise stat
    cascade per BN (cross-tile psum'd when the ctx says bn_cross_tile).
    Running-stat deposits are stripe-averaged and pmean'd over the real
    mesh axes before reaching the caller's sink."""
    from mpi4dl_tpu.layers import BatchNorm as _BN
    from mpi4dl_tpu.ops.d2 import apply_layers_premargin

    sp = ctx.spatial
    sharded_h, sharded_w = _sharded(sp)
    if acc is None:
        acc = _run_halo(layers)
    assert acc is not None, "stripe_layer_run on an unsupported run"
    mh = acc[0]
    mw = acc[1] if sharded_w else 0
    n, h, w, c = x.shape
    if plan is None:
        plan = _pick_stripes(
            h, _widest_row_bytes(layers, x.shape, x.dtype.itemsize)
        )
    if plan is None:
        return None
    stripes, sh = plan

    # --- margin realization (pad-once, the halo-D2 border semantics) -----
    # Every scope here is prefixed ``stripe_bwd``: turning the hatch on must
    # drift compiled-artifact contracts ONLY in stripe_bwd scopes
    # (tests/test_stripe_bwd.py asserts the locality).
    with scope("stripe_bwd_halo"):
        if sharded_h or sharded_w:
            xp = halo_exchange_2d(
                x,
                HaloSpec.symmetric(mh if sharded_h else 0),
                HaloSpec.symmetric(mw),
                sp.axis_h, sp.axis_w, sp.grid_h, sp.grid_w,
                rep_h=sp.rep_h, rep_w=sp.rep_w,
            )
            if not sharded_h and mh:
                xp = jnp.pad(xp, ((0, 0), (mh, mh), (0, 0), (0, 0)))
        elif mh:
            xp = jnp.pad(x, ((0, 0), (mh, mh), (0, 0), (0, 0)))
        else:
            xp = x

    # --- inner context: margins pre-realized, H consumed stripe-wise -----
    # The H "axis" exists only for margin-consuming geometry: when H is not
    # really sharded a fake axis name stands in (no collective ever fires
    # on it — exchanges are pre-consumed, BN runs bn_cross_tile=False with
    # local deposits; statistics are handled below over the REAL axes).
    base_sp = sp if sp is not None else SpatialCtx()
    inner_sp = dataclasses.replace(
        base_sp,
        axis_h=base_sp.axis_h if sharded_h else AXIS_SPH,
        grid_h=base_sp.grid_h if sharded_h else max(stripes, 2),
        rep_h=base_sp.rep_h if sharded_h else 1,
        bn_cross_tile=False,
        stat_local=True,
        d2_mode=False,
    )
    # data_axis/bn_stat_axes feed ONLY the running-stat deposit pmean
    # (BatchNorm._deposit_running; normalization statistics never read
    # them) — cleared here so per-stripe deposits inside the serialized
    # scan fire no collectives; the stripe-averaged deposit is pmean'd
    # over the full axis set once, below.
    inner_ctx = dataclasses.replace(
        ctx, spatial=inner_sp, bn_sink=None, remat_ops=False,
        data_axis=None, bn_stat_axes=(),
    )
    idx = jnp.arange(stripes, dtype=jnp.int32)

    # --- exact-stats cascade: fix every train-mode BN to GLOBAL stats ----
    eff_layers = list(layers)
    exact = _exact_stats() and ctx.train and not _has_lane_pad(layers)
    if exact:
        acc_dt = jnp.promote_types(jnp.float32, x.dtype)
        real_axes = (
            tuple(a for a in (sp.axis_h, sp.axis_w) if a)
            if (sp is not None and sp.active and sp.bn_cross_tile)
            else ()
        )
        for j, layer in enumerate(layers):
            if not isinstance(layer, _BN):
                continue
            if j == 0:
                s = jnp.sum(x, axis=(0, 1, 2), dtype=acc_dt)
                ss = jnp.sum(jnp.square(x.astype(acc_dt)), axis=(0, 1, 2))
            else:
                mh_j, mw_j = _margins_at(eff_layers, j, mh, mw)

                def stat_piece(i, xbuf, ps, _j=j, _mh=mh_j, _mw=mw_j):
                    xs = lax.dynamic_slice_in_dim(
                        xbuf, i * sh, sh + 2 * mh, axis=1
                    )
                    y, mho, mwo = apply_layers_premargin(
                        eff_layers[:_j], ps[:_j], xs, inner_ctx, mh, mw
                    )
                    assert (mho, mwo) == (_mh, _mw), ((mho, mwo), (_mh, _mw))
                    t = y[:, _mh:_mh + sh, _mw:y.shape[2] - _mw or None]
                    return (
                        jnp.sum(t, axis=(0, 1, 2), dtype=acc_dt),
                        jnp.sum(jnp.square(t.astype(acc_dt)), axis=(0, 1, 2)),
                    )

                ck = jax.checkpoint(stat_piece)
                with scope("stripe_bwd_stats"):
                    sA, ssA = lax.map(lambda i: ck(i, xp, params_seq), idx)
                s, ss = jnp.sum(sA, axis=0), jnp.sum(ssA, axis=0)
            cnt = jnp.asarray(n * h * w, acc_dt)
            if real_axes:
                with scope("stripe_bwd_stats"):
                    # Count is a trace-time constant: static multiply, not a
                    # wire psum (psum(1, axes) folds to the axis-size
                    # product).
                    cnt = cnt * lax.psum(1, real_axes)
                    s = lax.psum(s, real_axes)
                    ss = lax.psum(ss, real_axes)
            mean = s / cnt
            var = jnp.maximum(ss / cnt - mean * mean, 0.0)
            from mpi4dl_tpu.ops.hstripe_conv import _FixedStatsBN

            eff_layers[j] = _FixedStatsBN(layer, mean, var, cnt)

    # --- output pass: checkpointed stripes under a serializing scan ------
    with_sink = ctx.bn_sink is not None

    def piece(i, xbuf, ps):
        xs = lax.dynamic_slice_in_dim(xbuf, i * sh, sh + 2 * mh, axis=1)
        if with_sink:
            inner: dict = {}
            cc = dataclasses.replace(inner_ctx, bn_sink=inner)
        else:
            inner, cc = None, inner_ctx
        y, mho, mwo = apply_layers_premargin(eff_layers, ps, xs, cc, mh, mw)
        assert mho == 0 and mwo == 0, (mho, mwo)
        # Reassembly below assumes W is preserved (stride-1 run).
        assert y.shape[1] == sh and y.shape[2] == w, (y.shape, sh, w)
        stats = (
            [inner.get(id(l)) for l in jax.tree.leaves(ps)]
            if inner is not None else []
        )
        return y, stats

    ck_piece = jax.checkpoint(piece)
    with scope("stripe_bwd_scan"):
        ys, stats = lax.map(lambda i: ck_piece(i, xp, params_seq), idx)
    if with_sink:
        names = _deposit_axes(ctx)
        for leaf, sarr in zip(jax.tree.leaves(params_seq), stats):
            if sarr is not None:
                v = jnp.mean(sarr, axis=0)
                if names:
                    with scope("stripe_bwd_stats"):
                        v = lax.pmean(v, names)
                ctx.bn_sink[id(leaf)] = v
    oc = ys.shape[-1]
    return ys.transpose(1, 0, 2, 3, 4).reshape(n, h, w, oc)
