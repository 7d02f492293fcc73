"""Apply-time context threading for layers.

The reference framework bakes spatial-parallel behaviour into *model classes*
(``conv_spatial`` vs ``nn.Conv2d`` chosen at construction,
reference ``src/models/amoebanet.py:79-399``).  Here the *same* model code runs
either replicated or spatially sharded: layers consult an :class:`ApplyCtx` at
apply time.  When ``ctx.spatial`` is set (we are inside ``shard_map`` with the
image H/W sharded over mesh axes), convs/pools perform halo exchange; when it
is ``None`` they are plain ops.  This is what makes shape inference trivial
(run the model un-sharded under ``jax.eval_shape`` on the global shape) and
lets one model definition serve the sequential / spatial / D2 variants the
reference implements three times over.
"""

from __future__ import annotations

import dataclasses
from typing import Optional
from mpi4dl_tpu.mesh import AXIS_SPH, AXIS_SPW


@dataclasses.dataclass(frozen=True)
class SpatialCtx:
    """Describes how the image dims are sharded inside the current shard_map.

    ``axis_h``/``axis_w`` are mesh-axis names sharding H and W, or ``None``
    when that dim is unsharded.  Grid sizes are static ints.  The reference's
    slice methods (``train_spatial.py:241-290``) map as:

    - ``horizontal``: axis_h='sp', axis_w=None (H-strips)
    - ``vertical``:   axis_h=None, axis_w='sp' (W-strips)
    - ``square``:     axis_h='sph', axis_w='spw' (2-D tile grid)
    """

    axis_h: Optional[str] = None
    axis_w: Optional[str] = None
    grid_h: int = 1
    grid_w: int = 1
    # Replication factor per axis: the mesh axis has grid*rep devices and each
    # tile is held by `rep` consecutive devices (tile index = axis_index//rep).
    # rep > 1 arises at the COARSER levels of multi-level spatial parallelism
    # (reference num_spatial_parts="4,2", train_spatial.py:453-504): the level
    # runs on fewer tiles than the mesh axis carries, and the freed devices
    # either duplicate tile compute or take batch shards at the junction.
    # Halo exchange with rep>1 ppermutes with stride `rep` (ops/halo.py).
    rep_h: int = 1
    rep_w: int = 1
    # BatchNorm statistics scope: True → psum batch stats across the tile grid
    # (numerically equals single-device training); False → per-tile stats, the
    # reference's behaviour (plain nn.BatchNorm2d inside spatial layers,
    # reference resnet_spatial.py:149-163).
    bn_cross_tile: bool = True
    # When True, maximal conv runs fuse their halo exchanges: ONE accumulated
    # exchange at run start, convs run VALID on the sharded dims and consume
    # the margin (the reference's "Design-2", resnet_spatial_d2.py:651-697 /
    # amoebanet_d2.py — there implemented as separate model classes; here an
    # apply-time mode).  See ops/d2.py.
    d2_mode: bool = False
    # Internal: set by the D2 driver for the layers *inside* a fused run —
    # the margin is already present, so convs skip their own exchange and run
    # VALID on the sharded dims.
    halo_pre_exchanged: bool = False
    # Internal: the CURRENT margin (per sharded dim) carried by the activation
    # inside a fused run — set per layer by the D2 drivers.  BatchNorm uses it
    # to exclude the not-yet-consumed margin rows from its statistics (they
    # duplicate neighbour rows / hold boundary zeros); pools to know their
    # input is already extended.
    pre_margin_h: int = 0
    pre_margin_w: int = 0
    # Cap on margin-consuming (padded) layers per fused run — the reference's
    # --fused-layers knob (resnet_spatial_d2.py get_balance); None = fuse
    # maximal runs (better: fewer exchanges).
    d2_max_fused: Optional[int] = None
    # The axes of this ctx are a SINGLE-DEVICE fiction (the H-striped
    # layer-run executor, ops/hstripe_conv.hstripe_layer_run): no mesh axis
    # exists, so BN statistic deposits must stay local — no pmean over the
    # tile axes (the caller averages per-stripe updates itself).
    stat_local: bool = False

    @property
    def active(self) -> bool:
        return (self.axis_h is not None and self.grid_h > 1) or (
            self.axis_w is not None and self.grid_w > 1
        )


@dataclasses.dataclass(frozen=True)
class ApplyCtx:
    """Context passed to every layer apply().

    ``train``:     batch-stat BN + (future) dropout.
    ``spatial``:   spatial sharding description or None.
    ``data_axis``: mesh axis name for data parallelism (used only by layers
                   that want cross-replica stats; grads are psum'd outside).
    ``bn_sink``:   when set (a plain dict, fresh per trace), BatchNorm layers
                   deposit their UPDATED running statistics into it keyed by
                   ``id()`` of the corresponding parameter leaf (the tracer
                   object read from their params dict).  Step builders collect
                   the sink into a leaf-aligned update list and write it back
                   into the post-optimizer params — the JAX-functional form of
                   torch BatchNorm2d's in-place running-buffer update
                   (reference models use plain nn.BatchNorm2d,
                   resnet_spatial.py:149-163).  Any layer's running
                   statistic travels the same way: the routed expert layer's
                   ``load`` (ops/moe.py) does.
    """

    train: bool = True
    spatial: Optional[SpatialCtx] = None
    data_axis: Optional[str] = None
    bn_sink: Optional[dict] = None
    # Extra mesh axes the activations vary over beyond spatial/data — e.g. the
    # tile axes in the batch-split tail after an SP→LP junction (each former
    # tile device holds a different batch shard).  Stat deposits pmean over
    # these so written-back running stats stay replicated.
    bn_stat_axes: tuple = ()
    # Fine-grained rematerialization: additionally checkpoint each op inside
    # composite cells (AmoebaCell reduce/ops), bounding backward temps to one
    # op at a time — set by make_train_step(remat="fine"); the
    # max-trainable-resolution configuration (PERF_NOTES.md).
    remat_ops: bool = False
    # Internal: set by ``layers.apply_run`` for the layers INSIDE a folded
    # run: the activation is carried as ``[N, H, W/fold, fold·C]`` from one
    # W-folded convolution to the next (ops/wfold_conv.py), and BatchNorm,
    # the convolutions and their bias work on that form.  0: ``[N, H, W, C]``.
    fold: int = 0

    def with_spatial(self, spatial: Optional[SpatialCtx]) -> "ApplyCtx":
        return dataclasses.replace(self, spatial=spatial)


# Convenience singletons
EVAL_CTX = ApplyCtx(train=False)
TRAIN_CTX = ApplyCtx(train=True)


def spatial_ctx_for(slice_method: str, num_spatial_parts: int, **kw) -> SpatialCtx:
    """Build a SpatialCtx from the reference's (slice_method, num_spatial_parts)
    config vocabulary (reference parser.py:21-143)."""
    if slice_method == "vertical":
        return SpatialCtx(axis_w=AXIS_SPW, grid_w=num_spatial_parts, **kw)
    if slice_method == "horizontal":
        return SpatialCtx(axis_h=AXIS_SPH, grid_h=num_spatial_parts, **kw)
    if slice_method == "square":
        import math

        g = int(math.isqrt(num_spatial_parts))
        if g * g != num_spatial_parts:
            raise ValueError(
                f"square slicing needs a perfect-square part count, got {num_spatial_parts}"
            )
        return SpatialCtx(axis_h=AXIS_SPH, axis_w=AXIS_SPW, grid_h=g, grid_w=g, **kw)
    raise ValueError(f"unknown slice_method {slice_method!r}")


def _level_grid(parts: int, gh0: int, gw0: int) -> tuple:
    """Factor `parts` into a (gh, gw) sub-grid of the base (gh0, gw0) grid —
    gh | gh0 and gw | gw0 — preferring the most square factorization (ties go
    to the wider-W split: spw is the innermost, most bandwidth-local axis)."""
    best = None
    for d in range(1, parts + 1):
        if parts % d:
            continue
        e = parts // d
        if gh0 % d == 0 and gw0 % e == 0:
            score = abs(d - e)
            if best is None or score < best[0]:
                best = (score, d, e)
    if best is None:
        raise ValueError(
            f"spatial level of {parts} tiles does not embed in the base "
            f"{gh0}x{gw0} grid: need a factorization gh*gw={parts} with "
            f"gh | {gh0} and gw | {gw0}"
        )
    return best[1], best[2]


def spatial_levels_for(slice_method: str, parts_list, **kw) -> list:
    """Per-level SpatialCtx chain for multi-level spatial parallelism
    (reference ``num_spatial_parts="4,2"``: successive spatial pipeline splits
    run on shrinking tile grids, train_spatial.py:453-504, :557-641).

    Level 0 defines the mesh axes (rep=1).  Later levels keep the SAME axes
    but a coarser grid with replication factor rep = base_grid/level_grid;
    transitions between levels are a :func:`parallel.spatial.respatial`
    re-shard (one all_gather + slice, the TPU form of the reference's skewed
    spatial→spatial send/recv).
    """
    parts_list = list(parts_list)
    base = spatial_ctx_for(slice_method, parts_list[0], **kw)
    out = [base]
    gh0, gw0 = base.grid_h, base.grid_w
    for p in parts_list[1:]:
        if p > parts_list[0]:
            raise ValueError(
                f"spatial levels must not grow: {p} > {parts_list[0]}"
            )
        gh, gw = _level_grid(p, gh0, gw0)
        out.append(
            dataclasses.replace(
                base, grid_h=gh, grid_w=gw, rep_h=gh0 // gh, rep_w=gw0 // gw
            )
        )
    return out
