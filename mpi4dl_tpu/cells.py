"""Cells: the unit of layer-parallel splitting.

The reference splits a top-level ``nn.Sequential`` of coarse "cells" by index
range (``src/torchgems/mp_pipeline.py:41-83``) and discovers inter-split
shapes by a two-phase dummy forward (``:126-168``).  Here a model *is* a list
of :class:`Cell` objects; shapes come from ``jax.eval_shape`` over the global
(unsharded) shapes — no probe forward, no `image_size_seq` rescaling
(reference benchmark_amoebanet_sp.py:120-125 exists only because probing at
full resolution OOMs; eval_shape is abstract so it cannot).

A cell's activation may be a single array or a tuple of arrays — AmoebaNet
cells carry ``(x, skip)`` tuple state (reference amoebanet.py:500-532,
the reason the reference pipeline supports MULTIPLE_INPUT/OUTPUT).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from mpi4dl_tpu.layer_ctx import ApplyCtx, EVAL_CTX
from mpi4dl_tpu.layers import Layer, stream_fold
from mpi4dl_tpu.obs.scopes import scope

Act = Union[jax.Array, Tuple[jax.Array, ...]]
ShapeLike = Union[Tuple[int, ...], Tuple[Tuple[int, ...], ...]]


class Cell:
    """One pipeline-splittable unit: init/apply plus a human name."""

    name: str = "cell"

    def init(self, key, in_shape: ShapeLike):
        raise NotImplementedError

    def apply(self, params, x: Act, ctx: ApplyCtx) -> Act:
        raise NotImplementedError


@dataclasses.dataclass
class LayerCell(Cell):
    """A cell made of a plain sequence of layers (single-tensor state)."""

    layers: Sequence[Layer]
    name: str = "seq"

    def init(self, key, in_shape):
        keys = jax.random.split(key, max(len(self.layers), 1))
        params = []
        shape = in_shape
        for k, layer in zip(keys, self.layers):
            p, shape = layer.init(k, shape)
            params.append(p)
        return params, shape

    def apply(self, params, x, ctx):
        from mpi4dl_tpu.ops.d2 import maybe_run_d2
        from mpi4dl_tpu.ops.stripe_bwd import maybe_stripe_run

        y = maybe_run_d2(self.layers, params, x, ctx)
        if y is not None:
            return y
        # Stripe-wise execution (MPI4DL_STRIPE_BWD=1): the whole cell runs —
        # forward and backward — one H-stripe at a time under pad-once
        # margins (ops/stripe_bwd.py; the flagship's O(parts) buy-back).
        y = maybe_stripe_run(self.layers, params, x, ctx)
        if y is not None:
            return y
        for p, layer in zip(params, self.layers):
            x = layer.apply(p, x, ctx)
        return x


@dataclasses.dataclass
class FnCell(Cell):
    """A cell defined by explicit init/apply callables (for residual blocks,
    NAS cells, heads...)."""

    init_fn: Callable[[Any, ShapeLike], Tuple[Any, ShapeLike]]
    apply_fn: Callable[[Any, Act, ApplyCtx], Act]
    name: str = "fn"

    def init(self, key, in_shape):
        return self.init_fn(key, in_shape)

    def apply(self, params, x, ctx):
        return self.apply_fn(params, x, ctx)


@dataclasses.dataclass
class CellModel:
    """A model: ordered cells + metadata.

    ``spatial_until``: number of leading cells that run under spatial sharding
    (the analog of the reference's `spatial_size` splits running conv_spatial;
    the junction gather happens after cell index spatial_until-1).
    """

    cells: List[Cell]
    in_shape: Tuple[int, ...]
    num_classes: int
    spatial_until: int = 0
    name: str = "model"
    # ``step_metrics(params, tokens) -> {name: scalar}``: what a step counted
    # besides its loss, from statistics the layers left in the parameters (a
    # routed model's expert load); the one-chip step returns it as
    # ``metrics["counted"]`` and the loop writes it on the ``step`` span.
    step_metrics: Optional[Callable[[Any, int], dict]] = None
    # ``(owner, reader, name)``: the leaf or subtree ``name`` of cell
    # ``owner``'s parameters is read by cell ``reader`` too, under the same
    # name (a head that is the embedding's table; a layer applied again on
    # its own weights, a tie of each of its top-level names).  A name may
    # have several readers.  ``init`` and the train state hold it once, with
    # the owner; ``apply`` hands each reader its own parameters with the tied
    # names beside them, so the gradient is the sum over every use and the
    # update is one.  Of the engines that pack each stage's parameters into a
    # row of its own, the GPipe schedule keeps a copy in each reader's row and
    # sums all uses' gradients (``parallel/pipeline.sum_tied_grads``); the
    # others refuse such a model (:meth:`refuse_tied`).
    tied: Tuple[Tuple[int, int, str], ...] = ()

    def cell_params(self, params_list, i: int):
        """What cell ``i`` is applied to: its own parameters, and the tied
        leaves it reads from their owners'."""
        read = {name: params_list[owner][name]
                for owner, reader, name in self.tied if reader == i}
        return {**params_list[i], **read} if read else params_list[i]

    def per_cell(self, params_list) -> List[Any]:
        """``params_list`` as the cells are applied to it, one entry a cell
        (a tied leaf appears with its owner and with its reader, the same
        array): what a cell-by-cell walk outside :meth:`apply` indexes."""
        return [self.cell_params(params_list, i) for i in range(len(self.cells))]

    def refuse_tied(self, engine: str) -> None:
        """The error of an engine that gives each stage a parameter row of
        its own and does not sum a tied leaf's gradients over the stage
        axis, for a model with a leaf that several cells read."""
        if not self.tied:
            return
        owner, reader, name = self.tied[0]
        raise ValueError(
            f"{self.name}: cell {reader} ({self.cells[reader].name}) reads "
            f"the leaf {name!r} of cell {owner} ({self.cells[owner].name}), "
            f"and {engine} keeps each stage's parameters in a row of its "
            "own: its uses' gradients have to be summed over the stage "
            "axis before the update, which only the lp family's GPipe "
            "schedule does (ROADMAP R6); run it there or on one chip")

    def init(self, key) -> Tuple[List[Any], List[ShapeLike]]:
        """Init all cells; returns (params_list, shape_list) where
        shape_list[i] is the *output* shape of cell i (global shapes).
        shape_list mirrors the reference's get_output_shapes result
        (mp_pipeline.py:126-168)."""
        keys = jax.random.split(key, len(self.cells))
        params_list, shapes = [], []
        shape: ShapeLike = self.in_shape
        for k, cell in zip(keys, self.cells):
            p, shape = cell.init(k, shape)
            params_list.append(p)
            shapes.append(shape)
        return params_list, shapes

    def apply(self, params_list, x: Act, ctx: ApplyCtx, *,
              start: int = 0, stop: Optional[int] = None,
              remat=False) -> Act:
        """Run cells [start, stop) — the per-stage sub-model.

        ``remat=True`` wraps each cell in :func:`jax.checkpoint` so backward
        recomputes activations per cell instead of storing them — the memory
        lever that lets high-resolution configs (the reference's 1024²-2048²
        charts, BASELINE.md) fit on a single chip.

        ``remat="sqrt"`` adds a second checkpoint level: cells run in ~√n
        groups, the OUTER checkpoint saves only group-boundary activations
        and the inner per-cell checkpoints exist transiently during one
        group's backward — O(√n) live boundaries instead of O(n), the
        classic two-level recursive schedule (deep ResNets hold 55 block
        boundaries at high resolution; this is what lets them fit).
        """
        stop = len(self.cells) if stop is None else stop
        if remat == "sqrt" and stop - start > 3:
            import math as _m
            import os as _os

            n = stop - start
            # Group count: ~sqrt(n) balances outer boundaries against live
            # inner boundaries; MPI4DL_SQRT_GROUPS overrides for memory
            # tuning (bigger = smaller groups = fewer inner boundaries live
            # during one group's backward).
            g = int(_os.environ.get("MPI4DL_SQRT_GROUPS", "0")) or max(
                2, _m.isqrt(n)
            )
            meta = None
            for lo, hi in split_even(n, min(n, g)):
                grp = tuple(range(start + lo, start + hi))

                def grp_fn(ps, x, c, _grp=grp):
                    m = None
                    for k, i in enumerate(_grp):
                        with scope(f"cell{i:02d}"):
                            x, m = checkpointed_apply(
                                self.cells[i].apply, ps[k], x, c,
                                in_meta=m, pack=True,
                            )
                    return _unpack_act(x, m)

                x, meta = checkpointed_apply(
                    grp_fn, [self.cell_params(params_list, i) for i in grp],
                    x, ctx,
                    in_meta=meta, pack=True,
                )
            return _unpack_act(x, meta)
        meta = None
        for i in range(start, stop):
            with scope(f"cell{i:02d}"):
                if remat:
                    x, meta = checkpointed_apply(
                        self.cells[i].apply, self.cell_params(params_list, i),
                        x, ctx, in_meta=meta, pack=True,
                    )
                else:
                    x = self.cells[i].apply(
                        self.cell_params(params_list, i), x, ctx)
        return _unpack_act(x, meta) if remat else x

    def out_shapes(self, params_list) -> List[ShapeLike]:
        """Abstract shape inference via eval_shape (no FLOPs, no memory)."""
        shapes: List[ShapeLike] = []
        x = jax.ShapeDtypeStruct(self.in_shape, jnp.float32)
        for cell, p in zip(self.cells, self.per_cell(params_list)):
            x = jax.eval_shape(lambda p, x, c=cell: c.apply(p, x, EVAL_CTX), p, x)
            shapes.append(
                tuple(t.shape for t in x) if isinstance(x, tuple) else x.shape
            )
        return shapes


# ---------------------------------------------------------------------------
# Boundary lane-packing: large checkpoint residuals stored exactly-128-lane.
#
# A [1, 2048, 2048, 64] bf16 boundary costs 1 GB on TPU — 2x its real size —
# because any channels-minor layout pads C=64 to the 128-lane tile (and XLA's
# backward temps for such shapes showed up in T(2,128) layouts padded 4-16x,
# the measured ResNet-110 2048² OOM driver after conv temps were fixed,
# PERF_NOTES r4).  Re-splitting the flattened (W, C) trailing dims as
# (W*C/128, 128) makes every saved residual (and its cotangent) an
# exactly-128-lane tensor with no padding at all — and a shape whose natural
# layout XLA stores densely packed (the r4 AmoebaNet frontier's binding mass,
# [1,416,416,1664] bf16, measured ~2x its 553 MB logical size: an unpacked
# narrow-tile layout this reshape makes impossible).  The pack/unpack
# reshapes live INSIDE the checkpoint, so only the packed form is ever
# stored.  Gated to large boundaries (and C not already exactly 128):
# W*C a multiple of 128 takes the W-fold form [N,H,W*C/128,128]; otherwise
# (margined SP tiles) H*W*C a multiple of 128 takes the full-flatten form
# [N,H*W*C/128,128]; packs nothing else — zero graph change.
#
# Inside the W-fold's gate the W-fold form has the lanes of the narrow
# stage's folded runs (layers.stream_fold: [N,H,W/8,512] for ResNet-110 v2's
# 64-channel boundaries at 1024², as today for its 16-channel one): a block
# that runs folded then unpacks and packs by no relayout at all.  Measured on
# the chip against 128 lanes there (PERF.md, PR 30): 13 ms of a 427 ms step
# for 0.78 GiB.
# ---------------------------------------------------------------------------

_PACK_MIN_ELEMS = 1 << 24  # 16.7M elements = 32 MB bf16 per saved boundary


def _pack_meta(shape):
    """(w, c) for the W-fold form [N,H,W*C/lanes,lanes], or (h, w, c) for the
    full-flatten form [N,H*W*C/128,128] (margined SP tiles, whose halo
    rows/cols break the per-row divisibility), or None (no packing)."""
    import os

    if os.environ.get("MPI4DL_NO_PACK") == "1" or len(shape) != 4:
        return None
    n, h, w, c = shape
    if c == 128 or h * w * c < _PACK_MIN_ELEMS:
        return None
    if (w * c) % 128 == 0:
        return (w, c)
    if (h * w * c) % 128 == 0:
        return (h, w, c)
    return None


def _pack_lanes(shape) -> int:
    """Lanes of the W-fold form of ``shape``: 128, or the p·C of the narrow
    stage's folded runs where ``shape`` is theirs."""
    lanes = stream_fold(shape) * shape[3]
    return lanes if lanes and lanes % 128 == 0 else 128


def _pack_one(x):
    m = _pack_meta(getattr(x, "shape", ()))
    if m is None:
        return x, None
    n, h, w, c = x.shape
    if len(m) == 2:
        lanes = _pack_lanes(x.shape)
        return x.reshape(n, h, (w * c) // lanes, lanes), m
    return x.reshape(n, (h * w * c) // 128, 128), m


def _unpack_one(x, m):
    if m is None:
        return x
    n = x.shape[0]
    if len(m) == 2:
        w, c = m
        return x.reshape(n, x.shape[1], w, c)
    h, w, c = m
    return x.reshape(n, h, w, c)


def _pack_act(y: Act):
    if isinstance(y, tuple):
        pairs = [_pack_one(t) for t in y]
        return tuple(p[0] for p in pairs), tuple(p[1] for p in pairs)
    return _pack_one(y)


def _unpack_act(y: Act, meta) -> Act:
    if meta is None:
        return y
    if isinstance(y, tuple):
        return tuple(_unpack_one(t, m) for t, m in zip(y, meta))
    return _unpack_one(y, meta)


def checkpointed_apply(apply_fn, params, x: Act, ctx: ApplyCtx,
                       in_meta=None, pack: bool = False):
    """Run ``apply_fn(params, x, ctx)`` under jax.checkpoint.

    When a BN stats sink is active it must cross the checkpoint boundary
    explicitly: the sink captures tracers of the INNER (rematerialized) trace,
    which would escape if consumed outside.  The checkpointed fn therefore
    returns the stat updates aligned to the flattened param leaves, and they
    are re-deposited into the outer sink under the OUTER leaves' ids.

    ``pack=True`` threads boundary channel-packing through the checkpoint:
    ``x`` arrives in the packed form described by ``in_meta`` (unpacked
    INSIDE the checkpointed fn) and the returned value is ``(y_packed,
    out_meta)``.  The metas are static Python data captured at trace time.

    Serves the per-cell remat (model.apply remat=True) and the finer per-op
    remat inside AmoebaNet cells (ctx.remat_ops — the 'fine' level that
    bounds backward temps to one op's internals at a time; the
    max-trainable-resolution lever, PERF_NOTES.md)."""
    import dataclasses as _dc

    out_meta = [None]

    def body(p, x, c):
        y = apply_fn(p, _unpack_act(x, in_meta) if pack else x, c)
        if pack:
            y, out_meta[0] = _pack_act(y)
        return y

    if ctx.bn_sink is None:
        y = jax.checkpoint(lambda p, x: body(p, x, ctx))(params, x)
        return (y, out_meta[0]) if pack else y

    def fn(p, x):
        inner: dict = {}
        y = body(p, x, _dc.replace(ctx, bn_sink=inner))
        stats = [inner.get(id(leaf)) for leaf in jax.tree.leaves(p)]
        return y, stats

    y, stats = jax.checkpoint(fn)(params, x)
    for leaf, s in zip(jax.tree.leaves(params), stats):
        if s is not None:
            ctx.bn_sink[id(leaf)] = s
    return (y, out_meta[0]) if pack else y


def split_even(n_cells: int, split_size: int, balance: Optional[Sequence[int]] = None
               ) -> List[Tuple[int, int]]:
    """Partition cell indices into `split_size` contiguous ranges.

    Even split puts the remainder on the earliest stages, matching the
    reference's get_start_end_layer_index (mp_pipeline.py:41-69); an explicit
    `balance` list of per-stage cell counts overrides (must sum to n_cells,
    reference asserts mp_pipeline.py:55-58).
    """
    if balance is not None:
        assert sum(balance) == n_cells, (balance, n_cells)
        out, start = [], 0
        for b in balance:
            out.append((start, start + b))
            start += b
        return out
    base = n_cells // split_size
    rem = n_cells % split_size
    out, start = [], 0
    for s in range(split_size):
        size = base + (1 if s < rem else 0)
        out.append((start, start + size))
        start += size
    return out
