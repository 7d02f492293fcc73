"""ResNet v1 (6n+2) and v2 bottleneck (9n+2) as cell lists.

Same topology as the reference builders (``src/models/resnet.py:145-178``
v1, ``:270-323`` v2): a flat sequence of coarse cells — the unit the layer
splitter partitions — ending in an avg-pool + FC head.  One definition serves
sequential and spatial execution (the reference maintains three copies:
resnet.py / resnet_spatial.py / resnet_spatial_d2.py); spatial behaviour is
chosen by the ApplyCtx at apply time.

Head deviation (flagged): the reference applies ``F.softmax`` inside the model
*and* later CrossEntropyLoss — a double-softmax quirk (reference resnet.py:140,
mp_pipeline.py:226).  Default here is logits out / softmax-cross-entropy in the
loss; set ``softmax_in_model=True`` for bit-parity behaviour.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from mpi4dl_tpu.cells import (
    Cell, CellModel, LayerCell, _unpack_act, checkpointed_apply,
)
from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.layers import (
    BatchNorm,
    Conv2d,
    Dense,
    Flatten,
    Layer,
    Pool2d,
    ReLU,
    Softmax,
    apply_run,
    run_fold,
    stream_fold,
)
from mpi4dl_tpu.ops.wfold_conv import fold, unfold


def _resnet_layer(
    in_f: int,
    out_f: int,
    kernel: int = 3,
    stride: int = 1,
    activation: bool = True,
    batch_norm: bool = True,
    conv_first: bool = True,
) -> List[Layer]:
    """conv-bn-act (conv_first) or bn-act-conv (pre-activation), the
    reference's resnet_layer building block (resnet.py:24-77)."""
    conv = Conv2d(in_f, out_f, kernel_size=kernel, stride=stride)
    if conv_first:
        seq: List[Layer] = [conv]
        if batch_norm:
            seq.append(BatchNorm(out_f))
        if activation:
            seq.append(ReLU())
    else:
        seq = []
        if batch_norm:
            seq.append(BatchNorm(in_f))
        if activation:
            seq.append(ReLU())
        seq.append(conv)
    return seq


def _apply_branch(sub_cells, sub_params, x, ctx: ApplyCtx):
    """Run a residual branch's sub-layer-cells in order.

    Under ``ctx.remat_ops`` (remat='fine', or MPI4DL_REMAT_OPS=1 combined
    with any outer level) each sub-cell runs in its own jax.checkpoint with
    boundary lane-packing: one cell-level remat re-executes the WHOLE
    branch, so during a deep group's backward every recomputed BN-stat
    input of every branch stays live at once (measured as the ~20 x 256 MB
    stage-2 temp pile behind the ResNet-110 2048² OOM, r5 bench log);
    per-op checkpoints bound that to one sub-cell's temps plus packed
    boundaries."""
    if not ctx.remat_ops:
        for cell, p in zip(sub_cells, sub_params):
            x = cell.apply(p, x, ctx)
        return x
    meta = None
    for cell, p in zip(sub_cells, sub_params):
        x, meta = checkpointed_apply(
            cell.apply, p, x, ctx, in_meta=meta, pack=True
        )
    return _unpack_act(x, meta)


@dataclasses.dataclass
class ResBlockV1(Cell):
    """v1 basic residual cell (reference make_cell_v1, resnet.py:81-113)."""

    in_f: int
    out_f: int
    stride: int
    shortcut_conv: bool
    name: str = "res_v1"

    def __post_init__(self):
        self.r1 = LayerCell(_resnet_layer(self.in_f, self.out_f, stride=self.stride))
        self.r2 = LayerCell(_resnet_layer(self.out_f, self.out_f, activation=False))
        self.r3 = (
            LayerCell(
                _resnet_layer(
                    self.in_f, self.out_f, kernel=1, stride=self.stride,
                    activation=False, batch_norm=False,
                )
            )
            if self.shortcut_conv
            else None
        )

    def init(self, key, in_shape):
        k1, k2, k3 = jax.random.split(key, 3)
        p1, s = self.r1.init(k1, in_shape)
        p2, s = self.r2.init(k2, s)
        params = {"r1": p1, "r2": p2}
        if self.r3 is not None:
            p3, _ = self.r3.init(k3, in_shape)
            params["r3"] = p3
        return params, s

    def apply(self, params, x, ctx: ApplyCtx):
        from mpi4dl_tpu.ops.d2 import maybe_run_d2

        # D2: fuse the main path's two convs into one halo exchange; the
        # shortcut taps the pre-exchange input (margin 0 on both sides of the
        # add — the reference's D2 crops instead, resnet_spatial_d2.py:462-480).
        y = maybe_run_d2(
            list(self.r1.layers) + list(self.r2.layers),
            list(params["r1"]) + list(params["r2"]),
            x,
            ctx,
        )
        if y is None and self.stride == 1:
            from mpi4dl_tpu.ops.stripe_bwd import maybe_stripe_run

            y = maybe_stripe_run(
                list(self.r1.layers) + list(self.r2.layers),
                list(params["r1"]) + list(params["r2"]),
                x, ctx,
            )
        if y is None:
            y = _apply_branch(
                (self.r1, self.r2), (params["r1"], params["r2"]), x, ctx
            )
        if self.r3 is not None:
            x = self.r3.apply(params["r3"], x, ctx)
        return jax.nn.relu(x + y)


@dataclasses.dataclass
class ResBlockV2(Cell):
    """v2 pre-activation bottleneck cell (reference make_cell_v2,
    resnet.py:180-230).  Note the reference's r1/r2 use 3x3 kernels and r3 is
    the 1x1 expansion; there is no post-add ReLU."""

    in_f: int
    f1: int
    f2: int
    stride: int
    first_block: bool  # resblock == 0 → conv shortcut
    pre_activation: bool  # False only for stage0/block0 (act=None, bn=False)
    name: str = "res_v2"

    def __post_init__(self):
        self.r1 = LayerCell(
            _resnet_layer(
                self.in_f, self.f1, stride=self.stride,
                activation=self.pre_activation, batch_norm=self.pre_activation,
                conv_first=False,
            )
        )
        self.r2 = LayerCell(_resnet_layer(self.f1, self.f1, conv_first=False))
        self.r3 = LayerCell(_resnet_layer(self.f1, self.f2, kernel=1, conv_first=False))
        self.r4 = (
            LayerCell(
                _resnet_layer(
                    self.in_f, self.f2, kernel=1, stride=self.stride,
                    activation=False, batch_norm=False,
                )
            )
            if self.first_block
            else None
        )

    def init(self, key, in_shape):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p1, s = self.r1.init(k1, in_shape)
        p2, s = self.r2.init(k2, s)
        p3, s = self.r3.init(k3, s)
        params = {"r1": p1, "r2": p2, "r3": p3}
        if self.r4 is not None:
            p4, _ = self.r4.init(k4, in_shape)
            params["r4"] = p4
        return params, s

    def apply(self, params, x, ctx: ApplyCtx):
        from mpi4dl_tpu.layers import _hstripe_enabled
        from mpi4dl_tpu.ops.d2 import maybe_run_d2

        branch_layers = (
            list(self.r1.layers) + list(self.r2.layers) + list(self.r3.layers)
        )
        branch_params = (
            list(params["r1"]) + list(params["r2"]) + list(params["r3"])
        )
        # D2: one halo exchange for the whole bottleneck (3x3 + 3x3 + 1x1).
        y = maybe_run_d2(branch_layers, branch_params, x, ctx)
        if y is None and self.stride == 1:
            # Stripe-wise fwd+bwd for the whole bottleneck branch — ONE
            # accumulated halo realization, then a checkpointed scan over H
            # stripes whose transpose re-executes each stripe in place
            # (ops/stripe_bwd.py; MPI4DL_STRIPE_BWD=1).  Dispatched at the
            # branch so the three sub-runs share a single exchange.
            from mpi4dl_tpu.ops.stripe_bwd import maybe_stripe_run

            y = maybe_stripe_run(branch_layers, branch_params, x, ctx)
        if y is None and self.stride == 1 and _hstripe_enabled():
            # Single-device huge-spatial blocks run the branch H-stripe by
            # H-stripe (ops/hstripe_conv.hstripe_layer_run) so the branch's
            # full-size intermediates never materialize — the capacity
            # lever for 2048²-class ResNet on one chip (PERF_NOTES r4).
            # Semantics: halo-D2 pad-once borders + per-stripe train-BN
            # statistics — both the reference's own high-res semantics.
            from mpi4dl_tpu.ops.hstripe_conv import (
                hstripe_layer_run, hstripe_run_eligible,
            )

            if hstripe_run_eligible(branch_layers, x.shape, ctx):
                y = hstripe_layer_run(branch_layers, branch_params, x, ctx)
        if y is None and not ctx.remat_ops:
            out = self._folded_block(branch_layers, branch_params, params,
                                     x, ctx)
            if out is not None:
                return out
            y = self._branch_behind_the_stream(params, x, ctx)
        if y is None:
            y = _apply_branch(
                (self.r1, self.r2, self.r3),
                (params["r1"], params["r2"], params["r3"]), x, ctx,
            )
        if self.r4 is not None:
            x = self.r4.apply(params["r4"], x, ctx)
        return x + y

    def _folded_block(self, layers, flat, params, x, ctx: ApplyCtx):
        """The block as one folded run, or None.  In the narrow stage at 2²⁰
        pixels and more every convolution of the branch is W-folded by one p
        (``layers.run_fold``), so the block stays on ``[N, H, W/p, p·C]``
        from its input to its residual add, the shortcut convolution with it
        where it takes the same fold."""
        p = run_fold(layers, x.shape, ctx)
        if not p:
            return None
        xf = fold(x, p)
        yf = apply_run(layers, flat, xf, ctx, p)
        if self.r4 is not None:
            if run_fold(self.r4.layers, x.shape, ctx) != p:
                return self.r4.apply(params["r4"], x, ctx) + unfold(yf, p)
            xf = apply_run(self.r4.layers, params["r4"], xf, ctx, p)
        return unfold(xf + yf, p)

    def _branch_behind_the_stream(self, params, x, ctx: ApplyCtx):
        """The branch of the block that follows the narrow stage, or None:
        its strided convolution does not fold, but the BatchNorm and ReLU
        before it take the stream as the stage's runs left it
        (``layers.stream_fold``), not re-tiled for the reduction."""
        lead = len(self.r1.layers) - 1
        if not lead or self.stride == 1:
            return None
        p = run_fold(self.r1.layers[:lead], x.shape, ctx, stream_fold(x.shape))
        if not p:
            return None
        h = unfold(apply_run(self.r1.layers[:lead], params["r1"][:lead],
                             fold(x, p), ctx, p), p)
        return _apply_branch(
            (LayerCell(self.r1.layers[lead:]), self.r2, self.r3),
            (params["r1"][lead:], params["r2"], params["r3"]), h, ctx,
        )


def _head(
    num_filters: int,
    num_classes: int,
    pool_kernel: int,
    with_bn: bool,
    softmax_in_model: bool,
    feature_hw: int,
) -> LayerCell:
    """avg-pool + flatten + FC head (reference end_part_v1/v2,
    resnet.py:117-142, :234-267)."""
    seq: List[Layer] = []
    if with_bn:
        seq += [BatchNorm(num_filters), ReLU()]
    seq.append(Pool2d("avg", pool_kernel))
    seq.append(Flatten())
    flat = num_filters * (feature_hw // pool_kernel) ** 2
    seq.append(Dense(flat, num_classes))
    if softmax_in_model:
        seq.append(Softmax())
    return LayerCell(seq, name="head")


def get_resnet_v1(
    in_shape: Tuple[int, int, int, int],
    depth: int,
    num_classes: int = 10,
    softmax_in_model: bool = False,
) -> CellModel:
    if (depth - 2) % 6 != 0:
        raise ValueError("depth should be 6n+2 (e.g. 20, 32, 44)")
    n_blocks = (depth - 2) // 6
    cells: List[Cell] = [LayerCell(_resnet_layer(3, 16), name="stem")]
    in_f, f = 16, 16
    for stack in range(3):
        for block in range(n_blocks):
            stride = 2 if (stack > 0 and block == 0) else 1
            cells.append(
                ResBlockV1(
                    in_f, f, stride,
                    shortcut_conv=(block == 0 and stack > 0),
                    name=f"s{stack}b{block}",
                )
            )
            in_f = f
        f *= 2
    feature_hw = in_shape[1] // 4  # two stride-2 stages
    cells.append(_head(in_f, num_classes, 8, False, softmax_in_model, feature_hw))
    return CellModel(cells, in_shape, num_classes, name=f"resnet{depth}_v1")


def get_resnet_v2(
    in_shape: Tuple[int, int, int, int],
    depth: int,
    num_classes: int = 10,
    softmax_in_model: bool = False,
) -> CellModel:
    if (depth - 2) % 9 != 0:
        raise ValueError("depth should be 9n+2 (e.g. 56, 110)")
    n_blocks = (depth - 2) // 9
    cells: List[Cell] = [LayerCell(_resnet_layer(3, 16), name="stem")]
    in_f, f_in = 16, 16
    for stage in range(3):
        for block in range(n_blocks):
            stride = 1
            pre_act = True
            if stage == 0:
                f_out = f_in * 4
                if block == 0:
                    pre_act = False
            else:
                f_out = f_in * 2
                if block == 0:
                    stride = 2
            cells.append(
                ResBlockV2(
                    in_f, f_in, f_out, stride,
                    first_block=(block == 0), pre_activation=pre_act,
                    name=f"s{stage}b{block}",
                )
            )
            in_f = f_out
        f_in = f_out
    feature_hw = in_shape[1] // 4
    cells.append(_head(in_f, num_classes, 8, True, softmax_in_model, feature_hw))
    return CellModel(cells, in_shape, num_classes, name=f"resnet{depth}_v2")


def get_resnet(
    in_shape,
    depth: int,
    num_classes: int = 10,
    version: int = 2,
    softmax_in_model: bool = False,
) -> CellModel:
    fn = get_resnet_v1 if version == 1 else get_resnet_v2
    return fn(in_shape, depth, num_classes, softmax_in_model)
