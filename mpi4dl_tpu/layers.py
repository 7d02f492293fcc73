"""Functional NHWC layer library.

Each layer is a lightweight frozen dataclass with

- ``init(key, in_shape) -> (params, out_shape)`` — params is a pytree of
  jnp arrays; shapes are *global* (unsharded) shapes including batch.
- ``apply(params, x, ctx) -> y`` — pure; `ctx` is an ApplyCtx.  When
  ``ctx.spatial`` is active (inside shard_map, H/W sharded), convs and pools
  exchange halos via ops/halo.py; otherwise they are plain XLA ops.

This replaces three parallel class hierarchies in the reference (sequential /
spatial "D1" / spatial "D2" copies of every model,
``src/models/{resnet,resnet_spatial,resnet_spatial_d2}.py`` etc.) with one
definition whose behaviour is chosen by sharding context at apply time.

Layout notes (TPU-first):
- NHWC activations, HWIO conv kernels: the channel dim lands on the TPU lane
  dimension (128) so convs map straight onto the MXU.
- Compute dtype is the incoming activation dtype; params are kept fp32 by
  default and cast at use (bf16 matmul/conv with fp32 master weights).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.layer_ctx import ApplyCtx, SpatialCtx
from mpi4dl_tpu.obs.spans import recorder
from mpi4dl_tpu.ops.halo import HaloSpec, halo_exchange_2d, halo_exchange_with_mask

# Escape hatches, read at DISPATCH time (trace), not import — so a script
# can toggle them between step builds for A/B runs (the pattern bench.py
# uses for MPI4DL_SQRT_GROUPS):
#  MPI4DL_NO_PHASE_DX=1  — strided convs keep XLA's lhs-dilation backward
#                          instead of ops/conv_phase.py.
#  MPI4DL_NO_HSTRIPE=1   — tiny-channel huge-spatial convs keep the plain
#                          XLA conv instead of ops/wfold_conv.py (or, where
#                          the fold is not exact, ops/hstripe_conv.py);
#                          no run of layers is carried folded either.
# Both wins are scheduling/layout properties of XLA's TPU lowering, not of
# the math — hence the hatches.
def _phase_dx_enabled() -> bool:
    import os

    return os.environ.get("MPI4DL_NO_PHASE_DX") != "1"


def _hstripe_enabled() -> bool:
    import os

    return os.environ.get("MPI4DL_NO_HSTRIPE") != "1"


# The TPU's lane count: the minor dimension of a tile.
_LANES = 128
_HSTRIPE_MIN_PIXELS = 1 << 20
# The W-fold takes narrow convs below this size.  From 2048² up a narrow
# block runs H-stripe by H-stripe on flat [N, H, W·C] buffers
# (hstripe_conv._RUN_MIN_PIXELS), and the one conv left beside it (ResNet's
# 16→64 shortcut) folded to a full-size lane-dense tensor costs the
# ResNet-110 v2 2048² step 3.5 GiB (19.13 against 15.60 GiB compiled for a
# v5e under remat='sqrt', PERF.md PR 27): there it keeps the striped path.
_WFOLD_MAX_PIXELS = 1 << 22
# Pools at or below this input size take the phase-view strided reduction
# (fast path); larger ones keep strided slices (see _window_reduce).
# 256 MB covers the 1024² headline (109 MB pools); a 512 MB setting that
# would cover the 2048² rung's 436 MB pools was tried and the rung's
# compile did not finish inside 25 min (round 5) — kept conservative.
_PHASE_POOL_MAX_BYTES = 256 * 1024 * 1024

Params = Any
Shape = Tuple[int, ...]


def _narrow_huge(shape) -> bool:
    """A narrow-channel huge-spatial ``[N, H, W, C]``: what the W-fold and
    the H stripes exist for (``Conv2d._hstripe_shape``)."""
    n, h, w, c = shape
    return _hstripe_enabled() and c <= 64 and h * w >= _HSTRIPE_MIN_PIXELS


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _uniform(key, shape, bound, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, minval=-bound, maxval=bound)


class Layer:
    """Base: subclasses implement init/apply."""

    def init(self, key, in_shape: Shape):
        raise NotImplementedError

    def apply(self, params, x, ctx: ApplyCtx):
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Conv2d with spatial-parallel halo exchange
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Conv2d(Layer):
    """2-D convolution, NHWC/HWIO.

    Replicated mode: plain ``lax.conv_general_dilated`` with explicit
    symmetric padding.  Spatial mode (ctx.spatial active): halo-exchange the
    padding region from neighbour tiles, then VALID conv — the TPU-native
    equivalent of the reference's ``conv_spatial``
    (``src/torchgems/spatial.py:1019-1029``: pad → exchange → copy → conv).

    Requirements inherited from the reference's design (and checked):
    tile H/W divisible by stride so windows align across tiles.
    """

    in_channels: int
    out_channels: int
    kernel_size: Any = 3
    stride: Any = 1
    padding: Any = None  # None → (k-1)//2 per dim ("same"-style like reference)
    bias: bool = True
    feature_group_count: int = 1
    # Function-preserving lane padding (0 = off): the conv consumes/produces
    # activations padded to these channel widths, with the extra kernel
    # columns/rows ZERO — so padded input channels contribute exact zeros
    # and padded output channels are exact zeros.  Params keep their true
    # shapes (autodiff of the pad is a slice, so weight grads are exact).
    # Purpose: keep narrow mid-channel chains (AmoebaNet bottlenecks,
    # c/4 ∈ {52,104,156}) on one dense 128-lane layout through a whole op
    # chain instead of XLA flipping narrow padded tilings around each conv
    # (the r4 layout-copy mass, PERF_NOTES).
    lane_pad_in: int = 0
    lane_pad_out: int = 0

    def _geometry(self):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        if self.padding is None:
            ph, pw = (kh - 1) // 2, (kw - 1) // 2
        else:
            ph, pw = _pair(self.padding)
        return kh, kw, sh, sw, ph, pw

    def init(self, key, in_shape: Shape):
        kh, kw, sh, sw, ph, pw = self._geometry()
        n, h, w, c = in_shape
        expect_c = self.lane_pad_in or self.in_channels
        assert c == expect_c, f"expected C={expect_c}, got {c} in {in_shape}"
        if self.lane_pad_in or self.lane_pad_out:
            assert self.feature_group_count == 1, "lane_pad: groups unsupported"
            assert not self.lane_pad_in or self.lane_pad_in >= self.in_channels, \
                (self.lane_pad_in, self.in_channels)
            assert not self.lane_pad_out or self.lane_pad_out >= self.out_channels, \
                (self.lane_pad_out, self.out_channels)
        fan_in = self.in_channels // self.feature_group_count * kh * kw
        bound = 1.0 / math.sqrt(fan_in)
        kkey, bkey = jax.random.split(key)
        params = {
            "kernel": _uniform(
                kkey,
                (kh, kw, self.in_channels // self.feature_group_count,
                 self.out_channels),
                bound,
            )
        }
        if self.bias:
            params["bias"] = _uniform(bkey, (self.out_channels,), bound)
        oh = (h + 2 * ph - kh) // sh + 1
        ow = (w + 2 * pw - kw) // sw + 1
        return params, (n, oh, ow, self.lane_pad_out or self.out_channels)

    @staticmethod
    def _hstripe_shape(kh, kw, sh, sw, groups, shape) -> bool:
        """The shape gate for XLA-hostile convs: stride-1 convs on
        NARROW-channel HUGE-spatial inputs, where XLA's TPU lowering puts
        the <= 64 channels in the 128 lanes (2-8x the tensor in memory and
        traffic) and materializes an im2col-style patch tensor (measured
        ~3 GB per 3x3 conv at C=16, 2048²).  A conv that passes takes the
        W-fold (ops/wfold_conv.py: lane-dense operands, no loop) wherever
        that is exact, which is SAME padding on a W the fold divides, and
        the image is under _WFOLD_MAX_PIXELS; what is left for the H
        stripes (ops/hstripe_conv.py) is a tile whose W margin came from a
        halo exchange (VALID on W), an indivisible W, the stem's Cin 3 and
        single convs at 2048² and up.
        MPI4DL_NO_HSTRIPE=1 opts out of both: the plain XLA conv."""
        # 1x1 convs are pure matmuls, but at huge spatial XLA still splits
        # them with ~2x-padded GB-scale temps — striping bounds those too.
        return (sh, sw) == (1, 1) and groups == 1 and _narrow_huge(shape)

    @staticmethod
    def _sharded(sp) -> Tuple[bool, bool]:
        """Whether H and W are sharded over more than one tile: a sharded
        dim takes its margin from neighbour tiles and runs VALID."""
        if sp is None or not sp.active:
            return False, False
        return (bool(sp.axis_h) and sp.grid_h > 1,
                bool(sp.axis_w) and sp.grid_w > 1)

    def _wfold(self, shape, pad_w) -> int:
        """For a convolution that passed ``_hstripe_shape`` on an input of
        ``shape``: the fold it takes (ops/wfold_conv.py), or 0 where it is
        left to the H stripes."""
        n, h, w, c = shape
        if h * w >= _WFOLD_MAX_PIXELS:
            return 0
        from mpi4dl_tpu.ops.wfold_conv import wfold_factor

        return wfold_factor(
            w, _pair(self.kernel_size)[1],
            self.lane_pad_in or self.in_channels,
            self.lane_pad_out or self.out_channels, pad_w,
        )

    def fold_for(self, shape, ctx: ApplyCtx) -> int:
        """The fold ``apply`` gives this convolution on an unfolded input of
        ``shape`` under ``ctx``, or 0 where it takes any other path: what a
        folded run (:func:`run_fold`) asks of each of its convolutions."""
        kh, kw, sh, sw, ph, pw = self._geometry()
        if not self._hstripe_shape(
            kh, kw, sh, sw, self.feature_group_count, shape
        ):
            return 0
        exchanged = self._sharded(ctx.spatial)[1] and pw
        return self._wfold(shape, (0, 0) if exchanged else (pw, pw))

    def apply(self, params, x, ctx: ApplyCtx):
        kh, kw, sh, sw, ph, pw = self._geometry()
        kernel = params["kernel"].astype(x.dtype)
        bias = params["bias"] if self.bias else None
        if self.lane_pad_in or self.lane_pad_out:
            pi = max(0, (self.lane_pad_in or self.in_channels) - self.in_channels)
            po = max(0, (self.lane_pad_out or self.out_channels) - self.out_channels)
            kernel = jnp.pad(kernel, ((0, 0), (0, 0), (0, pi), (0, po)))
            if bias is not None and po:
                bias = jnp.pad(bias, (0, po))
        sp = ctx.spatial
        if sp is not None and sp.active:
            sharded_h, sharded_w = self._sharded(sp)
            halo_h = HaloSpec.symmetric(ph if sharded_h else 0)
            halo_w = HaloSpec.symmetric(pw if sharded_w else 0)
            # Per-conv ("D1") halo exchange of the receptive-field overlap —
            # skipped inside a D2 fused run (sp.halo_pre_exchanged: the
            # accumulated margin is already in x); either way the conv then
            # runs VALID on the sharded dims, consuming ph/pw of margin.
            if not sp.halo_pre_exchanged and (halo_h.lo or halo_w.lo):
                x = halo_exchange_2d(
                    x, halo_h, halo_w, sp.axis_h, sp.axis_w, sp.grid_h, sp.grid_w,
                    rep_h=sp.rep_h, rep_w=sp.rep_w,
                )
            # A dim whose margin came from exchange (or pre-exchange) needs no
            # padding; unsharded dims keep explicit symmetric padding.
            padding = (
                (0, 0) if halo_h.lo else (ph, ph),
                (0, 0) if halo_w.lo else (pw, pw),
            )
        else:
            padding = ((ph, ph), (pw, pw))
        groups = self.feature_group_count
        if ctx.fold:
            # Inside a folded run, whose gate (run_fold) held this
            # convolution to the fold below: x is [N, H, W/p, p·Cin] and
            # stays folded on the way out.
            from mpi4dl_tpu.ops.wfold_conv import wfold_conv_folded

            path = "wfold"
            y = wfold_conv_folded(x, kernel, padding[0], ctx.fold)
            if bias is not None:
                bias = jnp.tile(bias, ctx.fold)
        elif self._hstripe_shape(kh, kw, sh, sw, groups, x.shape):
            p = self._wfold(x.shape, padding[1])
            if p:
                from mpi4dl_tpu.ops.wfold_conv import wfold_conv2d

                path = "wfold"
                y = wfold_conv2d(x, kernel, padding[0], p)
            else:
                from mpi4dl_tpu.ops.hstripe_conv import hstripe_conv2d

                path = "hstripe"
                y = hstripe_conv2d(x, kernel, padding[0], padding[1])
        elif ((kh, kw, sh, sw, groups) == (1, 1, 1, 1, 1)
              and padding == ((0, 0), (0, 0))
              and (x.shape[-1] % _LANES or kernel.shape[-1] % _LANES)):
            # A pointwise convolution is a matrix product over the channel
            # axis, and goes to XLA as one, on the activation as it stands.
            # XLA:TPU rewrites a batch-1 convolution into a batched one over
            # a split of W in a channel-minor layout; where a channel count
            # is no multiple of the 128 lanes it gives the elementwise work
            # round it an H-minor layout, and brackets the convolution with
            # transposes between the two (AmoebaNet-D at 416 and 832
            # channels: 69 % of a normal cell's copy bytes, a third of the
            # 2048² step; PERF.md, PR 32).  A product takes the layout of
            # its neighbours.  Where both widths fill the lanes XLA keeps
            # channels minor on both sides, there is no transpose to save,
            # and the product form costs ResNet-110 v2 at 2048² 0.84 GiB
            # (its 128 -> 256 at 512²): those stay convolutions.
            path = "dot"
            y = lax.dot_general(x, kernel[0, 0], (((3,), (0,)), ((), ())))
        elif (sh, sw) != (1, 1) and groups == 1 and _phase_dx_enabled():
            # Strided convs take the phase-decomposed-backward form: same
            # forward conv, but dx avoids XLA's lhs-dilation machinery
            # (ops/conv_phase.py; measured step-level win, PERF_NOTES r4).
            from mpi4dl_tpu.ops.conv_phase import conv2d_strided_t

            path = "phase"
            y = conv2d_strided_t(x, kernel, (sh, sw), padding)
        else:
            path = "xla"
            y = lax.conv_general_dilated(
                x,
                kernel,
                window_strides=(sh, sw),
                padding=padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups,
            )
        recorder().note_conv(self, path)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y


# ---------------------------------------------------------------------------
# BatchNorm
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BatchNorm(Layer):
    """BatchNorm2d over (N, H, W) per channel.

    Train mode uses batch statistics.  Under spatial sharding the stats are
    psum'd across the tile grid by default (``ctx.spatial.bn_cross_tile``),
    which makes sharded training numerically identical to single-device — the
    reference instead computes per-tile stats (plain nn.BatchNorm2d inside
    spatial layers, reference resnet_spatial.py:149-163); set
    ``bn_cross_tile=False`` on the SpatialCtx for that parity behaviour.

    Running stats (`mean`,`var`) live in params; they receive no gradient in
    train mode.  When ``ctx.bn_sink`` is set, train-mode apply() deposits the
    momentum-updated running values (torch semantics: unbiased variance for
    the running buffer) into the sink keyed by ``id()`` of the param leaves;
    step builders write them back post-optimizer-update.  Eval mode
    (``ctx.train=False``) normalizes with the running stats.
    """

    num_features: int
    eps: float = 1e-5
    momentum: float = 0.1
    # Function-preserving lane padding (see Conv2d.lane_pad_*): the layer
    # normalizes an activation padded to this channel width.  Padded
    # channels get scale 0 / bias 0, so their output is exactly 0 (the
    # batch statistics of a zero channel never reach the output); params
    # and running stats keep the true num_features width.
    lane_pad: int = 0

    def init(self, key, in_shape: Shape):
        c = in_shape[-1]
        assert not self.lane_pad or self.lane_pad >= self.num_features, \
            (self.lane_pad, self.num_features)
        expect_c = self.lane_pad or self.num_features
        assert c == expect_c, f"expected C={expect_c}, got {in_shape}"
        nf = self.num_features
        params = {
            "scale": jnp.ones((nf,), jnp.float32),
            "bias": jnp.zeros((nf,), jnp.float32),
            "mean": jnp.zeros((nf,), jnp.float32),
            "var": jnp.ones((nf,), jnp.float32),
        }
        return params, in_shape

    def apply(self, params, x, ctx: ApplyCtx):
        # Memory discipline on the TRAIN path (the 2048px→beyond lever,
        # PERF_NOTES.md; eval below trades it back for fp32 precision):
        # never materialize an fp32 copy of the activation.  Statistics come from
        # ONE sum/sumsq pair with fp32 ACCUMULATION over the original dtype,
        # and normalization is folded to y = x·a + b with per-channel fp32
        # (a, b) precomputed — a single fma in the compute dtype, so both
        # the forward temp and the backward cotangents stay bf16 under
        # bf16 compute.  XLA fuses the upcast and the square into the
        # reductions where the activation's layout is the reduction's own;
        # behind a W-folded convolution it is not, and the chip wrote x and
        # x² out in float32 (64.6 ms of the ResNet-110 v2 1024² step, PERF.md
        # PR 27).  Inside a folded run (ctx.fold = p, apply_run below) the
        # activation arrives as [N, H, W/p, p·C] and stays so: the sums run
        # over (N, H, W/p) to a [p·C] vector each and then over the p folded
        # pixels, the same sums in another order, and (a, b) are tiled p
        # times.
        orig_dtype = x.dtype
        fold = ctx.fold  # train mode only: run_fold
        recorder().note_site("norm", self, "folded" if fold else "plain")
        pad = (self.lane_pad - self.num_features) if self.lane_pad else 0
        scale = jnp.pad(params["scale"], (0, pad)) if pad else params["scale"]
        bias = jnp.pad(params["bias"], (0, pad)) if pad else params["bias"]
        if ctx.train:
            axes = tuple(range(x.ndim - 1))  # all but channel
            sp = ctx.spatial
            stat_x = x
            if sp is not None and sp.halo_pre_exchanged and (
                sp.pre_margin_h or sp.pre_margin_w
            ):
                # Inside a D2 fused run the tile still carries not-yet-consumed
                # margin rows (duplicated neighbour data / boundary zeros);
                # statistics come from the true tile region only, so fused-run
                # BN matches the unfused (and single-device) statistics
                # exactly.  Normalisation still covers the full extended tile.
                # (No run is folded over such a margin: run_fold.)
                mh = sp.pre_margin_h if (sp.axis_h and sp.grid_h > 1) else 0
                mw = sp.pre_margin_w if (sp.axis_w and sp.grid_w > 1) else 0
                stat_x = x[:, mh : x.shape[1] - mh, mw : x.shape[2] - mw, :]
            # Accumulate in fp32 for bf16/fp32 activations; promote to f64
            # under x64 inputs (keeps f64 runs genuinely f64 end-to-end).
            acc_dt = jnp.promote_types(jnp.float32, x.dtype)
            cnt = jnp.asarray(
                math.prod([stat_x.shape[a] for a in axes]) * (fold or 1),
                acc_dt,
            )
            s = jnp.sum(stat_x, axis=axes, dtype=acc_dt)
            ss = jnp.sum(
                jnp.square(stat_x.astype(acc_dt)), axis=axes
            )
            if fold:
                s = s.reshape(fold, -1).sum(axis=0)
                ss = ss.reshape(fold, -1).sum(axis=0)
            if sp is not None and sp.active and sp.bn_cross_tile:
                # Cross-tile statistics: psum local (sum, sumsq).  The count
                # is a trace-time constant (SPMD tiles share a shape), so its
                # "reduce" is a static multiply — psum(1, axes) constant-folds
                # to the axis-size product, no wire (ircheck: wasted-wire).
                ax_names = tuple(a for a in (sp.axis_h, sp.axis_w) if a)
                cnt = cnt * lax.psum(1, ax_names)
                s = lax.psum(s, ax_names)
                ss = lax.psum(ss, ax_names)
            mean = s / cnt
            # E[x²]-E[x]² cancellation can go slightly negative in fp.
            var = jnp.maximum(ss / cnt - mean * mean, 0.0)
            if ctx.bn_sink is not None:
                nf = self.num_features
                self._deposit_running(
                    params, mean[:nf] if pad else mean,
                    var[:nf] if pad else var, cnt, ctx,
                )
        else:
            # Eval has no backward and therefore no activation-memory
            # pressure — keep the affine in fp32 (ADVICE r3: the folded
            # compute-dtype fma is a training-memory lever only; inference
            # outputs keep full precision).
            mean, var = params["mean"], params["var"]
            if pad:
                mean = jnp.pad(mean, (0, pad))
                var = jnp.pad(var, (0, pad), constant_values=1.0)
            inv = lax.rsqrt(var + self.eps) * scale
            y = x.astype(jnp.float32) * inv + (bias - mean * inv)
            return y.astype(orig_dtype)
        inv = lax.rsqrt(var + self.eps) * scale
        a = inv.astype(orig_dtype)
        b = (bias - mean * inv).astype(orig_dtype)
        if fold:
            a, b = jnp.tile(a, fold), jnp.tile(b, fold)
        return x * a + b

    def normalize_with_stats(self, params, x, mean, var, cnt, ctx: ApplyCtx):
        """Train-mode normalization with externally computed batch
        statistics: the exact-stats striped run (ops/hstripe_conv.py
        ``_FixedStatsBN``) hands every stripe the same global (mean, var).
        Running-stat deposit and the folded compute-dtype fma are identical
        to apply()'s train path.  ``lane_pad`` is unsupported here (the
        caller gates it)."""
        assert not self.lane_pad, "fixed-stats path does not support lane_pad"
        if ctx.bn_sink is not None:
            self._deposit_running(params, mean, var, cnt, ctx)
        inv = lax.rsqrt(var + self.eps) * params["scale"]
        a = inv.astype(x.dtype)
        b = (params["bias"] - mean * inv).astype(x.dtype)
        return x * a + b

    def _deposit_running(self, params, mean, var, cnt, ctx: ApplyCtx):
        """Put momentum-updated running stats into ctx.bn_sink.

        Stats must come out replicated (params are replicated), so axes over
        which the batch statistics still vary are pmean'd first: the data axis
        always; the tile axes only when per-tile stats are in use
        (bn_cross_tile=False — the psum'd cross-tile stats are already
        tile-invariant).  The variance stored in the running buffer is the
        unbiased one (torch nn.BatchNorm2d semantics)."""
        sp = ctx.spatial
        names = list(ctx.bn_stat_axes)
        if (sp is not None and sp.active and not sp.bn_cross_tile
                and not sp.stat_local):
            names += [a for a in (sp.axis_h, sp.axis_w) if a]
        if ctx.data_axis:
            names.append(ctx.data_axis)
        if names:
            mean = lax.pmean(mean, tuple(names))
            var = lax.pmean(var, tuple(names))
        unbiased = var * (cnt / jnp.maximum(cnt - 1.0, 1.0))
        m = self.momentum
        ctx.bn_sink[id(params["mean"])] = (1 - m) * params["mean"] + m * mean
        ctx.bn_sink[id(params["var"])] = (1 - m) * params["var"] + m * unbiased


# ---------------------------------------------------------------------------
# Activations / simple layers
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReLU(Layer):
    def init(self, key, in_shape):
        return {}, in_shape

    def apply(self, params, x, ctx):
        return jax.nn.relu(x)


@dataclasses.dataclass(frozen=True)
class Identity(Layer):
    def init(self, key, in_shape):
        return {}, in_shape

    def apply(self, params, x, ctx):
        return x


# ---------------------------------------------------------------------------
# A folded run: layers between W-folded convolutions, on the folded form
# ---------------------------------------------------------------------------


# The narrowest convolutions the models here have (ResNet's first stage: 16
# channels, with 64-channel tensors between its bottleneck blocks).
_NARROW_CHANNELS = 16


def stream_fold(shape: Shape) -> int:
    """The fold in which a narrow stage hands an activation of ``shape``
    from one block to the next, or 0 where ``shape`` is outside the W-fold's
    gate: that of a convolution between it and ``_NARROW_CHANNELS`` channels
    (8 for ResNet-110 v2's 16- and 64-channel tensors at 1024²), which is the
    fold of the stage's runs.  The packed cell boundary takes this form
    (cells._pack_lanes), and so does a BatchNorm that follows the stage
    without a folded convolution of its own (``run_fold`` with ``p``)."""
    if len(shape) != 4 or not _narrow_huge(shape):
        return 0
    n, h, w, c = shape
    if h * w >= _WFOLD_MAX_PIXELS:  # as Conv2d._wfold
        return 0
    from mpi4dl_tpu.ops.wfold_conv import wfold_factor

    return wfold_factor(w, 1, c, _NARROW_CHANNELS, (0, 0))


def run_fold(layers: Sequence[Layer], shape: Shape, ctx: ApplyCtx,
             p: int = 0) -> int:
    """The fold p that carries ``layers`` from an input of ``shape`` as
    ``[N, H, W/p, p·C]`` throughout, or 0: the run is folded only where it is
    BatchNorm, ReLU and convolutions, in train mode, with no pre-exchanged
    margin in the activation (BatchNorm slices that off its statistics), and
    every convolution takes the W-fold (``Conv2d.fold_for``: the dispatch's
    own gate) with one and the same p.  The fold is the run's, not a
    layer's: a BatchNorm(64) between convolutions folded by 8 folds by 8.
    Given ``p`` (the fold of the stream the run continues, ``stream_fold``)
    the run follows that one or none, and may be without a convolution.
    Decided from shapes and the context alone; where it is 0 every layer
    takes the path it takes alone."""
    sp = ctx.spatial
    if len(shape) != 4 or ctx.fold or not ctx.train or (
        sp is not None and sp.halo_pre_exchanged
        and (sp.pre_margin_h or sp.pre_margin_w)
    ):
        return 0
    n, h, w, c = shape
    for layer in layers:
        if isinstance(layer, Conv2d):
            q = layer.fold_for((n, h, w, c), ctx)
            if not q or (p and q != p):
                return 0
            kh, _, _, _, ph, _ = layer._geometry()
            p, h = q, h + 2 * ph - kh + 1
            c = layer.lane_pad_out or layer.out_channels
        elif isinstance(layer, BatchNorm):
            if layer.lane_pad:
                return 0
        elif not isinstance(layer, (ReLU, Identity)):
            return 0
    return p


def apply_run(layers: Sequence[Layer], params, x, ctx: ApplyCtx, fold: int = 0):
    """``layers`` in order; with ``fold`` (from :func:`run_fold`) on an ``x``
    that is already folded, handing the folded result on."""
    if fold:
        ctx = dataclasses.replace(ctx, fold=fold)
    for p, layer in zip(params, layers):
        x = layer.apply(p, x, ctx)
    return x


@dataclasses.dataclass(frozen=True)
class Softmax(Layer):
    """Channel softmax — exists to reproduce the reference's softmax-in-model
    head (resnet.py:140) behind cfg.softmax_in_model."""

    def init(self, key, in_shape):
        return {}, in_shape

    def apply(self, params, x, ctx):
        return jax.nn.softmax(x, axis=-1)


@dataclasses.dataclass(frozen=True)
class Dense(Layer):
    """``x @ kernel (+ bias)`` over the last axis: ``[B, F]`` or ``[B, S, F]``."""

    in_features: int
    out_features: int
    use_bias: bool = True

    def init(self, key, in_shape):
        assert in_shape[-1] == self.in_features, (in_shape, self.in_features)
        bound = 1.0 / math.sqrt(self.in_features)
        k1, k2 = jax.random.split(key)
        params = {
            "kernel": _uniform(k1, (self.in_features, self.out_features), bound),
        }
        if self.use_bias:
            params["bias"] = _uniform(k2, (self.out_features,), bound)
        return params, (*in_shape[:-1], self.out_features)

    def apply(self, params, x, ctx):
        y = x @ params["kernel"].astype(x.dtype)
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return y


@dataclasses.dataclass(frozen=True)
class RMSNorm(Layer):
    """``x / rms(x) * scale`` over the last axis, computed in float32 and
    handed on in the activation's dtype; ``scale`` is learned, from one."""

    features: int
    eps: float = 1e-5

    def init(self, key, in_shape):
        assert in_shape[-1] == self.features, (in_shape, self.features)
        return {"scale": jnp.ones((self.features,), jnp.float32)}, in_shape

    def apply(self, params, x, ctx):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + self.eps) * params["scale"].astype(jnp.float32)
        return y.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class CausalConv1d(Layer):
    """Depthwise causal convolution along the sequence axis of ``[B, S, C]``:
    ``y[t] = sum_j kernel[j] * x[t - (K-1) + j]``, zeros left of the
    sequence, plus a bias a channel where ``use_bias`` (both uniform within
    ``1/sqrt(K)``, torch's Conv1d at one input channel a group).  The spatial layers'
    one-dimensional case, written as K shifted multiply-adds: a depthwise
    kernel of a few taps has no matrix product in it.
    (``ops/ring.ghost_conv1d`` is dense and centred.)"""

    features: int
    kernel_size: int = 3
    use_bias: bool = False

    def init(self, key, in_shape):
        assert in_shape[-1] == self.features, (in_shape, self.features)
        bound = 1.0 / math.sqrt(self.kernel_size)
        params = {"kernel": _uniform(
            key, (self.kernel_size, self.features), bound)}
        if self.use_bias:  # a key of its own: the kernel is the bias-free one
            params["bias"] = _uniform(
                jax.random.fold_in(key, 1), (self.features,), bound)
        return params, in_shape

    def apply(self, params, x, ctx):
        k = self.kernel_size
        w = params["kernel"].astype(x.dtype)
        padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        s = x.shape[1]
        y = sum(padded[:, j:j + s] * w[j] for j in range(k))
        return y + params["bias"].astype(x.dtype) if self.use_bias else y


@dataclasses.dataclass(frozen=True)
class Flatten(Layer):
    def init(self, key, in_shape):
        n = in_shape[0]
        return {}, (n, int(math.prod(in_shape[1:])))

    def apply(self, params, x, ctx):
        # Spatially sharded tensors must be gathered before flattening; model
        # builders place the SP→LP junction before any Flatten.
        return x.reshape(x.shape[0], -1)


# ---------------------------------------------------------------------------
# Pooling (with distributed-correct halo + divisor/mask handling)
# ---------------------------------------------------------------------------


def _window_reduce(x, kh, kw, sh, sw, ph, pw, op: str):
    """Differentiable window reduction (max/add) over NHWC.

    Non-overlapping unpadded windows use a reshape.  STRIDED overlapping
    windows use a phase decomposition: pad, reshape H→(H/s, s) W→(W/s, s),
    and read every tap as a UNIT-stride slice ``y[:, i//s : i//s + oh, i % s,
    ...]`` — on TPU a stride-s slice lowers to gathers in the forward and
    chained pad-scatter fusions in the backward (measured the single largest
    self-inflicted cost class of the AmoebaNet step at 1024²: ~9 ms of
    forward gathers + ~25 ms of scatter chains per 244 ms step, PERF_NOTES
    r4), while unit-stride slices of the phase view fuse into plain loop
    fusions with pad transposes.  Stride-1 windows keep the direct shifted
    slices (k ≤ 8 here, so ≤ 64 fused ops).
    """
    n, h, w, c = x.shape
    if ph == 0 and pw == 0 and kh == sh and kw == sw and h % kh == 0 and w % kw == 0:
        r = x.reshape(n, h // kh, kh, w // kw, kw, c)
        return jnp.max(r, axis=(2, 4)) if op == "max" else jnp.sum(r, axis=(2, 4))
    fill = jnp.asarray(-jnp.inf if op == "max" else 0, x.dtype)
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    # The phase view materializes a ~input-sized buffer that lives through
    # the pool's backward; at the memory FRONTIER (AmoebaNet ≥3328², where
    # pools at 1664-res × 208ch exceed a GB) that buffer costs trainable
    # resolution, so huge pools keep the strided-slice form (slower:
    # gathers + scatter chains — the throughput rungs never see it).
    phase_ok = (n * h * w * c * x.dtype.itemsize) <= _PHASE_POOL_MAX_BYTES
    if (sh > 1 or sw > 1) and phase_ok:
        # Phase view: padded row b = q·s + φ ↦ y[..., q, φ, ...].  Tap i of
        # output q reads padded row q·s + i = (q + i//s)·s + (i % s): a
        # unit-stride slice at phase i % s, offset i//s.  Rows/cols are
        # padded up to the phase grid; taps never read past (oh-1)·s + k-1,
        # so the grid crop below is safe for any h, s, k.
        hr = oh + (kh - 1) // sh
        wr = ow + (kw - 1) // sw
        xp = jnp.pad(
            x,
            ((0, 0), (ph, max(0, hr * sh - h - ph)),
             (pw, max(0, wr * sw - w - pw)), (0, 0)),
            constant_values=fill,
        )
        y = xp[:, : hr * sh, : wr * sw, :].reshape(n, hr, sh, wr, sw, c)
        acc = None
        for i in range(kh):
            for j in range(kw):
                piece = y[:, i // sh : i // sh + oh, i % sh,
                          j // sw : j // sw + ow, j % sw, :]
                if acc is None:
                    acc = piece
                elif op == "max":
                    acc = jnp.maximum(acc, piece)
                else:
                    acc = acc + piece
        return acc
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)), constant_values=fill)
        h, w = h + 2 * ph, w + 2 * pw
    acc = None
    for i in range(kh):
        for j in range(kw):
            piece = x[
                :, i : i + (oh - 1) * sh + 1 : sh,
                j : j + (ow - 1) * sw + 1 : sw, :,
            ]
            if acc is None:
                acc = piece
            elif op == "max":
                acc = jnp.maximum(acc, piece)
            else:
                acc = acc + piece
    return acc


@dataclasses.dataclass(frozen=True)
class Pool2d(Layer):
    """Max/Avg pooling with exact distributed semantics.

    Spatial mode exchanges a halo of the padding width (the reference's Pool,
    ``spatial.py:1416-1509``) and additionally exchanges a validity mask so

    - avg with count_include_pad=False divides by the number of *in-bounds*
      elements (global semantics), and
    - max treats out-of-bounds as -inf instead of 0 (fixing the reference's
      zero-halo leak at image borders).
    """

    op: str  # "max" | "avg"
    kernel_size: Any
    stride: Any = None
    padding: Any = 0
    count_include_pad: bool = True

    def _geometry(self):
        kh, kw = _pair(self.kernel_size)
        s = self.stride if self.stride is not None else self.kernel_size
        sh, sw = _pair(s)
        ph, pw = _pair(self.padding)
        return kh, kw, sh, sw, ph, pw

    def init(self, key, in_shape):
        kh, kw, sh, sw, ph, pw = self._geometry()
        n, h, w, c = in_shape
        oh = (h + 2 * ph - kh) // sh + 1
        ow = (w + 2 * pw - kw) // sw + 1
        return {}, (n, oh, ow, c)

    def apply(self, params, x, ctx: ApplyCtx):
        kh, kw, sh, sw, ph, pw = self._geometry()
        sp = ctx.spatial
        sharded_h = sp is not None and sp.active and sp.axis_h and sp.grid_h > 1
        sharded_w = sp is not None and sp.active and sp.axis_w and sp.grid_w > 1

        need_mask = (self.op == "avg" and not self.count_include_pad) or (
            self.op == "max" and (ph or pw)
        )

        if sp is not None and sp.halo_pre_exchanged and (
            (sharded_h and ph) or (sharded_w and pw)
        ):
            # Inside a D2 fused run: the margin (incl. this pool's padding) is
            # already present, so run VALID on the sharded dims.  Pad-once D2
            # semantics apply: boundary margin rows are zeros (no -inf mask,
            # no in-bounds divisor on the sharded dims) — exactly what the
            # pad-global-once emulation computes; the D1 path below keeps the
            # exact global semantics.  Unsharded dims keep their own padding.
            rem_ph = 0 if sharded_h else ph
            rem_pw = 0 if sharded_w else pw
            if self.op == "max":
                return _window_reduce(x, kh, kw, sh, sw, rem_ph, rem_pw, "max")
            ysum = _window_reduce(x, kh, kw, sh, sw, rem_ph, rem_pw, "add")
            return ysum / jnp.asarray(kh * kw, x.dtype)

        if (sharded_h and ph) or (sharded_w and pw):
            halo_h = HaloSpec.symmetric(ph if sharded_h else 0)
            halo_w = HaloSpec.symmetric(pw if sharded_w else 0)
            mask = jnp.ones(x.shape[:-1] + (1,), x.dtype)
            x, mask = halo_exchange_with_mask(
                x, mask, halo_h, halo_w, sp.axis_h, sp.axis_w, sp.grid_h, sp.grid_w,
                rep_h=sp.rep_h, rep_w=sp.rep_w,
            )
            # Remaining explicit pad for unsharded dims
            rem_ph = 0 if sharded_h else ph
            rem_pw = 0 if sharded_w else pw
        else:
            # Unsharded max needs no mask: _window_reduce pads with -inf
            # itself, and a where() against an all-ones mask is a full
            # activation pass for nothing.  Avg keeps it for the in-bounds
            # divisor (a constant XLA folds away).
            mask = (
                jnp.ones(x.shape[:-1] + (1,), x.dtype)
                if (need_mask and self.op == "avg") else None
            )
            rem_ph, rem_pw = ph, pw

        # NOTE: implemented with shifted-slice reductions rather than
        # lax.reduce_window — reduce_window's reverse-mode AD is unsupported
        # inside shard_map (jax 0.9), and for the small kernels CNNs use the
        # unrolled form fuses just as well on TPU.
        if self.op == "max":
            neg = jnp.asarray(-jnp.inf, x.dtype)
            if mask is not None:
                x = jnp.where(mask > 0, x, neg)
            y = _window_reduce(x, kh, kw, sh, sw, rem_ph, rem_pw, "max")
            return y
        # avg
        ysum = _window_reduce(x, kh, kw, sh, sw, rem_ph, rem_pw, "add")
        if self.count_include_pad or (ph == 0 and pw == 0):
            return ysum / jnp.asarray(kh * kw, x.dtype)
        div = _window_reduce(mask, kh, kw, sh, sw, rem_ph, rem_pw, "add")
        return ysum / jnp.maximum(div, 1)


@dataclasses.dataclass(frozen=True)
class GlobalAvgPool(Layer):
    """AdaptiveAvgPool2d((1,1)) + flatten (reference Classify head,
    amoebanet.py:401-417).  Under spatial sharding this is a local mean plus a
    weighted psum over the tile grid — the natural SP→LP junction for heads."""

    def init(self, key, in_shape):
        n, h, w, c = in_shape
        return {}, (n, c)

    def apply(self, params, x, ctx: ApplyCtx):
        sp = ctx.spatial
        y = jnp.mean(x, axis=(1, 2))
        if sp is not None and sp.active:
            ax = tuple(a for a in (sp.axis_h, sp.axis_w) if a)
            y = lax.pmean(y, ax)
        return y
