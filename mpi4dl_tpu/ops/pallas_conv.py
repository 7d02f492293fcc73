"""Pallas halo-consuming convolution — the SURVEY §7 "D2 endgame" spike.

The D2 path amortizes halo exchange over fused layer runs (ops/d2.py); its
hot op is then a stride-1 conv that consumes a pre-exchanged margin: input
``[H + kh-1, W + kw-1, Cin]`` → VALID conv → ``[H, W, Cout]``.  This module
implements that op as a Pallas TPU kernel, formulated as implicit GEMM so
the FLOPs land on the MXU:

    out[y, x, :] = Σ_{dy, dx}  X[y+dy, x+dx, :] @ W[dy, dx, :, :]

Grid = (H tiles, W tiles, Cout tiles).  Each program DMAs its overlapping
input window HBM→VMEM (windows overlap by the margin, so the input stays
unblocked in ANY/HBM and the kernel slices with element-granular ``pl.ds``),
then accumulates the kh·kw shifted ``[TH·TW, Cin] @ [Cin, TCO]`` matmuls in
an fp32 VMEM scratch.

Scope (deliberate, per VERDICT r3 task 9 "measure, then decide"):
- forward only — adoption into Conv2d.apply is gated on the micro-benchmark
  (benchmarks/communication/halo/benchmark_pallas_conv.py) beating XLA's
  conv by >10% on real hardware; XLA's conv is the production path today.
- stride 1 (the fused-run hot case; strided convs stay on XLA).

Channel counts are zero-padded to the 128-lane width and H/W to the tile
grid by the wrapper; the un-padded result is sliced back out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _kernel(x_any, w_any, o_ref, xwin, wbuf, acc, sem, wsem,
            *, kh, kw, th, tw, tww, tco, relu=False):
    """One (H-tile, W-tile, Cout-tile) program.

    The input window carries the FULL Cin depth — deep layers shrink the H
    tile (wrapper) instead of chunking Cin in-kernel.  An earlier revision
    chunked Cin through slot-reused DMA scratch; hardware runs showed that
    races: Mosaic does not fence a DMA write into VMEM against in-flight
    vector/MXU reads of the same buffer, so the chunk DMA landed while the
    previous chunk's matmuls were still reading (WAR hazard — wrong sums at
    n_ci >= 3, verified against a pure-DMA addressing probe that was exact).
    Keeping Cin whole means every scratch buffer is written by exactly one
    DMA per (i, j) visit, waited before first read — no reuse, no race.
    This is no longer only a comment: pallascheck's DMA-discipline pass
    (analysis/pallascheck/interp.py) walks this kernel's jaxpr over the
    full grid and fails the build on any read of a DMA destination before
    its wait or write to a DMA source while the copy is in flight — the
    exact hazard class the chunked revision hit on hardware.

    The window DMA is guarded on the first Cout tile: scratch persists
    across the (innermost) Cout grid dimension, so the same window serves
    every Cout tile without re-reading HBM.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    c = pl.program_id(2)

    # Mosaic requires HBM slice extents on the sublane dim (W here) to be
    # multiples of the 8-row tiling — `tww` is tw+kw-1 rounded up to 8
    # (the wrapper pads the input so the over-read stays in bounds).
    win_copy = pltpu.make_async_copy(
        x_any.at[pl.ds(i * th, th + kh - 1), pl.ds(j * tw, tww), :],
        xwin,
        sem,
    )
    w_copy = pltpu.make_async_copy(
        w_any.at[:, :, :, pl.ds(c * tco, tco)],
        wbuf,
        wsem,
    )

    w_copy.start()

    @pl.when(c == 0)
    def _():
        win_copy.start()
        win_copy.wait()
        if relu:
            # Fused ReLU prologue: one VMEM-local pass over the window
            # (margins included — elementwise, identical to relu-then-conv).
            # A plain vector write AFTER the DMA wait: ordinary dataflow
            # ordering, not the DMA-vs-vector hazard documented above.
            xwin[:] = jnp.maximum(xwin[:], 0)

    w_copy.wait()
    acc[:] = jnp.zeros_like(acc)
    for dy in range(kh):
        for dx in range(kw):
            xs = xwin[dy : dy + th, dx : dx + tw, :].reshape(th * tw, -1)
            acc[:] += jnp.dot(
                xs, wbuf[dy, dx], preferred_element_type=jnp.float32
            )
    o_ref[:] = acc[:].reshape(th, tw, tco).astype(o_ref.dtype)


def _kernel_stats(x_any, w_any, o_ref, s_ref, sq_ref, xwin, wbuf, acc, sem,
                  wsem, *, kh, kw, th, tw, tww, tco, relu, win):
    """The fused-epilogue variant: conv (+ optional ReLU prologue) plus
    per-program partial BN statistics of the CAST output over the static
    stat window ``win`` = (h0, h1, w0, w1) in out coords (excludes padding
    and any not-yet-consumed D2 margin, mirroring BatchNorm's stat_x
    slicing).  Statistics are taken over the cast (compute-dtype) output
    with fp32 accumulation — the same numbers the unfused BatchNorm
    computes from the conv's output tensor."""
    _kernel(x_any, w_any, o_ref, xwin, wbuf, acc, sem, wsem,
            kh=kh, kw=kw, th=th, tw=tw, tww=tww, tco=tco, relu=relu)
    i = pl.program_id(0)
    j = pl.program_id(1)
    h0, h1, w0, w1 = win
    # The window mask is built on the accumulator's own [th*tw, tco] layout,
    # one column that broadcasts along the lanes: Mosaic refuses the
    # [th, tw] -> [th, tw, 1] shape cast a 2-D mask would need.
    p = jax.lax.broadcasted_iota(jnp.int32, (th * tw, 1), 0)
    ri = p // tw + i * th
    ci = p % tw + j * tw
    valid = (ri >= h0) & (ri < h1) & (ci >= w0) & (ci < w1)
    yf = acc[:].astype(o_ref.dtype).astype(jnp.float32)
    yv = jnp.where(valid, yf, 0.0)
    s_ref[0, 0] = jnp.sum(yv, axis=0, keepdims=True)
    sq_ref[0, 0] = jnp.sum(yv * yv, axis=0, keepdims=True)


# Per-core VMEM pool the kernel budgets against (~16 MiB on current TPUs;
# see the Pallas guide).  The caps below are DERIVED splits of this pool —
# not hand-maintained constants — and the static verifier
# (analysis/pallascheck) re-derives the per-grid-point total from the traced
# specs and certifies it against this same number, so the splits cannot
# silently drift past what a core can hold.
_VMEM_BYTES = 16 * 1024 * 1024
# Input-window scratch share (3/8 = 6 MiB): the H tile halves until the
# full-Cin window fits, so deep layers (cin 1024-2048) run instead of dying
# in an opaque Mosaic allocation error.
_WINDOW_BUDGET = (3 * _VMEM_BYTES) // 8
# Weight-slab share (1/2 = 8 MiB) for the per-Cout-tile slab
# [kh, kw, Cin, tco] — beyond this the kernel would not fit VMEM alongside
# the window; callers should fall back to XLA's conv (Conv2d's dispatch
# checks pallas_conv_eligible).  The remaining 1/8 of the pool plus
# whatever the shrink loops free covers the fp32 accumulator and the
# double-buffered output block — bounded by _vmem_total_bytes below.
_WSLAB_CAP = _VMEM_BYTES // 2
# Default Cout tile — shared by halo_conv2d, the eligibility gate, and
# _bwd's fallback check so their slab math cannot drift apart.
_DEFAULT_TCO = 128


# Default W tile — shared with the eligibility gate's window math.
_DEFAULT_TW = 128


def _cpad(c: int) -> int:
    """Channel padding target: the 128-lane width, always.  A sub-128 pad
    was tried for the tiny-channel huge-spatial regime (ResNet C∈{3,16}) and
    REJECTED by Mosaic on hardware: a DMA window slice of a sub-128 channel
    extent lowers to a lane-dim memref_slice, which Mosaic refuses (both for
    the input window and the weight slab).  Tiny-channel shapes therefore
    must NOT take this kernel (the 128-pad multiplies the whole input in
    HBM — 42.7x for C=3); they use ops/hstripe_conv.py instead."""
    return _round_up(c, 128)


def _wslab_bytes(c: int, kh: int, kw: int, tco: int, itemsize: int) -> int:
    return kh * kw * _cpad(c) * tco * itemsize


def _win_bytes(c: int, kh: int, kw: int, th: int, tw: int, itemsize: int) -> int:
    """Bytes of the [th + kh-1, round8(tw + kw-1), Cin_pad] input-window
    scratch — the same formula the wrapper's H-tile shrink loop minimizes."""
    return (th + kh - 1) * _round_up(tw + kw - 1, 8) * _cpad(c) * itemsize


def _vmem_total_bytes(cin: int, kh: int, kw: int, th: int, tw: int,
                      tco: int, in_item: int, w_item: int,
                      out_item: int) -> int:
    """Per-grid-point VMEM of one program: input window + weight slab +
    fp32 accumulator scratch + the double-buffered output block (the Pallas
    pipeline keeps two output buffers in flight).  This is the model the
    wrapper's shrink loop bounds by ``_VMEM_BYTES`` and pallascheck's VMEM
    certification re-derives from the traced ``pallas_call`` specs — the
    first full verifier run flagged the fp32-at-default-tiles config at
    ~17.2 MiB (window 4.6 + slab 0.6 + acc 4 + 2x4 out), which the
    window-only budget could not see."""
    return (
        _win_bytes(cin, kh, kw, th, tw, in_item)
        + _wslab_bytes(cin, kh, kw, tco, w_item)
        + th * tw * tco * 4
        + 2 * th * tw * tco * out_item
    )


def pallas_conv_eligible(cin: int, cout: int | None = None, kh: int = 3,
                         kw: int = 3, tco: int = _DEFAULT_TCO,
                         itemsize: int = 2) -> bool:
    """True when the kernel's VMEM scratch fits its caps — the dispatch-time
    check mirroring the wrapper's trace-time errors.  Two bounds:

    - weight slab [kh, kw, Cin, tco] within ``_WSLAB_CAP``; when ``cout`` is
      given, the backward dx conv's io-swapped slab [kh, kw, Cout, tco] must
      fit too (``_bwd`` runs the same kernel with Cin/Cout exchanged);
    - input window within ``_WINDOW_BUDGET`` at the SMALLEST H tile (th=1) —
      tall-kernel deep-Cin shapes (e.g. 7x1 at Cin ~4k) can pass the slab cap
      yet have no fitting window, which previously surfaced as an opaque
      Mosaic allocation error instead of a clean lax.conv fallback;
    - the TOTAL per-grid-point model (window + slab + accumulator +
      double-buffered out block, ``_vmem_total_bytes``) within the VMEM
      pool at th=1 — two under-cap pieces can still sum past the core."""
    ok = (
        _wslab_bytes(cin, kh, kw, tco, itemsize) <= _WSLAB_CAP
        and _win_bytes(cin, kh, kw, 1, _DEFAULT_TW, itemsize) <= _WINDOW_BUDGET
        and _vmem_total_bytes(cin, kh, kw, 1, _DEFAULT_TW, tco, itemsize,
                              itemsize, itemsize) <= _VMEM_BYTES
    )
    if cout is not None:
        ok = ok and pallas_conv_eligible(cout, None, kh, kw, tco, itemsize)
    return ok


@functools.partial(
    jax.jit, static_argnames=(
        "th", "tw", "tco", "interpret", "out_dtype", "fuse_relu",
        "stat_window",
    )
)
def halo_conv2d(
    x: jax.Array,
    w: jax.Array,
    th: int = 64,
    tw: int = 128,
    tco: int = _DEFAULT_TCO,
    out_dtype=None,
    interpret: bool = False,
    fuse_relu: bool = False,
    stat_window=None,
):
    """VALID stride-1 conv consuming a pre-exchanged margin.

    x: [N, H + kh-1, W + kw-1, Cin] (margin already present — halo-exchanged
       under SP, or ``jnp.pad`` for the single-device case);
    w: [kh, kw, Cin, Cout].  Returns [N, H, W, Cout].

    ``th`` is an upper bound: it halves until the full-Cin input window fits
    the VMEM budget (Cin is never chunked — see the WAR-hazard note on
    ``_kernel``).

    ``fuse_relu`` applies ReLU to the input window in VMEM (one pass, no
    HBM round-trip for the pre-activation).  ``stat_window=(h0,h1,w0,w1)``
    (out coords) additionally returns fp32 partial BN statistics
    ``(y, sum, sumsq)`` of the cast output over that window, summed over
    batch/tiles to shape [Cout] — the epilogue that lets the kernel compete
    with XLA's conv+BN+ReLU fusion at step level (VERDICT r4 task 5).
    """
    n, hp, wp, cin = x.shape
    kh, kw, wcin, cout = w.shape
    assert wcin == cin, (wcin, cin)
    h, wid = hp - (kh - 1), wp - (kw - 1)
    assert h > 0 and wid > 0, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype

    cin_p = _cpad(cin)
    wslab = _wslab_bytes(cin, kh, kw, tco, w.dtype.itemsize)
    if wslab > _WSLAB_CAP:
        raise ValueError(
            f"pallas halo_conv2d: weight slab {wslab} B for cin={cin} "
            f"kh*kw={kh * kw} exceeds the VMEM cap {_WSLAB_CAP} B — use "
            f"lax.conv for this layer (pallas_conv_eligible gates dispatch)"
        )
    # Narrow images need no full-width W tile: clamping tw to the real width
    # keeps deep-Cin narrow shapes inside the window budget (the gate stays
    # conservative at tw=128 — it has no W — so dispatch merely declines
    # them; direct callers get the capability).
    tw = min(tw, max(wid, 8))
    while th > 1 and _win_bytes(cin, kh, kw, th, tw, x.dtype.itemsize) > _WINDOW_BUDGET:
        th //= 2
    if _win_bytes(cin, kh, kw, th, tw, x.dtype.itemsize) > _WINDOW_BUDGET:
        raise ValueError(
            f"pallas halo_conv2d: input window "
            f"{_win_bytes(cin, kh, kw, th, tw, x.dtype.itemsize)} B at the "
            f"minimum H tile (th={th}) for cin={cin} kh={kh} kw={kw} tw={tw} "
            f"exceeds the VMEM window budget {_WINDOW_BUDGET} B — use "
            f"lax.conv for this layer (pallas_conv_eligible gates dispatch)"
        )
    # Bound the TOTAL per-grid-point model, not just the window: the fp32
    # accumulator and the double-buffered output block scale with th too,
    # and at fp32 defaults (th=64, tw=tco=128) the sum exceeds the 16 MiB
    # pool even though window and slab are each under their caps — the
    # verifier's first full run surfaced exactly this (pallascheck
    # vmem-overbudget; see _vmem_total_bytes).
    out_item = jnp.dtype(out_dtype).itemsize
    while th > 1 and _vmem_total_bytes(
        cin, kh, kw, th, tw, tco, x.dtype.itemsize, w.dtype.itemsize,
        out_item,
    ) > _VMEM_BYTES:
        th //= 2
    if _vmem_total_bytes(cin, kh, kw, th, tw, tco, x.dtype.itemsize,
                         w.dtype.itemsize, out_item) > _VMEM_BYTES:
        raise ValueError(
            f"pallas halo_conv2d: per-grid-point VMEM total at the minimum "
            f"H tile (th={th}) for cin={cin} kh={kh} kw={kw} tw={tw} "
            f"tco={tco} exceeds the {_VMEM_BYTES} B pool — use lax.conv "
            f"for this layer (pallas_conv_eligible gates dispatch)"
        )
    cout_p = _round_up(cout, tco)
    h_p = _round_up(h, th)
    w_p = _round_up(wid, tw)
    # DMA window width rounded to the 8-row sublane tiling (Mosaic slice
    # alignment); the input's W is padded so the last tile's over-read of
    # (tww - tw - (kw-1)) columns stays in bounds.
    tww = _round_up(tw + kw - 1, 8)
    x_p = jnp.pad(
        x,
        ((0, 0), (0, h_p - h), (0, w_p + tww - tw - (kw - 1) - wid),
         (0, cin_p - cin)),
    )
    w_pd = jnp.pad(w, ((0, 0), (0, 0), (0, cin_p - cin), (0, cout_p - cout)))

    grid = (h_p // th, w_p // tw, cout_p // tco)
    # Under shard_map with vma checking, pallas_call must declare how its
    # output varies across mesh axes: the union of the inputs' vma.
    def _struct(shape, dtype):
        try:
            vma = frozenset(jax.typeof(x).vma) | frozenset(jax.typeof(w).vma)
            return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
        except (AttributeError, TypeError):
            return jax.ShapeDtypeStruct(shape, dtype)

    scratch = [
        pltpu.VMEM((th + kh - 1, tww, cin_p), x.dtype),
        pltpu.VMEM((kh, kw, cin_p, tco), w.dtype),
        pltpu.VMEM((th * tw, tco), jnp.float32),
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA,
    ]
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    o_spec = pl.BlockSpec(
        (th, tw, tco), lambda i, j, c: (i, j, c), memory_space=pltpu.VMEM
    )
    if stat_window is None:
        call = pl.pallas_call(
            functools.partial(
                _kernel, kh=kh, kw=kw, th=th, tw=tw, tww=tww, tco=tco,
                relu=fuse_relu,
            ),
            out_shape=_struct((h_p, w_p, cout_p), out_dtype),
            grid=grid,
            in_specs=in_specs,
            out_specs=o_spec,
            scratch_shapes=scratch,
            interpret=interpret,
        )
        y = jax.vmap(call, in_axes=(0, None))(x_p, w_pd)
        return y[:, :h, :wid, :cout]
    # One [1, tco] row per program.  The unit dim before the lanes is the
    # ARRAY's own extent there: Mosaic requires a block's last two dims to
    # be (8, 128)-divisible or equal to the array's, and a (1, 1, tco) block
    # of a [gi, gj, Cout] array is neither (refused when compiled for v5e).
    stat_shape = (grid[0], grid[1], 1, cout_p)
    stat_spec = pl.BlockSpec(
        (1, 1, 1, tco), lambda i, j, c: (i, j, 0, c), memory_space=pltpu.VMEM
    )
    call = pl.pallas_call(
        functools.partial(
            _kernel_stats, kh=kh, kw=kw, th=th, tw=tw, tww=tww, tco=tco,
            relu=fuse_relu, win=tuple(stat_window),
        ),
        out_shape=(
            _struct((h_p, w_p, cout_p), out_dtype),
            _struct(stat_shape, jnp.float32),
            _struct(stat_shape, jnp.float32),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(o_spec, stat_spec, stat_spec),
        scratch_shapes=scratch,
        interpret=interpret,
    )
    y, s, ss = jax.vmap(call, in_axes=(0, None))(x_p, w_pd)
    return (
        y[:, :h, :wid, :cout],
        jnp.sum(s, axis=(0, 1, 2, 3))[:cout],
        jnp.sum(ss, axis=(0, 1, 2, 3))[:cout],
    )


def conv_flops(n: int, h: int, w: int, cin: int, cout: int, kh: int, kw: int) -> int:
    """MAC-based FLOPs of the VALID conv (2 flops per MAC)."""
    return 2 * n * h * w * cin * cout * kh * kw


# ---------------------------------------------------------------------------
# Differentiable wrapper: custom VJP so the kernel can train.
#
#   y[n,p,q,co] = Σ_{dy,dx,ci} x[n,p+dy,q+dx,ci] · w[dy,dx,ci,co]
#   dx[n,a,b,ci] = Σ ct[n,a-dy,b-dx,co] · w[dy,dx,ci,co]
#              = VALID conv of ct zero-padded by (kh-1, kw-1) with the
#                spatially-flipped, io-swapped kernel — the SAME primitive.
#   dw = XLA's conv-backprop-filter (via jax.vjp of the lax reference conv:
#        a full-spatial reduction that is not this kernel's shape).
# ---------------------------------------------------------------------------


def _auto_interpret(interpret: bool) -> bool:
    # Pallas TPU kernels need the interpreter on CPU hosts (tests / smoke).
    return interpret or jax.default_backend() == "cpu"


def _lax_valid_conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def halo_conv2d_t(x: jax.Array, w: jax.Array, interpret: bool = False) -> jax.Array:
    """Trainable (custom-VJP) form of :func:`halo_conv2d` with default tiles."""
    return halo_conv2d(x, w, interpret=_auto_interpret(interpret))


def _fwd(x, w, interpret):
    return halo_conv2d(x, w, interpret=_auto_interpret(interpret)), (x, w)


def _bwd(interpret, res, ct):
    x, w = res
    kh, kw = w.shape[0], w.shape[1]
    # dx: margin-consuming conv of the padded cotangent with flip+swap(w);
    # its output is exactly x's (padded-input) shape.
    ct_pad = jnp.pad(ct, ((0, 0), (kh - 1, kh - 1), (kw - 1, kw - 1), (0, 0)))
    w_t = jnp.flip(w, axis=(0, 1)).swapaxes(2, 3)
    if pallas_conv_eligible(w_t.shape[2], None, kh, kw, _DEFAULT_TCO,
                            ct.dtype.itemsize):
        dx = halo_conv2d(
            ct_pad, w_t.astype(ct.dtype), out_dtype=x.dtype,
            interpret=_auto_interpret(interpret),
        )
    else:
        # Swapped slab (Cin' = forward Cout) too big for VMEM: same math on
        # XLA's conv.  Reached only when halo_conv2d_t is called directly —
        # Conv2d's dispatch gate bounds both directions.
        dx = _lax_valid_conv(ct_pad, w_t.astype(ct.dtype)).astype(x.dtype)
    # dw: XLA's backprop-filter.  linear_transpose (the conv is linear in w)
    # avoids jax.vjp's throwaway primal forward on eager backward calls.
    w_t_fn = jax.linear_transpose(lambda w_: _lax_valid_conv(x, w_), w)
    (dw,) = w_t_fn(ct.astype(x.dtype))
    return dx, dw


halo_conv2d_t.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Fused relu→conv→BN-stats op (VERDICT r4 task 5: the kernel's one fair shot
# against XLA's conv+BN+ReLU fusion at step level).
#
#   (y, s, ss) = (conv(relu(x), w),
#                 Σ_win cast(y),  Σ_win cast(y)²)      win ⊂ out coords
#
# The ReLU rides the window DMA (no HBM pass for the pre-activation) and the
# statistics ride the accumulator cast (no re-read of y for BN's reduce).
# VJP (manual, no primal recompute):
#   dy_total = ct_y + 1_win·(ct_s + 2·y·ct_ss)
#   dx       = relu'(x) ⊙ conv(pad(dy_total), flip+swap(w))
#   dw       = conv-backprop-filter(relu(x), dy_total)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_relu_conv_bn_t(x: jax.Array, w: jax.Array, stat_window,
                         interpret: bool = False):
    """Trainable fused op: returns ``(y, sum, sumsq)`` with y = conv(relu(x),
    w) (VALID, margin-consuming) and fp32 statistics of the cast output over
    ``stat_window`` = (h0, h1, w0, w1) in out coords."""
    return halo_conv2d(
        x, w, interpret=_auto_interpret(interpret), fuse_relu=True,
        stat_window=tuple(stat_window),
    )


def _fused_fwd(x, w, stat_window, interpret):
    y, s, ss = fused_relu_conv_bn_t(x, w, stat_window, interpret)
    return (y, s, ss), (x, w, y)


def _fused_bwd(stat_window, interpret, res, cts):
    x, w, y = res
    ct_y, ct_s, ct_ss = cts
    h0, h1, w0, w1 = stat_window
    # Statistics backward: only the stat window receives the broadcast
    # ct_s and the 2·y·ct_ss term (fp32, then back to the compute dtype).
    y_win = y[:, h0:h1, w0:w1, :].astype(jnp.float32)
    dwin = ct_s[None, None, None, :] + 2.0 * y_win * ct_ss[None, None, None, :]
    dy = ct_y.astype(jnp.float32)
    dy = dy.at[:, h0:h1, w0:w1, :].add(dwin)
    dy = dy.astype(ct_y.dtype)
    # Conv backward — same structure as _bwd, plus the ReLU mask on dx and
    # relu(x) as the dw primal.
    kh, kw = w.shape[0], w.shape[1]
    ct_pad = jnp.pad(dy, ((0, 0), (kh - 1, kh - 1), (kw - 1, kw - 1), (0, 0)))
    w_t = jnp.flip(w, axis=(0, 1)).swapaxes(2, 3)
    if pallas_conv_eligible(w_t.shape[2], None, kh, kw, _DEFAULT_TCO,
                            dy.dtype.itemsize):
        dx_lin = halo_conv2d(
            ct_pad, w_t.astype(dy.dtype), out_dtype=x.dtype,
            interpret=_auto_interpret(interpret),
        )
    else:
        dx_lin = _lax_valid_conv(ct_pad, w_t.astype(dy.dtype)).astype(x.dtype)
    dx = jnp.where(x > 0, dx_lin, jnp.zeros((), dx_lin.dtype))
    xr = jax.nn.relu(x)
    w_t_fn = jax.linear_transpose(lambda w_: _lax_valid_conv(xr, w_), w)
    (dw,) = w_t_fn(dy.astype(xr.dtype))
    return dx, dw


fused_relu_conv_bn_t.defvjp(_fused_fwd, _fused_bwd)
