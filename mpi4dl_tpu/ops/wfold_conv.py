"""W-folded convolution: narrow channels made lane-dense by a reshape.

XLA lays a ``[N, H, W, C]`` activation out with C in the TPU's 128 lanes, so
a 16-channel tensor uses 16 lanes of 128: 8x its size in memory and traffic,
and 16 of the MXU's 128 output columns.  At 1024² that padding is what the
H-striped loop of ops/hstripe_conv.py exists to keep small.

A stride-1 convolution with SAME padding on W equals a convolution on
``[N, H, W/p, p·C]``: p neighbouring pixels of a row folded into the
channels, a reshape of the same row-major bytes.  Its kernel
``[kh, kw', p·Cin, p·Cout]`` (kw' = 3, or 1 for kw = 1) holds the true one p
times along a block diagonal band,

    K'[y, j, (a, i), (b, o)] = K[y, a + p·(j − j0) − b + pw, i, o]

and exact zeros where that tap is out of range.  With ``p = 128 //
min(Cin, Cout)`` every operand is lane-dense (16→16 runs as 128→128, 64→16 as
512→128): the same products summed, plus zeros, for p x the FLOPs of a
convolution that was a few ms of arithmetic.  No loop, no padded temporary.

The folded kernel is built inside the step from the true one, so parameters,
optimiser state and checkpoints know nothing of it, and the weight gradient
comes from autodiff as the sum of the p diagonal blocks, accumulated in
float32.  H padding is the caller's and passes through unchanged.

Two callers.  A convolution that stands alone takes ``wfold_conv2d``:
``fold``, the folded convolution, ``unfold``.  A run of layers between
convolutions that all fold by one p (``layers.run_fold``: ResNet v2's narrow
stage, BatchNorm, ReLU and the residual add with them) folds its input once,
calls ``wfold_conv_folded`` (folded in, folded out) and unfolds its result:
behind a folded convolution XLA keeps the activation in the convolution's
tiling, and what follows on ``[N, H, W, C]`` pays a relayout for it (BatchNorm
wrote x and x² out in float32, 64.6 ms of a 483.6 ms ResNet-110 v2 step at
1024², PERF.md PR 27 and PR 30).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_DIMNUMS = ("NHWC", "HWIO", "NHWC")
_LANES = 128


def wfold_factor(wid: int, kw: int, cin: int, cout: int, pad_w) -> int:
    """How many pixels of a row fold into the channels, or 0 where the fold
    is not exact: W padding has to be SAME and symmetric (kw odd), the
    kernel's reach within one folded pixel, and W a multiple of the fold."""
    p = _LANES // min(cin, cout)
    pw = (kw - 1) // 2
    if p <= 1 or kw % 2 == 0 or tuple(pad_w) != (pw, pw) or pw > p or wid % p:
        return 0
    return p


def _selector(p: int, kw: int) -> np.ndarray:
    """``S[j, a, b, x]``: 1 where tap x of the true kernel takes input pixel
    a of folded column q + j − j0 to output pixel b of folded column q."""
    pw = (kw - 1) // 2
    j0 = 1 if pw else 0
    j, a, b, x = np.ogrid[: 2 * j0 + 1, :p, :p, :kw]
    return (x == a + p * (j - j0) - b + pw).astype(np.float32)


def fold_kernel(w: jax.Array, p: int) -> jax.Array:
    """``[kh, kw, Cin, Cout]`` → ``[kh, kw', p·Cin, p·Cout]``.  In float32
    whatever ``w`` is: forward every element is one product with 1, and the
    transpose sums the p diagonal blocks of the folded gradient in float32
    before the cast back to ``w``'s dtype."""
    kh, kw, cin, cout = w.shape
    sel = _selector(p, kw)
    acc = jnp.promote_types(jnp.float32, w.dtype)
    wf = jnp.einsum(
        "jabx,yxio->yjaibo", sel.astype(acc), w.astype(acc),
        precision=lax.Precision.HIGHEST,
    )
    return wf.reshape(kh, sel.shape[0], p * cin, p * cout).astype(w.dtype)


def fold(x: jax.Array, p: int) -> jax.Array:
    """``[N, H, W, C]`` → ``[N, H, W/p, p·C]``: the same row-major bytes."""
    n, h, wid, c = x.shape
    return x.reshape(n, h, wid // p, p * c)


def unfold(xf: jax.Array, p: int) -> jax.Array:
    """``[N, H, W/p, p·C]`` → ``[N, H, W, C]``."""
    n, h, wq, pc = xf.shape
    return xf.reshape(n, h, wq * p, pc // p)


def _conv_folded(xf: jax.Array, wf: jax.Array, pad_h) -> jax.Array:
    reach = wf.shape[1] // 2  # folded columns the kernel reaches to each side
    return lax.conv_general_dilated(
        xf, wf, (1, 1), (tuple(pad_h), (reach, reach)),
        dimension_numbers=_DIMNUMS,
    )


def wfold_conv_folded(xf: jax.Array, w: jax.Array, pad_h, p: int) -> jax.Array:
    """The folded convolution proper, folded in and folded out, for a run
    of layers that stays on the folded form (``layers.run_fold``): xf
    ``[N, H, W/p, p·Cin]``; w the true ``[kh, kw, Cin, Cout]`` →
    ``[N, H + Σpad_h − kh + 1, W/p, p·Cout]``."""
    return _conv_folded(xf, fold_kernel(w, p), pad_h)


def wfold_conv2d(x: jax.Array, w: jax.Array, pad_h, p: int) -> jax.Array:
    """Stride-1 convolution, SAME on W, ``pad_h`` on H, folded by ``p``
    (from :func:`wfold_factor`), for a convolution that stands alone: the
    reshapes round the folded convolution.  x: [N, H, W, Cin];
    w: [kh, kw, Cin, Cout] → [N, H + Σpad_h − kh + 1, W, Cout]."""
    wf = fold_kernel(w, p)  # before the reshape, as the step was lowered before
    return unfold(_conv_folded(fold(x, p), wf, pad_h), p)
