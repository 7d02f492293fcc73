"""Phase-decomposed input gradient for strided convolutions.

XLA computes the input grad of a stride-s conv as a conv with
``lhs_dilation=s`` — on TPU that materializes a zero-interleaved cotangent
(reshape/broadcast "data formatting" chains) and, at some shapes, chained
gather fusions.  Profiling the AmoebaNet-D 1024² bs1 step (PERF_NOTES r4)
attributed a large share of its 52.7 ms/step of backward-conv time plus
much of the 55.8 ms/step "data formatting" mass to exactly this machinery
(the reference framework never faces the issue: cuDNN has native strided
backward kernels, ``/root/reference/src/torchgems/mp_pipeline.py`` just
calls ``loss.backward()``).

Here dx is built WITHOUT zero-stuffing.  Writing padded input row
b = s·q + φ (phase φ ∈ [0, s)), the transpose of the forward

    y[p] = Σ_i x_pad[p·s + i] · w[i]

restricted to phase φ is

    dx_pad[s·q + φ] = Σ_m w[s·m + φ] · ct[q − m]

i.e. phase φ of dx_pad is the *correlation of the un-dilated cotangent
with the φ-subsampled kernel* — a plain stride-1 VALID conv of the
(Lφ−1)-padded cotangent with the flipped, io-swapped sub-kernel, exactly
the stride-1 transpose rule.  The s·s phase outputs interleave back with
ONE reshape.  FLOPs are identical to the dilated form (Σφ Lφ = k per dim);
what disappears is the gather/interleave traffic.

The weight gradient stays on XLA's conv-backprop-filter (measured
compute-bound at 36–52 TFLOPs in the same trace — not the problem).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_DIMNUMS = ("NHWC", "HWIO", "NHWC")


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _phase_dx(ct, w, strides, padding, x_shape, x_dtype):
    """dx for y = conv(x, w, strides, padding) given cotangent ct.

    ct: [N, OH, OW, Cout]; w: [KH, KW, Cin, Cout]; padding: ((phl, phh),
    (pwl, pwh)); x_shape: the forward input's [N, H, W, Cin].
    """
    n, oh, ow, cout = ct.shape
    kh, kw, cin, _ = w.shape
    sh, sw = strides
    (phl, phh), (pwl, pwh) = padding
    h, wid = x_shape[1], x_shape[2]
    hp, wp = h + phl + phh, wid + pwl + pwh
    hr, wr = _ceil_div(hp, sh), _ceil_div(wp, sw)

    wf = w.astype(ct.dtype)
    rows = []
    for fh in range(sh):
        cols = []
        lh = len(range(fh, kh, sh))
        # Valid q range for this phase: s·q + φ < hp.
        hq = _ceil_div(hp - fh, sh) if hp > fh else 0
        for fw in range(sw):
            lw = len(range(fw, kw, sw))
            wq = _ceil_div(wp - fw, sw) if wp > fw else 0
            if lh == 0 or lw == 0 or hq <= 0 or wq <= 0:
                cols.append(jnp.zeros((n, hr, wr, cin), ct.dtype))
                continue
            wsub = wf[fh::sh, fw::sw]                      # [lh, lw, cin, cout]
            wt = jnp.flip(wsub, axis=(0, 1)).swapaxes(2, 3)
            ctp = jnp.pad(ct, ((0, 0), (lh - 1, lh - 1), (lw - 1, lw - 1), (0, 0)))
            d = lax.conv_general_dilated(
                ctp, wt, (1, 1), "VALID", dimension_numbers=_DIMNUMS
            )                                              # [n, oh+lh-1, ow+lw-1, cin]
            # Crop to the phase's valid q range, then pad to the uniform
            # (hr, wr) grid.  hq can EXCEED the conv's extent when trailing
            # input rows are read by no window (h + 2p − k not divisible by
            # s) — those rows' grad is exactly zero, so the pad supplies it.
            d = d[:, : min(hq, d.shape[1]), : min(wq, d.shape[2]), :]
            d = jnp.pad(d, ((0, 0), (0, hr - d.shape[1]),
                            (0, wr - d.shape[2]), (0, 0)))
            cols.append(d)
        rows.append(jnp.stack(cols, axis=3))               # [n, hr, wr, sw, cin]
    dxp = jnp.stack(rows, axis=2)                          # [n, hr, sh, wr, sw, cin]
    dxp = dxp.reshape(n, hr * sh, wr * sw, cin)
    return dxp[:, phl : phl + h, pwl : pwl + wid, :].astype(x_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv2d_strided_t(x, w, strides, padding):
    """``lax.conv_general_dilated`` (NHWC/HWIO, groups=1) whose input grad
    uses the phase decomposition above.  ``strides``/``padding`` are static
    (tuple of ints / tuple of (lo, hi) pairs)."""
    return lax.conv_general_dilated(
        x, w, strides, padding, dimension_numbers=_DIMNUMS
    )


def _fwd(x, w, strides, padding):
    n, h, wid, c = x.shape
    # The residual is needed only by dw.  A tiny-channel x saved as-is is
    # stored in a channels-minor conv layout padded up to 42x (measured: the
    # C=3 stem input at 2048² held 2 GB across the whole backward,
    # PERF_NOTES r4); flattening (W, C) makes the saved buffer tile cleanly,
    # and the unflatten in _bwd is transient.
    xr = x.reshape(n, h, wid * c) if c < 128 else x
    return conv2d_strided_t(x, w, strides, padding), (xr, w)


def _bwd(strides, padding, res, ct):
    xr, w = res
    cin = w.shape[2]
    if xr.ndim == 3:
        n, h, wc = xr.shape
        x = xr.reshape(n, h, wc // cin, cin)
    else:
        x = xr
    dx = _phase_dx(ct, w, strides, padding, x.shape, x.dtype)
    # dw: XLA's backprop-filter (linear_transpose avoids a throwaway primal
    # forward on eager backward calls).
    w_t_fn = jax.linear_transpose(
        lambda w_: lax.conv_general_dilated(
            x, w_, strides, padding, dimension_numbers=_DIMNUMS
        ),
        w,
    )
    (dw,) = w_t_fn(ct.astype(x.dtype))
    return dx, dw


conv2d_strided_t.defvjp(_fwd, _bwd)
