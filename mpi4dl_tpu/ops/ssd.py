"""The state-space recurrence of a Mamba-2 layer as a chunked scan.

A head keeps a state ``S`` of ``[P, N]`` (its ``P`` channels by the ``N``
values of the state); at every position

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t + D x_t

with ``A < 0`` and ``D`` one scalar a head, ``dt_t > 0`` a head and position,
and ``B_t``, ``C_t`` of ``N`` values that all heads share (one group).

:func:`ssd_chunked` computes the same function on chunks of ``chunk``
positions ("state-space duality", arXiv:2405.21060): with ``l_t`` the running
sum of ``dt_s A`` inside a chunk,

    Y_diag[t] = sum_{s<=t} (C_t . B_s) exp(l_t - l_s) dt_s x_s     (in the chunk)
    T_c       = sum_s exp(l_end - l_s) dt_s x_s B_s^T              (a chunk's own state)
    S_c       = exp(l_end) S_{c-1} + T_c                           (chunk to chunk)
    Y_off[t]  = exp(l_t) (S_{c-1} C_t)                             (what came in)

so that all but the chunk-to-chunk line are matrix products.  The four
products take their operands in ``x``'s dtype and sum in float32; ``dt``,
the decays and their running sums are float32 throughout, and the state goes
from chunk to chunk in float32 by ``lax.scan``.  ``exp`` is taken of
differences masked to ``s <= t`` before it, so never of a positive number.
The backward pass is ``jax.grad`` of this.  XLA's products: no Pallas kernel.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.compat import pcast


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array, *, chunk: int,
                count_carried: bool = False
                ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """``x [B, S, H, P]``, ``dt [B, S, H]`` (positive, after its softplus),
    ``a [H]`` (negative), ``b``, ``c`` ``[B, S, N]``, ``d [H]``: ``y [B, S,
    H, P]`` in ``x``'s dtype, and, where ``count_carried``, ``[|Y_off|^2,
    |Y_diag + Y_off|^2]`` (what of the output came through the state carried
    into a chunk, and the whole without the ``D x`` skip) in float32: two
    reductions over ``y``, a millisecond each at the published widths and
    16,384 tokens, so a model asks one layer for them; else None."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    if s % chunk:
        raise ValueError(
            f"the chunked scan takes sequences that its chunk of {chunk} "
            f"divides, not {s} positions")
    nc, f32, cd = s // chunk, jnp.float32, x.dtype
    dt = dt.astype(f32)
    # l_t: the running sum of dt_s A inside a chunk, [B, nc, Q, H], <= 0
    ell = jnp.cumsum((dt * a.astype(f32)).reshape(bsz, nc, chunk, h), axis=2)
    x = x.astype(f32).reshape(bsz, nc, chunk, h, p)
    xdt = x * dt.reshape(bsz, nc, chunk, h, 1)
    bc = b.astype(cd).reshape(bsz, nc, chunk, n)
    cc = c.astype(cd).reshape(bsz, nc, chunk, n)

    # Inside a chunk: (C_t . B_s) exp(l_t - l_s) over s <= t, a head.
    scores = jnp.einsum("bcqn,bcsn->bcqs", cc, bc, preferred_element_type=f32)
    ell_h = jnp.moveaxis(ell, 3, 2)  # [B, nc, H, Q]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal, ell_h[..., :, None] - ell_h[..., None, :], -jnp.inf))
    y_diag = jnp.einsum(
        "bchqs,bcshp->bcqhp", (scores[:, :, None] * decay).astype(cd),
        xdt.astype(cd), preferred_element_type=f32)

    # A chunk's own state, and the states from chunk to chunk.
    to_end = jnp.exp(ell[:, :, -1:] - ell)  # [B, nc, Q, H], <= 1
    own = jnp.einsum("bcsn,bcshp->bchpn", bc,
                     (xdt * to_end[..., None]).astype(cd),
                     preferred_element_type=f32)
    through = jnp.exp(ell[:, :, -1])  # a whole chunk's decay, [B, nc, H]

    def next_chunk(state, chunk_c):
        own_c, through_c = chunk_c
        # emits the state that ENTERS the chunk
        return through_c[..., None, None] * state + own_c, state

    start = jnp.zeros((bsz, h, p, n), f32)
    varying = tuple(jax.typeof(own).vma)  # inside shard_map: as the data
    if varying:
        start = pcast(start, varying, to="varying")
    _, entering = lax.scan(
        next_chunk, start,
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(through, 1, 0)))
    y_off = jnp.einsum(
        "bcqn,bchpn->bcqhp", cc, jnp.moveaxis(entering, 0, 1).astype(cd),
        preferred_element_type=f32) * jnp.exp(ell)[..., None]

    y = y_diag + y_off
    carried = (jnp.stack([jnp.sum(jnp.square(y_off)), jnp.sum(jnp.square(y))])
               if count_carried else None)
    y = (y + d.astype(f32)[:, None] * x).astype(cd)
    return y.reshape(bsz, s, h, p), carried

