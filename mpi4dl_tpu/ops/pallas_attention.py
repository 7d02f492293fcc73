"""Pallas blockwise (flash) attention for the long-context path.

The 1-D sequence-parallel module (ops/ring.py) is exact ring attention:
K/V blocks circulate the ICI ring and each device folds blocks into its
queries' output with an online softmax.  Its local block compute, written
as einsums, materializes the [B, H, Tq, Tk] score tensor between the two
matmuls — O(T_local²) HBM traffic per hop, which becomes the long-context
ceiling (134 MB fp32 at T_local = 2048, B=1, H=8).  This module fuses that
block compute into a Pallas kernel in the flash-attention style: scores
live only as a [TQ, TK] VMEM tile between the QKᵀ and P·V matmuls.

Design (deliberately different from a monolithic flash attention):

- :func:`block_flash` returns the block's UNNORMALIZED partial state
  ``(o_hat, m, l)`` — the flash m/l/o triple — instead of a normalized
  output, because ring attention must keep folding further K/V blocks in.
- :func:`mlo_merge` is the associative combine of two partial states; the
  ring body merges each hop's block state into the running state (the same
  update ops/ring.py applies inline today, so results are bit-comparable).
- normalization (o / l) happens once, after the last block.

The kernel pipelines via BlockSpec index maps only (no manual DMA): grid =
(B·H, Tq tiles, Tk tiles), with the Tk dimension innermost so the fp32
accumulator scratch persists across it (zeroed at k==0, emitted at the
last k tile).  Causal masking is by GLOBAL token position: the q/k block
offsets arrive as scalar-prefetch arguments so one compiled kernel serves
every ring hop (the k offset is a traced, device-varying value).

Training: :func:`block_flash` carries a custom VJP whose backward is a
``lax.scan`` of einsum tiles over the Tk dimension — memory stays
O(TQ·TK) per step (never the full score matrix) while the matmuls stay on
the MXU.  Reference: the flash-attention backward recurrences; residuals
saved are (q, k, v, o_hat, m, l).

Used by :func:`mpi4dl_tpu.ops.ring.ring_attention` when ``use_flash``
resolves on (auto: TPU backends).  Interpret mode runs on CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi4dl_tpu.compat import pcast
from mpi4dl_tpu.obs.scopes import scope

_NEG_INF = -1e30  # large-negative instead of -inf: exp() of it is exactly 0
                  # and max() never produces nan from (-inf) - (-inf).
_LANES = 128


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _out_structs(operands, shapes_dtypes):
    """ShapeDtypeStructs carrying the operands' union vma — under shard_map
    with vma checking, pallas_call must declare how outputs vary across mesh
    axes."""
    try:
        vma = frozenset()
        for op in operands:
            vma = vma | frozenset(jax.typeof(op).vma)
        return [
            jax.ShapeDtypeStruct(s, d, vma=vma) for s, d in shapes_dtypes
        ]
    except (AttributeError, TypeError):
        return [jax.ShapeDtypeStruct(s, d) for s, d in shapes_dtypes]


def _kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc, m_scr, l_scr, *, tq, tk, nk, causal, t_k_real, scale):
    """One (bh, q-tile, k-tile) step.  Scratch (acc, m, l) persists across
    the innermost k dimension; outputs are written at the last k tile.
    ``t_k_real``: un-padded key count (static) — key slots past it are
    masked out so Tk padding contributes exactly nothing.

    Both products take their operands as they come (bf16 operands run on
    the MXU as bf16; float32 ones as float32) and accumulate in float32;
    the scale is applied to the float32 scores.  Under ``causal`` a tile
    whose every key lies after its last query is skipped: it would add
    exactly nothing (the guard below), so only the causal half is computed."""
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def fold():
        # DEFAULT, said outright: a caller's "highest" default (the
        # benchmark's check traces under one) asks Mosaic for a float32
        # product of bf16 operands, which it refuses.
        s = jax.lax.dot_general(                    # [TQ, TK]
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        ) * scale
        col = ki * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        if t_k_real % tk:
            s = jnp.where(col < t_k_real, s, _NEG_INF)
        if causal:
            q_pos = offs_ref[0] + qi * tq + lax.broadcasted_iota(
                jnp.int32, (tq, tk), 0
            )
            s = jnp.where(q_pos >= offs_ref[1] + col, s, _NEG_INF)

        m_prev = m_scr[:, 0]                        # [TQ]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        c = jnp.exp(m_prev - m_new)
        # Guard fully-masked rows: there m_new == _NEG_INF and the naive
        # exp(s - m_new) = exp(0) = 1 would count every masked key (the
        # classic flash pitfall — causal ring hops from later devices mask
        # whole rows).
        p = jnp.where(
            s > _NEG_INF * 0.5, jnp.exp(s - m_new[:, None]), 0.0
        )                                           # [TQ, TK]
        l_new = l_scr[:, 0] * c + jnp.sum(p, axis=-1)
        v = v_ref[0]
        acc[:] = acc[:] * c[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if causal:
        # the tile's last query is at or after its first key
        pl.when(offs_ref[0] + (qi + 1) * tq - 1 >= offs_ref[1] + ki * tk)(fold)
    else:
        fold()

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0] = acc[:].astype(o_ref.dtype)
        m_ref[0] = m_scr[...].astype(m_ref.dtype)
        l_ref[0] = l_scr[...].astype(l_ref.dtype)


def _any_vma(*arrays) -> bool:
    try:
        return any(frozenset(jax.typeof(a).vma) for a in arrays)
    except (AttributeError, TypeError):
        return False


def _block_flash_fwd_impl(q, k, v, q_off, k_off, *, causal, scale,
                          tq, tk, interpret):
    """Pallas forward.  q: [BH, Tq, D]; k: [BH, Tk_total, D]; v: [BH,
    Tk_total, Dv] (fp32/bf16); Dv may differ from D (latent attention: keys
    of 192, values of 128), each padded to the lanes on its own.
    Returns (o_hat [BH, Tq, Dv] fp32, m [BH, Tq] fp32, l [BH, Tq] fp32)."""
    if interpret and _any_vma(q, k, v, q_off, k_off):
        # Interpret-mode pallas_call under shard_map trips the vma checker
        # (its BlockSpec emulation dynamic_slices varying operands with
        # uniform grid indices).  CPU tests of the SHARDED ring path run the
        # einsum reference instead — identical math; the kernel itself is
        # pinned by the uniform-context interpret tests and TPU validation.
        return _reference_mlo(q, k, v, q_off, k_off, causal, scale)
    bh, t_q, d = q.shape
    _, t_k, _ = k.shape
    dv = v.shape[-1]
    tq = min(tq, _round_up(t_q, 8))
    tk = min(tk, _round_up(t_k, 128))
    tq_p = _round_up(t_q, tq)
    tk_p = _round_up(t_k, tk)
    d_p, dv_p = _round_up(d, _LANES), _round_up(dv, _LANES)
    qp = jnp.pad(q, ((0, 0), (0, tq_p - t_q), (0, d_p - d)))
    kp = jnp.pad(k, ((0, 0), (0, tk_p - t_k), (0, d_p - d)))
    vp = jnp.pad(v, ((0, 0), (0, tk_p - t_k), (0, dv_p - dv)))
    # Padded key slots (a q·0 = 0 score would pollute m/l) are masked inside
    # the kernel by local column id against the static t_k.
    nq, nk = tq_p // tq, tk_p // tk
    offs = jnp.stack([q_off, k_off]).astype(jnp.int32)

    grid = (bh, nq, nk)
    kern = pl.pallas_call(
        functools.partial(_kernel, tq=tq, tk=tk, nk=nk, causal=causal,
                          t_k_real=t_k, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tq, d_p), lambda b, i, j, offs: (b, i, 0)),
                pl.BlockSpec((1, tk, d_p), lambda b, i, j, offs: (b, j, 0)),
                pl.BlockSpec((1, tk, dv_p), lambda b, i, j, offs: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, tq, dv_p), lambda b, i, j, offs: (b, i, 0)),
                pl.BlockSpec((1, tq, _LANES), lambda b, i, j, offs: (b, i, 0)),
                pl.BlockSpec((1, tq, _LANES), lambda b, i, j, offs: (b, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((tq, dv_p), jnp.float32),
                pltpu.VMEM((tq, _LANES), jnp.float32),
                pltpu.VMEM((tq, _LANES), jnp.float32),
            ],
        ),
        out_shape=_out_structs(
            (qp, kp, vp, offs),
            [
                ((bh, tq_p, dv_p), jnp.float32),
                ((bh, tq_p, _LANES), jnp.float32),
                ((bh, tq_p, _LANES), jnp.float32),
            ],
        ),
        interpret=interpret,
        name="block_flash_fwd",
    )
    o, m, l = kern(offs, qp, kp, vp)
    return o[:, :t_q, :dv], m[:, :t_q, 0], l[:, :t_q, 0]


def _reference_mlo(q, k, v, q_off, k_off, causal, scale):
    """Einsum reference of the block partial state (for VJP + tests)."""
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bqd,bkd->bqk", qf, k.astype(jnp.float32))
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        q_pos = q_off + jnp.arange(t_q, dtype=jnp.int32)
        k_pos = k_off + jnp.arange(t_k, dtype=jnp.int32)
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(s > _NEG_INF * 0.5, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return o, m, l


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9)
)
def block_flash(q, k, v, q_off, k_off, causal=False, scale=1.0,
                tq=256, tk=512, interpret=False):
    """Unnormalized flash partial state of one attention block.

    q: [BH, Tq, D]; k: [BH, Tk, D]; v: [BH, Tk, Dv] (Dv = D, or a width of
    its own; ``o_hat`` is Dv wide); ``q_off``/``k_off``: scalar GLOBAL
    position offsets (traced values allowed — they ride scalar prefetch).
    Returns ``(o_hat, m, l)`` with ``o_hat = exp(s - m) @ v`` and
    ``l = rowsum(exp(s - m))``; combine across blocks with
    :func:`mlo_merge`, finish with ``o_hat / l``.
    """
    return _block_flash_fwd_impl(
        q, k, v, q_off, k_off, causal=causal, scale=scale,
        tq=tq, tk=tk, interpret=interpret,
    )


def _block_flash_fwd(q, k, v, q_off, k_off, causal, scale, tq, tk, interpret):
    o, m, l = block_flash(q, k, v, q_off, k_off, causal, scale, tq, tk,
                          interpret)
    return (o, m, l), (q, k, v, q_off, k_off, o, m, l)


# Tiles, measured on a v5e at 32 heads of 64 over 8,192 tokens in bf16
# (PERF.md, PR 29): the forward kernel alone takes 15.9 ms at (256, 512),
# 13.0 at (512, 512), 8.7 at (512, 1024) and 7.3 at (1024, 1024); the
# backward's einsum tiles are fastest at 1,024 queries by 512 keys (21 ms;
# 33 at 1,024 keys, 43 at 2,048 queries), whatever the forward's were.
LOCAL_TILES = (1024, 1024)  # query, key rows of a tile, flash_attention_local
_BWD_TQ, _BWD_TK = 1024, 512  # query, key rows of a backward tile


def _block_flash_bwd(causal, scale, tq, tk, interpret, res, cts):
    """:func:`block_flash`'s backward rule, under the scope by which a device
    trace finds attention itself, as its callers open it round the forward
    kernel."""
    with scope("attention_core"):
        return _block_flash_bwd_tiles(causal, scale, tq, tk, interpret, res,
                                      cts)


def _block_flash_bwd_tiles(causal, scale, tq, tk, interpret, res, cts):
    """Blockwise backward: a scan over Tk tiles, and inside it a scan over
    Tq tiles, of einsum blocks — never more than a [BH, TQ, TK] score tile
    at a time, whatever the sequence length.  Under ``causal`` a tile whose
    every key lies after its last query is skipped (``lax.cond``): it adds
    exactly nothing.  The products take q, k, v as they come (and dô in
    their dtype) and accumulate in float32.

    Of the five products s, dq and dk run at the key width D, and dP and dv
    at the value width Dv (dô is Dv wide).

    With ô = P·V, l = rowsum(P), P = exp(s - m) (m treated as a constant
    plateau — its cotangent is zero almost everywhere):
        dP = dô Vᵀ + dl·1ᵀ ;  ds = P ⊙ dP
        dq = ds K · scale ;  dk = dsᵀ Q · scale ;  dv = Pᵀ dô
    """
    q, k, v, q_off, k_off, o, m, l = res
    do, dm, dl = cts  # dm is zero a.e.; fold dl into dP
    del o, dm, l
    bh, t_q, d = q.shape
    t_k, dv = k.shape[1], v.shape[-1]
    f32 = jnp.float32
    del tq, tk  # the forward kernel's; the backward's tiles are its own
    nk = max(1, (t_k + _BWD_TK - 1) // _BWD_TK)
    tk_c = _round_up(t_k, nk) // nk if t_k else t_k
    nq = max(1, (t_q + _BWD_TQ - 1) // _BWD_TQ)
    tq_c = _round_up(t_q, nq) // nq if t_q else t_q
    k_pad, q_pad = nk * tk_c - t_k, nq * tq_c - t_q

    def tiles(x, n, pad):
        """[BH, T, ...] as [n, BH, T/n, ...], zero rows appended."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(bh, n, x.shape[1] // n, *x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    # Padded queries have dô = dl = 0, so they add nothing to dk and dv, and
    # their dq rows are cut off; padded keys are masked by their index.
    kts, vts = tiles(k, nk, k_pad), tiles(v, nk, k_pad)
    k_ids = jnp.arange(nk * tk_c, dtype=jnp.int32).reshape(nk, tk_c)
    q_tiles = (tiles(q, nq, q_pad), tiles(do.astype(q.dtype), nq, q_pad),
               tiles(m.astype(f32), nq, q_pad), tiles(dl.astype(f32), nq, q_pad),
               (q_off + jnp.arange(nq * tq_c, dtype=jnp.int32)).reshape(nq, tq_c))

    # Under shard_map the accumulators become device-varying inside the
    # scans; their initial values must be marked varying up front.
    def vary(t):
        try:
            vma = frozenset()
            for a in (q, k, v, do):
                vma = vma | frozenset(jax.typeof(a).vma)
            return pcast(t, tuple(vma), to="varying") if vma else t
        except (AttributeError, TypeError):
            return t

    def k_tile(dq_acc, inp):
        kt, vt, ids = inp  # [BH, tk_c, D], [BH, tk_c, Dv], [tk_c]

        def q_tile(carry, qin):
            dk_t, dv_t, dq_acc = carry
            i, qt, dot, mt, dlt, q_pos = qin

            def fold(dk_t, dv_t):
                s = jnp.einsum("bqd,bkd->bqk", qt, kt,
                               preferred_element_type=f32) * scale
                mask = jnp.broadcast_to((ids < t_k)[None, :], s.shape[1:])
                if causal:
                    mask = mask & (q_pos[:, None] >= (k_off + ids)[None, :])
                s = jnp.where(mask[None], s, _NEG_INF)
                p = jnp.where(s > _NEG_INF * 0.5, jnp.exp(s - mt[..., None]), 0.0)
                dp = jnp.einsum("bqd,bkd->bqk", dot, vt,
                                preferred_element_type=f32) + dlt[..., None]
                ds = (p * dp).astype(qt.dtype)
                dq_t = jnp.einsum("bqk,bkd->bqd", ds, kt,
                                  preferred_element_type=f32)
                dk_t = dk_t + jnp.einsum("bqk,bqd->bkd", ds, qt,
                                         preferred_element_type=f32)
                dv_t = dv_t + jnp.einsum("bqk,bqd->bkd", p.astype(dot.dtype),
                                         dot, preferred_element_type=f32)
                return dk_t, dv_t, dq_t

            if causal:
                # the tile's last query is at or after its first key
                dk_t, dv_t, dq_t = lax.cond(
                    q_pos[-1] >= k_off + ids[0], fold,
                    lambda dk_t, dv_t: (dk_t, dv_t,
                                        vary(jnp.zeros((bh, tq_c, d), f32))),
                    dk_t, dv_t)
            else:
                dk_t, dv_t, dq_t = fold(dk_t, dv_t)
            # dq's tile is added where it lies: the accumulator is a loop
            # carry, updated in place, and never passes over as a whole
            dq_acc = lax.dynamic_update_index_in_dim(
                dq_acc, lax.dynamic_index_in_dim(dq_acc, i, 0, False) + dq_t,
                i, 0)
            return (dk_t, dv_t, dq_acc), None

        zero = vary(jnp.zeros((bh, tk_c, d), f32))
        zero_v = zero if dv == d else vary(jnp.zeros((bh, tk_c, dv), f32))
        (dk_t, dv_t, dq_acc), _ = lax.scan(
            q_tile, (zero, zero_v, dq_acc),
            (jnp.arange(nq, dtype=jnp.int32), *q_tiles))
        return dq_acc, (dk_t, dv_t)

    dq0 = vary(jnp.zeros((nq, bh, tq_c, d), f32))
    dq, (dks, dvs) = lax.scan(k_tile, dq0, (kts, vts, k_ids))
    dq = jnp.moveaxis(dq, 0, 1).reshape(bh, nq * tq_c, d)
    untile = lambda x: jnp.moveaxis(x, 0, 1).reshape(
        bh, nk * tk_c, x.shape[-1])[:, :t_k]
    # Integer (position-offset) primals take float0 cotangents.
    import numpy as np

    f0 = np.zeros((), jax.dtypes.float0)
    return (
        (dq[:, :t_q] * scale).astype(q.dtype),
        (untile(dks) * scale).astype(k.dtype), untile(dvs).astype(v.dtype),
        f0, f0,
    )


block_flash.defvjp(_block_flash_fwd, _block_flash_bwd)


def mlo_merge(state_a, state_b):
    """Associative combine of two flash partial states (o, m, l)."""
    o1, m1, l1 = state_a
    o2, m2, l2 = state_b
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    return (
        o1 * c1[..., None] + o2 * c2[..., None],
        m,
        l1 * c1 + l2 * c2,
    )


def flash_attention_local(q, k, v, causal=False, scale=None,
                          interpret=False):
    """Single-device exact attention via the block kernel.

    q, k: [B, T, H, D]; v: [B, T, H, Dv] (the ring module's layout).
    Returns [B, T, H, Dv] in q.dtype.  Memory: never materializes [T, T]
    scores.
    """
    b, t, h, d = q.shape
    sc = scale if scale is not None else float(1.0 / (d ** 0.5))
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * h, x.shape[1], x.shape[3])
    zero = jnp.zeros((), jnp.int32)
    qf, kf, vf = fold(q), fold(k), fold(v)
    # the kernel alone: the scope by which a device trace finds attention
    # itself, forward and recomputed (the backward rule opens its own)
    with scope("attention_core"):
        o, m, l = block_flash(qf, kf, vf, zero, zero, causal, sc,
                              *LOCAL_TILES, interpret)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, t, v.shape[3]).transpose(0, 2, 1, 3).astype(q.dtype)
