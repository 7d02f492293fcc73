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

Training: :func:`block_flash` carries a custom VJP whose backward is ONE
Pallas kernel, :func:`block_flash_backward` (the flash-attention backward
recurrences; residuals saved are (q, k, v, o_hat, m, l), of which it reads
q, k, v and m beside the cotangents of o_hat and l).  Its grid is (B·H, Tk
tiles, Tq tiles): dk and dv of a k tile, and dq of the B·H index's whole
sequence, accumulate in float32 VMEM; it reads and writes its operands
feature-major (tokens along the lanes), so that a head of 64 is no operand
padded to 128 lanes in HBM; it masks by the same GLOBAL positions, from the
same scalar prefetch, so one compiled kernel serves every ring hop too.

Used by :func:`mpi4dl_tpu.ops.ring.ring_attention` when ``use_flash``
resolves on (auto: TPU backends).  Interpret mode runs on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

from mpi4dl_tpu.obs.scopes import scope

_NEG_INF = -1e30  # large-negative instead of -inf: exp() of it is exactly 0
                  # and max() never produces nan from (-inf) - (-inf).
_LANES = 128
# Tiles of the forward kernel (measured: beside block_flash_backward below).
LOCAL_TILES = (1024, 1024)  # query, key rows of a tile, flash_attention_local
# Under a key selection a grid step is a key-value group of heads: measured
# on a v5e at the Keye cell's shape (eight heads of 128 a group, 16,384
# tokens; PERF.md), 22.8 ms a call at (256, 1024), 21.0 at (512, 1024) and
# 20.1 at (1024, 1024), which names a VMEM limit (below).
LOCAL_TILES_SPARSE = (1024, 1024)


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


def _fwd_tiles(t_q: int, t_k: int, tq: int, tk: int):
    """The forward kernel's tiles over ``t_q`` queries and ``t_k`` keys when
    ``(tq, tk)`` are asked for: no more rows than the lengths take in whole
    sublanes and lanes."""
    return min(tq, _round_up(t_q, 8)), min(tk, _round_up(t_k, 128))


def _tile_kind(q0, k0, ki, *, tq, tk, t_k_real, causal):
    """``(whole, live)`` of the forward tile whose first query is at GLOBAL
    position ``q0`` and first key at ``k0``, the ``ki``-th k tile: whole when
    every query sees every key (its last key at or before its first query,
    and inside the ``t_k_real`` keys), live when some query sees some key
    (its last query at or after its first key).  A whole tile folds with no
    mask and no guard; a live one that is not whole (the diagonal, a ragged
    tail) with both; a tile that is not live is skipped.  The kernel reads
    it on traced scalars, :func:`causal_tile_split` on arrays of ints."""
    whole = True if t_k_real % tk == 0 else (ki + 1) * tk <= t_k_real
    if not causal:
        return whole, True
    return (k0 + tk - 1 <= q0) & whole, q0 + tq - 1 >= k0


def causal_tile_split(t_q: int, t_k: int, tq: int, tk: int):
    """``(whole, diagonal, skipped)``: how many tiles of a causal call of the
    forward kernel over ``t_q`` queries and ``t_k`` keys at zero offsets,
    asked for at tiles ``(tq, tk)``, are of each kind (:func:`_tile_kind`);
    they sum to the grid's tiles of one ``bh`` index."""
    tq, tk = _fwd_tiles(t_q, t_k, tq, tk)
    nq, nk = -(-t_q // tq), -(-t_k // tk)
    ki = np.arange(nk)[None, :]
    whole, live = (np.broadcast_to(x, (nq, nk)) for x in _tile_kind(
        np.arange(nq)[:, None] * tq, ki * tk, ki, tq=tq, tk=tk, t_k_real=t_k,
        causal=True))
    n_whole, n_diagonal = int(whole.sum()), int((live & ~whole).sum())
    return n_whole, n_diagonal, nq * nk - n_whole - n_diagonal


def _kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc, m_scr, l_scr, *, tq, tk, nk, causal, t_k_real, scale):
    """One (bh, q-tile, k-tile) step.  Scratch (acc, m, l) persists across
    the innermost k dimension; outputs are written at the last k tile.
    ``t_k_real``: un-padded key count (static) — key slots past it are
    masked out so Tk padding contributes exactly nothing.

    Both products take their operands as they come (bf16 operands run on
    the MXU as bf16; float32 ones as float32) and accumulate in float32;
    the scale is applied to the float32 scores.  A step is of one of three
    kinds (:func:`_tile_kind`, from the scalar-prefetched GLOBAL offsets):
    a whole tile folds with no mask and no guard (there the mask is all
    true and every score finite, so both would be identities); a diagonal
    or ragged tile folds masked and guarded; under ``causal`` a tile whose
    every key lies after its last query is skipped, and its k and v blocks
    are not fetched (the index maps of :func:`_block_flash_fwd_impl`)."""
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    q0 = offs_ref[0] + qi * tq          # the tile's first query
    k0 = offs_ref[1] + ki * tk          # and first key

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def fold(masked: bool):
        # DEFAULT, said outright: a caller's "highest" default (the
        # benchmark's check traces under one) asks Mosaic for a float32
        # product of bf16 operands, which it refuses.
        s = jax.lax.dot_general(                    # [TQ, TK]
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        ) * scale
        if masked:
            col = ki * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            if t_k_real % tk:
                s = jnp.where(col < t_k_real, s, _NEG_INF)
            if causal:
                q_pos = q0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
                s = jnp.where(q_pos >= offs_ref[1] + col, s, _NEG_INF)

        m_prev = m_scr[:, 0]                        # [TQ]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        c = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])             # [TQ, TK]
        if masked:
            # Guard fully-masked rows: there m_new == _NEG_INF and the naive
            # exp(s - m_new) = exp(0) = 1 would count every masked key (the
            # classic flash pitfall — causal ring hops from later devices
            # mask whole rows).
            p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
        l_new = l_scr[:, 0] * c + jnp.sum(p, axis=-1)
        v = v_ref[0]
        acc[:] = acc[:] * c[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    whole, live = _tile_kind(q0, k0, ki, tq=tq, tk=tk, t_k_real=t_k_real,
                             causal=causal)
    if whole is True:  # not causal, and no ragged tail: every tile is whole
        fold(False)
    else:
        pl.when(whole)(functools.partial(fold, False))
        pl.when(jnp.logical_and(live, jnp.logical_not(whole)))(
            functools.partial(fold, True))

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0] = acc[:].astype(o_ref.dtype)
        m_ref[0] = m_scr[...].astype(m_ref.dtype)
        l_ref[0] = l_scr[...].astype(l_ref.dtype)


def selection_width(t_k: int) -> int:
    """Words a query's row of a key selection takes: key ``s`` is bit
    ``s // W`` of word ``s % W``, so that the keys of a bit are ``W``
    consecutive ones, whole lanes (``W`` a multiple of 128)."""
    return _round_up(max(1, -(-t_k // 32)), _LANES)


def _plane(words, ki, tk, width, axis):
    """The selection of a tile of ``tk`` keys from the words of its queries
    (``axis`` 1: ``[TQ, W]`` to ``[TQ, TK]``; ``axis`` 0, transposed: ``[W,
    TQ]`` to ``[TK, TQ]``): the ``tk // width`` bits of the tile, each over
    the whole words, side by side."""
    per = tk // width
    planes = [jnp.bitwise_and(lax.shift_right_logical(
        words, jnp.full(words.shape, ki * per + i, jnp.int32)), 1) != 0
        for i in range(per)]
    return planes[0] if per == 1 else jnp.concatenate(planes, axis=axis)


def _sparse_kernel(q_ref, k_ref, v_ref, words_ref, o_ref, m_ref, l_ref,
                   m_scr, l_scr, *, rep, tq, tk, nk, t_k_real, scale, width):
    """One (key-value group, q tile, k tile) step of
    :func:`sparse_flash_forward`: the ``rep`` query heads of a group (``q_ref``
    [rep, TQ, D]) against the group's one k and v block.  The selection's
    bits of the tile (:func:`_plane` of the words of its queries), the causal
    mask and the padded keys' are combined once into one ``[TQ, TK]`` plane,
    which each head applies to its scores with one select and uses again as
    the guard of its ``exp``: where the plane holds, the score is finite, so
    ``where(plane, exp(s − m), 0)`` is the dense kernel's
    ``where(s > −∞, exp(s − m), 0)``.  Each head's scores, maxima, sums and
    products are :func:`_kernel`'s, in the same k-tile order.  The group's
    block of ``o_hat`` (``o_ref``, float32) stays in VMEM across the k tiles
    and is the accumulator; m and l of each head are kept in scratch and
    written at the last k tile, where ``o_hat`` is divided by l (the sum of a
    row that sees no key taken as 1e-30)."""
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def fold():
        col = ki * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        plane = _plane(words_ref[0], ki, tk, width, 1) & (
            qi * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0) >= col)
        if t_k_real % tk:
            plane = plane & (col < t_k_real)
        k, v = k_ref[0], v_ref[0]
        for h in range(rep):
            # DEFAULT, said outright: as in _kernel
            s = jnp.where(plane, jax.lax.dot_general(   # [TQ, TK]
                q_ref[h], k, (((1,), (1,)), ((), ())),
                precision=lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32,
            ) * scale, _NEG_INF)
            m_prev = m_scr[h, :, 0]                     # [TQ]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            c = jnp.exp(m_prev - m_new)
            p = jnp.where(plane, jnp.exp(s - m_new[:, None]), 0.0)
            l_new = l_scr[h, :, 0] * c + jnp.sum(p, axis=-1)
            o_ref[h] = o_ref[h] * c[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = jnp.broadcast_to(m_new[:, None], m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new[:, None], l_scr.shape[1:])

    # causal: the tile's last query is at or after its first key
    pl.when((qi + 1) * tq - 1 >= ki * tk)(fold)

    @pl.when(ki == nk - 1)
    def _():
        m_ref[...] = m_scr[...]
        l_ref[...] = l_scr[...]
        for h in range(rep):
            o_ref[h] = o_ref[h] / jnp.maximum(l_scr[h, :, :1], 1e-30)


def _sparse_fwd_vmem_bytes(rep, tq, tk, d, dv, width, itemsize):
    """An upper bound of the VMEM :func:`_sparse_kernel` takes: the blocks of
    a grid step double buffered (the group's q, one k and one v block, the
    words of its queries, and o, m and l of each head in float32), m and l
    of each head in scratch, about sixteen bytes an element of one head's
    [TQ, TK] tile for the plane, the scores and their kin, and 2 MiB.
    Fitted to the limits under which the kernel compiled for a v5e at the
    Keye cell's shape (PERF.md, section 6): 29.0 MiB at tiles of (512, 1024)
    (this gives 31.0), 56.7 at (1024, 1024) (59.0)."""
    blocks = 2 * (itemsize * (rep * tq * d + tk * (d + dv)) + 4 * tq * width
                  + 4 * rep * tq * (dv + 2 * _LANES))
    return blocks + 2 * 4 * rep * tq * _LANES + 16 * tq * tk + 2 ** 21


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
    tq, tk = _fwd_tiles(t_q, t_k, tq, tk)
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

    def tile_of_kv(i, j, offs):
        """Under ``causal`` a skipped tile asks for the k and v blocks of the
        last live tile of its q tile (the first, where its queries see no
        key), the pipeline then copying nothing for it."""
        if not causal:
            return j
        last = lax.div(jnp.maximum(offs[0] + (i + 1) * tq - 1 - offs[1], 0), tk)
        return jnp.minimum(j, last)

    grid = (bh, nq, nk)
    kern = pl.pallas_call(
        functools.partial(_kernel, tq=tq, tk=tk, nk=nk, causal=causal,
                          t_k_real=t_k, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tq, d_p), lambda b, i, j, offs: (b, i, 0)),
                pl.BlockSpec((1, tk, d_p), lambda b, i, j, offs: (
                    b, tile_of_kv(i, j, offs), 0)),
                pl.BlockSpec((1, tk, dv_p), lambda b, i, j, offs: (
                    b, tile_of_kv(i, j, offs), 0)),
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


def sparse_flash_forward(q, k, v, words, *, heads, scale,
                         tq=LOCAL_TILES_SPARSE[0], tk=LOCAL_TILES_SPARSE[1],
                         interpret=False):
    """Causal attention in which query ``t`` of a sequence sees only the
    keys its row of ``words`` [B, Tq, W] selects (:func:`selection_width`),
    all ``heads`` of the sequence alike, as ``(o, m, l)``:
    ``q`` [B·heads, Tq, D] and the key-value heads ``k`` [B·KV, Tk, D], ``v``
    [B·KV, Tk, Dv], query head ``h`` reading key-value head ``h // (heads //
    KV)`` (``jnp.repeat``'s order).  Positions are the sequence's own.  The
    kernel is :func:`_sparse_kernel`, ``sparse_flash_fwd``, over the grid
    (B·KV, q tile, k tile): a step reads one k and one v block for the
    group's heads and decodes one plane of the selection for them all.
    The output ``o`` = ``o_hat / l`` [B·heads, Tq, Dv] and the row maxima
    and sums m and l [B·heads, Tq], in float32 (the kernel divides: XLA's
    division, a broadcast of l to every feature, cost the Keye step's heap
    0.69 GiB; PERF.md, section 6).  Its backward is
    :func:`sparse_flash_backward`."""
    bh, t_q, d = q.shape
    bkv, t_k, _ = k.shape
    dv = v.shape[-1]
    width = words.shape[-1]
    rep = bh // bkv
    kv = heads // rep
    assert bkv * rep == bh and kv * rep == heads, (bh, bkv, heads)
    # whole lanes of keys, a multiple of the selection's width
    tq = min(tq, _round_up(t_q, 8))
    tk = _round_up(min(tk, _round_up(t_k, 128)), width)
    tq_p, tk_p = _round_up(t_q, tq), _round_up(t_k, tk)
    assert tk_p <= 32 * width, (t_k, width)
    d_p, dv_p = _round_up(d, _LANES), _round_up(dv, _LANES)
    qp = jnp.pad(q, ((0, 0), (0, tq_p - t_q), (0, d_p - d)))
    kp = jnp.pad(k, ((0, 0), (0, tk_p - t_k), (0, d_p - d)))
    vp = jnp.pad(v, ((0, 0), (0, tk_p - t_k), (0, dv_p - dv)))
    wp = jnp.pad(words, ((0, 0), (0, tq_p - words.shape[1]), (0, 0)))
    nq, nk = tq_p // tq, tk_p // tk

    def tile_of_k(i, j):
        """A tile above the diagonal (skipped) asks for the k tile of the
        last that is not, the pipeline then copying nothing for it."""
        return jnp.minimum(j, lax.div((i + 1) * tq - 1, tk))

    f32 = jnp.float32
    vmem = _sparse_fwd_vmem_bytes(rep, tq, tk, d_p, dv_p, width,
                                  q.dtype.itemsize)
    o, m, l = pl.pallas_call(
        functools.partial(_sparse_kernel, rep=rep, tq=tq, tk=tk, nk=nk,
                          t_k_real=t_k, scale=scale, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(bkv, nq, nk),
            in_specs=[
                pl.BlockSpec((rep, tq, d_p), lambda g, i, j: (g, i, 0)),
                pl.BlockSpec((1, tk, d_p),
                             lambda g, i, j: (g, tile_of_k(i, j), 0)),
                pl.BlockSpec((1, tk, dv_p),
                             lambda g, i, j: (g, tile_of_k(i, j), 0)),
                pl.BlockSpec((1, tq, width), lambda g, i, j: (g // kv, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((rep, tq, dv_p), lambda g, i, j: (g, i, 0)),
                pl.BlockSpec((rep, tq, _LANES), lambda g, i, j: (g, i, 0)),
                pl.BlockSpec((rep, tq, _LANES), lambda g, i, j: (g, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((rep, tq, _LANES), f32),
                            pltpu.VMEM((rep, tq, _LANES), f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq_p, dv_p), f32),
            jax.ShapeDtypeStruct((bh, tq_p, _LANES), f32),
            jax.ShapeDtypeStruct((bh, tq_p, _LANES), f32),
        ],
        compiler_params=(
            None if vmem <= _DEFAULT_VMEM
            else pltpu.CompilerParams(vmem_limit_bytes=vmem + 2 ** 21)),
        interpret=interpret,
        name="sparse_flash_fwd",
    )(qp, kp, vp, wp)
    return o[:, :t_q, :dv], m[:, :t_q, 0], l[:, :t_q, 0]


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


# Tiles, measured on a v5e at 32 heads of 64 over 8,192 tokens in bf16.  The
# forward alone, causal, its lane pads and slices included (PERF.md, section
# 6; wall clock, median of 30 calls): 7.45 ms at (1024, 1024), 8.66 at
# (512, 1024), 12.31 at (1024, 512); (2048, 1024) and (1024, 2048) need more
# than the compiler's 16 MiB of VMEM.  The backward kernel alone, causal
# (PERF.md, section 6): 7.87 ms at (1024, 1024), 8.26 at (512, 1024), 8.39 at
# (1024, 512), 9.13 at (512, 512), 12.44 at (256, 512), 7.95 at (2048, 1024),
# 8.00 at (1024, 2048) and 7.83 at (2048, 2048), which needs a VMEM limit
# named (below); the einsum tiles it replaced took 20.86.
_BWD_TQ, _BWD_TK = 1024, 1024  # the most query, key rows of a backward tile
# The compiler's own VMEM budget for a kernel on a v5e.  The backward kernel
# asks for more only where it needs more: a kernel that names any limit
# (even this one) makes XLA give every instruction of the program a scoped
# VMEM reservation, and the LFM2 step then takes 0.28 GiB more HBM
# (PERF.md, section 6).
_DEFAULT_VMEM = 16 * 2 ** 20

# Traced once for all the calls of a step that make it alike, and put into the
# caller's trace where it stands (its instructions carry the caller's scopes):
# a kernel's body is a large jaxpr, and on the chip's host the Kanana-2 step's
# five backward kernels took 0.8 s each to trace and 0.3 s each to lower
# (PERF.md).  Equations with one jaxpr are lowered once.
_traced_once = functools.partial(jax.jit, inline=True)


def _bwd_tile(t: int, most: int) -> int:
    """Rows of a backward tile over ``t`` rows: as few tiles as ``most``
    allows, of equal size rounded up to whole lanes (a tile of either kind
    lies along the lanes of its feature-major blocks)."""
    n = max(1, -(-t // most))
    return _round_up(max(1, -(-t // n)), _LANES)


def _bwd_vmem_bytes(tq, tk, tq_p, d, dv, itemsize):
    """An upper bound of the VMEM :func:`_bwd_kernel` takes: the resident dq
    (float32 sums and one output block), the blocks of a grid step (double
    buffered, and dk's and dv's float32 sums) and about ten bytes an element
    of a [TK, TQ] tile for the scores and their kin.  Fitted to the limits
    under which the kernel compiled for a v5e (PERF.md, section 6): 14.3 MiB at
    tiles of 1,024, heads of 64 and 8,192 tokens (this gives 15.0), 23.6 at
    keys of 192 and values of 128 (24.0), 23.1 at 32,768 tokens (24.0)."""
    resident = (4 + itemsize) * d * tq_p
    blocks = (2 * itemsize * (d + dv) * (tq + 2 * tk)
              + 4 * (d + dv) * tk)
    return resident + blocks + 10 * tq * tk


def _bwd_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, m_ref, dl_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                tq, tk, nq, nk, causal, t_k_real, scale):
    """One (bh, k-tile, q-tile) step of the backward on FEATURE-MAJOR blocks
    (``q_ref`` [1, D, TQ], ``k_ref`` [1, D, TK], …: tokens along the lanes),
    the scores transposed (keys on the sublanes, queries along the lanes,
    where ``m`` and ``dl`` lie).  ``dk_acc`` and ``dv_acc`` persist across
    the innermost q dimension: zeroed at its first tile, written at its last.
    ``dq_acc`` holds the whole sequence of the bh index across both: a q
    tile's columns are zeroed at the first k tile and written after the last
    one its queries see.  Positions are GLOBAL (``offs_ref``: the q and k
    offsets), as in the forward kernel."""
    ki, qi = pl.program_id(1), pl.program_id(2)
    cols = pl.ds(pl.multiple_of(qi * tq, tq), tq)
    q0 = offs_ref[0] + qi * tq          # the tile's first query
    k0 = offs_ref[1] + ki * tk          # and first key
    ragged = t_k_real % tk != 0

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(ki == 0)
    def _():
        dq_acc[:, cols] = jnp.zeros((dq_acc.shape[0], tq), jnp.float32)

    def product(a, b, contract):
        # DEFAULT, said outright: as in the forward kernel
        return lax.dot_general(
            a, b, ((contract[:1], contract[1:]), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    def fold(masked: bool):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = product(k, q, (0, 0)) * scale               # [TK, TQ]
        p = jnp.exp(s - m_ref[0])                       # m: [1, TQ]
        if masked:
            key = ki * tk + lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
            mask = key < t_k_real
            if causal:
                mask = mask & (q0 + lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
                               >= offs_ref[1] + key)
            # also a row without a visible key: m = _NEG_INF there, and
            # exp(s − m) would count every masked key
            p = jnp.where(mask, p, 0.0)
        ds = (p * (product(v, do, (0, 0)) + dl_ref[0])).astype(q.dtype)
        dv_acc[:] += product(do, p.astype(do.dtype), (1, 1))   # [Dv, TK]
        dk_acc[:] += product(q, ds, (1, 1))                    # [D, TK]
        dq_acc[:, cols] += product(k, ds, (1, 0))              # [D, TQ]

    # A tile all of whose keys every one of its queries sees (its last key at
    # or before its first query, and inside the keys) needs no mask; under
    # ``causal`` one whose first key lies after its last query adds nothing
    # and is skipped.
    inside = (ki + 1) * tk <= t_k_real
    if causal:
        whole = k0 + tk - 1 <= q0
        if ragged:
            whole = jnp.logical_and(whole, inside)
        pl.when(whole)(functools.partial(fold, False))
        pl.when(jnp.logical_and(jnp.logical_not(whole), q0 + tq - 1 >= k0))(
            functools.partial(fold, True))
        # the last k tile whose first key the tile's last query sees (the
        # first, where none is: its columns are then zero)
        last = jnp.minimum(
            lax.div(jnp.maximum(q0 + tq - 1 - offs_ref[1], 0), tk), nk - 1)
    else:
        if ragged:
            pl.when(inside)(functools.partial(fold, False))
            pl.when(jnp.logical_not(inside))(functools.partial(fold, True))
        else:
            fold(False)
        last = nk - 1

    @pl.when(ki == last)
    def _():
        dq_ref[0, :, cols] = (dq_acc[:, cols] * scale).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _reference_backward(q, k, v, q_off, k_off, m, do, dl, causal, scale):
    """The backward's mathematics as einsums over the whole score matrix in
    float32: what interpret mode runs under ``shard_map`` (see
    :func:`_block_flash_fwd_impl`)."""
    f32 = jnp.float32
    q32, k32, v32, do32 = (x.astype(f32) for x in (q, k, v, do))
    p = jnp.exp(jnp.einsum("bqd,bkd->bqk", q32, k32) * scale - m[..., None])
    if causal:
        q_pos = q_off + jnp.arange(q.shape[1], dtype=jnp.int32)
        k_pos = k_off + jnp.arange(k.shape[1], dtype=jnp.int32)
        p = jnp.where(q_pos[:, None] >= k_pos[None, :], p, 0.0)
    ds = p * (jnp.einsum("bqd,bkd->bqk", do32, v32) + dl[..., None])
    return ((jnp.einsum("bqk,bkd->bqd", ds, k32) * scale).astype(q.dtype),
            (jnp.einsum("bqk,bqd->bkd", ds, q32) * scale).astype(k.dtype),
            jnp.einsum("bqk,bqd->bkd", p, do32).astype(v.dtype))


@functools.partial(_traced_once, static_argnums=(8, 9, 10, 11, 12))
def block_flash_backward(q, k, v, q_off, k_off, m, do, dl, causal, scale,
                         tq, tk, interpret=False):
    """:func:`block_flash`'s backward as ONE kernel over the grid (bh, k tile,
    q tile): from the operands, the row maxima ``m`` [BH, Tq] and the
    cotangents ``do`` [BH, Tq, Dv] of ``o_hat`` and ``dl`` [BH, Tq] of ``l``,
    the cotangents ``(dq, dk, dv)`` in the operands' shapes and dtypes.

    With P = exp(s − m), ô = P·V and l = rowsum(P) (m a constant plateau:
    its cotangent is zero almost everywhere):
        dP = dô Vᵀ + dl·1ᵀ ;  dS = P ⊙ dP
        dq = dS K · scale ;  dk = dSᵀ Q · scale ;  dv = Pᵀ dô
    The scores are computed transposed (keys on the sublanes), so that m and
    dl are rows along the lanes.  ``dô`` goes to the products in the
    operands' dtype, P and dS are rounded to it once before theirs, and every
    product sums in float32: dk and dv of a k tile across the q tiles, dq of
    the bh index's whole sequence across the k tiles, all in VMEM.  A tile
    above the diagonal is skipped and asks for the blocks it already holds,
    so nothing is copied for it.  Padded keys are masked by index; padded
    queries carry dô = dl = m = 0 and give exactly nothing, as does a row
    that sees no key.  ``tq`` and ``tk`` are multiples of 128: tokens lie
    along the lanes of every block."""
    if interpret and _any_vma(q, k, v, q_off, k_off, m, do, dl):
        # as the forward: interpret mode cannot run under shard_map
        return _reference_backward(q, k, v, q_off, k_off, m, do, dl, causal,
                                   scale)
    bh, t_q, d = q.shape
    t_k, dv = k.shape[1], v.shape[-1]
    f32 = jnp.float32
    tq_p, tk_p = _round_up(t_q, tq), _round_up(t_k, tk)
    # feature-major, tokens along the lanes: a 64-wide operand row-major
    # would be padded to 128 lanes in HBM, and so would the residuals that
    # XLA lays out to match (PERF.md, section 6)
    tokens_last = lambda x, t_p: jnp.pad(
        jnp.swapaxes(x, 1, 2), ((0, 0), (0, 0), (0, t_p - x.shape[1])))
    q, do = tokens_last(q, tq_p), tokens_last(do.astype(q.dtype), tq_p)
    k, v = tokens_last(k, tk_p), tokens_last(v, tk_p)
    m, dl = (jnp.pad(x.astype(f32), ((0, 0), (0, tq_p - t_q)))[:, None, :]
             for x in (m, dl))
    nq, nk = tq_p // tq, tk_p // tk
    offs = jnp.stack([q_off, k_off]).astype(jnp.int32)
    vmem = _bwd_vmem_bytes(tq, tk, tq_p, d, dv, q.dtype.itemsize)

    def tile_of_q(j, i, offs):
        """Under ``causal`` a skipped tile (its queries before its keys) asks
        for the q tile of the first that is not, the pipeline then copying
        nothing for it."""
        if not causal:
            return i
        first = lax.div(jnp.maximum(offs[1] + j * tk - offs[0], 0), tq)
        return jnp.maximum(i, jnp.minimum(first, nq - 1))

    of_q = lambda w: pl.BlockSpec(
        (1, w, tq), lambda b, j, i, offs: (b, 0, tile_of_q(j, i, offs)))
    of_k = lambda w: pl.BlockSpec((1, w, tk), lambda b, j, i, offs: (b, 0, j))
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(_bwd_kernel, tq=tq, tk=tk, nq=nq, nk=nk,
                          causal=causal, t_k_real=t_k, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nk, nq),
            in_specs=[of_q(d), of_k(d), of_k(dv), of_q(dv), of_q(1), of_q(1)],
            out_specs=[
                # the whole sequence, written back when bh moves on: one
                # buffer, which the pipeline drains before the next bh
                pl.BlockSpec((1, d, tq_p), lambda b, j, i, offs: (b, 0, 0),
                             pipeline_mode=pl.Buffered(1)),
                of_k(d), of_k(dv),
            ],
            scratch_shapes=[pltpu.VMEM((d, tq_p), f32),
                            pltpu.VMEM((d, tk), f32),
                            pltpu.VMEM((dv, tk), f32)],
        ),
        out_shape=_out_structs(
            (q, k, v, do, m, dl, offs),
            [((bh, d, tq_p), q.dtype), ((bh, d, tk_p), k.dtype),
             ((bh, dv, tk_p), v.dtype)]),
        compiler_params=(
            None if vmem <= _DEFAULT_VMEM
            else pltpu.CompilerParams(vmem_limit_bytes=vmem + 2 ** 21)),
        interpret=interpret,
        name="block_flash_bwd",
    )(offs, q, k, v, do, m, dl)
    return (jnp.swapaxes(dq[..., :t_q], 1, 2), jnp.swapaxes(dk[..., :t_k], 1, 2),
            jnp.swapaxes(dv_[..., :t_k], 1, 2))


def _sparse_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, m_ref, l_ref,
                       words_ref, dq_ref, dk_ref, dv_ref,
                       dq_acc, dk_acc, dv_acc, dos, dl, *,
                       rep, tq, tk, nq, nk, t_k_real, scale, width):
    """One (key-value group, q tile, k tile) step of
    :func:`sparse_flash_backward`: the ``rep`` query heads of a group
    (``q_ref``, ``do_ref`` [rep, D, TQ]) against the group's one k and one v
    block (``[1, D, TK]``), FEATURE-MAJOR and the scores transposed as in
    :func:`_bwd_kernel`, whose products, in the same order, each head makes.

    At a q tile's first k tile (which every causal q tile sees) each head's
    ``dô / l`` goes to ``dos`` in the operands' dtype and ``−Δ / l``, with
    Δ = Σ dô·o over a query's features (``o_ref`` [rep, TQ, Dv], turned to
    features on the sublanes), to ``dl``.  The selection's bits of the tile
    (:func:`_plane` of the words of its queries), with the causal and the
    padded keys' masks on a tile that needs them, make one ``[TK, TQ]``
    plane that each head applies once to its ``p``.  ``dq_acc`` holds the
    group's q tile across the k tiles and is written after the last its
    queries see; ``dk_acc`` and ``dv_acc`` hold the group's whole sequence,
    summed over its heads and the q tiles in float32, and leave a k tile at
    a time at the last q tile, which sees every k tile."""
    i, j = pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32

    @pl.when((i == 0) & (j == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        for h in range(rep):
            l = jnp.maximum(l_ref[h], 1e-30)                # [1, TQ]
            do = do_ref[h].astype(f32)                      # [Dv, TQ]
            dos[h] = (do / l).astype(dos.dtype)
            dl[h] = -jnp.sum(do * o_ref[h].astype(f32).T, axis=0,
                             keepdims=True) / l

    def product(a, b, contract):
        # DEFAULT, said outright: as in the forward kernel
        return lax.dot_general(
            a, b, ((contract[:1], contract[1:]), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=f32)

    def fold(masked: bool):
        k, v = k_ref[0], v_ref[0]
        plane = _plane(words_ref[0], j, tk, width, 0)       # [TK, TQ]
        if masked:
            key = j * tk + lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
            plane = plane & (
                i * tq + lax.broadcasted_iota(jnp.int32, (tk, tq), 1) >= key)
            if t_k_real % tk:
                plane = plane & (key < t_k_real)
        # a loop of two heads an iteration: on a v5e at the Keye cell's shape
        # the call took 59.3 ms with the eight unrolled, 65.6 with four a
        # loop iteration, 35.3 with two and 35.8 with one (PERF.md, section 6)
        per = 2 if rep % 2 == 0 else 1

        def heads(t, carry):
            for u in range(per):
                h = t * per + u
                q = q_ref[h]
                s = product(k, q, (0, 0)) * scale           # [TK, TQ]
                # also a row without a visible key: m = _NEG_INF there, and
                # exp(s − m) would count every masked key
                p = jnp.where(plane, jnp.exp(s - m_ref[h]), 0.0)
                ds = (p * (product(v, dos[h], (0, 0)) + dl[h])).astype(
                    q.dtype)
                dv_acc[j] += product(dos[h], p.astype(dos.dtype), (1, 1))
                dk_acc[j] += product(q, ds, (1, 1))
                dq_acc[h] += product(k, ds, (1, 0))
            return carry

        lax.fori_loop(0, rep // per, heads, None)

    # as _bwd_kernel: a tile all of whose keys every one of its queries sees
    # needs no mask; one whose first key lies after its last query is skipped
    whole = (j + 1) * tk - 1 <= i * tq
    if t_k_real % tk:
        whole = jnp.logical_and(whole, (j + 1) * tk <= t_k_real)
    pl.when(whole)(functools.partial(fold, False))
    pl.when(jnp.logical_and(jnp.logical_not(whole),
                            (i + 1) * tq - 1 >= j * tk))(
        functools.partial(fold, True))

    @pl.when(j == jnp.minimum(((i + 1) * tq - 1) // tk, nk - 1))
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[j] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[j].astype(dv_ref.dtype)


def _sparse_bwd_vmem_bytes(rep, tq, tk, tk_p, d, dv, width, itemsize):
    """An upper bound of the VMEM :func:`_sparse_bwd_kernel` takes: the
    blocks of a grid step double buffered (the group's q, dô and dq, o in
    float32, m and l a row a head padded to eight sublanes, one k and one v
    block, dk's and dv's, the words of its queries), the group's dq, dk and
    dv sums in float32 and ``dô / l`` and ``−Δ / l`` in scratch, and about
    sixteen bytes an element of one head's [TK, TQ] tile for the plane, the
    scores and their kin.  Fitted to the limit under which the kernel
    compiled for a v5e at the Keye cell's shape (PERF.md, section 6): 62.85
    MiB at tiles of (1024, 1024) (this gives 65.25)."""
    blocks = (itemsize * (2 * rep * d * tq + rep * dv * tq + (d + dv) * 2 * tk)
              + 4 * rep * tq * dv + 2 * 4 * rep * 8 * tq + 4 * width * tq)
    scratch = (4 * (rep * d * tq + (d + dv) * tk_p + rep * 8 * tq)
               + itemsize * rep * dv * tq)
    return 2 * blocks + scratch + 16 * tq * tk


@functools.partial(_traced_once, static_argnums=(8, 9, 10, 11, 12))
def _sparse_bwd(q, k, v, o, m, l, do, words_t, heads, scale, tq, tk,
                interpret):
    """:func:`sparse_flash_backward` at tiles ``(tq, tk)``."""
    bh, t_q, d = q.shape
    bkv, t_k, _ = k.shape
    dv = v.shape[-1]
    width = words_t.shape[1]
    rep = bh // bkv
    kv = heads // rep
    assert bkv * rep == bh and kv * rep == heads, (bh, bkv, heads)
    assert tk % width == 0, (tk, width)
    f32 = jnp.float32
    tq_p, tk_p = _round_up(t_q, tq), _round_up(t_k, tk)
    assert tk_p <= 32 * width, (t_k, width)
    tokens_last = lambda x, t_p: jnp.pad(
        jnp.swapaxes(x, 1, 2), ((0, 0), (0, 0), (0, t_p - x.shape[1])))
    q, do = tokens_last(q, tq_p), tokens_last(do, tq_p)
    k, v = tokens_last(k, tk_p), tokens_last(v, tk_p)
    o = jnp.pad(o, ((0, 0), (0, tq_p - t_q), (0, 0)))
    m, l = (jnp.pad(x.astype(f32), ((0, 0), (0, tq_p - t_q)))[:, None, :]
            for x in (m, l))
    words_t = jnp.pad(words_t, ((0, 0), (0, 0), (0, tq_p - words_t.shape[2])))
    nq, nk = tq_p // tq, tk_p // tk
    vmem = _sparse_bwd_vmem_bytes(rep, tq, tk, tk_p, d, dv, width,
                                  q.dtype.itemsize)

    def tile_of_k(i, j):
        """A tile above the diagonal (skipped) asks for the k tile of the
        last that is not, the pipeline then copying nothing for it."""
        return jnp.minimum(j, lax.div((i + 1) * tq - 1, tk))

    of_q = lambda w: pl.BlockSpec((rep, w, tq), lambda g, i, j: (g, 0, i))
    of_k = lambda w: pl.BlockSpec(
        (1, w, tk), lambda g, i, j: (g, 0, tile_of_k(i, j)))
    # dk and dv leave a k tile at a time at the last q tile, and stand on the
    # first before it (the pipeline writes a block back when it moves on)
    of_dk = lambda w: pl.BlockSpec((1, w, tk), lambda g, i, j: (
        g, 0, jnp.where(i == nq - 1, j, 0)))
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(_sparse_bwd_kernel, rep=rep, tq=tq, tk=tk, nq=nq,
                          nk=nk, t_k_real=t_k, scale=scale, width=width),
        grid=(bkv, nq, nk),
        in_specs=[of_q(d), of_k(d), of_k(dv),
                  pl.BlockSpec((rep, tq, dv), lambda g, i, j: (g, i, 0)),
                  of_q(dv), of_q(1), of_q(1),
                  pl.BlockSpec((1, width, tq), lambda g, i, j: (g // kv, 0, i))],
        out_specs=[of_q(d), of_dk(d), of_dk(dv)],
        scratch_shapes=[pltpu.VMEM((rep, d, tq), f32),
                        pltpu.VMEM((nk, d, tk), f32),
                        pltpu.VMEM((nk, dv, tk), f32),
                        pltpu.VMEM((rep, dv, tq), q.dtype),
                        pltpu.VMEM((rep, 1, tq), f32)],
        out_shape=[jax.ShapeDtypeStruct((bh, d, tq_p), q.dtype),
                   jax.ShapeDtypeStruct((bkv, d, tk_p), k.dtype),
                   jax.ShapeDtypeStruct((bkv, dv, tk_p), v.dtype)],
        compiler_params=(
            None if vmem <= _DEFAULT_VMEM
            else pltpu.CompilerParams(vmem_limit_bytes=vmem + 2 ** 21)),
        interpret=interpret,
        name="sparse_flash_bwd",
    )(q, k, v, o, do, m, l, words_t)
    return (jnp.swapaxes(dq[..., :t_q], 1, 2), jnp.swapaxes(dk[..., :t_k], 1, 2),
            jnp.swapaxes(dv_[..., :t_k], 1, 2))


def sparse_flash_backward(q, k, v, o, m, l, do, words_t, *, heads, scale,
                          tq=None, tk=None, interpret=False):
    """:func:`sparse_flash_forward`'s backward, from its operands (``q``
    [B·heads, Tq, D], the key-value heads ``k`` [B·KV, Tk, D] and ``v`` [B·KV,
    Tk, Dv] as they are), the normalized output ``o`` = ``o_hat`` / l
    [B·heads, Tq, Dv], the row maxima ``m`` and sums ``l`` [B·heads, Tq], the
    cotangent ``do`` of ``o`` in the dtype the caller has it, and the words
    transposed (``words_t`` [B, W, Tq]: a tile's selection comes out keys on
    the sublanes, as its scores do): ``(dq, dk, dv)`` in the operands'
    shapes and dtypes.  With ô = o·l, the cotangents of ô and l are dô / l
    and −Δ / l, Δ = Σ dô·o a query, and the rest is the mathematics of
    :func:`block_flash_backward` (its docstring), each tile's scores masked
    by the selection.  The kernel is :func:`_sparse_bwd_kernel`,
    ``sparse_flash_bwd``, over the grid (B·KV, q tile, k tile): a step reads
    one k and one v block and decodes one plane of the selection for the
    group's heads, and dk and dv are the group's sums.  Tiles of its own,
    whole multiples of the selection's width, unless ``tq`` and ``tk`` say
    (multiples of 128, and ``tk`` of the width)."""
    return _sparse_bwd(q, k, v, o, m, l, do, words_t, heads, scale,
                       tq or _bwd_tile(q.shape[1], _BWD_TQ),
                       tk or _round_up(_bwd_tile(k.shape[1], _BWD_TK),
                                       words_t.shape[1]), interpret)


def _block_flash_bwd(causal, scale, tq, tk, interpret, res, cts):
    """:func:`block_flash`'s backward rule: :func:`block_flash_backward` at
    tiles of its own, chosen from the lengths, under the scope by which a
    device trace finds attention itself, as its callers open it round the
    forward kernel."""
    q, k, v, q_off, k_off, o, m, l = res
    do, dm, dl = cts  # dm is zero almost everywhere
    del tq, tk, o, l, dm  # the forward kernel's tiles; the backward's are its own
    with scope("attention_core"):
        dq, dk, dv = block_flash_backward(
            q, k, v, q_off, k_off, m, do, dl, causal, scale,
            _bwd_tile(q.shape[1], _BWD_TQ), _bwd_tile(k.shape[1], _BWD_TK),
            interpret)
    # Integer (position-offset) primals take float0 cotangents.
    f0 = np.zeros((), jax.dtypes.float0)
    return dq, dk, dv, f0, f0


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
