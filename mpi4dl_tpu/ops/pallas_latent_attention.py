"""Causal latent attention, forward and backward, as Pallas kernels on the
arrays the projections wrote (``models/deepseek_v3.LatentAttention``).

``ops/pallas_attention.block_flash`` takes heads-first operands of one
width: to reach it the latent layer concatenated each head's rotary columns
to its ``nope`` columns, broadcast the one rotary key to every head,
transposed q, k and v to ``[H, S, w]`` and padded keys of 192 to 256 lanes,
all in HBM and again for every sequence.  Here the kernels' ``BlockSpec``
index maps pick a head's columns where they lie:

- ``q``    ``[B, S, H·nope]``: the ``nope`` columns of ``q_proj``;
- ``q_pe`` ``[B, S, H·rope]``: its ``rope`` columns after the rotary
  embedding;
- ``kv``   ``[B, S, H·(nope + v)]``: ``kv_b_proj``'s output, a head's
  ``k_nope`` then its ``v``;
- ``k_pe`` ``[B, S, rope]``: the rotary key, ONE for all heads;
- the output ``[B, S, H·v]`` in the operands' dtype, normalized at the last
  key tile: what ``o_proj`` reads.

The scores are two products summed in float32 in VMEM, ``q_nope·k_nopeᵀ +
q_pe·k_peᵀ``.  A grid step takes as many heads as make every block a whole
number of 128-lane tiles (two at the published 128 + 64 and 128 + 128) and
slices a head's columns statically.  The arithmetic is ``block_flash``'s
kernel's: operands as they come on the MXU (``Precision.DEFAULT`` said
outright), float32 scores and accumulator, tiles above the diagonal skipped,
fully-masked rows guarded.

Training: a ``custom_vjp`` whose residuals are the operands as handed in, the
output and the row statistics ``m`` and ``l`` ``[B, H, S]``.  The backward
rule (:func:`latent_flash_backward`, under the scope ``attention_core``) is
ONE kernel over the grid (sequence, group of heads, k tile, q tile):

- it READS ``q``, ``q_pe``, ``kv``, ``k_pe``, ``o`` and the cotangent ``dô``
  where they lie, a head's columns by the same index maps, and ``m`` and
  ``l`` with the queries along the lanes; a tile above the diagonal asks for
  the block it already holds, so nothing is copied for it;
- it KEEPS IN VMEM, from the first product to the last, the tiles of the
  five products with the scores TRANSPOSED (keys on the sublanes, so that
  ``m``, ``l`` and Δ are rows and need no relayout): ``sᵀ = k_nope·qᵀ +
  k_pe·q_peᵀ``, ``P̂ᵀ = exp(sᵀ − m − log l)``, ``dPᵀ = v·dôᵀ``, ``dSᵀ = P̂ᵀ ⊙
  (dPᵀ − Δ)`` with ``Δ = Σ_v dô ⊙ o`` a query (float32, computed in the
  kernel at the first k tile and kept a row a q tile), ``dv += P̂ᵀ·dô``,
  ``dk += dSᵀ·q``, ``dq += dS·k`` (the one product that contracts the
  sublanes); ``P̂`` and ``dS`` are rounded to the operands' dtype once,
  before their products, and every product sums in float32; and the float32
  accumulators: dk and dv of a k tile across the q tiles (2 MiB at the
  published widths), dq of the group's WHOLE sequence across the k tiles
  (8,192 × 2 × 192 × 4 = 12 MiB);
- it WRITES ``dq`` ``[B, S, H·nope]``, ``dq_pe`` ``[B, S, H·rope]`` (a q
  tile's rows after the last k tile its queries see) and ``dkv`` ``[B, S,
  H·(nope + v)]`` (a head's ``dk_nope`` then its ``dv``, where
  ``kv_b_proj``'s backward reads them) in the operands' dtype, scaled once
  from the float32 sums, and the rotary key's gradient as float32 partials
  ``[B, H/2, S, rope]``, a group's heads summed in the kernel; the sum over
  the groups (float32, then the operands' dtype) is the one XLA instruction
  the rule leaves.

A zero-padded row (``q = o = dô = m = 0``, ``l = 1``) and a row whose
cotangent is zero give exactly nothing.  The resident dq wants
``vmem_limit_bytes`` raised over the compiler's default 16 MiB: a v5e has
128.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.ops.pallas_attention import (
    _LANES, _NEG_INF, _out_structs, _round_up, _traced_once)

# Query and key rows of a tile, forward and backward, and the VMEM each kernel
# may take.  Measured on a v5e at 4 sequences of 8,192 tokens, 32 heads of 128
# + 64 and values of 128, bf16.  Forward (PERF.md, PR 34): 28.0 ms at (1024,
# 1024), 33.6 at (512, 1024), 28.8 at (2048, 1024), 30.4 at (1024, 2048); at
# (1024, 1024) two heads' tiles take 16.4 MB, over the compiler's default of
# 16.  Backward (PERF.md, PR 37; the rule it replaced: 248.4 ms alone): 55.7
# ms at (1024, 1024), 57.2 at (512, 1024), 57.1 at (1024, 512), 57.9 at (512,
# 512); with Δ still summed by XLA, 59.3 at (1024, 1024), 115.7 at (2048,
# 1024), 116.2 at (1024, 2048), and dq's product contracting the sublanes as
# it stands or after an explicit transpose the same to 0.2 ms.  The tiles
# are the forward's: the rule takes the caller's.
TILES = (1024, 1024)
_VMEM_LIMIT = 32 * 2 ** 20
_BWD_VMEM_LIMIT = 96 * 2 ** 20


def _heads_a_step(heads: int, widths) -> int:
    """The fewest heads whose columns fill whole 128-lane tiles in every
    operand (``widths``: a head's columns in each); all of them (a block is
    then the array's own width) where no divisor of ``heads`` does."""
    for hp in range(1, heads):
        if heads % hp == 0 and all(hp * w % _LANES == 0 for w in widths):
            return hp
    return heads


def _kernel(q_ref, qpe_ref, kv_ref, kpe_ref, o_ref, m_ref, l_ref,
            acc, m_scr, l_scr, *, hp, nope, rope, dv, tq, tk, nk, t_real,
            scale):
    """One (sequence, group of ``hp`` heads, q-tile, k-tile) step.  Scratch
    (acc, m, l: a slab a head) persists across the innermost k dimension; the
    outputs are written at the last k tile, the statistics with the queries
    along the lanes.  ``t_real``: the sequence's length before it was padded
    with zero rows to whole tiles; key slots past it are masked."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    ragged = t_real % tk != 0

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def product(a, b, contract_b):
        # DEFAULT, said outright: under a caller's "highest" default Mosaic
        # is asked for a float32 product of bf16 operands, and refuses.
        return lax.dot_general(
            a, b, (((1,), (contract_b,)), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    def fold(masked: bool):
        if masked:
            col = ki * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            mask = qi * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0) >= col
            if ragged:
                mask = mask & (col < t_real)
        k_pe = kpe_ref[0]                               # [TK, rope]
        for h in range(hp):
            q0, kv0 = h * nope, h * (nope + dv)
            s = (product(q_ref[0, :, q0:q0 + nope],
                         kv_ref[0, :, kv0:kv0 + nope], 1)
                 + product(qpe_ref[0, :, h * rope:(h + 1) * rope], k_pe, 1)
                 ) * scale                              # [TQ, TK]
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[h, :, 0]                     # [TQ]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            c = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            if masked:
                # a row with no key yet has m_new == _NEG_INF, and
                # exp(s - m_new) would count every masked key as one
                p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
            l_new = l_scr[h, :, 0] * c + jnp.sum(p, axis=-1)
            v = kv_ref[0, :, kv0 + nope:kv0 + nope + dv]
            acc[h] = acc[h] * c[:, None] + product(p.astype(v.dtype), v, 0)
            m_scr[h] = jnp.broadcast_to(m_new[:, None], m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new[:, None], l_scr.shape[1:])

    # A tile all of whose keys every one of its queries sees (its last key at
    # or before its first query, and inside the sequence) needs no mask; one
    # whose first key lies after its last query adds nothing and is skipped.
    last_key = (ki + 1) * tk - 1
    whole = last_key <= qi * tq
    if ragged:
        whole = jnp.logical_and(whole, last_key < t_real)
    pl.when(whole)(functools.partial(fold, False))
    pl.when(jnp.logical_and(jnp.logical_not(whole),
                            (qi + 1) * tq - 1 >= ki * tk))(
        functools.partial(fold, True))

    @pl.when(ki == nk - 1)
    def _():
        for h in range(hp):
            l = l_scr[h]                                # [TQ, 128], lanes alike
            o_ref[0, :, h * dv:(h + 1) * dv] = (
                acc[h] / jnp.maximum(l[:, :1], 1e-30)).astype(o_ref.dtype)
            m_ref[0, h] = m_scr[h].T[:1]
            l_ref[0, h] = l.T[:1]


@functools.partial(_traced_once, static_argnums=(4, 5, 6, 7, 8))
def _forward(q, q_pe, kv, k_pe, heads, scale, tq, tk, interpret):
    """``(o [B, S, H·v], m [B, H, S], l [B, H, S])``."""
    b, s, _ = q.shape
    nope, rope = q.shape[-1] // heads, k_pe.shape[-1]
    dv = kv.shape[-1] // heads - nope
    assert q_pe.shape == (b, s, heads * rope) and kv.shape[:2] == (b, s)
    hp = _heads_a_step(heads, (nope, rope, nope + dv, dv))
    tq, tk = min(tq, s), min(tk, s)
    s_p = _round_up(s, math.lcm(tq, tk))
    if s_p != s:        # zero rows to whole tiles: no block past an array's end
        q, q_pe, kv, k_pe = (jnp.pad(x, ((0, 0), (0, s_p - s), (0, 0)))
                             for x in (q, q_pe, kv, k_pe))
    nq, nk = s_p // tq, s_p // tk
    stat = pl.BlockSpec((1, hp, 1, tq), lambda b, g, i, j: (b, g, 0, i))
    slab = lambda w: pltpu.VMEM((hp, tq, w), jnp.float32)
    o, m, l = pl.pallas_call(
        functools.partial(_kernel, hp=hp, nope=nope, rope=rope, dv=dv, tq=tq,
                          tk=tk, nk=nk, t_real=s, scale=scale),
        grid=(b, heads // hp, nq, nk),
        in_specs=[
            pl.BlockSpec((1, tq, hp * nope), lambda b, g, i, j: (b, i, g)),
            pl.BlockSpec((1, tq, hp * rope), lambda b, g, i, j: (b, i, g)),
            pl.BlockSpec((1, tk, hp * (nope + dv)),
                         lambda b, g, i, j: (b, j, g)),
            pl.BlockSpec((1, tk, rope), lambda b, g, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, hp * dv), lambda b, g, i, j: (b, i, g)),
            stat, stat,
        ],
        scratch_shapes=[slab(dv), slab(_LANES), slab(_LANES)],
        out_shape=_out_structs(
            (q, q_pe, kv, k_pe),
            [((b, s_p, heads * dv), q.dtype),
             ((b, heads, 1, s_p), jnp.float32),
             ((b, heads, 1, s_p), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="block_flash_fwd",
    )(q, q_pe, kv, k_pe)
    return o[:, :s], m[:, :, 0, :s], l[:, :, 0, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def latent_flash(q, q_pe, kv, k_pe, heads, scale, tq=TILES[0], tk=TILES[1],
                 interpret=False):
    """Causal attention of ``heads`` heads over ``[B, S]`` tokens from the
    operands the module's text describes; returns ``[B, S, heads·v]`` in
    ``q.dtype``.  The widths come from the shapes: ``nope`` is a head of
    ``q``, ``rope`` is ``k_pe``'s, ``v`` what is left of a head of ``kv``."""
    return _forward(q, q_pe, kv, k_pe, heads, scale, tq, tk, interpret)[0]


def _latent_flash_fwd(q, q_pe, kv, k_pe, heads, scale, tq, tk, interpret):
    o, m, l = _forward(q, q_pe, kv, k_pe, heads, scale, tq, tk, interpret)
    return o, (q, q_pe, kv, k_pe, o, m, l)


def _bwd_kernel(q_ref, qpe_ref, kv_ref, kpe_ref, o_ref, do_ref, m_ref, l_ref,
                dq_ref, dqpe_ref, dkv_ref, dkpe_ref,
                dq_acc, dqpe_acc, dkv_acc, dkpe_acc, delta, *, hp, nope, rope,
                dv, tq, tk, nq, scale):
    """One (sequence, group of ``hp`` heads, k-tile, q-tile) step of the
    backward, the scores TRANSPOSED (keys on the sublanes, queries along the
    lanes, where ``m`` and ``l`` lie).  ``dkv_acc`` and ``dkpe_acc`` persist
    across the innermost q dimension: zeroed at its first tile, written at
    its last.  ``dq_acc`` and ``dqpe_acc`` hold the group's whole sequence
    across both: a q-tile's rows are zeroed at the first k tile and written
    after the last one that its queries see.  ``delta`` holds Δ = Σ_v dô·o of
    every q tile, a row a head, from the first k tile (whose keys every q
    tile sees) on.  ``dk_pe`` leaves as the float32 sum over the group's
    heads."""
    ki, qi = pl.program_id(2), pl.program_id(3)
    rows = pl.ds(pl.multiple_of(qi * tq, tq), tq)

    @pl.when(qi == 0)
    def _():
        dkv_acc[:] = jnp.zeros_like(dkv_acc)
        dkpe_acc[:] = jnp.zeros_like(dkpe_acc)

    @pl.when(ki == 0)
    def _():
        dq_acc[rows] = jnp.zeros((tq, dq_acc.shape[1]), jnp.float32)
        dqpe_acc[rows] = jnp.zeros((tq, dqpe_acc.shape[1]), jnp.float32)
        for h in range(hp):
            cols = slice(h * dv, (h + 1) * dv)
            d = jnp.sum(do_ref[0, :, cols].astype(jnp.float32)
                        * o_ref[0, :, cols].astype(jnp.float32),
                        axis=-1, keepdims=True)         # [TQ, 1]
            # to the lanes as the forward turns m and l
            delta[qi, h] = jnp.broadcast_to(d, (tq, _LANES)).T[:1]

    def product(a, b, contract):
        # DEFAULT, said outright: as in the forward kernel
        return lax.dot_general(
            a, b, ((contract[:1], contract[1:]), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    def fold(masked: bool):
        if masked:
            mask = (qi * tq + lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
                    >= ki * tk + lax.broadcasted_iota(jnp.int32, (tk, tq), 0))
        k_pe = kpe_ref[0]                               # [TK, rope]
        dk_pe = jnp.zeros((tk, rope), jnp.float32)
        for h in range(hp):
            q0, pe0, kv0 = h * nope, h * rope, h * (nope + dv)
            q = q_ref[0, :, q0:q0 + nope]               # [TQ, nope]
            q_pe = qpe_ref[0, :, pe0:pe0 + rope]        # [TQ, rope]
            k = kv_ref[0, :, kv0:kv0 + nope]            # [TK, nope]
            v = kv_ref[0, :, kv0 + nope:kv0 + nope + dv]
            do = do_ref[0, :, h * dv:(h + 1) * dv]      # [TQ, dv]
            s = (product(k, q, (1, 1)) + product(k_pe, q_pe, (1, 1))) * scale
            # P̂ = exp(s − m) ÷ l as one exponential, [TK, TQ]
            p = jnp.exp(s - (m_ref[0, h] + jnp.log(
                jnp.maximum(l_ref[0, h], 1e-30))))
            if masked:
                # also a row without a key: m = _NEG_INF there, and
                # exp(s − m) would count every masked key as one
                p = jnp.where(mask, p, 0.0)
            ds = (p * (product(v, do, (1, 1)) - delta[qi, h])).astype(q.dtype)
            dkv_acc[:, kv0 + nope:kv0 + nope + dv] += product(
                p.astype(do.dtype), do, (1, 0))
            dkv_acc[:, kv0:kv0 + nope] += product(ds, q, (1, 0))
            dk_pe += product(ds, q_pe, (1, 0))
            dq_acc[rows, q0:q0 + nope] += product(ds, k, (0, 0))
            dqpe_acc[rows, pe0:pe0 + rope] += product(ds, k_pe, (0, 0))
        dkpe_acc[:] += dk_pe

    # as the forward: a tile all of whose keys every one of its queries sees
    # needs no mask, one whose first key lies after its last query is skipped
    whole = (ki + 1) * tk - 1 <= qi * tq
    pl.when(whole)(functools.partial(fold, False))
    pl.when(jnp.logical_and(jnp.logical_not(whole),
                            (qi + 1) * tq - 1 >= ki * tk))(
        functools.partial(fold, True))

    @pl.when(ki == ((qi + 1) * tq - 1) // tk)
    def _():
        dq_ref[0, rows] = (dq_acc[rows] * scale).astype(dq_ref.dtype)
        dqpe_ref[0, rows] = (dqpe_acc[rows] * scale).astype(dqpe_ref.dtype)

    @pl.when(qi == nq - 1)
    def _():
        for h in range(hp):
            kv0 = h * (nope + dv)
            dkv_ref[0, :, kv0:kv0 + nope] = (
                dkv_acc[:, kv0:kv0 + nope] * scale).astype(dkv_ref.dtype)
            dkv_ref[0, :, kv0 + nope:kv0 + nope + dv] = (
                dkv_acc[:, kv0 + nope:kv0 + nope + dv].astype(dkv_ref.dtype))
        dkpe_ref[0, 0] = dkpe_acc[:] * scale


def _latent_flash_bwd(heads, scale, tq, tk, interpret, res, do):
    with scope("attention_core"):
        return latent_flash_backward(*res, do, heads, scale, tq, tk, interpret)


@functools.partial(_traced_once, static_argnums=(8, 9, 10, 11, 12))
def latent_flash_backward(q, q_pe, kv, k_pe, o, m, l, do, heads, scale,
                          tq=TILES[0], tk=TILES[1], interpret=False):
    """:func:`latent_flash`'s backward rule: from its operands, its output
    ``o``, the row statistics ``m`` and ``l`` ``[B, heads, S]`` and the
    output's cotangent ``do``, the cotangents ``(dq, dq_pe, dkv, dk_pe)``,
    each in its operand's shape and dtype."""
    b, s, _ = q.shape
    nope, rope = q.shape[-1] // heads, k_pe.shape[-1]
    dv = kv.shape[-1] // heads - nope
    hp = _heads_a_step(heads, (nope, rope, nope + dv, dv))
    f32 = jnp.float32
    tq, tk = min(tq, s), min(tk, s)
    s_p = _round_up(s, math.lcm(tq, tk))
    pad = s_p - s
    if pad:             # zero rows to whole tiles, as the forward pads them:
        # with q = o = dô = m = 0 and l = 1 a padded row gives exactly nothing
        q, q_pe, kv, k_pe, o, do = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                                    for x in (q, q_pe, kv, k_pe, o, do))
        m = jnp.pad(m, ((0, 0), (0, 0), (0, pad)))
        l = jnp.pad(l, ((0, 0), (0, 0), (0, pad)), constant_values=1.0)
    nq, nk, groups = s_p // tq, s_p // tk, heads // hp
    # a skipped tile (its queries before its keys) asks for the block of the
    # first tile that is not: the pipeline then copies nothing for it
    first = lambda j: (j * tk) // tq
    of_q = lambda w: pl.BlockSpec(
        (1, tq, hp * w), lambda b, g, j, i: (b, jnp.maximum(i, first(j)), g))
    kv_tile = pl.BlockSpec(
        (1, tk, hp * (nope + dv)), lambda b, g, j, i: (b, j, g))
    stat = pl.BlockSpec(
        (1, hp, 1, tq), lambda b, g, j, i: (b, g, 0, jnp.maximum(i, first(j))))
    # o is read at the first k tile alone, and stands still after it
    of_o = pl.BlockSpec((1, tq, hp * dv), lambda b, g, j, i: (
        b, jnp.where(j == 0, i, nq - 1), g))
    whole = lambda w: pl.BlockSpec(
        (1, s_p, hp * w), lambda b, g, j, i: (b, 0, g))
    dq, dq_pe, dkv, dk_pe = pl.pallas_call(
        functools.partial(_bwd_kernel, hp=hp, nope=nope, rope=rope, dv=dv,
                          tq=tq, tk=tk, nq=nq, scale=scale),
        grid=(b, groups, nk, nq),
        in_specs=[
            of_q(nope), of_q(rope), kv_tile,
            pl.BlockSpec((1, tk, rope), lambda b, g, j, i: (b, j, 0)),
            of_o, of_q(dv), stat, stat,
        ],
        out_specs=[
            whole(nope), whole(rope), kv_tile,
            pl.BlockSpec((1, 1, tk, rope), lambda b, g, j, i: (b, g, j, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((s_p, hp * nope), f32),
                        pltpu.VMEM((s_p, hp * rope), f32),
                        pltpu.VMEM((tk, hp * (nope + dv)), f32),
                        pltpu.VMEM((tk, rope), f32),
                        pltpu.VMEM((nq, hp, 1, tq), f32)],
        out_shape=_out_structs(
            (q, q_pe, kv, k_pe, o, do),
            [(q.shape, q.dtype), (q_pe.shape, q_pe.dtype),
             (kv.shape, kv.dtype), ((b, groups, s_p, rope), f32)]),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
        name="latent_flash_bwd",
    )(q, q_pe, kv, k_pe, o, do, m[:, :, None, :], l[:, :, None, :])
    return (dq[:, :s], dq_pe[:, :s], dkv[:, :s],
            jnp.sum(dk_pe, axis=1)[:, :s].astype(k_pe.dtype))


latent_flash.defvjp(_latent_flash_fwd, _latent_flash_bwd)
