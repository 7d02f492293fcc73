"""Causal latent attention's forward as a Pallas kernel on the arrays the
projections wrote (``models/deepseek_v3.LatentAttention``).

``ops/pallas_attention.block_flash`` takes heads-first operands of one
width: to reach it the latent layer concatenated each head's rotary columns
to its ``nope`` columns, broadcast the one rotary key to every head,
transposed q, k and v to ``[H, S, w]`` and padded keys of 192 to 256 lanes,
all in HBM and again for every sequence.  Here the kernel's ``BlockSpec``
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
output and the row statistics ``m`` and ``l``.  The backward builds one
sequence's heads-first q, k, v and dô from them and runs
``pallas_attention``'s tiled einsum backward on it, sequence after sequence:
with a normalized output ``dô̂ = dô ÷ l`` and ``dl = −Σ(dô·o) ÷ l``; the
rotary key's gradient is the sum over heads.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi4dl_tpu.ops.pallas_attention import (
    _LANES, _NEG_INF, _block_flash_bwd, _out_structs, _round_up)

# Query and key rows of a forward tile, and the VMEM the kernel may take for
# them.  Measured on a v5e at 4 sequences of 8,192 tokens, 32 heads of 128 +
# 64 and values of 128, bf16 (PERF.md, PR 34): 28.0 ms at (1024, 1024), 33.6
# at (512, 1024), 28.8 at (2048, 1024), 30.4 at (1024, 2048); at (1024, 1024)
# two heads' tiles take 16.4 MB, over the compiler's default of 16.
TILES = (1024, 1024)
_VMEM_LIMIT = 32 * 2 ** 20


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


def _latent_flash_bwd(heads, scale, tq, tk, interpret, res, do):
    s, nope, rope = res[0].shape[1], res[0].shape[-1] // heads, res[3].shape[-1]
    f32 = jnp.float32
    zero = jnp.zeros((), jnp.int32)

    def heads_first(x):                     # [S, H·w] as [H, S, w]
        return x.reshape(s, heads, -1).transpose(1, 0, 2)

    def tokens_first(x):                    # [H, S, w] as [S, H·w]
        return x.transpose(1, 0, 2).reshape(s, -1)

    def sequence(args):
        q, q_pe, kv, k_pe, o, m, l, do = args
        kv = heads_first(kv)
        qh = jnp.concatenate([heads_first(q), heads_first(q_pe)], axis=-1)
        kh = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (heads, s, rope))], axis=-1)
        do = heads_first(do).astype(f32)
        inv_l = 1.0 / jnp.maximum(l, 1e-30)
        dl = -jnp.sum(do * heads_first(o).astype(f32), axis=-1) * inv_l
        dq, dk, dv, _, _ = _block_flash_bwd(
            True, scale, tq, tk, interpret,
            (qh, kh, kv[..., nope:], zero, zero, None, m, None),
            (do * inv_l[..., None], None, dl))
        return (
            tokens_first(dq),
            tokens_first(jnp.concatenate([dk[..., :nope], dv], axis=-1)),
            jnp.sum(dk[..., nope:].astype(f32), axis=0).astype(k_pe.dtype))

    # dq leaves the loop whole and is taken apart outside it: sliced inside,
    # the step measured 4.5 ms longer on the chip (PERF.md, PR 34)
    dq, dkv, dk_pe = lax.map(sequence, (*res, do))
    dq = dq.reshape(*dq.shape[:2], heads, nope + rope)
    return (dq[..., :nope].reshape(*dq.shape[:2], -1),
            dq[..., nope:].reshape(*dq.shape[:2], -1), dkv, dk_pe)


latent_flash.defvjp(_latent_flash_fwd, _latent_flash_bwd)
