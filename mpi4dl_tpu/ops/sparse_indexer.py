"""DeepSeek Sparse Attention's indexer: its scores, the exact top-k key
selection, and the gradient of its KL loss, for one layer's sequences.

With ``iq`` ``[B, T, Hi, Di]`` (the indexer's rotated queries), ``ik``
``[B, T, Di]`` (its one key head) and ``w`` ``[B, T, Hi]`` (its head
weights, scales included), the score of query ``t`` for key ``s <= t`` is

    I[t, s] = sum_j w[t, j] * relu(iq[t, j] . ik[s])

and ``t`` selects the ``min(topk, t + 1)`` keys of the largest scores, a tie
going to the lower position: ONE set for all the attention's heads.  The
selection is exact: the ``topk``-th largest score of a row is found by
bisection over the bits of the scores' order-preserving int32 images (32
passes, each a count), then ties by a bisection over positions; no sort and
nothing approximate.

A selection leaves here as words (``ops/pallas_attention.selection_width``:
key ``s`` is bit ``s // W`` of word ``s % W``), transposed, ``[B, W, T]``:
what the attention's backward kernel reads, and, swapped, its forward.

The indexer loss (DeepSeek-V3.2-Exp, the sparse stage) is ``mean_t KL(p_t ||
softmax_{s in S_t} I[t, s])``, ``p_t`` the main attention's probabilities
over the selected keys, summed over its heads and L1-normalised.  ``p`` and
the indexer's input are constants to it, and nothing is differentiable
through the selection, so its gradient is ``(softmax(I) - p) / N`` on the
selected pairs alone (``N`` the positions of the batch), taken to ``iq``,
``ik`` and ``w`` through the ReLU: :func:`indexer_grads`.

Each computation has two forms that give the same numbers: Pallas kernels
(``indexer_select``: grid sequence x block of 128 queries, the scores of a
block in VMEM by keys in chunks of ``W``; ``indexer_backward``: grid
sequence x q tile x k tile, the main attention's probabilities of a tile
summed over its heads from ``q``, ``k`` and the forward's row statistics,
the indexer's scores recomputed, and the three gradients accumulated in
float32 VMEM) on a TPU backend, and XLA's products over whole score
matrices (``*_dense``) elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi4dl_tpu.ops.pallas_attention import (
    _DEFAULT_VMEM, _round_up, _traced_once, selection_width)

_INT_MIN = np.int32(-2 ** 31)
_NEG = -1e30  # a running maximum's start: exp() of it is exactly 0
SELECT_ROWS = 128  # queries a step of the selection kernel (lanes)
GRAD_TQ = 256  # queries a tile of the backward kernel (lanes)


def _product(a, b, contract):
    """``a`` by ``b`` over the dims ``contract`` names, float32 sums; the
    operands as they come (DEFAULT, said outright: a caller's "highest"
    default asks Mosaic for float32 products of bf16 operands)."""
    return lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def order_keys(scores):
    """int32 images of float32 scores in the same order (``-0.0`` as
    ``0.0``): a score's bits where it is positive, its magnitude's bits
    flipped where it is negative."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    key = jnp.where(bits < 0, jnp.bitwise_xor(bits, np.int32(0x7FFFFFFF)), bits)
    return jnp.where(scores == 0, 0, key)


def _from_keys(key):
    bits = jnp.where(key < 0, jnp.bitwise_xor(key, np.int32(0x7FFFFFFF)), key)
    return lax.bitcast_convert_type(bits, jnp.float32)


def pack_selection(sel):
    """``[B, Tq, Tk]`` booleans as the words ``[B, Tq, W]`` int32."""
    b, t_q, t_k = sel.shape
    width = selection_width(t_k)
    sel = jnp.pad(sel, ((0, 0), (0, 0), (0, 32 * width - t_k)))
    planes = sel.reshape(b, t_q, 32, width).astype(jnp.uint32)
    words = jnp.sum(planes << jnp.arange(32, dtype=jnp.uint32)[:, None],
                    axis=2, dtype=jnp.uint32)
    return lax.bitcast_convert_type(words, jnp.int32)


def unpack_selection(words, t_k: int):
    """The words ``[B, Tq, W]`` as ``[B, Tq, t_k]`` booleans."""
    b, t_q, width = words.shape
    bits = jnp.right_shift(lax.bitcast_convert_type(words, jnp.uint32)[:, :, None, :],
                           jnp.arange(32, dtype=jnp.uint32)[:, None]) & 1
    return bits.reshape(b, t_q, 32 * width)[..., :t_k].astype(bool)


def scores_dense(iq, ik, w):
    """``I [B, Tq, Tk]`` float32, every pair (the causal mask is the
    caller's): head by head, ``w_j * relu(iq_j . ik)`` summed in head order
    from zero, as the kernels sum them."""
    acc = jnp.zeros((iq.shape[0], iq.shape[1], ik.shape[1]), jnp.float32)
    for j in range(iq.shape[2]):
        z = jnp.einsum("bqd,bkd->bqk", iq[:, :, j], ik,
                       preferred_element_type=jnp.float32,
                       precision=lax.Precision.DEFAULT)
        acc = acc + w[:, :, j, None] * jnp.maximum(z, 0.0)
    return acc


def select_dense(iq, ik, w, topk: int):
    """The selection by XLA over whole score matrices: ``(words_t [B, W,
    Tq] int32, lse [B, Tq] float32)``, ``lse`` the log-sum-exp of a row's
    selected scores.  The same numbers as :func:`indexer_select`'s kernel."""
    b, t, _, _ = iq.shape
    scores = scores_dense(iq, ik, w)
    pos = jnp.arange(t, dtype=jnp.int32)
    valid = pos[None, :] <= pos[:, None]                           # [Tq, Tk]
    key = jnp.where(valid, order_keys(scores), _INT_MIN)
    k_eff = jnp.minimum(topk, pos + 1)[None, :, None]              # [1, Tq, 1]

    count = lambda m: jnp.sum(m, axis=-1, keepdims=True, dtype=jnp.int32)
    tau_u = jnp.zeros((b, t, 1), jnp.int32)
    for bit in range(31, -1, -1):
        cand = jnp.bitwise_or(tau_u, np.int32(np.uint32(1 << bit).view(np.int32)))
        ok = count(key >= jnp.bitwise_xor(cand, _INT_MIN)) >= k_eff
        tau_u = jnp.where(ok, cand, tau_u)
    tau = jnp.bitwise_xor(tau_u, _INT_MIN)
    eq = (key == tau) & valid
    need = k_eff - count(key > tau)
    # the largest q with fewer than ``need`` ties before it: the ties at or
    # before q are those the row takes
    q = jnp.zeros((b, t, 1), jnp.int32)
    for bit in range(max(t - 1, 1).bit_length() - 1, -1, -1):
        cand = q | (1 << bit)
        ok = count(eq & (pos < cand)) < need
        q = jnp.where(ok, cand, q)
    sel = ((key > tau) | (eq & (pos <= q))) & valid
    f = jnp.where(sel, scores, -jnp.inf)
    m = jnp.max(f, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(f - m[..., None]), axis=-1))
    return jnp.swapaxes(pack_selection(sel), 1, 2), lse


def _select_kernel(iq_ref, ik_ref, w_ref, words_ref, lse_ref, *rest, tr, kc,
                   heads, topk, t_q, t_k, pos_bits, with_scores):
    """One (sequence, block of ``tr`` queries) step, TRANSPOSED: keys on the
    sublanes, the block's queries along the lanes, so that a row's count,
    threshold and sum are ``[1, tr]`` rows.  ``keys`` holds the int32 images
    of the block's scores for the keys it sees (chunks of ``kc`` = ``W``
    keys, so that chunk ``c`` is bit ``c`` of the words)."""
    if with_scores:
        scores_ref, keys, q_scr = rest
    else:
        keys, q_scr = rest
        scores_ref = None
    r = pl.program_id(1)
    row0 = r * tr
    rows = row0 + lax.broadcasted_iota(jnp.int32, (1, tr), 1)
    k_eff = jnp.minimum(topk, rows + 1)
    # chunks a query of the block sees
    n_chunks = jnp.minimum((row0 + tr - 1) // kc + 1, -(-t_k // kc))
    i32 = jnp.int32

    def score_chunk(c, carry):
        start = pl.multiple_of(c * kc, kc)
        ik = ik_ref[0, :, pl.ds(start, kc)]                        # [Di, kc]
        acc = jnp.zeros((kc, tr), jnp.float32)
        for j in range(heads):
            z = _product(ik, iq_ref[0, j], (0, 0))                 # [kc, tr]
            acc = acc + w_ref[0, pl.ds(j, 1), :] * jnp.maximum(z, 0.0)
        key_pos = start + lax.broadcasted_iota(i32, (kc, tr), 0)
        valid = (key_pos <= rows) & (key_pos < t_k) & (rows < t_q)
        keys[pl.ds(start, kc), :] = jnp.where(valid, order_keys(acc), _INT_MIN)
        if scores_ref is not None:
            scores_ref[0, pl.ds(start, kc), :] = jnp.where(valid, acc, -jnp.inf)
        return carry

    if scores_ref is not None:
        scores_ref[0] = jnp.full(scores_ref.shape[1:], -jnp.inf, jnp.float32)
    lax.fori_loop(0, n_chunks, score_chunk, 0)

    def count(pred):
        """Per query, how many of the seen keys ``pred(key, position)``."""
        def body(c, acc):
            start = pl.multiple_of(c * kc, kc)
            kk = keys[pl.ds(start, kc), :]
            pos = start + lax.broadcasted_iota(i32, (kc, tr), 0)
            return acc + jnp.sum(pred(kk, pos).astype(i32), axis=0, keepdims=True)
        return lax.fori_loop(0, n_chunks, body, jnp.zeros((1, tr), i32))

    tau_u = jnp.zeros((1, tr), i32)
    for bit in range(31, -1, -1):
        cand = jnp.bitwise_or(tau_u, np.int32(np.uint32(1 << bit).view(np.int32)))
        thr = jnp.bitwise_xor(cand, _INT_MIN)
        ok = count(lambda kk, pos: kk >= thr) >= k_eff
        tau_u = jnp.where(ok, cand, tau_u)
    tau = jnp.bitwise_xor(tau_u, _INT_MIN)
    need = k_eff - count(lambda kk, pos: kk > tau)
    ties = count(lambda kk, pos: (kk == tau) & (kk != _INT_MIN))
    q_scr[...] = jnp.full((1, tr), 2 ** 30, i32)

    @pl.when(jnp.any(ties > need))
    def _():
        q = jnp.zeros((1, tr), i32)
        for bit in range(pos_bits - 1, -1, -1):
            cand = q | (1 << bit)
            ok = count(lambda kk, pos: (kk == tau) & (pos < cand)) < need
            q = jnp.where(ok, cand, q)
        q_scr[...] = q

    q_last = q_scr[...]
    words_ref[0] = jnp.zeros(words_ref.shape[1:], i32)

    def pack(c, carry):
        m, l = carry
        start = pl.multiple_of(c * kc, kc)
        kk = keys[pl.ds(start, kc), :]
        pos = start + lax.broadcasted_iota(i32, (kc, tr), 0)
        sel = ((kk > tau) | ((kk == tau) & (pos <= q_last))) & (kk != _INT_MIN)
        words_ref[0] = jnp.bitwise_or(words_ref[0], lax.shift_left(
            sel.astype(i32), jnp.full((kc, tr), c, i32)))
        f = jnp.where(sel, _from_keys(kk), _NEG)
        m_new = jnp.maximum(m, jnp.max(f, axis=0, keepdims=True))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.where(sel, jnp.exp(f - m_new), 0.0), axis=0, keepdims=True)
        return m_new, l

    m, l = lax.fori_loop(0, n_chunks, pack,
                         (jnp.full((1, tr), _NEG, jnp.float32),
                          jnp.zeros((1, tr), jnp.float32)))
    lse_ref[0] = m + jnp.log(l)


@functools.partial(_traced_once, static_argnums=(3, 4, 5, 6, 7))
def _select_call(iq_t, ik_t, w_t, topk, t_q, t_k, interpret, with_scores):
    """The selection kernel on its layouts: ``iq_t [B, Hi, Di, Tq_p]``,
    ``ik_t [B, Di, 32·W]``, ``w_t [B, Hi, Tq_p]``."""
    b, heads, di, t_qp = iq_t.shape
    t_kp = ik_t.shape[2]
    width = t_kp // 32
    tr, kc = SELECT_ROWS, width
    outs = [jax.ShapeDtypeStruct((b, width, t_qp), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, t_qp), jnp.float32)]
    out_specs = [pl.BlockSpec((1, width, tr), lambda s, r: (s, 0, r)),
                 pl.BlockSpec((1, 1, tr), lambda s, r: (s, 0, r))]
    if with_scores:
        outs.append(jax.ShapeDtypeStruct((b, t_kp, t_qp), jnp.float32))
        out_specs.append(pl.BlockSpec((1, t_kp, tr), lambda s, r: (s, 0, r)))
    vmem = (4 * t_kp * tr + 2 * 2 * di * t_kp + 2 * 2 * heads * di * tr
            + 2 * 4 * width * tr + 8 * 4 * kc * tr
            + (2 * 4 * t_kp * tr if with_scores else 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, tr=tr, kc=kc, heads=heads, topk=topk,
                          t_q=t_q, t_k=t_k,
                          pos_bits=max(t_kp - 1, 1).bit_length(),
                          with_scores=with_scores),
        grid=(b, t_qp // tr),
        in_specs=[pl.BlockSpec((1, heads, di, tr), lambda s, r: (s, 0, 0, r)),
                  pl.BlockSpec((1, di, t_kp), lambda s, r: (s, 0, 0)),
                  pl.BlockSpec((1, heads, tr), lambda s, r: (s, 0, r))],
        out_specs=out_specs,
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((t_kp, tr), jnp.int32),
                        pltpu.VMEM((1, tr), jnp.int32)],
        compiler_params=(
            None if vmem <= _DEFAULT_VMEM
            else pltpu.CompilerParams(vmem_limit_bytes=vmem + 2 ** 22)),
        interpret=interpret,
        name="sparse_indexer_select",
    )(iq_t, ik_t, w_t)


def indexer_select(iq, ik, w, topk: int, *, interpret=False,
                   with_scores=False):
    """:func:`select_dense`'s result by the Pallas kernel
    ``sparse_indexer_select``: ``(words_t [B, W, T], lse [B, T])``, and,
    ``with_scores``, the kernel's own scores ``[B, T(keys), T(queries)]``
    (``-inf`` where a key is not seen), for a check of the selection."""
    b, t, heads, di = iq.shape
    width = selection_width(t)
    t_qp = _round_up(t, SELECT_ROWS)
    iq_t = jnp.pad(jnp.transpose(iq, (0, 2, 3, 1)),
                   ((0, 0), (0, 0), (0, 0), (0, t_qp - t)))
    ik_t = jnp.pad(jnp.swapaxes(ik, 1, 2), ((0, 0), (0, 0), (0, 32 * width - t)))
    w_t = jnp.pad(jnp.swapaxes(w.astype(jnp.float32), 1, 2),
                  ((0, 0), (0, 0), (0, t_qp - t)))
    out = _select_call(iq_t, ik_t, w_t, topk, t, t, interpret, with_scores)
    words_t, lse = out[0][..., :t], out[1][:, 0, :t]
    if with_scores:
        return words_t, lse, out[2][:, :t, :t]
    return words_t, lse


def head_mean_probs(q, k, sel, scale):
    """The main attention's probabilities over the selected keys, summed
    over its heads and L1-normalised (each head's sum to one: the mean over
    heads): ``[B, Tq, Tk]`` float32 from ``q [B, T, H, D]`` and ``k [B, T,
    KV, D]`` (each key-value head serving ``H / KV`` query heads)."""
    rep = q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, axis=2),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(sel[:, None], s, -jnp.inf)
    return jnp.mean(jax.nn.softmax(s, axis=-1), axis=1)


def indexer_grads_dense(p, iq, ik, w, sel, lse, inv_n: float):
    """The indexer loss's gradient by XLA over whole score matrices, from
    ``p`` (:func:`head_mean_probs`, a constant): ``(diq [B, T, Hi, Di],
    dik [B, T, Di], dw [B, T, Hi])`` float32, ``inv_n`` the loss's ``1 / N``."""
    scores = scores_dense(iq, ik, w)
    soft = jnp.where(sel, jnp.exp(scores - lse[..., None]), 0.0)
    g = (soft - jnp.where(sel, p, 0.0)) * inv_n
    f32 = jnp.float32
    diq, dw = [], []
    dik = jnp.zeros(ik.shape, f32)
    for j in range(iq.shape[2]):
        z = jnp.einsum("bqd,bkd->bqk", iq[:, :, j], ik,
                       preferred_element_type=f32,
                       precision=lax.Precision.DEFAULT)
        gz = jnp.where(z > 0, g * w[:, :, j, None], 0.0)
        diq.append(jnp.einsum("bqk,bkd->bqd", gz, ik.astype(f32)))
        dik = dik + jnp.einsum("bqk,bqd->bkd", gz, iq[:, :, j].astype(f32))
        dw.append(jnp.sum(g * jnp.maximum(z, 0.0), axis=-1))
    return jnp.stack(diq, axis=2), dik, jnp.stack(dw, axis=-1)


def _grad_kernel(q_ref, k_ref, c_ref, iq_ref, ik_ref, w_ref, lse_ref,
                 words_ref, diq_ref, dw_ref, dik_ref, diq_acc, dw_acc, dik_acc,
                 *, tq, tk, nq, nk, heads, kv_heads, head_dim, iheads, scale,
                 inv_n):
    """One (sequence, q tile, k tile) step, TRANSPOSED as the selection
    (keys on the sublanes, queries along the lanes).  ``diq_acc`` and
    ``dw_acc`` hold the q tile's sums across the innermost k dimension;
    ``dik_acc`` the sequence's keys across both.  The tile's selection is
    bit ``ki`` of its queries' words (``tk`` is the selection's width)."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    cols = pl.ds(pl.multiple_of(ki * tk, tk), tk)

    @pl.when(jnp.logical_and(qi == 0, ki == 0))
    def _():
        dik_acc[...] = jnp.zeros_like(dik_acc)

    @pl.when(ki == 0)
    def _():
        diq_acc[...] = jnp.zeros_like(diq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    def fold():
        sel = jnp.bitwise_and(lax.shift_right_logical(
            words_ref[0], jnp.full(words_ref.shape[1:], ki, jnp.int32)), 1) != 0
        q, k = q_ref[0], k_ref[0]                      # [TQ, H·D], [TK, KV·D]
        rep = heads // kv_heads
        p = jnp.zeros((tk, tq), jnp.float32)
        for h in range(heads):
            g_ = h // rep
            s = _product(k[:, g_ * head_dim:(g_ + 1) * head_dim],
                         q[:, h * head_dim:(h + 1) * head_dim], (1, 1)) * scale
            p = p + jnp.exp(jnp.minimum(s - c_ref[0, pl.ds(h, 1), :], 0.0))
        ik = ik_ref[0]                                 # [Di, TK]
        score = jnp.zeros((tk, tq), jnp.float32)
        for j in range(iheads):
            z = _product(ik, iq_ref[0, j], (0, 0))
            score = score + w_ref[0, pl.ds(j, 1), :] * jnp.maximum(z, 0.0)
        soft = jnp.exp(jnp.minimum(score - lse_ref[0], 0.0))
        g = jnp.where(sel, soft - p * (1.0 / heads), 0.0) * inv_n
        for j in range(iheads):
            iq = iq_ref[0, j]                          # [Di, TQ]
            z = _product(ik, iq, (0, 0))
            w_j = w_ref[0, pl.ds(j, 1), :]
            gz = jnp.where(z > 0, g * w_j, 0.0)
            diq_acc[j] += _product(ik.astype(jnp.float32), gz, (1, 0))
            dik_acc[:, cols] += _product(iq.astype(jnp.float32), gz, (1, 1))
            dw_acc[pl.ds(j, 1), :] += jnp.sum(g * jnp.maximum(z, 0.0), axis=0,
                                              keepdims=True)

    # some query of the tile sees a key of it
    pl.when(ki * tk <= (qi + 1) * tq - 1)(fold)

    @pl.when(ki == nk - 1)
    def _():
        diq_ref[0] = diq_acc[...]
        dw_ref[0] = dw_acc[...]

    @pl.when(jnp.logical_and(qi == nq - 1, ki == nk - 1))
    def _():
        dik_ref[0] = dik_acc[...]


@functools.partial(_traced_once, static_argnums=(8, 9, 10))
def _grad_call(q, k, c, iq_t, ik_t, w_t, lse, words_t, scale, inv_n, interpret):
    """The backward kernel on its layouts (see :func:`indexer_backward`)."""
    b, t_qp, hd = q.shape
    t_kp, kvd = k.shape[1], k.shape[2]
    heads = c.shape[1]
    head_dim = hd // heads
    _, iheads, di, _ = iq_t.shape
    width = words_t.shape[1]
    tq, tk = GRAD_TQ, width
    nq, nk = t_qp // tq, t_kp // tk
    f32 = jnp.float32

    def k_tile(qi, ki):  # a tile after the diagonal asks for one held
        return jnp.minimum(ki, ((qi + 1) * tq - 1) // tk)

    vmem = (2 * 2 * (tq * hd + tk * kvd + iheads * di * tq + di * tk)
            + 2 * 4 * (width * tq + heads * tq + iheads * tq + tq)
            + 3 * 4 * (iheads * di * tq + iheads * tq) + 2 * 4 * di * t_kp
            + 12 * 4 * tk * tq)
    return pl.pallas_call(
        functools.partial(_grad_kernel, tq=tq, tk=tk, nq=nq, nk=nk,
                          heads=heads, kv_heads=kvd // head_dim,
                          head_dim=head_dim, iheads=iheads, scale=scale,
                          inv_n=inv_n),
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((1, tq, hd), lambda s, i, j: (s, i, 0)),
            pl.BlockSpec((1, tk, kvd), lambda s, i, j: (s, k_tile(i, j), 0)),
            pl.BlockSpec((1, heads, tq), lambda s, i, j: (s, 0, i)),
            pl.BlockSpec((1, iheads, di, tq), lambda s, i, j: (s, 0, 0, i)),
            pl.BlockSpec((1, di, tk), lambda s, i, j: (s, 0, k_tile(i, j))),
            pl.BlockSpec((1, iheads, tq), lambda s, i, j: (s, 0, i)),
            pl.BlockSpec((1, 1, tq), lambda s, i, j: (s, 0, i)),
            pl.BlockSpec((1, width, tq), lambda s, i, j: (s, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, iheads, di, tq), lambda s, i, j: (s, 0, 0, i)),
            pl.BlockSpec((1, iheads, tq), lambda s, i, j: (s, 0, i)),
            pl.BlockSpec((1, di, t_kp), lambda s, i, j: (s, 0, 0),
                         pipeline_mode=pl.Buffered(1)),
        ],
        scratch_shapes=[pltpu.VMEM((iheads, di, tq), f32),
                        pltpu.VMEM((iheads, tq), f32),
                        pltpu.VMEM((di, t_kp), f32)],
        out_shape=[jax.ShapeDtypeStruct((b, iheads, di, t_qp), f32),
                   jax.ShapeDtypeStruct((b, iheads, t_qp), f32),
                   jax.ShapeDtypeStruct((b, di, t_kp), f32)],
        compiler_params=(
            None if vmem <= _DEFAULT_VMEM
            else pltpu.CompilerParams(vmem_limit_bytes=vmem + 2 ** 22)),
        interpret=interpret,
        name="sparse_indexer_bwd",
    )(q, k, c, iq_t, ik_t, w_t, lse, words_t)


def indexer_backward(q, k, c, iq, ik, w, words_t, lse, *, scale, inv_n,
                     interpret=False):
    """:func:`indexer_grads_dense`'s result by the Pallas kernel
    ``sparse_indexer_bwd``, the main attention's probabilities made in it
    from ``q [B, T, H, D]``, ``k [B, T, KV, D]`` and ``c = m + log l``
    ``[B, H, T]`` (the forward's row statistics), the selection from
    ``words_t`` and ``lse`` (:func:`indexer_select`).  A tile after the
    diagonal is skipped."""
    b, t, heads, d = q.shape
    width = words_t.shape[1]
    t_qp, t_kp = _round_up(t, GRAD_TQ), 32 * width
    pad_q = lambda x, axis, fill=0.0: jnp.pad(
        x, [(0, t_qp - t if i == axis else 0) for i in range(x.ndim)],
        constant_values=fill)
    q2 = pad_q(q.reshape(b, t, heads * d), 1)
    k2 = jnp.pad(k.reshape(b, t, -1), ((0, 0), (0, t_kp - t), (0, 0)))
    iq_t = pad_q(jnp.transpose(iq, (0, 2, 3, 1)), 3)
    ik_t = jnp.pad(jnp.swapaxes(ik, 1, 2), ((0, 0), (0, 0), (0, t_kp - t)))
    w_t = pad_q(jnp.swapaxes(w.astype(jnp.float32), 1, 2), 2)
    c_p = pad_q(c.astype(jnp.float32), 2)
    lse_p = pad_q(lse.astype(jnp.float32), 1)[:, None, :]
    diq_t, dw_t, dik_t = _grad_call(q2, k2, c_p, iq_t, ik_t, w_t, lse_p,
                                    pad_q(words_t, 2, 0), scale, inv_n,
                                    interpret)
    return (jnp.transpose(diq_t, (0, 3, 1, 2))[:, :t],
            jnp.swapaxes(dik_t, 1, 2)[:, :t], jnp.swapaxes(dw_t, 1, 2)[:, :t])
