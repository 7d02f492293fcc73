"""granite-4.0-h-micro (Hugging Face ``granitemoehybrid``), plain float32 forward.

After the published description (``modeling_granitemoehybrid.py`` of
transformers, and the model's ``config.json``): every projection without
bias; ``h = embedding_multiplier x E[ids]``; a layer is ``h +=
residual_multiplier x mixer(RMSNorm(h))`` then ``h += residual_multiplier x
mlp(RMSNorm(h))``; RMSNorm has a learned scale and eps 1e-5; after the last
layer one more RMSNorm, then ``logits = h E^T / logits_scaling``: the head is
the embedding's table (``tie_word_embeddings``).

- ``mlp`` (``num_local_experts`` 0: the always-on ``shared_mlp`` alone):
  ``a, b = split(W_in u)`` at 8192; ``W_out (silu(a) * b)``.
- ``mixer`` of an ``attention`` layer: ``q = W_q u`` as 32 heads of 64,
  ``k, v = W_k u, W_v u`` as 8 heads of 64, each serving 4 query heads; NO
  rotary embedding and no other position signal (``position_embedding_type``
  ``nope``), no norm on q or k; scores ``q . k x attention_multiplier``
  (0.015625 = 1/64, not 64^-1/2), causal softmax; ``W_o`` on the heads side
  by side.
- ``mixer`` of a ``mamba`` layer (Mamba-2; 64 heads of 64, state 128, one
  group): ``z, xBC, dt = split(W_in u)`` at 4096 and 8448; ``xBC =
  silu(conv(xBC) + bias)``, the convolution depthwise and causal over 4 taps;
  ``x, B, C = split(xBC)`` at 4096 and 4224; ``dt = softplus(dt + dt_bias)``
  and ``A = -exp(A_log)`` a head.  THE RECURRENCE, a head, position by
  position, its state ``S`` of ``[64, 128]`` from zero:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``; ``y_t = S_t C_t + D x_t``.
  Then ``W_out RMSNorm_4096(y * silu(z))``: the gate before the norm.

Departures, each the configuration's (``deployment`` and ``assumed`` in its
file), none the program's alone:
- the program keeps the MLP's ``input_linear`` as its two halves ``w1`` (the
  half that goes through silu) and ``w3``, and ``output_linear`` as ``w2``:
  the same sums;
- the vocabulary is the slice ``sizes["vocab_size"]``: table, logits and loss
  are over the slice;
- the layers are the first ``sizes["num_layers"]`` with their published
  ``layer_types``; logits go to the loss as they are.

Straightforward ``jax.numpy``.  The state-space layer is the recurrence
itself (``lax.scan`` over the positions), NOT the chunked form the program
runs: it shares none of its algebra.  Attention by blocks of queries and the
MLP by sequence, so that 8,192 tokens fit the chip beside the program's
cells.  No code of the program under test.  Weights are the program's
parameter tree: a list with one entry per cell; the table is the first
cell's ``table``, for the embedding and for the head.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.references.plain import Tally

HI = lax.Precision.HIGHEST
QUERY_BLOCK = 256


def batch_spec(sizes, traffic):
    shape = (traffic["batch_size"], traffic["size"])
    return (jax.ShapeDtypeStruct(shape, jnp.int32),
            jax.ShapeDtypeStruct(shape, jnp.int32))


def linear(x, p, tally):
    """``x @ kernel`` over the last axis; no bias on any projection."""
    k = p["kernel"].astype(jnp.float32)
    if tally is not None:
        tally.add("dense", math.prod(x.shape[:-1]) * k.shape[0] * k.shape[1])
    return jnp.dot(x, k, precision=HI)


def rms_norm(x, p, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p[
        "scale"].astype(jnp.float32)


def silu(x):
    return x * jax.nn.sigmoid(x)


def positions(q, k):
    """What the model does to q and k for their positions: nothing."""
    return q, k


def attention_scale(sizes):
    return sizes["attention_multiplier"]


def attention(h, p, sizes, tally):
    bsz, s, _ = h.shape
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["hidden_size"] // nh
    q = linear(h, p["q_proj"], tally).reshape(bsz, s, nh, hd)
    k = linear(h, p["k_proj"], tally).reshape(bsz, s, nkv, hd)
    v = linear(h, p["v_proj"], tally).reshape(bsz, s, nkv, hd)
    q, k = positions(q, k)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    if tally is not None:  # the causal half: q k^T and p v at hd each
        tally.add("attn_scores", bsz * nh * (s * (s + 1) // 2) * 2 * hd)

    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)
    scale = attention_scale(sizes)

    def one_block(i):
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k, precision=HI) * scale
        q_pos = i * block + jnp.arange(block)
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores, -jnp.inf)
        return jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1),
                          v, precision=HI)

    out = lax.map(one_block, jnp.arange(s // block))  # [blocks, B, block, ...]
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, s, nh * hd)
    return linear(out, p["out_proj"], tally)


def causal_conv(x, p, tally):
    """``y[t] = sum_j kernel[j] x[t - (K-1) + j] + bias``, a channel."""
    w = p["kernel"].astype(jnp.float32)
    taps, s = w.shape[0], x.shape[1]
    if tally is not None:
        tally.add("conv", math.prod(x.shape) * taps)
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + s] * w[j] for j in range(taps))
    return conv_bias(y, p)


def conv_bias(y, p):
    return y + p["bias"].astype(jnp.float32)


def step_size(dt, p):
    """A head's step: ``softplus(dt + dt_bias)``."""
    return jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))


def skip(y, x, p):
    """``+ D x``, ``D`` a scalar a head."""
    return y + p["D"].astype(jnp.float32)[:, None] * x


def gate_and_norm(y, z, p, eps):
    """The gate before the norm, one group over all channels."""
    return rms_norm(y * silu(z), p["norm"], eps)


def recurrence(x, dt, a, b, c, restart_every=None):
    """``[B, S, H, P]`` from the recurrence, position by position, the state
    ``[B, H, P, N]`` from zero.  (``restart_every``: a planted fault's, the
    state put back to zero every so many positions; None in the model.)"""
    bsz, s, h, pdim = x.shape

    def step(state, at_t):
        x_t, dt_t, b_t, c_t, t = at_t
        if restart_every is not None:
            state = jnp.where(t % restart_every == 0, 0.0, state)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t, precision=HI)

    _, y = lax.scan(
        step, jnp.zeros((bsz, h, pdim, b.shape[-1]), jnp.float32),
        (*(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)), jnp.arange(s)))
    return jnp.moveaxis(y, 0, 1)


def scan(x, dt, a, b, c):
    return recurrence(x, dt, a, b, c)


def mamba(h, p, sizes, tally):
    bsz, s, _ = h.shape
    nh, hd, n = sizes["mamba_n_heads"], sizes["mamba_d_head"], sizes["mamba_d_state"]
    inner = nh * hd
    assert sizes["mamba_n_groups"] == 1
    zxbcdt = linear(h, p["in_proj"], tally)
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * n],
                  zxbcdt[..., 2 * inner + 2 * n:])
    assert dt.shape[-1] == nh and p["conv1d"]["kernel"].shape[0] == sizes["mamba_d_conv"]
    xbc = silu(causal_conv(xbc, p["conv1d"], tally))
    x = xbc[..., :inner].reshape(bsz, s, nh, hd)
    b, c = xbc[..., inner:inner + n], xbc[..., inner + n:]
    if tally is not None:  # the state's update and its read-out, a position
        tally.add("ssm_scan", bsz * s * 2 * n * hd * nh)
    y = scan(x, step_size(dt, p), -jnp.exp(p["A_log"].astype(jnp.float32)), b, c)
    y = skip(y, x, p).reshape(bsz, s, inner)
    return linear(gate_and_norm(y, z, p, sizes["rms_norm_eps"]),
                  p["out_proj"], tally)


def mlp(h, p, tally):
    """By sequence: the width is 8,192.  ``w1`` and ``w3`` are the halves of
    the published ``input_linear``, ``w2`` its ``output_linear``."""
    if tally is not None:
        for name in ("w1", "w3", "w2"):
            k = p[name]["kernel"]
            tally.add("dense", math.prod(h.shape[:-1]) * k.shape[0] * k.shape[1])

    def one(x):
        return linear(silu(linear(x, p["w1"], None)) * linear(x, p["w3"], None),
                      p["w2"], None)

    return lax.map(one, h)


def residual_multiplier(sizes):
    return sizes["residual_multiplier"]


def layer(p, h, kind, sizes, tally):
    eps, m = sizes["rms_norm_eps"], residual_multiplier(sizes)
    mixer = {"mamba": mamba, "attention": attention}[kind]
    h = h + m * mixer(rms_norm(h, p["op_norm"], eps), p["op"], sizes, tally)
    return h + m * mlp(rms_norm(h, p["ffn_norm"], eps), p["ffn"], tally)


def embedding_multiplier(sizes):
    return sizes["embedding_multiplier"]


def logits_scaling(sizes):
    return sizes["logits_scaling"]


def head_table(params):
    """The head's weights: the embedding's table (tied)."""
    return params[0]["table"]


def head_product(x, table):
    """``x E^T``."""
    return jnp.dot(x, table.astype(jnp.float32).T, precision=HI)


def cells(params, sizes, tally: Tally | None = None):
    """One function per cell of the program's model: the embedding, the
    ``sizes["num_layers"]`` layers, final norm and (tied) head."""
    n = sizes["num_layers"]
    kinds = sizes["layer_types"]
    assert n == len(params) - 2 == len(kinds), (n, len(params), kinds)
    assert params[0]["table"].shape == (sizes["vocab_size"], sizes["hidden_size"])

    def embed(ids):
        return embedding_multiplier(sizes) * params[0]["table"].astype(
            jnp.float32)[ids]

    def block(i):
        return lambda h: layer(params[i + 1], h, kinds[i], sizes, tally)

    def head(h):
        x = rms_norm(h, params[-1]["norm"], sizes["rms_norm_eps"])
        table = head_table(params)
        if tally is not None:
            tally.add("head", math.prod(x.shape[:-1]) * table.shape[0] * table.shape[1])
        return head_product(x, table) / logits_scaling(sizes)

    return [embed] + [block(i) for i in range(n)] + [head]
