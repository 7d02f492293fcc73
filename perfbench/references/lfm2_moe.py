"""LFM2-24B-A2B (Hugging Face ``lfm2_moe``), plain float32 forward.

After the published description (``modeling_lfm2_moe.py`` of transformers,
and the model's ``config.json``): every projection without bias; a layer is
``h += op(RMSNorm(h))`` then ``h += ffn(RMSNorm(h))``; RMSNorm has a learned
scale and eps 1e-5; after the last layer one more RMSNorm, then the head.

- ``op`` of a ``conv`` layer: ``B, C, x = split3(W_in h)``; ``u = B * x``;
  ``c[t] = sum_j w[j] * u[t - 2 + j]`` (depthwise, causal, three taps, zeros
  left of the sequence, no bias); ``y = W_out (C * c)``.
- ``op`` of a ``full_attention`` layer: 32 query heads and 8 key-value heads
  of 64; RMSNorm over each head of q and of k; rotary embedding (theta 1e6,
  the two halves of a head rotated) on q and k; causal softmax attention, each
  key-value head serving four query heads, scale 1/8; ``W_o``.
- ``ffn`` of a leading dense layer: ``W_2 (silu(W_1 h) * W_3 h)``.
- ``ffn`` of the others: ``s = sigmoid(W_r h)``; a token's experts are the top
  four of ``s + b``; their weights the chosen ``s`` over their sum + 1e-6,
  times ``routed_scaling_factor``; the output the weighted sum of the chosen
  experts, each a SwiGLU.  No shared expert.

Departures, each the configuration's (``deployment`` and ``assumed`` in its
file), none the program's alone:
- this chip holds ``sizes["num_experts"]`` of the ``num_experts_published``
  experts, from ``expert_first``: the router scores and chooses over all of
  them, and what an absent expert would have added is left out of the sum;
- the vocabulary is the slice ``sizes["vocab_size"]``: embedding, head, logits
  and loss are over the slice;
- embedding and head are two parameters (the config has no tying key);
- the layers are ``sizes["layer_types"]``, the first ``dense_layers`` of them
  with the dense ``ffn``; logits go to the loss as they are.

Straightforward ``jax.numpy``: attention by blocks of queries and the dense
``ffn`` by sequence, so that 8,192 tokens fit the chip beside the program's
cells; the experts as a plain loop over the held experts, each over every
token, with the routing weight zero where it was not chosen.  No code of the
program under test.  Weights are the program's parameter tree: a list with
one entry per cell.
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
    """``x @ kernel`` over the last axis; no bias anywhere in this model."""
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


def causal_conv(u, p, tally):
    """``c[t] = sum_j w[j] * u[t - (K-1) + j]``, ``u`` zero before t = 0."""
    w = p["kernel"].astype(jnp.float32)  # [K, D]
    taps, s = w.shape[0], u.shape[1]
    if tally is not None:
        tally.add("conv1d", math.prod(u.shape) * taps)
    padded = jnp.concatenate(
        [jnp.zeros((u.shape[0], taps - 1, u.shape[2]), u.dtype), u], axis=1)
    return sum(w[j] * padded[:, j:j + s] for j in range(taps))


def short_conv(h, p, sizes, tally):
    b, c, x = jnp.split(linear(h, p["in_proj"], tally), 3, axis=-1)
    return linear(c * causal_conv(b * x, p["conv"], tally), p["out_proj"], tally)


def rotate(x, theta):
    """[B, S, H, hd]: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)."""
    s, hd = x.shape[1], x.shape[3]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[None, :, None, :]
    half = hd // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def attention(h, p, sizes, tally):
    bsz, s, _ = h.shape
    nh, nkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                   sizes["head_dim"])
    eps, theta = sizes["norm_eps"], float(sizes["rope_theta"])
    q = linear(h, p["q_proj"], tally).reshape(bsz, s, nh, hd)
    k = linear(h, p["k_proj"], tally).reshape(bsz, s, nkv, hd)
    v = linear(h, p["v_proj"], tally).reshape(bsz, s, nkv, hd)
    q = rotate(rms_norm(q, p["q_norm"], eps), theta)
    k = rotate(rms_norm(k, p["k_norm"], eps), theta)
    group = nh // nkv  # query heads g*group .. (g+1)*group-1 read kv head g
    q = q.reshape(bsz, s, nkv, group, hd)
    if tally is not None:  # the causal half, for q k^T and for p v
        tally.add("attn_scores", 2 * bsz * nh * (s * (s + 1) // 2) * hd)

    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)

    def one_block(i):
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        scores = jnp.einsum("bqngd,bknd->bngqk", qb, k, precision=HI) / math.sqrt(hd)
        q_pos = i * block + jnp.arange(block)
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores, -jnp.inf)
        return jnp.einsum("bngqk,bknd->bqngd", jax.nn.softmax(scores, axis=-1),
                          v, precision=HI)

    out = lax.map(one_block, jnp.arange(s // block))  # [blocks, B, block, ...]
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, s, nh * hd)
    return linear(out, p["out_proj"], tally)


def swiglu(h, p, tally):
    """By sequence: the dense width is 11,776."""
    if tally is not None:
        for name in ("w1", "w3", "w2"):
            k = p[name]["kernel"]
            tally.add("dense", math.prod(h.shape[:-1]) * k.shape[0] * k.shape[1])

    def one(x):
        return linear(silu(linear(x, p["w1"], None)) * linear(x, p["w3"], None),
                      p["w2"], None)

    return lax.map(one, h)


def route(h, p, sizes):
    """Scores, the chosen experts' indices and their weights, over all the
    published experts."""
    top_k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.dot(h, p["kernel"].astype(jnp.float32), precision=HI))
    # the bias enters the choice only; ties go to the lower index
    chosen = jnp.argsort(-(s + p["bias"].astype(jnp.float32)), axis=-1,
                         stable=True)[..., :top_k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return chosen, w * sizes["routed_scaling_factor"]


def experts(h, p, sizes, tally):
    held, first = sizes["num_experts"], sizes["expert_first"]
    total, top_k = sizes["num_experts_published"], sizes["num_experts_per_tok"]
    d = h.shape[-1]
    x = h.reshape(-1, d)
    ffn = p["experts"]["w1"].shape[-1]
    assert p["experts"]["w1"].shape[0] == held and p["router"]["kernel"].shape[1] == total
    if tally is not None:
        tally.add("router", x.shape[0] * d * total)
        # The balanced load, from shapes alone: of a token's top_k experts,
        # held / total are held here.  What a run really routed here is the
        # program's counter (expert_rows_held_pct).
        tally.add("experts", x.shape[0] * top_k * held // total * 3 * d * ffn)
    chosen, w = route(x, p["router"], sizes)
    out = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        w1, w3, w2 = (p["experts"][n][e].astype(jnp.float32)
                      for n in ("w1", "w3", "w2"))
        y = jnp.dot(silu(jnp.dot(x, w1, precision=HI)) * jnp.dot(x, w3, precision=HI),
                    w2, precision=HI)
        out = out + w_e[:, None] * y
    return out.reshape(h.shape)


def layer(p, h, kind, dense, sizes, tally):
    eps = sizes["norm_eps"]
    op = {"conv": short_conv, "full_attention": attention}[kind]
    h = h + op(rms_norm(h, p["op_norm"], eps), p["op"], sizes, tally)
    x = rms_norm(h, p["ffn_norm"], eps)
    return h + (swiglu(x, p["ffn"], tally) if dense
                else experts(x, p["ffn"], sizes, tally))


def cells(params, sizes, tally: Tally | None = None):
    """One function per cell of the program's model: the embedding, the
    layers of ``sizes["layer_types"]``, final norm and head (the logits)."""
    kinds = sizes["layer_types"]
    assert len(kinds) == sizes["num_layers"] == len(params) - 2, (
        len(kinds), sizes["num_layers"], len(params))
    assert params[0]["table"].shape == (sizes["vocab_size"], sizes["hidden_size"])

    def embed(ids):
        return params[0]["table"].astype(jnp.float32)[ids]

    def block(i):
        return lambda h: layer(params[i + 1], h, kinds[i],
                               i < sizes["dense_layers"], sizes, tally)

    def head(h):
        return linear(rms_norm(h, params[-1]["norm"], sizes["norm_eps"]),
                      params[-1]["head"], tally)

    return [embed] + [block(i) for i in range(len(kinds))] + [head]
