"""Kanana-2-30B-A3B (Hugging Face ``deepseek_v3``), plain float32 forward.

After the published description (``modeling_deepseek_v3.py`` of transformers,
and the model's ``config.json``): every projection without bias; a layer is
``h += MLA(RMSNorm(h))`` then ``h += ffn(RMSNorm(h))``; RMSNorm has a learned
scale and eps 1e-6; after the last layer one more RMSNorm, then the head.

- ``MLA`` (``q_lora_rank`` null: the query is not compressed):
  ``q = W_q x``, 32 heads of 192, a head's first 128 columns ``q_nope`` and
  its last 64 ``q_pe``; ``c, k_pe = split(W_kva x)`` at 512; ``k_nope, v =
  split(W_kvb RMSNorm_512(c))``, a head's 128 and 128.  The rotary embedding
  (theta 1e6, no scaling, ``rope_interleave``: the pair ``i`` of a head is its
  columns ``(2i, 2i+1)``, turned by ``pos * theta^(-2i/64)``) on ``q_pe`` and
  on ``k_pe``, which is one key for all 32 heads.  ``k = [k_nope, k_pe]``;
  scores ``q . k / sqrt(192)``, causal softmax; the output ``p v`` is 128 wide
  a head; ``W_o`` takes the 4096 to 2048.
- ``ffn`` of the leading dense layer: ``W_2 (silu(W_1 h) * W_3 h)``, 6144 wide.
- ``ffn`` of the others: ``routed(h) + shared(h)``.  ``s = sigmoid(W_r h)``; a
  token's experts are the top six of ``s + b`` (one group: no group limit);
  their weights the chosen ``s`` over their sum + 1e-20, times 2.448; the
  routed output the weighted sum of the chosen experts, each a SwiGLU of 768.
  ``shared`` is one SwiGLU of 2 x 768 on every token.

Departures, each the configuration's (``deployment`` and ``assumed`` in its
file), none the program's alone:
- this chip holds ``sizes["n_routed_experts"]`` of the
  ``n_routed_experts_published`` experts, from ``expert_first``: the router
  scores and chooses over all of them, and what an absent expert would have
  added is left out of the sum; the shared expert is whole;
- the vocabulary is the slice ``sizes["vocab_size"]``: embedding, head, logits
  and loss are over the slice;
- the layers are the first ``sizes["num_layers"]``, the first
  ``dense_layers`` of them with the dense ``ffn``; logits go to the loss as
  they are;
- the bias ``b`` is zero and no rule updates it (the config names
  ``noaux_tc`` and gives no rule).
The published rotary code gathers a head's even columns before its odd ones
and rotates halves; turning the pairs in place, as here, gives the same
scores, since q and k are permuted alike.

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
ROUTE_SUM_EPS = 1e-20


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


def rotate_pairs(x, theta):
    """[B, S, H, hd]: the pair ``(x[2i], x[2i+1])`` turned by
    ``pos * theta^(-2i/hd)``, in place."""
    s, hd = x.shape[1], x.shape[3]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def compressed_norm(c, p, eps):
    """``kv_a_layernorm``: the compressed row's own RMSNorm."""
    return rms_norm(c, p["kv_a_layernorm"], eps)


def key_rotary(k_pe, theta):
    """The rotary embedding of the shared key."""
    return rotate_pairs(k_pe, theta)


def attention_scale(sizes):
    return 1.0 / math.sqrt(sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"])


def latent_attention(h, p, sizes, tally):
    bsz, s, _ = h.shape
    nh = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    vd, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    q = linear(h, p["q_proj"], tally).reshape(bsz, s, nh, nope + rope)
    ckv = linear(h, p["kv_a_proj_with_mqa"], tally)
    c, k_pe = ckv[..., :rank], ckv[..., rank:]
    kv = linear(compressed_norm(c, p, eps), p["kv_b_proj"], tally).reshape(
        bsz, s, nh, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = rotate_pairs(q[..., nope:], theta)
    k_pe = key_rotary(k_pe[:, :, None, :], theta)  # one head, shared by all
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.tile(k_pe, (1, 1, nh, 1))], axis=-1)
    if tally is not None:  # the causal half: q k^T at 192, p v at 128
        tally.add("attn_scores",
                  bsz * nh * (s * (s + 1) // 2) * (nope + rope + vd))

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
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, s, nh * vd)
    return linear(out, p["o_proj"], tally)


def swiglu(h, p, tally):
    """By sequence: the dense width is 6,144."""
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
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_SUM_EPS)
    return chosen, w * sizes["routed_scaling_factor"]


def routed(h, p, sizes, tally):
    held, first = sizes["n_routed_experts"], sizes["expert_first"]
    total, top_k = sizes["n_routed_experts_published"], sizes["num_experts_per_tok"]
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


def shared(h, p, sizes, tally):
    """The shared expert: one SwiGLU of ``n_shared_experts`` x
    ``moe_intermediate_size`` on every token."""
    assert p["w1"]["kernel"].shape[1] == (
        sizes["n_shared_experts"] * sizes["moe_intermediate_size"])
    if tally is not None:
        for name in ("w1", "w3", "w2"):
            k = p[name]["kernel"]
            tally.add("shared_expert",
                      math.prod(h.shape[:-1]) * k.shape[0] * k.shape[1])
    return linear(silu(linear(h, p["w1"], None)) * linear(h, p["w3"], None),
                  p["w2"], None)


def experts(h, p, sizes, tally):
    return routed(h, p, sizes, tally) + shared(h, p["shared_experts"], sizes, tally)


def layer(p, h, dense, sizes, tally):
    eps = sizes["rms_norm_eps"]
    h = h + latent_attention(rms_norm(h, p["op_norm"], eps), p["op"], sizes, tally)
    x = rms_norm(h, p["ffn_norm"], eps)
    return h + (swiglu(x, p["ffn"], tally) if dense
                else experts(x, p["ffn"], sizes, tally))


def cells(params, sizes, tally: Tally | None = None):
    """One function per cell of the program's model: the embedding, the
    ``sizes["num_layers"]`` layers, final norm and head (the logits)."""
    n = sizes["num_layers"]
    assert n == len(params) - 2, (n, len(params))
    assert params[0]["table"].shape == (sizes["vocab_size"], sizes["hidden_size"])

    def embed(ids):
        return params[0]["table"].astype(jnp.float32)[ids]

    def block(i):
        return lambda h: layer(params[i + 1], h, i < sizes["dense_layers"],
                               sizes, tally)

    def head(h):
        return linear(rms_norm(h, params[-1]["norm"], sizes["rms_norm_eps"]),
                      params[-1]["head"], tally)

    return [embed] + [block(i) for i in range(n)] + [head]
