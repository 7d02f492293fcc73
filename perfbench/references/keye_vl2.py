"""Keye-VL-2.0-30B-A3B's language model (Hugging Face ``KeyeVL2``), plain
float32 forward.

After the published description (the model's ``config.json``; the Qwen3-MoE
base whose sizes it shares; DeepSeek-V3.2-Exp's sparse attention, whose
indexer ``sa_config`` sizes): every projection without bias; a layer is
``h += attn(RMSNorm(h))`` then ``h += moe(RMSNorm(h))``, no dense layer;
RMSNorm has a learned scale and eps 1e-6; after the last layer one more
RMSNorm, then the head.

- ``attn``: 32 query heads and 4 key-value heads of 128; RMSNorm over each
  head of q and of k, then the rotary embedding (theta 1e7, the two halves of
  a head rotated; text only, so M-RoPE's sections carry one position).
  The indexer: ``q^I = RoPE(W_qI x)`` 16 heads of 64, ``k^I =
  RoPE(LayerNorm(W_kI x))`` one head of 64 (LayerNorm with a scale and a
  bias, eps 1e-6), the rotary embedding on the first 32 of a head's 64,
  ``w = W_w x / sqrt(16 * 64)``; ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] .
  k^I[s])`` for ``s <= t``.  Query ``t`` attends to the top ``min(2048, t +
  1)`` keys of ``I[t]`` (``lax.top_k``: a tie to the lower position), every
  head alike: causal softmax over those keys, scale 1/sqrt(128), each
  key-value head serving eight query heads; ``W_o``.
- ``moe``: ``r = softmax(W_r h)`` over all 128 experts; a token's experts
  are the top eight of ``r``, their weights ``r`` over the eight's sum; the
  output the weighted sum of the chosen experts, each a SwiGLU of 768.

Departures, each the configuration's (``deployment`` and ``assumed`` in its
file), none the program's alone:
- this chip holds ``sizes["num_experts"]`` of the ``num_experts_published``
  experts, from ``expert_first``: the router scores and chooses over all of
  them, and what an absent expert would have added is left out of the sum;
- the vocabulary is the slice ``sizes["vocab_size"]``: embedding, head, logits
  and loss are over the slice;
- the layers are the first ``sizes["num_layers"]``; logits go to the loss as
  they are.

Straightforward ``jax.numpy``: the indexer's scores and the attention by
blocks of 512 queries (so that 16,384 keys a row fit the chip), ``lax.top_k``
a row, a dense mask a block; the experts as a plain loop over the held
experts, each over every token, with the routing weight zero where it was not
chosen.  No code of the program under test.  Weights are the program's
parameter tree: a list with one entry per cell.

:func:`loss_with_indexer` is the same model's training objective for the
test suite's gradients: the LM cross-entropy plus each layer's indexer loss
(``mean_t KL(p_t || softmax_{S_t} I[t])``, ``p`` the attention's
probabilities over the set summed over heads and L1-normalised), ``p`` and the
indexer's input held constant.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.references.plain import Tally, cross_entropy

HI = lax.Precision.HIGHEST
QUERY_BLOCK = 512


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


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def silu(x):
    return x * jax.nn.sigmoid(x)


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


def qk_norm(x, p, eps):
    """The per-head RMSNorm of q and of k."""
    return rms_norm(x, p, eps)


def indexer_scores(iq, ik, w):
    """``I [B, q, S] = sum_j w_j relu(q^I_j . k^I)`` for a block of queries."""
    relu = jnp.maximum(jnp.einsum("bqjd,bkd->bqjk", iq, ik, precision=HI), 0)
    return jnp.einsum("bqj,bqjk->bqk", w, relu, precision=HI)


def select(scores, causal, topk):
    """The keys a query attends to: the top ``topk`` of its causal scores
    (``lax.top_k``, a tie to the lower position), ``[B, q, S]`` booleans."""
    bsz, block, s = scores.shape
    _, top = lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, s))
    return jnp.zeros(scores.shape, bool).at[
        jnp.arange(bsz)[:, None, None], jnp.arange(block)[None, :, None],
        top].set(True) & causal


def indexer(h, p, sizes, tally):
    """``(q^I [B, S, 16, 64], k^I [B, S, 64], w [B, S, 16])``."""
    bsz, s, _ = h.shape
    nh, hd = sizes["indexer_num_heads"], sizes["indexer_head_dim"]
    theta, rope = float(sizes["rope_theta"]), hd // 2
    q = linear(h, p["wq"], tally).reshape(bsz, s, nh, hd)
    q = jnp.concatenate([rotate(q[..., :rope], theta), q[..., rope:]], axis=-1)
    k = layer_norm(linear(h, p["wk"], tally), p["k_norm"], 1e-6)[:, :, None]
    k = jnp.concatenate([rotate(k[..., :rope], theta), k[..., rope:]], axis=-1)
    w = linear(h, p["weights_proj"], tally) / math.sqrt(nh * hd)
    return q, k[:, :, 0], w


def selected_pairs(s, topk):
    """Keys attended to in a sequence of ``s``: ``min(topk, t + 1)`` a query."""
    full = min(topk, s)
    return full * (full + 1) // 2 + (s - full) * topk


def attention(h, p, sizes, tally, with_loss=False):
    """The sparse attention's output; and, ``with_loss``, the layer's indexer
    loss beside it (``p`` and the indexer's input held constant)."""
    bsz, s, _ = h.shape
    nh, nkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                   sizes["head_dim"])
    eps, theta, topk = sizes["rms_norm_eps"], float(sizes["rope_theta"]), sizes["topk"]
    q = linear(h, p["q_proj"], tally).reshape(bsz, s, nh, hd)
    k = linear(h, p["k_proj"], tally).reshape(bsz, s, nkv, hd)
    v = linear(h, p["v_proj"], tally).reshape(bsz, s, nkv, hd)
    q = rotate(qk_norm(q, p["q_norm"], eps), theta)
    k = rotate(qk_norm(k, p["k_norm"], eps), theta)
    iq, ik, w = indexer(lax.stop_gradient(h), p["indexer"], sizes, tally)
    group = nh // nkv  # query heads g*group .. (g+1)*group-1 read kv head g
    q = q.reshape(bsz, s, nkv, group, hd)
    if tally is not None:
        tally.add("indexer_scores", bsz * (s * (s + 1) // 2)
                  * sizes["indexer_num_heads"] * sizes["indexer_head_dim"])
        tally.add("sparse_attn", bsz * selected_pairs(s, topk) * nh * 2 * hd)

    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)

    def one_block(i):
        q_pos = i * block + jnp.arange(block)
        causal = key_pos[None, :] <= q_pos[:, None]                 # [blk, S]
        scores = indexer_scores(
            lax.dynamic_slice_in_dim(iq, i * block, block, axis=1), ik,
            lax.dynamic_slice_in_dim(w, i * block, block, axis=1))
        chosen = select(lax.stop_gradient(scores), causal, topk)   # [B, blk, S]
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        logits = jnp.einsum("bqngd,bknd->bngqk", qb, k, precision=HI) / math.sqrt(hd)
        logits = jnp.where(chosen[:, None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bngqk,bknd->bqngd", probs, v, precision=HI)
        if not with_loss:
            return out, jnp.zeros(())
        p_t = lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))       # [B, blk, S]
        log_soft = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
        keep = chosen & (p_t > 0)
        kl = jnp.where(keep, p_t * (jnp.log(jnp.where(keep, p_t, 1.0))
                                    - jnp.where(keep, log_soft, 0.0)), 0.0)
        return out, jnp.sum(kl)

    out, kl = lax.map(one_block, jnp.arange(s // block))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, s, nh * hd)
    return linear(out, p["out_proj"], tally), jnp.sum(kl) / (bsz * s)


def route(h, p, sizes):
    """The chosen experts' indices and their weights, over all the
    published experts: softmax scores, the top ``num_experts_per_tok``,
    renormalised."""
    top_k = sizes["num_experts_per_tok"]
    r = jax.nn.softmax(jnp.dot(h, p["kernel"].astype(jnp.float32), precision=HI),
                       axis=-1)
    # ties go to the lower index
    chosen = jnp.argsort(-r, axis=-1, stable=True)[..., :top_k]
    w = jnp.take_along_axis(r, chosen, axis=-1)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True)


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


def layer(p, h, sizes, tally, with_loss=False):
    eps = sizes["rms_norm_eps"]
    a, kl = attention(rms_norm(h, p["op_norm"], eps), p["op"], sizes, tally,
                      with_loss)
    h = h + a
    h = h + experts(rms_norm(h, p["ffn_norm"], eps), p["ffn"], sizes, tally)
    return (h, kl) if with_loss else h


def cells(params, sizes, tally: Tally | None = None):
    """One function per cell of the program's model: the embedding, the
    ``sizes["num_layers"]`` layers, final norm and head (the logits)."""
    n = sizes["num_layers"]
    assert n == len(params) - 2, (n, len(params))
    assert params[0]["table"].shape == (sizes["vocab_size"], sizes["hidden_size"])

    def embed(ids):
        return params[0]["table"].astype(jnp.float32)[ids]

    def block(i):
        return lambda h: layer(params[i + 1], h, sizes, tally)

    def head(h):
        return linear(rms_norm(h, params[-1]["norm"], sizes["rms_norm_eps"]),
                      params[-1]["head"], tally)

    return [embed] + [block(i) for i in range(n)] + [head]


def loss_with_indexer(params, sizes, ids, labels):
    """The LM cross-entropy plus every layer's indexer loss: the objective
    whose gradient the program's step takes (its reported loss is the
    cross-entropy alone)."""
    h = params[0]["table"].astype(jnp.float32)[ids]
    total_kl = 0.0
    for i in range(sizes["num_layers"]):
        h, kl = layer(params[i + 1], h, sizes, None, with_loss=True)
        total_kl = total_kl + kl
    logits = linear(rms_norm(h, params[-1]["norm"], sizes["rms_norm_eps"]),
                    params[-1]["head"], None)
    return cross_entropy(logits, labels) + total_kl
