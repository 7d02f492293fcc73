"""Ouro-2.6B (ByteDance's LoopLM, Hugging Face ``ouro``), plain float32 forward.

After the published description (``modeling_ouro.py``, the model's
``config.json`` and arXiv 2510.25741): every projection without bias, RMSNorm
with a learned scale and eps 1e-6 (``rms_norm_eps``).

- A layer has sandwich norms:
  ``h = x + RMS_a2(attn(RMS_a1(x)))``; ``x' = h + RMS_m2(mlp(RMS_m1(h)))``.
- ``attn``: ``q, k, v = W_q u, W_k u, W_v u``, each 16 heads of 128; the
  rotary embedding on q and k (a head's two halves turned by
  ``pos / theta^(2i/128)``, theta 1e6), the same positions 0..S-1 in every
  pass; causal softmax of ``q . k / sqrt(128)``; ``W_o`` on the heads side by
  side.  No norm on q or k.
- ``mlp``: ``W_down(silu(W_gate u) * W_up u)``, 5,632 wide.
- The loop: ``x^0 = E[ids]``; for ``t = 1..total_ut_steps``, ``x^t =
  RMS_f(layer_{L-1} o ... o layer_0(x^{t-1}))`` with the SAME weights in
  every pass, the one final norm after every pass and its output the next
  pass's input; ``logits = x^T W_head``, no further norm, the head its own
  weights (``tie_word_embeddings`` false).
- The loss is ``OuroForCausalLM``'s with labels: the cross-entropy of the
  last pass's logits against the next ids (``cross_entropy`` of
  ``plain.py``, the harness's).

Departures, each the configuration's (``deployment`` and ``assumed`` in its
file), none the program's alone:
- the layers are the first ``sizes["num_layers"]``, one pipeline stage's,
  and ``RMS_f`` follows the last of them where the model has it after layer
  47;
- the exit gate (``Linear(2048, 1)`` on each pass's ``x^t``) and the
  entropy-regularised objective over the four exits are not computed: the
  gate feeds no logit and no term of the language-model loss;
- the program keeps ``gate_proj``, ``up_proj`` and ``down_proj`` as ``w1``,
  ``w3`` and ``w2``, and ``o_proj`` as ``out_proj``: the same sums.

Straightforward ``jax.numpy``: attention by blocks of queries and the MLP by
sequence, so that 8,192 tokens fit the chip beside the program's cells.  No
code of the program under test.  Weights are the program's parameter tree,
one entry a cell: every pass reads the embedding's ``table``, layer ``l``'s
parameters in entry ``1 + l`` and ``RMS_f``'s ``norm`` in entry ``1 + L``,
pass 0's cells; the head's ``head`` is the last entry's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.references.plain import Tally, cross_entropy

HI = lax.Precision.HIGHEST
QUERY_BLOCK = 256


def batch_spec(sizes, traffic):
    shape = (traffic["batch_size"], traffic["size"])
    return (jax.ShapeDtypeStruct(shape, jnp.int32),
            jax.ShapeDtypeStruct(shape, jnp.int32))


def linear(x, p, tally, kind):
    """``x @ kernel`` over the last axis; no bias on any projection."""
    k = p["kernel"].astype(jnp.float32)
    if tally is not None:
        tally.add(kind, math.prod(x.shape[:-1]) * k.shape[0] * k.shape[1])
    return jnp.dot(x, k, precision=HI)


def rms_norm(x, p, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p[
        "scale"].astype(jnp.float32)


def post_norm(y, p, eps):
    """A branch's output normalised before the residual add."""
    return rms_norm(y, p, eps)


def pass_norm(x, p, eps, t):
    """``RMS_f`` after pass ``t``."""
    return rms_norm(x, p, eps)


def passes(sizes):
    """How many times the layers run."""
    return sizes["total_ut_steps"]


def rope(x, theta):
    """``[B, S, H, hd]``: the two halves of a head turned by ``pos /
    theta^(2i/hd)``."""
    s, hd = x.shape[1], x.shape[3]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, p, sizes, tally):
    bsz, s, _ = h.shape
    nh, nkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                   sizes["head_dim"])
    q = linear(h, p["q_proj"], tally, "attn_proj").reshape(bsz, s, nh, hd)
    k = linear(h, p["k_proj"], tally, "attn_proj").reshape(bsz, s, nkv, hd)
    v = linear(h, p["v_proj"], tally, "attn_proj").reshape(bsz, s, nkv, hd)
    q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    if tally is not None:  # the causal half: q k^T and p v at hd each
        tally.add("attn_scores", bsz * nh * (s * (s + 1) // 2) * 2 * hd)

    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)

    def one_block(i):
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k, precision=HI) / math.sqrt(hd)
        q_pos = i * block + jnp.arange(block)
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores, -jnp.inf)
        return jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1),
                          v, precision=HI)

    out = lax.map(one_block, jnp.arange(s // block))  # [blocks, B, block, ...]
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, s, nh * hd)
    return linear(out, p["out_proj"], tally, "attn_proj")


def mlp(h, p, tally):
    """By sequence: ``w1`` is ``gate_proj``, ``w3`` ``up_proj``, ``w2``
    ``down_proj``."""
    if tally is not None:
        for name in ("w1", "w3", "w2"):
            k = p[name]["kernel"]
            tally.add("mlp", math.prod(h.shape[:-1]) * k.shape[0] * k.shape[1])

    def one(x):
        gate = linear(x, p["w1"], None, None)
        return linear(jax.nn.silu(gate) * linear(x, p["w3"], None, None),
                      p["w2"], None, None)

    return lax.map(one, h)


def layer(p, x, sizes, tally):
    eps = sizes["rms_norm_eps"]
    h = x + post_norm(attention(rms_norm(x, p["op_norm"], eps), p["op"], sizes,
                                tally), p["op_post_norm"], eps)
    return h + post_norm(mlp(rms_norm(h, p["ffn_norm"], eps), p["ffn"], tally),
                         p["ffn_post_norm"], eps)


def cells(params, sizes, tally: Tally | None = None):
    """One function per cell of the program's model: the embedding; for each
    of the ``total_ut_steps`` passes the ``num_layers`` layers and ``RMS_f``,
    on pass 0's parameters; the head.  A pass beyond :func:`passes` leaves
    its activation as it is (a planted fault's: too few passes)."""
    n, steps = sizes["num_layers"], sizes["total_ut_steps"]
    assert len(params) == 2 + steps * (n + 1), (len(params), n, steps)
    assert params[0]["table"].shape == (sizes["vocab_size"], sizes["hidden_size"])
    eps = sizes["rms_norm_eps"]

    def embed(ids):
        return params[0]["table"].astype(jnp.float32)[ids]

    def block(i):
        return lambda x: layer(params[1 + i], x, sizes, tally)

    def final_norm(t):
        return lambda x: pass_norm(x, params[1 + n]["norm"], eps, t)

    def head(x):
        return linear(x, params[-1]["head"], tally, "head")

    out = [embed]
    for t in range(steps):
        run = t < passes(sizes)
        out += [block(i) if run else (lambda x: x) for i in range(n)]
        out.append(final_norm(t) if run else (lambda x: x))
    return out + [head]


def loss(params, sizes, ids, labels):
    """The whole model's loss in float32, for the gradients."""
    act = ids
    for fn in cells(params, sizes):
        act = fn(act)
    return cross_entropy(act, labels)
