"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, Hugging Face
``KeyeVL2``) as a token ``CellModel``.

Forty-eight layers, each ``h += attn(RMSNorm(h))`` then ``h += moe(RMSNorm(h))``,
no leading dense layer (``mlp_only_layers`` empty, ``decoder_sparse_step``
1), then one more RMSNorm and the head (not tied to the embedding).

``attn`` is grouped-query attention (32 query heads, 4 key-value heads of
128; a per-head RMSNorm on q and k before the rotary embedding, the Qwen3
base's) under DeepSeek Sparse Attention: an indexer (``sa_config``: 16 heads
of 64, one key head) scores every earlier key,

    I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]),
    q^I = RoPE(W_q^I x),  k^I = RoPE(LayerNorm(W_k^I x)),
    w = W_w x / sqrt(16 · 64),

and query ``t`` attends to the ``min(2048, t + 1)`` keys of the largest
scores alone (``ops/sparse_indexer.py``: exact, a tie to the lower
position), one set for all 32 heads.  The indexer trains on its own loss,
``mean_t KL(p_t || softmax_{S_t} I[t])`` with ``p_t`` the attention's
probabilities over the set summed over heads and L1-normalised: ``p`` and
the indexer's input are constants to it, so the LM loss trains everything
but the indexer and the indexer loss the indexer alone.  Its gradient is
put in by the attention's backward rule (:func:`sparse_attention`); the
step's loss stays the LM cross-entropy.

``moe`` is 128 routed experts of 768, eight a token, softmax scores over all
128 renormalised over the eight (``ops/moe.RoutedExperts``, ``scoring
"softmax"``), no shared expert.

:data:`PUBLISHED` is the model's ``config.json`` (the language model's keys),
key for key.  The flags state only the cut and the job, as for the other
token models (models/lfm2.py, whose ``Attention`` parts, ``rotary``,
``BlockCell``, ``embed_cell``, ``head_cell`` and ``routed_step_metrics`` this
model shares).  Text only: M-RoPE's three sections carry one position, so
the rotary embedding is the 1-D one over a head's 128 dimensions.  The vision
tower is not here.

On a TPU backend the attention and the indexer are Pallas kernels
(``ops/pallas_attention.sparse_flash_forward`` and ``_backward`` over the
selection's bitmask, ``ops/sparse_indexer.indexer_select`` and
``indexer_backward``); elsewhere XLA's products over whole score matrices.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpi4dl_tpu.cells import CellModel
from mpi4dl_tpu.layers import Dense, Layer, RMSNorm
from mpi4dl_tpu.models.lfm2 import (
    Attention, BlockCell, embed_cell, head_cell, layers_run, rotary,
    routed_step_metrics)
from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.obs.spans import recorder
from mpi4dl_tpu.ops import pallas_attention, sparse_indexer
from mpi4dl_tpu.ops.moe import RoutedExperts


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    """``https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/
    config.json``, the language model's keys that say something about its
    shape, under their names."""

    attention_bias: bool = False
    decoder_sparse_step: int = 1
    head_dim: int = 128
    hidden_act: str = "silu"
    hidden_size: int = 2048
    intermediate_size: int = 6144
    max_position_embeddings: int = 262144
    max_window_layers: int = 48
    mlp_only_layers: Tuple[int, ...] = ()
    model_type: str = "KeyeVL2"
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_hidden_layers: int = 48
    num_key_value_heads: int = 4
    rms_norm_eps: float = 1e-06
    rope_scaling: Mapping[str, Any] = dataclasses.field(default_factory=lambda: {
        "mrope_section": (16, 24, 24), "rope_type": "default",
        "type": "default"})
    rope_theta: float = 10000000
    sa_config: Mapping[str, int] = dataclasses.field(default_factory=lambda: {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048})
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    vocab_size: int = 151936

    @property
    def num_dense_layers(self) -> int:
        """The leading layers whose ``ffn`` is dense (``lfm2.layers_run``'s
        name): none, ``mlp_only_layers`` is empty."""
        return 0


PUBLISHED = KeyeVL2Config()
# The embedding's standard deviation at initialisation; every kernel is
# U(+-1/sqrt(fan_in)) as elsewhere.  Not the family's initializer_range of
# 0.02, for Kanana-2's reason (models/deepseek_v3.EMBED_STD): under random
# weights attention's output is a running mean that all later tokens share,
# and beside a 0.02 embedding it carries the stream, so the router sends every
# token to the same experts (at 0.02 the busiest of 128 experts takes 15 times
# the mean from the second layer on; at 0.5 1.4 to 2.4 times, the held share
# 11.7 to 13.3 %: PERF.md, section 4).
EMBED_STD = 0.5
INDEXER_NORM_EPS = 1e-6  # DeepSeek-V3.2's indexer LayerNorm (not in the config)
KL_ROWS = 16  # queries a sequence whose indexer loss a step reports


@dataclasses.dataclass(frozen=True)
class LayerNorm(Layer):
    """``(x - mean) / sqrt(var + eps) * scale + bias`` over the last axis, in
    float32, handed on in the activation's dtype."""

    features: int
    eps: float

    def init(self, key, in_shape):
        return {"scale": jnp.ones((self.features,), jnp.float32),
                "bias": jnp.zeros((self.features,), jnp.float32)}, in_shape

    def apply(self, params, x, ctx):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        return (y * params["scale"] + params["bias"]).astype(x.dtype)


def rotary_first_half(x, theta: float):
    """The rotary embedding on the first half of a head's dimensions
    (``[B, S, H, hd]``, halves of that half turned), the rest as it is: the
    indexer's, after DeepSeek-V3.2."""
    half = x.shape[-1] // 2
    return jnp.concatenate([rotary(x[..., :half], theta), x[..., half:]], -1)


@dataclasses.dataclass(frozen=True)
class Indexer(Layer):
    """The indexer's queries, key and head weights from the layer's input,
    which is a constant to it (``stop_gradient``: its loss trains its own
    weights alone): ``(iq [B, S, heads, head_dim], ik [B, S, head_dim], w
    [B, S, heads] float32)``."""

    features: int
    heads: int
    head_dim: int
    rope_theta: float

    def _parts(self) -> Dict[str, Layer]:
        d = self.features
        return {"wq": Dense(d, self.heads * self.head_dim, use_bias=False),
                "wk": Dense(d, self.head_dim, use_bias=False),
                "k_norm": LayerNorm(self.head_dim, INDEXER_NORM_EPS),
                "weights_proj": Dense(d, self.heads, use_bias=False)}

    def init(self, key, in_shape):
        parts = self._parts()
        keys = jax.random.split(key, len(parts))
        shapes = {"k_norm": (*in_shape[:-1], self.head_dim)}
        return {n: layer.init(k, shapes.get(n, in_shape))[0]
                for k, (n, layer) in zip(keys, parts.items())}, in_shape

    def apply(self, params, x, ctx):
        parts = self._parts()
        x = jax.lax.stop_gradient(x)
        b, s, _ = x.shape
        part = lambda n, y: parts[n].apply(params[n], y, ctx)
        iq = rotary_first_half(
            part("wq", x).reshape(b, s, self.heads, self.head_dim),
            self.rope_theta)
        ik = rotary_first_half(part("k_norm", part("wk", x))[:, :, None],
                               self.rope_theta)[:, :, 0]
        w = part("weights_proj", x).astype(jnp.float32) * (
            self.heads * self.head_dim) ** -0.5
        return iq, ik, w


def dense_attention(q, k, v, sel, scale):
    """Attention over the selected keys by XLA's products: ``q [B, T, H,
    D]``, ``k``, ``v [B, T, KV, D]``, ``sel [B, T, T]`` booleans; the output
    ``[B, T, H, D]`` in q's dtype, the softmax in float32."""
    rep = q.shape[2] // k.shape[2]
    f32 = jnp.float32
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, axis=2),
                   preferred_element_type=f32) * scale
    p = jax.nn.softmax(jnp.where(sel[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      jnp.repeat(v, rep, axis=2).astype(f32)).astype(q.dtype)


def _fold(x):
    """``[B, T, H, D]`` heads-first, ``[B·H, T, D]``."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def sampled_kl(q, k, iq, ik, w, words_t, scale):
    """The indexer loss over :data:`KL_ROWS` queries of each sequence, evenly
    spaced and the last among them (what a step reports: the loss over every
    query needs the attention's probabilities of every pair, which only the
    backward kernel makes)."""
    t = q.shape[1]
    n = min(KL_ROWS, t)
    rows = (np.arange(1, n + 1) * t) // n - 1
    sel = sparse_indexer.unpack_selection(
        jnp.swapaxes(words_t[:, :, rows], 1, 2), t)
    p = sparse_indexer.head_mean_probs(q[:, rows], k, sel, scale)
    scores = sparse_indexer.scores_dense(iq[:, rows], ik, w[:, rows])
    log_soft = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
    keep = sel & (p > 0)
    kl = jnp.where(keep, p * (jnp.log(jnp.where(keep, p, 1.0))
                              - jnp.where(keep, log_soft, 0.0)), 0.0)
    return jnp.mean(jnp.sum(kl, axis=-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def sparse_attention(q, k, v, iq, ik, w, topk: int, scale: float,
                     flash: bool):
    """Attention of ``q [B, T, H, D]`` over ``k``, ``v [B, T, KV, D]`` where
    query ``t`` sees the ``min(topk, t + 1)`` keys the indexer scores highest
    (``iq``, ``ik``, ``w``: :class:`Indexer`'s).  Returns the output ``[B, T,
    H, D]`` and the sampled indexer loss (:func:`sampled_kl`).  The backward
    rule gives q, k and v the attention's
    gradient, and ``iq``, ``ik``, ``w`` the indexer loss's (mean over the
    batch's positions, weight one), whatever the output's cotangent."""
    return _sparse_fwd(q, k, v, iq, ik, w, topk, scale, flash)[0]


def _sparse_fwd(q, k, v, iq, ik, w, topk, scale, flash):
    b, t, h, _ = q.shape
    with scope("sparse_indexer"):
        select = (sparse_indexer.indexer_select if flash
                  else sparse_indexer.select_dense)
        words_t, lse = select(iq, ik, w, topk)
    words = jnp.swapaxes(words_t, 1, 2)
    if flash:
        # the key-value heads as they are: a grid step of the kernel reads
        # one k and one v block for the query heads of their group
        with scope("attention_core"):
            o, m, l = pallas_attention.sparse_flash_forward(
                _fold(q), _fold(k), _fold(v), words, heads=h, scale=scale)
        out = o.reshape(b, h, t, -1).transpose(0, 2, 1, 3).astype(q.dtype)
        rows = (m, l, o)
    else:
        with scope("attention_core"):
            out = dense_attention(
                q, k, v, sparse_indexer.unpack_selection(words, t), scale)
        rows = None
    with scope("sparse_indexer"):
        kl = sampled_kl(q, k, iq, ik, w, words_t, scale)
    return (out, kl), (q, k, v, iq, ik, w, words_t, lse, rows)


def _sparse_bwd(topk, scale, flash, res, cts):
    q, k, v, iq, ik, w, words_t, lse, rows = res
    do = cts[0]
    b, t, h, d = q.shape
    kv = k.shape[2]
    inv_n = 1.0 / (b * t)
    if flash:
        m, l, o = rows
        # the key-value heads as they are, dô as it comes: the kernel divides
        # by l, computes Δ = Σ dô·o and sums dk and dv over a group's heads
        with scope("attention_core"):
            dq, dk, dv = pallas_attention.sparse_flash_backward(
                _fold(q), _fold(k), _fold(v), o, m, l, _fold(do), words_t,
                heads=h, scale=scale)
        unfold = lambda x, n: x.reshape(b, n, t, d).transpose(0, 2, 1, 3)
        dq, dk, dv = unfold(dq, h), unfold(dk, kv), unfold(dv, kv)
        with scope("sparse_indexer"):
            diq, dik, dw = sparse_indexer.indexer_backward(
                q, k, (m + jnp.log(jnp.maximum(l, 1e-30))).reshape(b, h, t),
                iq, ik, w, words_t, lse, scale=scale, inv_n=inv_n)
    else:
        sel = sparse_indexer.unpack_selection(jnp.swapaxes(words_t, 1, 2), t)
        with scope("attention_core"):
            _, vjp = jax.vjp(lambda q, k, v: dense_attention(q, k, v, sel, scale),
                             q, k, v)
            dq, dk, dv = vjp(do)
        with scope("sparse_indexer"):
            diq, dik, dw = sparse_indexer.indexer_grads_dense(
                sparse_indexer.head_mean_probs(q, k, sel, scale), iq, ik, w,
                sel, lse, inv_n)
    return (dq, dk, dv, diq.astype(iq.dtype), dik.astype(ik.dtype),
            dw.astype(w.dtype))


sparse_attention.defvjp(_sparse_fwd, _sparse_bwd)


@dataclasses.dataclass(frozen=True)
class SparseAttention(Layer):
    """Grouped-query attention (``lfm2.Attention``'s projections, per-head
    norms, rotary embedding and grouping) over the keys :class:`Indexer`'s
    scores select (:func:`sparse_attention`).  Its parameters are the
    attention's, the indexer's under ``indexer``, and ``sparse_kl``: the last
    step's sampled indexer loss, written through ``ctx.bn_sink`` as the
    experts' ``load`` is."""

    attention: Attention
    indexer: Indexer
    topk: int

    def init(self, key, in_shape):
        k_att, k_idx = jax.random.split(key)
        return {**self.attention.init(k_att, in_shape)[0],
                "indexer": self.indexer.init(k_idx, in_shape)[0],
                "sparse_kl": jnp.zeros((), jnp.float32)}, in_shape

    def apply(self, params, x, ctx):
        from mpi4dl_tpu.ops.ring import _resolve_flash

        flash = _resolve_flash(None)
        rec = recorder()
        rec.note_site("attention", self,
                      "sparse_block_flash" if flash else "sparse_einsum")
        rec.note_site("sparse_indexer", self.indexer,
                      "pallas" if flash else "xla")
        att = self.attention
        if flash:  # the query heads that share a plane: the kernels' group
            for kind in ("sparse_plane_heads", "sparse_bwd_plane_heads"):
                rec.note_site(kind, self, str(att.heads // att.kv_heads))
        b, s, _ = x.shape
        q, k, v = att.project(params, x, ctx)
        with scope("sparse_indexer"):
            iq, ik, w = self.indexer.apply(params["indexer"], x, ctx)
        o, kl = sparse_attention(q, k, v, iq, ik, w, self.topk,
                                 att.head_dim ** -0.5, flash)
        if ctx.bn_sink is not None:
            ctx.bn_sink[id(params["sparse_kl"])] = kl
        return att._parts()["out_proj"].apply(
            params["out_proj"], o.reshape(b, s, att.heads * att.head_dim), ctx)


def _check(config: KeyeVL2Config) -> None:
    """What this model computes of the family; anything else is refused."""
    sa = config.sa_config
    unsupported = {
        "mlp_only_layers": bool(config.mlp_only_layers),
        "decoder_sparse_step": config.decoder_sparse_step != 1,
        "use_sliding_window": config.use_sliding_window,
        "attention_bias": config.attention_bias,
        "tie_word_embeddings": config.tie_word_embeddings,
        "hidden_act": config.hidden_act != "silu",
        "norm_topk_prob false": not config.norm_topk_prob,
        "indexer_num_kv_heads": sa["indexer_num_kv_heads"] != 1,
        "mrope_section": 2 * sum(config.rope_scaling["mrope_section"])
        != config.head_dim,
    }
    bad = [name for name, is_bad in unsupported.items() if is_bad]
    if bad:
        raise ValueError(f"keye_vl2: not computed here: {', '.join(bad)}")


def _block(config: KeyeVL2Config, layer: int, experts_held: int,
           expert_first: int) -> BlockCell:
    d, sa = config.hidden_size, config.sa_config
    op = SparseAttention(
        Attention(d, config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim, float(config.rope_theta),
                  config.rms_norm_eps),
        Indexer(d, sa["indexer_num_heads"], sa["indexer_head_dim"],
                float(config.rope_theta)),
        sa["topk"])
    ffn = RoutedExperts(d, config.moe_intermediate_size, config.num_experts,
                        config.num_experts_per_tok, experts_held, expert_first,
                        1.0, 0.0, "softmax")
    return BlockCell(op, ffn, RMSNorm(d, config.rms_norm_eps),
                     name=f"layer{layer:02d}_dsa")


def sparse_step_metrics(cells, routed_metrics):
    """``CellModel.step_metrics``: the experts' counts, and the sampled
    indexer loss averaged over the sparse attention layers ``cells``
    (``sparse_kl``)."""

    def step_metrics(params, tokens):
        out = routed_metrics(params, tokens)
        out["sparse_kl"] = jnp.mean(
            jnp.stack([params[i]["op"]["sparse_kl"] for i in cells]))
        return out

    return step_metrics


def keye_vl2(in_shape: Tuple[int, int], *, num_layers: int, vocab_size: int,
             experts_held: int, expert_first: int = 0,
             compute_dtype=jnp.float32,
             config: Optional[KeyeVL2Config] = None) -> CellModel:
    """The model on ``in_shape = (batch, seq_len)`` int32 ids below
    ``vocab_size``: embedding, ``num_layers`` layers, final norm and head;
    the logits are ``[batch, seq_len, vocab_size]`` in float32.  ``config``:
    :data:`PUBLISHED` unless a test hands in toy widths."""
    config = config or PUBLISHED
    _check(config)
    d = config.hidden_size
    if not 1 <= vocab_size <= config.vocab_size:
        raise ValueError(f"--vocab-size {vocab_size} of {config.vocab_size}")
    blocks = [_block(config, layer, experts_held, expert_first)
              for layer in layers_run(config, num_layers)]
    cells = list(range(1, len(blocks) + 1))
    return CellModel(
        [embed_cell(vocab_size, d, compute_dtype, EMBED_STD), *blocks,
         head_cell(vocab_size, d, config.rms_norm_eps)],
        tuple(in_shape), vocab_size, name="keye_vl2",
        step_metrics=sparse_step_metrics(
            cells, routed_step_metrics(cells, config.num_experts_per_tok)))
