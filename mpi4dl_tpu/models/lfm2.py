"""LFM2-24B-A2B (LiquidAI, Hugging Face ``lfm2_moe``) as a token ``CellModel``.

Forty layers, each ``h += op(RMSNorm(h))`` then ``h += ffn(RMSNorm(h))``.
``op`` is a gated short convolution (30 layers) or grouped-query attention
with a per-head RMSNorm and a rotary embedding on q and k (layers 2, 6, ...,
38); ``ffn`` is a SwiGLU in the first two layers and a routed expert layer
(64 experts, four a token, sigmoid scores) in the other 38.  Every projection
is without bias.  After the last layer one more RMSNorm, then the head.

:data:`PUBLISHED` is the model's ``config.json``, key for key.  The flags
state only the cut and the job (config.py): ``--num-layers`` (layers as run),
``--vocab-size`` (rows of the vocabulary held), ``--experts-held`` and
``--expert-first`` (this chip's experts under expert parallelism, ops/moe.py),
``--seq-len``.  Forty layers, 64 experts and 65,536 rows are the uncut model.
A cut in depth keeps the leading dense layers once: ``n`` layers are the
published layers ``num_dense_layers - 1`` to ``num_dense_layers + n - 2``,
each with its own published ``layer_type`` (nine layers: the dense layer 1,
then two whole periods of ``full_attention, conv, conv, conv``, layers 2-9).

One cell a layer, an embedding cell before and a cell of final norm and head
after; each cell owns its parameters (embedding and head are not tied here; a
model that ties them says so in ``CellModel.tied``), as ``split_even`` and the
benchmark's cell-by-cell check need.  Activations
between cells are ``[B, S, hidden]`` in the compute dtype; norms, the
router's scores, the softmax and the loss are computed in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.cells import Cell, CellModel, FnCell
from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.layers import CausalConv1d, Dense, Layer, RMSNorm
from mpi4dl_tpu.obs.spans import recorder
from mpi4dl_tpu.ops.moe import RoutedExperts

_PERIOD = ("conv", "conv", "full_attention", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """``https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json``,
    the keys that say something about the model's shape, under their names."""

    conv_L_cache: int = 3
    conv_bias: bool = False
    hidden_size: int = 2048
    intermediate_size: int = 11776
    layer_types: Tuple[str, ...] = _PERIOD * 10
    max_position_embeddings: int = 128000
    model_type: str = "lfm2_moe"
    moe_intermediate_size: int = 1536
    norm_eps: float = 1e-05
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    rope_parameters: Mapping[str, Any] = dataclasses.field(
        default_factory=lambda: {"rope_theta": 1000000, "rope_type": "default"})
    routed_scaling_factor: float = 1
    use_expert_bias: bool = True
    vocab_size: int = 65536

    @property
    def head_dim(self) -> int:
        # not a key of the config: hidden_size / num_attention_heads
        return self.hidden_size // self.num_attention_heads


PUBLISHED = Lfm2MoeConfig()
EMBED_STD = 0.02  # the family's initializer_range; kernels: U(+-1/sqrt(fan_in))


def layers_run(config: Lfm2MoeConfig, num_layers: int) -> Tuple[int, ...]:
    """The published layers that a model of ``num_layers`` layers runs: all of
    them, or, cut, a run that starts at the last leading dense layer."""
    if not 1 <= num_layers <= config.num_hidden_layers:
        raise ValueError(f"--num-layers {num_layers}: the model has "
                         f"{config.num_hidden_layers}")
    first = (0 if num_layers == config.num_hidden_layers
             else max(config.num_dense_layers - 1, 0))
    if first + num_layers > config.num_hidden_layers:
        raise ValueError(f"--num-layers {num_layers}: a cut model starts at "
                         f"layer {first} of {config.num_hidden_layers}")
    return tuple(range(first, first + num_layers))


@dataclasses.dataclass(frozen=True)
class ShortConv(Layer):
    """The gated short convolution: ``B, C, x = split3(W_in h)``;
    ``y = W_out (C * conv(B * x))``, the convolution depthwise and causal."""

    features: int
    kernel_size: int

    def _parts(self):
        d = self.features
        return (Dense(d, 3 * d, use_bias=False),
                CausalConv1d(d, self.kernel_size), Dense(d, d, use_bias=False))

    def init(self, key, in_shape):
        names = ("in_proj", "conv", "out_proj")
        keys = jax.random.split(key, 3)
        return {n: layer.init(k, in_shape)[0]
                for n, k, layer in zip(names, keys, self._parts())}, in_shape

    def apply(self, params, x, ctx):
        in_proj, conv, out_proj = self._parts()
        b, c, u = jnp.split(in_proj.apply(params["in_proj"], x, ctx), 3, axis=-1)
        y = c * conv.apply(params["conv"], b * u, ctx)
        return out_proj.apply(params["out_proj"], y, ctx)


def rotary(x, theta: float):
    """The default rotary embedding on ``[B, S, H, hd]``, positions 0..S-1:
    the two halves of a head rotated by ``pos / theta^(2i/hd)``, in float32."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class Attention(Layer):
    """Causal grouped-query attention: RMSNorm over each head of q and of k,
    the rotary embedding on both, each key-value head serving
    ``heads // kv_heads`` query heads, scale ``head_dim ** -0.5``.  A model
    without them says so: ``qk_norm`` false (no ``q_norm``, ``k_norm``
    parameters), ``rope_theta`` None (no position signal at all), a ``scale``
    of its own.

    The attention itself is ``ops.ring.ring_attention`` on one shard: the
    Pallas block kernel on a TPU backend, the einsum form elsewhere.  At
    8,192 tokens the einsum form's scores are 8.6 GB a sequence in float32,
    so it is not the chip's.  The kernel takes one sequence at a time
    (``lax.map``): its padded operands and its backward tiles are then one
    sequence's."""

    features: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: Optional[float]
    eps: float
    qk_norm: bool = True
    scale: Optional[float] = None

    def _parts(self) -> Dict[str, Layer]:
        d, hd = self.features, self.head_dim
        parts: Dict[str, Layer] = {
            "q_proj": Dense(d, self.heads * hd, use_bias=False),
            "k_proj": Dense(d, self.kv_heads * hd, use_bias=False),
            "v_proj": Dense(d, self.kv_heads * hd, use_bias=False),
            "out_proj": Dense(self.heads * hd, d, use_bias=False),
        }
        if self.qk_norm:
            parts.update(q_norm=RMSNorm(hd, self.eps),
                         k_norm=RMSNorm(hd, self.eps))
        return parts

    def init(self, key, in_shape):
        parts = self._parts()
        keys = jax.random.split(key, len(parts))
        shapes = {"out_proj": (*in_shape[:-1], self.heads * self.head_dim),
                  "q_norm": (self.head_dim,), "k_norm": (self.head_dim,)}
        return {n: layer.init(k, shapes.get(n, in_shape))[0]
                for k, (n, layer) in zip(keys, parts.items())}, in_shape

    def project(self, params, x, ctx):
        """``q [B, S, heads, head_dim]``, ``k`` and ``v [B, S, kv_heads,
        head_dim]``: the projections, q's and k's per-head norms and rotary
        embedding; a key-value head serves ``heads // kv_heads`` query heads
        (the caller repeats them)."""
        parts = self._parts()
        b, s, _ = x.shape

        def heads(name, n):
            y = parts[name + "_proj"].apply(params[name + "_proj"], x, ctx)
            return y.reshape(b, s, n, self.head_dim)

        def normed(name, n):
            y = heads(name, n)
            return (parts[name + "_norm"].apply(params[name + "_norm"], y, ctx)
                    if self.qk_norm else y)

        q, k = normed("q", self.heads), normed("k", self.kv_heads)
        if self.rope_theta is not None:
            q, k = rotary(q, self.rope_theta), rotary(k, self.rope_theta)
        return q, k, heads("v", self.kv_heads)

    def apply(self, params, x, ctx):
        from mpi4dl_tpu.ops.pallas_attention import (
            LOCAL_TILES, causal_tile_split)
        from mpi4dl_tpu.ops.ring import _resolve_flash, ring_attention

        parts = self._parts()
        b, s, _ = x.shape
        q, k, v = self.project(params, x, ctx)
        rep = self.heads // self.kv_heads
        if rep > 1:
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        flash = _resolve_flash(None)
        recorder().note_site("attention", self,
                             "block_flash" if flash else "einsum")
        if flash:  # the forward kernel's live tiles that fold whole, in %
            whole, diagonal, _ = causal_tile_split(s, s, *LOCAL_TILES)
            recorder().note_site("flash_whole_tile_pct", self,
                                 str(round(100 * whole / (whole + diagonal))))

        scale = self.head_dim ** -0.5 if self.scale is None else self.scale

        def attend(qkv):
            return ring_attention(*(t[None] for t in qkv), None, 1, causal=True,
                                  scale=scale)[0]

        o = lax.map(attend, (q, k, v))
        return parts["out_proj"].apply(
            params["out_proj"], o.reshape(b, s, self.heads * self.head_dim), ctx)


@dataclasses.dataclass(frozen=True)
class SwiGLU(Layer):
    """``W2 (silu(W1 h) * W3 h)``."""

    features: int
    ffn: int

    def _parts(self):
        return {"w1": Dense(self.features, self.ffn, use_bias=False),
                "w3": Dense(self.features, self.ffn, use_bias=False),
                "w2": Dense(self.ffn, self.features, use_bias=False)}

    def init(self, key, in_shape):
        parts = self._parts()
        keys = jax.random.split(key, 3)
        hidden = (*in_shape[:-1], self.ffn)
        return {n: layer.init(k, hidden if n == "w2" else in_shape)[0]
                for k, (n, layer) in zip(keys, parts.items())}, in_shape

    def apply(self, params, x, ctx):
        parts = self._parts()
        up = lambda n: parts[n].apply(params[n], x, ctx)
        return parts["w2"].apply(
            params["w2"], jax.nn.silu(up("w1")) * up("w3"), ctx)


@dataclasses.dataclass
class BlockCell(Cell):
    """One layer: ``h += m op(norm(h))``, then ``h += m ffn(norm(h))``, with
    ``m`` the ``residual_multiplier`` (1 in most models: nothing is traced
    for it then).  ``post_norms``: each branch's output is normalised too
    before the add, ``h += m norm2(op(norm1(h)))`` (sandwich norms, scales
    ``op_post_norm`` and ``ffn_post_norm``); off, nothing is traced for it."""

    op: Layer
    ffn: Layer
    norm: RMSNorm
    name: str = "layer"
    residual_multiplier: float = 1.0
    post_norms: bool = False

    def init(self, key, in_shape):
        k_op, k_ffn = jax.random.split(key)
        scale = lambda: self.norm.init(None, in_shape)[0]
        params = {"op_norm": scale(), "op": self.op.init(k_op, in_shape)[0],
                  "ffn_norm": scale(), "ffn": self.ffn.init(k_ffn, in_shape)[0]}
        if self.post_norms:
            params.update(op_post_norm=scale(), ffn_post_norm=scale())
        return params, in_shape

    def apply(self, params, x, ctx):
        m = self.residual_multiplier

        def branch(name, layer, x):
            y = layer.apply(
                params[name], self.norm.apply(params[name + "_norm"], x, ctx), ctx)
            if self.post_norms:
                y = self.norm.apply(params[name + "_post_norm"], y, ctx)
            return y if m == 1 else y * m

        x = x + branch("op", self.op, x)
        return x + branch("ffn", self.ffn, x)


def _block(config: Lfm2MoeConfig, layer: int, experts_held: int,
           expert_first: int) -> BlockCell:
    d = config.hidden_size
    kind = config.layer_types[layer]
    if kind == "conv":
        assert not config.conv_bias
        op: Layer = ShortConv(d, config.conv_L_cache)
    elif kind == "full_attention":
        op = Attention(d, config.num_attention_heads, config.num_key_value_heads,
                       config.head_dim, float(config.rope_parameters["rope_theta"]),
                       config.norm_eps)
    else:
        raise ValueError(f"layer {layer}: unknown layer_type {kind!r}")
    if layer < config.num_dense_layers:
        ffn: Layer = SwiGLU(d, config.intermediate_size)
    else:
        assert config.norm_topk_prob and config.use_expert_bias
        ffn = RoutedExperts(
            d, config.moe_intermediate_size, config.num_experts,
            config.num_experts_per_tok, experts_held, expert_first,
            float(config.routed_scaling_factor))
    return BlockCell(op, ffn, RMSNorm(d, config.norm_eps),
                     name=f"layer{layer:02d}_{kind}")


def embed_cell(vocab_size: int, features: int, compute_dtype,
               std: float = EMBED_STD, multiplier: float = 1.0) -> FnCell:
    """``[B, S]`` ids to ``[B, S, features]`` in the compute dtype, times
    ``multiplier`` where it is not 1; the table normal with standard
    deviation ``std``."""

    def embed_init(key, shape):
        table = jax.random.normal(
            key, (vocab_size, features), jnp.float32) * std
        return {"table": table}, (*shape, features)

    def embed(p, ids, ctx):
        # a pipeline stage's input arrives in the compute dtype
        rows = jnp.take(p["table"].astype(compute_dtype),
                        ids.astype(jnp.int32), axis=0)
        return rows if multiplier == 1 else rows * multiplier

    return FnCell(embed_init, embed, "embed")


def head_cell(vocab_size: int, features: int, eps: float, *,
              logits_scaling: float = 1.0, tied: bool = False,
              norm: bool = True) -> FnCell:
    """The final RMSNorm and the head: logits ``[B, S, vocab_size]`` in
    float32, over ``logits_scaling`` where it is not 1.  The head is this
    cell's own parameter ``head.kernel`` ``[features, vocab_size]``, or,
    ``tied``, the embedding's ``table`` ``[vocab_size, features]``: the cell
    then initialises no head and reads ``table`` beside its ``norm``, which
    the model's ``CellModel.tied`` puts there from the embedding cell's
    parameters.  ``norm`` false: the head alone, on an activation that a
    cell before it has normalised."""
    rms = RMSNorm(features, eps)
    head = Dense(features, vocab_size, use_bias=False)

    def head_init(key, shape):
        params = {"norm": rms.init(None, shape)[0]} if norm else {}
        if not tied:
            params["head"] = head.init(key, shape)[0]
        return params, (*shape[:-1], vocab_size)

    def head_apply(p, x, ctx):
        if norm:
            x = rms.apply(p["norm"], x, ctx)
        if tied:
            recorder().note_site("tied_head", rms, "table_transposed")
            logits = lax.dot_general(
                x, p["table"].astype(x.dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            logits = jnp.dot(x, p["head"]["kernel"].astype(x.dtype),
                             preferred_element_type=jnp.float32)
        return logits if logits_scaling == 1 else logits / logits_scaling

    return FnCell(head_init, head_apply, "norm_head" if norm else "head")


def routed_step_metrics(routed: Sequence[int], top_k: int):
    """``CellModel.step_metrics`` of a token model whose cells ``routed`` keep
    a routed layer's ``load`` statistic under ``ffn`` (what
    ``ops/moe.RoutedExperts`` leaves there): the rows computed here (over all
    expert layers), the assignments made (tokens x experts a token x expert
    layers), and the largest held expert's load over the mean of its layer,
    the worst layer's."""

    def step_metrics(params, tokens: int) -> Dict[str, jax.Array]:
        if not routed:
            return {}
        load = jnp.stack([params[i]["ffn"]["load"] for i in routed])
        per_layer = tokens * top_k
        return {
            "expert_rows": jnp.round(jnp.sum(load) * per_layer),
            "expert_assignments": jnp.float32(per_layer * len(routed)),
            "expert_load_max_over_mean": jnp.max(
                jnp.max(load, axis=1) / jnp.maximum(jnp.mean(load, axis=1), 1e-30)),
        }

    return step_metrics


def lfm2_moe(in_shape: Tuple[int, int], *, num_layers: int, vocab_size: int,
             experts_held: int, expert_first: int = 0,
             compute_dtype=jnp.float32,
             config: Lfm2MoeConfig = PUBLISHED) -> CellModel:
    """The model on ``in_shape = (batch, seq_len)`` int32 ids below
    ``vocab_size``: embedding, ``num_layers`` layers, final norm and head;
    the logits are ``[batch, seq_len, vocab_size]`` in float32."""
    d = config.hidden_size
    if not 1 <= vocab_size <= config.vocab_size:
        raise ValueError(f"--vocab-size {vocab_size} of {config.vocab_size}")
    blocks = [_block(config, layer, experts_held, expert_first)
              for layer in layers_run(config, num_layers)]
    routed = [i + 1 for i, b in enumerate(blocks)
              if isinstance(b.ffn, RoutedExperts)]
    return CellModel(
        [embed_cell(vocab_size, d, compute_dtype), *blocks,
         head_cell(vocab_size, d, config.norm_eps)],
        tuple(in_shape), vocab_size, name="lfm2_moe",
        step_metrics=routed_step_metrics(routed, config.num_experts_per_tok))
