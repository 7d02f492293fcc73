"""Kanana-2-30B-A3B (kakaocorp, Hugging Face ``deepseek_v3``) as a token
``CellModel``.

Forty-eight layers, each ``h += MLA(RMSNorm(h))`` then ``h += ffn(RMSNorm(h))``.
``MLA`` is multi-head latent attention without query compression
(``q_lora_rank`` null): keys and values come from one compressed row of
``kv_lora_rank`` values with a norm of its own, and a rotary key of
``qk_rope_head_dim`` values that all heads share rides beside each head's own
``qk_nope_head_dim``; a head's keys are ``qk_head_dim`` wide and its values
``v_head_dim``.  ``ffn`` is a SwiGLU in layer 0 (``first_k_dense_replace``)
and, in the other 47, the sum of a routed expert layer (128 experts, six a
token, sigmoid scores, ``routed_scaling_factor``) and a shared SwiGLU of
``n_shared_experts`` x ``moe_intermediate_size`` that every token takes.
Every projection is without bias.  After the last layer one more RMSNorm,
then the head (not tied to the embedding).

:data:`PUBLISHED` is the model's ``config.json``, key for key.  The flags
state only the cut and the job, as for ``lfm2_moe`` (models/lfm2.py, whose
cells, SwiGLU and depth rule this model shares): a cut in depth starts at
layer 0, so the leading dense layer is there once.

What of this family the model does not do, each an error at build time and
not a silent default: a compressed query (``q_lora_rank``), group-limited
routing (``n_group`` > 1), scaled rotary embeddings (``rope_scaling``),
attention biases.  The absorbed (latent-space) form of the attention and a
latent cache belong to a serving path, which this trainer has none of.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.cells import CellModel
from mpi4dl_tpu.layers import Dense, Layer, RMSNorm
from mpi4dl_tpu.models.lfm2 import (
    BlockCell, SwiGLU, embed_cell, head_cell, layers_run, rotary,
    routed_step_metrics)
from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.obs.spans import recorder
from mpi4dl_tpu.ops.moe import RoutedExperts


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """``https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/
    main/config.json``, the keys that say something about the model's shape,
    under their names."""

    attention_bias: bool = False
    first_k_dense_replace: int = 1
    head_dim: int = 64
    hidden_act: str = "silu"
    hidden_size: int = 2048
    intermediate_size: int = 6144
    kv_lora_rank: int = 512
    max_position_embeddings: int = 32768
    model_type: str = "deepseek_v3"
    moe_intermediate_size: int = 768
    moe_layer_freq: int = 1
    n_group: int = 1
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_experts_per_tok: int = 6
    num_hidden_layers: int = 48
    num_key_value_heads: int = 32
    q_lora_rank: Optional[int] = None
    qk_head_dim: int = 192
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    rms_norm_eps: float = 1e-06
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    rope_theta: float = 1000000
    routed_scaling_factor: float = 2.448
    scoring_func: str = "sigmoid"
    tie_word_embeddings: bool = False
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    v_head_dim: int = 128
    vocab_size: int = 128256

    @property
    def num_dense_layers(self) -> int:
        """The leading layers whose ``ffn`` is dense (``lfm2.layers_run``'s
        name for ``first_k_dense_replace``)."""
        return self.first_k_dense_replace


PUBLISHED = DeepseekV3Config()
# The modelling code's constant beside the chosen scores' sum (not a key of
# the config).
ROUTE_SUM_EPS = 1e-20
# The embedding's standard deviation at initialisation; every kernel is
# U(+-1/sqrt(fan_in)) as elsewhere.  Not the family's initializer_range of
# 0.02: attention is this model's first operation, and under random weights
# its softmax is near uniform, so its output is a running mean that all later
# tokens share.  Beside a 0.02 embedding that shared vector is as large as the
# token's own, every FFN amplifies it, and by the third layer 77 % of the
# normed stream is one direction: the router then picks the same experts for
# all tokens (the busiest of 128 experts gets 15 times the mean at 0.02 and 3
# at 0.3, 1.7 at 0.5, 1.3 at 1.0: PERF.md, PR 33), where a trained model's
# router is balanced.  At 0.5 the token's own vector stays 92-97 % of the
# stream through five layers, and the layers' outputs are still a large enough
# part of it for a wrong layer to show in a comparison of cell outputs.
EMBED_STD = 0.5


def rotary_interleaved(x, theta: float):
    """The rotary embedding of ``rope_interleave`` on ``[B, S, H, hd]``: the
    pairs are the columns ``(2i, 2i+1)``, turned by ``pos / theta^(2i/hd)``.
    As the modelling code does it: the even columns are gathered before the
    odd ones and the two halves rotated, and the result stays in that order.
    Queries and keys are permuted alike, so their products are those of the
    pairs turned in place."""
    evens_then_odds = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return rotary(evens_then_odds, theta)


def _rope_columns_apart(kernel, heads: int, nope: int, rope: int):
    """A projection's kernel ``[d, heads·(nope + rope)]`` as ``([d,
    heads·nope], [d, heads·rope])``: every head's ``rope`` columns apart from
    its others, their evens before their odds, which is the order
    :func:`rotary_interleaved` gathers an activation into."""
    w = kernel.reshape(kernel.shape[0], heads, nope + rope)
    pe = w[..., nope:]
    pe = jnp.concatenate([pe[..., 0::2], pe[..., 1::2]], axis=-1)
    return (w[..., :nope].reshape(kernel.shape[0], heads * nope),
            pe.reshape(kernel.shape[0], heads * rope))


@dataclasses.dataclass(frozen=True)
class LatentAttention(Layer):
    """Causal multi-head latent attention, the parts under the config's names:
    ``q = q_proj(x)`` split a head into ``nope`` and ``rope`` columns;
    ``c, k_pe = split(kv_a_proj_with_mqa(x))``; ``k_nope, v =
    split(kv_b_proj(kv_a_layernorm(c)))`` a head; the rotary embedding on the
    ``rope`` columns of q and on ``k_pe``, which is ONE key for all heads;
    ``k = [k_nope, k_pe]``; scale ``(nope + rope) ** -0.5``; the output
    ``v_head`` wide a head, then ``o_proj``.

    On a TPU backend the attention itself is
    ``ops.pallas_latent_attention.latent_flash`` on what the projections
    wrote (:meth:`_attend_flash`): a head's columns are picked by the kernel's
    index maps and the rotary key is read once for all heads; nothing is
    concatenated, broadcast, transposed to heads-first or padded to the lanes
    in HBM.  Elsewhere it is the einsum form of ``ops.ring.ring_attention`` on
    one shard, one sequence at a time, on q and k put together a head."""

    features: int
    heads: int
    nope: int
    rope: int
    v_head: int
    kv_rank: int
    rope_theta: float
    eps: float

    def _parts(self) -> Dict[str, Layer]:
        d, h = self.features, self.heads
        return {
            "q_proj": Dense(d, h * (self.nope + self.rope), use_bias=False),
            "kv_a_proj_with_mqa": Dense(d, self.kv_rank + self.rope,
                                        use_bias=False),
            "kv_a_layernorm": RMSNorm(self.kv_rank, self.eps),
            "kv_b_proj": Dense(self.kv_rank, h * (self.nope + self.v_head),
                               use_bias=False),
            "o_proj": Dense(h * self.v_head, d, use_bias=False),
        }

    def init(self, key, in_shape):
        parts = self._parts()
        keys = jax.random.split(key, len(parts))
        lead = in_shape[:-1]
        shapes = {"kv_a_layernorm": (*lead, self.kv_rank),
                  "kv_b_proj": (*lead, self.kv_rank),
                  "o_proj": (*lead, self.heads * self.v_head)}
        return {n: layer.init(k, shapes.get(n, in_shape))[0]
                for k, (n, layer) in zip(keys, parts.items())}, in_shape

    def apply(self, params, x, ctx):
        from mpi4dl_tpu.ops.ring import _resolve_flash

        flash = _resolve_flash(None)
        recorder().note_site(
            "attention", self, "latent_block_flash" if flash else "latent_einsum")
        attend = self._attend_flash if flash else self._attend_einsum
        return self._parts()["o_proj"].apply(
            params["o_proj"], attend(params, x, ctx), ctx)

    def _attend_flash(self, params, x, ctx):
        """``[B, S, H·v_head]`` by the Pallas kernel, from projections it can
        read in place.  ``q_proj`` and ``kv_a_proj_with_mqa`` run as two
        products each, the ``rope`` columns of their kernels apart from the
        others and evens before odds (:func:`_rope_columns_apart`: the same
        columns, so the same sums): the kernel then finds a head's ``nope``
        columns on whole lane tiles, and the rotary embedding turns
        contiguous halves where ``rotary_interleaved`` gathers every second
        column of an activation (for which XLA:TPU lays the whole projection
        out sequence-minor and copies it back)."""
        from mpi4dl_tpu.ops import pallas_latent_attention

        parts = self._parts()
        b, s, _ = x.shape
        h, nope, rope = self.heads, self.nope, self.rope

        def product(kernel, y):
            return Dense(*kernel.shape, use_bias=False).apply(
                {"kernel": kernel}, y, ctx)

        w_q, w_q_pe = _rope_columns_apart(params["q_proj"]["kernel"], h, nope, rope)
        w_c, w_k_pe = _rope_columns_apart(
            params["kv_a_proj_with_mqa"]["kernel"], 1, self.kv_rank, rope)
        c = parts["kv_a_layernorm"].apply(
            params["kv_a_layernorm"], product(w_c, x), ctx)
        kv = parts["kv_b_proj"].apply(params["kv_b_proj"], c, ctx)
        q_pe = rotary(product(w_q_pe, x).reshape(b, s, h, rope), self.rope_theta)
        k_pe = rotary(product(w_k_pe, x)[:, :, None, :], self.rope_theta)
        q, q_pe, k_pe = product(w_q, x), q_pe.reshape(b, s, h * rope), k_pe[:, :, 0]
        # the kernel alone: the scope by which a device trace finds attention
        # itself, forward and recomputed (the backward rule opens its own)
        with scope("attention_core"):
            return pallas_latent_attention.latent_flash(
                q, q_pe, kv, k_pe, h, (nope + rope) ** -0.5)

    def _attend_einsum(self, params, x, ctx):
        """``[B, S, H·v_head]`` by ``ring_attention``'s einsum form, one
        sequence at a time, on q and k put together a head."""
        from mpi4dl_tpu.ops.ring import ring_attention

        parts = self._parts()
        b, s, _ = x.shape
        h, nope, rope = self.heads, self.nope, self.rope

        def part(name, y):
            return parts[name].apply(params[name], y, ctx)

        q = part("q_proj", x).reshape(b, s, h, nope + rope)
        c, k_pe = jnp.split(part("kv_a_proj_with_mqa", x), [self.kv_rank], axis=-1)
        kv = part("kv_b_proj", part("kv_a_layernorm", c)).reshape(
            b, s, h, nope + self.v_head)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_pe = rotary_interleaved(q[..., nope:], self.rope_theta)
        k_pe = rotary_interleaved(k_pe[:, :, None, :], self.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (b, s, h, rope))], axis=-1)

        def attend(qkv):
            return ring_attention(*(t[None] for t in qkv), None, 1, causal=True,
                                  scale=(nope + rope) ** -0.5,
                                  use_flash=False)[0]

        return lax.map(attend, (q, k, v)).reshape(b, s, h * self.v_head)


@dataclasses.dataclass(frozen=True)
class SharedAndRoutedExperts(Layer):
    """``routed(x) + shared(x)``: the held experts' part of the routed sum
    (``ops/moe.RoutedExperts``) and the shared SwiGLU, which every chip of an
    expert-parallel layer computes alike for its own tokens.  The routed
    layer's parameters (``router``, ``experts``, ``load``) lie beside
    ``shared_experts``."""

    routed: RoutedExperts
    shared: SwiGLU

    def init(self, key, in_shape):
        k_routed, k_shared = jax.random.split(key)
        return {**self.routed.init(k_routed, in_shape)[0],
                "shared_experts": self.shared.init(k_shared, in_shape)[0],
                }, in_shape

    def apply(self, params, x, ctx):
        recorder().note_site("shared_expert", self, "swiglu")
        routed = self.routed.apply(params, x, ctx)
        with scope("shared_expert"):
            shared = self.shared.apply(params["shared_experts"], x, ctx)
        return routed + shared


def _check(config: DeepseekV3Config) -> None:
    """What this model computes of the family; anything else is refused."""
    unsupported = {
        "q_lora_rank": config.q_lora_rank is not None,
        "n_group > 1": config.n_group != 1 or config.topk_group != 1,
        "rope_scaling": config.rope_scaling is not None,
        "attention_bias": config.attention_bias,
        "rope_interleave false": not config.rope_interleave,
        "scoring_func": config.scoring_func != "sigmoid",
        "norm_topk_prob false": not config.norm_topk_prob,
        "moe_layer_freq": config.moe_layer_freq != 1,
        "tie_word_embeddings": config.tie_word_embeddings,
        "hidden_act": config.hidden_act != "silu",
    }
    bad = [name for name, is_bad in unsupported.items() if is_bad]
    if bad:
        raise ValueError(f"deepseek_v3: not computed here: {', '.join(bad)}")
    assert config.qk_head_dim == config.qk_nope_head_dim + config.qk_rope_head_dim


def _block(config: DeepseekV3Config, layer: int, experts_held: int,
           expert_first: int) -> BlockCell:
    d = config.hidden_size
    op = LatentAttention(
        d, config.num_attention_heads, config.qk_nope_head_dim,
        config.qk_rope_head_dim, config.v_head_dim, config.kv_lora_rank,
        float(config.rope_theta), config.rms_norm_eps)
    if layer < config.first_k_dense_replace:
        ffn: Layer = SwiGLU(d, config.intermediate_size)
    else:
        ffn = SharedAndRoutedExperts(
            RoutedExperts(
                d, config.moe_intermediate_size, config.n_routed_experts,
                config.num_experts_per_tok, experts_held, expert_first,
                float(config.routed_scaling_factor), ROUTE_SUM_EPS),
            SwiGLU(d, config.n_shared_experts * config.moe_intermediate_size))
    return BlockCell(op, ffn, RMSNorm(d, config.rms_norm_eps),
                     name=f"layer{layer:02d}_mla")


def deepseek_v3(in_shape: Tuple[int, int], *, num_layers: int, vocab_size: int,
                experts_held: int, expert_first: int = 0,
                compute_dtype=jnp.float32,
                config: Optional[DeepseekV3Config] = None) -> CellModel:
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
    routed = [i + 1 for i, b in enumerate(blocks)
              if isinstance(b.ffn, SharedAndRoutedExperts)]
    return CellModel(
        [embed_cell(vocab_size, d, compute_dtype, EMBED_STD), *blocks,
         head_cell(vocab_size, d, config.rms_norm_eps)],
        tuple(in_shape), vocab_size, name="deepseek_v3",
        step_metrics=routed_step_metrics(routed, config.num_experts_per_tok))
