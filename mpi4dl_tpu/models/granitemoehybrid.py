"""granite-4.0-h-micro (ibm-granite, Hugging Face ``granitemoehybrid``) as a
token ``CellModel``.

Forty layers, each ``h += m mixer(RMSNorm(h))`` then ``h += m mlp(RMSNorm(h))``
with ``m`` the ``residual_multiplier``.  The mixer is a Mamba-2 state-space
layer (36 layers) or grouped-query attention WITHOUT any position signal
(``position_embedding_type`` ``nope``; layers 5, 15, 25, 35: a period is ten
layers), scores times ``attention_multiplier``.  The model is dense
(``num_local_experts`` 0): a layer's feed-forward is the always-on
``shared_mlp`` alone, a SwiGLU of ``shared_intermediate_size``.  The
embedding's rows are taken times ``embedding_multiplier``; after the last
layer one more RMSNorm, then the head, which IS the embedding's table
(``tie_word_embeddings``), and the logits over ``logits_scaling``.  Every
projection is without bias; the depthwise convolution has one.

:data:`PUBLISHED` is the model's ``config.json``, key for key.  The flags
state only the cut and the job, as for ``lfm2_moe`` (models/lfm2.py, whose
cells, attention, SwiGLU and depth rule this model shares): ``--num-layers``
layers from layer 0 with ``layer_types`` in order, ``--vocab-size`` rows of
the one table.

What of this family the model does not do, each an error at build time and
not a silent default: routed experts beside the always-on MLP
(``num_local_experts`` > 0), several groups of B and C (``mamba_n_groups`` >
1), biases on the projections, rotary positions, a sequence that the chunk
does not divide.  The single-step recurrent form and its cache belong to a
serving path, which this trainer has none of.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from mpi4dl_tpu.cells import CellModel
from mpi4dl_tpu.layers import CausalConv1d, Dense, Layer, RMSNorm
from mpi4dl_tpu.models.lfm2 import (
    Attention, BlockCell, SwiGLU, embed_cell, head_cell, layers_run)
from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.obs.spans import recorder
from mpi4dl_tpu.ops.ssd import ssd_chunked

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class GraniteMoeHybridConfig:
    """``https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/
    config.json``, the keys that say something about the model's shape, under
    their names."""

    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12
    hidden_act: str = "silu"
    hidden_size: int = 2048
    intermediate_size: int = 8192
    layer_types: Tuple[str, ...] = _PERIOD * 4
    logits_scaling: float = 8
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_n_heads: int = 64
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 131072
    model_type: str = "granitemoehybrid"
    normalization_function: str = "rmsnorm"
    num_attention_heads: int = 32
    num_experts_per_tok: int = 0
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    num_local_experts: int = 0
    position_embedding_type: str = "nope"
    residual_multiplier: float = 0.22
    rms_norm_eps: float = 1e-05
    rope_scaling: Optional[dict] = None
    rope_theta: float = 10000
    shared_intermediate_size: int = 8192
    tie_word_embeddings: bool = True
    vocab_size: int = 100352

    # what lfm2.layers_run reads: no leading dense layers, a cut starts at 0
    num_dense_layers = 0

    @property
    def head_dim(self) -> int:
        # not a key of the config: hidden_size / num_attention_heads
        return self.hidden_size // self.num_attention_heads


PUBLISHED = GraniteMoeHybridConfig()
EMBED_STD = 0.02  # the family's initializer_range; kernels: U(+-1/sqrt(fan_in))
# The scan's own parameters at initialisation, which the config does not
# give: Mamba-2's published ones (arXiv:2405.21060; the ``Mamba2`` defaults of
# state-spaces/mamba).  A head's step ``dt`` is drawn log-uniformly from
# DT_RANGE and ``dt_bias`` is its inverse softplus; ``A = -U(A_RANGE)``;
# ``D = 1``.  Not the transformers module's placeholders (``dt_bias`` 1,
# ``A = -(1..H)``), under which every head forgets within a few positions and
# the state that goes from chunk to chunk carries nothing.
DT_RANGE = (0.001, 0.1)
DT_FLOOR = 1e-4
A_RANGE = (1.0, 16.0)


@dataclasses.dataclass(frozen=True)
class Mamba2Mixer(Layer):
    """The Mamba-2 mixer on ``[B, S, features]``, the parts under the
    modelling code's names: ``z, xBC, dt = split(in_proj(u))``; ``x, B, C =
    split(silu(conv1d(xBC)))``, the convolution depthwise, causal and with a
    bias; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the
    recurrence (``ops/ssd.ssd_chunked``) with ``D`` its skip;
    ``out_proj(norm(y * silu(z)))``: the gate BEFORE the norm, which is over
    all ``heads x head_dim`` channels as one group.

    ``carried`` (where ``count_carried``: the model's first state-space
    layer) is no weight: the scan's ``[|Y_off|^2, |Y_diag + Y_off|^2]`` of the
    last step, a running statistic written through ``ctx.bn_sink`` as an
    expert layer's ``load`` is (what ``ssm_carried_share`` is made from)."""

    features: int
    heads: int
    head_dim: int
    state: int
    conv_kernel: int
    chunk: int
    eps: float
    conv_bias: bool = True
    count_carried: bool = False

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    def _parts(self) -> Dict[str, Layer]:
        conv_dim = self.inner + 2 * self.state
        return {
            "in_proj": Dense(self.features, self.inner + conv_dim + self.heads,
                             use_bias=False),
            "conv1d": CausalConv1d(conv_dim, self.conv_kernel, self.conv_bias),
            "norm": RMSNorm(self.inner, self.eps),
            "out_proj": Dense(self.inner, self.features, use_bias=False),
        }

    def init(self, key, in_shape):
        parts = self._parts()
        k_parts = jax.random.split(key, len(parts) + 2)
        lead = in_shape[:-1]
        shapes = {"conv1d": (*lead, self.inner + 2 * self.state),
                  "norm": (*lead, self.inner), "out_proj": (*lead, self.inner)}
        params = {n: layer.init(k, shapes.get(n, in_shape))[0]
                  for k, (n, layer) in zip(k_parts, parts.items())}
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k_parts[-2], (self.heads,), jnp.float32, lo, hi)), DT_FLOOR)
        params["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        params["A_log"] = jnp.log(jax.random.uniform(
            k_parts[-1], (self.heads,), jnp.float32, *A_RANGE))
        params["D"] = jnp.ones((self.heads,), jnp.float32)
        if self.count_carried:
            params["carried"] = jnp.zeros((2,), jnp.float32)
        return params, in_shape

    def apply(self, params, u, ctx):
        # the whole mixer under ssm_mixer, the scan alone under ssm_scan
        with scope("ssm_mixer"):
            return self._mix(params, u, ctx)

    def _mix(self, params, u, ctx):
        parts = self._parts()
        recorder().note_site("ssm_scan", self, "chunked")
        b, s, _ = u.shape
        f32 = jnp.float32
        z, xbc, dt = jnp.split(
            parts["in_proj"].apply(params["in_proj"], u, ctx),
            [self.inner, 2 * self.inner + 2 * self.state], axis=-1)
        xbc = jax.nn.silu(parts["conv1d"].apply(params["conv1d"], xbc, ctx))
        x, b_t, c_t = jnp.split(xbc, [self.inner, self.inner + self.state], axis=-1)
        with scope("ssm_scan"):
            dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))
            y, carried = ssd_chunked(
                x.reshape(b, s, self.heads, self.head_dim), dt,
                -jnp.exp(params["A_log"].astype(f32)), b_t, c_t, params["D"],
                chunk=self.chunk, count_carried=self.count_carried)
        if carried is not None and ctx.bn_sink is not None:
            ctx.bn_sink[id(params["carried"])] = carried
        y = y.reshape(b, s, self.inner)
        gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(y.dtype)
        return parts["out_proj"].apply(
            params["out_proj"], parts["norm"].apply(params["norm"], gated, ctx),
            ctx)


def _check(config: GraniteMoeHybridConfig, seq_len: int) -> None:
    """What this model computes of the family; anything else is refused, by
    its key's name."""
    unsupported = {
        "num_local_experts > 0": config.num_local_experts != 0
        or config.num_experts_per_tok != 0,
        "mamba_n_groups > 1": config.mamba_n_groups != 1,
        "mamba_proj_bias": config.mamba_proj_bias,
        "attention_bias": config.attention_bias,
        f"position_embedding_type {config.position_embedding_type!r}":
            config.position_embedding_type != "nope",
        "normalization_function": config.normalization_function != "rmsnorm",
        "hidden_act": config.hidden_act != "silu",
        "tie_word_embeddings false": not config.tie_word_embeddings,
        f"a sequence of {seq_len} that mamba_chunk_size "
        f"{config.mamba_chunk_size} does not divide":
            seq_len % config.mamba_chunk_size != 0,
    }
    bad = [name for name, is_bad in unsupported.items() if is_bad]
    if bad:
        raise ValueError(f"granitemoehybrid: not computed here: {', '.join(bad)}")
    assert (config.mamba_n_heads * config.mamba_d_head
            == config.mamba_expand * config.hidden_size)


def _block(config: GraniteMoeHybridConfig, layer: int,
           count_carried: bool = False) -> BlockCell:
    d = config.hidden_size
    kind = config.layer_types[layer]
    if kind == "mamba":
        op: Layer = Mamba2Mixer(
            d, config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state,
            config.mamba_d_conv, config.mamba_chunk_size, config.rms_norm_eps,
            config.mamba_conv_bias, count_carried)
    elif kind == "attention":
        op = Attention(d, config.num_attention_heads, config.num_key_value_heads,
                       config.head_dim, None, config.rms_norm_eps,
                       qk_norm=False, scale=float(config.attention_multiplier))
    else:
        raise ValueError(f"layer {layer}: unknown layer_type {kind!r}")
    return BlockCell(op, SwiGLU(d, config.shared_intermediate_size),
                     RMSNorm(d, config.rms_norm_eps),
                     name=f"layer{layer:02d}_{kind}",
                     residual_multiplier=float(config.residual_multiplier))


def carried_step_metrics(counting: Optional[int]):
    """``CellModel.step_metrics`` of a model whose cell ``counting`` keeps a
    Mamba-2 mixer's ``carried`` statistic under ``op``:
    ``ssm_carried_share``, the squared norm of what the scan's output took
    from the state carried into a chunk over that of the whole (without the
    ``D x`` skip), in the model's first state-space layer (every such layer
    draws its decays alike; the two reductions cost 1.8 ms a layer and step
    on the chip, 1.6 % of the step over nine: PERF.md, PR 35)."""

    def step_metrics(params, tokens: int) -> Dict[str, jax.Array]:
        if counting is None:
            return {}
        carried = params[counting]["op"]["carried"]
        return {"ssm_carried_share": carried[0] / jnp.maximum(carried[1], 1e-30)}

    return step_metrics


def granitemoehybrid(in_shape: Tuple[int, int], *, num_layers: int,
                     vocab_size: int, compute_dtype=jnp.float32,
                     config: Optional[GraniteMoeHybridConfig] = None
                     ) -> CellModel:
    """The model on ``in_shape = (batch, seq_len)`` int32 ids below
    ``vocab_size``: embedding, ``num_layers`` layers, final norm and the tied
    head; the logits are ``[batch, seq_len, vocab_size]`` in float32.
    ``config``: :data:`PUBLISHED` unless a test hands in toy widths."""
    config = config or PUBLISHED
    _check(config, in_shape[1])
    d = config.hidden_size
    if not 1 <= vocab_size <= config.vocab_size:
        raise ValueError(f"--vocab-size {vocab_size} of {config.vocab_size}")
    run = layers_run(config, num_layers)
    kinds = [config.layer_types[layer] for layer in run]
    # the first state-space layer counts the carried share; cell 0 embeds
    counting = kinds.index("mamba") + 1 if "mamba" in kinds else None
    blocks = [_block(config, layer, i + 1 == counting)
              for i, layer in enumerate(run)]
    return CellModel(
        [embed_cell(vocab_size, d, compute_dtype, EMBED_STD,
                    float(config.embedding_multiplier)),
         *blocks,
         head_cell(vocab_size, d, config.rms_norm_eps,
                   logits_scaling=float(config.logits_scaling), tied=True)],
        tuple(in_shape), vocab_size, name="granitemoehybrid",
        step_metrics=carried_step_metrics(counting),
        tied=((0, len(blocks) + 1, "table"),))
