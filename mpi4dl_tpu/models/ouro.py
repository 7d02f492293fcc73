"""Ouro-2.6B (ByteDance's LoopLM, Hugging Face ``ouro``) as a token
``CellModel``.

Forty-eight layers that run FOUR times on the same weights
(``total_ut_steps``).  A layer has sandwich norms, four RMSNorms::

    h  = x + RMS_a2(attn(RMS_a1(x)))
    x' = h + RMS_m2(mlp(RMS_m1(h)))

``attn`` is causal multi-head attention (16 heads, 16 key-value heads of
128, the rotary embedding on q and k at the same positions in every pass, no
norm on q or k); ``mlp`` a SwiGLU of 5,632.  Every projection is without
bias.  The loop: ``x^0 = E[ids]``; for ``t = 1..4``, ``x^t = RMS_f(layer_47
o ... o layer_0(x^{t-1}))``, the one final norm after EVERY pass, its output
the next pass's input; ``logits = x^4 W_head``, no further norm, the head not
tied to the embedding.  The exit gate (``Linear(hidden, 1)`` on each pass's
``x^t``) feeds no logit and no loss of the language-model objective and
serves early exit at inference: it is not here.

:data:`PUBLISHED` is the model's ``config.json``, key for key.  The flags
state only the cut and the job, as for the other token models
(models/lfm2.py, whose ``Attention``, ``SwiGLU``, ``BlockCell``,
``embed_cell`` and ``head_cell`` this model shares): ``--num-layers`` layers
from layer 0, ``--vocab-size`` rows.  The number of passes is the published
``total_ut_steps``, not a flag.

One cell an application: the embedding; pass 0's layers and ``RMS_f``, which
hold the weights; passes 1 to ``total_ut_steps - 1`` as the same cells again,
each applying pass 0's parameters through ``CellModel.tied`` and holding none
of its own; the head.  So per-cell remat saves one boundary an application,
the state holds each layer once, and the gradient of a weight is the sum over
its applications.  Every application runs inside the scopes ``ut_loop`` and
``ut_step{t}``.

What of this family the model does not do, each an error at build time and
not a silent default: a sliding window, biases on the projections, a per-head
q/k norm, tied embeddings, rope scaling.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from mpi4dl_tpu.cells import Cell, CellModel, FnCell
from mpi4dl_tpu.layers import RMSNorm
from mpi4dl_tpu.models.lfm2 import (
    Attention, BlockCell, SwiGLU, embed_cell, head_cell, layers_run)
from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.obs.spans import recorder


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """``https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json``,
    the keys that say something about the model's shape, under their names.
    ``attention_bias`` and ``qk_norm`` are not keys of it: the model has
    neither (the configuration's ``assumed``), and a model that had one is
    refused."""

    early_exit_threshold: float = 1
    head_dim: int = 128
    hidden_act: str = "silu"
    hidden_size: int = 2048
    intermediate_size: int = 5632
    layer_types: Tuple[str, ...] = ("full_attention",) * 48
    max_position_embeddings: int = 65536
    max_window_layers: int = 48
    model_type: str = "ouro"
    num_attention_heads: int = 16
    num_hidden_layers: int = 48
    num_key_value_heads: int = 16
    rms_norm_eps: float = 1e-6
    rope_scaling: Optional[dict] = None
    rope_theta: float = 1000000
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = False
    total_ut_steps: int = 4
    use_sliding_window: bool = False
    vocab_size: int = 49152
    attention_bias: bool = False
    qk_norm: bool = False

    # what lfm2.layers_run reads: no leading dense layers, a cut starts at 0
    num_dense_layers = 0


PUBLISHED = OuroConfig()
EMBED_STD = 0.02  # the family's initializer_range; kernels: U(+-1/sqrt(fan_in))


@dataclasses.dataclass
class LoopCell(Cell):
    """One application of ``cell`` in pass ``step`` of the loop, inside the
    scopes ``ut_loop`` and ``ut_step{step}``.  Pass 0's holds the weights;
    a later pass's (``holds`` false) initialises none and is applied to the
    holder's, which ``CellModel.tied`` hands it.  ``applications``: how many
    cells of the model apply the layer ``cell``, the recorder's trace-time
    count ``ut_loop`` (None for ``RMS_f``, which is no layer)."""

    cell: Cell
    step: int
    applications: Optional[int]
    holds: bool
    name: str

    def init(self, key, in_shape):
        params, out_shape = self.cell.init(key, in_shape)
        return (params if self.holds else {}), out_shape

    def apply(self, params, x, ctx):
        if self.applications is not None:
            recorder().note_site("ut_loop", self.cell, str(self.applications))
        with scope("ut_loop"), scope(f"ut_step{self.step}"):
            return self.cell.apply(params, x, ctx)


def _check(config: OuroConfig) -> None:
    """What this model computes of the family; anything else is refused, by
    its key's name."""
    unsupported = {
        "use_sliding_window": config.use_sliding_window
        or config.sliding_window is not None,
        "attention_bias": config.attention_bias,
        "qk_norm": config.qk_norm,
        "tie_word_embeddings": config.tie_word_embeddings,
        "rope_scaling": config.rope_scaling is not None,
        "hidden_act": config.hidden_act != "silu",
        "layer_types other than full_attention": any(
            k != "full_attention" for k in config.layer_types),
        "total_ut_steps < 1": config.total_ut_steps < 1,
    }
    bad = [name for name, is_bad in unsupported.items() if is_bad]
    if bad:
        raise ValueError(f"ouro: not computed here: {', '.join(bad)}")


def _block(config: OuroConfig, layer: int) -> BlockCell:
    d = config.hidden_size
    return BlockCell(
        Attention(d, config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim, float(config.rope_theta), config.rms_norm_eps,
                  qk_norm=False),
        SwiGLU(d, config.intermediate_size), RMSNorm(d, config.rms_norm_eps),
        name=f"layer{layer:02d}", post_norms=True)


def _final_norm(features: int, eps: float) -> FnCell:
    """``RMS_f``, the norm after every pass: ``{"norm": {"scale"}}``."""
    rms = RMSNorm(features, eps)
    return FnCell(lambda key, shape: ({"norm": rms.init(None, shape)[0]}, shape),
                  lambda p, x, ctx: rms.apply(p["norm"], x, ctx), "norm")


def ouro(in_shape: Tuple[int, int], *, num_layers: int, vocab_size: int,
         compute_dtype=jnp.float32, config: Optional[OuroConfig] = None
         ) -> CellModel:
    """The model on ``in_shape = (batch, seq_len)`` int32 ids below
    ``vocab_size``: embedding, ``num_layers`` layers and ``RMS_f`` applied
    ``total_ut_steps`` times, the head; the logits are ``[batch, seq_len,
    vocab_size]`` in float32.  ``config``: :data:`PUBLISHED` unless a test
    hands in toy widths or another number of passes."""
    config = config or PUBLISHED
    _check(config)
    steps = config.total_ut_steps
    d = config.hidden_size
    if not 1 <= vocab_size <= config.vocab_size:
        raise ValueError(f"--vocab-size {vocab_size} of {config.vocab_size}")
    held = [*(_block(config, layer) for layer in layers_run(config, num_layers)),
            _final_norm(d, config.rms_norm_eps)]
    # the top-level names of each held cell's parameters, which a later
    # pass's application reads from it
    shape = (*in_shape, d)
    names = [tuple(jax.eval_shape(lambda c=c: c.init(jax.random.key(0), shape)[0]))
             for c in held]
    cells: list = [embed_cell(vocab_size, d, compute_dtype, EMBED_STD)]
    tied: list = []
    for t in range(steps):
        for j, cell in enumerate(held):
            owner = 1 + j
            if t:
                tied += [(owner, len(cells), name) for name in names[j]]
            layer = isinstance(cell, BlockCell)
            cells.append(LoopCell(cell, t, steps if layer else None,
                                  holds=t == 0, name=f"ut{t}_{cell.name}"))
    cells.append(head_cell(vocab_size, d, config.rms_norm_eps, norm=False))
    return CellModel(cells, tuple(in_shape), vocab_size, name="ouro",
                     tied=tuple(tied))
