"""Plain float32 building blocks of the reference forwards.

Straightforward ``jax.numpy``: no kernels, no remat, no layout tricks, no
code of the program under test.  Every convolution and dense layer also
records its multiply-accumulates in a :class:`Tally`, so the walk that
computes the reference is the walk that counts the model's FLOPs: one
description of the architecture serves both.  A reference with other kinds
of product (attention scores, experts) counts them under names of its own:
``tally.add("attn_scores", macs)``.

The caller runs these under ``jax.default_matmul_precision("highest")``: on
a TPU a float32 matmul otherwise runs in bf16 passes.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5  # torch.nn.BatchNorm2d's default, which the reference models use


class Tally:
    """Multiply-accumulates of the forward pass, counted from shapes, by the
    kind of product: ``conv`` and ``dense`` here, any other name a reference
    gives.  ``macs`` is the sum over all kinds."""

    def __init__(self) -> None:
        self.by_kind: Dict[str, int] = {}

    def add(self, kind: str, macs: int) -> None:
        self.by_kind[kind] = self.by_kind.get(kind, 0) + int(macs)

    @property
    def macs(self) -> int:
        return sum(self.by_kind.values())

    @property
    def conv_macs(self) -> int:
        return self.by_kind.get("conv", 0)

    @property
    def dense_macs(self) -> int:
        return self.by_kind.get("dense", 0)


def model_flops(forward_macs: int) -> int:
    """FLOPs a training step needs for ``forward_macs`` forward MACs: two
    FLOPs a MAC, and the backward pass costs twice the forward (gradients
    with respect to the input and to the weights).  Recomputation under
    remat is not counted: it is work the memory budget forces, not work the
    model needs."""
    return 3 * 2 * int(forward_macs)


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def conv(x, p, stride=1, padding=0, tally: Tally | None = None):
    """NHWC x HWIO convolution with symmetric zero padding; ``p`` holds
    ``kernel`` and optionally ``bias``."""
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    k = p["kernel"].astype(jnp.float32)
    y = lax.conv_general_dilated(
        x, k, window_strides=(sh, sw), padding=((ph, ph), (pw, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )
    if tally is not None:
        n, oh, ow, oc = y.shape
        kh, kw, ic, _ = k.shape
        tally.add("conv", n * oh * ow * oc * kh * kw * ic)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y


def dense(x, p, tally: Tally | None = None):
    """``x @ kernel + bias`` over the last axis of ``x``."""
    k = p["kernel"].astype(jnp.float32)
    if tally is not None:
        tally.add("dense", math.prod(x.shape[:-1]) * k.shape[0] * k.shape[1])
    return jnp.dot(x, k, precision=lax.Precision.HIGHEST) + p["bias"].astype(
        jnp.float32)


def batchnorm_train(x, p):
    """Training-mode batch norm: statistics of this batch over N, H, W
    (biased variance), then scale and shift."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def relu(x):
    return jnp.maximum(x, 0.0)


def _windows(x, k, stride, padding, init, op):
    return lax.reduce_window(
        x, init, op, (1, k, k, 1), (1, stride, stride, 1),
        ((0, 0), (padding, padding), (padding, padding), (0, 0)),
    )


def max_pool(x, k, stride, padding=0):
    """Padding counts as minus infinity, never as zero."""
    return _windows(x, k, stride, padding, -jnp.inf, lax.max)


def avg_pool(x, k, stride, padding=0, count_include_pad=True):
    s = _windows(x, k, stride, padding, 0.0, lax.add)
    if count_include_pad or padding == 0:
        return s / float(k * k)
    ones = jnp.ones((1, x.shape[1], x.shape[2], 1), x.dtype)
    return s / _windows(ones, k, stride, padding, 0.0, lax.add)


def cast_floating(tree, dtype):
    """Every floating leaf (an image, an activation) in ``dtype``; any other
    (token ids) as it came: cast to bf16, ids are exact only below 256."""
    return jax.tree.map(
        lambda a: (a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
                   else a), tree)


def image_batch_spec(sizes, traffic):
    """``(x, y)`` of a batch where the reference states none of its own: one
    square float32 RGB image and one class a sample."""
    n, size = traffic["batch_size"], traffic["size"]
    return (jax.ShapeDtypeStruct((n, size, size, 3), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.int32))


def forward(cells, x):
    """The logits: every cell in turn, in float32."""
    act = cast_floating(x, jnp.float32)
    for cell in cells:
        act = cell(act)
    return act


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy with integer labels of the logits' leading
    shape: ``[B, V]`` with ``[B]``, or ``[B, S, V]`` with ``[B, S]`` and the
    mean over every position."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
