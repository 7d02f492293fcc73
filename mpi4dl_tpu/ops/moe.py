"""A routed expert layer that holds a share of the experts.

Under expert parallelism a chip holds ``held`` of a layer's ``total`` experts,
from ``first``.  It routes every token over ALL ``total`` experts (the router
keeps its published width and its experts a token), keeps the assignments
that fall on its own experts, gathers those rows by expert, runs the grouped
SwiGLU over them and scatters the weighted result back.  What the absent
experts would have added is left out: the partial sum goes on to the next
layer, and on one chip the layer runs without its exchange.  ``held == total``
is the uncut layer.  Nothing here stands in for the absent chips.

No token is dropped, whatever the routing.  The rows are worked through in
rounds of ``capacity`` rows (:data:`CAPACITY_FACTOR` times the balanced load,
so balanced routing needs one round); a round runs only if the routing
reached it (``lax.cond``), so the work follows the rows really routed here
and the buffers stay at the balanced size.  Rounds after the first are
checkpointed: they keep nothing for the backward pass but their inputs.

Gather and scatter are a pair of transposes (:func:`_dispatch`,
:func:`_combine`), each the other's backward pass, so neither direction
lowers to a scatter-add of rows.

Two scopes name the layer's work beside the grouped products, which carry
neither: ``expert_route`` (the router, its top-k and weights, the counting
sort and the ``load`` statistic) and ``expert_dispatch`` (a round's row
bookkeeping, the gathers and the weighted scatter back, forward and inside
both backward rules).

The grouped product is ``jax.lax.ragged_dot``, on every backend.  (PERF.md,
PR 29: megablox ``gmm`` read the same in the layer alone and 0.45 % more
sequences a second in the cell, which did not pay for a second path.)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.layers import Layer, _uniform
from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.obs.spans import recorder

CAPACITY_FACTOR = 1.25
ROW_TILE = 512  # a round's rows are a multiple of the grouped product's row tile


def round_capacity(assignments: int, held: int, total: int) -> int:
    """Rows a round works through: the balanced load of the held experts
    times :data:`CAPACITY_FACTOR`, in whole row tiles, at most all rows."""
    balanced = assignments * held / total
    rows = math.ceil(balanced * CAPACITY_FACTOR / ROW_TILE) * ROW_TILE
    return min(rows, math.ceil(assignments / ROW_TILE) * ROW_TILE)


def route(x, kernel, bias, top_k: int, scaling: float = 1.0,
          sum_eps: float = 1e-6, scoring: str = "sigmoid"):
    """The router, in float32: ``s = sigmoid(x W_r)``; a token's experts are
    the ``top_k`` of ``s + bias`` (the bias enters the choice only and takes
    no gradient); their weights are the chosen ``s`` over their sum +
    ``sum_eps``, times ``scaling``.  ``sum_eps`` is the model's own constant
    (``lfm2_moe`` publishes 1e-6, ``deepseek_v3`` 1e-20: beside a sum of
    ``top_k`` sigmoids the two differ in the weights' seventh digit).
    ``scoring="softmax"`` (Qwen3-MoE's router): ``s = softmax(x W_r)`` over
    all the experts, the ``top_k`` of ``s`` with no bias (``bias`` None),
    their weights the chosen ``s`` over their sum, times ``scaling``.
    Returns ``(experts [N, k] int32, weights [N, k])``."""
    logits = jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = lax.top_k(scores, top_k)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + sum_eps) * scaling
        return chosen, w
    if scoring != "sigmoid":
        raise ValueError(f"route: no scoring {scoring!r}")
    scores = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(scores + lax.stop_gradient(bias.astype(jnp.float32)),
                          top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + sum_eps) * scaling
    return chosen, w


def _gather_rows(ys, slot, valid):
    """``out[n] = sum_j valid[n, j] * ys[slot[n, j]]`` in float32."""
    out = jnp.zeros((slot.shape[0], ys.shape[1]), jnp.float32)
    for j in range(slot.shape[1]):
        rows = jnp.take(ys, slot[:, j], axis=0).astype(jnp.float32)
        out = out + jnp.where(valid[:, j, None], rows, 0.0)
    return out


@jax.custom_vjp
def _dispatch(x, tok, slot, valid):
    """Rows of ``x [N, D]`` in the round's order: ``x[tok]`` (``[C, D]``)."""
    return jnp.take(x, tok, axis=0)


def _dispatch_fwd(x, tok, slot, valid):
    return jnp.take(x, tok, axis=0), (slot, valid)


def _dispatch_bwd(res, g):
    with scope("expert_dispatch"):
        return (_gather_rows(g, *res).astype(g.dtype), None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, tok, row_valid, slot, valid):
    """The round's rows ``ys [C, D]`` back to their tokens, summed over a
    token's experts: ``[N, D]`` float32.  The transpose of :func:`_dispatch`."""
    return _gather_rows(ys, slot, valid)


def _combine_fwd(ys, tok, row_valid, slot, valid):
    return _gather_rows(ys, slot, valid), (tok, row_valid, slot, valid,
                                           jnp.zeros((0,), ys.dtype))


def _combine_bwd(res, g):
    tok, row_valid, slot, valid, like = res
    with scope("expert_dispatch"):
        rows = jnp.where(row_valid[:, None], jnp.take(g, tok, axis=0), 0.0)
        return (rows.astype(like.dtype), None, None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _grouped_dot(lhs, rhs, sizes):
    """``lhs[rows of group g] @ rhs[g]``; rows past the groups give zero.
    At the default precision whatever the caller's default is: the operands
    are in the compute dtype, and the benchmark's check traces the cells
    under a "highest" default."""
    return lax.ragged_dot(lhs, rhs, sizes, precision=lax.Precision.DEFAULT,
                          preferred_element_type=lhs.dtype)


def routed_experts(x, router, experts, *, first: int, held: int, total: int,
                   top_k: int, scaling: float = 1.0, sum_eps: float = 1e-6,
                   scoring: str = "sigmoid") -> Tuple[jax.Array, jax.Array]:
    """``x [N, D]`` through the held experts' part of the layer.

    ``router``: ``{"kernel" [D, total], "bias" [total]}`` (no ``bias`` under
    ``scoring="softmax"``, see :func:`route`); ``experts``:
    ``{"w1", "w3" [held, D, F], "w2" [held, F, D]}``.  Returns the partial
    sum ``[N, D]`` in ``x``'s dtype and, for each held expert, the share of
    all ``N * top_k`` assignments that fell on it (float32, ``[held]``)."""
    n, _ = x.shape
    a = n * top_k
    capacity = round_capacity(a, held, total)
    rounds = math.ceil(a / capacity)
    with scope("expert_route"):
        chosen, weights = route(x, router["kernel"], router.get("bias"),
                                top_k, scaling, sum_eps, scoring)

        # Counting sort of the assignments by held expert; group ``held``
        # takes those of absent experts, behind all the others.
        local = chosen.reshape(a) - first
        group = jnp.where((local >= 0) & (local < held), local, held)
        onehot = (group[:, None] == jnp.arange(held + 1, dtype=jnp.int32)[None, :]
                  ).astype(jnp.int32)
        sizes = jnp.sum(onehot, axis=0)
        starts = jnp.cumsum(sizes) - sizes
        rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), group[:, None],
                                   axis=1)[:, 0] - 1
        pos = (starts[group] + rank).reshape(n, top_k)  # place in sorted order
        order = jnp.argsort(group, stable=True)  # assignment at each place
        mine = (group < held).reshape(n, top_k)
        rows = jnp.sum(sizes[:held])
        order = jnp.pad(order, (0, rounds * capacity - a))
        w_flat = weights.reshape(a)
    cast = lambda w: w.astype(x.dtype)
    w1, w3, w2 = cast(experts["w1"]), cast(experts["w3"]), cast(experts["w2"])
    ends = starts[:held] + sizes[:held]

    def one_round(r):
        with scope("expert_dispatch"):
            lo = r * capacity
            asg = lax.dynamic_slice_in_dim(order, lo, capacity)
            tok = asg // top_k
            row_valid = lo + jnp.arange(capacity, dtype=jnp.int32) < rows
            slot = jnp.clip(pos - lo, 0, capacity - 1)
            valid = mine & (pos >= lo) & (pos < lo + capacity)
            sizes_r = (jnp.clip(ends - lo, 0, capacity)
                       - jnp.clip(starts[:held] - lo, 0, capacity))
            xs = _dispatch(x, tok, slot, valid)
        h = jax.nn.silu(_grouped_dot(xs, w1, sizes_r)) * _grouped_dot(
            xs, w3, sizes_r)
        ys = _grouped_dot(h, w2, sizes_r)
        with scope("expert_dispatch"):
            w_r = jnp.where(row_valid, jnp.take(w_flat, asg), 0.0)
            ys = (ys.astype(jnp.float32) * w_r[:, None]).astype(x.dtype)
            return _combine(ys, tok, row_valid, slot, valid)

    out = one_round(0)
    if rounds > 1:
        @jax.checkpoint
        def more(acc, r):
            return acc + lax.cond(r * capacity < rows, one_round,
                                  lambda r: jnp.zeros_like(acc), r), None

        # One branch round the whole of the overflow: routing that fits the
        # first round (any balanced one) pays for no scan at all, forward or
        # backward (its skipped rounds cost the chip 15 ms a layer, PR 29).
        out = lax.cond(
            rows > capacity,
            lambda out: lax.scan(
                more, out, jnp.arange(1, rounds, dtype=jnp.int32))[0],
            lambda out: out, out)
    out = out.astype(x.dtype)
    with scope("expert_route"):
        load = sizes[:held].astype(jnp.float32) / a
    return out, load


@dataclasses.dataclass(frozen=True)
class RoutedExperts(Layer):
    """The layer on ``[B, S, D]``: the router over all ``total`` experts, the
    ``held`` experts from ``first`` as SwiGLUs of width ``ffn``.

    Parameters: ``router.kernel`` (trained), ``router.bias`` (enters the
    choice only, no gradient, zero unless a balancing rule writes it; none
    under ``scoring="softmax"``, whose router has no bias),
    ``experts.w1/w3/w2``, and ``load``: the share of the assignments that
    fell on each held expert in the last step, a running statistic written
    through ``ctx.bn_sink`` as BatchNorm's are (what a balancing rule for the
    bias would read, and what the step's expert counters are made from)."""

    features: int
    ffn: int
    total: int
    top_k: int
    held: int
    first: int = 0
    scaling: float = 1.0
    sum_eps: float = 1e-6  # beside the chosen scores' sum (see route)
    scoring: str = "sigmoid"  # or "softmax" (see route)

    def __post_init__(self):
        if not (0 <= self.first and self.first + self.held <= self.total
                and self.held >= 1):
            raise ValueError(
                f"experts {self.first}..{self.first + self.held} of {self.total}")

    def init(self, key, in_shape):
        d, f = self.features, self.ffn
        assert in_shape[-1] == d, (in_shape, d)
        kr, k1, k3, k2 = jax.random.split(key, 4)
        router = {"kernel": _uniform(kr, (d, self.total), d ** -0.5)}
        if self.scoring == "sigmoid":
            router["bias"] = jnp.zeros((self.total,), jnp.float32)
        params = {
            "router": router,
            "experts": {
                "w1": _uniform(k1, (self.held, d, f), d ** -0.5),
                "w3": _uniform(k3, (self.held, d, f), d ** -0.5),
                "w2": _uniform(k2, (self.held, f, d), f ** -0.5),
            },
            "load": jnp.zeros((self.held,), jnp.float32),
        }
        return params, in_shape

    def apply(self, params, x, ctx: ApplyCtx):
        recorder().note_site("experts", self, "ragged_dot")
        b, s, d = x.shape
        y, load = routed_experts(
            x.reshape(b * s, d), params["router"], params["experts"],
            first=self.first, held=self.held, total=self.total,
            top_k=self.top_k, scaling=self.scaling, sum_eps=self.sum_eps,
            scoring=self.scoring)
        if ctx.bn_sink is not None:
            ctx.bn_sink[id(params["load"])] = load
        return y.reshape(b, s, d)
