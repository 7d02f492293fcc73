"""AmoebaNet-D, plain float32 forward (training-mode batch norm).

After the GPipe/torchgpipe AmoebaNet-D that the MPI4DL reference benchmarks
(`benchmark_amoebanet_*.py --num-layers L --num-filters F`): a stem
(relu, 3x3 stride-2 conv, bn), two reduction cells, then three groups of
L/3 normal cells separated by reduction cells, global average pool, dense.
Every cell takes (x, skip) and returns (concat of chosen states, x).

Departures from that source, both the program's and written in its model
file: ``max_pool_3x3`` is a real max pool (the source builds an average
pool there), and the logits go to the loss without a softmax of the
model's own.

Weights are the program's parameter tree (a list with one entry per cell),
because the comparison is of two forwards on the same weights.
"""

from __future__ import annotations

import jax.numpy as jnp

from perfbench.references import plain
from perfbench.references.plain import Tally

# (index of the input state, operation) in pairs whose outputs are added.
NORMAL = [(1, "conv_1x1"), (1, "max_pool_3x3"), (1, "none"), (0, "conv_1x7_7x1"),
          (0, "conv_1x1"), (0, "conv_1x7_7x1"), (2, "max_pool_3x3"), (2, "none"),
          (1, "avg_pool_3x3"), (5, "conv_1x1")]
NORMAL_CONCAT = [0, 3, 4, 6]
REDUCTION = [(0, "max_pool_2x2"), (0, "max_pool_3x3"), (2, "none"),
             (1, "conv_3x3"), (2, "conv_1x7_7x1"), (2, "max_pool_3x3"),
             (3, "none"), (1, "max_pool_2x2"), (2, "avg_pool_3x3"),
             (3, "conv_1x1")]
REDUCTION_CONCAT = [4, 5, 6]


def _relu_conv_bn(x, p, stride=1, padding=0, tally=None):
    """``p`` is the program's [relu, conv, bn] parameter triple."""
    return plain.batchnorm_train(
        plain.conv(plain.relu(x), p[1], stride, padding, tally), p[2])


def _chain(x, p, geometry, tally):
    """A run of relu-conv-bn triples; ``p`` is the flat list of their
    parameters, ``geometry`` one (stride, padding) per triple."""
    for i, (stride, padding) in enumerate(geometry):
        x = _relu_conv_bn(x, p[3 * i: 3 * i + 3], stride, padding, tally)
    return x


def _factorized_reduce(x, p, tally):
    x = plain.relu(x)
    y = jnp.concatenate([plain.conv(x, p["conv1"], 2, 0, tally),
                         plain.conv(x, p["conv2"], 2, 0, tally)], axis=-1)
    return plain.batchnorm_train(y, p["bn"])


def _op(name, x, p, stride, tally):
    if name == "none":
        return x if stride == 1 else _factorized_reduce(x, p, tally)
    if name == "avg_pool_3x3":
        return plain.avg_pool(x, 3, stride, 1, count_include_pad=False)
    if name == "max_pool_3x3":
        return plain.max_pool(x, 3, stride, 1)
    if name == "max_pool_2x2":
        return plain.max_pool(x, 2, stride, 0)
    if name == "conv_1x1":
        return _chain(x, p, [(stride, 0)], tally)
    if name == "conv_3x3":  # bottleneck c -> c/4 -> c
        return _chain(x, p, [(1, 0), (stride, 1), (1, 0)], tally)
    if name == "conv_1x7_7x1":  # c -> c/4 -> (1,7) -> (7,1) -> c
        return _chain(x, p, [(1, 0), ((1, stride), (0, 3)),
                             ((stride, 1), (3, 0)), (1, 0)], tally)
    raise ValueError(name)


def _cell(p, x, skip_in, reduction, tally):
    """One NAS cell on (x, skip); returns (out, x)."""
    s1 = _chain(x, p["reduce1"], [(1, 0)], tally)
    r2 = p["reduce2"]
    if isinstance(r2, dict):  # the cell before reduced: halve the skip too
        s2 = _factorized_reduce(skip_in, r2, tally)
    elif len(r2) == 3:  # widths differ: 1x1 projection
        s2 = _chain(skip_in, r2, [(1, 0)], tally)
    else:
        s2 = skip_in
    ops, concat = (REDUCTION, REDUCTION_CONCAT) if reduction else (
        NORMAL, NORMAL_CONCAT)
    states = [s1, s2]
    for j in range(0, len(ops), 2):
        pair = []
        for jj in (j, j + 1):
            src, name = ops[jj]
            stride = 2 if (reduction and src < 2) else 1
            pair.append(_op(name, states[src], p["ops"][jj], stride, tally))
        states.append(pair[0] + pair[1])
    return jnp.concatenate([states[i] for i in concat], axis=-1), x


def cells(params, sizes, tally: Tally | None = None):
    """AmoebaNet-D(``num_layers``, ``num_filters``) as one function per cell
    of the program's model, each from the activation before it to the one
    after: an array after the stem, then (x, skip), at last the logits.
    ``sizes`` is the configuration file's ``sizes`` object."""
    repeat = sizes["num_layers"] // 3
    plan = [True, True] + [False] * repeat + [True] + [False] * repeat + [
        True] + [False] * repeat
    assert len(params) == len(plan) + 2, (len(params), len(plan))

    def stem(x):
        p = params[0]
        return plain.batchnorm_train(
            plain.conv(plain.relu(x), p["conv"], 2, 1, tally), p["bn"])

    def nas_cell(p, reduction):
        def run(act):
            x, skip = act if isinstance(act, tuple) else (act, act)
            return _cell(p, x, skip, reduction, tally)
        return run

    def classify(act):
        return plain.dense(jnp.mean(act[0], axis=(1, 2)), params[-1]["fc"],
                           tally)

    return [stem] + [nas_cell(p, r) for p, r in zip(params[1:-1], plan)] + [
        classify]
