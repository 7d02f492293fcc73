"""AmoebaNet-D as a cell list.

Topology per the reference (``src/models/amoebanet.py:449-615``, itself after
the TensorFlow/GPipe AmoebaNet-D): a Stem, two reduction stem cells, three
groups of normal cells separated by reduction cells, and a Classify head.
Each NAS cell carries tuple state ``(x, skip)`` — the multi-tensor activation
the pipeline engine must forward between stages (reference
amoebanet.py:500-532; pipeline support mp_pipeline.py:215-223).

Deliberate fix (SURVEY §7 bug list — not replicated): the reference's
``max_pool_3x3`` constructs an **Avg**Pool in both branches
(amoebanet.py:108-125); here it is a real max pool.

As with ResNet, there is exactly one definition: the reference's separate
``amoebanetd_spatial`` / ``amoebanet_d2`` variants collapse into apply-time
ApplyCtx dispatch (halo-exchanging convs/pools under spatial sharding).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.cells import (
    Cell, CellModel, LayerCell, _unpack_one, checkpointed_apply,
)
from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.layers import (
    BatchNorm,
    Conv2d,
    Dense,
    GlobalAvgPool,
    Identity,
    Layer,
    Pool2d,
    ReLU,
)

# ---------------------------------------------------------------------------
# Op constructors (reference amoebanet.py:79-399).  Each returns a LayerCell
# operating on a single tensor; channels is the cell's working width c.
# ---------------------------------------------------------------------------


def _relu_conv_bn(in_c: int, out_c: int, kernel=1, stride=1, padding=0,
                  pad_in: int = 0, pad_out: int = 0) -> List[Layer]:
    """relu → conv → bn. ``pad_in``/``pad_out`` thread function-preserving
    lane padding (layers.Conv2d lane_pad_*) through the chain: the conv's
    zero-padded channels stay exact zeros through BN (scale pad 0) and ReLU,
    so a whole bottleneck runs on one dense 128-lane width."""
    return [
        ReLU(),
        Conv2d(in_c, out_c, kernel_size=kernel, stride=stride,
               padding=padding, bias=False,
               lane_pad_in=pad_in, lane_pad_out=pad_out),
        BatchNorm(out_c, lane_pad=pad_out),
    ]


def _lane_pad(c: int) -> int:
    """Padded width for a bottleneck mid-channel under MPI4DL_LANE_PAD=1
    (0 = disabled / already a multiple of 128).  Opt-in perf experiment:
    trades zero-weight FLOPs for one dense layout through the chain
    (judged on img/s, not mfu — flops_per_step counts the padding)."""
    import os

    if os.environ.get("MPI4DL_LANE_PAD") != "1" or c % 128 == 0:
        return 0
    return ((c + 127) // 128) * 128


@dataclasses.dataclass
class FactorizedReduce(Cell):
    """relu → concat(conv1(x), conv2(x)) → bn, both 1x1 stride-2 halves
    (reference amoebanet.py:56-76; the pixel-shifted second path is commented
    out there, so both halves see the same input)."""

    in_c: int
    out_c: int
    name: str = "fact_reduce"

    def __post_init__(self):
        self.conv1 = Conv2d(self.in_c, self.out_c // 2, kernel_size=1, stride=2,
                            padding=0, bias=False)
        self.conv2 = Conv2d(self.in_c, self.out_c // 2, kernel_size=1, stride=2,
                            padding=0, bias=False)
        self.bn = BatchNorm(self.out_c)

    def init(self, key, in_shape):
        k1, k2, k3 = jax.random.split(key, 3)
        p1, s1 = self.conv1.init(k1, in_shape)
        p2, _ = self.conv2.init(k2, in_shape)
        cat_shape = (*s1[:-1], self.out_c)
        p3, out = self.bn.init(k3, cat_shape)
        return {"conv1": p1, "conv2": p2, "bn": p3}, out

    def apply(self, params, x, ctx):
        x = jax.nn.relu(x)
        y = jnp.concatenate(
            [self.conv1.apply(params["conv1"], x, ctx),
             self.conv2.apply(params["conv2"], x, ctx)],
            axis=-1,
        )
        y = self.bn.apply(params["bn"], y, ctx)
        # The barrier is a fusion boundary the chip needs: XLA:TPU (libtpu
        # 0.0.34 under jax 0.9.0) miscompiles the bf16 backward of the cell
        # this feeds when this output fuses into its consumers — at 1024²
        # every gradient upstream of here came back NaN on the v5e, for any
        # input and any cotangent, while op-by-op execution, fp32 compute
        # and this barrier are all finite (PR 22, PERF.md).
        return lax.optimization_barrier(y)


def op_none(c: int, stride: int) -> Cell:
    if stride == 1:
        return LayerCell([Identity()], name="none")
    return FactorizedReduce(c, c)


def op_avg_pool_3x3(c: int, stride: int) -> Cell:
    return LayerCell(
        [Pool2d("avg", 3, stride, 1, count_include_pad=False)], name="avg_pool_3x3"
    )


def op_max_pool_3x3(c: int, stride: int) -> Cell:
    return LayerCell([Pool2d("max", 3, stride, 1)], name="max_pool_3x3")


def op_max_pool_2x2(c: int, stride: int) -> Cell:
    return LayerCell([Pool2d("max", 2, stride, 0)], name="max_pool_2x2")


def op_conv_1x1(c: int, stride: int) -> Cell:
    return LayerCell(_relu_conv_bn(c, c, 1, stride, 0), name="conv_1x1")


def op_conv_3x3(c: int, stride: int) -> Cell:
    # Bottleneck form c → c/4 → c (reference amoebanet.py:252-287)
    m, pm = c // 4, _lane_pad(c // 4)
    return LayerCell(
        _relu_conv_bn(c, m, 1, 1, 0, pad_out=pm)
        + _relu_conv_bn(m, m, 3, stride, 1, pad_in=pm, pad_out=pm)
        + _relu_conv_bn(m, c, 1, 1, 0, pad_in=pm),
        name="conv_3x3",
    )


def op_conv_1x7_7x1(c: int, stride: int) -> Cell:
    # c → c/4 → (1,7) → (7,1) → c with stride applied once per image dim
    # (reference amoebanet.py:147-243)
    m, pm = c // 4, _lane_pad(c // 4)
    return LayerCell(
        _relu_conv_bn(c, m, 1, 1, 0, pad_out=pm)
        + _relu_conv_bn(m, m, (1, 7), (1, stride), (0, 3), pad_in=pm, pad_out=pm)
        + _relu_conv_bn(m, m, (7, 1), (stride, 1), (3, 0), pad_in=pm, pad_out=pm)
        + _relu_conv_bn(m, c, 1, 1, 0, pad_in=pm),
        name="conv_1x7_7x1",
    )


# Genotype (reference amoebanet.py:290-330): (input_index, op_ctor) pairs.
NORMAL_OPERATIONS: List[Tuple[int, Callable[[int, int], Cell]]] = [
    (1, op_conv_1x1),
    (1, op_max_pool_3x3),
    (1, op_none),
    (0, op_conv_1x7_7x1),
    (0, op_conv_1x1),
    (0, op_conv_1x7_7x1),
    (2, op_max_pool_3x3),
    (2, op_none),
    (1, op_avg_pool_3x3),
    (5, op_conv_1x1),
]
NORMAL_CONCAT = [0, 3, 4, 6]

REDUCTION_OPERATIONS: List[Tuple[int, Callable[[int, int], Cell]]] = [
    (0, op_max_pool_2x2),
    (0, op_max_pool_3x3),
    (2, op_none),
    (1, op_conv_3x3),
    (2, op_conv_1x7_7x1),
    (2, op_max_pool_3x3),
    (3, op_none),
    (1, op_max_pool_2x2),
    (2, op_avg_pool_3x3),
    (3, op_conv_1x1),
]
REDUCTION_CONCAT = [4, 5, 6]


@dataclasses.dataclass
class Stem(Cell):
    """relu → conv3x3 s2 → bn (reference amoebanet.py:418-446; yes, the relu
    on raw input is what the reference does)."""

    channels: int
    name: str = "stem"

    def __post_init__(self):
        self.conv = Conv2d(3, self.channels, 3, stride=2, padding=1, bias=False)
        self.bn = BatchNorm(self.channels)

    def init(self, key, in_shape):
        k1, k2 = jax.random.split(key)
        p1, s = self.conv.init(k1, in_shape)
        p2, s = self.bn.init(k2, s)
        return {"conv": p1, "bn": p2}, s

    def apply(self, params, x, ctx):
        x = jax.nn.relu(x)
        x = self.conv.apply(params["conv"], x, ctx)
        return self.bn.apply(params["bn"], x, ctx)


@dataclasses.dataclass
class AmoebaCell(Cell):
    """One NAS cell.  State in/out is (x, skip); a lone tensor is broadcast to
    both (reference Cell.forward, amoebanet.py:500-532)."""

    channels_prev_prev: int
    channels_prev: int
    channels: int
    reduction: bool
    reduction_prev: bool
    name: str = "amoeba_cell"

    def __post_init__(self):
        c = self.channels
        self.reduce1 = LayerCell(_relu_conv_bn(self.channels_prev, c), name="reduce1")
        if self.reduction_prev:
            self.reduce2: Cell = FactorizedReduce(self.channels_prev_prev, c)
        elif self.channels_prev_prev != c:
            self.reduce2 = LayerCell(_relu_conv_bn(self.channels_prev_prev, c), name="reduce2")
        else:
            self.reduce2 = LayerCell([Identity()], name="reduce2_id")
        ops_spec = REDUCTION_OPERATIONS if self.reduction else NORMAL_OPERATIONS
        self.concat = REDUCTION_CONCAT if self.reduction else NORMAL_CONCAT
        self.indices = [i for i, _ in ops_spec]
        self.ops: List[Cell] = []
        for i, ctor in ops_spec:
            stride = 2 if (self.reduction and i < 2) else 1
            self.ops.append(ctor(c, stride))

    def init(self, key, in_shape):
        # in_shape: (shape_x, shape_skip) or a single shape used for both.
        if isinstance(in_shape[0], (tuple, list)):
            s1_shape, s2_shape = in_shape
        else:
            s1_shape = s2_shape = in_shape
        keys = jax.random.split(key, 2 + len(self.ops))
        p_r1, s1 = self.reduce1.init(keys[0], s1_shape)
        p_r2, s2 = self.reduce2.init(keys[1], s2_shape)
        state_shapes = [s1, s2]
        op_params = []
        for j in range(0, len(self.ops), 2):
            in1 = state_shapes[self.indices[j]]
            in2 = state_shapes[self.indices[j + 1]]
            p1, o1 = self.ops[j].init(keys[2 + j], in1)
            p2, o2 = self.ops[j + 1].init(keys[2 + j + 1], in2)
            assert o1 == o2, (self.name, j, o1, o2)
            op_params += [p1, p2]
            state_shapes.append(o1)
        out_c = self.channels * len(self.concat)
        out_shape = (*state_shapes[self.concat[0]][:-1], out_c)
        return {"reduce1": p_r1, "reduce2": p_r2, "ops": op_params}, (
            out_shape,
            s1_shape,
        )

    def apply(self, params, x, ctx: ApplyCtx):
        sp = ctx.spatial
        if (
            sp is not None
            and sp.active
            and sp.d2_mode
            and not sp.halo_pre_exchanged
            and not self.reduction
        ):
            plan = self.d2_plan()
            if plan is not None:
                return self._apply_d2(params, x, ctx, plan)
        if isinstance(x, tuple):
            s1, s2 = x
        else:
            s1 = s2 = x
        # One DAG walk; states are (value, pack_meta) pairs.  Fine remat
        # (ctx.remat_ops): each reduce/op is its own checkpoint region, so
        # the backward holds one op's internals at a time instead of the
        # whole cell DAG's (max-trainable-resolution lever) — and the DAG
        # states BETWEEN op checkpoints are stored lane-packed
        # ([N,H,W*C/128,128], cells.py): they are the live set of the
        # cell's backward, and at 2048-res they were the 4096² OOM
        # top-list ([1,2048,2048,208] ~1.6 GB x4+, PERF_NOTES r4).
        # Pack/unpack lives INSIDE each checkpoint (in_meta), so only the
        # packed form is ever saved; h1+h2 adds packed forms directly
        # (packing is a reshape — elementwise-safe).  Plain path: meta is
        # always None and app is a direct call.
        if ctx.remat_ops:
            def app(l, p, state):
                s, meta = state
                return checkpointed_apply(
                    l.apply, p, s, ctx, in_meta=meta, pack=True
                )
        else:
            def app(l, p, state):
                return l.apply(p, state[0], ctx), None

        skip = s1
        states = [
            app(self.reduce1, params["reduce1"], (s1, None)),
            app(self.reduce2, params["reduce2"], (s2, None)),
        ]
        for j in range(0, len(self.ops), 2):
            y1, m1 = app(self.ops[j], params["ops"][j], states[self.indices[j]])
            y2, m2 = app(
                self.ops[j + 1], params["ops"][j + 1],
                states[self.indices[j + 1]],
            )
            assert m1 == m2, (m1, m2)
            states.append((y1 + y2, m1))
        out = jnp.concatenate(
            [_unpack_one(*states[i]) for i in self.concat], axis=-1
        )
        return (out, skip)

    # ---- cell-level D2 (the reference's Cell_D2, amoebanet_d2.py:569-728) --

    def d2_plan(self):
        """Static margin plan for cell-level halo fusion (stride-1 cells).

        The reference pre-exchanges each input state once per cell with a
        hand-derived halo (s3: halo 3, s4: halo 2, s5 = s4[1:-1]) and runs the
        ops pad-free.  Here the same constants fall out of a backward pass
        over the genotype DAG:  need[s] = max over ops consuming state s of
        (op's accumulated halo + need[op's output state]); intermediate states
        inherit leftover margin (crop, no exchange).  For the normal-cell
        genotype this yields need[s1]=3, need[s2]=2 — the reference's
        constants.  Returns None when any op cannot participate."""
        if getattr(self, "_d2_plan_cache", "unset") != "unset":
            return self._d2_plan_cache
        from mpi4dl_tpu.ops.d2 import accumulated_halo

        margins = []
        plan = None
        for op in self.ops:
            if not isinstance(op, LayerCell):
                break
            acc = accumulated_halo(op.layers)
            if acc is None:
                break
            margins.append(acc)
        else:
            n_states = 2 + len(self.ops) // 2
            need = [(0, 0)] * n_states
            for j in reversed(range(0, len(self.ops), 2)):
                out_state = 2 + j // 2
                for jj in (j, j + 1):
                    s_in = self.indices[jj]
                    ch, cw = margins[jj]
                    need[s_in] = (
                        max(need[s_in][0], ch + need[out_state][0]),
                        max(need[s_in][1], cw + need[out_state][1]),
                    )
            plan = {"need": need, "margins": margins}
        self._d2_plan_cache = plan
        return plan

    def _apply_d2(self, params, x, ctx: ApplyCtx, plan):
        """One halo exchange per input state; ops run margin-consuming;
        intermediate states re-align by cropping leftover margin."""
        from mpi4dl_tpu.ops.d2 import apply_layers_premargin, premargin_out
        from mpi4dl_tpu.ops.halo import HaloSpec, halo_exchange_2d

        sp = ctx.spatial
        sharded_h = bool(sp.axis_h) and sp.grid_h > 1
        sharded_w = bool(sp.axis_w) and sp.grid_w > 1
        need = plan["need"]

        def dims(nh, nw):
            return (nh if sharded_h else 0, nw if sharded_w else 0)

        def crop(t, ch, cw):
            if ch == 0 and cw == 0:
                return t
            return t[:, ch : t.shape[1] - ch or None, cw : t.shape[2] - cw or None, :]

        if isinstance(x, tuple):
            s1_in, s2_in = x
        else:
            s1_in = s2_in = x
        skip = s1_in
        if ctx.remat_ops:
            s1 = checkpointed_apply(
                self.reduce1.apply, params["reduce1"], s1_in, ctx
            )
            s2 = checkpointed_apply(
                self.reduce2.apply, params["reduce2"], s2_in, ctx
            )
        else:
            s1 = self.reduce1.apply(params["reduce1"], s1_in, ctx)
            s2 = self.reduce2.apply(params["reduce2"], s2_in, ctx)

        states = []
        for t, (nh, nw) in ((s1, need[0]), (s2, need[1])):
            mh, mw = dims(nh, nw)
            t = halo_exchange_2d(
                t, HaloSpec.symmetric(mh), HaloSpec.symmetric(mw),
                sp.axis_h, sp.axis_w, sp.grid_h, sp.grid_w,
                rep_h=sp.rep_h, rep_w=sp.rep_w,
            )
            states.append((t, mh, mw))

        for j in range(0, len(self.ops), 2):
            out_state = 2 + j // 2
            tnh, tnw = dims(*need[out_state])
            outs = []
            for jj in (j, j + 1):
                t, mh, mw = states[self.indices[jj]]
                if ctx.remat_ops:
                    # Fine remat in the fused path: the checkpoint returns
                    # arrays only, so the static margins are re-derived by
                    # premargin_out (pure arithmetic).
                    def op_fn(p, tt, c, _l=self.ops[jj].layers,
                              _mh=mh, _mw=mw):
                        return apply_layers_premargin(_l, p, tt, c, _mh, _mw)[0]

                    y = checkpointed_apply(op_fn, params["ops"][jj], t, ctx)
                    mho, mwo = premargin_out(
                        self.ops[jj].layers, ctx, mh, mw
                    )
                else:
                    y, mho, mwo = apply_layers_premargin(
                        self.ops[jj].layers, params["ops"][jj], t, ctx, mh, mw
                    )
                outs.append(crop(y, mho - tnh, mwo - tnw))
            states.append((outs[0] + outs[1], tnh, tnw))

        out = jnp.concatenate(
            [crop(states[i][0], states[i][1], states[i][2]) for i in self.concat],
            axis=-1,
        )
        return (out, skip)


@dataclasses.dataclass
class Classify(Cell):
    """(x, skip) → global avg pool → FC (reference amoebanet.py:401-417)."""

    channels_prev: int
    num_classes: int
    name: str = "classify"

    def __post_init__(self):
        self.pool = GlobalAvgPool()
        self.fc = Dense(self.channels_prev, self.num_classes)

    def init(self, key, in_shape):
        x_shape = in_shape[0] if isinstance(in_shape[0], (tuple, list)) else in_shape
        p_pool, s = self.pool.init(key, x_shape)
        k1, _ = jax.random.split(key)
        p_fc, out = self.fc.init(k1, s)
        return {"fc": p_fc}, out

    def apply(self, params, x, ctx):
        if isinstance(x, tuple):
            x = x[0]
        y = self.pool.apply({}, x, ctx)
        return self.fc.apply(params["fc"], y, ctx)


def amoebanetd(
    in_shape: Tuple[int, int, int, int],
    num_classes: int = 10,
    num_layers: int = 4,
    num_filters: int = 512,
) -> CellModel:
    """Build AmoebaNet-D (reference amoebanetd(), amoebanet.py:535-615)."""
    assert num_layers % 3 == 0, "num_layers must be divisible by 3"
    repeat_normal = num_layers // 3

    channels = num_filters // 4
    channels_prev_prev = channels_prev = channels
    reduction_prev = False
    cells: List[Cell] = []

    def add_cell(reduction: bool, scale: int, name: str):
        nonlocal channels, channels_prev, channels_prev_prev, reduction_prev
        channels *= scale
        cell = AmoebaCell(
            channels_prev_prev, channels_prev, channels, reduction, reduction_prev,
            name=name,
        )
        cells.append(cell)
        channels_prev_prev = channels_prev
        channels_prev = channels * len(cell.concat)
        reduction_prev = reduction

    cells.append(Stem(channels))
    add_cell(True, 2, "stem2")
    add_cell(True, 2, "stem3")
    for i in range(repeat_normal):
        add_cell(False, 1, f"cell1_normal{i+1}")
    add_cell(True, 2, "cell2_reduction")
    for i in range(repeat_normal):
        add_cell(False, 1, f"cell3_normal{i+1}")
    add_cell(True, 2, "cell4_reduction")
    for i in range(repeat_normal):
        add_cell(False, 1, f"cell5_normal{i+1}")
    cells.append(Classify(channels_prev, num_classes))

    return CellModel(
        cells, in_shape, num_classes, name=f"amoebanetd_l{num_layers}_f{num_filters}"
    )
