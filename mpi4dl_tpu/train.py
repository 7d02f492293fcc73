"""Training steps: loss, optimizer, and jitted step builders.

The reference's training loops live in benchmark scripts + runtime classes
(`train_model.run_step/update`, mp_pipeline.py:509-538).  Here each regime is
a *builder* returning one jitted function `(state, batch) -> (state, metrics)`:

- :func:`make_train_step` — single device or pure DP (pjit over ``data``).
- :func:`make_spatial_train_step` — SP(+DP): shard_map over sph/spw(+data),
  halo convs inside, psum'd grads (the tile group doubles as a DP group for
  gradients, exactly the reference's create_allreduce_comm_spatial,
  comm.py:197-248).
- Pipeline/GEMS steps live in parallel/pipeline.py and parallel/gems.py.

Loss: softmax cross-entropy on logits (the reference's CrossEntropyLoss after
an in-model softmax is a double-softmax quirk, reproduced only when the model
was built with ``softmax_in_model=True``; then we take log of the model's
probabilities instead).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi4dl_tpu.compat import pcast

from mpi4dl_tpu.cells import CellModel
from mpi4dl_tpu.layer_ctx import ApplyCtx, SpatialCtx
from mpi4dl_tpu.mesh import AXIS_DATA
from mpi4dl_tpu.obs.scopes import scope


def cross_entropy(logits_or_probs: jax.Array, labels: jax.Array,
                  from_probs: bool = False) -> jax.Array:
    """Mean softmax cross-entropy with integer labels of the logits' leading
    shape: ``[B, V]`` with ``[B]``, or ``[B, S, V]`` with ``[B, S]`` and the
    mean over every position."""
    x = logits_or_probs.astype(jnp.float32)
    if from_probs:
        logp = jnp.log(jnp.clip(x, 1e-20, 1.0))
    else:
        logp = jax.nn.log_softmax(x, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), axis=-1)
    return jnp.mean(nll)


def cast_input(x, dtype):
    """The batch's input in the compute dtype, where it is floating; token
    ids stay as the loader made them (cast to bf16 they are exact only below
    256)."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        x)


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Optimizer — minimal SGD(+momentum) and Adam over arbitrary pytrees.
# (The reference uses torch.optim.SGD(lr=0.001); optax is available but the
# pipeline engine works on flat stage buffers where a hand-rolled update is
# clearer and allocation-free.)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """SGD(+momentum) / Adam with fp32 update arithmetic.

    Optimizer state (velocity, moments) is always fp32 and the update is
    computed in fp32 regardless of the parameter storage dtype, then rounded
    back — so ``--precision bf_16_all`` (params stored bf16, config.py) keeps
    fp32 math in the update path.  No persistent fp32 master copy is kept: a
    master would cost 4 extra bytes/param (6 vs 4 B — *negating* the memory
    capability the mode exists for) and would desynchronize from the BN
    running-stat write-back, which targets the live parameter buffer."""

    kind: str = "sgd"
    lr: float = 0.001
    momentum: float = 0.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8

    @staticmethod
    def _zeros32(params):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def init(self, params):
        if self.kind == "sgd" and self.momentum == 0.0:
            return ()
        if self.kind == "sgd":
            return (self._zeros32(params),)
        if self.kind == "adam":
            return (
                self._zeros32(params),
                self._zeros32(params),
                jnp.zeros((), jnp.int32),
            )
        raise ValueError(self.kind)

    def update(self, params, grads, opt_state):
        f32 = jnp.float32
        if self.kind == "sgd" and self.momentum == 0.0:
            new = jax.tree.map(
                lambda p, g: (p.astype(f32) - self.lr * g.astype(f32)).astype(p.dtype),
                params, grads,
            )
            return new, ()
        if self.kind == "sgd":
            (vel,) = opt_state
            vel = jax.tree.map(
                lambda v, g: self.momentum * v + g.astype(f32), vel, grads
            )
            new = jax.tree.map(
                lambda p, v: (p.astype(f32) - self.lr * v).astype(p.dtype),
                params, vel,
            )
            return new, (vel,)
        if self.kind == "adam":
            m, v, t = opt_state
            t = t + 1
            m = jax.tree.map(lambda a, g: self.b1 * a + (1 - self.b1) * g.astype(f32), m, grads)
            v = jax.tree.map(lambda a, g: self.b2 * a + (1 - self.b2) * jnp.square(g.astype(f32)), v, grads)
            bc1 = 1 - self.b1 ** t.astype(f32)
            bc2 = 1 - self.b2 ** t.astype(f32)
            new = jax.tree.map(
                lambda p, mm, vv: (
                    p.astype(f32) - self.lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + self.eps)
                ).astype(p.dtype),
                params, m, v,
            )
            return new, (m, v, t)
        raise ValueError(self.kind)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    @staticmethod
    def create(params, optimizer: Optimizer) -> "TrainState":
        return TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Single-device / DP train step
# ---------------------------------------------------------------------------


def stat_updates_from_sink(sink: Optional[dict], params) -> Optional[list]:
    """Collect a bn_sink into a list aligned with the flattened param leaves
    (None where a leaf has no running-stat update — None is an empty pytree
    node, so the list is a valid jit/scan-carry aux with static structure)."""
    if sink is None:
        return None
    return [sink.get(id(leaf)) for leaf in jax.tree.leaves(params)]


def merge_stat_updates(params, updates: Optional[list]):
    """Write collected running-stat updates back into a params tree (typically
    the post-optimizer one — the functional analog of torch BN's in-place
    running-buffer mutation)."""
    if updates is None or all(u is None for u in updates):
        return params
    leaves, treedef = jax.tree.flatten(params)
    merged = [l if u is None else u.astype(l.dtype) for l, u in zip(leaves, updates)]
    return jax.tree.unflatten(treedef, merged)


def make_loss_fn(model: CellModel, ctx: ApplyCtx, from_probs: bool = False,
                 remat=False, with_stats: bool = False):
    """Loss fn returning ``(loss, (logits, stat_updates))``; stat_updates is
    None unless with_stats (then a leaf-aligned BN running-stat update list).
    ``remat`` is forwarded to ``CellModel.apply`` (False/True/"sqrt")."""

    def loss_fn(params_list, x, labels):
        c = dataclasses.replace(ctx, bn_sink={}) if with_stats else ctx
        logits = model.apply(params_list, x, c, remat=remat)
        if isinstance(logits, tuple):
            logits = logits[0]
        stats = stat_updates_from_sink(c.bn_sink, params_list) if with_stats else None
        with scope("loss"):
            loss = cross_entropy(logits, labels, from_probs)
        return loss, (logits, stats)

    return loss_fn


def make_train_step(
    model: CellModel,
    optimizer: Optimizer,
    mesh: Optional[Mesh] = None,
    parts: int = 1,
    compute_dtype=jnp.float32,
    from_probs: bool = False,
    remat: bool = False,
    bn_stats: bool = True,
    donate: bool = False,
    scan_steps: int = 1,
):
    """Single-device or DP (batch sharded over 'data') training step.

    ``scan_steps=k`` returns a MULTI-step function ``(state, xs, ys) ->
    (state, metrics)`` with ``xs: [k, B, H, W, C]`` running k optimizer
    steps in ONE compiled program (lax.scan; metrics averaged over the
    scan).  Each dispatch costs host time the device sits out (round 4
    recorded ~28 ms a step) — k steps per dispatch amortizes it to ~0,
    which is also how a real training loop would drive the chip.
    Single-device only (the stacked-batch shardings are not plumbed).

    `parts` > 1 runs the micro-batch gradient-accumulation loop via lax.scan —
    the degenerate (split_size=1) form of the reference's GPipe parts loop.
    `remat=True` checkpoints per cell (memory for FLOPs — required for the
    reference's high-resolution configs at batch 1 on one chip);
    `remat="sqrt"` runs cells in ~√n two-level checkpoint groups (O(√n)
    live cell boundaries); `remat="fine"` keeps per-cell checkpoints and
    adds per-op checkpoints inside composite cells (ctx.remat_ops) — the
    max-trainable-resolution configuration for AmoebaNet (measured:
    boundary mass, not within-op temps, is what "fine" removes there;
    PERF_NOTES.md).
    `bn_stats=True` (default) updates BN running statistics each step (torch
    nn.BatchNorm2d semantics; with parts>1 the update uses the batch stats
    averaged over microbatches, which the momentum rule makes equivalent to
    averaging the per-microbatch updated values).
    """
    import os as _os

    # MPI4DL_REMAT_OPS=1 combines per-op checkpoints with ANY outer remat
    # level (e.g. sqrt grouping + per-op bounding for the ResNet-2048
    # memory frontier) — "fine" remains per-cell + per-op.
    ctx = ApplyCtx(
        train=True,
        remat_ops=(remat == "fine"
                   or _os.environ.get("MPI4DL_REMAT_OPS") == "1"),
    )
    model_remat = "sqrt" if remat == "sqrt" else bool(remat)
    loss_fn = make_loss_fn(
        model, ctx, from_probs, remat=model_remat, with_stats=bn_stats
    )

    def grads_for(params, x, labels):
        (loss, (logits, stats)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, cast_input(x, compute_dtype), labels
        )
        return loss, logits, stats, grads

    def step(state: TrainState, x, labels):
        if parts == 1:
            loss, logits, stats, grads = grads_for(state.params, x, labels)
            acc = accuracy(logits, labels)
        else:
            mb_x = x.reshape(parts, x.shape[0] // parts, *x.shape[1:])
            mb_y = labels.reshape(parts, labels.shape[0] // parts,
                                  *labels.shape[1:])
            zero = jax.tree.map(jnp.zeros_like, state.params)
            # Abstract probe for the (static) stat-update structure.
            stats_struct = jax.eval_shape(
                grads_for, state.params, mb_x[0], mb_y[0]
            )[2]
            stats_zero = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), stats_struct
            )

            def body(carry, mb):
                g_acc, loss_acc, acc_acc, st_acc = carry
                loss, logits, stats, grads = grads_for(state.params, mb[0], mb[1])
                g_acc = jax.tree.map(jnp.add, g_acc, grads)
                st_acc = jax.tree.map(jnp.add, st_acc, stats)
                return (
                    g_acc, loss_acc + loss, acc_acc + accuracy(logits, mb[1]), st_acc
                ), None

            (grads, loss, acc, stats), _ = lax.scan(
                body, (zero, jnp.zeros(()), jnp.zeros(()), stats_zero), (mb_x, mb_y)
            )
            grads = jax.tree.map(lambda g: g / parts, grads)
            stats = jax.tree.map(lambda s: s / parts, stats)
            loss, acc = loss / parts, acc / parts
        with scope("optimizer_update"):
            params, opt_state = optimizer.update(
                state.params, grads, state.opt_state)
        params = merge_stat_updates(params, stats)
        metrics = {"loss": loss, "accuracy": acc}
        if model.step_metrics is not None:
            metrics["counted"] = model.step_metrics(params, x.size)
        return TrainState(params, opt_state, state.step + 1), metrics

    if scan_steps > 1 and mesh is not None:
        raise ValueError("scan_steps>1 is single-device only")
    if scan_steps > 1:
        def multi(state: TrainState, xs, ys):
            state, ms = lax.scan(
                lambda s, xy: step(s, xy[0], xy[1]), state, (xs, ys)
            )
            return state, jax.tree.map(lambda a: jnp.mean(a), ms)

        return jax.jit(multi, donate_argnums=(0,) if donate else ())
    if mesh is None:
        # donate=True consumes the caller's state (params/opt buffers update
        # in place), removing a full extra copy of params+opt from peak
        # memory — part of the max-trainable-resolution story.  Off by
        # default: exact-match tests alias param arrays across states.
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    # DP: batch sharded over 'data'; params replicated.  XLA inserts the
    # gradient all-reduce (the reference's SyncAllreduce, comm.py:440-514).
    data_spec = NamedSharding(mesh, P(AXIS_DATA))
    repl = NamedSharding(mesh, P())
    jstep = jax.jit(
        step,
        in_shardings=(None, data_spec, data_spec),
        out_shardings=(None, None),
        donate_argnums=(0,) if donate else (),
    )
    return jstep


# ---------------------------------------------------------------------------
# Spatial-parallel (SP [+DP]) train step via shard_map
# ---------------------------------------------------------------------------


def spatial_partition_spec(sp: SpatialCtx, data: bool = False) -> P:
    """PartitionSpec for an NHWC batch under a SpatialCtx (the analog of the
    reference's split_input slicing, train_spatial.py:241-290)."""
    return P(AXIS_DATA if data else None, sp.axis_h, sp.axis_w, None)


def make_spatial_train_step(
    model: CellModel,
    optimizer: Optimizer,
    mesh: Mesh,
    sp: SpatialCtx,
    parts: int = 1,
    with_data_axis: bool = False,
    compute_dtype=jnp.float32,
    from_probs: bool = False,
    spatial_until: Optional[int] = None,
    junction: str = "gather",
    bn_stats: bool = True,
    levels=None,
    local_dp: Optional[int] = None,
    donate: bool = False,
    remat=False,
    quant=None,
):
    """SP(+DP) training step: one shard_map over the whole step.
    ``remat`` threads per-cell checkpointing through the spatial region and
    tail (False/True/"sqrt" — see CellModel.apply).

    Inside, convs/pools halo-exchange over sph/spw; after `spatial_until`
    cells the activation is gathered (SP→LP junction; 'batch_split' = the
    LOCAL_DP_LP variant, degree `local_dp`); gradients are psum'd over the
    spatial axes (+ data axis when present) — the spatial tile group being a
    gradient DP group is exactly reference comm.py:197-248.

    ``levels`` is a list of (stop_cell, SpatialCtx) for multi-level spatial
    parallelism (reference num_spatial_parts="4,2"); ``sp`` must be the
    level-0 ctx (it defines the mesh axes and the input sharding).

    ``quant`` (Optional[QuantPolicy], docs/quantization.md): junction/
    respatial payload quantization inside ``apply_spatial_model`` and the
    EQuARX-style quantized gradient pmean (the whole gradient pytree
    reduced as ONE flattened vector); ``None`` is bit-identical.
    """
    from mpi4dl_tpu.parallel.spatial import (
        apply_spatial_model,
        junction_shard_index,
    )

    ctx = ApplyCtx(train=True, spatial=sp, data_axis=AXIS_DATA if with_data_axis else None)
    sp_last = levels[-1][1] if levels else sp
    degree = local_dp if local_dp else sp_last.grid_h * sp_last.grid_w

    def loss_fn(params_list, x, labels):
        c = dataclasses.replace(ctx, bn_sink={}) if bn_stats else ctx
        logits = apply_spatial_model(
            model, params_list, x, c, spatial_until=spatial_until,
            junction=junction, levels=levels, local_dp=local_dp, remat=remat,
            quant=quant,
        )
        if isinstance(logits, tuple):
            logits = logits[0]
        if junction == "batch_split":
            shard = labels.shape[0] // degree
            labels = lax.dynamic_slice_in_dim(
                labels, junction_shard_index(sp_last, degree) * shard, shard, axis=0
            )
        stats = stat_updates_from_sink(c.bn_sink, params_list) if bn_stats else None
        return cross_entropy(logits, labels, from_probs), (logits, labels, stats)
    grad_axes = tuple(a for a in (sp.axis_h, sp.axis_w) if a)
    if with_data_axis:
        grad_axes = (AXIS_DATA,) + grad_axes

    x_spec = spatial_partition_spec(sp, data=with_data_axis)
    y_spec = P(AXIS_DATA) if with_data_axis else P()

    def global_loss_fn(p, xx, yy):
        # pmean over the tile axes makes the differentiated scalar the GLOBAL
        # loss; with shard_map's varying-axes tracking, each device's gradient
        # of it is then the complete gradient (the all_gather junction's
        # adjoint performs the cross-tile summation).  See tests/test_spatial.
        loss, aux = loss_fn(p, xx, yy)
        return lax.pmean(loss, grad_axes), aux

    def sharded_step(params, opt_state, x, labels):
        def grads_for(p, xx, yy):
            (loss, (logits, yy_used, stats)), grads = jax.value_and_grad(
                global_loss_fn, has_aux=True
            )(p, cast_input(xx, compute_dtype), yy)
            return loss, accuracy(logits, yy_used), stats, grads

        if parts == 1:
            loss, acc, stats, grads = grads_for(params, x, labels)
        else:
            mb_x = x.reshape(parts, x.shape[0] // parts, *x.shape[1:])
            mb_y = labels.reshape(parts, labels.shape[0] // parts)
            # Mark accumulators varying over the tile axes (see pipeline.py —
            # required for correct collective transposes under shard_map AD).
            v = lambda t: pcast(t, grad_axes, to="varying")
            zero = jax.tree.map(lambda p: v(jnp.zeros_like(p)), params)
            stats_struct = jax.eval_shape(grads_for, params, mb_x[0], mb_y[0])[2]
            stats_zero = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), stats_struct
            )

            def body(carry, mb):
                g_acc, l_acc, a_acc, st_acc = carry
                loss, acc, stats, grads = grads_for(params, mb[0], mb[1])
                return (
                    jax.tree.map(jnp.add, g_acc, grads),
                    l_acc + loss,
                    a_acc + acc,
                    jax.tree.map(jnp.add, st_acc, stats),
                ), None

            (grads, loss, acc, stats), _ = lax.scan(
                body,
                (zero, v(jnp.zeros(())), v(jnp.zeros(())), stats_zero),
                (mb_x, mb_y),
            )
            grads = jax.tree.map(lambda g: g / parts, grads)
            stats = jax.tree.map(lambda s: s / parts, stats)
            loss, acc = loss / parts, acc / parts

        grad_mode = quant.mode("grad") if quant is not None else None
        if grad_mode:
            from mpi4dl_tpu.quant.collectives import quantized_pmean_tree

            grads = quantized_pmean_tree(
                grads, grad_axes, grad_mode, quant.block
            )
        else:
            grads = jax.tree.map(lambda g: lax.pmean(g, grad_axes), grads)
        new_params, new_opt = optimizer.update(params, grads, opt_state)
        new_params = merge_stat_updates(new_params, stats)
        metrics = {
            "loss": lax.pmean(loss, grad_axes),
            "accuracy": lax.pmean(acc, grad_axes),
        }
        return new_params, new_opt, metrics

    from mpi4dl_tpu.compat import shard_map

    smapped = shard_map(
        sharded_step,
        mesh=mesh,
        in_specs=(P(), P(), x_spec, y_spec),
        out_specs=(P(), P(), P()),
    )

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(state: TrainState, x, labels):
        params, opt_state, metrics = smapped(state.params, state.opt_state, x, labels)
        return TrainState(params, opt_state, state.step + 1), metrics

    return step


# ---------------------------------------------------------------------------
# Eval / inference steps (train=False: BN normalizes with running stats)
# ---------------------------------------------------------------------------


def make_eval_step(
    model: CellModel,
    mesh: Optional[Mesh] = None,
    compute_dtype=jnp.float32,
    from_probs: bool = False,
):
    """Inference step `(params_list, x, labels) -> metrics` (train=False, so
    BN uses running statistics — the path the reference exercises implicitly
    through nn.BatchNorm2d.eval(), which round 1 lacked entirely)."""
    ctx = ApplyCtx(train=False)

    def estep(params_list, x, labels):
        logits = model.apply(params_list, cast_input(x, compute_dtype), ctx)
        if isinstance(logits, tuple):
            logits = logits[0]
        return {
            "loss": cross_entropy(logits, labels, from_probs),
            "accuracy": accuracy(logits, labels),
            "logits": logits,
        }

    if mesh is None:
        return jax.jit(estep)
    data_spec = NamedSharding(mesh, P(AXIS_DATA))
    return jax.jit(estep, in_shardings=(None, data_spec, data_spec))


def make_spatial_eval_step(
    model: CellModel,
    mesh: Mesh,
    sp: SpatialCtx,
    with_data_axis: bool = False,
    compute_dtype=jnp.float32,
    from_probs: bool = False,
    spatial_until: Optional[int] = None,
    junction: str = "gather",
    levels=None,
    local_dp: Optional[int] = None,
):
    """SP(+DP) inference step: tiles in, metrics out (train=False)."""
    from mpi4dl_tpu.compat import shard_map

    from mpi4dl_tpu.parallel.spatial import (
        apply_spatial_model,
        junction_shard_index,
    )

    ctx = ApplyCtx(
        train=False, spatial=sp, data_axis=AXIS_DATA if with_data_axis else None
    )
    red_axes = tuple(a for a in (sp.axis_h, sp.axis_w) if a)
    if with_data_axis:
        red_axes = (AXIS_DATA,) + red_axes
    x_spec = spatial_partition_spec(sp, data=with_data_axis)
    y_spec = P(AXIS_DATA) if with_data_axis else P()
    sp_last = levels[-1][1] if levels else sp
    degree = local_dp if local_dp else sp_last.grid_h * sp_last.grid_w

    def sharded_eval(params_list, x, labels):
        logits = apply_spatial_model(
            model, params_list, cast_input(x, compute_dtype), ctx,
            spatial_until=spatial_until, junction=junction,
            levels=levels, local_dp=local_dp,
        )
        if isinstance(logits, tuple):
            logits = logits[0]
        if junction == "batch_split":
            shard = labels.shape[0] // degree
            labels = lax.dynamic_slice_in_dim(
                labels, junction_shard_index(sp_last, degree) * shard, shard, axis=0
            )
        return {
            "loss": lax.pmean(cross_entropy(logits, labels, from_probs), red_axes),
            "accuracy": lax.pmean(accuracy(logits, labels), red_axes),
        }

    smapped = shard_map(
        sharded_eval, mesh=mesh, in_specs=(P(), x_spec, y_spec), out_specs=P()
    )
    return jax.jit(smapped)
