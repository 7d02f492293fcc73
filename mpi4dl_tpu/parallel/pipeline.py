"""The SPMD pipeline engine (LP + GPipe PP), single jitted program.

Reference behaviour being re-expressed: ``train_model`` runs per-rank
processes exchanging activations/grads with tagged MPI send/recv and loops
micro-batch "parts" all-forward-then-all-backward
(``mp_pipeline.py:294-432``, ``:509-534``).  Here the whole schedule is ONE
``lax.scan`` inside ONE ``shard_map``:

- Each device holds its stage's flat parameter row ([S, Pmax] sharded over
  ``stage``) and runs its stage via ``lax.switch`` (stages are heterogeneous;
  branch s statically unpacks stage s's params/activations).
- The activation buffer rotates stage→stage+1 with one non-wrapping
  ``ppermute`` per tick; stage 0 overwrites its buffer with the next
  micro-batch injection.
- T = parts + S - 1 ticks fill and drain the pipe (GPipe).  Bubble ticks
  compute on don't-care data and are masked out of the loss — the same
  wall-clock the reference's idle bubbles cost, with no control-flow
  divergence in the compiled program.
- **The backward pass is jax.grad of the scan** (``schedule="gpipe"``, the
  default).  AD transposes the forward ppermute into the reverse-direction
  cotangent ppermute (the reference's explicit grad send/recv chain,
  mp_pipeline.py:365-432) and replays ticks in reverse order —
  all-forward-then-all-backward falls out, with per-stage rematerialisation
  (jax.checkpoint) bounding activation memory exactly like GPipe.
- ``schedule="1f1b"`` replaces the AD replay with a schedule-level manual
  backward (stage_common.make_1f1b_scan): each tick runs one forward AND
  one backward micro-batch, bounding live activations to O(stages) instead
  of the replay's O(parts) tick carries (docs/pipeline.md).

No recv buffers, no tags, no GEMS_INVERSE rank mirroring — placement is the
mesh, ordering is dataflow.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.compat import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.parallel.partition import StagePartition, lax_slice
from mpi4dl_tpu.parallel.stage_common import (
    gpipe_scan,
    make_1f1b_scan,
    make_stage_branches,
    put_stage_opt,
    restore_opt_rows,
    scatter_stage_stats,
    squeeze_opt_rows,
    stage_opt_specs,
    use_1f1b_cell_remat,
)
from mpi4dl_tpu.quant.collectives import quantized_pmean
from mpi4dl_tpu.quant.policy import QuantPolicy
from mpi4dl_tpu.train import Optimizer
from mpi4dl_tpu.mesh import AXIS_DATA, AXIS_STAGE


def grad_pmean(x, axes, quant: Optional[QuantPolicy]):  # analysis: ok(unscoped-collective) — callers own the grad_reduce/stats_reduce scopes
    """The engines' gradient/BN-stats ``pmean``, EQuARX-style-quantized
    when the policy's ``grad`` class is on (quantized all_to_all → exact
    f32 dequant-accumulate per shard → quantized all_gather; see
    quant/collectives.quantized_pmean).  Runs OUTSIDE AD — the engines
    reduce value_and_grad outputs.  Shared by pipeline/gems/sp_pipeline."""
    mode = quant.mode("grad") if quant is not None else None
    if mode:
        return quantized_pmean(x, axes, mode, quant.block)
    return lax.pmean(x, axes)


def sum_tied_grads(part: StagePartition, grads):
    """A stage's gradient row with the gradient of every tied leaf
    (``StagePartition.tied_slots``) summed over all its uses at once: each
    use's stage holds a copy of the leaf and the gradient of that use; every
    copy gets the one sum, so one update keeps the copies one value.  Inside
    ``shard_map`` over the stage axis."""
    stage = lax.axis_index(AXIS_STAGE)
    for size, uses in part.tied_slots:
        with scope("tied_grad_reduce"):
            total = lax.psum(  # analysis: ok(unquantized-collective) — exact: the leaf's copies must stay one value
                sum(jnp.where(stage == s, lax_slice(grads, off, size), 0)
                    for s, off in uses), AXIS_STAGE)
        for s, off in uses:
            grads = lax.dynamic_update_slice(
                grads, jnp.where(stage == s, total, lax_slice(grads, off, size)),
                (off,))
    return grads


@dataclasses.dataclass
class PipelineState:
    """Flat training state: [S, Pmax] param buffer + optimizer state."""

    param_buf: jax.Array
    opt_state: Any
    step: jax.Array


jax.tree_util.register_dataclass(
    PipelineState, data_fields=["param_buf", "opt_state", "step"], meta_fields=[]
)


def make_pipeline_train_step(
    part: StagePartition,
    optimizer: Optimizer,
    mesh: Mesh,
    parts: int,
    compute_dtype=jnp.float32,
    remat: bool = True,
    from_probs: bool = False,
    with_data_axis: bool = False,
    loss_scale: float = 1.0,
    bn_stats: bool = True,
    donate: bool = False,
    schedule: str = "gpipe",
    quant: Optional[QuantPolicy] = None,
):
    """Build `(PipelineState, x, labels) -> (PipelineState, metrics)`.

    x: [B, H, W, C] global batch (B = parts * microbatch); labels: [B].  (A
    token model: x [B, S] ids, which ride the stage buffer in the compute
    dtype, and labels [B, S].)

    ``schedule``: ``"gpipe"`` (default — all-forward-then-all-backward as
    jax.grad of the tick scan, the exactness oracle) or ``"1f1b"`` (the
    one-forward-one-backward schedule with a schedule-level manual backward,
    stage_common.make_1f1b_scan: O(stages) live activations instead of
    O(parts)).  Both produce the same parameters after a step up to
    accumulation-order rounding; 1F1B always recomputes stage forwards
    inside its backward branches, so ``remat`` is moot there (branches are
    built unwrapped).  docs/pipeline.md covers when to pick which.

    ``quant``: opt-in quantized-collective policy (docs/quantization.md) —
    ``handoff`` quantizes the tick loop's stage/cotangent ppermutes,
    ``grad`` the DP gradient/stats pmeans; ``None`` is bit-identical to
    the unquantized engine.
    """
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown schedule {schedule!r}; use 'gpipe' or '1f1b'")
    if schedule != "gpipe":
        # its manual backward hands each stage its own row's cotangent
        part.model.refuse_tied(f"the {schedule} schedule")
    S = part.num_stages
    Pn = parts
    ctx = ApplyCtx(train=True)

    grad_axes: Tuple[str, ...] = (AXIS_DATA,) if with_data_axis else ()
    with_stats = bn_stats and part.stat_max > 0
    branches = make_stage_branches(
        part, ctx, compute_dtype, remat and schedule == "gpipe", with_stats,
        vary_axes=(AXIS_STAGE,) + grad_axes,
        cell_remat=schedule == "1f1b" and use_1f1b_cell_remat(part),
    )
    scan_1f1b = (
        make_1f1b_scan(
            part, branches,
            vary_axes=(AXIS_STAGE,) + grad_axes,
            from_probs=from_probs, compute_dtype=compute_dtype,
            seed_scale=loss_scale, quant=quant,
        )
        if schedule == "1f1b"
        else None
    )

    def sharded_step(param_row, opt_state, x, labels):
        # param_row: [1, Pmax] local stage block; squeeze to [Pmax] (the
        # optimizer-state moment rows get the same treatment; Adam's
        # replicated scalar step counter passes through — stage_common.
        # squeeze_opt_rows).
        flat_params = param_row[0]
        opt_local = squeeze_opt_rows(opt_state)
        mb = x.shape[0] // Pn
        x_parts = x.reshape(Pn, mb, *x.shape[1:]).astype(compute_dtype)
        y_parts = labels.reshape(Pn, mb, *labels.shape[1:])

        def loss_and_metrics(flat_params):
            if schedule == "1f1b":
                with scope("pp_1f1b_scan"):
                    loss_acc, acc_acc, st_acc = scan_1f1b(
                        flat_params, x_parts, y_parts
                    )
            else:
                with scope("gpipe_scan"):
                    loss_acc, acc_acc, st_acc = gpipe_scan(
                        part, branches, flat_params, x_parts, y_parts,
                        vary_axes=(AXIS_STAGE,) + grad_axes,
                        from_probs=from_probs,
                        compute_dtype=compute_dtype,
                        quant=quant,
                    )
            # Only the last stage accumulated; psum broadcasts to all stages
            # (and sums over data-parallel groups' mean below).
            with scope("loss_reduce"):
                loss = lax.psum(loss_acc, (AXIS_STAGE,)) / Pn
                acc = lax.psum(acc_acc, (AXIS_STAGE,)) / Pn
                if grad_axes:
                    loss = lax.pmean(loss, grad_axes)
                    acc = lax.pmean(acc, grad_axes)
            return loss * loss_scale, (acc, st_acc / Pn)

        (loss, (acc, stats)), grads = jax.value_and_grad(
            loss_and_metrics, has_aux=True
        )(flat_params)
        if loss_scale != 1.0:
            grads = grads / loss_scale
            loss = loss / loss_scale
        if part.tied_slots:
            grads = sum_tied_grads(part, grads)
        if grad_axes:
            with scope("grad_reduce"):
                grads = grad_pmean(grads, grad_axes, quant)
        with scope("optimizer_update"):
            new_flat, new_opt = optimizer.update(flat_params, grads, opt_local)
        if with_stats:
            if grad_axes:
                with scope("stats_reduce"):
                    stats = grad_pmean(stats, grad_axes, quant)
            new_flat = scatter_stage_stats(part, new_flat, stats)
        return (
            new_flat[None],
            restore_opt_rows(new_opt, opt_state),
            {"loss": loss, "accuracy": acc},
        )

    pspec = P(AXIS_STAGE, None)
    ospec = stage_opt_specs(optimizer, part)
    dspec = P(AXIS_DATA) if with_data_axis else P()
    smapped = shard_map(
        sharded_step,
        mesh=mesh,
        in_specs=(pspec, ospec, dspec, dspec),
        out_specs=(pspec, ospec, P()),
    )

    # donate=True: param/opt buffers update in place (one copy, not two, of
    # the stage buffers at peak).  Off by default: exact-match tests alias
    # param arrays across states.
    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(state: PipelineState, x, labels):
        pb, opt, metrics = smapped(state.param_buf, state.opt_state, x, labels)
        return PipelineState(pb, opt, state.step + 1), metrics

    return step


def init_pipeline_state(
    part: StagePartition, params_list, optimizer: Optimizer, mesh: Mesh
) -> PipelineState:
    """Pack params into the stage-sharded buffer and init the optimizer
    stage-locally (opt state shares the buffer's sharding)."""
    buf = part.pack_params(params_list)
    sharding = NamedSharding(mesh, P(AXIS_STAGE, None))
    buf = jax.device_put(buf, sharding)
    # Moment buffers ride the stage sharding; scalar leaves (Adam's step
    # counter) are replicated — same rule as the engines' shard_map specs.
    opt_state = put_stage_opt(optimizer.init(buf), mesh)
    return PipelineState(buf, opt_state, jnp.zeros((), jnp.int32))
