"""GEMS: bidirectional ("memory-aware") model parallelism, TPU-native.

Reference behaviour (``src/torchgems/gems_master.py``,
``train_spatial_master.py``): a second weight replica is laid out on the SAME
devices with stage order reversed (rank i hosts stage S-1-i), and each step
trains batch A through the forward chain and batch B through the reversed
chain, filling the pipeline bubbles in both directions; the two replicas'
gradients are combined by a mirrored-pair allreduce (``comm.py:460-504``) or
overlapped flat-buffer exchanges (MASTER-OPT,
``train_spatial_master.py:229-455``).

TPU-native re-design (this module):

- There is ONE set of weights: the [S, Pmax] stage-sharded flat buffer.  The
  reverse replica on device d is ``mirror = ppermute(buf, stage, i→S-1-i)`` —
  one ICI permute per step instead of a second resident optimizer state +
  param exchange protocol.  (SURVEY §7.6 flags this elimination as the thing
  to explore; it also makes MASTER-OPT moot: the replicas cannot diverge.)
- Both streams run in the SAME ``lax.scan``: buffer A rotates d→d+1, buffer B
  rotates d→d-1; device d applies stage d to A and stage S-1-d to B each tick
  (two switch branches back-to-back — XLA interleaves them, which is exactly
  the bidirectional bubble-filling).
- The mirrored-pair gradient combine is *free*: batch B's loss reaches the
  true weights through the mirror ppermute, so its adjoint routes the reverse
  replica's gradients back to their home stages automatically.
- ``times`` (reference ``--times`` replication, gems_master.py:87-102)
  processes `times` A/B pairs per step, accumulating gradients, then updates
  once — 2·times micro-batch groups per optimizer step.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.parallel.partition import StagePartition
from mpi4dl_tpu.parallel.pipeline import PipelineState, grad_pmean
from mpi4dl_tpu.quant.policy import QuantPolicy
from mpi4dl_tpu.parallel.stage_common import (
    gems_dual_scan,
    make_gems_1f1b_scan,
    make_stage_branches,
    restore_opt_rows,
    scatter_stage_stats,
    squeeze_opt_rows,
    stage_opt_specs,
    use_1f1b_cell_remat,
)
from mpi4dl_tpu.train import Optimizer
from mpi4dl_tpu.mesh import AXIS_DATA, AXIS_STAGE


def make_gems_train_step(
    part: StagePartition,
    optimizer: Optimizer,
    mesh: Mesh,
    parts: int,
    times: int = 1,
    compute_dtype=jnp.float32,
    remat: bool = True,
    from_probs: bool = False,
    with_data_axis: bool = False,
    bn_stats: bool = True,
    donate: bool = False,
    schedule: str = "gpipe",
    quant: Optional[QuantPolicy] = None,
):
    """Build the GEMS step: x is [2 * times * parts * mb, H, W, C]; the first
    half of each pair flows forward, the second backward.

    ``schedule="1f1b"`` swaps the dual tick loop for its manual-backward
    1F1B counterpart (stage_common.make_gems_1f1b_scan) — the mirror streams
    keep interleaving, with both streams' cotangent ppermutes riding the
    same ticks as the activations.

    ``quant``: opt-in quantized-collective policy (docs/quantization.md);
    both streams' activation/cotangent handoffs and the DP grad/stats
    pmeans quantize — the gems_mirror ppermute does NOT (it moves
    parameters)."""
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown schedule {schedule!r}; use 'gpipe' or '1f1b'")
    S = part.num_stages
    Pn = parts
    ctx = ApplyCtx(train=True)
    mirror_perm = [(i, S - 1 - i) for i in range(S)]
    grad_axes: Tuple[str, ...] = (AXIS_DATA,) if with_data_axis else ()

    with_stats = bn_stats and part.stat_max > 0
    branches = make_stage_branches(
        part, ctx, compute_dtype, remat and schedule == "gpipe", with_stats,
        vary_axes=(AXIS_STAGE,) + grad_axes,
        cell_remat=schedule == "1f1b" and use_1f1b_cell_remat(part),
    )
    scan_1f1b = (
        make_gems_1f1b_scan(
            part, branches,
            vary_axes=(AXIS_STAGE,) + grad_axes,
            from_probs=from_probs, compute_dtype=compute_dtype,
            quant=quant,
        )
        if schedule == "1f1b"
        else None
    )

    def sharded_step(param_row, opt_state, x, labels):
        flat_params = param_row[0]
        # Stage-sharded opt rows squeeze like the param row; replicated
        # scalar leaves pass through (see pipeline.py).
        opt_local = squeeze_opt_rows(opt_state)
        groups = 2 * times
        mb = x.shape[0] // (groups * Pn)
        # [times, 2, parts, mb, ...]
        xs = x.reshape(times, 2, Pn, mb, *x.shape[1:]).astype(compute_dtype)
        ys = labels.reshape(times, 2, Pn, mb)

        def loss_and_metrics(flat_params):
            # The reverse replica's params: device d gets stage S-1-d's row.
            with scope("gems_mirror"):
                mirror_params = lax.ppermute(
                    flat_params, AXIS_STAGE, mirror_perm
                )
            if schedule == "1f1b":
                with scope("gems_1f1b_scan"):
                    loss_acc, acc_acc, stA, stB = scan_1f1b(
                        flat_params, mirror_params, xs, ys
                    )
            else:
                with scope("gems_dual_scan"):
                    loss_acc, acc_acc, stA, stB = gems_dual_scan(
                        part, branches, flat_params, mirror_params, xs, ys,
                        vary_axes=(AXIS_STAGE,) + grad_axes,
                        from_probs=from_probs,
                        compute_dtype=compute_dtype,
                        quant=quant,
                    )
            denom = 2 * times * Pn
            with scope("loss_reduce"):
                loss = lax.psum(loss_acc, (AXIS_STAGE,)) / denom
                acc = lax.psum(acc_acc, (AXIS_STAGE,)) / denom
                if grad_axes:
                    loss = lax.pmean(loss, grad_axes)
                    acc = lax.pmean(acc, grad_axes)
            # Stream B's stats belong to stage S-1-d: route them home via the
            # mirror permute, then average over all 2*times*Pn deposits (each
            # stream contributed times*Pn).
            with scope("stats_mirror"):
                stats = (stA + lax.ppermute(stB, AXIS_STAGE, mirror_perm)) / denom
            return loss, (acc, stats)

        (loss, (acc, stats)), grads = jax.value_and_grad(
            loss_and_metrics, has_aux=True
        )(flat_params)
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

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(state: PipelineState, x, labels):
        pb, opt, metrics = smapped(state.param_buf, state.opt_state, x, labels)
        return PipelineState(pb, opt, state.step + 1), metrics

    return step
