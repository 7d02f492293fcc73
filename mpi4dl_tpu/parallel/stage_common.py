"""Machinery shared by the pipeline / GEMS / SP+PP engines.

Stage branches must be PURE COMPUTE: a collective (ppermute/psum) inside a
``lax.switch`` branch selected by ``axis_index`` deadlocks, because XLA lowers
a shard_map collective to ONE instruction whose rendezvous spans every device
on the axis — devices in other branches never arrive (verified empirically on
the CPU backend; the TPU lowering has the same cross-module semantics).  All
collectives — stage handoffs, halo exchanges, junction gathers — therefore
live at the schedule level, uniformly executed by every device.  This is the
structural reason the SP region runs as a separate uniform phase in
``sp_pipeline.py`` rather than inside stage-0's branch.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.compat import pcast

from mpi4dl_tpu.cells import checkpointed_apply
from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.parallel.partition import StagePartition, lax_slice, pad_to
from mpi4dl_tpu.quant.collectives import quantized_ppermute
from mpi4dl_tpu.quant.policy import QuantPolicy
from mpi4dl_tpu.train import accuracy, cross_entropy
from mpi4dl_tpu.mesh import AXIS_STAGE


def _handoff(y, perm, quant: Optional[QuantPolicy]):  # analysis: ok(unscoped-collective) — every caller wraps in scope("stage_handoff"/"cot_handoff")
    """One stage-handoff/cotangent ppermute, quantized when the policy's
    ``handoff`` class is on (per-block payload over the flat [act_max]
    buffer; quant/collectives.py).  The GEMS mirror ppermute must NOT go
    through here — it moves parameters, which are never quantized."""
    mode = quant.mode("handoff") if quant is not None else None
    if mode:
        return quantized_ppermute(y, AXIS_STAGE, perm, mode, quant.block)
    return lax.ppermute(y, AXIS_STAGE, perm)


def make_stage_branches(
    part: StagePartition,
    ctx: ApplyCtx,
    compute_dtype,
    remat: bool,
    with_stats: bool = False,
    vary_axes: Tuple[str, ...] = (),
    cell_remat: bool = False,
) -> List[Callable]:
    """One pure-compute branch per stage: unpack flat activation → run the
    stage's cells → pack/pad the output activation (reference per-rank
    sub-model forward, mp_pipeline.py:434-473).

    Every branch returns ``(act_out [act_max], stats [stat_max])`` — the
    second element carries the stage's UPDATED BN running stats (fp32, in the
    stage packing's slot order, zero-padded) when ``with_stats``; callers mask
    out bubble-tick garbage and scatter the average back into the stage's
    flat param row.  stat_max may be 0 (no BN / stats disabled).

    ``vary_axes``: mesh axes the engine's activations vary over.  A stage
    with NO stat leaves returns constant zeros for its stats slot, which
    lax.switch rejects against sibling branches whose (activation-derived)
    stats vary over those axes — the zeros are pcast to match.

    ``remat`` wraps the WHOLE branch in jax.checkpoint — what the GPipe
    grad-of-scan needs so AD saves only tick carries.  ``cell_remat``
    instead threads the stage body through per-cell ``checkpointed_apply``
    (CellModel.apply remat=True): a vjp of the branch then stores only cell
    boundaries and recomputes one cell at a time — the within-tick policy
    of the 1F1B manual backward, where a whole-branch checkpoint would be
    useless (its backward holds every stage-internal activation at once).
    The two are mutually exclusive by construction here."""
    stat_n = part.stat_max if with_stats else 0

    def stage_branch(s: int):
        pk_in = part.act_packs[s]
        out_pk = part.act_packs[s + 1] if s + 1 < part.num_stages else part.out_pack
        pkp = part.param_packs[s]
        r0, r1 = part.ranges[s]

        def fn(flat_params, buf):
            # The whole branch body rides the stage scope — the act/param
            # unpack and the output pack/pad allocate stage-owned buffers
            # (XLA hoists the loop-invariant parts of the tick switch out of
            # the scan; without the scope those hoisted temps show up
            # unattributed in the obs/hbm.py breakdown).
            with scope(f"stage{s}"):
                act = pk_in.unpack(
                    lax_slice(buf, 0, pk_in.total), dtype=compute_dtype
                )
                params = pkp.unpack(lax_slice(flat_params, 0, pkp.total))
                if stat_n:
                    sink: dict = {}
                    c = dataclasses.replace(ctx, bn_sink=sink)
                else:
                    sink, c = None, ctx
                y = act
                for i in range(r0, r1):
                    with scope(f"cell{i:02d}"):
                        if cell_remat:
                            y = checkpointed_apply(
                                part.model.cells[i].apply, params[i - r0], y, c
                            )
                        else:
                            y = part.model.cells[i].apply(params[i - r0], y, c)
                out = pad_to(out_pk.pack(y, compute_dtype), part.act_max)
                if not stat_n:
                    return out, jnp.zeros((0,), jnp.float32)
                leaves = jax.tree.leaves(params)
                vals = [
                    sink.get(id(leaves[i]), leaves[i])
                    for i in part.stat_leaf_ids[s]
                ]
                if vals:
                    svec = pad_to(
                        jnp.concatenate(
                            [jnp.ravel(v).astype(jnp.float32) for v in vals]
                        ),
                        stat_n,
                    )
                else:
                    svec = jnp.zeros((stat_n,), jnp.float32)
                    if vary_axes:
                        svec = pcast(svec, tuple(vary_axes), to="varying")
                return out, svec

        return jax.checkpoint(fn) if remat else fn

    return [stage_branch(s) for s in range(part.num_stages)]


def gpipe_scan(
    part: StagePartition,
    branches: List[Callable],
    flat_params: jax.Array,
    x_parts: jax.Array,
    y_parts: jax.Array,
    *,
    vary_axes: Tuple[str, ...],
    from_probs: bool,
    compute_dtype,
    quant: Optional[QuantPolicy] = None,
):
    """The GPipe tick loop (reference run_step, mp_pipeline.py:509-534).

    x_parts: [Pn, mb, ...] micro-batch inputs of stage 0 (device-local);
    y_parts: [Pn, mb] labels.  Returns (loss_acc, acc_acc, stats_acc):
    loss/acc accumulated ONLY on the last stage's devices over the Pn drained
    parts — callers psum over 'stage' and normalise; stats_acc is the sum of
    the stage's BN running-stat updates over its Pn VALID compute ticks
    (bubble ticks masked out) — callers divide by Pn and scatter into the
    stage param row.  T = Pn + S - 1 ticks; activations advance one stage per
    tick via a non-wrapping ppermute; the backward pass is the AD transpose of
    this scan (all-forwards-then-all-backwards falls out).
    """
    S = part.num_stages
    lead = jax.tree.leaves(x_parts)[0]
    Pn, mb = lead.shape[0], lead.shape[1]
    T = Pn + S - 1
    s_idx = lax.axis_index(AXIS_STAGE)
    is_last = s_idx == S - 1
    in_pack0 = part.act_packs[0]
    logits_n = part.out_pack.total
    amax = part.act_max
    stat_n = branches_stat_n(branches, part)

    def tick(carry, t):
        buf, loss_acc, acc_acc, st_acc = carry
        with scope("mb_inject"):
            p_in = jnp.clip(t, 0, Pn - 1)
            xp = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, p_in, keepdims=False),
                x_parts,
            )
            inj = pad_to(in_pack0.pack(xp, compute_dtype), amax)
            buf = jnp.where(s_idx == 0, inj, buf)
        y, st = lax.switch(s_idx, branches, flat_params, buf)
        # Stage s computes part p = t - s; stats only count on valid ticks.
        st_valid = (t >= s_idx) & (t - s_idx < Pn)
        st_acc = st_acc + jnp.where(st_valid, st, 0.0)
        # Last stage: loss for part p = t - (S-1) when in range.
        p_out = t - (S - 1)
        valid = (p_out >= 0) & (p_out < Pn) & is_last
        logits = lax_slice(y, 0, logits_n).reshape(part.out_pack.shapes[0])
        lbl = lax.dynamic_index_in_dim(
            y_parts, jnp.clip(p_out, 0, Pn - 1), keepdims=False
        )
        l = cross_entropy(logits, lbl, from_probs)
        a = accuracy(logits, lbl)
        loss_acc = loss_acc + jnp.where(valid, l, 0.0)
        acc_acc = acc_acc + jnp.where(valid, a, 0.0)
        # Hand activations to the next stage (non-wrap: stage 0's stale recv
        # is overwritten by injection next tick).
        with scope("stage_handoff"):
            buf = _handoff(y, [(i, i + 1) for i in range(S - 1)], quant)
        return (buf, loss_acc, acc_acc, st_acc), None

    # Initial carries must be marked varying over the axes the loop makes
    # them vary on, or shard_map's AD produces wrong collective transposes
    # (grads scaled by axis size).
    def v(t):
        return pcast(t, vary_axes, to="varying")

    buf0 = v(jnp.zeros((amax,), compute_dtype))
    st0 = v(jnp.zeros((stat_n,), jnp.float32))
    (_, loss_acc, acc_acc, stats_acc), _ = lax.scan(
        tick, (buf0, v(jnp.zeros((), jnp.float32)), v(jnp.zeros((), jnp.float32)), st0), jnp.arange(T, dtype=jnp.int32)
    )
    return loss_acc, acc_acc, stats_acc


def scatter_stage_stats(part: StagePartition, flat: jax.Array, stats: jax.Array):
    """Scatter averaged BN running-stat values into this device's stage param
    row.  ``stats`` is the [stat_max] vector in the stage's slot order (from
    gpipe_scan / gems_dual_scan, already divided by the part count); positions
    come from the -1-padded part.stat_idx table indexed by the device's stage.
    Padded entries resolve to a masked add of 0 at position 0, so the scatter
    is uniform across heterogeneous stages."""
    if part.stat_idx is None:
        return flat
    idx_all = jnp.asarray(part.stat_idx)  # [S, stat_max]
    row = lax.dynamic_index_in_dim(idx_all, lax.axis_index(AXIS_STAGE), keepdims=False)
    mask = row >= 0
    safe = jnp.where(mask, row, 0)
    cur = flat[safe]
    return flat.at[safe].add(jnp.where(mask, stats.astype(flat.dtype) - cur, 0.0))


def branches_stat_n(branches, part: StagePartition) -> int:
    """Static stats-vector length the branches were built with (0 or
    part.stat_max — probed abstractly so callers stay in sync)."""
    out = jax.eval_shape(
        branches[0],
        jax.ShapeDtypeStruct((part.param_max,), jnp.float32),
        jax.ShapeDtypeStruct((part.act_max,), jnp.float32),
    )
    return int(out[1].shape[0])


# ---------------------------------------------------------------------------
# 1F1B: one-forward-one-backward schedule, manual schedule-level backward
# ---------------------------------------------------------------------------


def stage_opt_specs(optimizer, part: StagePartition):
    """PartitionSpec pytree for an optimizer state over the [S, Pmax] stage
    buffer: moment buffers (rank >= 2, one row per stage) ride the stage
    sharding; scalar leaves (Adam's step counter) are replicated.  Derived
    from ``optimizer.init`` on a width-1 CONCRETE probe row buffer — the
    rule depends only on the state tree's structure and leaf ranks, and a
    concrete probe (unlike ``jax.eval_shape``) costs the engine build no
    counted trace, keeping it out of the contract gate's retrace budget —
    so the engines' shard_map in/out specs and the init-time device_put
    agree on a single rule."""
    from jax.sharding import PartitionSpec as P

    probe = optimizer.init(jnp.zeros((part.num_stages, 1), part.param_dtype))
    return jax.tree.map(
        lambda s: P(AXIS_STAGE, None) if s.ndim >= 2 else P(), probe
    )


def squeeze_opt_rows(opt_state):
    """Per-device view of a stage-sharded optimizer state: [1, Pmax] moment
    rows squeeze to [Pmax] (like the param row); replicated scalar leaves
    (Adam's step counter) pass through.  Stateful optimizers silently broke
    on the un-squeezed broadcast before this existed (caught by the donate
    exact-match test)."""
    return jax.tree.map(lambda z_: z_[0] if z_.ndim >= 2 else z_, opt_state)


def restore_opt_rows(new_opt, opt_in):
    """Inverse of :func:`squeeze_opt_rows` after the update (leaf-wise,
    keyed on the INPUT leaf's rank — the updated moment is rank 1)."""
    return jax.tree.map(
        lambda n_, o_: n_[None] if o_.ndim >= 2 else n_, new_opt, opt_in
    )


def put_stage_opt(opt_state, mesh):
    """Device-placement mirroring :func:`stage_opt_specs`: rank >= 2 leaves
    stage-sharded, scalars replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    row = NamedSharding(mesh, P(AXIS_STAGE, None))
    rep = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda z_: jax.device_put(z_, row if z_.ndim >= 2 else rep), opt_state
    )


def use_1f1b_cell_remat(part: StagePartition) -> bool:
    """Auto policy for per-cell checkpoints inside the 1F1B backward
    branches (``MPI4DL_1F1B_CELL_REMAT`` overrides: 1/0 force on/off).

    Measured on the virtual mesh (docs/pipeline.md): for SHORT stages
    (<= 3 cells) inner cell checkpoints let the outer transpose free one
    cell's recompute scratch before the next is born — roughly a stage
    working set saved.  For longer stages the effect inverts
    catastrophically (several-fold peak regressions): XLA schedules the
    per-cell backward recomputes concurrently, so every cell's scratch is
    live at once ON TOP of the saved cell boundaries."""
    import os

    v = os.environ.get("MPI4DL_1F1B_CELL_REMAT", "")
    if v in ("0", "1"):
        return v == "1"
    return max(r1 - r0 for r0, r1 in part.ranges) <= 3


def resid_depth(num_stages: int) -> int:
    """Rotating residual-buffer depth of the 1F1B schedule.

    Stage s holds a part's stage-input activation from its forward tick
    (t = p + s) to its backward tick (t = p + 2(S-1) - s): 2(S-1-s) ring
    entries in flight, at most 2(S-1) at stage 0 (the current tick's part
    is NOT counted — every tick reads its backward slot before writing its
    forward slot, and the last stage — whose forward and backward share a
    tick — reads the live ``buf`` directly, never the ring).  One uniform
    depth keeps the buffer SPMD (every device carries the same shape); the
    key property is that it is O(stages), independent of the micro-batch
    count — GPipe as grad-of-scan keeps O(parts + stages) tick carries live
    instead."""
    return max(1, 2 * (num_stages - 1))


def ring_store(resid, valid, slot, row):
    """Masked write of ``row`` into slot ``slot`` of the rotating residual
    ring: a no-op on bubble ticks (``valid`` false) so drain-phase garbage
    never clobbers a live residual.  Shared by the single- and dual-stream
    1F1B builders — both rely on reads preceding this write (the ring depth
    is exactly the stage-0 round trip; see :func:`resid_depth`)."""
    old = lax.dynamic_index_in_dim(resid, slot, keepdims=False)
    return lax.dynamic_update_index_in_dim(
        resid, jnp.where(valid, row, old), slot, 0
    )


def scatter_part_row(G, g, slot, mask):
    """Masked write of one micro-batch part's cotangent ``g`` into row
    ``slot`` of the per-part buffer ``G`` (the grad_x injection transpose:
    each part's row is written exactly once, on its backward tick at the
    injecting stage)."""
    old = lax.dynamic_index_in_dim(G, slot, keepdims=False)
    new = jnp.where(mask, g.astype(G.dtype), old)
    return lax.dynamic_update_index_in_dim(G, new, slot, 0)


def _vary_like(ct, primal):
    """``ct`` with the varying-axes type of the primal output it answers:
    typed shard_map checks a VJP's cotangents against its outputs' types."""
    missing = sorted(jax.typeof(primal).vma - jax.typeof(ct).vma)
    return pcast(ct, tuple(missing), to="varying") if missing else ct


def _make_fb_branches(
    branches: List[Callable],
    *,
    logits_n: int,
    nclass: int,
    stat_n: int,
    from_probs: bool,
    seed_scale: float,
    compute_dtype,
) -> List[Callable]:
    """Per-stage combined forward+manual-transpose branches: pure compute,
    one uniform signature ``(flat_params, buf, a_in, cot_in, lbl, valid_out)
    -> (y, st, loss, acc, cot_a_in, grad_params)``.

    One tick = one switch: the forward micro-batch (``buf``) and the
    backward micro-batch (``jax.vjp`` of the same stage at the STORED input
    ``a_in`` — recompute-and-transpose, the same per-tick work GPipe's AD
    does under per-branch ``jax.checkpoint``) share a single branch body.
    Fusing them matters for memory, not just tidiness: two separate
    ``lax.switch`` calls per tick lower to two HLO conditionals whose
    internals get disjoint buffer regions, doubling the per-tick stage
    working set; one branch body lets buffer assignment reuse the forward's
    scratch for the transpose.  The stage index is STATIC inside each
    branch, so the last stage seeds its own backward from this tick's
    logits (1F1B: a part's last-stage forward and backward share a tick)
    while every other stage consumes the cotangent handed down by the
    reverse ppermute.  Callers must pass branches built with
    ``remat=False`` — the transpose half wraps its own ``jax.checkpoint``
    below, and a second wrapper would nest checkpoints for no benefit
    (``cell_remat`` is the supported inner policy, see
    ``use_1f1b_cell_remat``).  Stats get a zero cotangent (running-stat
    deposits are not differentiated, matching the GPipe engines' has_aux
    treatment).  Collectives stay at schedule level (lax.switch deadlock
    rule, module docstring)."""
    S = len(branches)

    def part_loss(yvec, lbl):
        logits = lax_slice(yvec, 0, logits_n).reshape(-1, nclass)
        return cross_entropy(logits, lbl, from_probs)

    def fb_branch(s: int) -> Callable:
        fwd = branches[s]
        seeds_self = s == S - 1

        def fn(flat_params, buf, a_in, cot_y, lbl, valid_out):
            y, st = fwd(flat_params, buf)
            l, ce_vjp = jax.vjp(lambda yv: part_loss(yv, lbl), y)
            logits = lax_slice(y, 0, logits_n).reshape(-1, nclass)
            a = accuracy(logits, lbl)
            if seeds_self:
                # 1F1B: a part's last-stage forward and backward share a
                # tick, so the self-seeding branch backwards THIS tick's
                # micro-batch — its stage input is the live ``buf``, not a
                # ring entry (statically selected: no where-materialised
                # extra activation buffer).
                (seed,) = ce_vjp(
                    _vary_like(jnp.asarray(seed_scale, jnp.float32), l))
                cot_y = jnp.where(valid_out, seed, 0.0).astype(compute_dtype)
                a_in = buf
            # Sequence the backward after the forward: without the barrier
            # XLA's scheduler is free to interleave the two micro-batches'
            # stage bodies, which makes their scratch buffers live
            # simultaneously — the peak then carries TWO stage working sets
            # and the schedule's whole memory win evaporates.  The barrier
            # pins "forward scratch dies before transpose scratch is born".
            y, st, l, a, a_in, cot_y = lax.optimization_barrier(
                (y, st, l, a, a_in, cot_y)
            )
            # vjp through jax.checkpoint with the primal outputs UNUSED: the
            # primal pass is dead code, so what remains is exactly the
            # recompute-then-transpose body GPipe's AD emits per tick —
            # same structure, same per-tick working set, no stored
            # linearization residuals (a plain jax.vjp would materialize
            # every transpose operand during the forward sweep and hold it
            # across the whole stage body).
            (y_r, st_r), vjp = jax.vjp(jax.checkpoint(fwd), flat_params, a_in)
            gp, ga = vjp((
                _vary_like(cot_y, y_r),
                _vary_like(jnp.zeros((stat_n,), jnp.float32), st_r),
            ))
            return y, st, l, a, ga, gp

        return fn

    return [fb_branch(s) for s in range(S)]


def _wrap_schedule_vjp(run, *, n_params: int, n_outs: int, seed_scale: float,
                       grad_x: bool):
    """Shared ``jax.custom_vjp`` scaffolding of the 1F1B scan builders.

    ``run(*params, x, y)`` is the interleaved tick loop: it returns
    ``n_outs`` metric outputs followed by ``n_params`` accumulated parameter
    gradients and the injection cotangent ``gx``.  The wrapper's forward
    stashes the gradients as residuals; its backward just scales them by the
    incoming loss cotangent (a replicated scalar, so scaling commutes with
    every collective already baked into the accumulation) and undoes
    ``seed_scale``.  Only the loss (first output) is transposed — the rest
    are aux metrics whose (zero) cotangents are ignored.

    Shapes of x/y are recorded at fwd-trace time (static), so the bwd rule
    can fabricate its zero cotangents without the fwd pass materialising
    (and the scan carrying) batch-sized zero residuals.  ``grad_x=False``
    therefore means "x is not a differentiation target": an engine that did
    differentiate x with it off would silently get zeros.  Labels are
    integers and get float0 cotangents."""
    import numpy as np

    structs: dict = {}

    @jax.custom_vjp
    def scan_sched(*args):
        return run(*args)[:n_outs]

    def scan_fwd(*args):
        out = run(*args)
        x, y = args[n_params], args[n_params + 1]
        structs["x"] = (
            [(l.shape, jnp.result_type(l)) for l in jax.tree.leaves(x)],
            jax.tree.structure(x),
        )
        structs["y"] = (
            [l.shape for l in jax.tree.leaves(y)],
            jax.tree.structure(y),
        )
        return out[:n_outs], out[n_outs:]

    def scan_bwd(res, cots):
        *gps, gx = res
        dloss = (cots[0] / seed_scale).astype(jnp.float32)

        def scale(g):
            return (g.astype(jnp.float32) * dloss).astype(g.dtype)

        if grad_x:
            gx_cot = jax.tree.map(scale, gx)
        else:
            xs, xdef = structs["x"]
            gx_cot = jax.tree.unflatten(
                xdef, [jnp.zeros(s, d) for s, d in xs]
            )
        ys, ydef = structs["y"]
        y_cot = jax.tree.unflatten(
            ydef, [np.zeros(s, jax.dtypes.float0) for s in ys]
        )
        return (*(scale(g) for g in gps), gx_cot, y_cot)

    scan_sched.defvjp(scan_fwd, scan_bwd)
    return scan_sched


def make_1f1b_scan(
    part: StagePartition,
    branches: List[Callable],
    *,
    vary_axes: Tuple[str, ...],
    from_probs: bool,
    compute_dtype,
    seed_scale: float = 1.0,
    grad_x: bool = False,
    quant: Optional[QuantPolicy] = None,
):
    """Build the 1F1B tick loop as a ``jax.custom_vjp`` drop-in for
    :func:`gpipe_scan`: ``f(flat_params, x_parts, y_parts) -> (loss_acc,
    acc_acc, st_acc)`` with the same output semantics (loss/acc accumulated
    on the last stage over the Pn drained parts, stats summed over valid
    forward ticks).

    Why this cannot be ``jax.grad`` of a scan: AD transposes the tick loop
    by replaying ticks in REVERSE — all-forwards-then-all-backwards, which
    *is* GPipe, and it must keep every tick's carry live for the replay
    (O(parts) stage-boundary activations).  Here the backward is part of the
    schedule itself: each tick runs one forward micro-batch AND one backward
    micro-batch (stage s forwards part t-s and backwards part t-2(S-1)+s),
    with the activation ppermute and the reverse cotangent ppermute in the
    same tick.  The scan carries a depth-``resid_depth(S)`` rotating
    residual buffer (stage INPUTS only; the stage body is recomputed inside
    the backward branch) plus one cotangent buffer — O(stages) live
    activations — and accumulates parameter gradients into the flat stage
    row in-scan.  T = Pn + 2(S-1) ticks fill and drain both directions.

    The ``custom_vjp`` wrapper is what lets the engines keep their
    ``jax.value_and_grad(loss_and_metrics)`` structure unchanged: the
    forward pass runs the interleaved loop and stashes the accumulated
    gradients as residuals; the backward rule just scales them by the
    incoming loss cotangent (a replicated scalar, so scaling commutes with
    every collective already baked into the accumulation — psum/pmean
    normalisation and loss-scale transposes stay in AD-land).  Only the
    loss output is transposed; acc/stats are aux metrics and their (zero)
    cotangents are ignored.

    ``seed_scale``: multiplies the in-scan loss-cotangent seed (and divides
    it back out in the vjp rule) so bf16 cotangents inside the scan enjoy
    the same underflow protection as the engines' ``loss_scale``.
    ``grad_x``: also accumulate the cotangent w.r.t. ``x_parts`` (stage-0
    backward, injection transpose) — required when the injections are
    produced by a differentiated phase (the SP region of sp_pipeline);
    engines whose inputs are raw batches leave it off and get a zeros
    cotangent.  Labels are integers and get float0 cotangents."""
    S = part.num_stages
    D = resid_depth(S)
    in_pack0 = part.act_packs[0]
    logits_n = part.out_pack.total
    nclass = part.out_pack.shapes[0][-1]
    amax = part.act_max
    stat_n = branches_stat_n(branches, part)
    fb_branches = _make_fb_branches(
        branches, logits_n=logits_n, nclass=nclass, stat_n=stat_n,
        from_probs=from_probs, seed_scale=seed_scale,
        compute_dtype=compute_dtype,
    )
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    rev_perm = [(i + 1, i) for i in range(S - 1)]

    def v(t):
        return pcast(t, tuple(vary_axes), to="varying")

    def run(flat_params, x_parts, y_parts):
        lead = jax.tree.leaves(x_parts)[0]
        Pn = lead.shape[0]
        T = Pn + 2 * (S - 1)
        s_idx = lax.axis_index(AXIS_STAGE)
        is_last = s_idx == S - 1
        is_first = s_idx == 0

        def tick(carry, t):
            buf, cot, resid, gacc, gx, loss_acc, acc_acc, st_acc = carry
            with scope("fwd_tick"):
                with scope("mb_inject"):
                    p_in = jnp.clip(t, 0, Pn - 1)
                    xp = jax.tree.map(
                        lambda a: lax.dynamic_index_in_dim(
                            a, p_in, keepdims=False
                        ),
                        x_parts,
                    )
                    inj = pad_to(in_pack0.pack(xp, compute_dtype), amax)
                    buf = jnp.where(is_first, inj, buf)
            # Backward operands FIRST: stage s backwards part t - 2(S-1) + s
            # (the seed enters at the last stage — same tick as that part's
            # forward there — and descends one stage per tick).  The read
            # precedes this tick's ring write, which is what lets the ring
            # be exactly 2(S-1) deep: stage 0's read and write land on the
            # SAME slot (its round trip equals the ring size) and the last
            # stage takes the live ``buf`` instead of touching the ring.
            p_b = t - 2 * (S - 1) + s_idx
            valid_b = (p_b >= 0) & (p_b < Pn)
            slot_r = jnp.clip(p_b, 0, Pn - 1) % D
            # The self-seeding branch statically ignores a_in (it backwards
            # the live buf); every other stage reads its ring slot.
            a_in = lax.dynamic_index_in_dim(resid, slot_r, keepdims=False)
            with scope("fwd_tick"):
                # Rotate this tick's stage input into the residual ring
                # (slot p mod D; the draining last stage backwards its live
                # buf instead and never touches the ring).
                p_f = t - s_idx
                valid_f = (p_f >= 0) & (p_f < Pn)
                resid = ring_store(
                    resid, valid_f & (~is_last),
                    jnp.clip(p_f, 0, Pn - 1) % D, buf,
                )
            p_out = t - (S - 1)
            valid_out = (p_out >= 0) & (p_out < Pn)
            lbl = lax.dynamic_index_in_dim(
                y_parts, jnp.clip(p_out, 0, Pn - 1), keepdims=False
            )
            # ONE switch runs this tick's forward AND backward micro-batch
            # (see _make_fb_branches for why the fusion matters).
            y, st, l, a, ga, gp = lax.switch(
                s_idx, fb_branches, flat_params, buf, a_in,
                cot.astype(compute_dtype), lbl, valid_out,
            )
            st_acc = st_acc + jnp.where(valid_f, st, 0.0)
            out_here = valid_out & is_last
            loss_acc = loss_acc + jnp.where(out_here, l, 0.0)
            acc_acc = acc_acc + jnp.where(out_here, a, 0.0)
            with scope("fwd_tick"), scope("stage_handoff"):
                nbuf = (
                    _handoff(y, fwd_perm, quant)
                    if fwd_perm
                    else jnp.zeros_like(y)
                )
            with scope("bwd_tick"):
                gacc = gacc + jnp.where(valid_b, gp, jnp.zeros_like(gp))
                if grad_x:
                    # Injection transpose: stage 0's input cotangent belongs
                    # to part p_b of x_parts (written exactly once per part).
                    gxa = in_pack0.unpack(
                        lax_slice(ga, 0, in_pack0.total), dtype=compute_dtype
                    )
                    slot_x = jnp.clip(p_b, 0, Pn - 1)
                    gx = jax.tree.map(
                        lambda G, g: scatter_part_row(
                            G, g, slot_x, valid_b & is_first
                        ),
                        gx, gxa,
                    )
                with scope("cot_handoff"):
                    cot = (
                        _handoff(ga, rev_perm, quant)
                        if rev_perm
                        else jnp.zeros_like(ga)
                    )
            return (nbuf, cot, resid, gacc, gx, loss_acc, acc_acc, st_acc), None

        z = jnp.zeros
        # scope: the zero ring/cotangent/accumulator inits get sunk into the
        # per-stage dispatch conditional by XLA — name them so the obs/hbm.py
        # breakdown attributes the ring slots instead of dropping them.
        with scope("schedule_init"):
            gx0 = (
                jax.tree.map(
                    lambda a_: v(z(a_.shape, compute_dtype)), x_parts
                )
                if grad_x
                else ()
            )
            init = (
                v(z((amax,), compute_dtype)),
                v(z((amax,), compute_dtype)),
                v(z((D, amax), compute_dtype)),
                v(z(flat_params.shape, flat_params.dtype)),
                gx0,
                v(z((), jnp.float32)),
                v(z((), jnp.float32)),
                v(z((stat_n,), jnp.float32)),
            )
        (_, _, _, gacc, gx, loss_acc, acc_acc, st_acc), _ = lax.scan(
            tick, init, jnp.arange(T, dtype=jnp.int32)
        )
        return loss_acc, acc_acc, st_acc, gacc, gx

    return _wrap_schedule_vjp(
        run, n_params=1, n_outs=3, seed_scale=seed_scale, grad_x=grad_x
    )


def gems_dual_scan(
    part: StagePartition,
    branches: List[Callable],
    flat_params: jax.Array,
    mirror_params: jax.Array,
    x_groups,
    y_groups: jax.Array,
    *,
    vary_axes: Tuple[str, ...],
    from_probs: bool,
    compute_dtype,
    quant: Optional[QuantPolicy] = None,
):
    """The GEMS bidirectional tick loop (reference gems_master.py:72-103).

    x_groups: pytree with leaves [times, 2, Pn, mb, ...]; y_groups
    [times, 2, Pn, mb].  Stream A of each pair flows stage 0→S-1 with the true
    params; stream B flows S-1→0 against ``mirror_params`` (device d holding
    stage S-1-d's row via the mirror ppermute) — the two switch branches per
    tick are what XLA interleaves into bidirectional bubble-filling.  Returns
    (loss_acc, acc_acc, statsA_acc, statsB_acc): loss/acc accumulated on the
    boundary stages over all 2·times·Pn drained parts (callers psum over
    'stage' and normalise); statsA_acc holds device d's stage-d BN stat
    updates from the forward stream, statsB_acc its stage-(S-1-d) updates from
    the reverse stream — callers mirror-ppermute B, average, and scatter.
    """
    S = part.num_stages
    lead = jax.tree.leaves(x_groups)[0]
    times, Pn, mb = lead.shape[0], lead.shape[2], lead.shape[3]
    T = Pn + S - 1
    d = lax.axis_index(AXIS_STAGE)
    in_pack0 = part.act_packs[0]
    logits_n = part.out_pack.total
    nclass = part.out_pack.shapes[0][-1]
    amax = part.act_max
    stat_n = branches_stat_n(branches, part)
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    bwd_perm = [(i + 1, i) for i in range(S - 1)]

    def v(t):
        return pcast(t, vary_axes, to="varying")

    def one_pair(carry, pair):
        loss_in, acc_in, stA_in, stB_in = carry
        xp, yp = pair  # leaves [2, Pn, mb, ...], [2, Pn, mb]

        def sel(tree, j, p):
            return jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(
                    a[j], p, keepdims=False
                ),
                tree,
            )

        def tick(c, t):
            bufA, bufB, l_acc, a_acc, stA, stB = c
            p_in = jnp.clip(t, 0, Pn - 1)
            injA = pad_to(in_pack0.pack(sel(xp, 0, p_in), compute_dtype), amax)
            injB = pad_to(in_pack0.pack(sel(xp, 1, p_in), compute_dtype), amax)
            bufA = jnp.where(d == 0, injA, bufA)
            bufB = jnp.where(d == S - 1, injB, bufB)
            yA, sA = lax.switch(d, branches, flat_params, bufA)
            yB, sB = lax.switch(S - 1 - d, branches, mirror_params, bufB)
            # Stream A: device d runs stage d on part t-d; stream B: device d
            # runs stage S-1-d, which part p enters at tick p+(S-1-d)... i.e.
            # processes part t-(S-1-d).
            vA = (t >= d) & (t - d < Pn)
            vB = (t >= (S - 1 - d)) & (t - (S - 1 - d) < Pn)
            stA = stA + jnp.where(vA, sA, 0.0)
            stB = stB + jnp.where(vB, sB, 0.0)
            p_out = t - (S - 1)
            in_range = (p_out >= 0) & (p_out < Pn)
            p_sel = jnp.clip(p_out, 0, Pn - 1)
            lblA = lax.dynamic_index_in_dim(yp[0], p_sel, keepdims=False)
            lblB = lax.dynamic_index_in_dim(yp[1], p_sel, keepdims=False)
            logitsA = lax_slice(yA, 0, logits_n).reshape(mb, nclass)
            logitsB = lax_slice(yB, 0, logits_n).reshape(mb, nclass)
            validA = in_range & (d == S - 1)
            validB = in_range & (d == 0)
            l_acc = (
                l_acc
                + jnp.where(validA, cross_entropy(logitsA, lblA, from_probs), 0.0)
                + jnp.where(validB, cross_entropy(logitsB, lblB, from_probs), 0.0)
            )
            a_acc = (
                a_acc
                + jnp.where(validA, accuracy(logitsA, lblA), 0.0)
                + jnp.where(validB, accuracy(logitsB, lblB), 0.0)
            )
            with scope("stage_handoff"):
                bufA = _handoff(yA, fwd_perm, quant)
                bufB = _handoff(yB, bwd_perm, quant)
            return (bufA, bufB, l_acc, a_acc, stA, stB), None

        init = (
            v(jnp.zeros((amax,), compute_dtype)),
            v(jnp.zeros((amax,), compute_dtype)),
            v(jnp.zeros((), jnp.float32)),
            v(jnp.zeros((), jnp.float32)),
            stA_in,
            stB_in,
        )
        (_, _, l_acc, a_acc, stA, stB), _ = lax.scan(tick, init, jnp.arange(T, dtype=jnp.int32))
        return (loss_in + l_acc, acc_in + a_acc, stA, stB), None

    st0 = v(jnp.zeros((stat_n,), jnp.float32))
    (loss_acc, acc_acc, stA_acc, stB_acc), _ = lax.scan(
        one_pair,
        (v(jnp.zeros((), jnp.float32)), v(jnp.zeros((), jnp.float32)), st0, v(jnp.zeros((stat_n,), jnp.float32))),
        (x_groups, y_groups),
    )
    return loss_acc, acc_acc, stA_acc, stB_acc


def make_gems_1f1b_scan(
    part: StagePartition,
    branches: List[Callable],
    *,
    vary_axes: Tuple[str, ...],
    from_probs: bool,
    compute_dtype,
    seed_scale: float = 1.0,
    grad_x: bool = False,
    quant: Optional[QuantPolicy] = None,
):
    """1F1B counterpart of :func:`gems_dual_scan` (see :func:`make_1f1b_scan`
    for the schedule/custom_vjp design): ``f(flat_params, mirror_params,
    x_groups, y_groups) -> (loss_acc, acc_acc, statsA_acc, statsB_acc)``.

    Each tick runs one forward AND one backward micro-batch of BOTH streams:
    stream A's cotangents descend the stage chain (reverse ppermute) while
    stream B's — whose activations flow S-1→0 against the mirror rows —
    ascend it (forward ppermute), so the mirror streams keep interleaving
    under 1F1B exactly as they do under GPipe.  Stream B's accumulated
    gradients are returned as the MIRROR param cotangent; the engine-level
    ``mirror = ppermute(flat_params)`` transposes them home (the mirror
    permutation is an involution), identically to the GPipe AD path."""
    S = part.num_stages
    D = resid_depth(S)
    in_pack0 = part.act_packs[0]
    logits_n = part.out_pack.total
    nclass = part.out_pack.shapes[0][-1]
    amax = part.act_max
    stat_n = branches_stat_n(branches, part)
    # One combined forward+backward branch list serves BOTH streams: stream
    # B selects branch S-1-d, so the model's last stage (the self-seeding
    # branch) lands on device 0 — exactly where stream B drains.
    fb_branches = _make_fb_branches(
        branches, logits_n=logits_n, nclass=nclass, stat_n=stat_n,
        from_probs=from_probs, seed_scale=seed_scale,
        compute_dtype=compute_dtype,
    )
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    rev_perm = [(i + 1, i) for i in range(S - 1)]

    def v(t):
        return pcast(t, tuple(vary_axes), to="varying")

    def run(flat_params, mirror_params, x_groups, y_groups):
        lead = jax.tree.leaves(x_groups)[0]
        Pn = lead.shape[2]
        T = Pn + 2 * (S - 1)
        d = lax.axis_index(AXIS_STAGE)
        sB = S - 1 - d  # stream B's stage on this device
        is_lastA = d == S - 1
        is_lastB = d == 0
        z = jnp.zeros

        def sel(tree, j, p):
            return jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a[j], p, keepdims=False),
                tree,
            )

        def one_pair(carry, pair):
            gA, gB, loss_in, acc_in, stA_in, stB_in = carry
            xp, yp = pair  # leaves [2, Pn, mb, ...], [2, Pn, mb]

            def tick(c, t):
                (bufA, bufB, cotA, cotB, resA, resB,
                 gA, gB, gxA, gxB, l_acc, a_acc, stA, stB) = c
                with scope("fwd_tick"), scope("mb_inject"):
                    p_in = jnp.clip(t, 0, Pn - 1)
                    injA = pad_to(
                        in_pack0.pack(sel(xp, 0, p_in), compute_dtype), amax
                    )
                    injB = pad_to(
                        in_pack0.pack(sel(xp, 1, p_in), compute_dtype), amax
                    )
                    bufA = jnp.where(d == 0, injA, bufA)
                    bufB = jnp.where(d == S - 1, injB, bufB)
                # Reads precede writes (ring depth is exactly the round
                # trip; see make_1f1b_scan); each stream's draining device
                # takes its live buf directly — stream A drains at d=S-1,
                # stream B at d=0.
                p_fA = t - d
                p_fB = t - sB
                vA = (p_fA >= 0) & (p_fA < Pn)
                vB = (p_fB >= 0) & (p_fB < Pn)
                p_bA = t - 2 * (S - 1) + d
                p_bB = t - (S - 1) - d
                vbA = (p_bA >= 0) & (p_bA < Pn)
                vbB = (p_bB >= 0) & (p_bB < Pn)
                # The self-seeding branch (A: d=S-1, B: d=0) statically
                # ignores a_in and backwards its live buf.
                a_inA = lax.dynamic_index_in_dim(
                    resA, jnp.clip(p_bA, 0, Pn - 1) % D, keepdims=False
                )
                a_inB = lax.dynamic_index_in_dim(
                    resB, jnp.clip(p_bB, 0, Pn - 1) % D, keepdims=False
                )
                resA = ring_store(
                    resA, vA & (~is_lastA), jnp.clip(p_fA, 0, Pn - 1) % D, bufA
                )
                resB = ring_store(
                    resB, vB & (~is_lastB), jnp.clip(p_fB, 0, Pn - 1) % D, bufB
                )
                p_out = t - (S - 1)
                valid_out = (p_out >= 0) & (p_out < Pn)
                p_sel = jnp.clip(p_out, 0, Pn - 1)
                lblA = lax.dynamic_index_in_dim(yp[0], p_sel, keepdims=False)
                lblB = lax.dynamic_index_in_dim(yp[1], p_sel, keepdims=False)
                yA, sA_st, lA, aA, gaA, gpA = lax.switch(
                    d, fb_branches, flat_params, bufA, a_inA,
                    cotA.astype(compute_dtype), lblA, valid_out,
                )
                yB, sB_st, lB, aB, gaB, gpB = lax.switch(
                    sB, fb_branches, mirror_params, bufB, a_inB,
                    cotB.astype(compute_dtype), lblB, valid_out,
                )
                stA = stA + jnp.where(vA, sA_st, 0.0)
                stB = stB + jnp.where(vB, sB_st, 0.0)
                outA = valid_out & is_lastA
                outB = valid_out & is_lastB
                l_acc = (
                    l_acc + jnp.where(outA, lA, 0.0) + jnp.where(outB, lB, 0.0)
                )
                a_acc = (
                    a_acc + jnp.where(outA, aA, 0.0) + jnp.where(outB, aB, 0.0)
                )
                with scope("fwd_tick"), scope("stage_handoff"):
                    nbufA = (
                        _handoff(yA, fwd_perm, quant)
                        if fwd_perm else jnp.zeros_like(yA)
                    )
                    nbufB = (
                        _handoff(yB, rev_perm, quant)
                        if rev_perm else jnp.zeros_like(yB)
                    )
                with scope("bwd_tick"):
                    gA = gA + jnp.where(vbA, gpA, jnp.zeros_like(gpA))
                    gB = gB + jnp.where(vbB, gpB, jnp.zeros_like(gpB))
                    if grad_x:
                        gxa = in_pack0.unpack(
                            lax_slice(gaA, 0, in_pack0.total), dtype=compute_dtype
                        )
                        gxb = in_pack0.unpack(
                            lax_slice(gaB, 0, in_pack0.total), dtype=compute_dtype
                        )
                        slA, mA = jnp.clip(p_bA, 0, Pn - 1), vbA & (d == 0)
                        slB, mB = jnp.clip(p_bB, 0, Pn - 1), vbB & (d == S - 1)
                        gxA = jax.tree.map(
                            lambda G, g: scatter_part_row(G, g, slA, mA),
                            gxA, gxa,
                        )
                        gxB = jax.tree.map(
                            lambda G, g: scatter_part_row(G, g, slB, mB),
                            gxB, gxb,
                        )
                    with scope("cot_handoff"):
                        cotA = (
                            _handoff(gaA, rev_perm, quant)
                            if rev_perm else jnp.zeros_like(gaA)
                        )
                        cotB = (
                            _handoff(gaB, fwd_perm, quant)
                            if fwd_perm else jnp.zeros_like(gaB)
                        )
                return (nbufA, nbufB, cotA, cotB, resA, resB,
                        gA, gB, gxA, gxB, l_acc, a_acc, stA, stB), None

            # scope: see make_1f1b_scan — zero inits sunk into the stage
            # dispatch conditional need a name for HBM attribution.
            with scope("schedule_init"):
                gx0 = (
                    jax.tree.map(
                        lambda a_: v(z(a_.shape[1:], compute_dtype)), xp
                    )
                    if grad_x
                    else ()
                )
                init = (
                    v(z((amax,), compute_dtype)), v(z((amax,), compute_dtype)),
                    v(z((amax,), compute_dtype)), v(z((amax,), compute_dtype)),
                    v(z((D, amax), compute_dtype)),
                    v(z((D, amax), compute_dtype)),
                    gA, gB, gx0, gx0,
                    v(z((), jnp.float32)), v(z((), jnp.float32)),
                    stA_in, stB_in,
                )
            (_, _, _, _, _, _, gA, gB, gxA, gxB, l_acc, a_acc, stA, stB), _ = (
                lax.scan(tick, init, jnp.arange(T, dtype=jnp.int32))
            )
            gx_pair = (
                jax.tree.map(lambda a_, b_: jnp.stack([a_, b_]), gxA, gxB)
                if grad_x
                else ()
            )
            return (gA, gB, loss_in + l_acc, acc_in + a_acc, stA, stB), gx_pair

        st0 = v(z((stat_n,), jnp.float32))
        g0 = v(z(flat_params.shape, flat_params.dtype))
        (gA, gB, loss_acc, acc_acc, stA_acc, stB_acc), gx = lax.scan(
            one_pair,
            (g0, v(z(flat_params.shape, flat_params.dtype)),
             v(z((), jnp.float32)), v(z((), jnp.float32)),
             st0, v(z((stat_n,), jnp.float32))),
            (x_groups, y_groups),
        )
        return loss_acc, acc_acc, stA_acc, stB_acc, gA, gB, gx

    return _wrap_schedule_vjp(
        run, n_params=2, n_outs=4, seed_scale=seed_scale, grad_x=grad_x
    )
