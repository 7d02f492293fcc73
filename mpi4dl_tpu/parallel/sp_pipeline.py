"""SP x PP: spatial parallelism composed with the pipeline engine.

Reference behaviour being re-expressed: ``train_model_spatial``
(``src/torchgems/train_spatial.py:293-1458``) runs the first ``spatial_size``
pipeline split(s) spread over ``num_spatial_parts`` tile ranks (halo-exchange
convs), then hands tiles to the layer-parallel tail — via a joint-rank
gather + concat mosaic (``:690-721``, ``:1083-1188``) or the scatter/gather
LOCAL_DP_LP junction (``:809-1028``) — and pipelines micro-batch parts
through the tail ranks.

TPU-native re-design (one jitted SPMD program over mesh (data, stage, sph,
spw); every collective is uniform — see stage_common.py for why stage
branches must be pure compute):

- **SP phase**: the ``stage`` axis is data-parallel over the batch.  Every
  stage block takes its 1/S chunk of the batch and runs the spatial region
  tiled over (sph, spw) with halo exchanges.  Where the reference idles the
  tail GPUs during spatial compute (and the tile GPUs during tail compute),
  here every device computes the spatial region on distinct images —
  S x more spatial throughput from the same mesh.
- **Junction**: ``all_gather`` over the tile axes (the mosaic merge), then
  either replicate the tail per tile coordinate (junction='gather', the
  reference's plain SP→LP handoff) or batch-split over tile coordinates
  (junction='batch_split', the reference's LOCAL_DP_LP); finally an
  ``all_gather`` over ``stage`` lines junction activations up in micro-batch
  injection order.
- **PP phase**: the shared GPipe tick scan (stage_common.gpipe_scan) over the
  tail cells — or, under ``schedule="1f1b"``, the manual-backward 1F1B tick
  loop (stage_common.make_1f1b_scan; docs/pipeline.md).  The backward pass
  of BOTH phases is one jax.grad through the whole program: the junction
  gathers transpose into the tile/stage scatter of cotangents the reference
  implements by hand (the 1F1B scan's custom_vjp hands AD the tail-injection
  cotangents, so the same transposes fire either way).

Gradient combine — DERIVATION (validated exactly against single-device SGD
in tests/test_sp_pipeline.py for both junctions):

shard_map's AD reduces the cotangent of an axis-INVARIANT input itself: when
a replicated value (sp params, in_specs P(); tail rows, invariant over the
tile/data axes) feeds axis-varying compute, the transpose inserts the
cross-device psum so the returned cotangent is again invariant — including
the contributions routed home by the junction all_gather's adjoint
(reduce-scatter) and the ppermute transposes.  Each device's ``g_sp`` /
``g_tail`` therefore already IS the complete gradient of the
mean-over-devices loss.  The explicit ``pmean``s below are numerically the
identity on these already-reduced values — they exist to make the invariance
explicit (vma bookkeeping), not to combine anything; this is also why a
``psum`` over ``stage`` would multiply the gradient by exactly S.
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

from mpi4dl_tpu.cells import CellModel
from mpi4dl_tpu.layer_ctx import ApplyCtx, SpatialCtx
from mpi4dl_tpu.obs.scopes import scope
import numpy as np

from mpi4dl_tpu.parallel.partition import (
    StagePartition,
    TreePack,
    pad_to,
    stat_leaf_info,
)
from mpi4dl_tpu.parallel.pipeline import grad_pmean
from mpi4dl_tpu.parallel.spatial import (
    apply_junction,
    apply_spatial_region,
    junction_shard_index,
)
from mpi4dl_tpu.quant.collectives import quantized_all_gather
from mpi4dl_tpu.quant.policy import QuantPolicy
from mpi4dl_tpu.parallel.stage_common import (
    gems_dual_scan,
    gpipe_scan,
    make_1f1b_scan,
    make_gems_1f1b_scan,
    make_stage_branches,
    put_stage_opt,
    restore_opt_rows,
    scatter_stage_stats,
    squeeze_opt_rows,
    stage_opt_specs,
    use_1f1b_cell_remat,
)
from mpi4dl_tpu.train import Optimizer, spatial_partition_spec
from mpi4dl_tpu.mesh import AXIS_DATA, AXIS_STAGE


@dataclasses.dataclass
class SPPipeline:
    """Static partition of a model into a spatial region + pipeline tail."""

    model: CellModel
    spatial_until: int
    sp: SpatialCtx
    sp_pack: TreePack  # spatial-region params, one flat vector
    tail_part: StagePartition  # pipeline partition of the tail cells
    junction: str  # 'gather' | 'batch_split'
    mb_tail: int  # per-device tail micro-batch
    # BN running-stat positions inside the spatial-region packing (the tail's
    # live in tail_part.stat_*): leaf indices into the unpacked tree + flat
    # positions in sp_buf for the write-back.
    sp_stat_leaf_ids: list = dataclasses.field(default_factory=list)
    sp_stat_idx: Optional[np.ndarray] = None
    # Multi-level spatial region: [(stop_cell, SpatialCtx)] — level 0 is `sp`;
    # None means the single level [(spatial_until, sp)].
    levels: Optional[list] = None
    # Junction batch-split degree (LOCAL_DP_LP, reference comm.py:278-294);
    # defaults to the final level's tile count.
    degree: int = 1
    # Storage dtype of sp_buf / tail_buf (bf_16_all — see StagePartition).
    param_dtype: Any = jnp.float32

    @classmethod
    def build(
        cls,
        model: CellModel,
        params_list,
        split_size: int,
        sp: SpatialCtx,
        microbatch: int,
        junction: str = "batch_split",
        balance=None,
        compute_dtype=jnp.float32,
        levels: Optional[list] = None,
        local_dp: Optional[int] = None,
        param_dtype=jnp.float32,
    ) -> "SPPipeline":
        su = model.spatial_until
        assert 0 < su < len(model.cells), f"spatial_until={su} must split the model"
        if levels is not None:
            assert levels[-1][0] == su, (levels, su)
            assert levels[0][1].rep_h == 1 and levels[0][1].rep_w == 1, (
                "level 0 must be the mesh-defining (rep=1) ctx"
            )
        sp_last = levels[-1][1] if levels else sp
        degree = local_dp if local_dp else sp_last.grid_h * sp_last.grid_w
        # Junction activation structure from abstract evaluation at GLOBAL
        # shapes (the reference's get_shapes_spatial tile math collapses into
        # eval_shape + one divide, train_spatial.py:61-238).
        ctx = ApplyCtx(train=True)
        jstruct = jax.eval_shape(
            lambda ps, xx: model.apply(ps, xx, ctx, start=0, stop=su),
            params_list[:su],
            jax.ShapeDtypeStruct((microbatch, *model.in_shape[1:]), compute_dtype),
        )
        if junction == "batch_split":
            assert microbatch % degree == 0, (microbatch, degree)
            mb_tail = microbatch // degree
        else:
            mb_tail = microbatch
        tail_in = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((mb_tail, *s.shape[1:]), compute_dtype),
            jstruct,
        )
        tail_model = CellModel(
            model.cells[su:],
            model.in_shape,
            model.num_classes,
            name=model.name + "_tail",
        )
        tail_part = StagePartition.build(
            tail_model, params_list[su:], split_size, tail_in,
            balance=balance, compute_dtype=compute_dtype, param_dtype=param_dtype,
        )
        sp_pack = TreePack.of(params_list[:su])
        sp_ids, sp_slots = stat_leaf_info(params_list[:su])
        sp_idx = (
            np.concatenate(
                [np.arange(o, o + s, dtype=np.int32) for o, s in sp_slots]
            )
            if sp_slots
            else None
        )
        return cls(
            model, su, sp, sp_pack, tail_part, junction, mb_tail, sp_ids, sp_idx,
            levels=levels, degree=degree, param_dtype=param_dtype,
        )

    def pack_spatial(self, params_list) -> jax.Array:
        return self.sp_pack.pack(params_list[: self.spatial_until], self.param_dtype)

    def unpack_all(self, sp_vec, tail_buf) -> list:
        """Reassemble the full params_list (host-side)."""
        return list(self.sp_pack.unpack(sp_vec)) + self.tail_part.unpack_params(tail_buf)


@dataclasses.dataclass
class SPPipelineState:
    sp_buf: jax.Array  # [sp_total] replicated
    tail_buf: jax.Array  # [S, Pmax] stage-sharded
    opt_sp: Any
    opt_tail: Any
    step: jax.Array


jax.tree_util.register_dataclass(
    SPPipelineState,
    data_fields=["sp_buf", "tail_buf", "opt_sp", "opt_tail", "step"],
    meta_fields=[],
)


def init_sp_pipeline_state(
    spp: SPPipeline, params_list, optimizer: Optimizer, mesh: Mesh
) -> SPPipelineState:
    sp_buf = jax.device_put(
        spp.pack_spatial(params_list), NamedSharding(mesh, P())
    )
    tail_sharding = NamedSharding(mesh, P(AXIS_STAGE, None))
    tail_buf = jax.device_put(spp.tail_part.pack_params(params_list[spp.spatial_until:]),
                              tail_sharding)
    opt_sp = optimizer.init(sp_buf)
    # Tail moment rows ride the stage sharding; scalar leaves (Adam's step
    # counter) are replicated — same rule as _make_sp_step's shard_map specs.
    opt_tail = put_stage_opt(optimizer.init(tail_buf), mesh)
    return SPPipelineState(sp_buf, tail_buf, opt_sp, opt_tail, jnp.zeros((), jnp.int32))


def _make_sp_step(
    spp: SPPipeline,
    optimizer: Optimizer,
    mesh: Mesh,
    lead_shape: Tuple[int, ...],
    scan_fn,
    denom: int,
    compute_dtype,
    remat: bool,
    with_data_axis: bool,
    bn_stats: bool = True,
    donate: bool = False,
    schedule: str = "gpipe",
    quant: Optional[QuantPolicy] = None,
):
    """Shared scaffolding of the SP(+GEMS) x PP steps: phase-1 spatial region,
    junction, tail scan (``scan_fn``), loss reduction, grad combine, update.

    ``schedule="1f1b"`` only affects how the tail branches are built here
    (unwrapped — the 1F1B scans recompute stage forwards in their own
    backward branches); the schedule itself lives in ``scan_fn``, whose
    custom_vjp hands the tail-injection cotangents back to this function's
    ``jax.value_and_grad``, which routes them through the junction/spatial
    transposes exactly as the GPipe AD path does.  The spatial region keeps
    its own remat setting either way.

    ``lead_shape`` shapes the injection pytree's leading dims —
    ``(Pn,)`` for GPipe, ``(times, 2, Pn)`` for the GEMS dual stream.
    ``scan_fn(branches, tail_flat, x_parts, y_parts, vary_axes)`` returns the
    boundary-stage (loss_acc, acc_acc, stats_avg); ``denom`` is the drained
    part count.

    BN running stats: the spatial region deposits once per step over the full
    per-device chunk (coarser batch-stat granularity than the per-micro-batch
    reference semantics — a documented, statistically stronger deviation); the
    tail deposits per valid tick via the scan, engine-normalized in scan_fn.
    """
    sp = spp.sp
    part = spp.tail_part
    S = part.num_stages
    su = spp.spatial_until
    levels = spp.levels if spp.levels is not None else [(su, sp)]
    sp_last = levels[-1][1]
    degree = spp.degree
    groups = 1
    for d in lead_shape:
        groups *= d
    tile_axes = tuple(a for a in (sp.axis_h, sp.axis_w) if a)
    grad_axes: Tuple[str, ...] = (AXIS_DATA,) if with_data_axis else ()
    sp_ctx = ApplyCtx(train=True, spatial=sp)
    tail_ctx = ApplyCtx(train=True)

    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown schedule {schedule!r}; use 'gpipe' or '1f1b'")
    with_stats_sp = bn_stats and bool(spp.sp_stat_leaf_ids)
    with_stats_tail = bn_stats and part.stat_max > 0
    branches = make_stage_branches(
        part, tail_ctx, compute_dtype, remat and schedule == "gpipe",
        with_stats_tail,
        vary_axes=(AXIS_STAGE,) + tile_axes + grad_axes,
        cell_remat=schedule == "1f1b" and use_1f1b_cell_remat(part),
    )

    def phase1(sp_flat, x_tile):
        """Spatial region on this device's (stage-chunk, tile): returns the
        tail injection pytree [*lead_shape, mb_tail, ...] in batch order,
        plus the spatial region's BN stat-update vector."""
        B = x_tile.shape[0]
        assert B % S == 0, f"batch {B} must divide over {S} stage blocks"
        chunk = B // S
        if spp.junction == "batch_split":
            assert chunk % degree == 0, (
                f"stage chunk {chunk} (= batch {B} / {S} stages) must divide "
                f"over junction degree {degree} for the batch_split junction; "
                f"choose batch = {groups} * microbatch with (B/S) % degree == 0"
            )
        s_idx = lax.axis_index(AXIS_STAGE)
        xs = lax.dynamic_slice_in_dim(x_tile, s_idx * chunk, chunk, axis=0)
        params_sp = spp.sp_pack.unpack(sp_flat)

        def region(ps, xx):
            if with_stats_sp:
                sink: dict = {}
                c = dataclasses.replace(sp_ctx, bn_sink=sink)
            else:
                sink, c = None, sp_ctx
            act, _ = apply_spatial_region(
                spp.model, ps, xx, c, levels, remat=remat, quant=quant
            )
            if not with_stats_sp:
                return act, jnp.zeros((0,), jnp.float32)
            leaves = jax.tree.leaves(ps)
            vals = [
                sink.get(id(leaves[i]), leaves[i]) for i in spp.sp_stat_leaf_ids
            ]
            svec = jnp.concatenate([jnp.ravel(v).astype(jnp.float32) for v in vals])
            return act, svec

        if remat:
            region = jax.checkpoint(region)
        with scope("sp_region"):
            act, sp_stats = region(params_sp, xs.astype(compute_dtype))
        # Junction: mosaic-merge tiles; batch-split for LOCAL_DP_LP (via the
        # all_to_all fast path when every tile device takes a distinct shard
        # — degree x less ICI traffic and junction memory than gather+slice).
        act = apply_junction(act, sp_last, spp.junction, degree, quant=quant)

        # Line all stage chunks up in batch order on every device (junction
        # wire class: the policy's junction mode quantizes the payload).
        j_mode = quant.mode("junction") if quant is not None else None

        def g(t):  # analysis: ok(unscoped-collective) — applied under scope("stage_lineup") below
            if j_mode:
                t = quantized_all_gather(t, AXIS_STAGE, 0, j_mode, quant.block)
            else:
                t = lax.all_gather(t, AXIS_STAGE, axis=0, tiled=True)
            return t.reshape(*lead_shape, spp.mb_tail, *t.shape[1:])

        with scope("stage_lineup"):
            return jax.tree.map(g, act), sp_stats

    def labels_to_parts(labels):
        """The same index transform phase1 applies to images (chunk by stage
        block, junction batch-split, gather) — applied to labels."""
        B = labels.shape[0]
        chunk = B // S
        if spp.junction == "batch_split":
            k = junction_shard_index(sp_last, degree)
            lab = labels.reshape(S, degree, chunk // degree)
            lab = lax.dynamic_index_in_dim(lab, k, axis=1, keepdims=False)
            lab = lab.reshape(-1)
        else:
            lab = labels
        return lab.reshape(*lead_shape, spp.mb_tail)

    def sharded_step(sp_buf, tail_row, opt_sp, opt_tail, x, labels):
        tail_flat = tail_row[0]
        # Stage-sharded tail opt moment rows squeeze like the param row;
        # scalar leaves pass through (see pipeline.py).  opt_sp is fully
        # replicated and passes through whole.
        opt_tail_local = squeeze_opt_rows(opt_tail)
        y_parts = labels_to_parts(labels)
        vary_axes = (AXIS_STAGE,) + tile_axes + grad_axes

        def loss_and_metrics(sp_flat, tail_flat):
            x_parts, sp_stats = phase1(sp_flat, x)
            with scope("tail_scan"):
                loss_acc, acc_acc, tail_stats = scan_fn(
                    branches, tail_flat, x_parts, y_parts, vary_axes
                )
            with scope("loss_reduce"):
                loss = lax.psum(loss_acc, (AXIS_STAGE,)) / denom
                acc = lax.psum(acc_acc, (AXIS_STAGE,)) / denom
                # batch_split leaves per-tile batch shards to merge.  Under
                # 'gather' every tile device saw the full batch and loss/acc
                # ARE equal on every tile — but ``lax.all_gather`` is typed
                # varying→varying, so typed shard_map cannot know it, and a
                # collective has to say so: this pmean (two scalars; an
                # identity on the value), as apply_spatial_model does for the
                # SP family.  ``all_gather_invariant`` at the junction would
                # say it without wire; it is not public API on jax 0.9.0 and
                # needs the gradient bookkeeping below re-derived (ROADMAP D1).
                if tile_axes:
                    loss = lax.pmean(loss, tile_axes)
                    acc = lax.pmean(acc, tile_axes)
                if grad_axes:
                    loss = lax.pmean(loss, grad_axes)
                    acc = lax.pmean(acc, grad_axes)
            return loss, (acc, sp_stats, tail_stats)

        (loss, (acc, sp_stats, tail_stats)), (g_sp, g_tail) = jax.value_and_grad(
            loss_and_metrics, argnums=(0, 1), has_aux=True
        )(sp_buf, tail_flat)

        # Identity-on-value invariance bookkeeping (derivation in the module
        # docstring: AD already psum'd these cotangents home).  Identity on
        # the VALUE, not on the wire: these pmeans move the full flat param
        # buffers per axis, which is why the quant policy's grad class
        # routes them through the EQuARX-style quantized reduce
        # (pipeline.grad_pmean).
        with scope("grad_reduce"):
            g_sp = grad_pmean(g_sp, AXIS_STAGE, quant)
            if tile_axes:
                g_sp = grad_pmean(g_sp, tile_axes, quant)
                g_tail = grad_pmean(g_tail, tile_axes, quant)
            if grad_axes:
                g_sp = grad_pmean(g_sp, grad_axes, quant)
                g_tail = grad_pmean(g_tail, grad_axes, quant)

        with scope("optimizer_update"):
            new_sp, new_opt_sp = optimizer.update(sp_buf, g_sp, opt_sp)
            new_tail, new_opt_tail = optimizer.update(
                tail_flat, g_tail, opt_tail_local
            )
        if with_stats_sp:
            # Spatial stats vary over stage (distinct batch chunks) and data;
            # the tile axes are already reduced inside BN (cross-tile psum) or
            # the deposit (per-tile pmean).  sp_buf is fully replicated.
            with scope("stats_reduce"):
                st = grad_pmean(sp_stats, (AXIS_STAGE,) + grad_axes, quant)
            new_sp = new_sp.at[jnp.asarray(spp.sp_stat_idx)].set(
                st.astype(new_sp.dtype)
            )
        if with_stats_tail:
            # Tail stats vary over the tile axes under junction='batch_split'
            # (distinct batch shards) and over data.  Under 'gather' they are
            # identical over tiles but typed varying (see loss_reduce above):
            # the pmean is what makes new_tail provably tile-invariant for
            # out_specs, at the cost of one stats vector on the wire.
            stt = tail_stats
            with scope("stats_reduce"):
                if tile_axes:
                    stt = grad_pmean(stt, tile_axes, quant)
                if grad_axes:
                    stt = grad_pmean(stt, grad_axes, quant)
            new_tail = scatter_stage_stats(part, new_tail, stt)
        return (
            new_sp,
            new_tail[None],
            new_opt_sp,
            restore_opt_rows(new_opt_tail, opt_tail),
            {"loss": loss, "accuracy": acc},
        )

    x_spec = spatial_partition_spec(sp, data=with_data_axis)
    y_spec = P(AXIS_DATA) if with_data_axis else P()
    tail_spec = P(AXIS_STAGE, None)
    tail_ospec = stage_opt_specs(optimizer, part)
    smapped = shard_map(
        sharded_step,
        mesh=mesh,
        in_specs=(P(), tail_spec, P(), tail_ospec, x_spec, y_spec),
        out_specs=(P(), tail_spec, P(), tail_ospec, P()),
    )

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(state: SPPipelineState, x, labels):
        sp_buf, tail_buf, opt_sp, opt_tail, metrics = smapped(
            state.sp_buf, state.tail_buf, state.opt_sp, state.opt_tail, x, labels
        )
        return (
            SPPipelineState(sp_buf, tail_buf, opt_sp, opt_tail, state.step + 1),
            metrics,
        )

    return step


def make_sp_pipeline_train_step(
    spp: SPPipeline,
    optimizer: Optimizer,
    mesh: Mesh,
    parts: int,
    compute_dtype=jnp.float32,
    remat: bool = True,
    from_probs: bool = False,
    with_data_axis: bool = False,
    bn_stats: bool = True,
    donate: bool = False,
    schedule: str = "gpipe",
    quant: Optional[QuantPolicy] = None,
):
    """Build `(SPPipelineState, x, labels) -> (SPPipelineState, metrics)`.

    x: [B, H, W, C] global batch per data replica group; B = parts * microbatch.
    Constraints: B % S == 0 (stage blocks take equal chunks) and, for
    junction='batch_split', (B/S) % tiles == 0 (each stage chunk splits over
    the tile grid) — both checked at trace time.

    ``schedule="1f1b"`` runs the tail under the manual-backward 1F1B tick
    loop (grad_x=True: the scan's custom_vjp returns the tail-injection
    cotangents so AD can route them back through the junction into the
    spatial region).

    ``quant``: opt-in quantized-collective policy (docs/quantization.md):
    junction gathers/lineup, respatial reshards, grad/stats reduces, and
    tail handoffs per the policy's classes; ``None`` is bit-identical.
    """
    part = spp.tail_part
    cache: dict = {}

    def scan_fn(branches, tail_flat, x_parts, y_parts, vary_axes):
        if schedule == "1f1b":
            if "scan" not in cache:
                cache["scan"] = make_1f1b_scan(
                    part, branches,
                    vary_axes=vary_axes,
                    from_probs=from_probs,
                    compute_dtype=compute_dtype,
                    grad_x=True,
                    quant=quant,
                )
            loss_acc, acc_acc, st_acc = cache["scan"](
                tail_flat, x_parts, y_parts
            )
        else:
            loss_acc, acc_acc, st_acc = gpipe_scan(
                part, branches, tail_flat, x_parts, y_parts,
                vary_axes=vary_axes,
                from_probs=from_probs,
                compute_dtype=compute_dtype,
                quant=quant,
            )
        return loss_acc, acc_acc, st_acc / parts

    return _make_sp_step(
        spp, optimizer, mesh, (parts,), scan_fn, parts,
        compute_dtype, remat, with_data_axis, bn_stats, donate, schedule,
        quant=quant,
    )


def make_sp_gems_train_step(
    spp: SPPipeline,
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
    """SP x GEMS x PP — the reference's flagship 5D composition
    (``train_spatial_master.py``: two spatial models over mirrored rank sets
    with flat param/grad exchange; here ONE weight set, the reverse stream
    reading mirror-ppermuted stage rows, see parallel/gems.py).

    x: [B, H, W, C] with B = 2 * times * parts * microbatch per data replica;
    pairs alternate direction through the tail stage chain.
    ``schedule="1f1b"``: both mirror streams run one-forward-one-backward
    (stage_common.make_gems_1f1b_scan, grad_x=True for the junction
    transpose); the mirror-ppermute here stays outside the scan so AD still
    routes stream B's gradients home.
    """
    part = spp.tail_part
    S = part.num_stages
    mirror_perm = [(i, S - 1 - i) for i in range(S)]
    cache: dict = {}

    def scan_fn(branches, tail_flat, x_parts, y_parts, vary_axes):
        with scope("gems_mirror"):
            mirror_params = lax.ppermute(tail_flat, AXIS_STAGE, mirror_perm)
        if schedule == "1f1b":
            if "scan" not in cache:
                cache["scan"] = make_gems_1f1b_scan(
                    part, branches,
                    vary_axes=vary_axes,
                    from_probs=from_probs,
                    compute_dtype=compute_dtype,
                    grad_x=True,
                    quant=quant,
                )
            loss_acc, acc_acc, stA, stB = cache["scan"](
                tail_flat, mirror_params, x_parts, y_parts
            )
        else:
            loss_acc, acc_acc, stA, stB = gems_dual_scan(
                part, branches, tail_flat, mirror_params, x_parts, y_parts,
                vary_axes=vary_axes,
                from_probs=from_probs,
                compute_dtype=compute_dtype,
                quant=quant,
            )
        with scope("stats_mirror"):
            st = (stA + lax.ppermute(stB, AXIS_STAGE, mirror_perm)) / (2 * times * parts)
        return loss_acc, acc_acc, st

    return _make_sp_step(
        spp, optimizer, mesh, (times, 2, parts), scan_fn, 2 * times * parts,
        compute_dtype, remat, with_data_axis, bn_stats, donate, schedule,
        quant=quant,
    )
