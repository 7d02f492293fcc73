"""Multi-level spatial parallelism + decoupled LOCAL_DP_LP degree.

Reference behaviour being matched: ``num_spatial_parts="4,2"`` runs the first
spatial split on 4 tiles and the second on 2 tiles with a skewed
spatial→spatial transition (``/root/reference/src/torchgems/train_spatial.py:453-504``,
``:557-641``); ``LOCAL_DP_LP`` lets the post-junction region run k-way data
parallelism with k independent of the tile count (``comm.py:278-294``).

Here levels are per-level SpatialCtx grids on the same mesh axes (coarser
levels replicated with rep>1) and the transition is one respatial re-shard;
both must reproduce single-device SGD exactly on BN-free models, and
cross-tile-BN models must match when the batch-stat granularity lines up.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.cells import CellModel, LayerCell
from mpi4dl_tpu.layer_ctx import SpatialCtx, spatial_levels_for
from mpi4dl_tpu.layers import BatchNorm, Conv2d, Dense, Flatten, Pool2d, ReLU
from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.train import (
    Optimizer,
    TrainState,
    make_spatial_train_step,
    make_train_step,
)


def _bnfree_model(batch):
    cells = [
        LayerCell([Conv2d(3, 8, 3), ReLU()], name="c0"),
        LayerCell([Conv2d(8, 8, 3, stride=2), ReLU()], name="c1"),
        LayerCell([Conv2d(8, 8, 3), ReLU(), Pool2d("max", 2)], name="c2"),
        LayerCell([Flatten(), Dense(8 * 8 * 8, 10)], name="head"),
    ]
    return CellModel(cells, (batch, 32, 32, 3), 10, spatial_until=3)


def _bn_model(batch):
    cells = [
        LayerCell([Conv2d(3, 8, 3), BatchNorm(8), ReLU()], name="c0"),
        LayerCell([Conv2d(8, 8, 3, stride=2), BatchNorm(8), ReLU()], name="c1"),
        LayerCell([Conv2d(8, 8, 3), BatchNorm(8), ReLU()], name="c2"),
        LayerCell([Flatten(), Dense(8 * 16 * 16, 10)], name="head"),
    ]
    return CellModel(cells, (batch, 32, 32, 3), 10, spatial_until=3)


def test_spatial_levels_for_grids():
    lv = spatial_levels_for("square", [4, 2])
    assert (lv[0].grid_h, lv[0].grid_w, lv[0].rep_h, lv[0].rep_w) == (2, 2, 1, 1)
    assert (lv[1].grid_h, lv[1].grid_w) == (1, 2)
    assert (lv[1].rep_h, lv[1].rep_w) == (2, 1)
    lv = spatial_levels_for("vertical", [4, 2, 1])
    assert [(c.grid_w, c.rep_w) for c in lv] == [(4, 1), (2, 2), (1, 4)]
    with pytest.raises(ValueError):
        spatial_levels_for("vertical", [4, 3])
    with pytest.raises(ValueError):
        spatial_levels_for("vertical", [4, 8])


def _run_pair(model, levels, junction, local_dp, batch, steps=2, parts=1):
    params, _ = model.init(jax.random.key(0))
    sp = levels[0][1]
    spec = MeshSpec(
        sph=sp.grid_h if sp.axis_h else 1, spw=sp.grid_w if sp.axis_w else 1
    )
    mesh = build_mesh(spec, jax.devices()[: spec.size])
    opt = Optimizer("sgd", lr=0.01)
    step = make_spatial_train_step(
        model, opt, mesh, sp, parts=parts, junction=junction,
        spatial_until=model.spatial_until, levels=levels, local_dp=local_dp,
    )
    state = TrainState.create(params, opt)
    ref_step = make_train_step(model, opt, parts=parts)
    ref_state = TrainState.create(params, opt)

    x = jax.random.normal(jax.random.key(1), (batch, 32, 32, 3))
    y = jnp.arange(batch, dtype=jnp.int32) % 10
    for _ in range(steps):
        state, m = step(state, x, y)
        ref_state, m_ref = ref_step(ref_state, x, y)
        np.testing.assert_allclose(float(m_ref["loss"]), float(m["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(ref_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5)


def test_multilevel_square_4_to_2_exact(devices8):
    """Square 2x2 level 0 → (1,2) level 1 (the reference's skewed 4→2),
    gather junction: must equal single-device SGD exactly (BN-free)."""
    model = _bnfree_model(2)
    ctxs = spatial_levels_for("square", [4, 2])
    levels = [(2, ctxs[0]), (3, ctxs[1])]
    _run_pair(model, levels, "gather", None, batch=2)


def test_multilevel_vertical_4_to_2_exact(devices8):
    model = _bnfree_model(2)
    ctxs = spatial_levels_for("vertical", [4, 2])
    levels = [(2, ctxs[0]), (3, ctxs[1])]
    _run_pair(model, levels, "gather", None, batch=2)


def test_multilevel_bn_cross_tile_exact(devices8):
    """Cross-tile BN stats are exact under replicated coarse levels too: the
    psum'd statistics count each tile rep times in numerator and denominator."""
    model = _bn_model(2)
    ctxs = spatial_levels_for("square", [4, 2])
    levels = [(2, ctxs[0]), (3, ctxs[1])]
    _run_pair(model, levels, "gather", None, batch=2)


def test_local_dp_degree_2_on_4_tiles_exact(devices8):
    """LOCAL_DP_LP degree 2 on a 2x2 tile grid (degree != tile count,
    reference comm.py:278-294): tail runs 2-way batch DP in duplicated
    device groups; BN-free so the re-sharding is numerically transparent."""
    model = _bnfree_model(4)
    sp = SpatialCtx(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2)
    levels = [(3, sp)]
    _run_pair(model, levels, "batch_split", 2, batch=4)


def test_multilevel_with_local_dp_full_devices(devices8):
    """Multi-level + LOCAL_DP_LP = 4 over the freed replication groups: the
    coarse level runs 2 tiles x 2 replicas, then the junction gives all four
    devices distinct batch shards (no redundant tail compute)."""
    model = _bnfree_model(4)
    ctxs = spatial_levels_for("square", [4, 2])
    levels = [(2, ctxs[0]), (3, ctxs[1])]
    _run_pair(model, levels, "batch_split", 4, batch=4)


def test_multilevel_d2_forward_matches_single_level(devices8):
    """D2 fused-halo runs under a coarse (rep>1) level must equal the same
    pad-once computation on the fine grid: both layouts realize identical
    global semantics, so the rep-strided halo exchange is pinned exactly."""
    from mpi4dl_tpu.compat import shard_map
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from mpi4dl_tpu.parallel.spatial import apply_spatial_region, gather_spatial

    model = _bnfree_model(2)
    params, _ = model.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(7), (2, 32, 32, 3))
    ctxs = spatial_levels_for("vertical", [4, 2], d2_mode=True)
    mesh = build_mesh(MeshSpec(sph=1, spw=4), jax.devices()[:4])
    spec = P(None, None, "spw", None)

    def run(levels):
        def f(ps, t):
            ctx = ApplyCtx(train=True, spatial=levels[0][1])
            act, last = apply_spatial_region(model, ps, t, ctx, levels)
            return lax.pmean(gather_spatial(act, last), ("spw",))

        return jax.jit(
            shard_map(f, mesh=mesh, in_specs=(P(), spec), out_specs=P())
        )(params, x)

    fine = run([(3, ctxs[0])])
    multi = run([(2, ctxs[0]), (3, ctxs[1])])
    np.testing.assert_allclose(np.asarray(fine), np.asarray(multi), atol=2e-5)


def test_amoeba_cell_d2_rep_layout_matches_fine_grid(devices8):
    """AmoebaCell's cell-level D2 pre-exchange with rep_w=2 on a 4-device
    axis must match the fine-grid (grid_w=4) result — the halo pull must
    stride over replication groups, not adjacent devices."""
    from mpi4dl_tpu.compat import shard_map
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from mpi4dl_tpu.models.amoebanet import AmoebaCell
    from mpi4dl_tpu.parallel.spatial import gather_spatial, respatial

    cell = AmoebaCell(32, 32, 32, reduction=False, reduction_prev=False)
    params, _ = cell.init(jax.random.key(0), (1, 32, 32, 32))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 32))
    sp4 = SpatialCtx(axis_w="spw", grid_w=4, d2_mode=True)
    sp2 = SpatialCtx(axis_w="spw", grid_w=2, rep_w=2, d2_mode=True)
    mesh = build_mesh(MeshSpec(sph=1, spw=4), jax.devices()[:4])
    spec = P(None, None, "spw", None)

    def run(sp):
        def f(t):
            if sp is not sp4:
                t = respatial(t, sp4, sp)
            y = cell.apply(params, t, ApplyCtx(train=True, spatial=sp))[0]
            return lax.pmean(gather_spatial(y, sp), ("spw",))

        return jax.jit(shard_map(f, mesh=mesh, in_specs=spec, out_specs=P()))(x)

    # atol covers layout-dependent conv reduction-order noise; a wrong halo
    # stride would produce O(1) errors at tile boundaries.
    np.testing.assert_allclose(
        np.asarray(run(sp4)), np.asarray(run(sp2)), atol=3e-4
    )


def test_sp_pipeline_statless_stage_branch(devices8):
    """A pipeline tail mixing BN and BN-free stages must compile: the BN-free
    stage's zero stats vector is pcast to match its siblings' varying stats
    (lax.switch vma uniformity — crashed the flagship '4,2' resnet CLI)."""
    from mpi4dl_tpu.parallel.sp_pipeline import (
        SPPipeline,
        init_sp_pipeline_state,
        make_sp_pipeline_train_step,
    )

    cells = [
        LayerCell([Conv2d(3, 8, 3), ReLU()], name="sp0"),
        LayerCell([Conv2d(8, 8, 3, stride=2), BatchNorm(8), ReLU()], name="t0"),
        LayerCell([Flatten(), Dense(8 * 16 * 16, 10)], name="head"),  # no BN
    ]
    model = CellModel(cells, (2, 32, 32, 3), 10, spatial_until=1)
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2)
    mesh = build_mesh(MeshSpec(stage=2, sph=2, spw=2), jax.devices()[:8])
    spp = SPPipeline.build(model, params, 2, sp, 2, junction="gather")
    opt = Optimizer("sgd", lr=0.01)
    step = make_sp_pipeline_train_step(spp, opt, mesh, parts=2)
    state = init_sp_pipeline_state(spp, params, opt, mesh)
    x = jax.random.normal(jax.random.key(4), (4, 32, 32, 3))
    y = jnp.arange(4, dtype=jnp.int32) % 10
    state, m = step(state, x, y)
    assert np.isfinite(float(m["loss"]))


def test_multilevel_sp_pipeline_exact(devices8):
    """SP x PP with a two-level spatial region (stage=2 x sph=2 x spw=2):
    matches single-device micro-batched SGD exactly on a BN-free model."""
    from mpi4dl_tpu.parallel.sp_pipeline import (
        SPPipeline,
        init_sp_pipeline_state,
        make_sp_pipeline_train_step,
    )

    batch = 4
    model = _bnfree_model(batch)
    model.spatial_until = 3
    params, _ = model.init(jax.random.key(0))
    ctxs = spatial_levels_for("square", [4, 2])
    levels = [(2, ctxs[0]), (3, ctxs[1])]
    mesh = build_mesh(MeshSpec(stage=2, sph=2, spw=2), jax.devices()[:8])

    parts, mb = 2, 2
    spp = SPPipeline.build(
        model, params, 2, ctxs[0], mb, junction="gather", levels=levels
    )
    opt = Optimizer("sgd", lr=0.01)
    step = make_sp_pipeline_train_step(spp, opt, mesh, parts)
    state = init_sp_pipeline_state(spp, params, opt, mesh)

    ref_step = make_train_step(model, opt, parts=parts)
    ref_state = TrainState.create(params, opt)

    x = jax.random.normal(jax.random.key(3), (batch, 32, 32, 3))
    y = jnp.arange(batch, dtype=jnp.int32) % 10
    for _ in range(2):
        state, m = step(state, x, y)
        ref_state, m_ref = ref_step(ref_state, x, y)
        np.testing.assert_allclose(float(m_ref["loss"]), float(m["loss"]), rtol=1e-4)
    got = spp.unpack_all(np.asarray(state.sp_buf), np.asarray(state.tail_buf))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5)


def test_batch_split_junction_uses_all_to_all(devices8):
    """degree == tile devices, rep == 1 → the junction must compile to
    all_to_all (1/degree the ICI traffic and junction memory of
    gather+slice), not all_gather; degree < devices falls back."""
    from mpi4dl_tpu.train import make_spatial_train_step

    model = _bnfree_model(4)
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2)
    mesh = build_mesh(MeshSpec(sph=2, spw=2), jax.devices()[:4])
    opt = Optimizer("sgd", lr=0.01)
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    y = jnp.arange(4, dtype=jnp.int32) % 10

    def jaxpr_of(local_dp):
        step = make_spatial_train_step(
            model, opt, mesh, sp, junction="batch_split",
            spatial_until=3, local_dp=local_dp,
        )
        state = TrainState.create(params, opt)
        return str(jax.make_jaxpr(lambda s: step(s, x, y))(state))

    fast = jaxpr_of(4)
    assert "all_to_all" in fast, "a2a junction not taken at degree==devices"
    slow = jaxpr_of(2)
    assert "all_to_all" not in slow  # degree 2 on 4 devices: gather+slice


def test_multilevel_gems_sp_composition(devices8):
    """The full 5-D composition: GEMS dual-stream x multi-level SP x PP in
    one program — finite, decreasing loss across steps."""
    from mpi4dl_tpu.parallel.sp_pipeline import (
        SPPipeline,
        init_sp_pipeline_state,
        make_sp_gems_train_step,
    )

    batch = 8  # 2 * times(1) * parts(2) * microbatch(2)
    model = _bnfree_model(batch)
    params, _ = model.init(jax.random.key(0))
    ctxs = spatial_levels_for("square", [4, 2])
    levels = [(2, ctxs[0]), (3, ctxs[1])]
    mesh = build_mesh(MeshSpec(stage=2, sph=2, spw=2), jax.devices()[:8])
    spp = SPPipeline.build(
        model, params, 2, ctxs[0], 2, junction="gather", levels=levels
    )
    opt = Optimizer("sgd", lr=0.02)
    step = make_sp_gems_train_step(spp, opt, mesh, parts=2, times=1)
    state = init_sp_pipeline_state(spp, params, opt, mesh)
    x = jax.random.normal(jax.random.key(9), (batch, 32, 32, 3))
    y = jnp.arange(batch, dtype=jnp.int32) % 10
    losses = []
    for _ in range(3):
        state, m = step(state, x, y)
        assert np.isfinite(float(m["loss"]))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_multilevel_tuple_state_amoebanet_forward(devices8):
    """AmoebaNet cells carry (x, skip) tuple state; respatial must re-shard
    BOTH tensors at a level transition — gathered two-level forward equals
    the unsharded forward."""
    from jax import lax
    from mpi4dl_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from mpi4dl_tpu.models.amoebanet import amoebanetd
    from mpi4dl_tpu.parallel.spatial import apply_spatial_region, gather_spatial

    model = amoebanetd((1, 64, 64, 3), num_classes=10, num_layers=3,
                       num_filters=32)
    params, _ = model.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 64, 64, 3))
    ctxs = spatial_levels_for("vertical", [4, 2], bn_cross_tile=True)
    # Levels inside the cell stack (stem is cell 0; split mid-cells).
    levels = [(2, ctxs[0]), (4, ctxs[1])]
    mesh = build_mesh(MeshSpec(sph=1, spw=4), jax.devices()[:4])
    spec = P(None, None, "spw", None)

    def f(ps, t):
        ctx = ApplyCtx(train=False, spatial=ctxs[0])
        act, last = apply_spatial_region(model, ps, t, ctx, levels)
        act = gather_spatial(act, last)
        act = tuple(lax.pmean(a, ("spw",)) for a in act) if isinstance(act, tuple) \
            else lax.pmean(act, ("spw",))
        return act

    got = jax.jit(
        shard_map(f, mesh=mesh, in_specs=(P(), spec), out_specs=P())
    )(params, x)
    want = model.apply(params, x, ApplyCtx(train=False), start=0, stop=4)
    got_t = got if isinstance(got, tuple) else (got,)
    want_t = want if isinstance(want, tuple) else (want,)
    assert len(got_t) == len(want_t), (len(got_t), len(want_t))
    for a, b in zip(got_t, want_t):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)
