"""SP x GEMS x PP (the reference's flagship 5D composition,
train_spatial_master.py) must reproduce single-device gradient accumulation
over the same 2·times·parts micro-batches exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.cells import CellModel, LayerCell
from mpi4dl_tpu.layer_ctx import SpatialCtx
from mpi4dl_tpu.layers import Conv2d, Dense, GlobalAvgPool, ReLU
from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.models.resnet import get_resnet_v2
from mpi4dl_tpu.parallel.sp_pipeline import (
    SPPipeline,
    init_sp_pipeline_state,
    make_sp_gems_train_step,
)
from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step


def _bn_free_model(mb):
    """BatchNorm-free conv net: exactness then holds for ANY times/parts
    grouping (BN batch-stat scope is the only grouping-sensitive op)."""
    cells = [
        LayerCell([Conv2d(3, 8, 3), ReLU()], name="c1"),
        LayerCell([Conv2d(8, 8, 3, stride=2), ReLU()], name="c2"),
        LayerCell([Conv2d(8, 16, 3), ReLU()], name="c3"),
        LayerCell([GlobalAvgPool(), Dense(16, 10)], name="head"),
    ]
    m = CellModel(cells, (mb, 32, 32, 3), 10, spatial_until=2, name="bnfree")
    return m


@pytest.mark.parametrize("times,parts", [(1, 1), (2, 1), (1, 2)])
def test_sp_gems_matches_single_device(devices8, times, parts):
    """2-stage tail x 2-tile SP region; BN-free model so the GEMS schedule
    math (dual streams, mirror params, grad combine) is isolated from BN
    batch-stat grouping."""
    mb = 2
    S = 2
    B = 2 * times * parts * mb

    model = _bn_free_model(mb)
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_w="spw", grid_w=2)
    mesh = build_mesh(MeshSpec(data=1, stage=2, sph=1, spw=2), jax.devices()[:4])

    spp = SPPipeline.build(model, params, S, sp, mb, junction="gather")
    opt = Optimizer("sgd", lr=0.01)
    step = make_sp_gems_train_step(spp, opt, mesh, parts, times=times)
    state = init_sp_pipeline_state(spp, params, opt, mesh)

    ref_step = make_train_step(model, opt, parts=B // mb)
    ref_state = TrainState.create(params, opt)

    x = jax.random.normal(jax.random.key(1), (B, 32, 32, 3))
    y = (jnp.arange(B) % 10).astype(jnp.int32)

    for _ in range(2):
        ref_state, m_ref = ref_step(ref_state, x, y)
        state, m = step(state, x, y)
        np.testing.assert_allclose(float(m_ref["loss"]), float(m["loss"]), rtol=1e-4)

    got = spp.unpack_all(np.asarray(state.sp_buf), np.asarray(state.tail_buf))
    want = jax.tree.leaves(ref_state.params)
    for a, b in zip(jax.tree.leaves(got), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5)


def test_sp_gems_resnet_bn_aligned(devices8):
    """Full ResNet (with BN): exact when phase-1 stage chunks coincide with
    micro-batches (2*times*parts == S)."""
    mb, S = 2, 2
    model = get_resnet_v2((mb, 32, 32, 3), depth=11, num_classes=10)
    model.spatial_until = 2
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_w="spw", grid_w=2)
    mesh = build_mesh(MeshSpec(data=1, stage=2, sph=1, spw=2), jax.devices()[:4])
    spp = SPPipeline.build(model, params, S, sp, mb, junction="gather")
    opt = Optimizer("sgd", lr=0.01)
    step = make_sp_gems_train_step(spp, opt, mesh, parts=1, times=1)
    state = init_sp_pipeline_state(spp, params, opt, mesh)
    ref_step = make_train_step(model, opt, parts=2)
    ref_state = TrainState.create(params, opt)
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 3], jnp.int32)
    ref_state, m_ref = ref_step(ref_state, x, y)
    state, m = step(state, x, y)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m["loss"]), rtol=1e-4)


def test_sp_gems_batch_split_smoke(devices8):
    """LOCAL_DP_LP junction under GEMS: finite + decreasing loss on the full
    (data=1, stage=2, sph=2, spw=2) mesh — 4D of the 5D composition in one
    program (DP via with_data_axis covered in test_sp_pipeline)."""
    model = get_resnet_v2((4, 32, 32, 3), depth=11, num_classes=10)
    model.spatial_until = 2
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2)
    mesh = build_mesh(MeshSpec(data=1, stage=2, sph=2, spw=2), jax.devices()[:8])
    spp = SPPipeline.build(model, params, 2, sp, 4, junction="batch_split")
    opt = Optimizer("sgd", lr=0.01)
    step = make_sp_gems_train_step(spp, opt, mesh, parts=1, times=1)
    state = init_sp_pipeline_state(spp, params, opt, mesh)
    x = jax.random.normal(jax.random.key(2), (8, 32, 32, 3))
    y = (jnp.arange(8) % 10).astype(jnp.int32)
    losses = []
    for _ in range(3):
        state, m = step(state, x, y)
        assert np.isfinite(float(m["loss"]))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
