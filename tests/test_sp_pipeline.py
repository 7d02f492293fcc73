"""SP x PP composition: spatial region + pipeline tail in one SPMD program
must reproduce single-device micro-batched SGD exactly (reference
train_model_spatial has no such test — it eyeballs losses, SURVEY §4).

Exactness conditions (BatchNorm statistics scope):
- parts == split_size, so each stage block's spatial chunk IS one micro-batch
  (cross-tile BN stats then equal the single-device per-micro-batch stats);
- junction='gather' for the equality test (batch_split shrinks the tail
  per-device batch, legitimately changing tail BN stats — covered by a
  separate consistency test).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.layer_ctx import SpatialCtx
from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.models.amoebanet import amoebanetd
from mpi4dl_tpu.models.resnet import get_resnet_v2
from mpi4dl_tpu.parallel.sp_pipeline import (
    SPPipeline,
    init_sp_pipeline_state,
    make_sp_pipeline_train_step,
)
from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step


def _mk(model, params, mesh, sp, split_size, parts, mb, junction, data=1):
    spp = SPPipeline.build(model, params, split_size, sp, mb, junction=junction)
    opt = Optimizer("sgd", lr=0.01)
    step = make_sp_pipeline_train_step(
        spp, opt, mesh, parts, with_data_axis=(data > 1)
    )
    state = init_sp_pipeline_state(spp, params, opt, mesh)
    return spp, opt, step, state


def test_sp_pipeline_matches_single_device(devices8):
    """stage=2 x spw=2 (vertical 2-tile SP region, 2-stage tail pipeline)."""
    model = get_resnet_v2((2, 32, 32, 3), depth=11, num_classes=10)
    model.spatial_until = 2
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_w="spw", grid_w=2)
    mesh = build_mesh(MeshSpec(data=1, stage=2, sph=1, spw=2), jax.devices()[:4])

    parts, mb = 2, 2  # batch 4; parts == split_size
    spp, opt, step, state = _mk(model, params, mesh, sp, 2, parts, mb, "gather")

    ref_step = make_train_step(model, opt, parts=parts)
    ref_state = TrainState.create(params, opt)

    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 3], jnp.int32)

    for _ in range(2):
        ref_state, m_ref = ref_step(ref_state, x, y)
        state, m = step(state, x, y)
        np.testing.assert_allclose(float(m_ref["loss"]), float(m["loss"]), rtol=1e-4)
        np.testing.assert_allclose(
            float(m_ref["accuracy"]), float(m["accuracy"]), rtol=1e-5
        )

    got = spp.unpack_all(np.asarray(state.sp_buf), np.asarray(state.tail_buf))
    want = jax.tree.leaves(ref_state.params)
    for a, b in zip(jax.tree.leaves(got), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5)


def test_sp_pipeline_batch_split_junction(devices8):
    """LOCAL_DP_LP junction: tail batch-split over tiles.  BN stats differ
    from single-device by design (per-shard, like the reference's per-rank
    DDP BN), so check finiteness + cross-step decrease + replica agreement."""
    model = get_resnet_v2((4, 32, 32, 3), depth=11, num_classes=10)
    model.spatial_until = 2
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2)
    mesh = build_mesh(MeshSpec(data=1, stage=2, sph=2, spw=2), jax.devices()[:8])

    parts, mb = 2, 4  # batch 8; microbatch 4 splits over 4 tiles
    spp, opt, step, state = _mk(model, params, mesh, sp, 2, parts, mb, "batch_split")

    x = jax.random.normal(jax.random.key(2), (8, 32, 32, 3))
    y = jnp.arange(8, dtype=jnp.int32) % 10
    losses = []
    for _ in range(3):
        state, m = step(state, x, y)
        assert np.isfinite(float(m["loss"]))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_sp_pipeline_batch_split_exact_bn_free(devices8):
    """ADVICE r1: pin the gradient-combine rule for the batch_split junction
    too.  On a BN-free model the junction's batch re-sharding is numerically
    transparent, so SP×PP with batch_split must reproduce single-device SGD
    exactly — any mis-scaled collective transpose would show up here."""
    from mpi4dl_tpu.cells import CellModel, LayerCell
    from mpi4dl_tpu.layers import Conv2d, Dense, Flatten, ReLU

    cells = [
        LayerCell([Conv2d(3, 8, 3), ReLU()], name="c0"),
        LayerCell([Conv2d(8, 8, 3, stride=2), ReLU()], name="c1"),
        LayerCell([Conv2d(8, 8, 3), ReLU()], name="c2"),
        LayerCell([Flatten(), Dense(8 * 16 * 16, 10)], name="head"),
    ]
    model = CellModel(cells, (4, 32, 32, 3), 10, spatial_until=2)
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2)
    mesh = build_mesh(MeshSpec(data=1, stage=2, sph=2, spw=2), jax.devices()[:8])

    parts, mb = 2, 4  # batch 8; each stage chunk of 4 splits over 4 tiles
    spp, opt, step, state = _mk(model, params, mesh, sp, 2, parts, mb, "batch_split")
    ref_step = make_train_step(model, opt, parts=parts)
    ref_state = TrainState.create(params, opt)

    x = jax.random.normal(jax.random.key(5), (8, 32, 32, 3))
    y = jnp.arange(8, dtype=jnp.int32) % 10
    for _ in range(2):
        ref_state, m_ref = ref_step(ref_state, x, y)
        state, m = step(state, x, y)
        np.testing.assert_allclose(float(m_ref["loss"]), float(m["loss"]), rtol=1e-4)

    got = spp.unpack_all(np.asarray(state.sp_buf), np.asarray(state.tail_buf))
    want = jax.tree.leaves(ref_state.params)
    for a, b in zip(jax.tree.leaves(got), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5)


def test_sp_pipeline_amoebanet_tuple_junction(devices8):
    """AmoebaNet's (x, skip) tuple state must cross the SP→LP junction and
    the stage handoffs (reference MULTIPLE_INPUT support)."""
    model = amoebanetd((2, 64, 64, 3), num_classes=10, num_layers=3, num_filters=64)
    model.spatial_until = 4  # stem + 2 reductions + 1 normal cell spatial
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_w="spw", grid_w=2)
    mesh = build_mesh(MeshSpec(data=1, stage=2, sph=1, spw=2), jax.devices()[:4])

    parts, mb = 2, 1
    spp, opt, step, state = _mk(model, params, mesh, sp, 2, parts, mb, "gather")
    # The junction really carries a tuple
    assert len(spp.tail_part.act_packs[0].shapes) > 1

    ref_step = make_train_step(model, opt, parts=parts)
    ref_state = TrainState.create(params, opt)
    x = jax.random.normal(jax.random.key(3), (2, 64, 64, 3))
    y = jnp.array([0, 1], jnp.int32)
    ref_state, m_ref = ref_step(ref_state, x, y)
    state, m = step(state, x, y)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m["loss"]), rtol=1e-4)


def test_sp_pipeline_with_data_parallel(devices8):
    """DP x SP x PP: 2-way data x 2-stage x 2-tile on 8 devices."""
    model = get_resnet_v2((2, 32, 32, 3), depth=11, num_classes=10)
    model.spatial_until = 2
    params, _ = model.init(jax.random.key(0))
    sp = SpatialCtx(axis_w="spw", grid_w=2)
    mesh = build_mesh(MeshSpec(data=2, stage=2, sph=1, spw=2), jax.devices()[:8])

    parts, mb = 2, 2  # per-replica batch 4
    spp, opt, step, state = _mk(
        model, params, mesh, sp, 2, parts, mb, "gather", data=2
    )
    ref_step = make_train_step(model, opt, parts=4)  # 8 imgs / mb 2
    ref_state = TrainState.create(params, opt)
    x = jax.random.normal(jax.random.key(4), (8, 32, 32, 3))
    y = jnp.arange(8, dtype=jnp.int32) % 10
    ref_state, m_ref = ref_step(ref_state, x, y)
    state, m = step(state, x, y)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m["loss"]), rtol=1e-4)
