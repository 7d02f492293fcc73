"""GEMS bidirectional schedule: must equal single-device gradient
accumulation over all 2*times micro-batch groups (the reference's mirrored
allreduce makes both replicas see the combined gradient; here there is one
weight buffer, so equality is exact by construction — verify it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.models.resnet import get_resnet_v2
from mpi4dl_tpu.parallel.gems import make_gems_train_step
from mpi4dl_tpu.parallel.partition import StagePartition
from mpi4dl_tpu.parallel.pipeline import init_pipeline_state
from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step


@pytest.mark.parametrize("times,parts", [(1, 1), (1, 2), (2, 1)])
def test_gems_matches_single_device(devices8, times, parts):
    S = 4
    mb = 1
    groups = 2 * times
    B = groups * parts * mb
    model = get_resnet_v2((mb, 32, 32, 3), depth=11, num_classes=10)
    params, _ = model.init(jax.random.key(0))
    mesh = build_mesh(MeshSpec(stage=S), devices8)
    part = StagePartition.build(model, params, S, (mb, 32, 32, 3))
    opt = Optimizer("sgd", lr=0.01)

    gstep = make_gems_train_step(part, opt, mesh, parts, times=times)
    gstate = init_pipeline_state(part, params, opt, mesh)

    # Reference: accumulate over all groups*parts micro-batches of size mb.
    ref_step = make_train_step(model, opt, parts=groups * parts)
    ref_state = TrainState.create(params, opt)

    x = jax.random.normal(jax.random.key(1), (B, 32, 32, 3))
    y = (jnp.arange(B) % 10).astype(jnp.int32)

    for _ in range(2):
        ref_state, m_ref = ref_step(ref_state, x, y)
        gstate, m_g = gstep(gstate, x, y)
        np.testing.assert_allclose(float(m_ref["loss"]), float(m_g["loss"]), rtol=1e-4)

    got = part.unpack_params(np.asarray(gstate.param_buf))
    want = jax.tree.leaves(ref_state.params)
    for a, b in zip(jax.tree.leaves(got), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5)


def test_gems_amoebanet_smoke(devices8):
    from mpi4dl_tpu.models.amoebanet import amoebanetd

    model = amoebanetd((1, 64, 64, 3), num_classes=10, num_layers=3, num_filters=64)
    params, _ = model.init(jax.random.key(0))
    mesh = build_mesh(MeshSpec(stage=4), devices8)
    part = StagePartition.build(model, params, 4, (1, 64, 64, 3))
    opt = Optimizer("sgd", lr=0.01)
    gstep = make_gems_train_step(part, opt, mesh, parts=2, times=1)
    gstate = init_pipeline_state(part, params, opt, mesh)
    x = jax.random.normal(jax.random.key(2), (4, 64, 64, 3))
    y = jnp.array([0, 1, 2, 3], jnp.int32)
    gstate, m = gstep(gstate, x, y)
    assert np.isfinite(float(m["loss"]))
