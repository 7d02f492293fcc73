"""Pipeline engine (LP/PP) correctness: the SPMD GPipe scan must produce the
same loss and the same parameter updates as single-device micro-batched
gradient accumulation (the reference can only eyeball losses; SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.cells import split_even
from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.models.amoebanet import amoebanetd
from mpi4dl_tpu.models.resnet import get_resnet_v2
from mpi4dl_tpu.parallel.partition import StagePartition
from mpi4dl_tpu.parallel.pipeline import (
    PipelineState,
    init_pipeline_state,
    make_pipeline_train_step,
)
from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step


def _setup(model, batch, parts, split_size, devices, balance=None, data=1):
    params, _ = model.init(jax.random.key(0))
    mesh = build_mesh(MeshSpec(data=data, stage=split_size), devices)
    part = StagePartition.build(
        model, params, split_size, (batch // parts // data, *model.in_shape[1:]),
        balance=balance,
    )
    opt = Optimizer("sgd", lr=0.01)
    step = make_pipeline_train_step(part, opt, mesh, parts,
                                    with_data_axis=(data > 1))
    state = init_pipeline_state(part, params, opt, mesh)
    return params, part, opt, step, state


@pytest.mark.parametrize("parts,split_size", [(1, 2), (2, 4), (4, 2)])
def test_pipeline_matches_single_device(devices8, parts, split_size):
    model = get_resnet_v2((4, 32, 32, 3), depth=11, num_classes=10)
    params, part, opt, pstep, pstate = _setup(model, 4, parts, split_size, devices8)

    ref_step = make_train_step(model, opt, parts=parts)
    ref_state = TrainState.create(params, opt)

    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 3], jnp.int32)

    for _ in range(2):
        ref_state, m_ref = ref_step(ref_state, x, y)
        pstate, m_p = pstep(pstate, x, y)
        np.testing.assert_allclose(
            float(m_ref["loss"]), float(m_p["loss"]), rtol=1e-4
        )

    # Parameter buffers must match the reference step's updated params.
    # atol: BN's single-pass fused statistics (layers.py) shift reduction
    # order between the packed-buffer and reference executions.
    got = part.unpack_params(np.asarray(pstate.param_buf))
    want = jax.tree.leaves(ref_state.params)
    for a, b in zip(jax.tree.leaves(got), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=5e-5)


def test_pipeline_amoebanet_tuple_state(devices8):
    """(x, skip) tuple activations must cross stage boundaries (the
    reference's MULTIPLE_INPUT/OUTPUT support, mp_pipeline.py:215-223)."""
    model = amoebanetd((2, 64, 64, 3), num_classes=10, num_layers=3, num_filters=64)
    params, part, opt, pstep, pstate = _setup(model, 2, 2, 4, devices8)
    # Verify at least one stage boundary carries a tuple
    assert any(len(p.shapes) > 1 for p in part.act_packs[1:])

    ref_step = make_train_step(model, opt, parts=2)
    ref_state = TrainState.create(params, opt)
    x = jax.random.normal(jax.random.key(2), (2, 64, 64, 3))
    y = jnp.array([0, 1], jnp.int32)
    ref_state, m_ref = ref_step(ref_state, x, y)
    pstate, m_p = pstep(pstate, x, y)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_p["loss"]), rtol=1e-4)


def test_pipeline_with_balance(devices8):
    model = get_resnet_v2((2, 32, 32, 3), depth=29, num_classes=10)
    params, part, opt, pstep, pstate = _setup(
        model, 2, 2, 4, devices8, balance=[2, 3, 3, 3]
    )
    assert part.ranges == [(0, 2), (2, 5), (5, 8), (8, 11)]
    x = jax.random.normal(jax.random.key(3), (2, 32, 32, 3))
    y = jnp.array([0, 1], jnp.int32)
    pstate, m = pstep(pstate, x, y)
    assert np.isfinite(float(m["loss"]))


def test_pipeline_plus_data_parallel(devices8):
    """DP×PP: 2-way data × 4-stage pipeline on 8 devices; loss must match
    single-device accumulation over the full batch."""
    model = get_resnet_v2((4, 32, 32, 3), depth=11, num_classes=10)
    params, part, opt, pstep, pstate = _setup(
        model, 8, 2, 4, devices8, data=2
    )
    ref_step = make_train_step(model, opt, parts=4)  # 8 images / 2 per micro
    ref_state = TrainState.create(params, opt)
    x = jax.random.normal(jax.random.key(4), (8, 32, 32, 3))
    y = jnp.arange(8, dtype=jnp.int32) % 10
    ref_state, m_ref = ref_step(ref_state, x, y)
    pstate, m_p = pstep(pstate, x, y)
    # DP halves are different micro-batch groupings of the same batch; losses
    # match because BN stats are per-micro-batch of equal size in both.
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_p["loss"]), rtol=1e-4)
