"""BN running statistics + eval path.

The reference gets running stats implicitly from nn.BatchNorm2d (e.g.
resnet_spatial.py:149-163: plain torch BN inside spatial layers); its eval
path is torch's .eval().  Here the running buffers live in params and are
updated through the bn_sink mechanism by every step builder; these tests pin

- the torch update rule (momentum-weighted, unbiased running variance),
- microbatch (parts>1) and remat paths producing the same updates,
- eval (train=False) using the running stats,
- SP training updating stats identically to single-device training.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.cells import CellModel, LayerCell
from mpi4dl_tpu.layer_ctx import spatial_ctx_for
from mpi4dl_tpu.layers import BatchNorm, Conv2d, Dense, Flatten, ReLU
from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.models.resnet import get_resnet_v2
from mpi4dl_tpu.train import (
    Optimizer,
    TrainState,
    make_eval_step,
    make_spatial_eval_step,
    make_spatial_train_step,
    make_train_step,
)


def _tiny_bn_model(n=4, hw=8, c=3, classes=5):
    cells = [
        LayerCell([Conv2d(c, 8, 3), BatchNorm(8), ReLU()], name="body"),
        LayerCell([Flatten(), Dense(8 * hw * hw, classes)], name="head"),
    ]
    return CellModel(cells, (n, hw, hw, c), classes)


def _bn_stats(params):
    # body cell -> layer 1 (BatchNorm) params dict
    return params[0][1]["mean"], params[0][1]["var"]


def test_running_stats_torch_rule():
    """One step: running = (1-m)*init + m*batch_stat, var unbiased."""
    model = _tiny_bn_model()
    params, _ = model.init(jax.random.key(0))
    opt = Optimizer("sgd", lr=0.0)  # lr 0: only stats change
    step = make_train_step(model, opt)
    state = TrainState.create(params, opt)
    x = jax.random.normal(jax.random.key(1), (4, 8, 8, 3)) * 2 + 1
    y = jnp.zeros((4,), jnp.int32)

    # Expected batch stats: BN input = conv output.
    from mpi4dl_tpu.layer_ctx import TRAIN_CTX

    conv_out = model.cells[0].layers[0].apply(params[0][0], x, TRAIN_CTX)
    bx = np.asarray(conv_out, np.float64)
    bmean = bx.mean(axis=(0, 1, 2))
    n = bx.size // bx.shape[-1]
    bvar_unbiased = bx.var(axis=(0, 1, 2)) * n / (n - 1)

    state, _ = step(state, x, y)
    mean, var = _bn_stats(state.params)
    np.testing.assert_allclose(np.asarray(mean), 0.1 * bmean, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(var), 0.9 * 1.0 + 0.1 * bvar_unbiased, rtol=1e-4
    )


def test_parts_and_remat_match():
    """parts=2 updates equal the averaged-microbatch rule; remat path equals
    the plain path bit-for-bit."""
    model = _tiny_bn_model()
    params, _ = model.init(jax.random.key(0))
    opt = Optimizer("sgd", lr=0.01)
    x = jax.random.normal(jax.random.key(2), (4, 8, 8, 3))
    y = jnp.array([0, 1, 2, 3], jnp.int32)

    s_plain = TrainState.create(params, opt)
    s_remat = TrainState.create(params, opt)
    step_plain = make_train_step(model, opt)
    step_remat = make_train_step(model, opt, remat=True)
    s_plain, _ = step_plain(s_plain, x, y)
    s_remat, _ = step_remat(s_remat, x, y)
    for a, b in zip(jax.tree.leaves(s_plain.params), jax.tree.leaves(s_remat.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)

    # parts=2: stats = momentum update with batch stats averaged over the two
    # microbatches (linearity of the momentum rule).
    step_mb = make_train_step(model, opt, parts=2)
    s_mb = TrainState.create(params, opt)
    s_mb, _ = step_mb(s_mb, x, y)
    m_mb, v_mb = _bn_stats(s_mb.params)
    assert not np.allclose(np.asarray(m_mb), 0.0)  # stats moved
    assert not np.allclose(np.asarray(v_mb), 1.0)


def test_eval_uses_running_stats():
    model = _tiny_bn_model()
    params, _ = model.init(jax.random.key(0))
    opt = Optimizer("sgd", lr=0.01)
    step = make_train_step(model, opt)
    estep = make_eval_step(model)
    state = TrainState.create(params, opt)
    x = jax.random.normal(jax.random.key(3), (4, 8, 8, 3)) + 2.0
    y = jnp.array([0, 1, 2, 3], jnp.int32)

    m0 = estep(state.params, x, y)
    for _ in range(5):
        state, _ = step(state, x, y)
    m1 = estep(state.params, x, y)
    mean, var = _bn_stats(state.params)
    assert not np.allclose(np.asarray(mean), 0.0), "running mean never updated"
    assert not np.allclose(np.asarray(var), 1.0), "running var never updated"
    assert float(m1["loss"]) != float(m0["loss"])
    assert np.isfinite(float(m1["loss"]))


def test_spatial_stats_match_single_device(devices8):
    """SP training (cross-tile BN) updates running stats identically to
    single-device training; SP eval then matches single-device eval."""
    sp = spatial_ctx_for("square", 4)
    mesh = build_mesh(MeshSpec(sph=2, spw=2), devices8)
    model = get_resnet_v2((4, 32, 32, 3), depth=11, num_classes=10)
    params, _ = model.init(jax.random.key(0))
    opt = Optimizer("sgd", lr=0.01)
    x = jax.random.normal(jax.random.key(4), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 3], jnp.int32)

    s_ref = TrainState.create(params, opt)
    s_sp = TrainState.create(params, opt)
    step_ref = make_train_step(model, opt)
    step_sp = make_spatial_train_step(model, opt, mesh, sp)
    for _ in range(2):
        s_ref, _ = step_ref(s_ref, x, y)
        s_sp, _ = step_sp(s_sp, x, y)
    for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_sp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-4)

    e_ref = make_eval_step(model)(s_ref.params, x, y)
    e_sp = make_spatial_eval_step(model, mesh, sp)(s_sp.params, x, y)
    np.testing.assert_allclose(
        float(e_ref["loss"]), float(e_sp["loss"]), rtol=1e-3
    )
    np.testing.assert_allclose(
        float(e_ref["accuracy"]), float(e_sp["accuracy"]), rtol=1e-6
    )


def _two_steps_plain_and_fine(batch, dtype):
    """Two SGD steps of a 3-cell AmoebaNet-D from one state, plain and under
    remat="fine", computed in ``dtype``: (state, metrics) of each."""
    from mpi4dl_tpu.models.amoebanet import amoebanetd

    model = amoebanetd((batch, 32, 32, 3), num_classes=5, num_layers=3,
                       num_filters=16)
    params, _ = model.init(jax.random.key(0))
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    opt = Optimizer("sgd", lr=0.01)
    x = jax.random.normal(jax.random.key(3), (batch, 32, 32, 3), jnp.float32)
    y = jnp.arange(batch, dtype=jnp.int32) % 5

    s_plain = TrainState.create(params, opt)
    s_fine = TrainState.create(params, opt)
    step_plain = make_train_step(model, opt, compute_dtype=dtype)
    step_fine = make_train_step(model, opt, remat="fine", compute_dtype=dtype)
    for _ in range(2):
        s_plain, m_p = step_plain(s_plain, x, y)
        s_fine, m_f = step_fine(s_fine, x, y)
    return (s_plain, m_p), (s_fine, m_f)


def test_fine_remat_matches_plain_on_amoebanet():
    """remat="fine" (per-op checkpoints inside AmoebaCells, ctx.remat_ops)
    must reproduce the plain step's updates — incl. BN running stats crossing
    the nested checkpoint boundaries.

    In float64 since PR 32, on the input and to the limits the test always
    had.  The cells' 1×1 convolutions are matrix products now, and a replayed
    product is the same op but not the same bits on XLA:CPU: the first
    gradients differ by one rounding of their largest entry (1.2e-7 in
    float32, 2.2e-16 in float64; PERF.md section 6, PR 32).  At batch 2 this
    net's BatchNorms over 2 to 8 values make 1.7e-5 of the second float32
    loss out of that; in float64 a rounding is far under the limits, and a
    replay that used a wrong value would not be."""
    with jax.enable_x64(True):
        (s_plain, m_p), (s_fine, m_f) = _two_steps_plain_and_fine(
            2, jnp.float64)
    np.testing.assert_allclose(
        float(m_p["loss"]), float(m_f["loss"]), rtol=1e-6
    )
    for a, b in zip(
        jax.tree.leaves(s_plain.params), jax.tree.leaves(s_fine.params)
    ):
        assert a.dtype == jnp.float64
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )


def test_fine_remat_matches_plain_on_amoebanet_in_float32_at_batch_8():
    """The same two steps in float32, at the smallest batch whose BatchNorms
    (over 8 to 32 values) do not amplify one rounding of a replayed product
    past a float32 comparison: the second losses read 1.1e-7 apart and the
    parameters 0.93 of 1e-7 + 1e-6·|b| here; held to ten times that."""
    (s_plain, m_p), (s_fine, m_f) = _two_steps_plain_and_fine(8, jnp.float32)
    np.testing.assert_allclose(
        float(m_p["loss"]), float(m_f["loss"]), rtol=1e-6
    )
    for a, b in zip(
        jax.tree.leaves(s_plain.params), jax.tree.leaves(s_fine.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_sqrt_remat_matches_plain_on_resnet():
    """remat="sqrt" (two-level group checkpointing) must reproduce the plain
    step exactly on a deep ResNet (many cell boundaries)."""
    from mpi4dl_tpu.models.resnet import get_resnet_v2

    model = get_resnet_v2((2, 32, 32, 3), depth=29, num_classes=5)
    params, _ = model.init(jax.random.key(0))
    opt = Optimizer("sgd", lr=0.01)
    x = jax.random.normal(jax.random.key(4), (2, 32, 32, 3))
    y = jnp.array([0, 1], jnp.int32)

    s_plain = TrainState.create(params, opt)
    s_sqrt = TrainState.create(params, opt)
    step_plain = make_train_step(model, opt)
    step_sqrt = make_train_step(model, opt, remat="sqrt")
    for _ in range(2):
        s_plain, m_p = step_plain(s_plain, x, y)
        s_sqrt, m_s = step_sqrt(s_sqrt, x, y)
    np.testing.assert_allclose(float(m_p["loss"]), float(m_s["loss"]), rtol=1e-6)
    for a, b in zip(
        jax.tree.leaves(s_plain.params), jax.tree.leaves(s_sqrt.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )
