"""Model construction + forward smoke tests (small geometries), including the
shape-list inference that replaces the reference's two-phase probe."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.cells import split_even
from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.models.amoebanet import amoebanetd
from mpi4dl_tpu.models.resnet import get_resnet_v1, get_resnet_v2

CTX = ApplyCtx(train=True)


def test_resnet_v1_forward():
    model = get_resnet_v1((2, 32, 32, 3), depth=20, num_classes=10)
    params, shapes = model.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    y = model.apply(params, x, CTX)
    assert y.shape == (2, 10)
    assert shapes[-1] == (2, 10)


def test_resnet_v2_forward_and_shapes():
    model = get_resnet_v2((2, 32, 32, 3), depth=29, num_classes=10)
    params, shapes = model.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    y = model.apply(params, x, CTX)
    assert y.shape == (2, 10)
    # eval_shape-based inference agrees with init-time propagation
    inferred = model.out_shapes(params)
    assert inferred == shapes


def test_resnet_cell_count_matches_depth_formula():
    # depth 9n+2 → n cells per stage * 3 + stem + head (reference get_depth)
    model = get_resnet_v2((1, 32, 32, 3), depth=29)
    assert len(model.cells) == 3 * 3 + 2


def test_amoebanet_forward_tuple_state():
    model = amoebanetd((2, 64, 64, 3), num_classes=10, num_layers=3, num_filters=64)
    params, shapes = model.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 64, 64, 3))
    y = model.apply(params, x, CTX)
    assert y.shape == (2, 10)
    # intermediate cells carry (x, skip) tuple state
    assert isinstance(shapes[1], tuple) and isinstance(shapes[1][0], tuple)


def test_amoebanet_cell_count():
    # stem + 2 reduction stems + 3*(num_layers//3) normal + 2 reduction + head
    model = amoebanetd((1, 64, 64, 3), num_layers=6, num_filters=64)
    assert len(model.cells) == 1 + 2 + 6 + 2 + 1


def test_split_even_matches_reference_semantics():
    assert split_even(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
    assert split_even(9, 3, balance=[2, 3, 4]) == [(0, 2), (2, 5), (5, 9)]


def test_softmax_in_model_flag():
    m = get_resnet_v2((1, 32, 32, 3), depth=11, softmax_in_model=True)
    params, _ = m.init(jax.random.key(0))
    y = m.apply(params, jnp.ones((1, 32, 32, 3)), CTX)
    np.testing.assert_allclose(float(jnp.sum(y)), 1.0, rtol=1e-5)


def test_lane_pad_function_preserving(monkeypatch):
    """MPI4DL_LANE_PAD=1 pads bottleneck mid-channels to 128 lanes with
    zero weights — losses, grads, and running stats must match the unpadded
    model exactly (the padding is dead compute, not a model change)."""
    from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step

    def build(flag):
        if flag:
            monkeypatch.setenv("MPI4DL_LANE_PAD", "1")
        else:
            monkeypatch.delenv("MPI4DL_LANE_PAD", raising=False)
        m = amoebanetd((2, 32, 32, 3), num_classes=10, num_layers=3,
                       num_filters=16)
        # Same init stream: params are true-shaped in both builds.
        params, _ = m.init(jax.random.key(0))
        return m, params

    m0, p0 = build(False)
    m1, p1 = build(True)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        assert a.shape == b.shape
    # The padded build really engages (mid = 16//4 = 4 -> 128).
    assert any(
        getattr(l, "lane_pad_out", 0) == 128
        for c in m1.cells for op in getattr(c, "ops", [])
        for l in getattr(op, "layers", [])
    )
    # Function preservation proved in f64, where the only remaining
    # difference — summation-order reassociation from the widened
    # contraction — is ~1e-15: the padded channels contribute exact zeros.
    # Gradients likewise (grad-of-pad = slice): measured max |Δgrad| ~8e-10
    # against grad magnitudes ~124 on this config.  (An fp32 multi-step
    # trajectory comparison is meaningless here: this toy config is
    # chaotic — 1e-7 reassociation noise bifurcates it.)
    with jax.enable_x64(True):
        x64 = jax.random.normal(jax.random.key(1), (2, 32, 32, 3), jnp.float64)
        yt = jnp.arange(2, dtype=jnp.int32)
        p64_0 = jax.tree.map(lambda a: a.astype(jnp.float64), p0)
        p64_1 = jax.tree.map(lambda a: a.astype(jnp.float64), p1)
        y0 = m0.apply(p64_0, x64, CTX)
        y1 = m1.apply(p64_1, x64, CTX)
        np.testing.assert_allclose(
            np.asarray(y0), np.asarray(y1), rtol=1e-10, atol=1e-12
        )

        def loss_of(m):
            def f(p):
                logits = m.apply(p, x64, CTX)
                lp = jax.nn.log_softmax(logits)
                return -jnp.mean(jnp.take_along_axis(lp, yt[:, None], 1))
            return f

        g0 = jax.grad(loss_of(m0))(p64_0)
        g1 = jax.grad(loss_of(m1))(p64_1)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-7, atol=1e-8
            )
    # fp32 train-step plumbing (stat-sink slicing under jit) runs and the
    # first losses agree to fp32 noise.
    opt = Optimizer("sgd", lr=0.01)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    y = jnp.arange(2, dtype=jnp.int32)
    s0, s1 = TrainState.create(p0, opt), TrainState.create(p1, opt)
    step0, step1 = make_train_step(m0, opt), make_train_step(m1, opt)
    s0, met0 = step0(s0, x, y)
    s1, met1 = step1(s1, x, y)
    np.testing.assert_allclose(
        float(met0["loss"]), float(met1["loss"]), rtol=2e-3
    )


def _packed_fine_remat_against_plain(monkeypatch, batch, dtype):
    """Two SGD steps of a 3-cell AmoebaNet-D whose DAG states are all
    lane-packed, under remat='fine' and plain, computed in ``dtype``: the
    loss of every step to 1e-6 and the parameters to 1e-5 (and 1e-7)."""
    from mpi4dl_tpu import cells as C
    from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step

    monkeypatch.setattr(C, "_PACK_MIN_ELEMS", 1)
    model = amoebanetd((batch, 32, 32, 3), num_classes=10, num_layers=3,
                       num_filters=16)
    params, _ = model.init(jax.random.key(0))
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    # Packing really engages on these DAG states (W*C = 16*16=256 | 128).
    assert C._pack_meta((batch, 16, 16, 16)) == (16, 16)
    opt = Optimizer("sgd", lr=0.01)
    x = jax.random.normal(jax.random.key(1), (batch, 32, 32, 3), jnp.float32)
    y = jnp.arange(batch, dtype=jnp.int32)
    s_f = TrainState.create(params, opt)
    s_o = TrainState.create(params, opt)
    step_f = make_train_step(model, opt, remat="fine", compute_dtype=dtype)
    step_o = make_train_step(model, opt, compute_dtype=dtype)
    for _ in range(2):
        s_f, m_f = step_f(s_f, x, y)
        s_o, m_o = step_o(s_o, x, y)
        np.testing.assert_allclose(
            float(m_f["loss"]), float(m_o["loss"]), rtol=1e-6
        )
    for a, b in zip(jax.tree.leaves(s_f.params), jax.tree.leaves(s_o.params)):
        assert a.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_amoebanet_fine_remat_packed_states_exact(monkeypatch):
    """remat='fine' (per-op checkpoints with lane-packed DAG states) must
    be equivalent to the no-remat path: packing is a reshape and checkpoint
    recompute replays identical ops.

    In float64 since PR 32, on the batch-2 input and to the limits the test
    always had.  Identical ops are not identical bits any more: the cells'
    1×1 convolutions are matrix products, and XLA:CPU's replayed product
    differs from the first by one rounding (the first gradients by 1.2e-7 of
    their largest entry in float32, by 2.9e-16 in float64; PERF.md section 6,
    PR 32), which this net's batch-2 BatchNorms over 2 to 8 values turn into
    7.6e-5 of the second float32 loss.  A rounding of float64 is far under
    the limits; a replay that used a wrong value would not be."""
    with jax.enable_x64(True):
        _packed_fine_remat_against_plain(monkeypatch, 2, jnp.float64)


def test_amoebanet_fine_remat_packed_states_in_float32_at_batch_8(monkeypatch):
    """The same two steps in float32, at the smallest batch whose BatchNorms
    (over 8 to 32 values) do not amplify one rounding of a replayed product
    past the same limits: both losses read equal and the parameters 0.90 of
    1e-7 + 1e-6·|b| here."""
    _packed_fine_remat_against_plain(monkeypatch, 8, jnp.float32)


def test_factorized_reduce_keeps_its_fusion_barrier():
    """XLA:TPU miscompiled the bf16 backward across FactorizedReduce's output
    (NaN gradients on the v5e, PR 22); the CPU cannot reproduce that, so this
    pins the barrier that fixed it.  chip_smoke.py is the test that runs it."""
    from mpi4dl_tpu.models.amoebanet import FactorizedReduce

    fr = FactorizedReduce(8, 16)
    params, out_shape = fr.init(jax.random.key(0), (1, 16, 16, 8))
    x = jnp.ones((1, 16, 16, 8), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, h: fr.apply(p, h, CTX))(params, x)
    assert "optimization_barrier" in str(jaxpr)
    assert out_shape == (1, 8, 8, 16)


@pytest.mark.parametrize("name,sizes", [
    ("resnet", dict(image_size=32, num_layers=1, num_classes=10)),
    ("amoebanet", dict(image_size=64, num_layers=3, num_filters=32,
                       num_classes=10)),
    ("lfm2_moe", dict(num_layers=2, seq_len=64, vocab_size=512,
                      experts_held=8)),
    ("deepseek_v3", dict(num_layers=2, seq_len=64, vocab_size=512,
                         experts_held=16)),
    ("granitemoehybrid", dict(num_layers=6, seq_len=256, vocab_size=512)),
    ("keye_vl2", dict(num_layers=1, seq_len=64, vocab_size=512,
                      experts_held=16)),
    ("ouro", dict(num_layers=1, seq_len=64, vocab_size=512)),
])
def test_every_convolution_of_a_model_takes_one_of_the_five_paths(
        monkeypatch, rec, name, sizes):
    """Each model of ``models.MODELS`` at its smallest size, every
    ``MPI4DL_*`` switch unset, traced on shapes: every ``Conv2d`` site the
    trace applied is counted by ``conv_paths`` under ``wfold``, ``hstripe``,
    ``phase``, ``xla`` or ``dot``, the recorder's whole vocabulary, so no
    site went down a path that has no name (the count drops what it cannot
    name).  Fails if a sixth arm is put into ``Conv2d.apply``, or a model is
    entered that hands a convolution over some other way."""
    import os

    from mpi4dl_tpu import layers as L
    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.models import MODELS, build_model
    from mpi4dl_tpu.obs import spans

    assert set(MODELS) == {"resnet", "amoebanet", "lfm2_moe", "deepseek_v3",
                           "granitemoehybrid", "keye_vl2", "ouro"}
    assert spans.CONV_PATHS == ("wfold", "hstripe", "phase", "xla", "dot")
    for key in [k for k in os.environ if k.startswith("MPI4DL_")]:
        monkeypatch.delenv(key)
    applied = set()
    apply = L.Conv2d.apply
    monkeypatch.setattr(
        L.Conv2d, "apply",
        lambda self, *a: applied.add(id(self)) or apply(self, *a))
    model = build_model(ParallelConfig(model=name, batch_size=2, **sizes))
    params = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    dtype = jnp.int32 if MODELS[name][0] == "tokens" else jnp.float32
    jax.eval_shape(lambda p, x: model.apply(p, x, CTX), params,
                   jax.ShapeDtypeStruct(model.in_shape, dtype))
    assert sum(rec.conv_paths().values()) == len(applied)
    assert bool(applied) == (MODELS[name][0] == "image")
