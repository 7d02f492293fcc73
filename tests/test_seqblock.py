"""Sequence-parallel transformer block (models/seqblock.py): forward and a
full CP training step must match the single-device (replicated) execution
exactly — the model-level proof of the long-context path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.models.seqblock import SeqBlock, make_seq_cp_train_step


def _data(b=2, t=32, d=16, key=0):
    k1, k2 = jax.random.split(jax.random.key(key))
    x = jax.random.normal(k1, (b, t, d))
    y = jax.random.normal(k2, (b, t, d))
    return x, y


@pytest.mark.parametrize("causal", [False, True])
def test_seqblock_forward_sharded_matches_replicated(devices8, causal):
    from mpi4dl_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    n = 4
    mesh = build_mesh(MeshSpec(spw=n), jax.devices()[:n])
    blk = SeqBlock(d_model=16, heads=2, causal=causal)
    params = blk.init(jax.random.key(1))
    x, _ = _data()

    ref = blk.apply(params, x)
    spec = P(None, "spw", None)
    out = jax.jit(
        shard_map(
            lambda t_: blk.apply(params, t_, "spw", n),
            mesh=mesh, in_specs=spec, out_specs=spec,
        )
    )(x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_seq_cp_train_step_matches_single_device(devices8):
    n = 4
    mesh = build_mesh(MeshSpec(spw=n), jax.devices()[:n])
    blocks = [SeqBlock(16, 2), SeqBlock(16, 2)]
    params = [b.init(jax.random.key(i)) for i, b in enumerate(blocks)]
    x, y = _data()
    lr = 0.05

    step = make_seq_cp_train_step(blocks, mesh, "spw", n, lr)

    def ref_loss(params_list, x, y):
        h = x
        for blk, p in zip(blocks, params_list):
            h = blk.apply(p, h)
        err = (h - y).astype(jnp.float32)
        return jnp.mean(err * err)

    ref_params = params
    cp_params = params
    losses_ref, losses_cp = [], []
    for _ in range(3):
        loss_r, grads = jax.value_and_grad(ref_loss)(ref_params, x, y)
        ref_params = jax.tree.map(lambda p, g: p - lr * g, ref_params, grads)
        cp_params, loss_c = step(cp_params, x, y)
        losses_ref.append(float(loss_r))
        losses_cp.append(float(loss_c))
    np.testing.assert_allclose(losses_cp, losses_ref, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(cp_params), jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )
    assert losses_cp[-1] < losses_cp[0]  # it actually trains
