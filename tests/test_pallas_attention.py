"""Pallas blockwise attention (ops/pallas_attention.py) — kernel vs einsum
reference in interpret mode, and the flash ring path vs the einsum ring path
on the 8-device CPU mesh (ops/ring.py use_flash=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P

from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.ops.pallas_attention import (
    block_flash, flash_attention_local, mlo_merge,
)
from mpi4dl_tpu.ops.ring import ring_attention


def _ref_attn(q, k, v, causal=False):
    b, t, h, d = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d)
    if causal:
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _qkv(b=2, t=48, h=2, d=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(0), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_local_matches_reference(causal):
    q, k, v = _qkv()
    got = flash_attention_local(q, k, v, causal=causal, interpret=True)
    want = _ref_attn(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_flash_local_unaligned_shapes():
    """T and D off the tile grid exercise the pad + bias-column masking of
    padded key slots (they must contribute exactly nothing)."""
    q, k, v = _qkv(t=50, d=24)
    got = flash_attention_local(q, k, v, causal=False, interpret=True)
    want = _ref_attn(q, k, v, False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_block_merge_equals_full_block():
    """mlo_merge of two half K/V blocks == one full block (associativity —
    the property the ring path is built on)."""
    b, t, h, d = 2, 32, 2, 16
    q, k, v = _qkv(b, t, h, d)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    qf, kf, vf = fold(q), fold(k), fold(v)
    z = jnp.zeros((), jnp.int32)
    sc = 1.0 / d ** 0.5
    full = block_flash(qf, kf, vf, z, z, False, sc, 256, 512, True)
    h1 = block_flash(qf, kf[:, : t // 2], vf[:, : t // 2], z, z,
                     False, sc, 256, 512, True)
    h2 = block_flash(qf, kf[:, t // 2:], vf[:, t // 2:], z,
                     jnp.asarray(t // 2), False, sc, 256, 512, True)
    merged = mlo_merge(h1, h2)
    for a, b_ in zip(merged, full):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-5, atol=1e-5
        )


def test_flash_fully_masked_rows_are_zero():
    """A causal block whose keys are all in the future must yield l == 0 and
    o_hat == 0 (the finite -NEG_INF guard; naive exp(0)=1 would poison the
    ring merge)."""
    b, t, h, d = 1, 16, 1, 8
    q, k, v = _qkv(b, t, h, d)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    o, m, l = block_flash(
        fold(q), fold(k), fold(v), jnp.asarray(0), jnp.asarray(1000),
        True, 1.0 / d ** 0.5, 256, 512, True,
    )
    np.testing.assert_array_equal(np.asarray(l), 0.0)
    np.testing.assert_array_equal(np.asarray(o), 0.0)


def test_flash_gradients_match_reference():
    q, k, v = _qkv(t=40, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention_local(q, k, v, causal=True, interpret=True) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attn(q, k, v, True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


def test_flash_ring_traced_offsets_interpret():
    """The sharded ring feeds block_flash TRACED per-hop scalar-prefetch
    offsets; shard_map's interpret-mode vma fallback routes around the kernel
    on CPU (ADVICE r3), so this emulates the ring schedule on ONE device —
    real interpret kernel, offsets carried through lax.scan exactly as the
    sharded program carries them."""
    from flash_ring_check import run_check

    run_check(interpret=True)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_single_device(devices8, causal):
    n = 4
    mesh = build_mesh(MeshSpec(spw=n), devices8[:n])
    b, t, h, d = 2, 32, 2, 8
    q, k, v = _qkv(b, t, h, d)

    ref = ring_attention(q, k, v, None, 1, causal=causal, use_flash=False)
    spec = P(None, "spw", None, None)
    out = jax.jit(
        shard_map(
            lambda a, bb, c: ring_attention(
                a, bb, c, "spw", n, causal=causal,
                use_flash=True, interpret=True,
            ),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_ring_flash_grads_match_einsum_ring(devices8):
    n = 4
    mesh = build_mesh(MeshSpec(spw=n), devices8[:n])
    b, t, h, d = 1, 16, 1, 4
    q, k, v = _qkv(b, t, h, d)
    spec = P(None, "spw", None, None)
    from jax import lax

    def make_loss(use_flash):
        def loss_sharded(q, k, v):
            o = ring_attention(
                q, k, v, "spw", n, causal=True,
                use_flash=use_flash, interpret=use_flash,
            )
            return lax.pmean(jnp.mean(o * o), "spw")

        return jax.jit(
            jax.grad(
                lambda q, k, v: shard_map(
                    loss_sharded, mesh=mesh,
                    in_specs=(spec, spec, spec), out_specs=P(),
                )(q, k, v)
            )
        )

    gf = make_loss(True)(q, k, v)
    ge = make_loss(False)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(gf), np.asarray(ge), rtol=1e-4, atol=1e-5
    )
