"""Pallas blockwise attention (ops/pallas_attention.py) — kernel vs einsum
reference in interpret mode, and the flash ring path vs the einsum ring path
on the 8-device CPU mesh (ops/ring.py use_flash=True); latent attention's
forward and backward kernels on the projections' layout
(ops/pallas_latent_attention.py) against the einsum form, forward and
gradients, and the backward against the rule it replaced (heads-first
operands through ``_block_flash_bwd``, kept here as the oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P

from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.ops.pallas_attention import (
    _block_flash_bwd, block_flash, flash_attention_local, mlo_merge,
)
from mpi4dl_tpu.ops.pallas_latent_attention import _forward, latent_flash
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


# --- latent attention on the projections' layout ------------------------------


def _latent_operands(b, s, h, nope, rope, dv, dtype, seed=0):
    """q [B, S, H·nope], q_pe [B, S, H·rope], kv [B, S, H·(nope + dv)] (a
    head's k_nope then its v), k_pe [B, S, rope]."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((b, s, w), np.float32), dtype)
                 for w in (h * nope, h * rope, h * (nope + dv), rope))


def _latent_reference(q, q_pe, kv, k_pe_heads, h, scale):
    """The einsum form in float32; ``k_pe_heads`` [B, S, H, rope] is the
    rotary key a head, so that its gradient can be had head by head."""
    b, s, _ = q.shape
    heads = lambda x: x.astype(jnp.float32).reshape(b, s, h, -1)
    nope = q.shape[-1] // h
    k = jnp.concatenate([heads(kv)[..., :nope],
                         k_pe_heads.astype(jnp.float32)], axis=-1)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", jnp.concatenate([heads(q), heads(q_pe)], axis=-1),
        k) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                     heads(kv)[..., nope:])
    return out.reshape(b, s, -1)


def _k_pe_a_head(k_pe, h):
    return jnp.broadcast_to(k_pe[:, :, None, :], (*k_pe.shape[:2], h,
                                                  k_pe.shape[-1]))


def _rel(a, b):
    a, b = (np.asarray(t, np.float32) for t in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _through(fn, ops):
    """Output and gradients with respect to all four operands."""
    def loss(*o):
        out = fn(*o)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
    return jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True)(*ops)


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("seq", [256, 300], ids=["tiled", "ragged"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_latent_flash_matches_the_einsum_form(dtype, seq, batch):
    """Heads of 16 + 8 with values of 16 (the published 2:1 of nope to rope,
    keys wider than values), tiles of 16 queries by 128 keys: at 300 tokens
    the last tiles of both run past the arrays' end.  Output and gradients
    with respect to all four operands."""
    h, nope, rope, dv = 4, 16, 8, 16
    ops = _latent_operands(batch, seq, h, nope, rope, dv, dtype)
    scale = (nope + rope) ** -0.5

    with jax.default_matmul_precision("highest"):
        (_, got), g_got = _through(
            lambda *o: latent_flash(*o, h, scale, 16, 128, True), ops)
        (_, want), g_want = _through(
            lambda q, q_pe, kv, k_pe: _latent_reference(
                q, q_pe, kv, _k_pe_a_head(k_pe, h), h, scale), ops)
    assert got.shape == (batch, seq, h * dv) and got.dtype == dtype
    f32 = dtype == jnp.float32
    assert _rel(got, want) < (1e-5 if f32 else 1e-2)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape and a.dtype == dtype
        assert _rel(a, b) < (1e-4 if f32 else 3e-2)


def test_latent_flash_at_the_published_widths_walks_its_groups_of_two_heads():
    """128 + 64 and values of 128: two heads fill whole lane tiles in every
    operand, so four heads are two steps of the grid's second axis, a head's
    columns sliced statically inside the block."""
    h, nope, rope, dv = 4, 128, 64, 128
    ops = _latent_operands(1, 64, h, nope, rope, dv, jnp.float32, seed=1)
    scale = (nope + rope) ** -0.5
    with jax.default_matmul_precision("highest"):
        got = latent_flash(*ops, h, scale, 32, 64, True)
        want = _latent_reference(*ops[:3], _k_pe_a_head(ops[3], h), h, scale)
    assert _rel(got, want) < 1e-5


def test_latent_flash_gives_the_rotary_key_the_sum_of_the_heads_gradients():
    h, nope, rope, dv = 4, 16, 8, 16
    q, q_pe, kv, k_pe = _latent_operands(2, 48, h, nope, rope, dv,
                                         jnp.float32, seed=2)
    scale = (nope + rope) ** -0.5
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda k_pe: jnp.sum(jnp.cos(latent_flash(
            q, q_pe, kv, k_pe, h, scale, 16, 128, True))))(k_pe)
        a_head = jax.grad(lambda k_pe_heads: jnp.sum(jnp.cos(_latent_reference(
            q, q_pe, kv, k_pe_heads, h, scale))))(_k_pe_a_head(k_pe, h))
    assert a_head.shape == (2, 48, h, rope)
    assert _rel(got, a_head.sum(axis=2)) < 1e-4
    # and no one head's share is the whole of it
    assert all(_rel(got, a_head[:, :, i]) > 0.1 for i in range(h))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_latent_flash_gradients_at_the_published_widths(dtype):
    """Heads of 128 + 64 and values of 128, four heads (two steps of the
    grid's second axis, two heads each), 200 tokens in tiles of 64 (ragged:
    the last tile holds 8 tokens and 56 zero rows; the grid has tiles above,
    on and below the diagonal): all four gradients against the float32 einsum
    form on the same operands."""
    h, nope, rope, dv = 4, 128, 64, 128
    ops = _latent_operands(1, 200, h, nope, rope, dv, dtype, seed=3)
    scale = (nope + rope) ** -0.5
    with jax.default_matmul_precision("highest"):
        _, g_got = _through(
            lambda *o: latent_flash(*o, h, scale, 64, 64, True), ops)
        _, g_want = _through(
            lambda q, q_pe, kv, k_pe: _latent_reference(
                q, q_pe, kv, _k_pe_a_head(k_pe, h), h, scale),
            tuple(o.astype(jnp.float32) for o in ops))
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape and a.dtype == dtype
        assert _rel(a, b) < (1e-4 if dtype == jnp.float32 else 3e-2)


def _parent_rule(heads, scale, res, do):
    """``latent_flash``'s backward as it was before it became a kernel (PR
    34): one sequence's heads-first q, k (the rotary key broadcast to every
    head and concatenated), v and dô ÷ l in float32 through
    ``pallas_attention._block_flash_bwd``'s einsum tiles, ``dl = −Σ(dô·o) ÷
    l``; the rotary key's gradient summed over the heads in float32."""
    s, nope = res[0].shape[1], res[0].shape[-1] // heads
    rope = res[3].shape[-1]
    f32 = jnp.float32
    zero = jnp.zeros((), jnp.int32)
    heads_first = lambda x: x.reshape(s, heads, -1).transpose(1, 0, 2)
    tokens_first = lambda x: x.transpose(1, 0, 2).reshape(s, -1)

    def sequence(args):
        q, q_pe, kv, k_pe, o, m, l, do = args
        kv = heads_first(kv)
        qh = jnp.concatenate([heads_first(q), heads_first(q_pe)], axis=-1)
        kh = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (heads, s, rope))], axis=-1)
        do = heads_first(do).astype(f32)
        inv_l = 1.0 / jnp.maximum(l, 1e-30)
        dl = -jnp.sum(do * heads_first(o).astype(f32), axis=-1) * inv_l
        dq, dk, dv, _, _ = _block_flash_bwd(
            True, scale, None, None, False,
            (qh, kh, kv[..., nope:], zero, zero, None, m, None),
            (do * inv_l[..., None], None, dl))
        return (tokens_first(dq[..., :nope]), tokens_first(dq[..., nope:]),
                tokens_first(jnp.concatenate([dk[..., :nope], dv], axis=-1)),
                jnp.sum(dk[..., nope:].astype(f32), axis=0).astype(k_pe.dtype))

    return jax.lax.map(sequence, (*res, do))


@pytest.mark.parametrize("widths", [(16, 8, 16), (128, 64, 128)],
                         ids=["test_widths", "published_widths"])
def test_latent_flash_bf16_gradients_are_the_parents_within_rounding(widths):
    """bf16 operands, the same residuals and cotangent to both rules: the
    kernels round P̂ and dS to bf16 once before their products where the
    parent rounded p, dô ÷ l and ds, and each rounds its result to bf16: the
    two differ by a bf16 rounding or two (2⁻⁷; they read 4e-3, as either does
    against the float32 form) and by no more."""
    nope, rope, dv = widths
    h, b, s = 4, 2, 200
    ops = _latent_operands(b, s, h, nope, rope, dv, jnp.bfloat16, seed=4)
    scale = (nope + rope) ** -0.5
    do = jnp.asarray(np.random.default_rng(5).standard_normal(
        (b, s, h * dv), np.float32), jnp.bfloat16)
    out, vjp = jax.vjp(lambda *o: latent_flash(*o, h, scale, 64, 64, True), *ops)
    got = vjp(do)
    o, m, l = _forward(*ops, h, scale, 64, 64, True)
    assert jnp.array_equal(o, out)
    want = _parent_rule(h, scale, (*ops, o, m, l), do)
    for a, w in zip(got, want):
        assert a.shape == w.shape and a.dtype == w.dtype == jnp.bfloat16
        assert _rel(a, w) < 2 ** -7


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_latent_flash_zero_cotangent_rows_and_the_padded_tail_give_exactly_zero(
        dtype):
    """The cotangent's rows from token 100 on are zero: dô = 0 and Δ = 0
    there, so dS and P̂ᵀ·dô are exactly zero: those queries get no gradient
    and give none to the keys that only they see, nor do the 56 zero rows
    that pad 200 tokens to whole tiles of 64 (were a padded row to count, the
    last real keys' gradients would hold it).  Before token 100 every
    gradient is there."""
    h, nope, rope, dv = 4, 16, 8, 16
    ops = _latent_operands(2, 200, h, nope, rope, dv, dtype, seed=6)
    scale = (nope + rope) ** -0.5
    do = np.random.default_rng(7).standard_normal((2, 200, h * dv), np.float32)
    do[:, 100:] = 0.0
    _, vjp = jax.vjp(lambda *o: latent_flash(*o, h, scale, 64, 64, True), *ops)
    for g in vjp(jnp.asarray(do, dtype)):
        g = np.asarray(g, np.float32)
        assert g.shape[1] == 200 and np.all(np.isfinite(g))
        assert not np.any(g[:, 100:])
        assert np.all(np.any(g[:, 1:100] != 0.0, axis=-1))
