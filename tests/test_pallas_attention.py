"""Pallas blockwise attention (ops/pallas_attention.py) — kernel vs einsum
reference in interpret mode, and the flash ring path vs the einsum ring path
on the 8-device CPU mesh (ops/ring.py use_flash=True); latent attention's
forward and backward kernels on the projections' layout
(ops/pallas_latent_attention.py) against the einsum form, forward and
gradients.  ``block_flash``'s backward kernel against ``jax.vjp`` of the
einsum reference and against the rule it replaced (a scan of einsum tiles,
kept here as ``_einsum_tile_rule``), which is also the oracle of
latent attention's backward (heads-first operands, ``_parent_rule``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.compat import pcast, shard_map
from jax.sharding import PartitionSpec as P

from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.ops.pallas_attention import (
    _NEG_INF, _reference_mlo, block_flash, block_flash_backward,
    causal_tile_split, flash_attention_local, mlo_merge,
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


# --- block_flash's backward kernel -------------------------------------------


def _einsum_tile_rule(causal, scale, res, cts, tq=1024, tk=512):
    """``block_flash``'s backward as it was written before it became a
    kernel: a scan over Tk tiles, and inside it a scan over Tq tiles, of
    einsum blocks; under ``causal`` a tile whose every key lies after its last
    query is skipped (``lax.cond``).  The products take q, k, v as they come
    (and dô in their dtype) and accumulate in float32.  ``res`` is ``(q, k,
    v, q_off, k_off, m)``, ``cts`` is ``(dô, dl)``; returns ``(dq, dk, dv)``.

        dP = dô Vᵀ + dl·1ᵀ ;  ds = P ⊙ dP
        dq = ds K · scale ;  dk = dsᵀ Q · scale ;  dv = Pᵀ dô
    """
    q, k, v, q_off, k_off, m = res
    do, dl = cts
    bh, t_q, d = q.shape
    t_k, dv = k.shape[1], v.shape[-1]
    f32 = jnp.float32
    nk = max(1, (t_k + tk - 1) // tk)
    tk_c = -(-t_k // nk)
    nq = max(1, (t_q + tq - 1) // tq)
    tq_c = -(-t_q // nq)
    k_pad, q_pad = nk * tk_c - t_k, nq * tq_c - t_q

    def tiles(x, n, pad):
        """[BH, T, ...] as [n, BH, T/n, ...], zero rows appended."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(bh, n, x.shape[1] // n, *x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    kts, vts = tiles(k, nk, k_pad), tiles(v, nk, k_pad)
    k_ids = jnp.arange(nk * tk_c, dtype=jnp.int32).reshape(nk, tk_c)
    q_tiles = (tiles(q, nq, q_pad), tiles(do.astype(q.dtype), nq, q_pad),
               tiles(m.astype(f32), nq, q_pad), tiles(dl.astype(f32), nq, q_pad),
               (q_off + jnp.arange(nq * tq_c, dtype=jnp.int32)).reshape(nq, tq_c))

    def vary(t):
        """Under shard_map the accumulators become device-varying inside the
        scans; their initial values are marked varying up front."""
        vma = frozenset().union(*(jax.typeof(a).vma for a in (q, k, v, do)))
        return pcast(t, tuple(vma), to="varying") if vma else t

    def k_tile(dq_acc, inp):
        kt, vt, ids = inp

        def q_tile(carry, qin):
            dk_t, dv_t, dq_acc = carry
            i, qt, dot, mt, dlt, q_pos = qin

            def fold(dk_t, dv_t):
                s = jnp.einsum("bqd,bkd->bqk", qt, kt,
                               preferred_element_type=f32) * scale
                mask = jnp.broadcast_to((ids < t_k)[None, :], s.shape[1:])
                if causal:
                    mask = mask & (q_pos[:, None] >= (k_off + ids)[None, :])
                s = jnp.where(mask[None], s, _NEG_INF)
                p = jnp.where(s > _NEG_INF * 0.5, jnp.exp(s - mt[..., None]), 0.0)
                dp = jnp.einsum("bqd,bkd->bqk", dot, vt,
                                preferred_element_type=f32) + dlt[..., None]
                ds = (p * dp).astype(qt.dtype)
                dq_t = jnp.einsum("bqk,bkd->bqd", ds, kt,
                                  preferred_element_type=f32)
                dk_t = dk_t + jnp.einsum("bqk,bqd->bkd", ds, qt,
                                         preferred_element_type=f32)
                dv_t = dv_t + jnp.einsum("bqk,bqd->bkd", p.astype(dot.dtype),
                                         dot, preferred_element_type=f32)
                return dk_t, dv_t, dq_t

            if causal:
                dk_t, dv_t, dq_t = jax.lax.cond(
                    q_pos[-1] >= k_off + ids[0], fold,
                    lambda dk_t, dv_t: (dk_t, dv_t,
                                        vary(jnp.zeros((bh, tq_c, d), f32))),
                    dk_t, dv_t)
            else:
                dk_t, dv_t, dq_t = fold(dk_t, dv_t)
            dq_acc = jax.lax.dynamic_update_index_in_dim(
                dq_acc, jax.lax.dynamic_index_in_dim(dq_acc, i, 0, False) + dq_t,
                i, 0)
            return (dk_t, dv_t, dq_acc), None

        zero = vary(jnp.zeros((bh, tk_c, d), f32))
        zero_v = vary(jnp.zeros((bh, tk_c, dv), f32))
        (dk_t, dv_t, dq_acc), _ = jax.lax.scan(
            q_tile, (zero, zero_v, dq_acc),
            (jnp.arange(nq, dtype=jnp.int32), *q_tiles))
        return dq_acc, (dk_t, dv_t)

    dq0 = vary(jnp.zeros((nq, bh, tq_c, d), f32))
    dq, (dks, dvs) = jax.lax.scan(k_tile, dq0, (kts, vts, k_ids))
    dq = jnp.moveaxis(dq, 0, 1).reshape(bh, nq * tq_c, d)
    untile = lambda x: jnp.moveaxis(x, 0, 1).reshape(
        bh, nk * tk_c, x.shape[-1])[:, :t_k]
    return ((dq[:, :t_q] * scale).astype(q.dtype),
            (untile(dks) * scale).astype(k.dtype), untile(dvs).astype(v.dtype))


# (queries, keys, key width, value width, causal, q_off, k_off): heads-first
# blocks as ring attention hands them over, hop by hop.
_BWD_CASES = {
    "causal": (256, 256, 32, 32, True, 0, 0),
    "not_causal": (256, 256, 32, 32, False, 0, 0),
    "later_hop": (256, 256, 32, 32, True, 256, 0),         # sees every key
    "diagonal_hop": (256, 384, 32, 32, True, 128, 0),      # some tiles masked
    "earlier_hop": (256, 256, 32, 32, True, 0, 256),       # sees no key
    "ragged": (300, 200, 32, 32, True, 100, 0),            # no tile divides
    "narrow_values": (256, 256, 24, 16, True, 0, 0),       # Dv != D
    "narrow_values_not_causal": (200, 300, 24, 16, False, 0, 0),
}


def _bwd_operands(case, dtype, seed=0):
    """q, k, v, the offsets, and ``(o, m, l)`` of the reference on them."""
    t_q, t_k, d, dv, causal, q_off, k_off = _BWD_CASES[case]
    rng = np.random.default_rng(seed)
    make = lambda t, w: jnp.asarray(rng.standard_normal((3, t, w), np.float32),
                                    dtype)
    q, k, v = make(t_q, d), make(t_k, d), make(t_k, dv)
    offs = jnp.int32(q_off), jnp.int32(k_off)
    with jax.default_matmul_precision("highest"):
        oml = _reference_mlo(q, k, v, *offs, causal, d ** -0.5)
    return (q, k, v, *offs), causal, d ** -0.5, oml, rng


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_BWD_CASES))
def test_block_flash_gradients_are_the_vjp_of_the_reference(case, dtype):
    """Through ``block_flash`` and its rule (tiles chosen from the lengths)
    against ``jax.vjp`` of ``_reference_mlo`` in float32, on a loss of the
    normalized output, whose cotangents carry a nonzero ``dl`` (there the
    reference's dependence on m cancels, so the two rules must agree)."""
    ops, causal, scale, _, rng = _bwd_operands(case, dtype)
    w = jnp.asarray(rng.standard_normal((3, ops[0].shape[1], ops[2].shape[-1]),
                                        np.float32))

    def loss(fn, q, k, v):
        o, _, l = fn(q, k, v)
        return jnp.sum(w * o / jnp.maximum(l, 1e-30)[..., None])

    with jax.default_matmul_precision("highest"):
        got = jax.grad(functools.partial(loss, lambda q, k, v: block_flash(
            q, k, v, *ops[3:], causal, scale, 256, 512, True)), (0, 1, 2))(
                *ops[:3])
        want = jax.grad(functools.partial(loss, lambda q, k, v: _reference_mlo(
            q, k, v, *ops[3:], causal, scale)), (0, 1, 2))(
                *(x.astype(jnp.float32) for x in ops[:3]))
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == dtype
        if case == "earlier_hop":
            assert not np.any(np.asarray(a, np.float32))
        else:
            assert _rel(a, b) < (1e-5 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("tiles", [(128, 128), (128, 256), (256, 128)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
@pytest.mark.parametrize("case", sorted(_BWD_CASES))
def test_block_flash_backward_kernel_is_the_einsum_tile_rule(case, tiles):
    """The kernel at small tiles (grids of several k and q tiles, tiles
    skipped, masked and whole) against the rule it replaced, float32, on
    random cotangents ``dô`` and ``dl`` (not those of a normalized output)."""
    ops, causal, scale, (_, m, _), rng = _bwd_operands(case, jnp.float32, 1)
    do = jnp.asarray(rng.standard_normal((3, ops[0].shape[1], ops[2].shape[-1]),
                                         np.float32))
    dl = jnp.asarray(rng.standard_normal(m.shape, np.float32))
    with jax.default_matmul_precision("highest"):
        got = block_flash_backward(*ops, m, do, dl, causal, scale, *tiles, True)
        want = _einsum_tile_rule(causal, scale, (*ops, m), (do, dl), 96, 64)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        if case == "earlier_hop":
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
        else:
            assert _rel(a, b) < 1e-5


@pytest.mark.parametrize("case", ["causal", "ragged", "narrow_values"])
def test_block_flash_backward_kernel_in_bf16_is_the_einsum_tile_rule_within_rounding(
        case):
    """bf16 operands and the same float32 residuals and cotangents to both:
    each rounds dô to bf16 before its products, P and dS once after their
    float32 sums, and its results to bf16, so the two differ by a bf16
    rounding or two (2⁻⁷) and by no more."""
    ops, causal, scale, (_, m, _), rng = _bwd_operands(case, jnp.bfloat16, 2)
    do = jnp.asarray(rng.standard_normal((3, ops[0].shape[1], ops[2].shape[-1]),
                                         np.float32))
    dl = jnp.asarray(rng.standard_normal(m.shape, np.float32))
    got = block_flash_backward(*ops, m, do, dl, causal, scale, 128, 128, True)
    want = _einsum_tile_rule(causal, scale, (*ops, m), (do, dl), 96, 64)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == jnp.bfloat16
        assert _rel(a, b) < 2 ** -7


def test_block_flash_backward_rows_that_see_no_key_give_exactly_nothing():
    """A hop whose first keys lie after the first queries (causal, k_off =
    100): queries 0–99 see no key (m = _NEG_INF, l = 0), so their dq rows
    are exactly zero whatever their cotangents, and they add nothing to dk
    and dv; the 84 rows that pad 300 queries to whole tiles of 128 are cut
    off.  Every other row has its gradient."""
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((2, t, 32), np.float32))
               for t in (300, 256, 256))
    offs = jnp.int32(0), jnp.int32(100)
    _, m, _ = _reference_mlo(q, k, v, *offs, True, 0.2)
    do = jnp.asarray(rng.standard_normal((2, 300, 32), np.float32))
    dl = jnp.asarray(rng.standard_normal((2, 300), np.float32))
    dq, dk, dv = block_flash_backward(q, k, v, *offs, m, do, dl, True, 0.2,
                                      128, 128, True)
    dq, dk, dv = (np.asarray(x) for x in (dq, dk, dv))
    assert dq.shape == (2, 300, 32) and np.all(np.isfinite(dq))
    assert not np.any(dq[:, :100]) and np.all(np.any(dq[:, 100:] != 0, -1))
    # the same with those rows' cotangents zeroed: dk and dv do not move
    do0, dl0 = do.at[:, :100].set(0.0), dl.at[:, :100].set(0.0)
    _, dk0, dv0 = block_flash_backward(q, k, v, *offs, m, do0, dl0, True, 0.2,
                                       128, 128, True)
    np.testing.assert_array_equal(dk, np.asarray(dk0))
    np.testing.assert_array_equal(dv, np.asarray(dv0))


# --- block_flash's forward kernel --------------------------------------------


def _masked_everywhere_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                              l_ref, acc, m_scr, l_scr, *, tq, tk, nk, causal,
                              t_k_real, scale):
    """The forward kernel as it was before it sorted its tiles: every live
    tile masked by position and padding and guarded, a tile past the
    diagonal skipped but its k and v blocks still fetched."""
    from jax import lax
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def fold():
        s = lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        ) * scale
        col = ki * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        if t_k_real % tk:
            s = jnp.where(col < t_k_real, s, _NEG_INF)
        if causal:
            q_pos = offs_ref[0] + qi * tq + lax.broadcasted_iota(
                jnp.int32, (tq, tk), 0)
            s = jnp.where(q_pos >= offs_ref[1] + col, s, _NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        c = jnp.exp(m_prev - m_new)
        p = jnp.where(s > _NEG_INF * 0.5, jnp.exp(s - m_new[:, None]), 0.0)
        l_new = l_scr[:, 0] * c + jnp.sum(p, axis=-1)
        v = v_ref[0]
        acc[:] = acc[:] * c[:, None] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if causal:
        pl.when(offs_ref[0] + (qi + 1) * tq - 1 >= offs_ref[1] + ki * tk)(fold)
    else:
        fold()

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0] = acc[:].astype(o_ref.dtype)
        m_ref[0] = m_scr[...].astype(m_ref.dtype)
        l_ref[0] = l_scr[...].astype(l_ref.dtype)


def _masked_everywhere_forward(q, k, v, q_off, k_off, causal, scale, tq, tk,
                               interpret=True):
    """``(o_hat, m, l)`` of :func:`_masked_everywhere_kernel` (in interpret
    mode unless told otherwise), on k and v blocks indexed by the k tile
    alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    up = lambda n, m: -(-n // m) * m
    bh, t_q, d = q.shape
    t_k, dv = k.shape[1], v.shape[-1]
    tq_p, tk_p, d_p, dv_p = up(t_q, tq), up(t_k, tk), up(d, 128), up(dv, 128)
    qp = jnp.pad(q, ((0, 0), (0, tq_p - t_q), (0, d_p - d)))
    kp = jnp.pad(k, ((0, 0), (0, tk_p - t_k), (0, d_p - d)))
    vp = jnp.pad(v, ((0, 0), (0, tk_p - t_k), (0, dv_p - dv)))
    nk = tk_p // tk
    f32 = jnp.float32
    rows = lambda w: pl.BlockSpec((1, tq, w), lambda b, i, j, offs: (b, i, 0))
    keys = lambda w: pl.BlockSpec((1, tk, w), lambda b, i, j, offs: (b, j, 0))
    o, m, l = pl.pallas_call(
        functools.partial(_masked_everywhere_kernel, tq=tq, tk=tk, nk=nk,
                          causal=causal, t_k_real=t_k, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, tq_p // tq, nk),
            in_specs=[rows(d_p), keys(d_p), keys(dv_p)],
            out_specs=[rows(dv_p), rows(128), rows(128)],
            scratch_shapes=[pltpu.VMEM((tq, dv_p), f32),
                            pltpu.VMEM((tq, 128), f32),
                            pltpu.VMEM((tq, 128), f32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, tq_p, dv_p), f32),
                   jax.ShapeDtypeStruct((bh, tq_p, 128), f32),
                   jax.ShapeDtypeStruct((bh, tq_p, 128), f32)],
        interpret=interpret,
    )(jnp.stack([q_off, k_off]).astype(jnp.int32), qp, kp, vp)
    return o[:, :t_q, :dv], m[:, :t_q, 0], l[:, :t_q, 0]


# The backward's hops, and 300 tokens in tiles of 128 (every kind of tile,
# a ragged tail) both ways, a hop in which queries 0-99 see no key, and hops
# whose tiles' first query lies one short of a tile's last key (the tile is
# not whole) or on it (whole).
_FWD_CASES = {
    **_BWD_CASES,
    "ragged_square": (300, 300, 32, 32, True, 0, 0),
    "ragged_square_not_causal": (300, 300, 32, 32, False, 0, 0),
    "rows_see_no_key": (300, 256, 32, 32, True, 0, 100),
    "one_short_of_whole": (256, 256, 32, 32, True, 126, 0),
    "just_whole": (256, 256, 32, 32, True, 127, 0),
}


_FWD_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _forward_both_ways(path):
    """Every case of ``_FWD_CASES`` in each of ``_FWD_DTYPES``, through
    ``block_flash`` and through :func:`_masked_everywhere_forward`, at tiles
    of 128 with the offsets traced as a ring hop hands them over; the six
    outputs of each saved to ``path`` (``.npz``) under ``<case>-<dtype>``."""
    out = {}
    for case, (t_q, t_k, d, dv, causal, q_off, k_off) in _FWD_CASES.items():
        for name, dtype in _FWD_DTYPES.items():
            rng = np.random.default_rng(4)
            q, k, v = (jnp.asarray(rng.standard_normal((3, t, w), np.float32),
                                   dtype)
                       for t, w in ((t_q, d), (t_k, d), (t_k, dv)))
            scale = d ** -0.5
            offs = jnp.int32(q_off), jnp.int32(k_off)
            got = jax.jit(lambda q_off, k_off: block_flash(
                q, k, v, q_off, k_off, causal, scale, 128, 128, True))(*offs)
            want = jax.jit(lambda q_off, k_off: _masked_everywhere_forward(
                q, k, v, q_off, k_off, causal, scale, 128, 128))(*offs)
            for tag, arrays in (("got", got), ("want", want)):
                for x, a in zip("oml", arrays):
                    out[f"{case}-{name}-{tag}-{x}"] = np.asarray(a)
    np.savez(path, **out)


@pytest.fixture(scope="module")
def forward_both_ways(tmp_path_factory):
    """:func:`_forward_both_ways` in a process of its own whose CPU target
    has no fused multiply-add: where it has one, XLA rounds an ``exp`` that
    shares its fusion with a ``select`` differently from one that does not
    (by an ulp), which says nothing of the kernels; without it every
    operation rounds once, as written."""
    import os
    import subprocess
    import sys

    path = tmp_path_factory.mktemp("forward") / "both.npz"
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "") + " --xla_cpu_max_isa=SSE4_2").strip(),
        PYTHONPATH=os.pathsep.join([os.path.dirname(here), here]))
    subprocess.run([sys.executable, "-c",
                    "import sys, test_pallas_attention as t; "
                    "t._forward_both_ways(sys.argv[1])", str(path)],
                   env=env, check=True, timeout=600)
    return np.load(path)


@pytest.mark.parametrize("dtype", sorted(_FWD_DTYPES))
@pytest.mark.parametrize("case", sorted(_FWD_CASES))
def test_block_flash_forward_is_the_masked_everywhere_kernel_bit_for_bit(
        forward_both_ways, case, dtype):
    """The forward kernel, which folds a whole tile with no mask and no
    guard and fetches no k or v for a skipped one, against the kernel that
    masked every live tile: ``(o_hat, m, l)`` equal bit for bit, in
    interpret mode, causal and not, aligned and ragged, on ring hops whose
    queries see all of the keys, part of them or none, and on one whose
    first 100 queries see no key (``l`` 0 there, positive elsewhere)."""
    for x in "oml":
        got = forward_both_ways[f"{case}-{dtype}-got-{x}"]
        want = forward_both_ways[f"{case}-{dtype}-want-{x}"]
        assert got.shape == want.shape and got.dtype == want.dtype == np.float32
        np.testing.assert_array_equal(got, want)
    if case == "rows_see_no_key":
        l = forward_both_ways[f"{case}-{dtype}-got-l"]
        assert not np.any(l[:, :100]) and np.all(l[:, 100:] > 0)


@pytest.mark.parametrize("lengths,tiles,split", [
    ((8192, 8192), (1024, 1024), (28, 8, 28)),
    ((300, 300), (128, 128), (3, 3, 3)),
    ((300, 200), (128, 128), (2, 3, 1)),
    ((200, 300), (128, 128), (1, 2, 3)),
    ((50, 50), (1024, 1024), (0, 1, 0)),
], ids=["8192", "300", "300x200", "200x300", "50"])
def test_causal_tile_split_counts_the_kernels_tiles_by_kind(lengths, tiles,
                                                            split):
    """Whole, diagonal and skipped tiles of a causal call at zero offsets:
    at 8,192 tokens in tiles of 1,024, 28 of the 36 live tiles fold whole;
    under ragged lengths the three kinds sum to the grid of the tiles the
    kernel takes (at most the lengths in whole sublanes and lanes)."""
    got = causal_tile_split(*lengths, *tiles)
    assert got == split
    tq, tk = min(tiles[0], -(-lengths[0] // 8) * 8), min(
        tiles[1], -(-lengths[1] // 128) * 128)
    assert sum(got) == -(-lengths[0] // tq) * -(-lengths[1] // tk)


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
    ``_einsum_tile_rule``, ``dl = −Σ(dô·o) ÷
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
        dq, dk, dv = _einsum_tile_rule(
            True, scale, (qh, kh, kv[..., nope:], zero, zero, m),
            (do * inv_l[..., None], dl))
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
