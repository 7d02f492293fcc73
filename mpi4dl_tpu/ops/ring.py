"""Sequence (context) parallelism: 1-D ghost-cell exchange + ring attention.

The reference is a CNN framework with no attention; its long-context analog
is spatial parallelism itself — partitioning the H/W "context" across devices
with ghost-region exchange (SURVEY §2a/§5: "the TPU build should implement
the halo/ghost primitive on a named mesh axis so that both 2-D image SP and
1-D sequence CP are instances of one mechanism").  This module is that 1-D
instance, built on the same ``halo_exchange_1d`` primitive:

- :func:`seq_ghost_exchange` — extend a [B, T_local, ...] sequence shard with
  neighbour tokens (ghost cells), the direct CP analog of the conv halo.
- :func:`ghost_conv1d` — "same"-padded 1-D convolution over a sharded
  sequence axis: exchange receptive-field overlap, then VALID conv — the
  sequence twin of layers.Conv2d's spatial mode.
- :func:`ring_attention` — exact blockwise attention over a sequence-sharded
  axis: K/V blocks circulate the ring via ``lax.ppermute`` while each device
  accumulates its queries' output with a numerically-stable online softmax
  (flash-attention style m/l/o running state).  One hop per step rides the
  ICI ring.  Per-device memory: O(T_local·H·D) on the default TPU path
  (``use_flash`` auto — the Pallas kernel in ops/pallas_attention.py keeps
  scores in VMEM tiles); the einsum fallback path materializes the per-hop
  O(T_local²·heads) score block and serves CPU + as the validation oracle.

All functions must be called inside shard_map with the named axis present.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.compat import pcast

from mpi4dl_tpu.obs.scopes import scope
from mpi4dl_tpu.ops.halo import HaloSpec, halo_exchange_1d


def seq_ghost_exchange(
    x: jax.Array,
    axis_name: str,
    n: int,
    lo: int,
    hi: int,
    dim: int = 1,
) -> jax.Array:
    """Extend the local sequence shard with `lo` trailing tokens of the
    previous shard and `hi` leading tokens of the next (zeros at the global
    sequence boundary — exactly the conv halo's zero-padding semantics)."""
    return halo_exchange_1d(x, dim, axis_name, n, HaloSpec(lo, hi))


def ghost_conv1d(
    x: jax.Array,
    kernel: jax.Array,
    axis_name: Optional[str],
    n: int,
    stride: int = 1,
) -> jax.Array:
    """1-D "same" convolution over a sequence-sharded [B, T, C] tensor.

    kernel: [K, C_in, C_out].  With `axis_name` None this is a plain padded
    conv; sharded, the (K-1)//2 overlap is ghost-exchanged and the conv runs
    VALID — bit-identical to the unsharded op (tests/test_ring.py)."""
    k = kernel.shape[0]
    lo, hi = (k - 1) // 2, k - 1 - (k - 1) // 2
    if axis_name is None:
        pad = ((lo, hi),)
    else:
        x = seq_ghost_exchange(x, axis_name, n, lo, hi)
        pad = ((0, 0),)
    return lax.conv_general_dilated(
        x, kernel.astype(x.dtype),
        window_strides=(stride,),
        padding=pad,
        dimension_numbers=("NHC", "HIO", "NHC"),
    )


def _resolve_flash(setting: Optional[bool]) -> bool:
    """None = auto: the Pallas block kernel (ops/pallas_attention.py) is a
    Mosaic program — on for TPU backends, einsum path elsewhere."""
    if setting is not None:
        return setting
    from mpi4dl_tpu.config import is_tpu_backend

    return is_tpu_backend()


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: Optional[str],
    n: int,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Exact attention over a sequence sharded on `axis_name` ([B, T_local,
    H, D] per device; v may have a width of its own, which is the output's).
    K/V blocks rotate around the ring; each device folds
    every block into its queries' output with the online-softmax update

        m' = max(m, rowmax(s));  c = exp(m - m')
        l' = l * c + rowsum(exp(s - m'));  o' = o * c + exp(s - m') @ v_blk

    which is invariant to block arrival order, so the result equals
    single-device softmax(QKᵀ)V exactly (up to fp accumulation).  `causal`
    masks by GLOBAL token position (block index from lax.axis_index).
    With `axis_name` None, computes plain (optionally causal) attention.
    """
    b, t, h, d = q.shape
    sc = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    qf = q.astype(jnp.float32) * sc

    def block_scores(kblk, q_pos, k_pos):
        # [B, H, Tq, Tk]
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kblk.astype(jnp.float32))
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        return s

    flash = _resolve_flash(use_flash)

    if axis_name is None:
        if flash:
            from mpi4dl_tpu.ops.pallas_attention import flash_attention_local

            return flash_attention_local(
                q, k, v, causal=causal, scale=scale, interpret=interpret
            )
        s = block_scores(k, jnp.arange(t, dtype=jnp.int32), jnp.arange(t, dtype=jnp.int32))
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v.astype(jnp.float32)
        )
        return out.astype(q.dtype)

    if flash:
        return _ring_attention_flash(
            q, k, v, axis_name, n, causal,
            float(scale) if scale is not None else 1.0 / float(d) ** 0.5,
            interpret,
        )

    my = lax.axis_index(axis_name)
    q_pos = my * t + jnp.arange(t, dtype=jnp.int32)
    perm = [(i, (i + 1) % n) for i in range(n)]  # ring: block from prev device

    def body(carry, _):
        kblk, vblk, src, m, l, o = carry
        with scope("ring_step_compute"):
            k_pos = src * t + jnp.arange(t, dtype=jnp.int32)
            s = block_scores(kblk, q_pos, k_pos)  # [B, H, Tq, Tk]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # exp(-inf - -inf) guard: rows with no valid keys yet keep m=-inf.
            c = jnp.exp(jnp.where(jnp.isfinite(m), m - m_new, -jnp.inf))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            l_new = l * c + jnp.sum(p, axis=-1)
            o_new = o * c[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32)
            )
        with scope("ring_step_hop"):
            kblk = lax.ppermute(kblk, axis_name, perm)
            vblk = lax.ppermute(vblk, axis_name, perm)
            src = lax.ppermute(src, axis_name, perm)
        return (kblk, vblk, src, m_new, l_new, o_new), None

    # Accumulators start device-uniform but become device-varying in the loop:
    # mark them varying up front (shard_map vma tracking requires carry types
    # to be loop-invariant; same pattern as the pipeline scans).
    vcast = lambda t_: pcast(t_, (axis_name,), to="varying")
    m0 = vcast(jnp.full((b, h, t), -jnp.inf, jnp.float32))
    l0 = vcast(jnp.zeros((b, h, t), jnp.float32))
    o0 = vcast(jnp.zeros((b, h, t, v.shape[3]), jnp.float32))
    (_, _, _, _, l, o), _ = lax.scan(body, (k, v, my, m0, l0, o0), None, length=n)
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis_name, n, causal, scale, interpret):
    """Ring attention with the Pallas block kernel as the local compute.

    Same schedule as the einsum path (K/V rotate via ppermute, one hop per
    scan step) but each hop's block state comes from
    :func:`mpi4dl_tpu.ops.pallas_attention.block_flash` — scores exist only
    as VMEM tiles, so per-hop HBM traffic drops from O(T_local²·H) to
    O(T_local·D·H), the long-context enabler.  Exact: block states fold via
    the associative :func:`mlo_merge` (same update the einsum path applies
    inline), so results match it to fp accumulation order.
    """
    from mpi4dl_tpu.ops.pallas_attention import block_flash, mlo_merge

    b, t, h, d = q.shape
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[3])
    dv = v.shape[3]  # the values' width, and the output's
    qf = fold(q)
    q_off = my * t

    def body(carry, _):
        kblk, vblk, src, m, l, o = carry

        def compute(m, l, o):
            kf, vf = fold(kblk), fold(vblk)
            with scope("attention_core"):  # the kernel alone, as local's
                blk = block_flash(  # all-positional: custom_vjp + nondiff args
                    qf, kf, vf, q_off, src * t, causal, scale, 256, 512,
                    interpret,
                )
            return mlo_merge((o, m, l), blk)

        with scope("ring_step_compute"):
            if causal:
                # A source block entirely in this device's future (src > my)
                # contributes exactly zero through the mask guard (blk =
                # (0, -inf, 0), an mlo_merge identity) — skip the kernel for
                # those ~n/2 hops instead of computing a fully-masked block
                # (ADVICE r3).  shard_map is per-device code, so the varying
                # predicate legitimately branches per device.
                o, m, l = lax.cond(
                    src <= my, compute, lambda m, l, o: (o, m, l), m, l, o
                )
            else:
                o, m, l = compute(m, l, o)
        with scope("ring_step_hop"):
            kblk = lax.ppermute(kblk, axis_name, perm)
            vblk = lax.ppermute(vblk, axis_name, perm)
            src = lax.ppermute(src, axis_name, perm)
        return (kblk, vblk, src, m, l, o), None

    vcast = lambda t_: pcast(t_, (axis_name,), to="varying")
    from mpi4dl_tpu.ops.pallas_attention import _NEG_INF

    m0 = vcast(jnp.full((b * h, t), _NEG_INF, jnp.float32))
    l0 = vcast(jnp.zeros((b * h, t), jnp.float32))
    o0 = vcast(jnp.zeros((b * h, t, dv), jnp.float32))
    (_, _, _, _, l, o), _ = lax.scan(
        body, (k, v, my, m0, l0, o0), None, length=n
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, t, dv).transpose(0, 2, 1, 3).astype(q.dtype)
