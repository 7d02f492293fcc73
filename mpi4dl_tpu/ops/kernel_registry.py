"""Pallas kernel registry — the enrollment point of the static verifier.

Every hand-written Pallas kernel in ``mpi4dl_tpu/ops`` registers its public
entry here as one or more :class:`KernelCase` rows: a representative trace
(shapes chosen so every grid dimension has interior AND edge points) for
each dtype/variant path the engines dispatch.  The verifier
(``mpi4dl_tpu/analysis/pallascheck``) traces each case on CPU, extracts the
``pallas_call`` specs from the jaxpr, and certifies grid/BlockSpec
soundness, the per-grid-point VMEM total, DMA/semaphore discipline and
accumulator-init coverage — see docs/analysis.md ("Pallas verifier").

Two things key off this module being the single registry:

- ``python -m mpi4dl_tpu.analysis pallascheck`` verifies exactly these
  cases, so a new kernel (ROADMAP item 2's halo-RDMA conv) is enrolled by
  adding a row — the gate covers it with no CI change;
- AST rule 12 ``unregistered-pallas-call`` statically parses THIS file's
  imports: a ``pl.pallas_call`` in any ``mpi4dl_tpu`` module not imported
  here is a violation, so a kernel cannot ship unverified.

Cases must trace with ``jax.make_jaxpr`` on a CPU host (no TPU compile, no
real mesh); keep shapes small — the verifier enumerates the full grid.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple

# Imports below double as rule-12 registration: a module whose kernels are
# verified must be imported here (statically parsed, never executed by the
# analyzer).
from mpi4dl_tpu.ops.pallas_attention import (
    block_flash, block_flash_backward, sparse_flash_backward,
    sparse_flash_forward)
from mpi4dl_tpu.ops.pallas_latent_attention import (
    latent_flash, latent_flash_backward)
from mpi4dl_tpu.ops.sparse_indexer import indexer_backward, indexer_select


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One verified trace of a registered kernel.

    ``build()`` returns ``(fn, args)`` such that ``jax.make_jaxpr(fn)(*args)``
    contains at least one ``pallas_call`` equation.  ``ring_size``, when
    set, declares the remote-DMA neighbor topology the kernel's
    ``make_async_remote_copy`` ``device_id`` map must be bijective against
    (None = the kernel performs no remote copies; a remote copy in such a
    case is itself a finding).
    """

    name: str
    build: Callable[[], Tuple[Callable, tuple]]
    ring_size: Optional[int] = None


def _flash_case(dtype: str, causal: bool):
    def build():
        import jax.numpy as jnp

        dt = jnp.dtype(dtype)
        # Grid (2, 3, 3): batch·heads edge-only, q/k dims with interior
        # points; Tk=300 exercises the padded-key masking tail.  Under
        # causal 300 queries in tiles of 128 too: three tiles whole, three
        # on the diagonal (masked) and three past it (skipped, their k and
        # v index maps clamped to the last live tile).
        q = jnp.zeros((2, 300 if causal else 48, 64), dt)
        k = jnp.zeros((2, 300, 64), dt)
        v = jnp.zeros((2, 300, 64), dt)
        z = jnp.zeros((), jnp.int32)
        fn = lambda q, k, v: block_flash(  # noqa: E731
            q, k, v, z, z, causal, 0.125, 128 if causal else 16, 128, False
        )
        return fn, (q, k, v)

    variant = "causal:" if causal else ""
    return KernelCase(name=f"block_flash:{variant}{dtype}", build=build)


def _flash_backward_case(dtype: str, causal: bool):
    def build():
        import jax.numpy as jnp

        # Grid (2, 3, 3): k tiles before q tiles; 300 tokens in tiles of 128
        # (a zero-padded tail in the last of each), feature-major blocks;
        # under causal three tiles skipped, three masked, three whole.
        dt = jnp.dtype(dtype)
        q = jnp.zeros((2, 300, 64), dt)
        stat = jnp.zeros((2, 300), jnp.float32)
        z = jnp.zeros((), jnp.int32)
        fn = lambda q, k, v, m, do, dl: block_flash_backward(  # noqa: E731
            q, k, v, z, z, m, do, dl, causal, 0.125, 128, 128, False
        )
        return fn, (q, q, q, stat, q, stat)

    variant = "causal:" if causal else ""
    return KernelCase(name=f"block_flash_backward:{variant}{dtype}",
                      build=build)


_LATENT_HEADS, _LATENT_TOKENS = 4, 300


def _latent_operands(dtype: str):
    """q, q_pe, kv, k_pe: four heads of 128 + 64 with values of 128 (the
    published widths: two heads a grid step), 300 tokens padded to three
    tiles of 128: grid (1, 2, 3, 3), a zero-padded tail in the last."""
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    return tuple(jnp.zeros((1, _LATENT_TOKENS, w), dt) for w in (
        _LATENT_HEADS * 128, _LATENT_HEADS * 64, _LATENT_HEADS * 256, 64))


def _latent_case(dtype: str):
    def build():
        fn = lambda q, q_pe, kv, k_pe: latent_flash(  # noqa: E731
            q, q_pe, kv, k_pe, _LATENT_HEADS, 192 ** -0.5, 128, 128, False
        )
        return fn, _latent_operands(dtype)

    return KernelCase(name=f"latent_flash:causal:{dtype}", build=build)


def _latent_backward_case(dtype: str):
    def build():
        import jax.numpy as jnp

        # The forward case's operands, k tiles before q tiles in the grid:
        # three of the nine tiles above the diagonal (skipped), three on it
        # (masked) and three below (whole).
        q, q_pe, kv, k_pe = _latent_operands(dtype)
        stat = jnp.zeros((1, _LATENT_HEADS, _LATENT_TOKENS), jnp.float32)
        fn = lambda q, q_pe, kv, k_pe, o, m, l, do: latent_flash_backward(  # noqa: E731
            q, q_pe, kv, k_pe, o, m, l, do, _LATENT_HEADS, 192 ** -0.5,
            128, 128, False
        )
        return fn, (q, q_pe, kv, k_pe, q, stat, stat, q)

    return KernelCase(name=f"latent_flash_backward:causal:{dtype}", build=build)


_SPARSE_TOKENS = 300  # three tiles of 128, a zero-padded tail in the last


def _sparse_flash_case(backward: bool):
    def build():
        import jax.numpy as jnp

        # Three heads a sequence share its selection's words (128 a query:
        # at 300 tokens a forward tile of 128 keys is one bit; the
        # backward's own three tiles of 768 over 2,300 tokens, a zero-padded
        # tail in the last, are six).  Both grids, (2, 3, 3), are one
        # key-value group of the three a sequence, on k and v as they are.
        t = 2300 if backward else _SPARSE_TOKENS
        q = jnp.zeros((6, t, 64), jnp.bfloat16)
        kv = jnp.zeros((2, t, 64), jnp.bfloat16)
        words = jnp.zeros((2, t, 128), jnp.int32)
        if not backward:
            fn = lambda q, k, v, words: sparse_flash_forward(  # noqa: E731
                q, k, v, words, heads=3, scale=0.125, tq=128, tk=128)
            return fn, (q, kv, kv, words)
        o = jnp.zeros((6, t, 64), jnp.float32)
        stat = jnp.zeros((6, t), jnp.float32)
        fn = lambda *a: sparse_flash_backward(  # noqa: E731
            *a, heads=3, scale=0.125)
        return fn, (q, kv, kv, o, stat, stat, q, jnp.swapaxes(words, 1, 2))

    name = "sparse_flash_backward" if backward else "sparse_flash_forward"
    return KernelCase(name=f"{name}:causal:bfloat16", build=build)


def _indexer_case(backward: bool):
    def build():
        import jax.numpy as jnp

        # One sequence of 300 tokens: the selection's grid (1, 3) over
        # blocks of 128 queries, the gradient's (1, 2, 32) over q tiles of
        # 256 and k tiles of the selection's width (128 words: 4,096 keys,
        # the last 29 tiles past the sequence); an indexer of 4 heads of 8.
        t = _SPARSE_TOKENS
        iq = jnp.zeros((1, t, 4, 8), jnp.bfloat16)
        ik = jnp.zeros((1, t, 8), jnp.bfloat16)
        w = jnp.zeros((1, t, 4), jnp.float32)
        if not backward:
            fn = lambda iq, ik, w: indexer_select(iq, ik, w, 16)  # noqa: E731
            return fn, (iq, ik, w)
        q, k = jnp.zeros((1, t, 4, 16), jnp.bfloat16), jnp.zeros(
            (1, t, 2, 16), jnp.bfloat16)
        c = jnp.zeros((1, 4, t), jnp.float32)
        words_t = jnp.zeros((1, 128, t), jnp.int32)
        lse = jnp.zeros((1, t), jnp.float32)
        fn = lambda *a: indexer_backward(  # noqa: E731
            *a, scale=0.25, inv_n=1.0 / t)
        return fn, (q, k, c, iq, ik, w, words_t, lse)

    name = "indexer_backward" if backward else "indexer_select"
    return KernelCase(name=f"{name}:bfloat16", build=build)


# The raw (fp32) path and the bf16 compute path the mixed-precision/quant
# engines dispatch (quant/kernels.py itself is pure jnp — no pallas_call,
# which rule 12 verifies stays true), forward and backward; latent
# attention's forward and backward kernels (always causal) in both; the
# sparse attention's kernels under a key selection and its indexer's two
# (the bf16 compute path, the only one they run on).
REGISTRY: Tuple[KernelCase, ...] = (
    _flash_case("float32", causal=False),
    _flash_case("bfloat16", causal=True),
    _flash_backward_case("float32", causal=False),
    _flash_backward_case("bfloat16", causal=True),
    _latent_case("float32"),
    _latent_case("bfloat16"),
    _latent_backward_case("float32"),
    _latent_backward_case("bfloat16"),
    _sparse_flash_case(backward=False),
    _sparse_flash_case(backward=True),
    _indexer_case(backward=False),
    _indexer_case(backward=True),
)


def registry_case(name: str) -> KernelCase:
    for case in REGISTRY:
        if case.name == name:
            return case
    raise KeyError(
        f"no registered kernel case {name!r}; have "
        f"{[c.name for c in REGISTRY]}"
    )


def case_names(kernels: Optional[Sequence[str]] = None) -> Tuple[str, ...]:
    """Registered case names, optionally filtered by kernel prefix (the
    part before the first ``:``) or exact case name."""
    names = tuple(c.name for c in REGISTRY)
    if kernels is None:
        return names
    wanted = set(kernels)
    out = tuple(
        n for n in names if n in wanted or n.split(":", 1)[0] in wanted
    )
    return out
