"""Semantic trace scopes: the naming layer of the telemetry subsystem.

The reference instruments phases with CUDA events around named code regions
(``benchmark_resnet_gems_master_with_sp.py:417-440``); on TPU the analog is
the XLA op-name stack: :func:`scope` pushes a name onto ``jax.named_scope``
so every op traced inside carries it — in XProf traces (``--profile-dir``),
in compiled-HLO ``op_name`` metadata, and in StableHLO debug locations.
Threaded through the hot paths (cells, halo exchange, D2 runs, ring steps,
pipeline stages), a trace reads ``stage1/cell03/halo_exchange_w/...`` instead
of anonymous fusions — the per-phase attribution T3-style overlap work needs
(PAPERS.md, arXiv:2401.16677).

Scopes are trace-time only (zero steady-state runtime cost: the context
manager runs while JAX builds the jaxpr, never per step on device) and can be
disabled outright with ``MPI4DL_NO_SCOPES=1`` for pristine A/B compiles.
Every scope of the package goes through :func:`scope`; the host-side step
marker is the recorder's ``step`` span (:mod:`~mpi4dl_tpu.obs.spans`).
"""

from __future__ import annotations

import contextlib
import os
from typing import ContextManager, Optional

_ENABLED: Optional[bool] = None


def scopes_enabled() -> bool:
    """Cached check of the ``MPI4DL_NO_SCOPES`` hatch (config.HATCHES)."""
    global _ENABLED
    if _ENABLED is None:
        _ENABLED = os.environ.get("MPI4DL_NO_SCOPES", "0") != "1"
    return _ENABLED


def _reset_enabled_cache() -> None:
    """Test hook: re-read MPI4DL_NO_SCOPES on the next scopes_enabled()."""
    global _ENABLED
    _ENABLED = None


def scope(name: str) -> ContextManager[None]:
    """Named trace scope for ops created inside the ``with`` block.

    Inside jit/shard_map tracing this is ``jax.named_scope``; disabled it is
    a nullcontext (zero cost, zero graph difference)."""
    if not scopes_enabled():
        return contextlib.nullcontext()
    import jax

    return jax.named_scope(name)

