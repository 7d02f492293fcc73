"""The jax surface the package leans on, in one place.

One supported jax: the vma-aware line (``jax.shard_map`` with
varying-manual-axes tracking, ``lax.pcast``); ``contracts/lp.json`` records
the version the goldens were made on and CI installs it.

- :func:`shard_map`, :func:`pcast` — re-exports, so import sites read
  ``from mpi4dl_tpu.compat import shard_map, pcast``.
- :func:`ensure_host_device_count` — virtual CPU devices for the CPU mesh
  (tests/conftest.py, benchmarks/common.py, the analysis CLIs).
- :func:`ensure_compilation_cache` — where the persistent compile cache goes.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
from jax import lax
from jax import shard_map  # noqa: F401 — re-export

pcast = lax.pcast

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ensure_host_device_count(n: int) -> None:
    """Request an ``n``-device CPU platform (``jax_num_cpu_devices``; read
    at backend initialisation, inert unless the CPU platform is selected)."""
    jax.config.update("jax_num_cpu_devices", n)


def ensure_compilation_cache() -> Optional[str]:
    """Place jax's persistent compilation cache; call before the first
    compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads
    it, so this does nothing and returns None.  Otherwise the cache goes to
    the fixed ``<checkout>/.jax_cache`` (the path is part of the cache key,
    so a directory that moves never hits) and that path is returned."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
