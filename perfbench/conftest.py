"""The benchmark's tests run on the CPU: the chip belongs to the benchmark.
Four host devices, asked for before jax loads, for the cells that span four;
a smaller count found in ``XLA_FLAGS`` is raised to four, a larger one kept."""

import os
import re

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_FLAG = "--xla_force_host_platform_device_count="
_flags = os.environ.get("XLA_FLAGS", "")
_found = re.search(re.escape(_FLAG) + r"(\d+)", _flags)
if _found is None or int(_found.group(1)) < 4:
    _flags = re.sub(re.escape(_FLAG) + r"\d+", "", _flags)
    os.environ["XLA_FLAGS"] = f"{_flags} {_FLAG}4".strip()
