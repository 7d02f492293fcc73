"""The benchmark's tests run on the CPU: the chip belongs to the benchmark."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
