"""resnet sp benchmark (reference: benchmarks/spatial_parallelism/benchmark_resnet_sp.py:116-370).

Example (CPU smoke run; the runner provisions the virtual CPU mesh itself):
  JAX_PLATFORMS=cpu \
  python spatial_parallelism/benchmark_resnet_sp.py --image-size 32 --num-layers 1 --batch-size 8 --steps-per-epoch 3
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.common import run

if __name__ == "__main__":
    run("sp", "resnet")
