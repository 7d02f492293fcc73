"""resnet gems benchmark (reference: benchmarks/gems_master_model/benchmark_resnet_gems_master.py).

Example (CPU smoke run; the runner provisions the virtual CPU mesh itself):
  JAX_PLATFORMS=cpu \
  python gems_master_model/benchmark_resnet_gems_master.py --image-size 32 --num-layers 1 --batch-size 8 --steps-per-epoch 3
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.common import run

if __name__ == "__main__":
    run("gems", "resnet")
