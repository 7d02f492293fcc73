"""granitemoehybrid lp benchmark: granite-4.0-h-micro (a token model: Mamba-2 layers beside position-free attention, one table for embedding and head) on one chip, or GPipe over its cells.

Under --split-size > 1 (with --precision fp_32) the embedding's stage and the head's each hold the table; their gradients are summed over the stage axis before the update.

Example (CPU smoke run; the runner provisions the virtual CPU mesh itself):
  JAX_PLATFORMS=cpu \
  python layer_parallelism/benchmark_granitemoehybrid_lp.py --num-layers 6 --vocab-size 512 --seq-len 256 --batch-size 2 --steps-per-epoch 3
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.common import run

if __name__ == "__main__":
    run("lp", "granitemoehybrid")
