"""deepseek_v3 lp benchmark: Kanana-2-30B-A3B (a token model) on one chip, or GPipe over its cells.

Example (CPU smoke run; the runner provisions the virtual CPU mesh itself):
  JAX_PLATFORMS=cpu \
  python layer_parallelism/benchmark_deepseek_v3_lp.py --num-layers 2 --vocab-size 512 --experts-held 16 --seq-len 64 --batch-size 2 --steps-per-epoch 3
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.common import run

if __name__ == "__main__":
    run("lp", "deepseek_v3")
