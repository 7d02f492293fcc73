"""perfbench: the benchmark of mpi4dl_tpu.

One command runs one cell once::

    python3 -m perfbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root of the checkout lists cells, metrics and
bounds; everything that belongs to one configuration, one traffic mix, one
per-layer metric or one kind of reader is a file of its own under this
directory, found by its name (``catalog.py``).  PERF.md says what each
metric means and why each cell exists.
"""
