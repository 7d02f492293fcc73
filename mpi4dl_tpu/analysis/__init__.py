"""Shard-safety analyzer: AST-based correctness lint for the package.

The hazards the TPU port moved from runtime into compile-time artifacts —
mesh-axis names, shard_map/PartitionSpec specs, ppermute permutation tables,
the bf16/fp32 policy, and the ``MPI4DL_*`` env hatches — are provable on any
CPU host in seconds, without a chip.  See docs/analysis.md.

Usage::

    python -m mpi4dl_tpu.analysis                     # whole repo, exit != 0 on findings
    python -m mpi4dl_tpu.analysis --json some/file.py
    python -m mpi4dl_tpu.analysis --baseline analysis_baseline.json

Programmatic::

    from mpi4dl_tpu.analysis import analyze_paths
    violations = analyze_paths(["mpi4dl_tpu"])
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from mpi4dl_tpu.analysis.core import (
    Project,
    Rule,
    Violation,
    apply_baseline,
    build_project,
    load_baseline,
    run_rules,
    stale_pragmas,
)
from mpi4dl_tpu.analysis.rules import RULE_TABLE, RULES_BY_NAME

__all__ = [
    "Project",
    "Rule",
    "Violation",
    "RULE_TABLE",
    "RULES_BY_NAME",
    "analyze_paths",
    "apply_baseline",
    "build_project",
    "load_baseline",
    "run_rules",
    "stale_pragmas",
]


def analyze_paths(
    paths: Sequence[str],
    root: Optional[str] = None,
    rules: Optional[Sequence[Rule]] = None,
) -> List[Violation]:
    project = build_project(paths, root=root)
    return run_rules(project, rules if rules is not None else RULE_TABLE)
