"""IR-level shard-flow verifier (ISSUE 16).

The AST analyzer (``analysis/rules_*``) and the compiled-artifact contract
gate (``analysis/contracts``) bracket an engine from outside — source
heuristics below, compiled byte counts above.  This package verifies the IR
*between* them: an abstract interpreter over the closed jaxpr plus structural
checks over the scheduled compiled HLO of every contract engine family,
producing typed :class:`Finding` records attributed to the owning
``obs.scope``.  It is the static harness ROADMAP item 2's hand-written async
halo-RDMA kernels will be developed against: a mismatched collective, a
read-after-donate alias or a DMA/compute race becomes a finding on a CPU
host instead of a hang on silicon (T3, arXiv:2401.16677; the MPMD
program-graph direction, arXiv:2412.14374).

Finding classification (every kind has a violating fixture in
tests/test_ircheck.py; docs/analysis.md walks the semantics):

jaxpr level (``check_jaxpr``):

- ``wasted-wire`` — a reducing collective (psum/pmax/pmin) over mesh axes
  along which the replication-flow interpreter proves the operand is
  already replicated: the wire moves bytes to compute a value every shard
  already holds (repflow.py);
- ``divergent-collective`` — a collective under a ``cond``/``while`` whose
  predicate is not replicated along the collective's axis: shards can
  disagree about executing it, the distributed analog of an MPI deadlock
  (repflow.py);
- ``nonbijective-perm`` — a ``ppermute`` table that is not an injective
  partial permutation of the *concrete* axis size taken from the enclosing
  ``shard_map`` mesh (the IR-proof upgrade of the AST ``collective-axis``
  rule's literal-table check, which cannot see dynamic tables or sizes);
- ``mismatched-replica-groups`` — ``axis_index_groups`` that fail to
  partition ``range(axis_size)`` into equal disjoint groups.

compiled scheduled HLO level (``check_hlo``):

- ``nonbijective-perm`` / ``mismatched-replica-groups`` — the same proofs
  against ``source_target_pairs=``/``replica_groups=`` after GSPMD
  partitioning, bounded by the module's ``num_partitions``;
- ``read-after-donate`` — an ``input_output_alias`` entry whose donated
  parameter buffer is read at a schedule position after the aliased output
  has been written (donation.py);
- ``double-donation`` — one parameter buffer aliased by two outputs;
- ``malformed-carry-alias`` — a ``while`` whose carry shape differs from
  its body's parameter/root shape (the in-place scan-carry alias contract);
- ``unpaired-async`` — a ``*-start`` with zero or several reachable
  ``*-done`` halves, or a done with no start (asyncsafe.py);
- ``async-dma-race`` — compute inside a start..done window that consumes
  the in-flight async value or writes in place into the DMA source buffer;
- ``pallas-alias`` — a custom call whose ``output_to_operand_aliasing``
  is out of range, doubly aliased, or shape-mismatched (the argument-alias
  contract the ``pallas_attention.py`` kernels must honor).

Entry points: :func:`check_jaxpr`, :func:`check_hlo`,
:func:`check_family` (builds a contract engine family and runs both), and
the CLI ``python -m mpi4dl_tpu.analysis ircheck``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

FINDING_KINDS = (
    "wasted-wire",
    "divergent-collective",
    "nonbijective-perm",
    "mismatched-replica-groups",
    "read-after-donate",
    "double-donation",
    "malformed-carry-alias",
    "unpaired-async",
    "async-dma-race",
    "pallas-alias",
)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One IR-level verification failure, attributed to its obs.scope."""

    kind: str      # one of FINDING_KINDS
    scope: str     # owning clean obs.scope path ("" when unattributed)
    message: str
    family: str = ""   # engine family ("" for fixture/unit runs)
    bytes: int = 0     # wasted/racing payload estimate where meaningful

    @property
    def baseline_key(self) -> Tuple[str, str, str, str]:
        return (self.kind, self.family, self.scope, self.message)

    def render(self) -> str:
        where = f"{self.family}:" if self.family else ""
        scope = self.scope or "<unscoped>"
        tail = f" (~{self.bytes} bytes)" if self.bytes else ""
        return f"{where}{scope}: [{self.kind}] {self.message}{tail}"


def check_jaxpr(closed_jaxpr, family: str = "") -> List[Finding]:
    """All jaxpr-level findings for one closed jaxpr."""
    from mpi4dl_tpu.analysis.ircheck.collectives import jaxpr_collective_findings
    from mpi4dl_tpu.analysis.ircheck.repflow import replication_findings

    out = replication_findings(closed_jaxpr, family=family)
    out += jaxpr_collective_findings(closed_jaxpr, family=family)
    return _sorted(out)


def check_hlo(hlo_text: str, family: str = "") -> List[Finding]:
    """All findings over one compiled (scheduled) HLO module's text."""
    from mpi4dl_tpu.analysis.ircheck.asyncsafe import async_findings
    from mpi4dl_tpu.analysis.ircheck.collectives import hlo_collective_findings
    from mpi4dl_tpu.analysis.ircheck.donation import donation_findings

    out = donation_findings(hlo_text, family=family)
    out += async_findings(hlo_text, family=family)
    out += hlo_collective_findings(hlo_text, family=family)
    return _sorted(out)


def check_family(family: str, quant=None, build=None) -> List[Finding]:
    """Build one contract engine family (optionally under a quant policy),
    lower + compile it on the virtual mesh, and run every check.  ``build``
    overrides the canonical builder exactly like
    :func:`~mpi4dl_tpu.analysis.contracts.extract.extract_contract` (tests
    inject perturbed engines through it)."""
    import jax

    from mpi4dl_tpu.analysis.contracts.engines import build_engine
    from mpi4dl_tpu.analysis.contracts.extract import compiled_text_of

    if build is None:
        if quant is not None:
            build = lambda f: build_engine(f, quant=quant)  # noqa: E731
        else:
            build = build_engine
    step, args = build(family)
    lowered = step.lower(*args)
    jaxpr = jax.make_jaxpr(step)(*args)
    out = check_jaxpr(jaxpr, family=family)
    out += check_hlo(compiled_text_of(lowered), family=family)
    return _sorted(out)


def finding_counts(findings) -> Dict[str, int]:
    """``{kind: count}`` over a finding list — the ``ircheck`` contract
    section's golden material (kinds with zero findings are omitted so a
    clean engine pins an empty dict)."""
    out: Dict[str, int] = {}
    for f in findings:
        out[f.kind] = out.get(f.kind, 0) + 1
    return dict(sorted(out.items()))


def _sorted(findings: List[Finding]) -> List[Finding]:
    return sorted(findings, key=lambda f: (f.kind, f.scope, f.message))


# -- shared jaxpr-walk helpers (repflow.py + collectives.py) ----------------

def aval_bytes(aval) -> int:
    try:
        import numpy as np

        n = 1
        for d in aval.shape:
            n *= int(d)
        return n * np.dtype(aval.dtype).itemsize
    except Exception:  # noqa: BLE001 — abstract tokens/effects have no shape
        return 0


def eqn_scope(eqn) -> str:
    """The obs.scope path of one jaxpr equation, from its name stack (the
    same vocabulary clean_scope_path extracts from compiled op_names)."""
    from mpi4dl_tpu.obs.hlo_stats import clean_scope_component

    stack = getattr(getattr(eqn, "source_info", None), "name_stack", None)
    if stack is None:
        return ""
    comps = [clean_scope_component(c) for c in str(stack).split("/")]
    return "/".join(c for c in comps if c)


def join_scope(prefix: str, scope: str) -> str:
    """Join an enclosing equation's scope path with a sub-jaxpr eqn's
    *relative* name stack (jax resets the stack when tracing control-flow
    bodies; the lowering re-prefixes — so must the interpreter)."""
    return "/".join(p for p in (prefix, scope) if p)


def collective_axes(eqn) -> Tuple[str, ...]:
    """The mesh-axis names a collective equation runs over."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def shard_map_context(eqn) -> Tuple[Dict[str, int], List[frozenset]]:
    """(manual axis sizes, per-invar replicated-axis sets) of a shard_map
    equation: an input is replicated along every manual axis its in_names
    entry does not shard a dimension over."""
    mesh = eqn.params.get("mesh")
    auto = eqn.params.get("auto", frozenset())
    sizes: Dict[str, int] = {}
    if mesh is not None:
        for name, size in zip(mesh.axis_names, mesh.shape.values()):
            if name not in auto:
                sizes[str(name)] = int(size)
    manual = frozenset(sizes)
    reps: List[frozenset] = []
    for names in eqn.params.get("in_names", ()):
        used = set()
        for axes in names.values():
            used.update(str(a) for a in axes)
        reps.append(manual - used)
    return sizes, reps


def sub_jaxprs(params) -> List:
    """Every jaxpr-like object reachable from an equation's params."""
    out = []
    for v in params.values():
        if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            out.extend(item for item in v
                       if hasattr(item, "eqns") or hasattr(item, "jaxpr"))
    return out
