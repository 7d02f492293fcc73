"""Framework for the shard-safety analyzer.

Pure-``ast`` static analysis — no dependency beyond the standard library, and
no imports of the analyzed code (so it runs in seconds on any CPU host, which
is the whole point: the invariants it proves — mesh-axis names, ``ppermute``
bijections, dtype policy, env-hatch hygiene, retrace hazards — otherwise
surface only in a run on the chip).

Vocabulary:

- A :class:`SourceFile` is one parsed module: its AST (walked once into a
  shared by-node-type index that every rule iterates via
  :meth:`SourceFile.nodes` — no per-rule re-walks), per-line pragma
  allowlist, and an import-alias table (so rules can resolve ``np``/``jnp``/
  ``P`` to their canonical modules without executing anything).
- A :class:`Project` is the set of scanned files plus the extracted ground
  truth: the mesh-axis vocabulary from ``mesh.py`` and the env-hatch registry
  from ``config.py`` — both parsed statically, falling back to the installed
  package sources when the scanned paths don't include them (e.g. when
  linting test fixtures).
- A :class:`Rule` contributes :class:`Violation` objects; the runner applies
  pragma suppression and the checked-in baseline, then reports.

Pragma syntax (suppresses on its own line, or the whole function when placed
on the ``def`` line)::

    x = float(eps)  # analysis: ok(tracer-leak)
    def helper():   # analysis: ok(tracer-leak, dtype-policy)
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

_PRAGMA_RE = re.compile(r"#\s*analysis:\s*ok\(([^)]*)\)")
_HATCH_NAME_RE = re.compile(r"^_?MPI4DL_[A-Z0-9_]+$")


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    path: str  # scan-root-relative, forward slashes
    line: int
    message: str

    @property
    def baseline_key(self) -> Tuple[str, str, str]:
        # Line numbers drift with unrelated edits; baseline entries match on
        # (rule, path, message) so a justified exception survives refactors.
        return (self.rule, self.path, self.message)

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class SourceFile:
    """One parsed python module with pragma and import-alias tables.

    The AST is walked exactly once at construction into a by-node-type
    index; rules iterate :meth:`nodes` instead of re-walking the whole tree
    per rule (the dominant cost of a whole-repo scan before this index)."""

    def __init__(self, path: str, rel: str, text: str):
        self.path = path
        self.rel = rel.replace(os.sep, "/")
        self.text = text
        self.tree = ast.parse(text, filename=path)
        self.by_type: Dict[type, List[ast.AST]] = {}
        for node in ast.walk(self.tree):
            self.by_type.setdefault(type(node), []).append(node)
        self.pragmas = self._collect_pragmas(text)
        self.aliases = self._collect_aliases(self)
        self.func_spans = self._collect_func_spans(self)

    def nodes(self, *types: type) -> Iterable[ast.AST]:
        """Every node of the given AST type(s), from the shared one-pass
        index.  Order is ``ast.walk`` order (breadth-first): nested nodes
        come after shallower ones regardless of line number — rules that
        need lexical structure must check spans, not index order."""
        for t in types:
            yield from self.by_type.get(t, ())

    # -- pragmas -----------------------------------------------------------
    @staticmethod
    def _collect_pragmas(text: str) -> Dict[int, Set[str]]:
        out: Dict[int, Set[str]] = {}
        try:
            toks = tokenize.generate_tokens(io.StringIO(text).readline)
            for tok in toks:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _PRAGMA_RE.search(tok.string)
                if m:
                    rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
                    out.setdefault(tok.start[0], set()).update(rules or {"*"})
        except tokenize.TokenError:
            pass
        return out

    @staticmethod
    def _collect_func_spans(src: "SourceFile") -> List[Tuple[int, int, int]]:
        """(def_line, body_start, body_end) for every function."""
        spans = []
        for node in src.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
            end = getattr(node, "end_lineno", node.lineno)
            spans.append((node.lineno, node.lineno, end))
        return spans

    def suppressed(self, rule: str, line: int) -> bool:
        return self.suppressing_line(rule, line) is not None

    def suppressing_line(self, rule: str, line: int) -> Optional[int]:
        """The pragma line that suppresses ``rule`` at ``line`` (None when
        nothing does) — the attribution the stale-pragma direction needs."""
        def hit(rules: Set[str]) -> bool:
            return "*" in rules or rule in rules

        if line in self.pragmas and hit(self.pragmas[line]):
            return line
        # a pragma on a def line covers the whole function body
        for def_line, start, end in self.func_spans:
            if start <= line <= end and def_line in self.pragmas and hit(
                self.pragmas[def_line]
            ):
                return def_line
        return None

    # -- import aliases ----------------------------------------------------
    @staticmethod
    def _collect_aliases(src: "SourceFile") -> Dict[str, str]:
        """Map local name -> dotted canonical origin.

        ``import numpy as np`` -> {'np': 'numpy'};
        ``from jax.sharding import PartitionSpec as P`` ->
        {'P': 'jax.sharding.PartitionSpec'};
        ``from jax import lax`` -> {'lax': 'jax.lax'}.
        Collected from every scope (local imports are common here).
        """
        out: Dict[str, str] = {}
        # Document order so a later rebinding of the same alias wins,
        # matching runtime semantics (the two node types interleave).
        nodes = sorted(
            src.nodes(ast.Import, ast.ImportFrom),
            key=lambda n: (n.lineno, n.col_offset),
        )
        for node in nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    out[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif node.module:
                for a in node.names:
                    out[a.asname or a.name] = f"{node.module}.{a.name}"
        return out

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Dotted canonical name for a Name/Attribute chain, through the
        import-alias table: ``jnp.zeros`` -> 'jax.numpy.zeros'."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            base = self.aliases.get(node.id, node.id)
            parts.append(base)
            return ".".join(reversed(parts))
        return None


@dataclasses.dataclass
class Project:
    files: List[SourceFile]
    axes: Tuple[str, ...]
    axis_constants: Dict[str, str]  # constant name -> axis string
    hatches: Dict[str, int]  # declared hatch name -> declaration line
    hatch_decl_path: str  # rel path of the registry (for dead-flag reports)
    # True when the registry file itself is part of the scan: the dead-flag
    # direction is only meaningful on a whole-tree scan (a single-file scan
    # trivially "never reads" every hatch).
    hatch_decl_in_scan: bool = False

    def package_files(self) -> List[SourceFile]:
        return [f for f in self.files if is_package_file(f.rel)]


def is_package_file(rel: str) -> bool:
    return "mpi4dl_tpu/" in f"/{rel}" or rel.startswith("mpi4dl_tpu")


class Rule:
    """Base class; subclasses set ``name``/``description`` and implement
    :meth:`check`.  Register instances in ``rules.RULE_TABLE``."""

    name: str = ""
    description: str = ""

    def check(self, project: Project) -> List[Violation]:  # pragma: no cover
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Ground-truth extraction (static — never imports the analyzed code)
# ---------------------------------------------------------------------------


def _find_file(files: Sequence[SourceFile], suffix: str) -> Optional[SourceFile]:
    for f in files:
        if f.rel.endswith(suffix):
            return f
    return None


def _parse_fallback(modname: str) -> Optional[SourceFile]:
    """Parse an installed package module's source without importing it."""
    import importlib.util

    try:
        spec = importlib.util.find_spec(modname)
    except (ImportError, ValueError):
        return None
    if spec is None or not spec.origin or not os.path.exists(spec.origin):
        return None
    with open(spec.origin, "r", encoding="utf-8") as fh:
        return SourceFile(spec.origin, os.path.basename(spec.origin), fh.read())


def extract_axes(files: Sequence[SourceFile]) -> Tuple[Tuple[str, ...], Dict[str, str]]:
    """The axis vocabulary: ``mesh.AXES`` plus the AXIS_* constant table."""
    src = _find_file(files, "mpi4dl_tpu/mesh.py") or _parse_fallback("mpi4dl_tpu.mesh")
    axes: List[str] = []
    constants: Dict[str, str] = {}
    if src is None:
        return tuple(axes), constants
    for node in src.tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not isinstance(tgt, ast.Name):
            continue
        if tgt.id.startswith("AXIS_") and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            constants[tgt.id] = node.value.value
        elif tgt.id == "AXES" and isinstance(node.value, (ast.Tuple, ast.List)):
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    axes.append(elt.value)
                elif isinstance(elt, ast.Name) and elt.id in constants:
                    axes.append(constants[elt.id])
    if not axes:
        axes = list(constants.values())
    return tuple(axes), constants


def extract_hatches(files: Sequence[SourceFile]) -> Tuple[Dict[str, int], str]:
    """Declared env hatches: every ``Hatch("NAME", ...)`` call in config.py."""
    src = _find_file(files, "mpi4dl_tpu/config.py") or _parse_fallback(
        "mpi4dl_tpu.config"
    )
    out: Dict[str, int] = {}
    if src is None:
        return out, ""
    for node in ast.walk(src.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "Hatch"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            out[node.args[0].value] = node.lineno
    return out, src.rel


# ---------------------------------------------------------------------------
# Shared AST helpers for rules
# ---------------------------------------------------------------------------


def environ_reads(src: SourceFile) -> Iterable[Tuple[str, int]]:
    """(name, line) for every env *read* of a string-literal key:
    ``os.environ.get/pop/setdefault(K)``, ``os.environ[K]`` (Load ctx), and
    ``getenv(K)``."""
    for node in src.nodes(ast.Call):
        key = None
        f = node.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr in ("get", "pop", "setdefault")
            and isinstance(f.value, ast.Attribute)
            and f.value.attr == "environ"
        ):
            key = node.args[0] if node.args else None
        elif isinstance(f, ast.Attribute) and f.attr == "getenv":
            key = node.args[0] if node.args else None
        elif isinstance(f, ast.Name) and f.id == "getenv":
            key = node.args[0] if node.args else None
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            yield key.value, node.lineno
    for node in src.nodes(ast.Subscript):
        if (
            isinstance(node.ctx, ast.Load)
            and isinstance(node.value, ast.Attribute)
            and node.value.attr == "environ"
            and isinstance(node.slice, ast.Constant)
            and isinstance(node.slice.value, str)
        ):
            yield node.slice.value, node.lineno


def is_hatch_name(name: str) -> bool:
    return bool(_HATCH_NAME_RE.match(name))


# ---------------------------------------------------------------------------
# File discovery + runner
# ---------------------------------------------------------------------------

_SKIP_DIRS = {".git", "__pycache__", ".claude", "node_modules", ".github"}


def discover(paths: Sequence[str], root: Optional[str] = None) -> List[SourceFile]:
    root = os.path.abspath(root or os.getcwd())
    found: List[str] = []
    for p in paths:
        ap = os.path.abspath(p)
        if os.path.isfile(ap) and ap.endswith(".py"):
            found.append(ap)
        elif os.path.isdir(ap):
            for dirpath, dirnames, filenames in os.walk(ap):
                dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        found.append(os.path.join(dirpath, fn))
    files: List[SourceFile] = []
    for ap in sorted(set(found)):
        rel = os.path.relpath(ap, root)
        with open(ap, "r", encoding="utf-8") as fh:
            text = fh.read()
        try:
            files.append(SourceFile(ap, rel, text))
        except SyntaxError as e:
            # a file we cannot parse cannot be verified — surface it
            raise SystemExit(f"analysis: cannot parse {rel}: {e}")
    return files


def build_project(paths: Sequence[str], root: Optional[str] = None) -> Project:
    files = discover(paths, root)
    axes, constants = extract_axes(files)
    hatches, decl_path = extract_hatches(files)
    return Project(
        files=files,
        axes=axes,
        axis_constants=constants,
        hatches=hatches,
        hatch_decl_path=decl_path,
        hatch_decl_in_scan=any(f.rel == decl_path for f in files),
    )


def run_rules(
    project: Project,
    rules: Sequence[Rule],
    used_pragmas: Optional[Set[Tuple[str, int]]] = None,
) -> List[Violation]:
    """Run rules with pragma suppression.  ``used_pragmas``, when given,
    collects ``(rel_path, pragma_line)`` of every pragma that actually
    suppressed a violation — the evidence :func:`stale_pragmas` subtracts
    from the declared set."""
    by_path = {f.rel: f for f in project.files}
    out: List[Violation] = []
    for rule in rules:
        for v in rule.check(project):
            src = by_path.get(v.path)
            if src is not None:
                pline = src.suppressing_line(v.rule, v.line)
                if pline is not None:
                    if used_pragmas is not None:
                        used_pragmas.add((src.rel, pline))
                    continue
            out.append(v)
    out.sort(key=lambda v: (v.path, v.line, v.rule))
    return out


def stale_pragmas(
    project: Project, used_pragmas: Set[Tuple[str, int]]
) -> List[Violation]:
    """``stale-pragma`` violations for every ``# analysis: ok(...)`` that
    suppressed nothing on this run — the pragma mirror of the env-hatch
    dead-flag direction, and like it only meaningful on a whole-tree
    all-rules scan (a partial scan trivially "never needs" every pragma).
    Package files only: test fixtures carry pragmas for rules they
    deliberately do not trip."""
    out: List[Violation] = []
    for src in project.package_files():
        for line, rules in sorted(src.pragmas.items()):
            if (src.rel, line) in used_pragmas:
                continue
            out.append(Violation(
                rule="stale-pragma",
                path=src.rel,
                line=line,
                message=(
                    f"pragma ok({', '.join(sorted(rules))}) no longer "
                    "suppresses any finding — remove it (or it will mask "
                    "the next real violation on this line)"
                ),
            ))
    return out


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------


def load_baseline(path: str) -> List[dict]:
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    if not isinstance(data, list):
        raise SystemExit(f"baseline {path}: expected a JSON list")
    return data


def apply_baseline(
    violations: Sequence[Violation], baseline: Sequence[dict]
) -> Tuple[List[Violation], List[dict]]:
    """Split into (new violations, stale baseline entries)."""
    keys = {
        (e.get("rule", ""), e.get("path", ""), e.get("message", ""))
        for e in baseline
    }
    new = [v for v in violations if v.baseline_key not in keys]
    seen = {v.baseline_key for v in violations}
    stale = [
        e
        for e in baseline
        if (e.get("rule", ""), e.get("path", ""), e.get("message", "")) not in seen
    ]
    return new, stale
