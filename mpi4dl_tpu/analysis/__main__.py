"""CLI: ``python -m mpi4dl_tpu.analysis [--json] [--baseline F] [paths...]``.

With no paths, scans the repository tree the package sits in: the package
itself plus ``tests/``, ``benchmarks/``, ``bench.py`` and
``chip_smoke.py`` (the env-hatch dead-flag check needs the whole tree —
several hatches are read only by the harness).  Exit status: 0 when no
violations remain after baseline filtering, 1 otherwise, 2 on usage errors.

``python -m mpi4dl_tpu.analysis contracts ...`` dispatches to the
compiled-artifact contract gate (analysis/contracts — lowers the engine
families and diffs their StableHLO/jaxpr contracts against checked-in
goldens; see its ``--help``).  ``python -m mpi4dl_tpu.analysis ircheck
...`` dispatches to the IR-level shard-flow verifier (analysis/ircheck —
replication flow, collective matching, donation safety, async
well-formedness over the same engine builds; see its ``--help``).
``python -m mpi4dl_tpu.analysis pallascheck ...`` dispatches to the static
Pallas kernel verifier (analysis/pallascheck — grid/BlockSpec soundness,
VMEM budget certification, DMA/semaphore discipline and accumulator-init
coverage over every kernel in ops/kernel_registry; see its ``--help``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import List, Optional

from mpi4dl_tpu.analysis import (
    RULE_TABLE,
    apply_baseline,
    build_project,
    load_baseline,
    run_rules,
)


def default_paths(root: str) -> List[str]:
    cand = ["mpi4dl_tpu", "tests", "benchmarks", "bench.py", "chip_smoke.py"]
    return [os.path.join(root, c) for c in cand if os.path.exists(os.path.join(root, c))]


def repo_root() -> str:
    # the directory that holds the mpi4dl_tpu package
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg_dir)


def scope_filter(paths: List[str], scope: List[str]) -> List[str]:
    """Restrict absolute paths to those inside the gate's scan scope (a
    scope entry is a file to match exactly or a directory prefix)."""
    out = []
    for p in paths:
        for s in scope:
            if p == s or p.startswith(s.rstrip(os.sep) + os.sep):
                out.append(p)
                break
    return out


# Files whose declarations are the cross-file ground truth every other
# module is checked against (mesh axes; the env-hatch registry).  A change
# here invalidates --changed-only's file-local view: the evidence for a
# violation in an UNCHANGED module can live in these files.
CROSS_FILE_GROUND_TRUTH = ("mpi4dl_tpu/config.py", "mpi4dl_tpu/mesh.py")


def cross_file_ground_truth(paths: List[str]) -> List[str]:
    """The ground-truth files present in ``paths`` (normalized, relative
    suffix match — paths arrive absolute from git)."""
    hits = []
    for p in paths:
        norm = p.replace(os.sep, "/")
        for g in CROSS_FILE_GROUND_TRUTH:
            if norm.endswith("/" + g) or norm == g:
                hits.append(g)
    return sorted(set(hits))


def changed_python_files(root: str) -> Optional[List[str]]:
    """Repo-relative ``.py`` paths touched per git (worktree + index +
    untracked), for ``--changed-only`` pre-commit runs.  None when git is
    unavailable (caller falls back to a full scan)."""
    names: List[str] = []
    # git emits names relative to the TOPLEVEL, which may sit above `root`
    # (repo vendored inside an outer git repo) — resolve against it, not
    # root, or every changed file fails the exists check and the gate
    # silently passes.
    for cmd in (
        ["git", "-C", root, "rev-parse", "--show-toplevel"],
        ["git", "-C", root, "diff", "--name-only", "HEAD"],
        ["git", "-C", root, "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=30, check=True
            )
        except (OSError, subprocess.SubprocessError):
            return None
        if cmd[3] == "rev-parse":
            toplevel = proc.stdout.strip() or root
        else:
            names.extend(proc.stdout.splitlines())
    out = []
    for name in dict.fromkeys(names):  # dedup, keep order
        path = os.path.join(toplevel, name)
        if name.endswith(".py") and os.path.exists(path):
            out.append(path)
    return out


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "contracts":
        from mpi4dl_tpu.analysis.contracts.__main__ import main as contracts_main

        return contracts_main(argv[1:])
    if argv and argv[0] == "ircheck":
        from mpi4dl_tpu.analysis.ircheck.__main__ import main as ircheck_main

        return ircheck_main(argv[1:])
    if argv and argv[0] == "pallascheck":
        from mpi4dl_tpu.analysis.pallascheck.__main__ import (
            main as pallascheck_main,
        )

        return pallascheck_main(argv[1:])

    ap = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analysis",
        description="Shard-safety static analyzer (see docs/analysis.md). "
        "The `contracts` subcommand runs the compiled-artifact contract "
        "gate instead.",
    )
    ap.add_argument("paths", nargs="*", help="files/dirs to scan (default: repo tree)")
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ap.add_argument("--baseline", metavar="F", default=None,
                    help="JSON list of accepted violations to filter out")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="rewrite --baseline dropping stale entries "
                         "(entries that no longer match any violation)")
    ap.add_argument("--changed-only", action="store_true",
                    help="scan only files git reports as changed/untracked "
                         "(fast pre-commit mode; the dead-flag direction of "
                         "env-hatch and stale-baseline reporting are "
                         "disabled — both need a whole-tree scan)")
    ap.add_argument("--rule", action="append", default=None, metavar="NAME",
                    help="run only the named rule(s)")
    ap.add_argument("--sarif", metavar="F", default=None,
                    help="also write the (post-baseline) violations as a "
                         "SARIF 2.1.0 log for GitHub code scanning")
    ap.add_argument("--prune-pragmas", action="store_true",
                    help="list stale `# analysis: ok(...)` pragmas (those "
                         "that suppressed nothing on a whole-tree scan) "
                         "for removal, instead of the normal report")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--hatch-docs", action="store_true",
                    help="print the README env-hatch table from config.HATCHES")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in RULE_TABLE:
            print(f"{r.name}: {r.description}")
        return 0
    if args.hatch_docs:
        from mpi4dl_tpu.config import hatches_markdown

        print(hatches_markdown())
        return 0
    if args.prune_baseline and not args.baseline:
        print("analysis: --prune-baseline requires --baseline",
              file=sys.stderr)
        return 2
    if args.prune_baseline and args.changed_only:
        # staleness is judged against the FULL violation set; a partial scan
        # would mark every entry for an unscanned file stale and prune it
        print("analysis: --prune-baseline needs a whole-tree scan and "
              "cannot be combined with --changed-only", file=sys.stderr)
        return 2
    if args.prune_pragmas and (args.changed_only or args.paths or args.rule):
        # pragma staleness needs the FULL rule set over the FULL tree — a
        # subset scan trivially "never needs" every pragma outside it
        print("analysis: --prune-pragmas needs a whole-tree all-rules scan "
              "and cannot be combined with --changed-only, --rule or "
              "explicit paths", file=sys.stderr)
        return 2

    # Subcommands dispatch only as the FIRST token; a flag-first spelling
    # (`--json contracts`) would otherwise be treated as a scan path with
    # no .py files in it and exit 0 looking like a passed gate.
    for sub in ("contracts", "ircheck", "pallascheck"):
        if sub in args.paths:
            print(
                f"analysis: `{sub}` must come first: "
                f"python -m mpi4dl_tpu.analysis {sub} [flags]",
                file=sys.stderr,
            )
            return 2

    root = repo_root()
    partial_scan = False  # True only when actually scanning a subset
    if args.changed_only:
        if args.paths:
            print("analysis: --changed-only and explicit paths are "
                  "mutually exclusive", file=sys.stderr)
            return 2
        changed = changed_python_files(root)
        if changed is None:
            print("analysis: git unavailable; --changed-only falling back "
                  "to a full scan", file=sys.stderr)
            paths = default_paths(root)
        else:
            # same scope as the full gate — a changed file OUTSIDE the
            # default tree must not fail here when the real gate and CI
            # would never scan it
            changed = scope_filter(changed, default_paths(root))
            if not changed:
                print("analysis: no changed python files in scope",
                      file=sys.stderr)
                return 0
            widen = cross_file_ground_truth(changed)
            if widen:
                # Cross-file rules judge every OTHER file against the
                # ground truth these files declare (mesh axes, env
                # hatches): an edit here changes what is a violation in
                # unchanged modules, so the scan must widen to the
                # dependency set — the whole tree.
                print(
                    "analysis: --changed-only: cross-file ground truth "
                    f"changed ({', '.join(widen)}); widening to a full "
                    "scan so dependent findings in unchanged files are "
                    "not missed", file=sys.stderr,
                )
                paths = default_paths(root)
            else:
                paths = changed
                partial_scan = True
    else:
        paths = args.paths or default_paths(root)
    if not paths:
        print("analysis: nothing to scan", file=sys.stderr)
        return 2

    rules = RULE_TABLE
    if args.rule:
        by_name = {r.name: r for r in RULE_TABLE}
        unknown = [n for n in args.rule if n not in by_name]
        if unknown:
            print(f"analysis: unknown rule(s) {unknown}; have "
                  f"{sorted(by_name)}", file=sys.stderr)
            return 2
        rules = [by_name[n] for n in args.rule]

    project = build_project(paths, root=root)
    if partial_scan:
        # The dead-flag direction needs every hatch reader in scope; a
        # partial scan that happens to include config.py would flag hatches
        # whose reads live in unscanned files.
        project.hatch_decl_in_scan = False
    # Pragma staleness mirrors the dead-flag gating: only a whole-tree
    # all-rules scan can say a pragma suppressed nothing.
    whole_tree = not partial_scan and not args.paths and rules is RULE_TABLE
    used_pragmas = set() if whole_tree else None
    violations = run_rules(project, rules, used_pragmas=used_pragmas)
    if used_pragmas is not None:
        from mpi4dl_tpu.analysis.core import stale_pragmas

        stale_p = stale_pragmas(project, used_pragmas)
        if args.prune_pragmas:
            for v in stale_p:
                text = ""
                src = next((f for f in project.files if f.rel == v.path),
                           None)
                if src is not None:
                    lines = src.text.splitlines()
                    if 0 < v.line <= len(lines):
                        text = lines[v.line - 1].strip()
                print(f"{v.path}:{v.line}: {text}")
            print(
                f"analysis: {len(stale_p)} stale pragma(s) listed for "
                "removal", file=sys.stderr,
            )
            return 1 if stale_p else 0
        violations = sorted(
            violations + stale_p, key=lambda v: (v.path, v.line, v.rule)
        )
    elif args.prune_pragmas:
        print("analysis: --prune-pragmas needs a whole-tree all-rules "
              "scan", file=sys.stderr)
        return 2

    stale: List[dict] = []
    if args.baseline:
        baseline = load_baseline(args.baseline)
        violations, stale = apply_baseline(violations, baseline)
        if partial_scan:
            stale = []  # staleness is meaningless on a partial scan
        if stale and args.prune_baseline:
            kept = [e for e in baseline if e not in stale]
            with open(args.baseline, "w", encoding="utf-8") as fh:
                json.dump(kept, fh, indent=1)
                fh.write("\n")
            print(
                f"analysis: pruned {len(stale)} stale baseline entr"
                f"{'y' if len(stale) == 1 else 'ies'} from {args.baseline} "
                f"({len(kept)} kept)",
                file=sys.stderr,
            )

    if args.sarif:
        from mpi4dl_tpu.analysis.sarif import sarif_log, write_sarif

        descriptions = {r.name: r.description for r in RULE_TABLE}
        descriptions["stale-pragma"] = (
            "# analysis: ok(...) pragma that no longer suppresses anything"
        )
        write_sarif(args.sarif, sarif_log(
            violations=violations, rule_descriptions=descriptions,
        ))

    if args.json:
        print(json.dumps(
            {
                "violations": [
                    {
                        "rule": v.rule,
                        "path": v.path,
                        "line": v.line,
                        "message": v.message,
                    }
                    for v in violations
                ],
                "stale_baseline": stale,
            },
            indent=2,
        ))
    else:
        for v in violations:
            print(v.render())
        for e in stale:
            msg = (
                f"stale baseline entry (no longer fires): "
                f"{e.get('path')}: [{e.get('rule')}] {e.get('message')}"
            )
            print(f"warning: {msg}", file=sys.stderr)
            if os.environ.get("GITHUB_ACTIONS"):
                # Surfaced as an inline annotation on the CI run.
                print(f"::warning title=stale analyzer baseline::{msg}")
        if stale and not args.prune_baseline:
            print(
                f"warning: {len(stale)} stale baseline entr"
                f"{'y' if len(stale) == 1 else 'ies'} — rewrite with "
                "--prune-baseline",
                file=sys.stderr,
            )
        n_files = len(project.files)
        print(
            f"analysis: {len(violations)} violation(s) in {n_files} file(s) "
            f"[axes={','.join(project.axes) or '?'}; "
            f"hatches={len(project.hatches)}]",
            file=sys.stderr,
        )
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
