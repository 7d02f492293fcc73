"""CLI: ``python -m mpi4dl_tpu.analysis ircheck [--json] [--families ...]
[--baseline F] [--sarif F] [--quant SPEC]``
(also reachable as ``python -m mpi4dl_tpu.analysis.ircheck``).

Builds each contract engine family on the virtual CPU mesh, lowers and
compiles it, and runs every IR-level check (see the package docstring for
the finding classification).  Exit status mirrors the analyzer: 0 = no findings
after baseline filtering, 1 = findings, 2 = usage/environment errors.
The CI job runs all 8 families with ``--json --out`` and uploads the
findings as an artifact on failure.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List


def main(argv=None) -> int:
    from mpi4dl_tpu.analysis.contracts.engines import ENGINE_FAMILIES
    from mpi4dl_tpu.analysis.contracts.extract import ensure_virtual_mesh
    from mpi4dl_tpu.analysis.ircheck import FINDING_KINDS, check_family

    ap = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analysis ircheck",
        description="IR-level shard-flow verifier (docs/analysis.md): "
        "abstract-interpret each engine family's jaxpr and compiled "
        "scheduled HLO, proving replication-flow soundness, collective "
        "matching/deadlock freedom, donation safety and async "
        "well-formedness.  Finding kinds: " + ", ".join(FINDING_KINDS),
    )
    ap.add_argument("--families", metavar="NAMES", default=None,
                    help="comma-separated subset of engine families "
                         f"(default: {','.join(ENGINE_FAMILIES)})")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("--out", metavar="F", default=None,
                    help="also write the JSON findings to this file")
    ap.add_argument("--baseline", metavar="F", default=None,
                    help="JSON list of accepted findings (keyed on "
                         "kind/family/scope/message) to filter out")
    ap.add_argument("--sarif", metavar="F", default=None,
                    help="write findings as a SARIF 2.1.0 log (GitHub "
                         "code-scanning annotations)")
    ap.add_argument("--quant", metavar="SPEC", default=None,
                    help="verify the quantized-collective build instead "
                         "(e.g. int8)")
    args = ap.parse_args(argv)

    families = list(ENGINE_FAMILIES)
    if args.families:
        families = [f.strip() for f in args.families.split(",") if f.strip()]
        unknown = [f for f in families if f not in ENGINE_FAMILIES]
        if unknown:
            print(f"ircheck: unknown engine(s) {unknown}; "
                  f"have {list(ENGINE_FAMILIES)}", file=sys.stderr)
            return 2

    policy = None
    if args.quant:
        from mpi4dl_tpu.quant import QuantPolicy

        try:
            policy = QuantPolicy.parse(args.quant)
        except ValueError as e:
            print(f"ircheck: {e}", file=sys.stderr)
            return 2
        if policy is None:
            print("ircheck: --quant off is the raw build; drop the flag",
                  file=sys.stderr)
            return 2

    err = ensure_virtual_mesh(families)
    if err:
        print(f"ircheck: {err}", file=sys.stderr)
        return 2

    findings = []
    for family in families:
        findings.extend(check_family(family, quant=policy))

    if args.baseline:
        with open(args.baseline, "r", encoding="utf-8") as fh:
            baseline = json.load(fh)
        if not isinstance(baseline, list):
            print(f"ircheck: baseline {args.baseline}: expected a JSON "
                  "list", file=sys.stderr)
            return 2
        keys = {
            (e.get("kind", ""), e.get("family", ""), e.get("scope", ""),
             e.get("message", ""))
            for e in baseline
        }
        findings = [f for f in findings if f.baseline_key not in keys]

    rows: List[dict] = [
        {"kind": f.kind, "family": f.family, "scope": f.scope,
         "message": f.message, "bytes": f.bytes}
        for f in findings
    ]
    payload = json.dumps({"findings": rows}, indent=2, sort_keys=True)
    if args.json:
        print(payload)
    else:
        for f in findings:
            print(f.render())
        print(
            f"ircheck: {len(findings)} finding(s) across "
            f"{len(families)} engine famil"
            f"{'y' if len(families) == 1 else 'ies'}"
            + (f" [quant {args.quant}]" if args.quant else ""),
            file=sys.stderr,
        )
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(payload + "\n")
    if args.sarif:
        from mpi4dl_tpu.analysis.sarif import sarif_log, write_sarif

        write_sarif(args.sarif, sarif_log(ircheck_findings=findings))
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
