"""CLI: ``python -m mpi4dl_tpu.analysis pallascheck [--json] [--kernels ...]
[--baseline F] [--sarif F] [--require-vmem-frac X]``
(also reachable as ``python -m mpi4dl_tpu.analysis.pallascheck``).

Traces every kernel case registered in ``mpi4dl_tpu.ops.kernel_registry``
on the CPU host (no TPU compile), enumerates each kernel's full grid, and
runs every check (see the package docstring for the finding classification).
Exit status mirrors the analyzer: 0 = no findings after baseline
filtering, 1 = findings, 2 = usage/environment errors.  The CI job runs
the full registry with ``--json --out`` + ``--sarif`` and uploads both as
artifacts on failure.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List


def main(argv=None) -> int:
    from mpi4dl_tpu.analysis.pallascheck import FINDING_KINDS, check_case
    from mpi4dl_tpu.ops.kernel_registry import REGISTRY, case_names

    ap = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analysis pallascheck",
        description="Static Pallas kernel verifier (docs/analysis.md): "
        "traces every registered kernel, enumerates the full grid, and "
        "abstract-interprets the kernel jaxpr per grid point, proving "
        "grid/BlockSpec soundness, the per-grid-point VMEM budget, "
        "DMA/semaphore discipline and accumulator-init coverage.  "
        "Finding kinds: " + ", ".join(FINDING_KINDS),
    )
    ap.add_argument("--kernels", metavar="NAMES", default=None,
                    help="comma-separated subset of registry cases; a bare "
                         "kernel name (e.g. block_flash) selects every "
                         "variant of it "
                         f"(default: {','.join(c.name for c in REGISTRY)})")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("--out", metavar="F", default=None,
                    help="also write the JSON findings to this file")
    ap.add_argument("--baseline", metavar="F", default=None,
                    help="JSON list of accepted findings (keyed on "
                         "kind/kernel/grid_class/message) to filter out")
    ap.add_argument("--sarif", metavar="F", default=None,
                    help="write findings as a SARIF 2.1.0 log (GitHub "
                         "code-scanning annotations)")
    ap.add_argument("--require-vmem-frac", metavar="X", type=float,
                    default=1.0,
                    help="fail any kernel whose per-grid-point VMEM total "
                         "(double-buffered blocked operands + scratch) "
                         "exceeds X of the 16 MiB pool (default 1.0; CI "
                         "gates at 0.75 to keep compiler headroom)")
    args = ap.parse_args(argv)

    if not 0.0 < args.require_vmem_frac <= 1.0:
        print(f"pallascheck: --require-vmem-frac {args.require_vmem_frac} "
              "must be in (0, 1]", file=sys.stderr)
        return 2

    wanted = None
    if args.kernels:
        wanted = [k.strip() for k in args.kernels.split(",") if k.strip()]
        known = {c.name for c in REGISTRY}
        known |= {c.name.split(":", 1)[0] for c in REGISTRY}
        unknown = [k for k in wanted if k not in known]
        if unknown:
            print(f"pallascheck: unknown kernel(s) {unknown}; "
                  f"have {[c.name for c in REGISTRY]}", file=sys.stderr)
            return 2
    names = set(case_names(wanted))
    cases = [c for c in REGISTRY if c.name in names]

    findings = []
    for case in cases:
        try:
            findings.extend(check_case(
                case, require_vmem_frac=args.require_vmem_frac))
        except Exception as e:  # noqa: BLE001 — a case that cannot trace
            print(f"pallascheck: {case.name}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 2

    if args.baseline:
        with open(args.baseline, "r", encoding="utf-8") as fh:
            baseline = json.load(fh)
        if not isinstance(baseline, list):
            print(f"pallascheck: baseline {args.baseline}: expected a "
                  "JSON list", file=sys.stderr)
            return 2
        keys = {
            (e.get("kind", ""), e.get("kernel", ""),
             e.get("grid_class", ""), e.get("message", ""))
            for e in baseline
        }
        findings = [f for f in findings if f.baseline_key not in keys]

    rows: List[dict] = [
        {"kind": f.kind, "kernel": f.kernel, "grid_class": f.grid_class,
         "message": f.message}
        for f in findings
    ]
    payload = json.dumps({"findings": rows}, indent=2, sort_keys=True)
    if args.json:
        print(payload)
    else:
        for f in findings:
            print(f.render())
        print(
            f"pallascheck: {len(findings)} finding(s) across "
            f"{len(cases)} kernel case(s) "
            f"[vmem frac {args.require_vmem_frac:g}]",
            file=sys.stderr,
        )
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(payload + "\n")
    if args.sarif:
        from mpi4dl_tpu.analysis.sarif import sarif_log, write_sarif

        write_sarif(args.sarif, sarif_log(pallas_findings=findings))
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
