"""From the profiler's trace to numbers.

On this chip the trace has a plane ``/device:TPU:<n>`` per chip with lines
``Steps``, ``XLA Modules``, ``XLA Ops`` and ``Async XLA Ops``.  ``XLA
Modules`` has one event per executed program (``jit_step(<hash>)``), on the
device's own clock.  ``XLA Ops`` has one event per HLO instruction executed,
named by the instruction's text; no scope name of the program reaches it.

Busy time is the union of the ``XLA Modules`` events.  The union of ``XLA
Ops`` would leave out the gaps between the 25 000 ops inside a running
program (PR 23 read 32 % idle that way where 1.4 % was true).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Tuple

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# `%fusion.33 = bf16[1052676,208]{1,0:T(8,128)(2,1)} fusion(...`
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])")
# Instructions whose event spans those of a body that is listed op by op:
# summing them with the rest would count the body twice.
CONTAINERS = ("while:", "conditional:", "call:")


def newest_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def op_key(text: str) -> str:
    """``fusion:bf16[1052676,208]`` from an instruction's text: the name
    without its number, and the type of the (first) result.  No classes."""
    m = _OP.match(text)
    return f"{m.group(1)}:{m.group(2)}" if m else text.split(" ", 1)[0][:64]


def read_planes(profile) -> List[Dict[str, Any]]:
    """The device planes of a ``jax.profiler.ProfileData`` (or of anything
    shaped like one) as plain data: per chip the module events
    ``(name, start_ns, duration_ns)`` and the op events likewise."""
    planes = []
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: line for line in plane.lines}
        planes.append({
            "chip": int(m.group(1)),
            "modules": _events(lines.get(MODULES_LINE)),
            "ops": _events(lines.get(OPS_LINE)),
        })
    return sorted(planes, key=lambda p: p["chip"])


def _events(line) -> List[Tuple[str, float, float]]:
    if line is None:
        return []
    return sorted(((e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events), key=lambda e: e[1])


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, edge = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > edge:
            total += end - max(start, edge)
            edge = end
    return total


def reduce_planes(planes: List[Dict[str, Any]], pattern: str) -> Dict[str, Any]:
    """What the benchmark takes from the device planes.

    ``pattern`` picks the step program among the module events.  The traced
    window runs from the start of the first step to the start of the last,
    so it holds whole periods only; busy time is the union of all module
    events inside it, averaged over the chips.  Op sums are over the same
    window, on the first chip.
    """
    step = re.compile(pattern)
    per_chip, busy, windows = [], [], []
    ops: Dict[str, float] = {}
    for plane in planes:
        steps = [e for e in plane["modules"] if step.search(e[0])]
        per_chip.append({"chip": plane["chip"],
                         "step_ms": [d / 1e6 for _, _, d in steps],
                         "program": steps[0][0] if steps else None})
        if len(steps) < 2:
            continue
        lo, hi = steps[0][1], steps[-1][1]
        inside = [(max(s, lo), min(s + d, hi))
                  for _, s, d in plane["modules"] if s + d > lo and s < hi]
        busy.append(union_ns(inside) / 1e9)
        windows.append((hi - lo) / 1e9)
        if not ops:
            for name, s, d in plane["ops"]:
                key = op_key(name)
                if lo <= s < hi and not key.startswith(CONTAINERS):
                    ops[key] = ops.get(key, 0.0) + d / 1e9
    return {
        "chips": per_chip,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": sum(windows) / len(windows) if windows else 0.0,
        "periods": max(min((len(c["step_ms"]) for c in per_chip), default=1) - 1, 0),
        "op_seconds": ops,
    }


def reduce_trace(log_dir: str, pattern: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    path = newest_xplane(log_dir)
    out = reduce_planes(read_planes(ProfileData.from_file(path)), pattern)
    out["xplane_bytes"] = os.path.getsize(path)
    return out
