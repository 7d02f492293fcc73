"""From the profiler's trace to numbers.

On this chip the trace has a plane ``/device:TPU:<n>`` per chip with lines
``Steps``, ``XLA Modules``, ``XLA Ops`` and ``Async XLA Ops``.  ``XLA
Modules`` has one event per executed program (``jit_step(<hash>)``), on the
device's own clock.  ``XLA Ops`` has one event per HLO instruction executed,
named by the instruction's own text (``%fusion.33 = bf16[...]{...}
fusion(...``); no scope name of the program reaches it, and the name and the
first result do not say what the instruction is (XLA:TPU puts a statistic
first in the tuple of a fusion whose body is a matrix product).  So the op
events are kept by the instruction's full name too (``fusion.33``), which
``optable.py`` joins to the compiled step.

Busy time is the union of the ``XLA Modules`` events.  The union of ``XLA
Ops`` would leave out the gaps between the 25 000 ops inside a running
program (PR 23 read 32 % idle that way where 1.4 % was true).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# `%fusion.33 = bf16[1052676,208]{1,0:T(8,128)(2,1)} fusion(...`
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])")
_HEAD = re.compile(r"^(?:ROOT )?%?([^\s=(]+) = ")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
# Opcodes of instructions whose event spans those of a body that is listed op
# by op: summing them with the rest would count the body twice.  By opcode:
# a ``conditional`` is as often named ``cond``, a ``call`` ``closed_call``.
CONTAINERS = frozenset(("while", "conditional", "call"))


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


def instruction_head(text: str) -> Optional[Tuple[str, str, str]]:
    """``(name, result type, opcode)`` of an instruction's text, the name with
    its number (``fusion.33``) and the type as written, a tuple's in its
    parentheses; None where the text is not an instruction's (or was cut
    before its opcode)."""
    m = _HEAD.match(text)
    if not m:
        return None
    rest = text[m.end():]
    if rest.startswith("("):  # a tuple's layouts hold parentheses of their own
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        else:
            return None
        end += 1
    else:
        end = rest.find(" ")
    op = _OPCODE.match(rest, end)
    return (m.group(1), rest[:end], op.group(1)) if end > 0 and op else None


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
    window, on the first chip, without the containers: ``op_seconds`` by
    ``op_key``, ``inst_seconds`` by the instruction's full name.
    """
    step = re.compile(pattern)
    per_chip, busy, windows = [], [], []
    ops: Dict[str, float] = {}
    insts: Dict[str, float] = {}
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
            for text, s, d in plane["ops"]:
                if not lo <= s < hi:
                    continue
                head = instruction_head(text)
                if head and head[2] in CONTAINERS:
                    continue
                key, inst = op_key(text), head[0] if head else text[:64]
                ops[key] = ops.get(key, 0.0) + d / 1e9
                insts[inst] = insts.get(inst, 0.0) + d / 1e9
    return {
        "chips": per_chip,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": sum(windows) / len(windows) if windows else 0.0,
        "periods": max(min((len(c["step_ms"]) for c in per_chip), default=1) - 1, 0),
        "op_seconds": ops,
        "inst_seconds": insts,
    }


def reduce_trace(log_dir: str, pattern: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    path = newest_xplane(log_dir)
    out = reduce_planes(read_planes(ProfileData.from_file(path)), pattern)
    out["xplane_bytes"] = os.path.getsize(path)
    return out
