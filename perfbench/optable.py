"""What an instruction of the compiled step IS.

A device trace names an op event by the instruction's text, and XLA:TPU names
an instruction by opcode and FIRST result.  It puts a statistic first in the
tuple of a fusion whose body is a matrix product or a convolution, so
``fusion:f32[2048]`` read as a reduction where it was the backward's products
with a norm's scale gradient as a by-output (PR 36).  The text of the step
executable says what each instruction is: ``parse`` makes one row an
instruction of every computation, ``describe`` reads a row's class, pass and
scopes (a fusion's from its fused instructions), and ``join`` hangs the
traced seconds of each instruction on its row.

``cls``, the first that holds:
  ``container``    a ``while``, ``conditional`` or ``call``: its event spans a
                   body that is listed op by op
  ``collective``   an ``all-reduce``, ``all-gather``, ``reduce-scatter``,
                   ``all-to-all`` or ``collective-permute``
  ``product``      a ``convolution``, ``dot`` or ``ragged-dot`` anywhere in it
  ``kernel``       a Mosaic custom call (``tpu_custom_call``)
  ``reduce``       a ``reduce`` or ``reduce-window`` and no product
  ``move``         nothing but copies, slices, pads, transposes, concatenations,
                   gathers, bitcasts, reshapes, broadcasts and conversions:
                   data movement without arithmetic (index arithmetic on
                   scalars aside)
  ``elementwise``  the rest
``pass``: ``recompute`` (``rematted_computation`` in ``op_name``), else
``backward`` (``transpose(jvp(``), else ``update`` (``optimizer_update``), else
``forward``.
``scopes``: every name in ``op_name``'s path (``cell06``, ``ssm_scan``,
``block_flash_fwd``), the wrappers ``jvp(...)`` and ``transpose(...)`` opened.

A fusion's own ``op_name`` is its root's.  So a fusion is read from its fused
instructions: those that speak for it are its products and kernels or, where
it has neither, every fused instruction that does work (and has an
``op_name``: what the compiler put in has none); it has the pass most of them
have, and a scope that at least half of them carry.
"""

from __future__ import annotations

import collections
import functools
import re
from typing import Any, Dict, FrozenSet, Iterable, List, Optional

from perfbench.trace import CONTAINERS, instruction_head

CLASSES = ("product", "kernel", "reduce", "move", "elementwise")
JOIN_FLOOR = 0.99  # of the traced op time has to find its row
_PRODUCTS = frozenset(("convolution", "dot", "ragged-dot"))
_REDUCES = frozenset(("reduce", "reduce-window"))
_COLLECTIVES = frozenset(("all-reduce", "all-gather", "reduce-scatter",
                          "all-to-all", "collective-permute",
                          "collective-broadcast"))
_MOVES = frozenset(("copy", "slice", "pad", "transpose", "concatenate",
                    "dynamic-slice", "dynamic-update-slice", "bitcast",
                    "reshape", "broadcast", "convert", "gather"))
_MOVE_CALLS = frozenset(("ConcatBitcast", "AllocateBuffer"))
# No work of their own: what a fused computation is handed and hands back.
_NEUTRAL = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                      "iota", "after-all", "partition-id", "replica-id"))
_MOSAIC = "tpu_custom_call"
_ASYNC = re.compile(r"-(start|done|update)$")
_TYPE = re.compile(r"\b([a-z]\w*\[[\d,]*\])")
_ATTRS = {
    "kind": re.compile(r", kind=k(\w+)"),
    "calls": re.compile(r", calls=%?([\w\-.]+)"),
    "target": re.compile(r', custom_call_target="([^"]*)"'),
    "op_name": re.compile(r'op_name="([^"]*)"'),
    "operand": re.compile(r"\((?:[^%()]*\s)?%([\w\-.]+)"),
}
_NUMBER = re.compile(r"\.\d+$")
_NAME = re.compile(r"[A-Za-z_][\w\-]*")
MAX_KEY_TYPES = 4


def parse(text: str) -> Dict[str, Dict[str, Any]]:
    """One row an instruction of every computation of a module's text, by the
    instruction's name (unique in a module): ``opcode``, the fusion ``kind``,
    every result type in ``types``, ``op_name``, a custom call's ``target``,
    the first ``operand``, and ``fused``, the rows of the computation that a
    fusion or an asynchronous pair ``calls``."""
    rows: Dict[str, Dict[str, Any]] = {}
    computations: Dict[str, List[Dict[str, Any]]] = {}
    inside: Optional[List[Dict[str, Any]]] = None
    for line in text.splitlines():
        if not line.startswith(" "):
            # "%fused_computation.3 (p: f32[8]) -> f32[8] {", "ENTRY %main ... {"
            if line.endswith("{") and not line.startswith("HloModule"):
                name = line.removeprefix("ENTRY ").split(" ", 1)[0].lstrip("%")
                inside = computations.setdefault(name, [])
            continue
        if inside is None:
            continue
        cut = line.find(", backend_config=")  # a Mosaic body is megabytes
        line = line[:cut].strip() if cut > 0 else line.strip()
        head = instruction_head(line)
        if head is None:
            continue
        name, types, opcode = head
        row: Dict[str, Any] = {"name": name, "opcode": opcode,
                               "types": _TYPE.findall(types)}
        for attr, pattern in _ATTRS.items():
            m = pattern.search(line)
            row[attr] = m.group(1) if m else None
        rows[name] = row
        inside.append(row)
    for row in rows.values():  # only a fusion and an async-start have `calls`
        row["fused"] = computations.get(row["calls"], [])
    for row in rows.values():
        # "async-done(%slice-start.482)": the work is what its start calls
        start = rows.get(row["operand"])
        if (row["opcode"].endswith(("-done", "-update")) and start
                and start["opcode"].endswith(("-start", "-update"))):
            row["fused"] = start["fused"]
    return rows


def _leaves(row: Dict[str, Any]) -> Iterable[Dict[str, Any]]:
    """The instructions that do a row's work: itself, or those of the
    computation a fusion calls, fusions inside it opened in turn."""
    if not row["fused"]:
        yield row
        return
    for inner in row["fused"]:
        yield from _leaves(inner)


def _is_scalar(row: Dict[str, Any]) -> bool:
    return all(t.endswith("[]") for t in row["types"])


def _base(opcode: str) -> str:
    """``copy`` of ``copy-start`` and ``copy-done``: one operation in two
    instructions, whose events on the ops line are its issue and its wait."""
    return _ASYNC.sub("", opcode)


@functools.lru_cache(maxsize=None)
def scopes_of(op_name: Optional[str]) -> FrozenSet[str]:
    """The names in an ``op_name``'s path: ``jit(step)/transpose(jvp(cell06))/
    ssm_scan/mul`` gives jit, step, transpose, jvp, cell06, ssm_scan, mul."""
    return frozenset(_NAME.findall(op_name or ""))


def pass_of(op_name: Optional[str]) -> str:
    op_name = op_name or ""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(jvp(" in op_name:
        return "backward"
    return "update" if "optimizer_update" in op_name else "forward"


def describe(row: Dict[str, Any]) -> Dict[str, Any]:
    """``cls``, ``pass``, ``scopes`` and the ``key`` under which a breakdown
    sums the row: the name without its number (a Mosaic kernel's is the
    kernel's), every result type and the class, ``fusion:f32[2048]+
    f32[2,8192]+bf16[2,8192,2048]{product}``."""
    leaves = list(_leaves(row))
    working = [r for r in leaves if r["opcode"] not in _NEUTRAL]
    opcodes = {_base(r["opcode"]) for r in working}
    kernels = [r for r in working if r["target"] == _MOSAIC]
    products = [r for r in working if r["opcode"] in _PRODUCTS]
    if _base(row["opcode"]) in CONTAINERS:
        cls = "container"
    elif opcodes & _COLLECTIVES:
        cls = "collective"
    elif products:
        cls = "product"
    elif kernels:
        cls = "kernel"
    elif opcodes & _REDUCES:
        cls = "reduce"
    elif all(_base(r["opcode"]) in _MOVES or r["target"] in _MOVE_CALLS
             or _is_scalar(r) for r in working):
        cls = "move"
    else:
        cls = "elementwise"
    speakers = products + kernels or working or leaves
    # what the compiler put in (a predicate's broadcast, an index) has no
    # op_name and no say where the program's own instructions have one
    speakers = [r for r in speakers if r["op_name"]] or speakers
    passes = collections.Counter(pass_of(r["op_name"]) for r in speakers)
    carried = collections.Counter(
        s for r in speakers for s in scopes_of(r["op_name"]))
    types = row["types"][:MAX_KEY_TYPES]
    if len(row["types"]) > MAX_KEY_TYPES:
        types.append(f"{len(row['types']) - MAX_KEY_TYPES}more")
    return {
        "cls": cls,
        "pass": passes.most_common(1)[0][0],
        "scopes": sorted(s for s, n in carried.items()
                         if 2 * n >= len(speakers)),
        "key": (f"{_NUMBER.sub('', row['name'])}:{'+'.join(types)}"
                f"{{{cls}}}"),
    }


def join(inst_seconds: Dict[str, float], rows: Dict[str, Dict[str, Any]]
         ) -> Dict[str, Any]:
    """The traced seconds of each instruction on its row.  ``found_share`` is
    the share of the op time whose instruction has a row; under
    ``JOIN_FLOOR`` the trace is of another executable than the text, and
    ``holds`` is false.  ``instructions`` has, by name, the seconds and what
    ``describe`` says of every instruction found, containers left out."""
    total = sum(inst_seconds.values())
    instructions: Dict[str, Dict[str, Any]] = {}
    lost = 0.0
    for name, seconds in inst_seconds.items():
        row = rows.get(name)
        if row is None:
            lost += seconds
            continue
        said = describe(row)
        if said["cls"] != "container":
            instructions[name] = {"seconds": seconds, **said}
    share = 1.0 - lost / total if total else 0.0
    return {"found_share": share, "holds": share >= JOIN_FLOOR,
            "lost_seconds": lost, "rows": len(rows),
            "instructions": instructions}


def seconds_where(joined: Optional[Dict[str, Any]], *, cls: Optional[str] = None,
                  pass_: Optional[str] = None, scope: Optional[str] = None
                  ) -> Optional[float]:
    """Seconds over the traced window of the instructions of one class, one
    pass or one scope (those given, together); None where the join does not
    hold."""
    if not joined or not joined["holds"]:
        return None
    return sum(i["seconds"] for i in joined["instructions"].values()
               if (cls is None or i["cls"] == cls)
               and (pass_ is None or i["pass"] == pass_)
               and (scope is None or scope in i["scopes"]))


def by_key(joined: Dict[str, Any]) -> Dict[str, float]:
    """Seconds by ``key``: what a breakdown lists."""
    out: Dict[str, float] = {}
    for i in joined["instructions"].values():
        out[i["key"]] = out.get(i["key"], 0.0) + i["seconds"]
    return out
