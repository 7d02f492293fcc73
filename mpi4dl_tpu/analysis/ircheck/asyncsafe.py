"""Async well-formedness over the compiled (scheduled) HLO module.

The schedule is execution order, so the async contract is structural:

- ``unpaired-async`` — every ``*-start`` (named collective halves and
  generic ``async-start`` wrappers) must have exactly one reachable
  ``*-done`` in its computation, resolved through ``async-update`` glue and
  view ops exactly like obs/overlap.py's ledger walk.  Zero dones: the
  transfer's completion is never awaited — on TPU the value is undefined
  and on a real interconnect the channel leaks; two dones: the second
  consumes a retired token.  A done whose chain reaches no start is the
  inverse orphan.
- ``async-dma-race`` — inside the start..done window, (a) any non-glue
  instruction consuming the in-flight start tuple (the DMA's live buffers)
  or (b) any in-place writer — an op carrying ``output_to_operand_aliasing``
  or a ``dynamic-update-slice`` — whose target buffer aliases the DMA
  *source* operand.  Both are the static form of the DMA/compute race the
  halo-RDMA kernels (ROADMAP item 2: ``make_async_remote_copy`` fused into
  the Pallas conv) must be developed against: compute scheduled into the
  window to hide the wire must not touch the window's live buffers.
- ``pallas-alias`` — every custom call's ``output_to_operand_aliasing``
  promises must be well-formed: operand index in range, no operand buffer
  promised to two outputs, aliased operand shape equal to the output
  (sub)shape.  This is the argument-alias contract a Pallas kernel asserts
  with ``input_output_aliasing`` (``pallas_attention.py``) — asserted
  manually, so nothing else checks it before silicon.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from mpi4dl_tpu.analysis.ircheck import Finding
from mpi4dl_tpu.obs.hbm import Instr, parse_hlo_module
from mpi4dl_tpu.obs.overlap import _tuple_elements
from mpi4dl_tpu.obs.timeline import ASYNC_GLUE_OPS, collective_base

_LAYOUT = re.compile(r"\{[\d,\s]*\}")
_ALIAS_ATTR = re.compile(r"output_to_operand_aliasing=\{(.*)")
_ALIAS_PAIR = re.compile(
    r"\{([\d,\s]*)\}:\s*\(\s*(\d+)\s*,\s*\{([\d,\s]*)\}\s*\)"
)


def _strip_layout(shape: str) -> str:
    return _LAYOUT.sub("", shape).replace(" ", "")


def _is_start(ins: Instr, comps: Dict[str, List[Instr]]) -> bool:
    """A wire-bearing async start: a named ``<collective>-start`` or a
    generic ``async-start`` wrapping a collective computation (copy-start
    and friends are not wire traffic — same convention as the overlap
    ledger)."""
    if not ins.opcode.endswith("-start"):
        return False
    if collective_base(ins.opcode):
        return True
    if ins.opcode == "async-start":
        for callee in ins.callees:
            for sub in comps.get(callee, ()):
                if collective_base(sub.opcode):
                    return True
    return False


def _chain_start(name: str, by_name: Dict[str, Instr],
                 starts: Set[str],
                 _seen: Optional[Set[str]] = None) -> Optional[str]:
    """Follow an operand chain through async-update glue and views back to
    a start's name (obs/overlap.py's ``_resolve_start`` shape)."""
    if name in starts:
        return name
    if _seen is None:
        _seen = set()
    if name in _seen:
        return None
    _seen.add(name)
    ins = by_name.get(name)
    if ins is None:
        return None
    if ins.opcode in ASYNC_GLUE_OPS or ins.is_view:
        for op in ins.operands:
            found = _chain_start(op, by_name, starts, _seen)
            if found:
                return found
    return None


def _buffer_roots(name: str, by_name: Dict[str, Instr],
                  _seen: Optional[Set[str]] = None) -> Set[str]:
    """Non-view instruction name(s) whose buffer ``name`` aliases."""
    if _seen is None:
        _seen = set()
    if name in _seen:
        return set()
    _seen.add(name)
    ins = by_name.get(name)
    if ins is None:
        return {name}
    if ins.opcode in ("get-tuple-element", "bitcast", "tuple"):
        roots: Set[str] = set()
        for op in ins.operands:
            roots |= _buffer_roots(op, by_name, _seen)
        return roots
    return {name}


def async_findings(hlo_text: str, family: str = "") -> List[Finding]:
    comps, _ = parse_hlo_module(hlo_text)
    out: List[Finding] = []
    for instrs in comps.values():
        out += _comp_async_findings(instrs, comps, family)
        out += _custom_call_alias_findings(instrs, family)
    return out


def _comp_async_findings(instrs: Sequence[Instr],
                         comps: Dict[str, List[Instr]],
                         family: str) -> List[Finding]:
    by_name = {i.name: i for i in instrs}
    pos = {i.name: k for k, i in enumerate(instrs)}
    starts = {i.name for i in instrs if _is_start(i, comps)}
    dones: Dict[str, List[str]] = {s: [] for s in starts}
    out: List[Finding] = []

    for ins in instrs:
        if not ins.opcode.endswith("-done"):
            continue
        if not (collective_base(ins.opcode) or ins.opcode == "async-done"):
            continue
        src = _chain_start(ins.operands[0], by_name, starts) \
            if ins.operands else None
        if src is None:
            out.append(Finding(
                kind="unpaired-async",
                scope=ins.scope,
                message=(
                    f"{ins.opcode} {ins.name} resolves to no pending "
                    "*-start in its computation (done without start)"
                ),
                family=family,
            ))
        else:
            dones[src].append(ins.name)

    for s in sorted(starts):
        ins = by_name[s]
        n = len(dones[s])
        if n != 1:
            what = ("is never awaited (start without done)" if n == 0 else
                    f"has {n} dones ({', '.join(dones[s])}) — the extras "
                    "consume a retired async token")
            out.append(Finding(
                kind="unpaired-async",
                scope=ins.scope,
                message=f"{ins.opcode} {s} {what}",
                family=family,
                bytes=ins.bytes,
            ))
            continue
        out += _window_race_findings(
            ins, by_name[dones[s][0]], instrs, by_name, pos, family
        )
    return out


def _window_race_findings(start: Instr, done: Instr,
                          instrs: Sequence[Instr],
                          by_name: Dict[str, Instr],
                          pos: Dict[str, int],
                          family: str) -> List[Finding]:
    out: List[Finding] = []
    lo, hi = pos[start.name], pos[done.name]
    # Buffers live across the window: the start tuple itself plus the
    # buffers its operands alias (the DMA source the transfer reads from).
    src_roots: Set[str] = set()
    for op in start.operands:
        src_roots |= _buffer_roots(op, by_name)
    window_glue = {start.name, done.name}
    for ins in instrs[lo + 1:hi]:
        if ins.name in window_glue:
            continue
        if ins.opcode in ASYNC_GLUE_OPS or ins.is_view:
            continue  # the pair's own glue/view plumbing
        reads: Set[str] = set()
        for op in ins.operands:
            reads |= _buffer_roots(op, by_name)
        if start.name in reads:
            out.append(Finding(
                kind="async-dma-race",
                scope=ins.scope or start.scope,
                message=(
                    f"{ins.opcode} {ins.name} consumes the in-flight "
                    f"async value of {start.opcode} {start.name} inside "
                    "its start..done window"
                ),
                family=family,
                bytes=ins.bytes,
            ))
            continue
        # In-place writers into the DMA source buffer.
        writes: Set[str] = set()
        if "output_to_operand_aliasing=" in ins.raw:
            for _, op_idx, _ in _ALIAS_PAIR.findall(ins.raw):
                k = int(op_idx)
                if k < len(ins.operands):
                    writes |= _buffer_roots(ins.operands[k], by_name)
        if ins.opcode == "dynamic-update-slice" and ins.operands:
            writes |= _buffer_roots(ins.operands[0], by_name)
        hit = writes & src_roots
        if hit:
            out.append(Finding(
                kind="async-dma-race",
                scope=ins.scope or start.scope,
                message=(
                    f"{ins.opcode} {ins.name} writes in place into buffer "
                    f"{'/'.join(sorted(hit))} while {start.opcode} "
                    f"{start.name} is reading it (DMA source overwritten "
                    "inside the start..done window)"
                ),
                family=family,
                bytes=ins.bytes,
            ))
    return out


def _custom_call_alias_findings(instrs: Sequence[Instr],
                                family: str) -> List[Finding]:
    out: List[Finding] = []
    for ins in instrs:
        if ins.opcode != "custom-call":
            continue
        m = _ALIAS_ATTR.search(ins.raw)
        if not m:
            continue
        pairs = _ALIAS_PAIR.findall(m.group(1))
        claimed: Dict[Tuple[int, Tuple[int, ...]], str] = {}
        outputs = _tuple_elements(ins.shape)
        for o_idx_s, op_idx_s, op_sub_s in pairs:
            o_idx = tuple(int(x) for x in o_idx_s.split(",") if x.strip())
            op_idx = int(op_idx_s)
            op_sub = tuple(int(x) for x in op_sub_s.split(",") if x.strip())
            if op_idx >= len(ins.operands):
                out.append(Finding(
                    kind="pallas-alias",
                    scope=ins.scope,
                    message=(
                        f"custom-call {ins.name}: output {list(o_idx)} "
                        f"aliases operand {op_idx} but the call has only "
                        f"{len(ins.operands)} operand(s)"
                    ),
                    family=family,
                ))
                continue
            key = (op_idx, op_sub)
            if key in claimed:
                out.append(Finding(
                    kind="pallas-alias",
                    scope=ins.scope,
                    message=(
                        f"custom-call {ins.name}: operand {op_idx} is "
                        f"aliased by outputs {claimed[key]} and "
                        f"{list(o_idx)} — double alias of one buffer"
                    ),
                    family=family,
                ))
                continue
            claimed[key] = str(list(o_idx))
            out_shape = ins.shape
            if o_idx:
                if o_idx[0] >= len(outputs):
                    out.append(Finding(
                        kind="pallas-alias",
                        scope=ins.scope,
                        message=(
                            f"custom-call {ins.name}: aliased output index "
                            f"{list(o_idx)} out of range for result shape "
                            f"{ins.shape}"
                        ),
                        family=family,
                    ))
                    continue
                out_shape = outputs[o_idx[0]]
            op_shape = _operand_shape(ins, op_idx, instrs)
            if op_shape and _strip_layout(op_shape) != \
                    _strip_layout(out_shape):
                out.append(Finding(
                    kind="pallas-alias",
                    scope=ins.scope,
                    message=(
                        f"custom-call {ins.name}: output {list(o_idx)} "
                        f"shape {_strip_layout(out_shape)} != aliased "
                        f"operand {op_idx} shape {_strip_layout(op_shape)}"
                    ),
                    family=family,
                ))
    return out


def _operand_shape(ins: Instr, op_idx: int,
                   instrs: Sequence[Instr]) -> Optional[str]:
    if op_idx >= len(ins.operands):
        return None
    name = ins.operands[op_idx]
    for other in instrs:
        if other.name == name:
            return other.shape
    return None
