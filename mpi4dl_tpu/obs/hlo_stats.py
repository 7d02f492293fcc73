"""Collective accounting from compiled HLO — the reusable library form of
``benchmarks/communication/comm_volume_report.py`` (which now imports from
here).

Any jitted step can report, at runtime and on any host, how many collectives
XLA actually scheduled per step and the bytes each class moves — the
compiler-derived counterpart of the reference's MPI message accounting
(SURVEY §2a): collective-permute (halo exchange, pipeline handoffs, GEMS
mirror), all-reduce (DP gradients, cross-tile BN), all-gather /
reduce-scatter / all-to-all (junctions, GSPMD resharding).

Also home to :func:`stablehlo_debug_text`, the scope-name view of a lowered
(not yet compiled) program: StableHLO printed with debug locations carries
the ``jax.named_scope`` stack (``loc("jit(step)/.../cell03/halo_exchange_w/
ppermute")``), which is how tests assert the obs scopes survive lowering
without paying for a compile.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

COLLECTIVE_CLASSES = (
    "collective-permute", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all",
)

# StableHLO op names of the same five classes (the *lowered*, pre-compile
# artifact — what the contract gate in analysis/contracts reads).
STABLEHLO_COLLECTIVES = (
    "stablehlo.collective_permute", "stablehlo.all_reduce",
    "stablehlo.all_gather", "stablehlo.reduce_scatter",
    "stablehlo.all_to_all",
)

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
    "s8": 1, "u8": 1, "f64": 8, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,  # quantized fp8 payloads (quant layer)
}


def _tensor_bytes(shape_str: str) -> int:
    """bytes of one HLO shape literal like 'bf16[2,16,16,8]{...}'."""
    m = re.match(r"(\w+)\[([\d,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def hlo_collective_stats(hlo_text: str) -> dict:
    """Count collectives + bytes moved per class from compiled HLO text.

    Counts each op once with its OUTPUT shape (for permutes/all-gathers the
    received bytes; start/done pairs are deduplicated by counting only the
    -start form when present)."""
    stats = {k: {"count": 0, "bytes": 0} for k in COLLECTIVE_CLASSES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(
            r"(?:ROOT\s+)?\S+\s*=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\]\S*))\s*"
            r"(collective-permute|all-reduce|all-gather|reduce-scatter|"
            r"all-to-all)(-start|-done)?\(", s)
        if not m:
            continue
        shape_str, kind, phase = m.groups()
        if phase == "-done":
            continue  # counted at -start
        if shape_str.startswith("("):
            # Array entries of the tuple (split(',') would break multi-dim
            # shapes like bf16[2,16,16,8]).
            parts = re.findall(r"\w+\[[\d,]*\]", shape_str)
            if phase == "-start":
                # Async start tuples are (operand, result[, contexts]) —
                # one transfer; count the RESULT so async and sync forms of
                # the same program report identical bytes (all-gather's
                # result carries the group factor, reduce-scatter's the
                # scattered shard — both matching their sync outputs).
                nbytes = (
                    _tensor_bytes(parts[1]) if len(parts) > 1
                    else (_tensor_bytes(parts[0]) if parts else 0)
                )
            else:
                nbytes = sum(_tensor_bytes(t) for t in parts)
        else:
            nbytes = _tensor_bytes(shape_str)
        stats[kind]["count"] += 1
        stats[kind]["bytes"] += nbytes
    stats["total_bytes"] = sum(
        v["bytes"] for k, v in stats.items() if isinstance(v, dict)
    )
    stats["total_count"] = sum(
        v["count"] for k, v in stats.items() if isinstance(v, dict)
    )
    return stats


def compiled_collective_stats(compiled) -> dict:
    """:func:`hlo_collective_stats` of a jax.stages.Compiled."""
    return hlo_collective_stats(compiled.as_text())


def stablehlo_debug_text(lowered) -> str:
    """StableHLO asm WITH debug locations for a jax.stages.Lowered — the
    cheapest artifact in which ``obs.scope`` names are visible (no compile).
    Falls back to the compiled HLO's op_name metadata if the MLIR handle
    does not expose debug printing on this jax version."""
    try:
        mod = lowered.compiler_ir("stablehlo")
        return mod.operation.get_asm(enable_debug_info=True)
    except Exception:  # noqa: BLE001 — jaxlib API drift
        return lowered.compile().as_text()


def scope_names(debug_text: str) -> Dict[str, int]:
    """Histogram of named-scope path components found in a debug-located
    StableHLO / metadata-bearing HLO text.  Component = one level of the
    ``a/b/c`` op-name path, with jit/shard_map framing stripped."""
    out: Dict[str, int] = {}
    for m in re.finditer(r'"((?:jit|shmap)[^"]*)"', debug_text):
        for comp in m.group(1).split("/"):
            if comp.startswith(("jit(", "shmap", "transpose(", "vmap(")):
                continue
            out[comp] = out.get(comp, 0) + 1
    return out


# ---------------------------------------------------------------------------
# Lowered-StableHLO structural extraction (the compiled-artifact contract
# gate's raw material: analysis/contracts reads collectives, scope coverage
# and sharding annotations from a jax.stages.Lowered WITHOUT compiling).
# ---------------------------------------------------------------------------

# Transform wrappers jax threads into the op-name path; unwrapped so the
# forward op and its AD transpose land under the SAME semantic scope.
_WRAPPER_RE = re.compile(
    r"^(?:jvp|vjp|transpose|vmap|pmap|custom_jvp|custom_vjp|checkpoint|"
    r"remat|rematted_computation)\((.*)\)$"
)

# Bare framing components jax control-flow/remat lowering inserts into the
# path; dropped so scope keys stay the ``obs.scope`` vocabulary (a remat
# policy change moves collectives BETWEEN these frames without changing the
# semantic region they belong to).
_FRAMING_COMPONENTS = re.compile(
    r"^(?:checkpoint|rematted_computation|remat|while|body|cond|"
    r"branch_\d+(?:_fun)?|shard_map|closed_call|None)$"
)

_MLIR_TENSOR_RE = re.compile(
    # element type may carry uppercase (f8E4M3FN — the quant layer's fp8)
    r"tensor<(?:([0-9x]+)x)?([a-z][a-zA-Z0-9]+)>"
)

_MLIR_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "f64": 8, "i1": 1, "i8": 1, "ui8": 1,
    "i16": 2, "ui16": 2, "i32": 4, "ui32": 4, "i64": 8, "ui64": 8,
    "f8E4M3FN": 1, "f8E5M2": 1,  # quantized fp8 payloads (quant layer)
}


def clean_scope_component(comp: str) -> Optional[str]:
    """One op-name path component reduced to its semantic scope name:
    ``jvp(sp_level0)`` -> ``sp_level0``; jit/shmap framing -> None."""
    while True:
        m = _WRAPPER_RE.match(comp)
        if m is None:
            break
        comp = m.group(1)
    if not comp or comp.startswith(("jit(", "shmap", "pjit(")):
        return None
    if _FRAMING_COMPONENTS.match(comp):
        return None
    return comp


def clean_scope_path(op_name_path: str) -> str:
    """Scope key for one op-name path: wrapper/framing components cleaned,
    the trailing primitive name dropped (it is the op, not a scope) —
    ``jit(step)/jit(main)/jit(shmap_body)/jvp(sp_level0)/cell00/
    halo_exchange_spw/ppermute`` -> ``sp_level0/cell00/halo_exchange_spw``."""
    comps = [clean_scope_component(c) for c in op_name_path.split("/")[:-1]]
    return "/".join(c for c in comps if c)


def _mlir_type_bytes(type_str: str) -> int:
    """Total payload bytes of an MLIR type string; tuples sum members."""
    total = 0
    for dims, dt in _MLIR_TENSOR_RE.findall(type_str):
        n = 1
        for d in dims.split("x"):
            if d:
                n *= int(d)
        total += n * _MLIR_DTYPE_BYTES.get(dt, 4)
    return total


def _named_loc_path(loc_str: str) -> Optional[str]:
    """The op-name path of an MLIR name location, if the location is one:
    ``loc("jit(step)/.../ppermute"(callsite(...)))`` -> the quoted path.
    Inside a ``shard_map`` body jax restarts the name stack, so the path
    there begins at the first ``obs.scope`` (``"loss_reduce/psum"``), not at
    ``jit(``.  A file location (``loc("x.py":3:1)``) is not a name."""
    m = re.match(r'loc\("([^"]*)"\(', loc_str)
    return m.group(1) if m else None


def _walk_mlir_ops(op):
    yield op
    for region in op.regions:
        for block in region:
            for inner in block:
                yield from _walk_mlir_ops(inner)


def stablehlo_collectives(lowered) -> List[dict]:
    """Every collective op in a Lowered's StableHLO module, as
    ``{"kind", "scope", "bytes"}`` dicts — kind is the bare StableHLO op name
    (``all_reduce``...), scope the :func:`clean_scope_path` of its location,
    bytes the op's total result payload.  Walks the MLIR module directly (no
    text round-trip, no compile)."""
    mod = lowered.compiler_ir("stablehlo")
    out: List[dict] = []
    for func in mod.body:
        for op in _walk_mlir_ops(func):
            name = op.operation.name if hasattr(op, "operation") else op.name
            if name not in STABLEHLO_COLLECTIVES:
                continue
            path = _named_loc_path(str(op.location))
            nbytes = sum(_mlir_type_bytes(str(r.type)) for r in op.results)
            out.append({
                "kind": name.split(".", 1)[1],
                "scope": clean_scope_path(path) if path else "",
                "bytes": nbytes,
            })
    return out


def stablehlo_sharding_annotations(lowered) -> Dict[str, int]:
    """Histogram of GSPMD sharding annotations (``mhlo.sharding`` on
    ``Sharding``/``SPMDFullToShardShape``/``SPMDShardToFullShape`` custom
    calls) in a Lowered's StableHLO — the pre-partitioning record of every
    sharding constraint and shard_map boundary.  A junction that starts
    resharding differently shows up here before any benchmark regresses."""
    mod = lowered.compiler_ir("stablehlo")
    out: Dict[str, int] = {}
    for func in mod.body:
        for op in _walk_mlir_ops(func):
            name = op.operation.name if hasattr(op, "operation") else op.name
            if name != "stablehlo.custom_call":
                continue
            attrs = op.attributes
            try:
                target = str(attrs["call_target_name"]).strip('"')
            except KeyError:
                continue
            if target not in (
                "Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape",
            ):
                continue
            try:
                sharding = str(attrs["mhlo.sharding"]).strip('"')
            except KeyError:
                sharding = "<unannotated>"
            key = f"{target}:{sharding}"
            out[key] = out.get(key, 0) + 1
    return out


def scope_coverage(lowered) -> List[str]:
    """Sorted set of semantic scope names reachable in a Lowered's StableHLO
    locations — the contract gate's drift check for *instrumentation* (an
    ``obs.scope`` that stops covering its region disappears from here)."""
    mod = lowered.compiler_ir("stablehlo")
    names = set()
    for func in mod.body:
        for op in _walk_mlir_ops(func):
            path = _named_loc_path(str(op.location))
            if not path:
                continue
            for comp in path.split("/")[:-1]:
                cleaned = clean_scope_component(comp)
                if cleaned:
                    names.add(cleaned)
    return sorted(names)
