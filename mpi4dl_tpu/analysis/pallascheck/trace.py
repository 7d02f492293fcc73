"""Kernel tracing and spec extraction.

Traces each registered :class:`~mpi4dl_tpu.ops.kernel_registry.KernelCase`
with ``jax.make_jaxpr`` (CPU, no TPU compile), finds every ``pallas_call``
equation in the closed jaxpr (recursing through pjit/custom-vjp/control-flow
sub-jaxprs), and lifts the parts the checks consume into a stable
:class:`KernelSpec`:

- the grid and every operand's role/block shape/memory space/index-map
  jaxpr (from ``grid_mapping``; kernel-invar order is index operands,
  inputs, outputs, scratch);
- the kernel jaxpr itself, for the DMA/accumulator abstract interpreter.

Written against jax 0.9.0's pallas internals (``GridMapping``/
``BlockMapping``); everything reached here is exercised by
tests/test_pallascheck.py so a jax upgrade that moves a field fails loudly
in the fixture lane, not silently in the gate.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

#: normalized memory-space tags
ANY, VMEM, SMEM, SEMAPHORE = "any", "vmem", "smem", "semaphore"


def _memory_space(aval) -> str:
    ms = getattr(aval, "memory_space", None)
    if ms is None:
        return VMEM  # pallas default for blocked operands
    name = getattr(ms, "value", None) or str(ms)
    name = str(name).lower()
    if "semaphore" in name:
        return SEMAPHORE
    if "smem" in name:
        return SMEM
    if "any" in name:
        return ANY
    return VMEM


def _inner_aval(aval):
    return getattr(aval, "inner_aval", aval)


@dataclasses.dataclass(frozen=True)
class Operand:
    """One kernel operand, at its kernel-invar position ``pos``."""

    pos: int
    role: str                 # "index" | "in" | "out" | "scratch"
    name: str                 # stable label, e.g. "in0" / "out1" / "scratch2"
    shape: Tuple[int, ...]    # block shape (scratch: allocation shape)
    dtype: Any
    memory_space: str         # "any" | "vmem" | "smem" | "semaphore"
    array_shape: Optional[Tuple[int, ...]] = None  # whole-array shape
    index_map: Any = None     # ClosedJaxpr (None for scratch/index/ANY)

    @property
    def blocked(self) -> bool:
        """True when the Pallas pipeline stages this operand block by block
        (a VMEM/SMEM block smaller than — or equal to — the array, driven
        by an index map).  ANY-space operands stay in HBM unbocked."""
        return (
            self.role in ("in", "out")
            and self.memory_space in (VMEM, SMEM)
            and self.index_map is not None
        )

    def block_bytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n * np.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Everything the checks need about one traced ``pallas_call``."""

    case: str                 # registry case name (the finding key's kernel)
    grid: Tuple[int, ...]
    operands: Tuple[Operand, ...]   # kernel-invar order
    jaxpr: Any                # the kernel body jaxpr

    @property
    def outputs(self) -> Tuple[Operand, ...]:
        return tuple(o for o in self.operands if o.role == "out")

    @property
    def scratch(self) -> Tuple[Operand, ...]:
        return tuple(o for o in self.operands if o.role == "scratch")

    def by_pos(self, pos: int) -> Operand:
        return self.operands[pos]


def _sub_jaxprs(params) -> List:
    out = []
    for v in params.values():
        if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
            out.append(getattr(v, "jaxpr", v))
        elif isinstance(v, (list, tuple)):
            for item in v:
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    out.append(getattr(item, "jaxpr", item))
    return out


def find_pallas_eqns(jaxpr) -> List:
    """Every ``pallas_call`` equation reachable from a (closed) jaxpr."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for sub in _sub_jaxprs(eqn.params):
            out.extend(find_pallas_eqns(sub))
    return out


def spec_of_eqn(eqn, case_name: str) -> KernelSpec:
    """Lift one ``pallas_call`` equation into a :class:`KernelSpec`."""
    gm = eqn.params["grid_mapping"]
    kernel_jaxpr = eqn.params["jaxpr"]
    invars = kernel_jaxpr.invars
    n_idx = int(gm.num_index_operands)
    n_in = int(gm.num_inputs)
    n_out = int(gm.num_outputs)
    n_scr = int(gm.num_scratch_operands)
    if len(invars) != n_idx + n_in + n_out + n_scr:
        raise ValueError(
            f"{case_name}: kernel invar count {len(invars)} does not match "
            f"grid_mapping operand counts ({n_idx}+{n_in}+{n_out}+{n_scr})"
        )
    block_mappings = list(gm.block_mappings)  # inputs then outputs
    operands: List[Operand] = []
    for pos, var in enumerate(invars):
        aval = _inner_aval(var.aval)
        ms = _memory_space(var.aval)
        shape = tuple(int(d) for d in getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", np.int32)
        if pos < n_idx:
            role, label = "index", f"index{pos}"
            arr_shape, imap = None, None
        elif pos < n_idx + n_in + n_out:
            io = pos - n_idx
            role = "in" if io < n_in else "out"
            label = f"in{io}" if io < n_in else f"out{io - n_in}"
            bm = block_mappings[io]
            arr_shape = tuple(int(d) for d in bm.array_aval.shape)
            imap = None if ms == ANY else bm.index_map_jaxpr
            # Blocked dims carry their size; Squeezed dims are size 1.
            bs = tuple(
                int(getattr(d, "block_size", 1))
                for d in (bm.block_shape or shape)
            )
            shape = bs or shape
        else:
            role = "scratch"
            label = f"scratch{pos - n_idx - n_in - n_out}"
            arr_shape, imap = None, None
        operands.append(Operand(
            pos=pos, role=role, name=label, shape=shape, dtype=dtype,
            memory_space=ms, array_shape=arr_shape, index_map=imap,
        ))
    return KernelSpec(
        case=case_name,
        grid=tuple(int(g) for g in gm.grid),
        operands=tuple(operands),
        jaxpr=kernel_jaxpr,
    )


def trace_case(case) -> List[KernelSpec]:
    """Trace one registry case and extract every ``pallas_call`` spec.
    Multiple calls in one trace get ``#<i>`` name suffixes."""
    import jax

    fn, args = case.build()
    closed = jax.make_jaxpr(fn)(*args)
    eqns = find_pallas_eqns(closed)
    if not eqns:
        raise ValueError(
            f"registry case {case.name!r} traced to a jaxpr with no "
            "pallas_call — the registered entry no longer dispatches the "
            "kernel (stale registry row?)"
        )
    specs = []
    for i, eqn in enumerate(eqns):
        suffix = f"#{i}" if len(eqns) > 1 else ""
        specs.append(spec_of_eqn(eqn, case.name + suffix))
    return specs


def eval_index_map(imap, point: Sequence[int]) -> Optional[Tuple[int, ...]]:
    """Evaluate one block index map at a concrete grid point.  Scalar int
    invars are fed the grid indices in order; ref invars (scalar-prefetch
    operands the map could read but our kernels do not) are fed zeros.
    Returns None when the map is not statically evaluable (e.g. it actually
    reads a prefetch ref in a data-dependent way)."""
    from jax.core import eval_jaxpr

    coords = list(point)
    args = []
    for var in imap.jaxpr.invars:
        aval = _inner_aval(var.aval)
        shape = tuple(getattr(aval, "shape", ()))
        if shape == () and np.issubdtype(
            np.dtype(getattr(aval, "dtype", np.int32)), np.integer
        ) and coords:
            args.append(np.int32(coords.pop(0)))
        else:
            args.append(np.zeros(shape, getattr(aval, "dtype", np.int32)))
    try:
        out = eval_jaxpr(imap.jaxpr, imap.consts, *args)
        return tuple(int(v) for v in out)
    except Exception:  # noqa: BLE001 — non-evaluable map = no offsets
        return None
