"""DMA/semaphore discipline and accumulator-init coverage: checks (c)+(d).

One sequential abstract interpretation of the kernel jaxpr over the full
grid, in the TPU's execution order (row-major, last dim innermost), with
scratch state persisting across grid points — exactly the machine model the
kernels are written against.  Scalar dataflow from ``program_id`` is
constant-folded so ``pl.when`` predicates like ``c == 0`` / ``ki == nk-1``
resolve concretely per grid point: the real kernels' guards take their
actual branches, and a *wrong* guard (the injected fixtures) walks the
wrong branch and trips a finding.  Unresolvable predicates walk BOTH
branches and merge conservatively: definite-written sets intersect,
maybe-written sets union, and a DMA started on one path but not the other
is an ``unmatched-dma`` finding by construction.

Tracked state:

- per-ref written/maybe-written (global across the grid — scratch
  persists) and per-visit-run written sets.  A scratch's runs are those of
  the outputs its values are stored into (:func:`_scratch_feeds`, a static
  value-flow pass): an accumulator that lives across several blocks of one
  output for the sake of another, coarser one (a flash backward's dq,
  resident over the k tiles while dk and dv leave a tile at a time) is held
  to the run of the output it feeds; a scratch that reaches no output is
  held to the runs in which EVERY output block stands (grid.output_runs);
- in-flight DMAs keyed by semaphore ref, carrying src/dst refs: a read of
  a dst before its wait or a write to a src/dst while in flight is a
  ``dma-race`` (the WAR hazard Mosaic does not fence, as an invariant);
- resolved ``device_id`` values of remote copies, checked bijective
  against the registry case's declared ring topology.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax.tree_util as jtu

from mpi4dl_tpu.analysis.pallascheck import Finding, point_class
from mpi4dl_tpu.analysis.pallascheck.grid import (
    block_offsets, grid_points, output_runs)
from mpi4dl_tpu.analysis.pallascheck.trace import KernelSpec

UNKNOWN = object()


@dataclasses.dataclass(frozen=True)
class _Ref:
    pos: int


@dataclasses.dataclass
class _Dma:
    src: Optional[int]
    dst: Optional[int]
    remote: bool
    start_class: str


@dataclasses.dataclass
class _State:
    written: set
    maybe: set
    run_written: set
    run_maybe: set
    inflight: Dict[int, _Dma]

    @classmethod
    def fresh(cls) -> "_State":
        return cls(set(), set(), set(), set(), {})

    def copy(self) -> "_State":
        return _State(set(self.written), set(self.maybe),
                      set(self.run_written), set(self.run_maybe),
                      dict(self.inflight))


class _Ctx:
    """Per-kernel walk context shared across grid points."""

    def __init__(self, spec: KernelSpec, case) -> None:
        self.spec = spec
        self.case = case
        self.findings: List[Finding] = []
        self._seen: set = set()
        self.point: Tuple[int, ...] = ()
        self.cls: str = ""
        self.run_revisit: Dict[int, bool] = {}  # by scratch pos, this point
        self.remote_ids: List[Tuple[Tuple[int, ...], Any]] = []

    def emit(self, kind: str, message: str, cls: Optional[str] = None) -> None:
        cls = self.cls if cls is None else cls
        if (kind, cls) in self._seen:
            return
        self._seen.add((kind, cls))
        self.findings.append(Finding(
            kind=kind, kernel=self.spec.case, grid_class=cls,
            message=message,
        ))

    def name(self, pos: Optional[int]) -> str:
        return self.spec.by_pos(pos).name if pos is not None else "?"


# -- scalar constant folding -------------------------------------------------

_FOLD = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "rem": lambda a, b: a % b if b else UNKNOWN,
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
    "and": lambda a, b: bool(a) and bool(b),
    "or": lambda a, b: bool(a) or bool(b),
    "xor": lambda a, b: bool(a) != bool(b),
    "not": lambda a: not a,
    "min": min,
    "max": max,
    "neg": lambda a: -a,
}


def _literal(v) -> Any:
    val = v.val
    try:
        if getattr(val, "shape", None) == ():
            return val.item()
    except (AttributeError, TypeError, ValueError):
        return UNKNOWN
    return val if isinstance(val, (int, float, bool)) else UNKNOWN


def _read(env: Dict, v) -> Any:
    if hasattr(v, "val"):  # Literal
        return _literal(v)
    return env.get(v, UNKNOWN)


def _scalar(x) -> bool:
    return isinstance(x, (bool, int, float)) and not isinstance(x, _Ref)


# -- DMA tree decoding -------------------------------------------------------

def _dma_parts(eqn, env):
    """(src_pos, dst_pos, sem_pos, src_sem_pos, device_id_value) of a
    dma_start/dma_wait equation via its flattening tree.  Layout on jax
    0.4.37: (src, src_transforms, dst, dst_transforms, dma_sem,
    sem_transforms, src_sem, src_sem_transforms, device_id).  ``src_sem``
    is non-None only for remote copies: the start signals it locally when
    the outbound data has left, so it carries the source-reuse (WAR)
    obligation while ``dma_sem`` (the recv semaphore) carries the
    destination-landing obligation."""
    tree = jtu.tree_unflatten(eqn.params["tree"], list(eqn.invars))
    if not isinstance(tree, (tuple, list)) or len(tree) < 5:
        return None, None, None, None, None

    def ref_pos(node):
        val = _read(env, node) if node is not None else None
        return val.pos if isinstance(val, _Ref) else None

    src, dst, sem = ref_pos(tree[0]), ref_pos(tree[2]), ref_pos(tree[4])
    src_sem = ref_pos(tree[6]) if len(tree) > 6 else None
    device_id = tree[8] if len(tree) > 8 else None
    if device_id is None:
        dev = None
    elif isinstance(device_id, (tuple, list)):
        dev = tuple(_read(env, d) if hasattr(d, "aval") or hasattr(d, "val")
                    else d for d in device_id)
    else:
        dev = _read(env, device_id)
    return src, dst, sem, src_sem, dev


# -- ref access checks -------------------------------------------------------

def _check_read(ctx: _Ctx, state: _State, pos: int) -> None:
    op = ctx.spec.by_pos(pos)
    for sem, dma in state.inflight.items():
        if dma.dst == pos:
            ctx.emit(
                "dma-race",
                f"{op.name} is read while the DMA into it (semaphore "
                f"{ctx.name(sem)}, started at class {dma.start_class}) is "
                "still in flight — Mosaic does not fence DMA writes "
                "against vector/MXU reads; wait first",
            )
    if op.role not in ("scratch", "out"):
        return
    if pos not in state.written and pos not in state.maybe:
        ctx.emit(
            "uninit-accumulator",
            f"{op.name} ({op.role}) is read at grid point {ctx.point} "
            "before anything ever wrote it",
        )
    elif (op.role == "scratch" and ctx.run_revisit.get(pos, False)
          and pos not in state.run_written and pos not in state.run_maybe
          and pos in state.written):
        ctx.emit(
            "uninit-accumulator",
            f"{op.name} (scratch) is read at the first grid point "
            f"{ctx.point} of a revisited-output run while still holding "
            "the previous output block's values — the init guard "
            "(pl.when(k == 0)-style) does not cover this revisit",
        )


def _check_write(ctx: _Ctx, state: _State, pos: int) -> None:
    for sem, dma in state.inflight.items():
        if dma.src == pos:
            ctx.emit(
                "dma-race",
                f"{ctx.name(pos)} is written while it is the SOURCE of an "
                f"in-flight DMA (semaphore {ctx.name(sem)}) — the "
                "write-after-read hazard, which Mosaic does not fence; "
                "wait before reusing the buffer",
            )
        if dma.dst == pos:
            ctx.emit(
                "dma-race",
                f"{ctx.name(pos)} is written while the DMA into it "
                f"(semaphore {ctx.name(sem)}) is still in flight — the "
                "store and the landing copy race",
            )
    state.written.add(pos)
    state.maybe.add(pos)
    state.run_written.add(pos)
    state.run_maybe.add(pos)


# -- the walk ----------------------------------------------------------------

def _merge(ctx: _Ctx, base: _State, branches: List[_State]) -> _State:
    """Conservative join after walking unknown-predicate branches."""
    written = set.intersection(*(b.written for b in branches))
    maybe = set.union(*(b.maybe for b in branches))
    run_written = set.intersection(*(b.run_written for b in branches))
    run_maybe = set.union(*(b.run_maybe for b in branches))
    keys = [set(b.inflight) for b in branches]
    if any(k != keys[0] for k in keys[1:]):
        diff = set.union(*keys) - set.intersection(*keys)
        ctx.emit(
            "unmatched-dma",
            "DMA in-flight set differs across a data-dependent branch "
            f"(semaphores {sorted(ctx.name(p) for p in diff)}): some path "
            "starts or waits a copy the other does not",
        )
    inflight: Dict[int, _Dma] = {}
    for b in branches:
        inflight.update(b.inflight)
    return _State(written, maybe, run_written, run_maybe, inflight)


def _walk(ctx: _Ctx, jaxpr, env: Dict, state: _State) -> _State:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "program_id":
            env[eqn.outvars[0]] = ctx.point[eqn.params["axis"]]
        elif prim == "num_programs":
            env[eqn.outvars[0]] = ctx.spec.grid[eqn.params["axis"]]
        elif prim in _FOLD:
            vals = [_read(env, v) for v in eqn.invars]
            if all(_scalar(v) for v in vals):
                env[eqn.outvars[0]] = _FOLD[prim](*vals)
        elif prim == "convert_element_type":
            val = _read(env, eqn.invars[0])
            if _scalar(val):
                env[eqn.outvars[0]] = int(val) if isinstance(val, bool) else val
        elif prim == "cond":
            state = _walk_cond(ctx, eqn, env, state)
        elif prim in ("pjit", "closed_call", "core_call", "custom_jvp_call",
                      "custom_vjp_call", "custom_vjp_call_jaxpr",
                      "remat_call", "checkpoint"):
            inner = (eqn.params.get("jaxpr")
                     or eqn.params.get("call_jaxpr")
                     or eqn.params.get("fun_jaxpr"))
            if inner is not None:
                consts = getattr(inner, "consts", ())
                ij = getattr(inner, "jaxpr", inner)
                for cv, c in zip(ij.constvars, consts):
                    env[cv] = c if _scalar(c) else UNKNOWN
                for bv, ov in zip(ij.invars, eqn.invars):
                    env[bv] = _read(env, ov)
                state = _walk(ctx, ij, env, state)
                for outer, innerv in zip(eqn.outvars, ij.outvars):
                    env[outer] = _read(env, innerv)
        elif prim in ("while", "scan"):
            state = _walk_loop(ctx, eqn, env, state)
        elif prim == "get":
            val = _read(env, eqn.invars[0])
            if isinstance(val, _Ref):
                _check_read(ctx, state, val.pos)
        elif prim == "swap":
            val = _read(env, eqn.invars[0])
            if isinstance(val, _Ref):
                _check_write(ctx, state, val.pos)
        elif prim == "addupdate":
            val = _read(env, eqn.invars[0])
            if isinstance(val, _Ref):
                _check_read(ctx, state, val.pos)
                _check_write(ctx, state, val.pos)
        elif prim == "dma_start":
            src, dst, sem, src_sem, dev = _dma_parts(eqn, env)
            remote = dev is not None or src_sem is not None
            for s, d in ((sem, _Dma(src=None if src_sem is not None else src,
                                    dst=dst, remote=remote,
                                    start_class=ctx.cls)),
                         (src_sem, _Dma(src=src, dst=None, remote=remote,
                                        start_class=ctx.cls))):
                if s is None:
                    continue
                if s in state.inflight:
                    ctx.emit(
                        "unmatched-dma",
                        f"second DMA start on semaphore {ctx.name(s)} "
                        f"while the copy started at class "
                        f"{state.inflight[s].start_class} has not been "
                        "waited — starts and waits must pair 1:1 per "
                        "semaphore",
                    )
                if d.dst is not None:
                    # the landing copy races any other in-flight copy's dst
                    for s2, dma in state.inflight.items():
                        if dma.dst == d.dst and s2 != s:
                            ctx.emit(
                                "dma-race",
                                f"two in-flight DMAs target "
                                f"{ctx.name(d.dst)} (semaphores "
                                f"{ctx.name(s2)}, {ctx.name(s)})",
                            )
                state.inflight[s] = d
            if dev is not None:
                ctx.remote_ids.append((ctx.point, dev))
        elif prim == "dma_wait":
            _, dst, sem, _, _ = _dma_parts(eqn, env)
            if sem is not None:
                dma = state.inflight.pop(sem, None)
                if dma is None:
                    ctx.emit(
                        "unmatched-dma",
                        f"DMA wait on semaphore {ctx.name(sem)} with no "
                        "copy in flight on it along this path",
                    )
                else:
                    landed = dma.dst if dma.dst is not None else dst
                    if landed is not None:
                        state.written.add(landed)
                        state.maybe.add(landed)
                        state.run_written.add(landed)
                        state.run_maybe.add(landed)
        # all other primitives: pure value flow, outvars stay UNKNOWN
    return state


def _walk_cond(ctx: _Ctx, eqn, env: Dict, state: _State) -> _State:
    branches = eqn.params["branches"]
    pred = _read(env, eqn.invars[0])
    operands = eqn.invars[1:]

    def enter(branch, st: _State) -> Tuple[_State, List]:
        ij = branch.jaxpr
        for cv, c in zip(ij.constvars, branch.consts):
            env[cv] = c if _scalar(c) else UNKNOWN
        for bv, ov in zip(ij.invars, operands):
            env[bv] = _read(env, ov)
        st = _walk(ctx, ij, env, st)
        return st, [_read(env, v) for v in ij.outvars]

    if _scalar(pred):
        idx = min(max(int(pred), 0), len(branches) - 1)
        state, outs = enter(branches[idx], state)
        for outer, val in zip(eqn.outvars, outs):
            env[outer] = val
        return state
    results, outs_per = [], []
    for branch in branches:
        st, outs = enter(branch, state.copy())
        results.append(st)
        outs_per.append(outs)
    for i, outer in enumerate(eqn.outvars):
        vals = [outs[i] for outs in outs_per]
        env[outer] = vals[0] if all(
            _scalar(v) and v == vals[0] for v in vals
        ) else UNKNOWN
    return _merge(ctx, state, results)


def _walk_loop(ctx: _Ctx, eqn, env: Dict, state: _State) -> _State:
    """One conservative body walk (the body may run 0..n times): writes
    inside become maybe-written only, and a body that changes the in-flight
    DMA set starts copies it cannot pair on every iteration count."""
    inner = (eqn.params.get("jaxpr") or eqn.params.get("body_jaxpr"))
    if inner is None:
        return state
    ij = getattr(inner, "jaxpr", inner)
    for cv, c in zip(ij.constvars, getattr(inner, "consts", ())):
        env[cv] = c if _scalar(c) else UNKNOWN
    for bv, ov in zip(ij.invars, eqn.invars[-len(ij.invars):]):
        env[bv] = _read(env, ov)
    after = _walk(ctx, ij, env, state.copy())
    if set(after.inflight) != set(state.inflight):
        ctx.emit(
            "unmatched-dma",
            "a loop body changes the set of in-flight DMAs "
            f"({sorted(ctx.name(p) for p in set(after.inflight) ^ set(state.inflight))})"
            " — starts and waits cannot pair for every trip count",
        )
    return _merge(ctx, state, [state.copy(), after])


def _device_map_findings(ctx: _Ctx) -> None:
    case = ctx.case
    ring = getattr(case, "ring_size", None) if case is not None else None
    if not ctx.remote_ids:
        return
    if ring is None:
        ctx.emit(
            "nonbijective-device-map",
            "kernel performs remote (inter-chip) copies but its registry "
            "case declares no ring/halo topology (KernelCase.ring_size) to "
            "check the device_id map against",
            cls="",
        )
        return
    resolved = [(pt, d) for pt, d in ctx.remote_ids if _scalar(d)]
    by_group: Dict[Tuple[int, ...], List[Tuple[Tuple[int, ...], int]]] = {}
    for pt, dev in resolved:
        if not 0 <= int(dev) < ring:
            ctx.emit(
                "nonbijective-device-map",
                f"remote copy at grid point {pt} targets device {dev}, "
                f"outside the declared ring of {ring}",
                cls=point_class(ctx.spec.grid, pt),
            )
        by_group.setdefault(tuple(pt[1:]), []).append((pt, int(dev)))
    for group, entries in by_group.items():
        seen: Dict[int, Tuple[int, ...]] = {}
        for pt, dev in entries:
            if dev in seen:
                ctx.emit(
                    "nonbijective-device-map",
                    f"device_id map is not injective over the ring grid "
                    f"dim: grid points {seen[dev]} and {pt} both target "
                    f"device {dev} (ring size {ring})",
                    cls=point_class(ctx.spec.grid, pt),
                )
                break
            seen[dev] = pt


def _scratch_feeds(spec: KernelSpec) -> Dict[int, frozenset]:
    """For each scratch ref (by pos), the OUTPUT refs that a value read from
    it is ever stored into, directly or through other scratch: one static
    pass over the kernel jaxpr, every branch walked, a value carrying the
    scratch refs it was computed from."""
    roles = {op.pos: op.role for op in spec.operands}
    stored: Dict[int, set] = {}  # scratch pos -> refs its values reach

    def walk(jaxpr, env: Dict) -> None:
        def bound(v):  # a ref, the scratch refs a value came from, or nothing
            return None if hasattr(v, "val") else env.get(v)

        def flow(v):
            got = bound(v)
            return got if isinstance(got, frozenset) else frozenset()

        def enter(inner, operands) -> List:
            ij = getattr(inner, "jaxpr", inner)
            for bv, ov in zip(ij.invars, operands):
                env[bv] = bound(ov)
            walk(ij, env)
            return [flow(v) for v in ij.outvars]

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            ref = bound(eqn.invars[0]) if eqn.invars else None
            if prim in ("get", "swap", "addupdate") and isinstance(ref, _Ref):
                own = (frozenset({ref.pos}) if roles[ref.pos] == "scratch"
                       else frozenset())
                if prim != "get":
                    for src in flow(eqn.invars[1]):
                        stored.setdefault(src, set()).add(ref.pos)
                for ov in eqn.outvars:
                    env[ov] = own
                continue
            if prim == "cond":
                outs = [enter(b, eqn.invars[1:]) for b in eqn.params["branches"]]
            elif prim in ("while", "scan"):
                inner = eqn.params.get("jaxpr") or eqn.params.get("body_jaxpr")
                n = len(getattr(inner, "jaxpr", inner).invars)
                outs = [enter(inner, eqn.invars[-n:])] if inner is not None else []
            else:
                inner = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                         or eqn.params.get("fun_jaxpr"))
                outs = [enter(inner, eqn.invars)] if inner is not None else []
            through = frozenset().union(*(flow(v) for v in eqn.invars))
            for i, ov in enumerate(eqn.outvars):
                env[ov] = through.union(*(o[i] for o in outs if i < len(o)))

    walk(spec.jaxpr, {spec.jaxpr.invars[op.pos]: _Ref(op.pos)
                      for op in spec.operands})
    feeds: Dict[int, frozenset] = {}
    for op in spec.operands:
        if op.role != "scratch":
            continue
        seen, todo = set(), [op.pos]
        while todo:
            for nxt in stored.get(todo.pop(), ()):
                if nxt not in seen:
                    seen.add(nxt)
                    todo.append(nxt)
        feeds[op.pos] = frozenset(p for p in seen if roles[p] == "out")
    return feeds


def _scratch_runs(spec: KernelSpec) -> Dict[int, List[int]]:
    """For each scratch ref, the id of its visit run at every grid point: a
    new run starts where the block of EVERY output it feeds moves on (the
    outputs of the registered kernels' accumulators move together); a
    scratch that feeds none keeps :func:`grid.output_runs`."""
    offsets = block_offsets(spec)
    fallback = output_runs(spec)
    out: Dict[int, List[int]] = {}
    for pos, fed in _scratch_feeds(spec).items():
        fed = [p for p in fed if p in offsets]
        if not fed:
            out[pos] = fallback
            continue
        runs, run = [], 0
        for t in range(len(fallback)):
            if t and all(offsets[p][t] != offsets[p][t - 1]
                         or offsets[p][t] is None for p in fed):
                run += 1
            runs.append(run)
        out[pos] = runs
    return out


def interp_findings(spec: KernelSpec, case=None) -> List[Finding]:
    ctx = _Ctx(spec, case)
    runs = _scratch_runs(spec)
    sizes = {pos: collections.Counter(r) for pos, r in runs.items()}
    points = grid_points(spec.grid)
    state = _State.fresh()
    for t, point in enumerate(points):
        ctx.point = point
        ctx.cls = point_class(spec.grid, point)
        for pos, r in runs.items():
            ctx.run_revisit[pos] = sizes[pos][r[t]] > 1
            if t == 0 or r[t] != r[t - 1]:
                state.run_written.discard(pos)
                state.run_maybe.discard(pos)
        env: Dict = {}
        for op in spec.operands:
            env[spec.jaxpr.invars[op.pos]] = _Ref(op.pos)
        state = _walk(ctx, spec.jaxpr, env, state)
    for sem, dma in state.inflight.items():
        ctx.emit(
            "unmatched-dma",
            f"DMA on semaphore {ctx.name(sem)} (into "
            f"{ctx.name(dma.dst)}, started at class {dma.start_class}) is "
            "still in flight when the kernel ends — no wait ever pairs it",
            cls=dma.start_class,
        )
    _device_map_findings(ctx)
    return ctx.findings
