"""Contract extraction: lowered StableHLO + jaxpr + scheduled HLO ->
structured contract.

The engine's train step is built, ``.lower()``-ed and (since schema 2)
``compile()``-d on the virtual mesh — never executed — so the gate runs on
any CPU host in tens of seconds, the same property that makes the source
analyzer usable without a chip.  The compile feeds the
``overlap`` section: the *scheduled* compiled HLO is the only artifact that
says whether a collective was split into async start/done halves (hideable)
or compiled sync (structurally unhideable) — obs/overlap.py's structural
projection, pinned per scope (ISSUE 9, ROADMAP item 2's overlap-structure
gate).
"""

from __future__ import annotations

from typing import Dict, List, Optional

# Schema 2: adds the ``overlap`` section (per-scope per-class async-pair/
# sync counts, payload bytes, structurally exposed bytes from the compiled
# scheduled HLO).  Schema 3: adds the ``ircheck`` section (per-kind IR
# verifier finding counts over the jaxpr + compiled HLO — a clean engine
# pins ``{}``, so a refactor that introduces a wasted-wire reduction or an
# unpaired async op fails the gate).  Goldens with an older schema are
# unusable — regenerate.
CONTRACT_SCHEMA = 3

# jaxpr collective primitives -> the mesh-axis parameter that names them.
_JAXPR_COLLECTIVES = ("psum", "pmax", "pmin", "ppermute", "all_gather",
                      "psum_scatter", "all_to_all", "pbroadcast")

# /jax/core/compile duration events (jax._src.dispatch): one per jaxpr
# trace / per jaxpr->MLIR lowering.  Counted during build+lower as the
# retrace budget — a refactor that starts tracing an engine twice shows up
# here before it shows up as wall-clock.
_TRACE_EVENT_SUFFIXES = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration")


def _aval_bytes(aval) -> int:
    try:
        import numpy as np

        n = 1
        for d in aval.shape:
            n *= int(d)
        return n * np.dtype(aval.dtype).itemsize
    except Exception:  # noqa: BLE001 — abstract tokens/effects have no shape
        return 0


def jaxpr_collective_stats(jaxpr) -> Dict[str, Dict[str, Dict[str, int]]]:
    """``{axis: {prim: {count, bytes}}}`` over every collective equation in
    a (closed) jaxpr, recursing into sub-jaxprs (scan/cond/pjit/remat/
    shard_map bodies).  Bytes are the equation's total output payload — the
    semantic per-invocation volume (a collective inside a scan body counts
    once; the contract is structural, not a per-step byte meter)."""
    out: Dict[str, Dict[str, Dict[str, int]]] = {}

    def record(axis: str, prim: str, nbytes: int) -> None:
        per_axis = out.setdefault(axis, {})
        entry = per_axis.setdefault(prim, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += nbytes

    def walk(jx) -> None:
        jx = getattr(jx, "jaxpr", jx)  # unwrap ClosedJaxpr
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim in _JAXPR_COLLECTIVES:
                axes = eqn.params.get("axes",
                                      eqn.params.get("axis_name", ()))
                if not isinstance(axes, (tuple, list)):
                    axes = (axes,)
                nbytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
                for ax in axes:
                    record(str(ax), prim, nbytes)
            for v in eqn.params.values():
                if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                    walk(v)
                elif isinstance(v, (list, tuple)):
                    for item in v:
                        if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                            walk(item)

    walk(jaxpr)
    return out


class _LoweringCounter:
    """Counts jaxpr traces and MLIR lowerings via jax.monitoring duration
    events while active (the retrace budget)."""

    def __init__(self):
        self.counts = {suffix: 0 for suffix in _TRACE_EVENT_SUFFIXES}

    def __call__(self, event: str, duration_secs: float, **kw) -> None:
        for suffix in _TRACE_EVENT_SUFFIXES:
            if event.endswith(suffix):
                self.counts[suffix] += 1

    def __enter__(self) -> "_LoweringCounter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)


def _entry_shapes(avals) -> List[str]:
    return [f"{getattr(a, 'dtype', '?')}{list(getattr(a, 'shape', ()))}"
            for a in avals]


def extract_contract(family: str, build=None) -> dict:
    """Extract the contract dict for one engine family.

    ``build`` overrides the canonical builder (tests inject perturbed
    engines through it); it must return ``(step, args)`` like
    :func:`~mpi4dl_tpu.analysis.contracts.engines.build_engine`.
    """
    import jax

    from mpi4dl_tpu.analysis.contracts.engines import build_engine
    from mpi4dl_tpu.obs.hlo_stats import (
        scope_coverage,
        stablehlo_collectives,
        stablehlo_sharding_annotations,
    )

    # Build+lower TWICE; the counter watches only the second (warm) pass.
    # Cold trace counts depend on process history (jax's trace caches are
    # shared — whichever engine runs first pays for common machinery), but
    # the warm count is the engine's intrinsic per-build retrace cost and is
    # history-independent (verified across extraction orders), so it can be
    # a golden.  A broken cache key that starts re-tracing per build shows
    # up here as a jump.
    builder = build or build_engine
    step, args = builder(family)
    step.lower(*args)
    with _LoweringCounter() as counter:
        step, args = builder(family)
        lowered = step.lower(*args)

    # Per-scope collective accounting from the lowered StableHLO.  (No
    # separate totals field: it would duplicate what the per-scope tree
    # already pins, as un-diffed golden state.)
    collectives: Dict[str, Dict[str, Dict[str, int]]] = {}
    for op in stablehlo_collectives(lowered):
        scope = op["scope"] or "<unscoped>"
        entry = collectives.setdefault(scope, {}).setdefault(
            op["kind"], {"count": 0, "bytes": 0}
        )
        entry["count"] += 1
        entry["bytes"] += op["bytes"]

    # Per-mesh-axis accounting from the jaxpr (trace-cache hit: the step was
    # just traced by .lower(), so this re-derivation is nearly free).
    jaxpr = jax.make_jaxpr(step)(*args)

    compiled_text = compiled_text_of(lowered)

    return {
        "schema": CONTRACT_SCHEMA,
        "engine": family,
        "jax": jax.__version__,
        "collectives": _sorted_nested(collectives),
        "axis_collectives": _sorted_nested(jaxpr_collective_stats(jaxpr)),
        "scopes": scope_coverage(lowered),
        "lowerings": {
            "traces": counter.counts["jaxpr_trace_duration"],
            "modules": counter.counts["jaxpr_to_mlir_module_duration"],
        },
        "shardings": {
            "annotations": dict(sorted(
                stablehlo_sharding_annotations(lowered).items()
            )),
            # in_avals is a pytree ((args...), kwargs{}) — flatten to the
            # actual leaf avals or the shape channel records nothing
            "inputs": _entry_shapes(
                jax.tree_util.tree_leaves(lowered.in_avals)
            ),
        },
        "overlap": _overlap_section(compiled_text),
        "ircheck": _ircheck_section(jaxpr, compiled_text, family),
    }


def _ircheck_section(jaxpr, compiled_text: str, family: str) -> dict:
    """Per-kind IR-verifier finding counts (analysis/ircheck) over the
    jaxpr and the compiled scheduled HLO.  ``{}`` = the engine proves
    clean; any nonzero count names the regression class directly."""
    from mpi4dl_tpu.analysis.ircheck import (
        check_hlo,
        check_jaxpr,
        finding_counts,
    )

    findings = check_jaxpr(jaxpr, family=family)
    findings += check_hlo(compiled_text, family=family)
    return finding_counts(findings)


def compiled_text_of(lowered) -> str:
    """Compile a lowered computation and return the scheduled HLO text.
    The compile bypasses the persistent compilation cache — it keys on the
    program minus debug metadata, so a scope-less executable compiled
    elsewhere could alias this build and hand back HLO without op_name
    paths (the obs/hbm.py attribution caveat applies here verbatim).
    Shared by the ``overlap``/``ircheck`` contract sections and
    ``analysis.ircheck.check_family``."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return compiled.as_text()


def _overlap_section(compiled_text: str) -> dict:
    """The compiled scheduled HLO's structural overlap projection
    (obs/overlap.py): which collectives ride async start/done pairs vs
    sync ops, per scope, with payload and structurally-exposed bytes —
    a collective compiled *without* a start/done split can never hide
    under compute, so a sync count that grows is an overlap regression no
    benchmark has to measure first."""
    from mpi4dl_tpu.obs.overlap import structural_overlap

    return structural_overlap(compiled_text)


def _sorted_nested(d: dict) -> dict:
    """Recursively key-sort so golden JSON files diff cleanly."""
    return {
        k: _sorted_nested(v) if isinstance(v, dict) else v
        for k, v in sorted(d.items())
    }


def ensure_virtual_mesh(families=None) -> Optional[str]:
    """Provision the 8-device CPU platform the engine builds need (the
    conftest/benchmark-runner recipe, applied just in time for the CLI).
    ``families`` limits the requirement to the engines actually being
    extracted.  Returns an error string when the backend is already
    initialized with too few devices, else None."""
    import jax

    from mpi4dl_tpu.analysis.contracts.engines import (
        ENGINE_FAMILIES,
        required_devices,
    )
    from mpi4dl_tpu.compat import ensure_host_device_count

    need = max(required_devices(f) for f in (families or ENGINE_FAMILIES))
    ensure_host_device_count(max(need, 8))
    have = len(jax.devices())
    if have < need:
        return (
            f"contract extraction needs {need} devices, have {have}; run "
            "under JAX_PLATFORMS=cpu in a fresh process so the virtual CPU "
            "mesh can be provisioned"
        )
    return None
