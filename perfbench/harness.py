"""Runs one cell once: build, check against the reference, warm up, measure.

The cell goes through the program's normal path and nothing else:
``config_from_args`` -> ``build_mesh`` -> ``benchmarks.common.build_train``
-> ``resilience.run_supervised`` with the anomaly guard on, as
``benchmarks.common.run`` does it.  ``run`` itself takes a number of steps,
not a time, so the benchmark makes the two calls of the loop itself.

The benchmark reads the host's clock in two places it hands to the loop: a
wrapper round the step function (stamps the call and its return) and the
loop's ``print_fn`` (stamps the step line, printed once the loss has been
fetched).  The loop is not edited.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

from perfbench import optable
from perfbench import trace as trace_mod
from perfbench.catalog import Catalog, Cell
from perfbench.references.plain import (
    Tally, cast_floating, cross_entropy, model_flops)

WARM_STEPS = 3  # the compiling step and two more
MIN_WINDOW_STEPS = 10
TRACED_STEPS = 4
STEP_PROGRAM = r"^jit_step\("
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

class CompileWatch:
    """Programs jax built or loaded, by phase: count, seconds, how many took
    a second or more, and how many came from the persistent cache.  After
    chip_smoke._CompileWatch, whose note that a cache hit does not fire the
    event is wrong for jax 0.9.0: the event wraps ``compile_or_get_cached``,
    so on a hit its seconds are those of reading and loading the executable."""

    def __init__(self) -> None:
        self.phase = "setup"
        self.by_phase: Dict[str, Dict[str, float]] = {}

    def _phase(self) -> Dict[str, float]:
        return self.by_phase.setdefault(
            self.phase, {"count": 0, "large": 0, "seconds": 0.0, "hits": 0})

    def __call__(self, event: str, duration_secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            p = self._phase()
            p["count"] += 1
            p["large"] += duration_secs >= 1.0
            p["seconds"] += duration_secs

    def hit(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._phase()["hits"] += 1

    def get(self, phase: str, what: str) -> float:
        return self.by_phase.get(phase, {}).get(what, 0)

    def __enter__(self) -> "CompileWatch":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self.hit)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)
        jax.monitoring.unregister_event_listener(self.hit)


class Stamps:
    """The step function and the ``print_fn`` handed to ``run_supervised``,
    each stamping the host's clock."""

    def __init__(self, step_fn, echo=print) -> None:
        self._fn = step_fn
        self._echo = echo
        self.calls: List[float] = []
        self.returns: List[float] = []
        self.lines: List[float] = []
        self.losses: List[Any] = []

    @property
    def _cache_size(self):  # the loop's retrace probe looks for this
        return self._fn._cache_size

    def step(self, state, x, y):
        self.calls.append(time.perf_counter())
        out = self._fn(state, x, y)
        self.returns.append(time.perf_counter())
        self.losses.append(out[1]["loss"])
        return out

    def line(self, text: str) -> None:
        if text.startswith("epoch "):
            self.lines.append(time.perf_counter())
        self._echo(text)

    def mark(self) -> int:
        return len(self.calls)


def window_steps(seconds: float, warm_period_s: float) -> int:
    """Steps that fill ``seconds`` at the warm period, to the nearest step
    (so that a reading of the period half a percent off does not change the
    count), and never fewer than ten."""
    return max(MIN_WINDOW_STEPS, round(seconds / warm_period_s))


def p90(values: List[float]) -> float:
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def spans_of(stamps: Stamps, lo: int, hi: int, step_ms: List[float]
             ) -> Dict[str, List[float]]:
    """Host spans, in ms, of the steps ``lo`` to ``hi`` of one
    ``run_supervised`` call.  ``step_ms`` is the loop's own time of each of
    those steps (step call and loss fetch), from its ``StepMeter``."""
    c, r, l = stamps.calls, stamps.returns, stamps.lines
    n = hi - lo
    dispatch = [(r[lo + i] - c[lo + i]) * 1e3 for i in range(n)]
    return {
        "period": [(c[lo + i + 1] - c[lo + i]) * 1e3 for i in range(n - 1)],
        "fetch": [(c[lo + i + 1] - l[lo + i]) * 1e3 for i in range(n - 1)],
        "dispatch": dispatch,
        "loss_wait": [step_ms[i] - dispatch[i] for i in range(n)],
        "loop_other": [(l[lo + i] - c[lo + i]) * 1e3 - step_ms[i]
                       for i in range(n)],
    }


def place_compile_cache() -> Dict[str, Any]:
    """The persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else ``<checkout>/.jax_cache`` (``compat.ensure_compilation_cache``).
    The step's executable is hundreds of MB; a machine that caps the cache
    below that (``JAX_COMPILATION_CACHE_MAX_SIZE``, 192 MiB on the chip
    machine) makes jax refuse to store it and every run compile for minutes,
    so the cap is lifted for this process.  Programs that compile in under a
    second are stored too: a run has some sixty of them."""
    import jax

    from mpi4dl_tpu.compat import ensure_compilation_cache

    path = ensure_compilation_cache() or os.environ["JAX_COMPILATION_CACHE_DIR"]
    cap = jax.config.jax_compilation_cache_max_size
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return {"dir": path, "cap_found": cap}


def cache_entries(path: str) -> Dict[str, Any]:
    sizes = []
    for dirpath, _, files in os.walk(path):
        sizes += [os.path.getsize(os.path.join(dirpath, f)) for f in files]
    return {"files": len(sizes), "bytes": sum(sizes),
            "largest": sorted(sizes, reverse=True)[:3]}


def build(cell: Cell, seed: int, devices):
    """The cell through the entry point's own builders."""
    from benchmarks.common import build_train
    from mpi4dl_tpu.config import config_from_args, get_parser
    from mpi4dl_tpu.data import make_dataset
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh

    argv = cell.argv(seed)
    cfg = config_from_args(get_parser().parse_args(argv))
    family = cell.family
    spec = (MeshSpec(data=cfg.data_parallel, stage=max(cfg.split_size, 1))
            if family in ("lp", "gems") else MeshSpec.from_config(cfg))
    if spec.size != cell.chips:
        raise ValueError(f"cell {cell.name} asks for {cell.chips} chip(s) but "
                         f"its flags make a mesh of {spec.size}: {spec}")
    mesh = build_mesh(spec, devices)
    step, state, eval_params_fn, global_batch = build_train(cfg, family, mesh)
    return cfg, argv, step, state, eval_params_fn, global_batch, make_dataset(cfg)


def reference_check(cell: Cell, cfg, params, x, y) -> Dict[str, Any]:
    """The program's cells against the plain float32 reference's, on the
    same weights and batch, in one program of its own (no remat, no
    donation).

    Cell by cell, and every cell of the program is fed the REFERENCE's
    activation: a deep net of random weights amplifies rounding from layer
    to layer (on the chip the bf16 logits of the whole 2048x2048 AmoebaNet-D
    are off by half of the largest logit, and two float32 forwards of it
    differ by 0.15 at 128x128), so a comparison at the far end cannot tell
    rounding from a wrong layer.  Fed the same input, a cell in the compute
    dtype is off by rounding alone.  Returns each cell's relative L2 error,
    the reference's loss, and the model's FLOPs per image as the
    reference's walk counted them."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from mpi4dl_tpu.models import build_model

    model = build_model(cfg)
    tally = Tally()

    def first(act):
        return act[0] if isinstance(act, tuple) else act

    def compare(p, x, y):
        with jax.default_matmul_precision("highest"):
            ref_cells = cell.reference_cells()(p, cell.config["sizes"], tally)
            assert len(ref_cells) == len(model.cells)
            # Only the first cell can meet a leaf that is not floating
            # (token ids): both sides get it as the loader made it.
            act, errors = cast_floating(x, jnp.float32), []
            for i, (ref_cell, cell_i) in enumerate(zip(ref_cells, model.cells)):
                given = cast_floating(act, cfg.compute_dtype)
                got = first(cell_i.apply(p[i], given, ApplyCtx(train=True)))
                act = ref_cell(act)
                want = first(act)
                errors.append(jnp.linalg.norm(got.astype(jnp.float32) - want)
                              / jnp.linalg.norm(want))
        return jnp.stack(errors), cross_entropy(act, y)

    errors, ref_loss = jax.jit(compare)(params, x, y)
    errors = [float(e) for e in errors]
    return {
        "reference_loss": float(ref_loss),
        "cell_rel_err": errors,
        "cell_rel_err_max": worst(errors),
        "forward_macs_per_img": tally.macs // x.shape[0],
        "forward_macs_per_img_by_kind": {
            k: v // x.shape[0] for k, v in tally.by_kind.items()},
        "model_flops_per_img": model_flops(tally.macs) // x.shape[0],
    }


def worst(errors: List[float]) -> float:
    """The largest error, and not a number where any is none: ``max()`` keeps
    its first argument against a NaN, so a cell that is not a number passed
    by wherever it was not the first (PR 35)."""
    return float("nan") if any(map(math.isnan, errors)) else max(errors)


def check_stored_flops(cell: Cell, counted: int) -> None:
    """The configuration's file states the model's FLOPs a sample for the
    sizes its cells' traffic uses; a count that has drifted from it is an
    error."""
    stored = cell.stored_model_flops()
    if stored is not None and stored != counted:
        raise ValueError(
            f"{cell.config_name}: model_flops_per_img at {cell.size} is "
            f"{stored} in the file, {counted} by the reference's count")


def all_finite(tree) -> bool:
    """Every inexact leaf finite, in one jitted reduction (PR 22's NaN was in
    the parameters while the loss still read finite)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def check(t):
        leaves = [l for l in jax.tree.leaves(t)
                  if jnp.issubdtype(l.dtype, jnp.inexact)]
        return jnp.all(jnp.stack([jnp.all(jnp.isfinite(l)) for l in leaves]))

    return bool(check(tree))


def compared(*, losses: List[float], anomalies: int, state_finite: bool,
             compiles_in_window: int, first_loss: float, reference_loss: float,
             loss_tolerance: float, cell_rel_err_max: float,
             cell_tolerance: float) -> Dict[str, Dict[str, Any]]:
    """Every number ``correct`` compares, beside its limit, under the name
    of the condition it decides: the one table that ``verdict`` judges and
    that every run prints.  A condition holds where its value is at or under
    its limit; a value that is not a number is ``None`` (valid JSON) and
    holds nowhere."""
    table = {
        # how many losses are not finite; how often the guard spoke
        "losses_finite": (sum(not math.isfinite(v) for v in losses), 0),
        "guard_silent": (anomalies, 0),
        # 1 where every step's loss is the same number, and where the state
        # has a leaf that is not finite
        "loss_moves": (int(len(set(losses)) <= 1), 0),
        "state_finite": (int(not state_finite), 0),
        "no_compile_in_window": (compiles_in_window, 0),
        "first_loss_matches_reference": (
            abs(first_loss - reference_loss) / abs(reference_loss),
            loss_tolerance),
        "cells_match_reference": (cell_rel_err_max, cell_tolerance),
    }
    return {name: {"value": value if math.isfinite(value) else None,
                   "limit": limit}
            for name, (value, limit) in table.items()}


def verdict(table: Dict[str, Dict[str, Any]]) -> Dict[str, bool]:
    """The conditions of ``correct``, from ``compared``; all must hold."""
    return {name: c["value"] is not None and c["value"] <= c["limit"]
            for name, c in table.items()}


def trace_options():
    """Device planes and the loop's own step annotations; not every TraceMe
    of the runtime, which at the default level gave 1.5 M ``Transpose``
    events a step for laying out one 50 MB input (PR 23).  The traced steps
    stretch all the same (by 48 % and 19 % in the two first cells): while it
    traces, the device waits between programs for its tracer to drain the
    op events.  The device's own times do not change."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def hbm_bytes(compiled) -> Dict[str, int]:
    """``memory_analysis()`` of the step executable for the live arguments
    (``step.lower(state, x, y).compile()``: the program that ran, from jax's
    own cache): what the program holds on a device while it runs.  The
    runtime's ``peak_bytes_in_use`` leaves out the program's temporaries (it
    read 2.2 GiB where this reads 12.55)."""
    ma = compiled.memory_analysis()
    out = {k: int(getattr(ma, k + "_size_in_bytes"))
           for k in ("argument", "output", "temp", "alias", "generated_code")}
    out["total"] = (out["argument"] + out["output"] + out["temp"]
                    - out["alias"])
    return out


def run_cell(catalog: Catalog, cell: Cell, *, seed: int, seconds: float,
             trace: bool, t0: float, devices, out_dir: str,
             keep_trace: bool = False, say=print) -> Dict[str, Any]:
    """One run of one cell; returns the result object of the contract (and
    writes the details beside it)."""
    import jax

    from mpi4dl_tpu.resilience import AnomalyGuard, run_supervised
    from mpi4dl_tpu.utils import StepMeter

    os.makedirs(out_dir, exist_ok=True)
    tag = f"{cell.name}.seed{seed}.trace{int(trace)}"
    details_path = os.path.join(out_dir, tag + ".json")
    watch = CompileWatch()
    with watch:
        cfg, argv, step, state, eval_params_fn, global_batch, dataset = build(
            cell, seed, devices)
        x0, y0 = dataset.batch(0, global_batch)
        t_ref = time.perf_counter()
        ref = reference_check(cell, cfg, eval_params_fn(state), x0, y0)
        gc.collect()  # the check program leaves the device before the step
        ref_s = time.perf_counter() - t_ref
        check_stored_flops(cell, ref["model_flops_per_img"])

        stamps = Stamps(step, echo=say)
        anomalies = 0

        def loop(state, steps: int, profile: bool = False):
            meter = StepMeter(global_batch)
            result = run_supervised(
                stamps.step, state, dataset, global_batch=global_batch,
                steps_per_epoch=steps, num_workers=cfg.num_workers,
                meter=meter, print_fn=stamps.line, profile=profile,
                guard=AnomalyGuard.from_env(),
            )
            return result, meter.times_ms

        # Warm-up: every shape the window uses; set-up ends at the window's
        # first step call.
        warm, _ = loop(state, WARM_STEPS)
        anomalies += warm.anomalies
        c, l = stamps.calls, stamps.lines
        warm_period_s = (l[WARM_STEPS - 1] - c[1]) / (WARM_STEPS - 1)
        steps = window_steps(seconds, warm_period_s)
        traced = TRACED_STEPS if trace else 0
        say(f"perfbench: warm after {time.perf_counter() - t0:.1f} s "
            f"(reference check {ref_s:.1f} s): "
            f"{watch.get('setup', 'count')} programs built or loaded, "
            f"{watch.get('setup', 'hits')} of them from the cache, "
            f"{watch.get('setup', 'large')} took a second or more, "
            f"{watch.get('setup', 'seconds'):.1f} s in all; warm period "
            f"{warm_period_s * 1e3:.1f} ms; window of {steps} steps"
            + (f", the last {traced} traced" if traced else ""))

        watch.phase = "window"
        lo = stamps.mark()
        state = warm.state
        trace_info: Optional[Dict[str, Any]] = None
        traced_spans: Optional[Dict[str, List[float]]] = None
        # Without a checkpoint directory the guard only detects: an anomaly
        # raises out of the loop and the run ends with no result.
        res, window_ms = loop(state, steps - traced)
        state, anomalies = res.state, anomalies + res.anomalies
        mid = stamps.mark()
        if traced:
            trace_dir = os.path.join(out_dir, "trace." + tag)
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_options())
            try:
                res, traced_ms = loop(state, traced, profile=True)
            finally:
                jax.profiler.stop_trace()
            state, anomalies = res.state, anomalies + res.anomalies
            traced_spans = spans_of(stamps, mid, stamps.mark(), traced_ms)
        hi = stamps.mark()
        watch.phase = "after"

        setup_s = stamps.calls[lo] - t0
        untraced = mid - lo
        spans = spans_of(stamps, lo, mid, window_ms)
        wall_s = stamps.lines[mid - 1] - stamps.calls[lo]
        img_per_s = untraced * global_batch / wall_s
        losses = [float(v) for v in stamps.losses]
        failed = sum(not math.isfinite(v) for v in losses[lo:hi])

        t_mem = time.perf_counter()
        compiled = step.lower(state, x0, y0).compile()
        hbm = hbm_bytes(compiled)
        mem_s = time.perf_counter() - t_mem
        state_finite = all_finite(state)

        if traced:
            trace_info = trace_mod.reduce_trace(trace_dir, STEP_PROGRAM)
            if not keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
            # What each traced instruction is, from the text of the same
            # executable: after the window, so neither it nor set-up sees it.
            t_join = time.perf_counter()
            text = compiled.as_text()
            if keep_trace:
                with open(os.path.join(out_dir, f"step.{tag}.hlo.txt"), "w",
                          encoding="utf-8") as f:
                    f.write(text)
            trace_info["joined"] = optable.join(
                trace_info["inst_seconds"], optable.parse(text))
            trace_info["joined"]["seconds_to_join"] = (
                time.perf_counter() - t_join)

    tolerances = cell.config["tolerances"]
    table = compared(
        losses=losses, anomalies=anomalies, state_finite=state_finite,
        compiles_in_window=int(watch.get("window", "count")),
        first_loss=losses[0], reference_loss=ref["reference_loss"],
        loss_tolerance=tolerances["loss"]["value"],
        cell_rel_err_max=ref["cell_rel_err_max"],
        cell_tolerance=tolerances["cell"]["value"],
    )
    checks = verdict(table)
    used = list(devices)
    runtime_peak = max(
        (int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
         for d in used), default=0)
    device = {
        "platform": used[0].platform, "kind": used[0].device_kind,
        "count": len(used),
        # The fullest chip: the larger of the runtime's own peak and what the
        # step executable holds while it runs, which the runtime's counter
        # leaves out on this backend (see hbm_bytes).
        "memory_peak_bytes": max(runtime_peak, hbm["total"]),
    }
    end_to_end = {
        "img_per_s": img_per_s,
        "step_ms_p90": p90(spans["period"]),
        "hbm_gib": hbm["total"] / 2**30,
        "setup_s": setup_s,
    }
    record = {
        "spans": spans,
        "counters": {
            "compiles_in_window": int(watch.get("window", "count")),
            "compile_s": float(watch.get("setup", "seconds")),
        },
        "trace": trace_info,
        "run": {"img_per_s": img_per_s, "chips": cell.chips},
        "model": {"flops_per_img": ref["model_flops_per_img"],
                  "forward_macs_per_img": ref["forward_macs_per_img_by_kind"]},
        "peaks": {"bf16_flops": (catalog.peak(used[0].device_kind, "bf16_flops")
                                 if used[0].platform != "cpu" else None)},
    }
    result: Dict[str, Any] = {
        "correct": all(checks.values()), "attempted": hi - lo,
        "failed": failed, "metrics": {}, "device": device,
    }
    if trace:
        for m in catalog.metrics("per_layer", cell.name):
            value = catalog.read_layer_metric(m["name"], record)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if trace_info and trace_info["window_s"] > 0:
            device["busy_s"] = trace_info["busy_s"]
            device["window_s"] = trace_info["window_s"]
            result["breakdown"] = breakdown(trace_info, spans)
            result["device_classes"] = classes_line(trace_info)
            say(f"perfbench: device time by class "
                f"{json.dumps(result['device_classes'])}")
            stretch = (statistics.median(traced_spans["period"])
                       / statistics.median(spans["period"]))
            say(f"perfbench: tracing stretched the traced steps' period by "
                f"{(stretch - 1) * 100:.2f} % "
                f"({statistics.median(traced_spans['period']):.1f} ms against "
                f"{statistics.median(spans['period']):.1f} ms untraced)")
    else:
        for m in catalog.metrics("end_to_end", cell.name):
            result["metrics"][m["name"]] = {"value": end_to_end[m["name"]],
                                            "unit": m["unit"]}

    # Every number `correct` compared, beside its limit: the last key of the
    # result's line and the last lines on standard error.
    result["compared"] = table
    details = {
        "cell": cell.name, "seed": seed, "seconds": seconds, "argv": argv,
        "result": result, "checks": checks, "reference": ref,
        "reference_s": ref_s, "memory_analysis": hbm,
        "memory_analysis_s": mem_s, "runtime_peak_bytes": runtime_peak,
        "end_to_end": end_to_end, "setup_s": setup_s, "window_s": wall_s,
        "steps": untraced, "traced_steps": hi - mid,
        "warm_period_ms": warm_period_s * 1e3,
        "compiles": watch.by_phase, "losses": losses,
        "spans_ms": spans, "traced_spans_ms": traced_spans,
        "trace": trace_info,
    }
    with open(details_path, "w", encoding="utf-8") as f:
        json.dump(details, f, indent=1)
    say(f"perfbench: {cell.name} seed {seed}: {untraced} steps in "
        f"{wall_s:.3f} s after {setup_s:.1f} s of set-up; period median "
        f"{statistics.median(spans['period']):.3f} ms; "
        f"{record['counters']['compiles_in_window']} compiles in the window; "
        f"first loss {losses[0]:.6f} against the reference's "
        f"{ref['reference_loss']:.6f} (rel "
        f"{table['first_loss_matches_reference']['value']!r}, tolerance "
        f"{tolerances['loss']['value']}); cells off by at most "
        f"{ref['cell_rel_err_max']:.2e} in relative L2, each fed the "
        f"reference's input (tolerance {tolerances['cell']['value']}); "
        f"memory_analysis "
        f"{hbm['total'] / 2**30:.3f} GiB in {mem_s:.1f} s, runtime peak "
        f"{runtime_peak / 2**30:.3f} GiB; checks "
        f"{json.dumps(checks)}; details in "
        f"{os.path.relpath(details_path, catalog.root)}")
    for name, c in table.items():
        print(f"perfbench: compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def classes_line(trace_info: Dict[str, Any]) -> Dict[str, Any]:
    """What a traced line says of the join: the share of the op time that
    found its instruction in the compiled step's text (under
    ``optable.JOIN_FLOOR`` the class metrics read nothing), the classes' ms a
    traced period, and their sum over the step program's device time, which
    is 1 within a hundredth where nothing is counted twice or left out."""
    joined = trace_info["joined"]
    periods = max(trace_info["periods"], 1)
    ms = {c: 0.0 for c in optable.CLASSES}
    for inst in joined["instructions"].values():
        ms[inst["cls"]] = ms.get(inst["cls"], 0.0) + inst["seconds"] * 1e3 / periods
    device_step = statistics.median(trace_info["chips"][0]["step_ms"])
    return {"found_share": joined["found_share"], "join_holds": joined["holds"],
            "lost_ms": joined["lost_seconds"] * 1e3 / periods, "ms": ms,
            "sum_over_device_step": sum(ms.values()) / device_step}


def breakdown(trace_info: Dict[str, Any], spans: Dict[str, List[float]]
              ) -> Dict[str, Any]:
    """The ten largest sums of device time by instruction (by what the
    compiled step says the instruction is, ``optable``'s key with every result
    and the class, where the join holds; else by name and first result), and
    the idle gap of the traced periods shared out over what the host was
    doing.  The loop is synchronous (the next step is called once this one's
    loss is on the host), so the device idles through all of ``fetch`` and
    ``loop_other``; what is left of the gap is host time inside the step call
    and the loss fetch that the device did not cover: first ``dispatch``,
    then ``loss_wait``."""
    joined = trace_info.get("joined")
    by_key = (optable.by_key(joined) if joined and joined["holds"]
              else trace_info["op_seconds"])
    ops = sorted(by_key.items(), key=lambda kv: -kv[1])[:10]
    med = {k: statistics.median(v) / 1e3 for k, v in spans.items()}
    device_step = statistics.median(trace_info["chips"][0]["step_ms"]) / 1e3
    gap = max(med["period"] - device_step, 0.0)
    shares, left = [], gap
    for name in ("fetch", "loop_other", "dispatch"):
        part = max(min(med[name], left), 0.0)
        shares.append([name, part * trace_info["periods"]])
        left -= part
    shares.append(["loss_wait", left * trace_info["periods"]])
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": sorted(shares, key=lambda kv: -kv[1])}


def main(argv: List[str], t0: float) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="python3 -m perfbench")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", action="store_true",
                   help="leave the profiler's files and the step executable's "
                        "text under perfbench/out")
    args = p.parse_args(argv)
    catalog = Catalog()
    cell = catalog.cell(args.workload)

    import jax

    # libtpu logs under /tmp/tpu_logs unless told otherwise; a run writes
    # nothing outside its checkout.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        catalog.bench_dir, "out", "tpu_logs"))
    cache = place_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"perfbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"jax found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2
    print(f"perfbench: compile cache at {cache['dir']} (cap found "
          f"{cache['cap_found']}, lifted); before the run "
          f"{json.dumps(cache_entries(cache['dir']))}")
    result = run_cell(
        catalog, cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t0=t0, devices=devices[: cell.chips],
        out_dir=os.path.join(catalog.bench_dir, "out"),
        keep_trace=args.keep_trace,
    )
    print(f"perfbench: compile cache after the run "
          f"{json.dumps(cache_entries(cache['dir']))}")
    sys.stdout.flush()
    print(json.dumps(result))
    return 0
