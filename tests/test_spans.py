"""The span recorder (obs/spans.py): nesting and self time on a fake clock,
the ring's bound, the ``MPI4DL_NO_SCOPES`` hatch, what the supervised loop
and the loader record for every step, the crash marker's phase words, the
retrace finder, and the benchmark's two readers on a recorder filled by
hand.  All on the CPU.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi4dl_tpu.obs import spans
from mpi4dl_tpu.obs.flight import FlightRecorder, read_flight
from mpi4dl_tpu.obs.scopes import _reset_enabled_cache
from mpi4dl_tpu.resilience import run_supervised
from mpi4dl_tpu.resilience.supervisor import classify_failure, read_crash_marker
from test_resilience import _ToyDataset, _toy_state, _toy_step


class FakeClock:
    """Nanoseconds that move only when the test says so."""

    def __init__(self) -> None:
        self.t = 1_000

    def __call__(self) -> int:
        return self.t

    def tick(self, ns: int) -> None:
        self.t += ns


@pytest.fixture
def clock():
    return FakeClock()


@pytest.fixture
def rec(clock):
    return spans.Recorder(clock=clock, enabled=True, annotate=False)


@pytest.fixture
def process_recorder():
    """A fresh process-wide recorder, forgotten again afterwards."""
    spans._reset_recorder()
    yield spans.recorder()
    spans._reset_recorder()


@pytest.fixture
def no_scopes(monkeypatch):
    """``MPI4DL_NO_SCOPES=1`` for the process's recorder, undone afterwards."""
    monkeypatch.setenv("MPI4DL_NO_SCOPES", "1")
    _reset_enabled_cache()
    spans._reset_recorder()
    yield spans.recorder()
    monkeypatch.delenv("MPI4DL_NO_SCOPES")
    _reset_enabled_cache()
    spans._reset_recorder()


# --- the recorder on a fake clock --------------------------------------------


def test_nesting_parent_ids_and_self_time(rec, clock):
    with rec.span("run") as run:
        clock.tick(5)
        with rec.span("step", gstep=7) as step:
            clock.tick(10)
            with rec.span("batch_wait") as wait:
                clock.tick(30)
            with rec.span("step_call") as call:
                clock.tick(200)
            clock.tick(2)
            with rec.span("step_call"):  # a name may repeat: it sums
                clock.tick(100)
            clock.tick(8)
    assert run.parent is None and step.parent == run.id
    assert wait.parent == step.id and call.parent == step.id
    assert len({run.id, step.id, wait.id, call.id}) == 4
    # gstep is what the spans of one step share: children take the parent's
    assert (run.gstep, step.gstep, wait.gstep, call.gstep) == (None, 7, 7, 7)
    assert wait.thread == step.thread == threading.get_ident()
    assert (step.start_ns, step.end_ns) == (1_005, 1_355)
    assert step.kids_ns == {"batch_wait": 30, "step_call": 300}
    assert step.ms == pytest.approx(350e-6)
    assert step.self_ms == pytest.approx(20e-6)  # 10 + 2 + 8
    assert run.self_ms == pytest.approx(5e-6)
    # children close before their parent: the ring is in closing order
    assert [s.name for s in rec.closed()] == [
        "batch_wait", "step_call", "step_call", "step", "run"]
    assert [s.name for s in rec.closed(within=step)] == [
        "batch_wait", "step_call", "step_call"]
    assert rec.closed("step", before_ns=1_300) == []


def test_where_a_thread_is_can_be_read_across_threads_and_is_sticky(rec):
    asked, go = threading.Event(), threading.Event()
    seen = {}

    def loop():
        with rec.span("step", gstep=0):
            with rec.span("batch_wait"):
                asked.set()
                go.wait(5.0)

    t = threading.Thread(target=loop)
    t.start()
    assert asked.wait(5.0)
    seen["other"] = rec.at(t.ident)  # the watchdog's question
    seen["mine"] = rec.at()
    go.set()
    t.join(5.0)
    assert not t.is_alive()
    assert seen == {"other": "batch_wait", "mine": None}
    # an ``except`` clause asks after the ``with`` blocks have unwound
    with pytest.raises(RuntimeError):
        with rec.span("step", gstep=1):
            with rec.span("save"):
                raise RuntimeError("disk full")
    assert rec.at() == "save"
    # and the preemption dump after its save has closed
    with rec.span("step", gstep=2):
        assert rec.at() == "step"
        with rec.span("save"):
            pass
        assert rec.at() == "save"
    assert rec.at() == "save"


def test_ring_is_bounded_and_the_program_counts_outlive_it(clock):
    rec = spans.Recorder(capacity=8, clock=clock, enabled=True, annotate=False)
    event = "/jax/core/compile/backend_compile_duration"
    for g in range(50):
        with rec.span("step", gstep=g):
            clock.tick(1)
            rec._on_scalar(event, 0.0)
            rec._on_time_span(event, 1.0, 1.0, fun_name="jit(f)")
    assert [s.gstep for s in rec.closed("step")] == list(range(46, 50))
    assert [s.gstep for s in rec.closed("jax/compile_or_load")] == list(
        range(46, 50))
    assert rec.programs("compile_or_load") == {"jit(f)": 50}
    assert rec.programs("trace") == {}
    assert spans.DEFAULT_CAPACITY >= 4_000 * 9


@pytest.mark.parametrize("name, first, word", [
    (None, False, "init"), ("run", False, "init"),
    ("batch_wait", False, "fetch"), ("make_batch", True, "fetch"),
    ("step_call", True, "compile"), ("step_call", False, "step"),
    ("loss_wait", False, "step"), ("guard", False, "loop"),
    ("record", False, "loop"), ("step", True, "loop"), ("save", False, "save"),
])
def test_phase_words_are_the_crash_markers(name, first, word):
    assert spans.phase_word(name, first_step=first) == word
    assert name is None or name in spans.VOCABULARY


def test_no_scopes_records_nothing_and_returns_nullcontext(no_scopes):
    rec = no_scopes
    assert not rec.enabled and not rec._listening
    assert isinstance(rec.span("step", gstep=0), contextlib.nullcontext)
    with rec.span("step", gstep=0) as s:
        assert s is None
        rec.annotate_open(ready=True)
    res = run_supervised(
        _toy_step(), _toy_state(), _ToyDataset(), global_batch=8,
        steps_per_epoch=3, num_workers=1)
    assert res.steps_run == 3
    assert rec.closed() == [] and rec.programs("compile_or_load") == {}
    assert rec.last_run(3) is None
    assert not any(rec.summary().values())
    # all it keeps is where each thread is, for the crash marker
    assert rec.at() == "record"


# --- what the loop and the loader record --------------------------------------


def _tiny_train():
    from benchmarks.common import build_train
    from mpi4dl_tpu.config import config_from_args, get_parser
    from mpi4dl_tpu.data import make_dataset
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh

    cfg = config_from_args(get_parser().parse_args(
        "--model resnet --num-layers 1 --image-size 32 --batch-size 2 "
        "--num-classes 10 --split-size 1 --parts 1 --app 3".split()))
    mesh = build_mesh(MeshSpec(data=1, stage=1), jax.devices()[:1])
    step, state, _, global_batch = build_train(cfg, "lp", mesh)
    return step, state, make_dataset(cfg), global_batch


def test_every_step_of_a_tiny_model_is_covered_by_named_spans(
        process_recorder, tmp_path):
    from mpi4dl_tpu.obs import RunLog, read_runlog

    rec = process_recorder
    step, state, dataset, global_batch = _tiny_train()
    lines = []
    runlog = RunLog(str(tmp_path / "run.jsonl"))
    flight = FlightRecorder(capacity=8, path=str(tmp_path / "flight.json"))
    res = run_supervised(
        step, state, dataset, global_batch=global_batch, steps_per_epoch=5,
        num_workers=1, print_fn=lines.append, runlog=runlog, flight=flight)
    runlog.close()
    assert res.steps_run == 5

    (run,) = rec.closed("run")
    assert run.attrs == {"steps": 5, "profile": False,
                         "global_batch": global_batch}
    steps = rec.closed("step", within=run)
    assert [s.gstep for s in steps] == [0, 1, 2, 3, 4]
    assert all(s.parent == run.id for s in steps)
    for s in steps:
        kids = [k for k in rec.closed(within=s) if k.parent == s.id]
        assert [k.name for k in kids] == [
            "batch_wait", "step_call", "loss_wait", "guard", "record"]
        assert all(k.gstep == s.gstep for k in kids)
        named = sum(s.kids_ms[n] for n in
                    ("batch_wait", "step_call", "loss_wait", "record"))
        assert named >= 0.95 * s.ms, (s.gstep, s.kids_ms, s.ms)
        assert isinstance(kids[0].attrs["ready"], bool)
    # the loader's thread made every batch, under the same gsteps
    made = rec.closed("make_batch", within=run)
    assert sorted(m.gstep for m in made) == [0, 1, 2, 3, 4]
    assert {m.thread for m in made}.isdisjoint({run.thread})
    assert all(m.parent is None and m.attrs == {} for m in made)

    # set-up: build_train and its four parts, jax's events inside them
    (build,) = rec.closed("setup/build_train")
    assert build.end_ns <= run.start_ns
    parts = [s for s in rec.closed(within=build) if s.parent == build.id]
    assert [p.name for p in parts if p.name.startswith("setup/")] == [
        "setup/build_model", "setup/init_params", "setup/make_step",
        "setup/place_state"]
    # the step program was traced, lowered and built once, in step 0
    for kind, program in (("jax/trace", "step"), ("jax/lower", "jit(step)"),
                          ("jax/compile_or_load", "jit(step)")):
        (ev,) = [s for s in rec.closed(kind) if s.attrs["program"] == program]
        assert ev.gstep == 0 and run.start_ns <= ev.start_ns < ev.end_ns
        assert ev.parent == rec.closed("step_call", within=steps[0])[0].id
    assert rec.programs("compile_or_load")["jit(step)"] == 1
    assert rec.last_run(5) is run and rec.last_run(4) is None

    # the step line reads what it read, and the records carry spans_ms
    assert [l.split()[:4] for l in lines] == [
        ["epoch", "0", "step", str(i)] for i in range(5)]
    ms = float(lines[2].split()[5])
    assert ms == pytest.approx(
        steps[2].kids_ms["step_call"] + steps[2].kids_ms["loss_wait"],
        abs=0.15)
    records = read_runlog(runlog.path)
    step_records = [r for r in records if r["kind"] == "step"]
    assert all(set(r["spans_ms"]) == {"batch_wait", "step_call", "loss_wait",
                                      "guard"} for r in step_records)
    assert step_records[2]["spans_ms"]["step_call"] == pytest.approx(
        steps[2].kids_ms["step_call"], abs=1e-3)
    (summary,) = [r for r in records if r["kind"] == "spans"]
    assert set(summary) == {"kind", "schema", "t", "setup_ms", "jax",
                            "built_in_loop", "conv_paths", "norm_paths"}
    # every convolution of the tiny model, by the path its dispatch chose
    # (32² is far under the narrow-channel gate: nothing folds or stripes;
    # the 1×1 that closes a block at stride 1 is a matrix product)
    assert summary["conv_paths"] == rec.conv_paths()
    assert set(summary["conv_paths"]) == {"phase", "xla", "dot"}
    assert set(summary["norm_paths"]) == {"plain"}
    assert summary["setup_ms"]["setup/build_train"] > 0
    assert summary["jax"]["jax/trace"]["top"][0]["program"] == "step"
    assert [(b["program"], b["gstep"]) for b in summary["built_in_loop"]
            if b["program"] == "jit(step)"] == [("jit(step)", 0)]
    # what an operator reads: the spans beside the step time
    from mpi4dl_tpu.obs.report import render_run

    report = render_run(runlog.path)
    assert "step spans ms (median): batch_wait " in report
    assert "set-up spans s: build_train " in report
    assert "jax/trace " in report and ": step " in report
    assert "programs built in the loop: jit(step)@0 " in report
    assert "conv paths (sites): phase " in report
    ring = flight.snapshot("probe")
    assert ring["spans"]["setup_ms"] == summary["setup_ms"]
    assert [e["spans_ms"]["loss_wait"] > 0 for e in ring["ring"]
            if e["kind"] == "step"] == [True] * 5


def test_inline_batches_nest_under_the_wait(process_recorder):
    rec = process_recorder
    run_supervised(_toy_step(), _toy_state(), _ToyDataset(), global_batch=8,
                   steps_per_epoch=3, num_workers=0)
    waits = rec.closed("batch_wait")
    made = rec.closed("make_batch")
    assert [m.parent for m in made] == [w.id for w in waits]
    assert [w.attrs["ready"] for w in waits] == [False] * 3


class _BoomDataset:
    def batch(self, idx, batch_size):
        raise RuntimeError("dataset exploded")


def _crash(tmp_path, monkeypatch, where, **kw):
    """One leg that dies in ``where``; returns (marker, flight.json)."""
    from mpi4dl_tpu.checkpoint import CheckpointManager
    from mpi4dl_tpu.resilience import FaultInjector
    from mpi4dl_tpu.resilience.faults import parse_fault

    marker = str(tmp_path / "crash_marker.json")
    monkeypatch.setenv("MPI4DL_CRASH_MARKER", marker)
    dataset, faults, ckpt, guard = _ToyDataset(), None, None, None
    if where == "fetch":
        dataset = _BoomDataset()
    elif where in ("compile", "step"):
        faults = FaultInjector(parse_fault(
            "oom_compile@0" if where == "compile" else "oom_step@2"))
    else:  # the save at the epoch's end
        class _FullDisk(CheckpointManager):
            def save(self, state, step_id):
                raise OSError("No space left on device")

        ckpt = _FullDisk(str(tmp_path / "ck"))
    with pytest.raises(Exception):
        run_supervised(
            _toy_step(), _toy_state(), dataset, global_batch=8,
            steps_per_epoch=4, faults=faults, ckpt=ckpt, guard=guard,
            async_writes=False,
            flight=FlightRecorder(capacity=4,
                                  path=str(tmp_path / "flight.json")), **kw)
    return read_crash_marker(marker), read_flight(str(tmp_path / "flight.json"))


CRASHES = [("fetch", 0, 0), ("fetch", 1, 0), ("compile", 0, 0), ("step", 1, 2),
           ("save", 0, 4)]


def _same_phase_words(marker, flight, where, gstep):
    assert marker["phase"] == where and marker["gstep"] == gstep
    assert flight["reason"] == "crash" and flight["phase"] == where
    assert flight["last_events"]["crash"]["phase"] == where
    if where in ("compile", "step"):
        assert classify_failure(1, marker).failure_class == "oom_" + where


@pytest.mark.parametrize("where, workers, gstep", CRASHES)
def test_crash_marker_and_flight_keep_their_phase_words(
        process_recorder, tmp_path, monkeypatch, where, workers, gstep):
    marker, flight = _crash(tmp_path, monkeypatch, where, num_workers=workers)
    _same_phase_words(marker, flight, where, gstep)


@pytest.mark.parametrize("where, workers, gstep", CRASHES)
def test_phase_words_do_not_hang_on_the_observability_switch(
        no_scopes, tmp_path, monkeypatch, where, workers, gstep):
    """A resilience decision (``oom_compile`` against ``oom_step``, a hang's
    site) is the same with the recorder off."""
    marker, flight = _crash(tmp_path, monkeypatch, where, num_workers=workers)
    _same_phase_words(marker, flight, where, gstep)
    assert no_scopes.closed() == []


@pytest.mark.parametrize("recorder_on", [True, False])
@pytest.mark.parametrize("with_ckpt, phase", [(False, "fetch"), (True, "save")])
def test_preemption_dump_reads_the_phase_it_read(
        request, tmp_path, monkeypatch, recorder_on, with_ckpt, phase):
    """A signal that lands during the fetch: the dump is written after the
    ``batch_wait`` span (and the save's) has closed, and still says where
    the loop last was, as the ``phase`` word did."""
    import signal

    from mpi4dl_tpu.checkpoint import CheckpointManager

    request.getfixturevalue("process_recorder" if recorder_on else "no_scopes")

    class _Signalling(_ToyDataset):
        def batch(self, idx, batch_size):
            if idx == 2:
                os.kill(os.getpid(), signal.SIGTERM)
            return super().batch(idx, batch_size)

    res = run_supervised(
        _toy_step(), _toy_state(), _Signalling(), global_batch=8,
        steps_per_epoch=6, num_workers=0,
        ckpt=CheckpointManager(str(tmp_path / "ck")) if with_ckpt else None,
        async_writes=False,
        flight=FlightRecorder(capacity=4, path=str(tmp_path / "flight.json")))
    assert res.preempted and res.steps_run == 2
    flight = read_flight(str(tmp_path / "flight.json"))
    assert flight["reason"] == "preemption" and flight["phase"] == phase


def test_a_function_jitted_for_two_shapes_is_found_with_its_gstep(
        process_recorder):
    """The retrace finder: the program's name and the step each build fell
    in (PR 22's four-chip smoke paid 231 s for one at step 2)."""
    rec = process_recorder

    @jax.jit
    def retrace_probe(x):
        return jnp.tanh(x).sum()

    with rec.span("run"):
        for g, n in ((0, 3), (1, 3), (2, 5)):
            with rec.span("step", gstep=g):
                with rec.span("step_call"):
                    retrace_probe(jnp.ones((n,), jnp.float32))
    built = [s for s in rec.closed("jax/compile_or_load")
             if s.attrs["program"] == "jit(retrace_probe)"]
    assert [s.gstep for s in built] == [0, 2]
    assert all(s.attrs["cache_hit"] is False for s in built)
    calls = rec.closed("step_call")
    assert [s.parent for s in built] == [calls[0].id, calls[2].id]
    # placed on the recorder's clock by its duration, ending at delivery
    assert all(calls[i].start_ns <= s.start_ns < s.end_ns <= calls[i].end_ns
               for i, s in zip((0, 2), built))
    assert rec.programs("compile_or_load")["jit(retrace_probe)"] == 2
    assert rec.programs("trace")["retrace_probe"] == 2
    # events inside another of jax's events (jnp functions traced inside the
    # jitted one) do not become spans
    assert not [s for s in rec.closed("jax/trace")
                if s.attrs["program"] == "tanh"]


# --- the benchmark's readers, on a recorder filled by hand --------------------


NEW_METRICS = {
    "batch_make_ms": 40e-6, "batch_wait_ms": 3e-6, "batch_ready_pct": 100 * 2 / 3,
    "build_s": 9e-9, "step_call_ms": 6e-6, "loss_wait_ms": 100e-6,
    "loop_self_ms": 11e-6, "trace_lower_s": 3e-9, "step_program_builds": 2.0,
}


@pytest.fixture
def filled(clock, monkeypatch):
    """Set-up, a warm run of 2 steps, a window of 4 (the second program
    build in its step 1), then a traced run of 2: as the harness makes them."""
    rec = spans.Recorder(clock=clock, enabled=True, annotate=False)
    monkeypatch.setattr(spans, "_RECORDER", rec)
    monkeypatch.setattr(rec, "listen_to_jax", lambda: None)
    span_event = "/jax/core/compile/{}_duration".format

    def jax_event(kind, program, ns):
        rec._on_scalar(span_event(kind), 0.0, fun_name=program)
        clock.tick(ns)
        rec._on_time_span(span_event(kind), 10.0, 10.0 + ns / 1e9,
                          fun_name=program)

    def run(steps, profile, retrace_at=None):
        with rec.span("run", steps=steps, profile=profile) as r:
            made = threading.Thread(target=lambda: [
                make_batch(g) for g in range(steps)])
            made.start()
            made.join(5.0)
            for g in range(steps):
                with rec.span("step", gstep=g):
                    clock.tick(1)
                    with rec.span("batch_wait", ready=g not in (0, 2)):
                        clock.tick(3)
                    with rec.span("step_call"):
                        clock.tick(6)
                        if g == retrace_at:
                            jax_event("backend_compile", "jit(step)", 50)
                    with rec.span("loss_wait"):
                        clock.tick(100)
                    with rec.span("record"):
                        clock.tick(10)
            r.set(steps=steps)

    def make_batch(g):
        with rec.span("make_batch", gstep=g):
            clock.tick(40)

    with rec.span("setup/build_train"):
        clock.tick(7)
        jax_event("jaxpr_trace", "init", 1)
        jax_event("jaxpr_to_mlir_module", "jit(init)", 1)
    jax_event("jaxpr_trace", "compare", 20)  # the harness's own program
    with rec.span("run", steps=1, profile=False):
        with rec.span("step", gstep=0):
            with rec.span("step_call"):
                jax_event("jaxpr_trace", "step", 1)
                jax_event("jaxpr_to_mlir_module", "jit(step)", 2)
                jax_event("backend_compile", "jit(step)", 5)
    run(4, False, retrace_at=1)
    run(2, True)
    yield rec


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_readers_on_a_recorder_filled_by_hand(filled, name):
    from perfbench.catalog import Catalog

    cat = Catalog()
    record = {"spans": {"dispatch": [0.0] * 4}}
    assert cat.read_layer_metric(name, record) == pytest.approx(
        NEW_METRICS[name])
    # a window whose step count is not the harness's reads nothing
    assert cat.read_layer_metric(name, {"spans": {"dispatch": [0.0] * 5}}) is None
    assert cat.read_layer_metric(name, {"spans": {}}) is None


def test_readers_read_nothing_from_a_program_without_spans(
        filled, monkeypatch):
    from perfbench.catalog import Catalog

    cat = Catalog()
    record = {"spans": {"dispatch": [0.0] * 4}}
    filled._closed.clear()  # a recorder that kept nothing (the hatch)
    assert all(cat.read_layer_metric(n, record) is None for n in NEW_METRICS)
    monkeypatch.setitem(sys.modules, "mpi4dl_tpu.obs.spans", None)  # the parent
    assert all(cat.read_layer_metric(n, record) is None for n in NEW_METRICS)


def test_new_metrics_are_entries_and_files_added_at_the_end():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:7] == ["fetch_ms", "dispatch_ms", "compiles_in_window",
                         "compile_s", "device_step_ms", "mfu_pct",
                         "device_idle_pct"]
    # PR 26's nine, then what later PRs added behind them
    assert set(names[7:16]) == set(NEW_METRICS) and len(names) >= 16
    for m in bench["per_layer"][7:16]:
        spec = json.load(open(os.path.join(
            root, "perfbench", "layer_metrics", m["name"] + ".json")))
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
        assert spec["reader"] in ("program_span", "program_count")
        assert m["source"] == {"program_span": "program_span",
                               "program_count": "program_counter"}[spec["reader"]]
