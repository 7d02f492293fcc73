"""The supervised training loop — every benchmark family runs under it.

This re-homes the epoch loop from ``benchmarks/common.py`` into the library
and wraps it with the resilience layer (ISSUE 3): anomaly guard with
checkpoint rollback, preemption-safe shutdown, background checkpoint
writes, deterministic fault injection, and the step watchdog.  The loop is
engine-agnostic — lp / sp / gems / gems_sp all present the same
``step(state, x, y) -> (state, metrics)`` contract, so one supervisor
covers all four.

Step addressing is GLOBAL: ``gstep`` counts optimizer steps across epochs,
the dataset index is ``gstep % steps_per_epoch`` (each epoch replays the
same deterministic batch indices, matching the pre-existing benchmark
semantics), and checkpoints are numbered by completed-step count — so a
resume at ``step_id`` continues the exact batch sequence instead of
restarting at 0 (the PR-3 satellite fix: ``restore_latest`` now returns the
step id it discarded before).

Event records written to the RunLog (see docs/resilience.md):

- ``anomaly``  — guard tripped (non-finite loss / grad-norm breach)
- ``recovery`` — state rolled back; the poison batch is skipped
- ``preempt``  — SIGTERM/SIGINT honored: in-flight step finished, state
  saved, loop exited cleanly
- ``checkpoint`` — one completed save: gather/write ms, bytes, shard
  count, peak pending host bytes (ISSUE 13: checkpoint stalls become
  observable instead of mystery gaps in the step stream)
- ``quarantine`` — a step skipped by the supervisor's poison-batch
  exclusion (``MPI4DL_QUARANTINE_STEPS``, ISSUE 15)

Supervision plumbing (ISSUE 15): when the ``MPI4DL_CRASH_MARKER`` hatch
points at a file, any exception escaping the loop first writes a structured
crash marker — the phase it died in (``compile`` covers the process's first
step, the one that pays the XLA compile), the global step, and the error —
so the supervisor can classify the failure without parsing tracebacks.  The
watchdog gains the compile-grace budget for the first step and, under
``MPI4DL_WATCHDOG_ESCALATE``, escalates a persistent straggler into a typed
``hang`` exit instead of dumping forever.
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
from typing import Any, Callable, Dict, Optional

from mpi4dl_tpu.checkpoint import CheckpointManager, arrays_to_state, state_to_arrays
from mpi4dl_tpu.data import prefetch_batches
from mpi4dl_tpu.resilience.faults import CKPT_FAULT_KINDS, FaultInjector
from mpi4dl_tpu.resilience.guard import AnomalyError, AnomalyGuard
from mpi4dl_tpu.resilience.preempt import PreemptionHandler
from mpi4dl_tpu.resilience.supervisor import (
    crash_marker_path,
    quarantine_steps_from_env,
    write_crash_marker,
)
from mpi4dl_tpu.resilience.watchdog import (
    HANG_EXIT_CODE,
    StepWatchdog,
    watchdog_compile_budget_from_env,
    watchdog_escalation_from_env,
)
from mpi4dl_tpu.resilience.writer import AsyncCheckpointWriter


@dataclasses.dataclass
class LoopResult:
    state: Any
    metrics: Dict[str, float]  # last completed step's {loss, accuracy}
    steps_run: int  # steps completed by THIS process
    final_step: int  # global step count after the loop (resume point)
    preempted: bool
    anomalies: int


def run_supervised(
    step_fn: Callable,
    state: Any,
    dataset: Any,
    *,
    global_batch: int,
    steps_per_epoch: int,
    num_epochs: int = 1,
    num_workers: int = 0,
    start_step: int = 0,
    ckpt: Optional[CheckpointManager] = None,
    async_writes: bool = True,
    runlog=None,
    meter=None,
    print_fn: Optional[Callable[[str], None]] = None,
    profile: bool = False,
    guard: Optional[AnomalyGuard] = None,
    faults: Optional[FaultInjector] = None,
    watchdog_secs: float = 0.0,
    watchdog_compile_secs: Optional[float] = None,
    handle_signals: bool = True,
    retries: int = 2,
    retry_backoff: float = 0.05,
    snapshot_rollback: bool = False,
    flight=None,
) -> LoopResult:
    """Run ``steps_per_epoch * num_epochs`` supervised steps from
    ``start_step``; returns the final state plus what happened.

    Checkpoint cadence: a guard baseline before the first step when the
    directory is empty, every epoch boundary, and on preemption — all
    through the background writer (``async_writes=False`` forces the
    synchronous path).  Without ``ckpt``, the guard is DETECTION-ONLY: an
    anomaly raises :class:`AnomalyError` after logging (fail fast beats
    both silent NaN training and an implicit full-state host copy) —
    unless ``snapshot_rollback=True``, which opts into an in-memory host
    snapshot refreshed at the checkpoint cadence (costs a full extra copy
    of the training state in host RAM; fine for tests/small models, not
    for pathology-scale stage buffers).

    The call is one ``run`` span of the process's span recorder
    (obs/spans.py), every step a ``step`` span with ``batch_wait``,
    ``step_call``, ``loss_wait``, ``guard``, ``record`` and ``save`` inside
    it; each is also a profiler annotation (``step`` a
    ``StepTraceAnnotation``), so a trace taken round the call shows them
    whether or not ``profile`` says so: ``profile`` is only recorded on the
    ``run`` span, for whoever reads the recorder to tell a traced run from a
    measured one.  The step line's ``time_ms`` is the step call plus the
    loss fetch, as ever.
    """
    emit = print_fn if print_fn is not None else (lambda line: None)
    faults = faults if faults is not None else FaultInjector(None)
    total = steps_per_epoch * num_epochs
    gstep = start_step
    metrics_out: Dict[str, float] = {}
    anomalies = 0
    preempted = False
    steps_run = 0
    # Supervisor plumbing (ISSUE 15): where to leave structured last words
    # and which steps are quarantined.
    marker_path = crash_marker_path()
    quarantine = quarantine_steps_from_env()

    # The span recorder knows where the loop is: the crash marker's and the
    # flight dump's phase word is that of the span this thread opened last
    # ("compile" is the process's first step).  The watchdog asks from its
    # own thread.
    from mpi4dl_tpu.obs.spans import phase_word, recorder

    rec = recorder()
    loop_thread = threading.get_ident()

    def _phase() -> str:
        return phase_word(rec.at(loop_thread), first_step=steps_run == 0)

    # Flight recorder (ISSUE 17): the always-on in-memory forensic ring
    # every leg runs by default.  Dumps land next to the crash marker when
    # the supervisor set one (its per-attempt directory), else next to the
    # RunLog; no destination = ring only (the watchdog still reads its tail).
    from mpi4dl_tpu.obs.flight import (
        FLIGHT_BASENAME,
        FlightRecorder,
        default_flight_path,
    )

    if flight is None:
        fpath = default_flight_path()
        if fpath is None and runlog is not None and getattr(runlog, "path", None):
            fpath = os.path.join(
                os.path.dirname(os.path.abspath(runlog.path)), FLIGHT_BASENAME)
        flight = FlightRecorder.from_env(path=fpath)

    def _ckpt_record(stats) -> None:
        """Emit the ``checkpoint`` RunLog record (worker thread for async
        saves, training thread for sync ones — RunLog.write is locked)."""
        if runlog is not None and stats is not None:
            runlog.write("checkpoint", **stats.record())
        if flight is not None and stats is not None:
            flight.note("checkpoint", **stats.record())

    writer = (
        AsyncCheckpointWriter(ckpt, on_saved=_ckpt_record)
        if (ckpt is not None and async_writes) else None
    )

    def _save(st: Any, step_id: int) -> Optional[str]:
        if ckpt is None:
            return None
        with rec.span("save"):
            if writer:
                path = writer.save(st, step_id)
            else:
                path = ckpt.save(st, step_id)
                _ckpt_record(ckpt.last_save_stats)
            if (faults.spec is not None
                    and faults.spec.kind in CKPT_FAULT_KINDS):
                if writer is not None:
                    writer.flush()  # the fault corrupts a file, not a queue entry
                faults.after_save(step_id, path)
        return path

    # Rollback target: newest on-disk checkpoint, else (opt-in) an
    # in-memory host snapshot (host copies are mandatory either way —
    # donation invalidates the device buffers the moment the next step
    # runs).  No ckpt and no opt-in = detection-only guard.
    snapshot = None
    if guard is not None:
        if ckpt is not None:
            if ckpt.latest_path() is None:
                _save(state, gstep)
        elif snapshot_rollback:
            snapshot = (state_to_arrays(state, gstep), gstep)

    def _boundary_save(st: Any, step_id: int) -> None:
        """Epoch-boundary persistence — one policy for the normal path and
        the rollback-jumped-the-boundary path (incl. step_id == total: the
        final state must persist or a resume replays the tail forever)."""
        nonlocal snapshot
        if ckpt is not None:
            _save(st, step_id)
        elif snapshot is not None:
            snapshot = (state_to_arrays(st, step_id), step_id)

    def _wd_context():
        """Stall-dump context: the last record of any kind PLUS the last
        ``checkpoint`` record, so a stall inside the shard-gather is
        distinguishable from a data stall — and the flight-recorder tail,
        the trajectory leading into the stall."""
        if runlog is None and flight is None:
            return None
        ctx = {
            "last": getattr(runlog, "last_record", None)
            if runlog is not None else None,
            "last_checkpoint": (getattr(runlog, "last_by_kind", {}).get(
                "checkpoint") if runlog is not None else None),
        }
        if flight is not None:
            ctx["flight_tail"] = flight.tail(5)
        return ctx

    def _escalate(label: str) -> None:
        """Watchdog escalation: the straggler never finished — leave a
        typed ``hang`` marker and exit the leg so the supervisor can
        classify and relaunch.  ``os._exit`` is deliberate: the training
        thread is wedged inside the very call we are escalating out of."""
        if flight is not None:
            # `phase` says WHERE the leg is wedged (fetch = data stall,
            # step = collective, save = checkpoint gather) — the evidence
            # the supervisor uses to split the hang classes.
            flight.dump("watchdog_escalation", phase=_phase(), gstep=gstep)
        if marker_path:
            write_crash_marker(
                marker_path, phase="step", gstep=gstep,
                steps_run=steps_run, failure_class="hang", label=label,
            )
        os._exit(HANG_EXIT_CODE)

    escalate_n = watchdog_escalation_from_env()
    watchdog = StepWatchdog(
        watchdog_secs,
        get_context=_wd_context,
        compile_budget_secs=watchdog_compile_budget_from_env(
            watchdog_compile_secs, watchdog_secs
        ),
        escalate_after=escalate_n,
        on_escalate=_escalate if escalate_n > 0 else None,
    )
    _on_signal = (
        (lambda signum: flight.note("preempt_signal", signum=signum,
                                    gstep=gstep))
        if flight is not None else None
    )
    preempt = (
        PreemptionHandler(on_signal=_on_signal) if handle_signals
        else PreemptionHandler((), on_signal=_on_signal)
    )

    def _preempt_exit(st: Any, step_id: int) -> None:
        saved = _save(st, step_id) is not None
        if writer is not None:
            writer.flush()  # "saved" must mean durable before exiting
        if runlog is not None:
            runlog.write("preempt", gstep=step_id, signum=preempt.signum,
                         saved=saved)
        if flight is not None:
            flight.note("preempt", gstep=step_id, signum=preempt.signum,
                        saved=saved)
            flight.dump("preemption", phase=_phase(), gstep=step_id)
        emit(
            f"preemption signal {preempt.signum} — "
            + (f"checkpoint saved at step {step_id}"
               if saved else
               f"NO checkpoint dir configured, step-{step_id} progress is "
               "not resumable")
            + "; exiting cleanly"
        )

    try:
        with rec.span("run", steps=total - start_step, profile=bool(profile),
                      global_batch=global_batch) as run_span, preempt, watchdog:
            while gstep < total and not preempted:
                # One contiguous segment of the batch stream; a rollback
                # closes it and reopens past the poison batch.
                segment = prefetch_batches(
                    dataset, global_batch, gstep, total,
                    index_of=lambda g: g % steps_per_epoch,
                    num_workers=num_workers, retries=retries,
                    backoff=retry_backoff, stall_hook=faults.stall_seconds,
                )
                rollback_to = None
                try:
                    while gstep < total:
                        with rec.span("step", gstep=gstep) as step_span:
                            # Arm BEFORE the fetch: a stalled producer is
                            # exactly the hang the watchdog exists for.  The
                            # process's first step pays the XLA compile, so it
                            # gets the compile-grace budget instead of the step
                            # budget (ISSUE 15 satellite).
                            watchdog.arm(f"step {gstep}",
                                         compile=steps_run == 0)
                            with rec.span("batch_wait"):
                                item = next(segment, None)
                            if item is None:  # the stream ended early
                                watchdog.disarm()
                                break
                            g, (x, y) = item
                            # A signal that landed during the fetch must not pay
                            # for a whole extra step before being honored — the
                            # grace window may not cover it.  `gstep` steps are
                            # complete; the just-fetched batch is simply dropped.
                            if preempt.requested:
                                watchdog.disarm()
                                _preempt_exit(state, gstep)
                                preempted = True
                                break
                            epoch, i = divmod(g, steps_per_epoch)
                            if g in quarantine:
                                # Supervisor poison-batch exclusion: a step the
                                # anomaly guard already fail-fasted on is
                                # skipped outright — same advance-past
                                # semantics as a rollback skip.
                                watchdog.disarm()
                                emit(f"step {g} quarantined "
                                     "(MPI4DL_QUARANTINE_STEPS); skipping")
                                if runlog is not None:
                                    runlog.write("quarantine", gstep=g,
                                                 epoch=epoch, step=i)
                                if flight is not None:
                                    flight.note("quarantine", gstep=g,
                                                epoch=epoch, step=i)
                                gstep = g + 1
                                if gstep % steps_per_epoch == 0:
                                    _boundary_save(state, gstep)
                                continue
                            # The injected faults stand in for the step's
                            # own (an OOM in its compile, a wedged
                            # collective), so they fall inside its span.
                            with rec.span("step_call"):
                                faults.before_step(g)
                                x = faults.poison_batch(g, x)
                                t_call = rec.clock()
                                out = step_fn(state, x, y)
                            with rec.span("loss_wait"):
                                # Rebinding `state` releases the donated
                                # one's arrays (a millisecond or two at a
                                # thousand leaves) while the device works:
                                # part of the wait, not of the call.
                                state, metrics = out
                                loss = float(metrics["loss"])  # blocks on device
                                # What the step counted besides (a routed
                                # model's expert rows and load), under the
                                # one key ``counted``: on the host with the
                                # loss, no second wait; attributes of the
                                # step span.
                                counted = metrics.get("counted")
                                if counted and step_span is not None:
                                    step_span.set(**{k: float(v) for k, v
                                                     in counted.items()})
                            ms = (rec.clock() - t_call) / 1e6
                            watchdog.disarm()
                            loss = faults.poison_loss(g, loss)

                            with rec.span("guard"):
                                reason = (
                                    guard.check(loss, metrics)
                                    if guard is not None else None
                                )
                            if reason is not None:
                                anomalies += 1
                                if runlog is not None:
                                    runlog.write(
                                        "anomaly", gstep=g, epoch=epoch, step=i,
                                        loss=loss, reason=reason,
                                    )
                                if flight is not None:
                                    flight.note("anomaly", gstep=g, epoch=epoch,
                                                step=i, loss=loss, reason=reason,
                                                guard=guard.snapshot()
                                                if guard is not None else None)
                                    flight.dump("anomaly", phase="step", gstep=g)
                                emit(f"anomaly at step {g}: {reason}")
                                if ckpt is None and snapshot is None:
                                    # detection-only: no rollback target exists
                                    # (and silently continuing would train on a
                                    # possibly-poisoned state)
                                    raise AnomalyError(
                                        f"anomaly at step {g} ({reason}) with no "
                                        "rollback target — pass a checkpoint "
                                        "directory (or snapshot_rollback=True) "
                                        "to recover instead of failing fast"
                                    )
                                guard.note_rollback()  # raises when exhausted
                                if ckpt is not None:
                                    if writer is not None:
                                        writer.flush()
                                    # require=True: with every on-disk file
                                    # invalid, handing back the live (possibly
                                    # NaN-poisoned) template as a "recovery"
                                    # would keep training on corrupt weights —
                                    # fail loudly instead.
                                    state, good = ckpt.restore_latest(
                                        state, require=True
                                    )
                                else:
                                    arrays, good = snapshot
                                    state = arrays_to_state(arrays, state)
                                if runlog is not None:
                                    runlog.write(
                                        "recovery", resumed_from=good,
                                        skipped_step=g, next_step=g + 1,
                                    )
                                emit(
                                    f"rolled back to step {good}; skipping "
                                    f"poison batch {g}"
                                )
                                rollback_to = g + 1
                                break

                            measured = (meter.add(ms) if meter is not None
                                        else True)
                            acc = float(metrics.get("accuracy", math.nan))
                            metrics_out = {"loss": loss, "accuracy": acc}
                            with rec.span("record"):
                                # the step's spans that have closed by now
                                spans_ms = (
                                    {k: round(v, 3)
                                     for k, v in step_span.kids_ms.items()}
                                    if step_span is not None else None
                                )
                                ips = global_batch / (ms / 1e3)
                                emit(
                                    f"epoch {epoch} step {i} time_ms {ms:.1f} "
                                    f"images_per_sec {ips:.3f} "
                                    f"loss {loss:.4f} acc {acc:.4f}"
                                )
                                if runlog is not None:
                                    runlog.write_step(
                                        epoch=epoch, step=i, ms=ms,
                                        images_per_sec=ips, loss=loss,
                                        accuracy=acc, step_fn=step_fn,
                                        measured=measured, gstep=g,
                                        spans_ms=spans_ms,
                                    )
                                if flight is not None:
                                    flight.note_step(
                                        gstep=g, phase=_phase(),
                                        step_fn=step_fn, epoch=epoch, step=i,
                                        ms=round(ms, 3), loss=loss,
                                        spans_ms=spans_ms,
                                    )
                            gstep = g + 1
                            steps_run += 1

                            if preempt.requested:
                                _preempt_exit(state, gstep)
                                preempted = True
                                break
                            if gstep % steps_per_epoch == 0:
                                _boundary_save(state, gstep)
                finally:
                    segment.close()
                if rollback_to is not None:
                    gstep = rollback_to
                    # A skipped poison batch can jump PAST an epoch boundary
                    # (or land on the very last step): the boundary save
                    # must still happen, or the rollback target silently
                    # ages — and a final-step rollback would leave nothing
                    # newer than the baseline, so every resume re-trains the
                    # whole run just to re-skip the same poison batch.
                    if gstep % steps_per_epoch == 0:
                        _boundary_save(state, gstep)
            if run_span is not None:
                run_span.set(steps=steps_run)  # planned until here
    except BaseException as e:
        # The leg's structured last words (ISSUE 15): phase + step + error,
        # written BEFORE the exception propagates so the supervisor can
        # classify this death even if the interpreter never unwinds
        # further.  write_crash_marker itself never raises.
        died_in = _phase()
        if flight is not None:
            flight.note("crash", error_type=type(e).__name__,
                        error=str(e)[:500], phase=died_in, gstep=gstep)
            flight.dump("crash", phase=died_in, gstep=gstep)
        if marker_path:
            extra = {}
            spec = getattr(e, "spec", None)
            if isinstance(spec, str) and spec:
                extra["shrunk_spec"] = spec  # MeshShrunk carries it
            write_crash_marker(
                marker_path, phase=died_in, gstep=gstep,
                steps_run=steps_run, error=e, **extra,
            )
        raise
    finally:
        if writer is not None:
            writer.close()

    return LoopResult(
        state=state, metrics=metrics_out, steps_run=steps_run,
        final_step=gstep, preempted=preempted, anomalies=anomalies,
    )
