"""Elastic supervisor: typed failure classification + per-class recovery (ISSUE 15
tentpole).

PR 13 made checkpoints elastic across geometries and PR 12 made geometry
*choosable* analytically; this module is the control plane that USES both
when something goes wrong.  The trainer becomes a restartable *leg* under a
process-level supervisor: the leg runs as a subprocess (fresh XLA backend
per attempt — also the only sound way to retry a compile-OOM), and every
leg exit is classified into a **typed failure classification** from three
evidence sources — a structured crash-marker file the leg writes on the way
down (:func:`write_crash_marker`, wired through
:func:`mpi4dl_tpu.resilience.loop.run_supervised`), the leg's RunLog tail,
and the exit status — then answered with a per-class **recovery policy**:

=================  =========================================================
``oom_compile``    ``RESOURCE_EXHAUSTED`` during the leg's FIRST step (the
                   phase that pays the XLA compile) → **degrade**: the
                   planner re-plans a feasible geometry and the relaunched
                   leg elastic-restores onto it
``oom_step``       ``RESOURCE_EXHAUSTED`` on a later step (allocator OOM
                   mid-run) → **degrade**
``mesh_shrunk``    the device set shrank (:class:`~mpi4dl_tpu.resilience.
                   faults.MeshShrunk`) → **degrade** within the surviving
                   device budget
``nan_cluster``    the anomaly guard fail-fasted (``AnomalyError``:
                   clustered NaNs past the rollback budget) →
                   **quarantine**: the anomalous batch steps are excluded
                   from the relaunched leg (``MPI4DL_QUARANTINE_STEPS``)
``hang``           watchdog escalation (``MPI4DL_WATCHDOG_ESCALATE`` dumps
                   exhausted) or SIGKILL → bounded **retry** with backoff
``preempted``      clean exit with a ``preempt`` record → immediate
                   **resume** relaunch (no backoff — the checkpoint is
                   durable and the grace window already paid the wait)
``lost_shard``     restore rejected a checkpoint for vanished shard files →
                   bounded **retry** (the restore walk falls back on its
                   own; the retry re-runs from the older checkpoint)
``transient_io``   ``OSError`` family / background checkpoint-write failure
                   → bounded **retry** with exponential backoff + jitter
``unknown``        anything else → one **retry**, then fail loudly
=================  =========================================================

Every decision emits a ``supervisor`` RunLog incident record (class,
evidence, policy, attempt, config delta) so ``obs report`` renders an
incident timeline, and the drill matrix
(:func:`mpi4dl_tpu.resilience.drill.supervisor_scenarios`) verifies the
whole loop — classification, feasibility-probed degrade, elastic resume —
against control runs with typed verdicts.

Knobs (``config.HATCHES``): ``MPI4DL_SUPERVISE_MAX_ATTEMPTS`` (total leg
relaunches, default 6), ``MPI4DL_SUPERVISE_BACKOFF`` (base seconds, default
1.0), ``MPI4DL_SUPERVISE_BACKOFF_CAP`` (default 30).  CLI::

    python -m mpi4dl_tpu.resilience supervise --family sp --out sup_out \
        -- --image-size 32 --num-layers 1 --batch-size 4 --checkpoint-dir ck
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

from mpi4dl_tpu.resilience.watchdog import HANG_EXIT_CODE

FAILURE_CLASSES = (
    "oom_compile", "oom_step", "nan_cluster", "hang", "preempted",
    "lost_shard", "mesh_shrunk", "transient_io", "unknown",
)

MARKER_SCHEMA = 1

# Substrings that identify a device/compiler OOM in an error repr or a
# stderr tail.  RESOURCE_EXHAUSTED is the XLA status code (it survives into
# XlaRuntimeError reprs and the synthetic fault); the prose forms cover
# allocator messages that drop the code.
_OOM_PATTERNS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


# ---------------------------------------------------------------------------
# Crash marker: the leg's structured last words
# ---------------------------------------------------------------------------


def crash_marker_path() -> Optional[str]:
    """Where this process should write its crash marker (the supervisor
    points the ``MPI4DL_CRASH_MARKER`` hatch at a per-attempt file)."""
    return os.environ.get("MPI4DL_CRASH_MARKER") or None


def write_crash_marker(path: str, *, phase: str, gstep: int = -1,
                       steps_run: int = -1,
                       error: Optional[BaseException] = None,
                       failure_class: Optional[str] = None,
                       **extra: Any) -> None:
    """Write the structured crash marker — atomically (tmp + rename), and
    NEVER raising: the marker is evidence about a failure already in
    flight, and masking the original exception with a marker-write error
    would destroy exactly what it exists to preserve."""
    try:
        rec: Dict[str, Any] = {
            "schema": MARKER_SCHEMA, "t": time.time(), "phase": phase,
            "gstep": int(gstep), "steps_run": int(steps_run),
            "failure_class": failure_class,
        }
        if error is not None:
            rec["error_type"] = type(error).__name__
            rec["error"] = repr(error)
            # Base-class names let the classifier match exception FAMILIES
            # (any OSError subclass is transient-io) without importing the
            # leg's modules.
            rec["error_bases"] = [c.__name__ for c in type(error).__mro__]
        rec.update(extra)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(rec, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except Exception:  # noqa: BLE001  # analysis: ok(swallow-except)
        pass  # deliberate: diagnostics must never mask the real failure


def read_crash_marker(path: Optional[str]) -> Optional[dict]:
    """Read a crash marker; None when absent/unreadable (no marker is
    itself evidence — the leg died too hard to write one)."""
    if not path:
        return None
    try:
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


# ---------------------------------------------------------------------------
# Quarantine list (poison-batch exclusion for nan_cluster recovery)
# ---------------------------------------------------------------------------


def quarantine_steps_from_env() -> frozenset:
    """Global steps the supervised loop must SKIP (fetch nothing, train
    nothing) — the supervisor sets ``MPI4DL_QUARANTINE_STEPS`` to the
    anomalous steps of a ``nan_cluster`` leg before relaunching."""
    raw = os.environ.get("MPI4DL_QUARANTINE_STEPS", "")
    out = set()
    for tok in raw.split(","):
        tok = tok.strip()
        if tok.lstrip("-").isdigit():
            out.add(int(tok))
    return frozenset(out)


# ---------------------------------------------------------------------------
# Classification
# ---------------------------------------------------------------------------


def _anomaly_steps(records: Sequence[Mapping[str, Any]]) -> List[int]:
    return sorted({
        int(r["gstep"]) for r in records
        if r.get("kind") == "anomaly" and r.get("gstep") is not None
    })


# Flight-recorder phase -> where a hang actually sits (ISSUE 17): the
# recorder's last known phase at escalation time distinguishes the stalls
# the exit status alone cannot.
_HANG_SITES = {
    "fetch": "data_stall",
    "step": "collective",
    "compile": "collective",
    "save": "checkpoint_gather",
}


def classify_failure(
    exit_code: Optional[int],
    marker: Optional[Mapping[str, Any]] = None,
    records: Sequence[Mapping[str, Any]] = (),
    stderr_tail: str = "",
    flight: Optional[Mapping[str, Any]] = None,
) -> "Classification":
    """Map one leg exit onto the typed classification.

    Evidence precedence: an explicit ``failure_class`` in the marker (the
    watchdog's ``hang``, the mesh faults) wins; then the marker's error
    analysis (type family + phase); then the exit status (SIGKILL/escalation
    exit = hang, SIGTERM = preempted); then stderr/RunLog-tail pattern
    matches; then ``unknown`` — never untyped, never silent.

    ``flight`` (the leg's ``flight.json`` dump, ISSUE 17) is the fourth
    evidence source: it refines rather than decides — a hang gains a
    ``hang_site`` (data stall vs collective vs checkpoint gather, from the
    recorder's phase at escalation), an ``oom_step`` gains the watermark
    growth + fastest-growing device from the ring, and the
    oom_compile/oom_step split survives a leg whose RunLog never made it
    back (the recorder's ``steps_seen`` says whether the first step ever
    completed)."""
    from mpi4dl_tpu.obs.flight import flight_summary, watermark_growth

    ev: Dict[str, Any] = {"exit_code": exit_code}
    fsum = flight_summary(flight)
    if fsum is not None:
        ev["flight"] = fsum

    def _hang_site() -> Optional[str]:
        if not flight:
            return None
        return _HANG_SITES.get(str(flight.get("phase") or ""))

    def _oom_localize() -> None:
        if not flight:
            return
        growth = watermark_growth(dict(flight))
        if growth is not None:
            ev["oom_watermark_growth_bytes"] = growth[0]
            if growth[1] is not None:
                ev["oom_device"] = growth[1]

    if marker:
        ev.update({
            "marker_phase": marker.get("phase"),
            "marker_gstep": marker.get("gstep"),
            "marker_error": marker.get("error"),
        })
        explicit = marker.get("failure_class")
        if explicit in FAILURE_CLASSES:
            ev["source"] = "marker:explicit"
            if explicit == "hang":
                site = _hang_site()
                if site:
                    ev["hang_site"] = site
            if explicit == "oom_step":
                _oom_localize()
            return Classification(explicit, ev)
        err = str(marker.get("error") or "")
        etype = marker.get("error_type") or ""
        bases = set(marker.get("error_bases") or ())
        if etype == "MeshShrunk" or "MeshShrunk" in bases:
            ev["source"] = "marker:error_type"
            ev["shrunk_spec"] = marker.get("shrunk_spec") or ""
            return Classification("mesh_shrunk", ev)
        if any(p in err for p in _OOM_PATTERNS):
            ev["source"] = "marker:oom_pattern"
            cls = (
                "oom_compile" if marker.get("phase") == "compile"
                else "oom_step"
            )
            if cls == "oom_step":
                _oom_localize()
            return Classification(cls, ev)
        if etype == "AnomalyError":
            ev["source"] = "marker:error_type"
            ev["anomaly_steps"] = _anomaly_steps(records)
            return Classification("nan_cluster", ev)
        if etype in ("CheckpointInvalid", "CheckpointMismatch") and (
            "shard file" in err
        ):
            ev["source"] = "marker:error_type"
            return Classification("lost_shard", ev)
        if "OSError" in bases or etype == "CheckpointWriteError":
            ev["source"] = "marker:error_family"
            return Classification("transient_io", ev)
    if exit_code is not None and exit_code != 0:
        import signal as _signal

        if exit_code == HANG_EXIT_CODE or exit_code == -_signal.SIGKILL:
            ev["source"] = "exit_code"
            site = _hang_site()
            if site:
                ev["hang_site"] = site
            return Classification("hang", ev)
        if exit_code == -_signal.SIGTERM:
            # killed before the grace-window save finished — still a
            # preemption; the resume loses at most one checkpoint interval
            ev["source"] = "exit_code"
            return Classification("preempted", ev)
    if any(p in stderr_tail for p in _OOM_PATTERNS):
        ev["source"] = "stderr:oom_pattern"
        # no marker phase to split on: a leg that died during its first
        # step never wrote a step record.  The flight recorder's
        # steps_seen covers the case where the RunLog itself was lost.
        stepped = any(r.get("kind") == "step" for r in records)
        if not stepped and flight:
            stepped = int(flight.get("steps_seen") or 0) > 0
        cls = "oom_step" if stepped else "oom_compile"
        if cls == "oom_step":
            _oom_localize()
        return Classification(cls, ev)
    n_anomalies = sum(1 for r in records if r.get("kind") == "anomaly")
    n_recoveries = sum(1 for r in records if r.get("kind") == "recovery")
    if "AnomalyError" in stderr_tail or n_anomalies > n_recoveries:
        # Every guard rollback pairs its anomaly with a recovery record; an
        # UNPAIRED anomaly at death is the guard fail-fasting.  A leg whose
        # anomalies all recovered and that later died of something else
        # must NOT land here (quarantining healthy steps) — it falls
        # through to unknown.
        ev["source"] = "stderr/runlog:anomaly"
        ev["anomaly_steps"] = _anomaly_steps(records)
        return Classification("nan_cluster", ev)
    ev["source"] = "fallback"
    return Classification("unknown", ev)


@dataclasses.dataclass(frozen=True)
class Classification:
    failure_class: str
    evidence: Dict[str, Any]


# ---------------------------------------------------------------------------
# Per-class recovery policy + backoff
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Policy:
    """What the supervisor does about one failure class.  ``max_attempts``
    bounds how many times THIS class may recur before giving up (the
    global ``MPI4DL_SUPERVISE_MAX_ATTEMPTS`` cap applies on top)."""

    action: str  # retry | degrade | quarantine | resume | fail
    max_attempts: int
    backoff: bool = False


POLICIES: Dict[str, Policy] = {
    "oom_compile": Policy("degrade", 3),
    "oom_step": Policy("degrade", 3),
    "mesh_shrunk": Policy("degrade", 3),
    "nan_cluster": Policy("quarantine", 2),
    "hang": Policy("retry", 2, backoff=True),
    "preempted": Policy("resume", 64),
    "lost_shard": Policy("retry", 2, backoff=True),
    "transient_io": Policy("retry", 3, backoff=True),
    "unknown": Policy("retry", 1, backoff=True),
}


def backoff_delay(attempt: int, *, base: float = 1.0, cap: float = 30.0,
                  jitter: float = 0.25, seed: int = 0,
                  job: str = "") -> float:
    """Exponential backoff with bounded jitter, deterministic under
    ``(job, seed)``: ``min(cap, base * 2**(attempt-1))`` scaled by a factor
    in ``[1-jitter, 1+jitter]`` drawn from ``Random((job, seed, attempt))``
    — two supervisors with different seeds OR different fleet job ids
    de-synchronize their retries (the thundering-herd point of jitter: a
    fleet's jobs share one seed but must not hammer shared I/O in
    lockstep) while one supervisor's schedule stays reproducible."""
    raw = min(float(cap), float(base) * (2.0 ** max(0, attempt - 1)))
    # str seeds hash via sha512 — stable across processes, unlike tuples.
    rng = random.Random(f"{job}:{seed}:{attempt}" if job
                        else f"{seed}:{attempt}")
    return raw * (1.0 + jitter * (2.0 * rng.random() - 1.0))


def supervise_knobs_from_env(
    max_attempts: Optional[int] = None,
    base: Optional[float] = None,
    cap: Optional[float] = None,
) -> Dict[str, float]:
    """Resolve the supervisor knobs: explicit values win, then the hatches
    (``MPI4DL_SUPERVISE_MAX_ATTEMPTS`` / ``_BACKOFF`` / ``_BACKOFF_CAP``),
    then the defaults (6 attempts, 1 s base, 30 s cap)."""
    return {
        "max_attempts": int(
            max_attempts if max_attempts is not None
            else os.environ.get("MPI4DL_SUPERVISE_MAX_ATTEMPTS", "") or 6
        ),
        "base": float(
            base if base is not None
            else os.environ.get("MPI4DL_SUPERVISE_BACKOFF", "") or 1.0
        ),
        "cap": float(
            cap if cap is not None
            else os.environ.get("MPI4DL_SUPERVISE_BACKOFF_CAP", "") or 30.0
        ),
    }


# ---------------------------------------------------------------------------
# Leg launching
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LegOutcome:
    """Everything one leg left behind: exit status, the result summary it
    wrote on success, its crash marker, its RunLog records, and the tail of
    its stderr."""

    rc: Optional[int]
    result: Optional[Dict[str, Any]] = None
    marker: Optional[Dict[str, Any]] = None
    records: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    stderr_tail: str = ""
    # The leg's flight.json dump (ISSUE 17) — the fourth evidence source;
    # None when the leg exited cleanly or the recorder was disabled.
    flight: Optional[Dict[str, Any]] = None


def flags_to_argv(flags: Mapping[str, Any]) -> List[str]:
    """``{"image-size": 32, "stripe-bwd": True}`` → bench-flag argv (the
    drill override vocabulary; True renders a bare flag, None/False omit)."""
    argv: List[str] = []
    for k, v in flags.items():
        if v is None or v is False:
            continue
        argv.append(f"--{k}")
        if v is not True:
            argv.append(str(v))
    return argv


def _leg_runlog_records(tele_dir: str) -> List[Dict[str, Any]]:
    """The newest RunLog in a leg's telemetry dir (its classification
    evidence); empty when the leg died before opening one."""
    from mpi4dl_tpu.obs.runlog import read_runlog

    try:
        files = sorted(
            os.path.join(tele_dir, f) for f in os.listdir(tele_dir)
            if f.endswith(".jsonl")
        )
    except OSError:
        return []
    if not files:
        return []
    newest = max(files, key=os.path.getmtime)
    try:
        return read_runlog(newest)
    except OSError:
        return []


def subprocess_leg_launcher(
    family: str, model: str, workdir: str,
    *, timeout: Optional[float] = None, job: str = "",
    on_spawn: Optional[Callable[[Any], None]] = None,
) -> Callable[[Mapping[str, Any], Mapping[str, str], int], LegOutcome]:
    """The real launcher: each attempt is one fresh
    ``python -m mpi4dl_tpu.resilience leg`` subprocess (fresh backend, so a
    compile-OOM retry is sound and the jax-0.4.x same-program compile-cache
    hazard documented in drill.py cannot occur across attempts).  Per-
    attempt artifacts land under ``workdir/attempt<N>/``: crash marker, leg
    result JSON, telemetry dir, stderr.

    ``job`` namespaces every per-attempt evidence artifact by fleet job id
    (``workdir/<job>/attempt<N>/`` + the ``MPI4DL_FLEET_JOB`` env tag), so
    N concurrent supervisors sharing one fleet workdir cannot clobber each
    other's markers / flight dumps / leg RunLogs.  ``on_spawn(proc)`` is
    called with the live ``Popen`` handle the moment the leg starts — the
    fleet scheduler registers it there so a preemption drain can SIGTERM
    the in-flight leg instead of waiting for it."""

    def launch(flags: Mapping[str, Any], env_extra: Mapping[str, str],
               attempt: int) -> LegOutcome:
        adir = (os.path.join(workdir, job, f"attempt{attempt}") if job
                else os.path.join(workdir, f"attempt{attempt}"))
        os.makedirs(adir, exist_ok=True)
        marker = os.path.join(adir, "crash_marker.json")
        result_path = os.path.join(adir, "leg_result.json")
        tele = os.path.join(adir, "tele")
        leg_flags = dict(flags)
        leg_flags.setdefault("telemetry-dir", tele)
        cmd = [
            sys.executable, "-m", "mpi4dl_tpu.resilience", "leg",
            "--family", family, "--model", model, "--result", result_path,
            "--", *flags_to_argv(leg_flags),
        ]
        env = dict(os.environ)
        # Injected faults never leak into retry legs: the supervisor owns
        # single-shot semantics ACROSS processes (the in-process injector
        # only owns them within one).
        env.pop("MPI4DL_FAULT", None)
        env.update(env_extra)
        env["MPI4DL_CRASH_MARKER"] = marker
        if job:
            env["MPI4DL_FLEET_JOB"] = job
        stderr_path = os.path.join(adir, "leg.stderr")
        with open(stderr_path, "wb") as errf:
            try:
                proc = subprocess.Popen(
                    cmd, env=env, stdout=errf, stderr=subprocess.STDOUT,
                )
                if on_spawn is not None:
                    on_spawn(proc)
                rc: Optional[int] = proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                rc = None  # leg wedged past the hard timeout: treat as hang
        result = None
        try:
            with open(result_path, "r", encoding="utf-8") as f:
                result = json.load(f)
        except (OSError, ValueError):
            result = None
        try:
            with open(stderr_path, "r", encoding="utf-8",
                      errors="replace") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - 16384))
                tail = f.read()
        except OSError:
            tail = ""
        from mpi4dl_tpu.obs.flight import FLIGHT_BASENAME, read_flight

        out = LegOutcome(
            rc=rc if rc is not None else HANG_EXIT_CODE,
            result=result,
            marker=read_crash_marker(marker),
            records=_leg_runlog_records(tele),
            stderr_tail=tail,
            flight=read_flight(os.path.join(adir, FLIGHT_BASENAME)),
        )
        return out

    return launch


def run_leg(family: str, model: str, argv: Sequence[str],
            result_path: Optional[str] = None) -> int:
    """One training leg in THIS process (the ``leg`` CLI body): run the
    benchmark entry point, persist its summary dict for the supervisor, and
    guarantee a crash marker exists on any failure path the supervised
    loop's own marker did not cover (build/mesh errors before the loop
    starts)."""
    marker = crash_marker_path()
    try:
        from benchmarks.common import run

        result = run(family, model, list(argv))
    except BaseException as e:
        if marker and not os.path.exists(marker):
            write_crash_marker(marker, phase="build", error=e)
        raise
    fleet_job = os.environ.get("MPI4DL_FLEET_JOB")
    if fleet_job:
        # Tag the summary with the owning fleet job: the scheduler's
        # cross-contamination check verifies evidence stayed in its lane.
        result = dict(result)
        result["fleet_job"] = fleet_job
    if result_path:
        tmp = f"{result_path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({k: v for k, v in result.items()
                       if _json_safe(v)}, f)
        os.replace(tmp, result_path)
    return 0


def _json_safe(v: Any) -> bool:
    try:
        json.dumps(v)
        return True
    except (TypeError, ValueError):
        return False


# ---------------------------------------------------------------------------
# The supervisor state machine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SupervisorResult:
    ok: bool
    attempts: int
    incidents: List[Dict[str, Any]]
    final: Optional[Dict[str, Any]] = None  # last leg's result summary
    flags: Optional[Dict[str, Any]] = None  # the flags the final leg ran
    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    reason: str = ""  # non-empty on failure
    # True when the fleet's stop hook drained this supervisor (graceful
    # preemption / migration) — NOT a job failure: the checkpoint is
    # durable and the scheduler relaunches elsewhere.
    stopped: bool = False


class Supervisor:
    """Run one training job as a sequence of supervised legs.

    ``launch(flags, env_extra, attempt) -> LegOutcome`` is injectable for
    tests; the default is :func:`subprocess_leg_launcher`.  ``probe`` is
    the planner's feasibility probe (``None`` = accept the first ladder
    rung — the planner still records that the probe was skipped).
    ``fault`` applies to attempt 1 ONLY: the drills inject one disaster
    into the first leg and supervision must recover without it."""

    def __init__(self, family: str, model: str,
                 flags: Mapping[str, Any], *,
                 workdir: str,
                 runlog=None,
                 launch=None,
                 probe: Optional[Callable[[Mapping[str, Any]],
                                          Optional[float]]] = None,
                 budget_gb: Optional[float] = None,
                 max_attempts: Optional[int] = None,
                 backoff_base: Optional[float] = None,
                 backoff_cap: Optional[float] = None,
                 seed: int = 0,
                 fault: str = "",
                 job: str = "",
                 stop: Optional[Callable[[], str]] = None,
                 on_spawn: Optional[Callable[[Any], None]] = None,
                 log: Callable[[str], None] = lambda s: None,
                 _sleep: Callable[[float], None] = time.sleep):
        knobs = supervise_knobs_from_env(max_attempts, backoff_base,
                                         backoff_cap)
        self.family, self.model = family, model
        self.flags = dict(flags)
        self.workdir = workdir
        self.runlog = runlog
        self.launch = (
            launch if launch is not None
            else subprocess_leg_launcher(family, model, workdir, job=job,
                                         on_spawn=on_spawn)
        )
        self.probe = probe
        self.budget_gb = budget_gb
        self.max_attempts = int(knobs["max_attempts"])
        self.backoff_base = float(knobs["base"])
        self.backoff_cap = float(knobs["cap"])
        self.seed = seed
        self.fault = fault
        self.job = job
        # ``stop() -> reason`` is polled between legs: a non-empty string
        # ends the run with ``stopped=True`` instead of relaunching (the
        # fleet scheduler's graceful preemption/migration drain).
        self.stop = stop
        self.log = log
        self._sleep = _sleep

    # -- incident plumbing -------------------------------------------------

    def _incident(self, rec: Dict[str, Any]) -> None:
        if self.runlog is not None:
            self.runlog.write("supervisor", **rec)
        self.log(
            f"[supervisor] attempt {rec.get('attempt')}: "
            f"{rec.get('failure_class')} -> {rec.get('policy')}"
            + (f" ({rec.get('note')})" if rec.get("note") else "")
        )

    def _summary(self, res: SupervisorResult) -> SupervisorResult:
        if self.runlog is not None:
            self.runlog.write(
                "supervisor_summary", ok=res.ok, attempts=res.attempts,
                incidents=len(res.incidents), reason=res.reason,
                final_flags=dict(res.flags or {}), final_env=dict(res.env),
                stopped=res.stopped, job=self.job or None,
            )
        return res

    # -- main loop ---------------------------------------------------------

    def run(self) -> SupervisorResult:
        flags = dict(self.flags)
        env_extra: Dict[str, str] = {}
        incidents: List[Dict[str, Any]] = []
        per_class: Dict[str, int] = {}
        quarantined: set = set()
        last_final: Optional[Dict[str, Any]] = None
        attempt = 0
        while attempt < self.max_attempts:
            why = self.stop() if self.stop is not None else ""
            if why:
                # Drained by the fleet: surface the last leg's summary (the
                # preempted leg checkpointed on the way out) and say so —
                # a stop is a scheduling decision, not a job failure.
                return self._summary(SupervisorResult(
                    ok=False, attempts=attempt, incidents=incidents,
                    final=last_final, flags=flags, env=env_extra,
                    reason=why, stopped=True,
                ))
            attempt += 1
            env = dict(env_extra)
            if self.fault and attempt == 1:
                env["MPI4DL_FAULT"] = self.fault
            out = self.launch(flags, env, attempt)
            if out.result is not None:
                last_final = out.result
            if out.rc == 0 and not (out.result or {}).get("preempted"):
                return self._summary(SupervisorResult(
                    ok=True, attempts=attempt, incidents=incidents,
                    final=out.result, flags=flags, env=env_extra,
                ))
            if out.rc == 0:
                cls = Classification(
                    "preempted",
                    {"exit_code": 0, "source": "leg_result:preempted",
                     "final_step": (out.result or {}).get("final_step")},
                )
            else:
                cls = classify_failure(out.rc, out.marker, out.records,
                                       out.stderr_tail, out.flight)
            policy = POLICIES[cls.failure_class]
            per_class[cls.failure_class] = (
                per_class.get(cls.failure_class, 0) + 1
            )
            nth = per_class[cls.failure_class]
            incident: Dict[str, Any] = {
                "attempt": attempt,
                "failure_class": cls.failure_class,
                "policy": policy.action,
                "class_attempt": nth,
                "evidence": cls.evidence,
            }
            if nth > policy.max_attempts:
                incident["policy"] = "fail"
                incident["note"] = (
                    f"{cls.failure_class} recurred {nth} times "
                    f"(> {policy.max_attempts}) — giving up"
                )
                incidents.append(incident)
                self._incident(incident)
                return self._summary(SupervisorResult(
                    ok=False, attempts=attempt, incidents=incidents,
                    flags=flags, env=env_extra,
                    reason=incident["note"],
                ))

            apply_backoff = policy.backoff
            if policy.action == "degrade":
                from mpi4dl_tpu.resilience.planner import plan_degrade

                plan = plan_degrade(
                    flags, self.family, cls.failure_class,
                    budget_gb=self.budget_gb, probe=self.probe,
                    evidence=cls.evidence,
                )
                if plan is None:
                    incident["policy"] = "fail"
                    incident["note"] = (
                        "degradation ladder exhausted: no feasible "
                        "geometry below the current one"
                    )
                    incidents.append(incident)
                    self._incident(incident)
                    return self._summary(SupervisorResult(
                        ok=False, attempts=attempt, incidents=incidents,
                        flags=flags, env=env_extra,
                        reason=incident["note"],
                    ))
                flags = dict(plan.flags)
                env_extra.update(plan.env)
                incident["config_delta"] = plan.delta
                incident["plan_rungs"] = plan.rungs
                incident["probe"] = plan.probe_evidence
                incident["note"] = plan.note
            elif policy.action == "quarantine":
                steps = set(cls.evidence.get("anomaly_steps") or ())
                steps |= set(_anomaly_steps(out.records))
                if not steps:
                    # no anomalous step identified: nothing to quarantine —
                    # the incident must SAY retry (and back off like one),
                    # not claim a quarantine that never happened
                    incident["policy"] = "retry"
                    apply_backoff = True
                    incident["note"] = (
                        "nan_cluster with no identifiable anomaly steps — "
                        "plain retry"
                    )
                else:
                    quarantined |= steps
                    env_extra["MPI4DL_QUARANTINE_STEPS"] = ",".join(
                        str(s) for s in sorted(quarantined)
                    )
                    incident["quarantined"] = sorted(quarantined)
                    incident["note"] = (
                        f"quarantined poison steps {sorted(steps)}"
                    )
            if apply_backoff:
                delay = backoff_delay(
                    nth, base=self.backoff_base, cap=self.backoff_cap,
                    seed=self.seed, job=self.job,
                )
                incident["backoff_s"] = round(delay, 3)
                incidents.append(incident)
                self._incident(incident)
                self._sleep(delay)
            else:
                incidents.append(incident)
                self._incident(incident)
        return self._summary(SupervisorResult(
            ok=False, attempts=attempt, incidents=incidents, flags=flags,
            env=env_extra,
            reason=f"MPI4DL_SUPERVISE_MAX_ATTEMPTS={self.max_attempts} "
                   "leg launches exhausted",
        ))
