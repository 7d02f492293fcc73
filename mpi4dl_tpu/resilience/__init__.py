"""Resilience subsystem: make training runs survive what obs/ observes.

ISSUE 3 — the reference MPI4DL stack has no fault tolerance at all (SURVEY
§5): no checkpointing, no recovery; a single NaN or a preempted rank kills
a multi-day pathology run.  This package turns the existing pieces
(checkpoint.py durability, obs/ telemetry) into a crash-survivable trainer:

- :mod:`~mpi4dl_tpu.resilience.loop` — ``run_supervised``: the one
  supervised training loop all four engine families (lp / sp / gems /
  gems_sp) run under.
- :mod:`~mpi4dl_tpu.resilience.guard` — per-step finite-loss (and opt-in
  grad-norm) check; on anomaly the loop rolls back to the last good
  checkpoint and skips the poison batch.
- :mod:`~mpi4dl_tpu.resilience.preempt` — SIGTERM/SIGINT → finish the
  in-flight step, save, exit 0.
- :mod:`~mpi4dl_tpu.resilience.writer` — background checkpoint writes
  (device_get on the training thread, serialize+fsync off it).
- :mod:`~mpi4dl_tpu.resilience.faults` — deterministic fault injection via
  ``MPI4DL_FAULT=<kind>@<step>[:arg]`` — powers tests and the CI
  kill-and-resume job; ISSUE 13 adds the mesh-level kinds
  (``lost_shard_files``, ``reshape``).
- :mod:`~mpi4dl_tpu.resilience.watchdog` — step wall-clock watchdog that
  dumps live stacks, the last RunLog + ``checkpoint`` records, and live
  memory stats before a hang dies silently.
- :mod:`~mpi4dl_tpu.resilience.drill` — the mesh-fault drill harness
  (``python -m mpi4dl_tpu.resilience drill``): scripted disasters with
  typed per-scenario verdicts; ``--supervisor`` drills the supervisor's
  whole control plane.
- :mod:`~mpi4dl_tpu.resilience.supervisor` — the elastic supervisor
  (ISSUE 15): legs as subprocesses, typed failure classification, per-class
  retry/backoff, poison-batch quarantine, degrade-and-continue.
- :mod:`~mpi4dl_tpu.resilience.planner` — the degradation ladder + the
  compile-only feasibility probe the supervisor re-plans with; ISSUE 18
  adds the upward (re-expansion) search.
- :mod:`~mpi4dl_tpu.resilience.allocator` /
  :mod:`~mpi4dl_tpu.resilience.fleet` — the multi-tenant fleet scheduler
  (ISSUE 18): bin-packed slices, typed job lifecycle, priority preemption,
  displace/degrade/re-expand via elastic checkpoints, poison-job
  quarantine, and the ``drill --fleet`` chaos matrix.

Event schema, fault kinds, manifest format, recovery semantics:
docs/resilience.md.
"""

from __future__ import annotations

from mpi4dl_tpu.resilience.drill import (
    DrillVerdict,
    Scenario,
    SupervisorScenario,
    default_scenarios,
    run_drills,
    run_scenario,
    run_supervisor_drills,
    supervisor_scenarios,
)
from mpi4dl_tpu.resilience.faults import (
    CKPT_FAULT_KINDS,
    FAULT_KINDS,
    FaultInjected,
    FaultInjector,
    FaultSpec,
    MeshShrunk,
    corrupt_file,
    fault_from_env,
    lose_shard_files,
    parse_fault,
    synthetic_oom,
)
from mpi4dl_tpu.resilience.allocator import PackResult, Request, Slice, pack
from mpi4dl_tpu.resilience.fleet import (
    JOB_STATES,
    TERMINAL_STATES,
    FleetJob,
    FleetResult,
    FleetScenario,
    FleetScheduler,
    fleet_knobs_from_env,
    fleet_scenarios,
    run_fleet_drills,
    run_fleet_scenario,
)
from mpi4dl_tpu.resilience.planner import (
    Plan,
    compile_probe,
    degrade_candidates,
    expand_candidates,
    plan_degrade,
    plan_expand,
    required_devices,
)
from mpi4dl_tpu.resilience.supervisor import (
    FAILURE_CLASSES,
    POLICIES,
    Classification,
    LegOutcome,
    Policy,
    Supervisor,
    SupervisorResult,
    backoff_delay,
    classify_failure,
    read_crash_marker,
    write_crash_marker,
)
from mpi4dl_tpu.resilience.guard import AnomalyError, AnomalyGuard, global_norm
from mpi4dl_tpu.resilience.loop import LoopResult, run_supervised
from mpi4dl_tpu.resilience.preempt import PreemptionHandler
from mpi4dl_tpu.resilience.watchdog import (
    StepWatchdog,
    dump_stacks,
    watchdog_budget_from_env,
)
from mpi4dl_tpu.resilience.writer import AsyncCheckpointWriter, CheckpointWriteError

__all__ = [
    "CKPT_FAULT_KINDS",
    "FAILURE_CLASSES",
    "FAULT_KINDS",
    "JOB_STATES",
    "POLICIES",
    "TERMINAL_STATES",
    "AnomalyError",
    "AnomalyGuard",
    "AsyncCheckpointWriter",
    "CheckpointWriteError",
    "Classification",
    "DrillVerdict",
    "FaultInjected",
    "FaultInjector",
    "FaultSpec",
    "FleetJob",
    "FleetResult",
    "FleetScenario",
    "FleetScheduler",
    "LegOutcome",
    "LoopResult",
    "MeshShrunk",
    "PackResult",
    "Plan",
    "Policy",
    "PreemptionHandler",
    "Request",
    "Scenario",
    "Slice",
    "StepWatchdog",
    "Supervisor",
    "SupervisorResult",
    "SupervisorScenario",
    "backoff_delay",
    "classify_failure",
    "compile_probe",
    "corrupt_file",
    "default_scenarios",
    "degrade_candidates",
    "dump_stacks",
    "expand_candidates",
    "fault_from_env",
    "fleet_knobs_from_env",
    "fleet_scenarios",
    "global_norm",
    "lose_shard_files",
    "pack",
    "parse_fault",
    "plan_degrade",
    "plan_expand",
    "read_crash_marker",
    "required_devices",
    "run_drills",
    "run_fleet_drills",
    "run_fleet_scenario",
    "run_scenario",
    "run_supervised",
    "run_supervisor_drills",
    "supervisor_scenarios",
    "synthetic_oom",
    "watchdog_budget_from_env",
    "write_crash_marker",
]
