"""Elastic supervisor (mpi4dl_tpu/resilience/supervisor.py + planner.py,
ISSUE 15): the typed failure classification, the crash-marker plumbing through
the supervised loop, backoff arithmetic, the degradation ladder with its
feasibility probe, the supervisor state machine (fake legs), the drill
judge, and — slow lane — the end-to-end oom-degrade drill on the virtual
mesh."""

from __future__ import annotations

import signal

import pytest

from mpi4dl_tpu.resilience import (
    FAILURE_CLASSES,
    POLICIES,
    FaultInjector,
    LegOutcome,
    MeshShrunk,
    Supervisor,
    SupervisorScenario,
    backoff_delay,
    classify_failure,
    degrade_candidates,
    parse_fault,
    plan_degrade,
    read_crash_marker,
    run_supervised,
    supervisor_scenarios,
    synthetic_oom,
    write_crash_marker,
)
from mpi4dl_tpu.resilience.drill import run_supervisor_scenario
from mpi4dl_tpu.resilience.supervisor import quarantine_steps_from_env
from mpi4dl_tpu.resilience.watchdog import HANG_EXIT_CODE
from mpi4dl_tpu.obs import RunLog, read_runlog

from test_resilience import _ToyDataset, _toy_state, _toy_step


def _marker_for(error, phase="step", gstep=2, **extra):
    return {
        "schema": 1, "phase": phase, "gstep": gstep, "steps_run": gstep,
        "failure_class": extra.pop("failure_class", None),
        "error_type": type(error).__name__, "error": repr(error),
        "error_bases": [c.__name__ for c in type(error).__mro__],
        **extra,
    }


# ---------------------------------------------------------------------------
# Fault parsing (the new kinds)
# ---------------------------------------------------------------------------


def test_parse_new_fault_kinds():
    assert parse_fault("oom_compile@0").kind == "oom_compile"
    assert parse_fault("oom_step@2").step == 2
    ms = parse_fault("mesh_shrunk@1:devices=4")
    assert ms.opts == "devices=4" and ms.arg == 0.0
    assert parse_fault("slow_step@1:0.5").arg == 0.5
    assert parse_fault("io_error@3").kind == "io_error"
    with pytest.raises(ValueError):
        parse_fault("slow_step@1:fast")  # numeric-arg kind with text arg


def test_synthetic_oom_message_carries_the_status_code():
    e = synthetic_oom("oom_compile", 0)
    assert "RESOURCE_EXHAUSTED" in repr(e)


# ---------------------------------------------------------------------------
# Classification classification — every class, plus the unknown fallback
# ---------------------------------------------------------------------------


def test_classify_every_class_from_markers():
    cases = [
        (_marker_for(synthetic_oom("oom_compile", 0), phase="compile",
                     gstep=0), "oom_compile"),
        (_marker_for(synthetic_oom("oom_step", 2), phase="step"),
         "oom_step"),
        (_marker_for(OSError("nfs blip")), "transient_io"),
        (_marker_for(MeshShrunk("devices=4"), shrunk_spec="devices=4"),
         "mesh_shrunk"),
        ({"schema": 1, "phase": "step", "gstep": 3,
          "failure_class": "hang"}, "hang"),
    ]
    for marker, expect in cases:
        c = classify_failure(1, marker)
        assert c.failure_class == expect, (marker, c)
        assert c.evidence.get("source")

    # nan_cluster: AnomalyError marker + the anomalous steps as evidence
    class AnomalyError(RuntimeError):
        pass

    c = classify_failure(
        1, _marker_for(AnomalyError("4 rollbacks")),
        records=[{"kind": "anomaly", "gstep": 1},
                 {"kind": "anomaly", "gstep": 3}],
    )
    assert c.failure_class == "nan_cluster"
    assert c.evidence["anomaly_steps"] == [1, 3]

    # lost_shard: a restore that died on vanished shard files
    class CheckpointInvalid(ValueError):
        pass

    c = classify_failure(
        1, _marker_for(CheckpointInvalid(
            "ck/ckpt_2: shard file leaf00001_s000.bin missing (leaf 1)"
        ), phase="init"),
    )
    assert c.failure_class == "lost_shard"


def test_classify_recovered_anomalies_are_not_a_nan_cluster():
    """A leg whose anomalies all ROLLED BACK (anomaly+recovery pairs) and
    that later died of something else must not read as nan_cluster — that
    would quarantine healthy, already-recovered steps."""
    records = [
        {"kind": "anomaly", "gstep": 2},
        {"kind": "recovery", "resumed_from": 0},
        {"kind": "step", "gstep": 3},
        {"kind": "step", "gstep": 4},
        {"kind": "step", "gstep": 5},
    ]
    assert classify_failure(-11, None, records).failure_class == "unknown"
    # an UNPAIRED anomaly at death is still the guard fail-fasting
    records.append({"kind": "anomaly", "gstep": 6})
    c = classify_failure(1, None, records)
    assert c.failure_class == "nan_cluster"
    assert c.evidence["anomaly_steps"] == [2, 6]


def test_classify_exit_codes_without_marker():
    assert classify_failure(HANG_EXIT_CODE).failure_class == "hang"
    assert classify_failure(-signal.SIGKILL).failure_class == "hang"
    assert classify_failure(-signal.SIGTERM).failure_class == "preempted"
    c = classify_failure(7)
    assert c.failure_class == "unknown" and c.evidence["source"] == "fallback"


def test_classify_stderr_tail_oom_phase_split():
    tail = "...RESOURCE_EXHAUSTED: out of memory allocating 12GB..."
    # no step record ever written -> the compile never finished
    assert classify_failure(1, None, [], tail).failure_class == "oom_compile"
    steps = [{"kind": "step", "gstep": 0}]
    assert classify_failure(1, None, steps, tail).failure_class == "oom_step"


def test_every_failure_class_has_a_policy():
    assert set(POLICIES) == set(FAILURE_CLASSES)


# ---------------------------------------------------------------------------
# Crash marker: round-trip + what the supervised loop writes on the way down
# ---------------------------------------------------------------------------


def test_crash_marker_roundtrip_and_never_raises(tmp_path):
    p = str(tmp_path / "m.json")
    write_crash_marker(p, phase="compile", gstep=0, steps_run=0,
                       error=synthetic_oom("oom_compile", 0))
    m = read_crash_marker(p)
    assert m["phase"] == "compile" and "RESOURCE_EXHAUSTED" in m["error"]
    assert "RuntimeError" in m["error_bases"]
    # unwritable path: silently a no-op (diagnostics must not mask the
    # real failure), unreadable path: None
    write_crash_marker(str(tmp_path / "no" / "dir" / "m.json"),
                       phase="step", error=OSError("x"))
    assert read_crash_marker(str(tmp_path / "absent.json")) is None
    assert read_crash_marker(None) is None


def _run_toy_with_fault(tmp_path, fault, **kw):
    return run_supervised(
        _toy_step(), _toy_state(), _ToyDataset(), global_batch=8,
        steps_per_epoch=4, num_epochs=1,
        faults=FaultInjector(parse_fault(fault)), **kw,
    )


def test_loop_writes_oom_compile_marker(tmp_path, monkeypatch):
    marker = str(tmp_path / "crash_marker.json")
    monkeypatch.setenv("MPI4DL_CRASH_MARKER", marker)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _run_toy_with_fault(tmp_path, "oom_compile@0")
    m = read_crash_marker(marker)
    assert m["phase"] == "compile" and m["steps_run"] == 0
    assert classify_failure(1, m).failure_class == "oom_compile"


def test_loop_writes_oom_step_marker_after_first_step(tmp_path, monkeypatch):
    marker = str(tmp_path / "crash_marker.json")
    monkeypatch.setenv("MPI4DL_CRASH_MARKER", marker)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _run_toy_with_fault(tmp_path, "oom_step@2")
    m = read_crash_marker(marker)
    assert m["phase"] == "step" and m["gstep"] == 2 and m["steps_run"] == 2
    assert classify_failure(1, m).failure_class == "oom_step"


def test_loop_writes_mesh_shrunk_marker_with_spec(tmp_path, monkeypatch):
    marker = str(tmp_path / "crash_marker.json")
    monkeypatch.setenv("MPI4DL_CRASH_MARKER", marker)
    with pytest.raises(MeshShrunk):
        _run_toy_with_fault(tmp_path, "mesh_shrunk@1:devices=4")
    m = read_crash_marker(marker)
    c = classify_failure(1, m)
    assert c.failure_class == "mesh_shrunk"
    assert c.evidence["shrunk_spec"] == "devices=4"


def test_loop_writes_no_marker_when_unconfigured(tmp_path, monkeypatch):
    monkeypatch.delenv("MPI4DL_CRASH_MARKER", raising=False)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _run_toy_with_fault(tmp_path, "oom_step@1")  # must not error out


def test_oom_compile_fires_on_resumed_first_step(tmp_path):
    """oom_compile@k is at-or-after on the process's FIRST step: a resumed
    leg starting past k still dies in its compile phase."""
    faults = FaultInjector(parse_fault("oom_compile@0"))
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        run_supervised(
            _toy_step(), _toy_state(), _ToyDataset(), global_batch=8,
            steps_per_epoch=4, num_epochs=1, start_step=2, faults=faults,
        )


# ---------------------------------------------------------------------------
# Quarantine (poison-batch exclusion)
# ---------------------------------------------------------------------------


def test_quarantine_env_parsing(monkeypatch):
    monkeypatch.setenv("MPI4DL_QUARANTINE_STEPS", "3, 1,junk,7")
    assert quarantine_steps_from_env() == frozenset({1, 3, 7})
    monkeypatch.delenv("MPI4DL_QUARANTINE_STEPS")
    assert quarantine_steps_from_env() == frozenset()


def test_loop_skips_quarantined_steps(tmp_path, monkeypatch):
    monkeypatch.setenv("MPI4DL_QUARANTINE_STEPS", "1")
    runlog = RunLog(str(tmp_path / "q.jsonl"))
    res = run_supervised(
        _toy_step(), _toy_state(), _ToyDataset(), global_batch=8,
        steps_per_epoch=4, num_epochs=1, runlog=runlog,
    )
    runlog.close()
    assert res.final_step == 4 and res.steps_run == 3  # step 1 skipped
    recs = read_runlog(str(tmp_path / "q.jsonl"))
    q = [r for r in recs if r["kind"] == "quarantine"]
    assert len(q) == 1 and q[0]["gstep"] == 1
    assert sorted(r["gstep"] for r in recs if r["kind"] == "step") == [0, 2, 3]


# ---------------------------------------------------------------------------
# Backoff arithmetic
# ---------------------------------------------------------------------------


def test_backoff_deterministic_bounded_and_jittered():
    a = [backoff_delay(i, base=1.0, cap=30.0, seed=7) for i in range(1, 8)]
    b = [backoff_delay(i, base=1.0, cap=30.0, seed=7) for i in range(1, 8)]
    assert a == b  # deterministic under seed
    for i, d in enumerate(a, start=1):
        raw = min(30.0, 2.0 ** (i - 1))
        assert raw * 0.75 <= d <= raw * 1.25  # jitter stays bounded
    assert max(a) <= 30.0 * 1.25  # cap holds under jitter
    # different seeds de-synchronize (the thundering-herd point)
    assert backoff_delay(3, seed=1) != backoff_delay(3, seed=2)


def test_backoff_job_key_desynchronizes_fleet_tenants():
    """ISSUE 18 satellite: two fleet jobs sharing ONE seed must not retry
    in lockstep — the jitter draw is keyed by (job id, seed, attempt)."""
    alpha = [backoff_delay(i, base=1.0, cap=30.0, seed=7, job="alpha")
             for i in range(1, 6)]
    beta = [backoff_delay(i, base=1.0, cap=30.0, seed=7, job="beta")
            for i in range(1, 6)]
    assert alpha != beta  # same seed, different tenants: de-synchronized
    assert all(x != y for x, y in zip(alpha, beta))  # at every attempt
    # ...but each tenant's own schedule is reproducible,
    assert alpha == [backoff_delay(i, base=1.0, cap=30.0, seed=7,
                                   job="alpha") for i in range(1, 6)]
    # bounded exactly like the solo supervisor's,
    for i, d in enumerate(alpha, start=1):
        raw = min(30.0, 2.0 ** (i - 1))
        assert raw * 0.75 <= d <= raw * 1.25
    # and job="" (no fleet) reproduces the legacy pre-fleet sequence.
    legacy = [backoff_delay(i, base=1.0, cap=30.0, seed=7)
              for i in range(1, 6)]
    assert [backoff_delay(i, base=1.0, cap=30.0, seed=7, job="")
            for i in range(1, 6)] == legacy


# ---------------------------------------------------------------------------
# Planner: ladder order, elasticity awareness, feasibility
# ---------------------------------------------------------------------------

_PP_FLAGS = {"split-size": 2, "parts": 4, "batch-size": 4,
             "num-spatial-parts": "4", "slice-method": "square"}


def test_ladder_order_pipeline_family_skips_junction_move():
    """sp_pipeline states re-pack their buffers when the junction moves, so
    the first rung for split-size>=2 must be halve_parts, not
    spatial-until (elastic restorability is part of feasibility)."""
    cands = degrade_candidates(_PP_FLAGS, "sp")
    assert cands[0].rungs == ["halve_parts"]
    assert all("spatial_until_auto" not in c.rungs for c in cands)
    # cumulative: each candidate extends the previous
    assert cands[1].rungs == ["halve_parts", "stripe_bwd"]
    assert cands[1].env == {"MPI4DL_STRIPE_BWD": "1"}


def test_ladder_order_plain_sp_leads_with_junction_move():
    flags = {"parts": 2, "batch-size": 4, "num-spatial-parts": "4",
             "slice-method": "square", "split-size": 1}
    cands = degrade_candidates(flags, "sp")
    assert cands[0].rungs == ["spatial_until_auto"]
    assert cands[0].flags["spatial-until"] == "auto"
    # full ladder, in the documented order
    assert cands[-1].rungs == ["spatial_until_auto", "halve_parts",
                               "stripe_bwd", "shrink_sp"]


def test_ladder_respects_batch_divisibility_and_gems_groups():
    # batch 4, parts 4 -> 2 ok; gems doubles the group so 2*1*2=4 divides
    cands = degrade_candidates(
        {"parts": 4, "batch-size": 4, "times": 1, "split-size": 2},
        "gems",
    )
    assert any("halve_parts" in c.rungs for c in cands)
    # parts already 1: nothing to halve, lp family has no SP rungs at all
    assert degrade_candidates({"parts": 1, "split-size": 2}, "lp") == []


def test_plan_degrade_walks_past_infeasible_rungs():
    probed = []

    def probe(flags, env):
        probed.append(flags.get("parts"))
        # reject the first candidate (parts=2), admit the second
        return 200.0 if len(probed) == 1 else 10.0

    plan = plan_degrade(_PP_FLAGS, "sp", "oom_step",
                        budget_gb=95.0, probe=probe)
    assert plan is not None and plan.rungs == ["halve_parts", "stripe_bwd"]
    assert plan.probe_evidence["probe_peak_gb"] == 10.0
    assert plan.probe_evidence["skipped"][0]["reason"].startswith(
        "probe peak 200.0"
    )


def test_plan_degrade_probe_compile_failure_is_infeasible():
    from mpi4dl_tpu.resilience.planner import INFEASIBLE

    plan = plan_degrade(_PP_FLAGS, "sp", "oom_compile",
                        probe=lambda f, e: INFEASIBLE)
    assert plan is None  # whole ladder failed to compile -> supervisor fails


def test_plan_degrade_mesh_shrunk_fits_the_surviving_devices():
    flags = {"parts": 2, "batch-size": 4, "num-spatial-parts": "4",
             "slice-method": "vertical", "split-size": 2}
    # 4 tiles x 2 stages = 8 devices; only 4 survive -> the plan must land
    # on the shrink_sp rung (2 tiles x 2 stages = 4)
    plan = plan_degrade(flags, "sp", "mesh_shrunk",
                        evidence={"shrunk_spec": "devices=4"})
    assert plan is not None and "shrink_sp" in plan.rungs
    assert plan.flags["num-spatial-parts"] == "2"
    skipped = plan.probe_evidence["skipped"]
    assert all("devices" in s["reason"] for s in skipped)


# ---------------------------------------------------------------------------
# Supervisor state machine (fake legs — no subprocesses, no compiles)
# ---------------------------------------------------------------------------


def _sup(tmp_path, launch, flags=None, runlog=None, **kw):
    kw.setdefault("_sleep", lambda s: None)
    return Supervisor(
        "sp", "resnet", flags if flags is not None else dict(_PP_FLAGS),
        workdir=str(tmp_path / "legs"), launch=launch, runlog=runlog, **kw,
    )


def test_supervisor_clean_leg_zero_incidents(tmp_path):
    """The no-false-positive invariant: a clean run produces zero
    incident records."""
    runlog = RunLog(str(tmp_path / "s.jsonl"))
    res = _sup(tmp_path, lambda f, e, a: LegOutcome(
        rc=0, result={"loss": 1.0, "final_step": 4}), runlog=runlog).run()
    runlog.close()
    assert res.ok and res.attempts == 1 and res.incidents == []
    recs = read_runlog(str(tmp_path / "s.jsonl"))
    assert [r["kind"] for r in recs] == ["supervisor_summary"]
    assert recs[0]["ok"] and recs[0]["incidents"] == 0


def test_supervisor_transient_io_retries_with_backoff_no_delta(tmp_path):
    calls = []
    slept = []

    def launch(flags, env, attempt):
        calls.append((dict(flags), dict(env)))
        if attempt == 1:
            return LegOutcome(rc=1, marker=_marker_for(OSError("blip")))
        return LegOutcome(rc=0, result={"loss": 1.0, "final_step": 4})

    res = _sup(tmp_path, launch, fault="io_error@2", _sleep=slept.append,
               seed=3).run()
    assert res.ok and res.attempts == 2
    inc = res.incidents[0]
    assert inc["failure_class"] == "transient_io" and inc["policy"] == "retry"
    assert inc["backoff_s"] > 0 and slept == [pytest.approx(
        inc["backoff_s"], abs=5e-4)]
    assert "config_delta" not in inc  # no geometry change on transient I/O
    assert calls[0][0] == calls[1][0]  # same flags relaunched
    # the injected fault reaches attempt 1 ONLY
    assert calls[0][1].get("MPI4DL_FAULT") == "io_error@2"
    assert "MPI4DL_FAULT" not in calls[1][1]


def test_supervisor_oom_degrades_with_probe_evidence(tmp_path):
    def launch(flags, env, attempt):
        if attempt == 1:
            return LegOutcome(rc=1, marker=_marker_for(
                synthetic_oom("oom_compile", 0), phase="compile", gstep=0))
        return LegOutcome(rc=0, result={"loss": 0.5, "final_step": 4,
                                        "elastic": True})

    runlog = RunLog(str(tmp_path / "s.jsonl"))
    res = _sup(tmp_path, launch, runlog=runlog, budget_gb=95.0,
               probe=lambda f, e: 0.4).run()
    runlog.close()
    assert res.ok and res.flags["parts"] == 2
    inc = res.incidents[0]
    assert inc["failure_class"] == "oom_compile"
    assert inc["policy"] == "degrade"
    assert inc["config_delta"]["parts"] == {"from": 4, "to": 2}
    assert inc["probe"]["probe_peak_gb"] == 0.4
    recs = read_runlog(str(tmp_path / "s.jsonl"))
    sup_recs = [r for r in recs if r["kind"] == "supervisor"]
    assert len(sup_recs) == 1 and sup_recs[0]["failure_class"] == "oom_compile"


def test_supervisor_nan_cluster_quarantines_anomaly_steps(tmp_path):
    class AnomalyError(RuntimeError):
        pass

    seen_env = []

    def launch(flags, env, attempt):
        seen_env.append(dict(env))
        if attempt == 1:
            return LegOutcome(
                rc=1, marker=_marker_for(AnomalyError("clustered")),
                records=[{"kind": "anomaly", "gstep": 1},
                         {"kind": "anomaly", "gstep": 3}],
            )
        return LegOutcome(rc=0, result={"loss": 1.0, "final_step": 4})

    res = _sup(tmp_path, launch).run()
    assert res.ok
    assert res.incidents[0]["policy"] == "quarantine"
    assert res.incidents[0]["quarantined"] == [1, 3]
    assert seen_env[1]["MPI4DL_QUARANTINE_STEPS"] == "1,3"


def test_supervisor_empty_quarantine_reports_retry_with_backoff(tmp_path):
    """nan_cluster with NO identifiable anomaly steps must record (and
    behave as) a backoff retry — never claim a quarantine that did not
    happen."""

    class AnomalyError(RuntimeError):
        pass

    slept = []

    def launch(flags, env, attempt):
        if attempt == 1:
            return LegOutcome(rc=1,
                              marker=_marker_for(AnomalyError("no steps")))
        return LegOutcome(rc=0, result={"loss": 1.0, "final_step": 4})

    res = _sup(tmp_path, launch, _sleep=slept.append).run()
    assert res.ok
    inc = res.incidents[0]
    assert inc["failure_class"] == "nan_cluster"
    assert inc["policy"] == "retry" and "quarantined" not in inc
    assert inc["backoff_s"] > 0 and slept
    assert not res.env  # no MPI4DL_QUARANTINE_STEPS was set


def test_probe_argv_forwards_the_full_geometry():
    """The feasibility probe must build the SAME engine the relaunch
    would — slice method and junction placement included."""
    from mpi4dl_tpu.resilience.planner import _probe_argv

    argv = _probe_argv(
        {"batch-size": 4, "parts": 2, "split-size": 2,
         "num-spatial-parts": "8", "slice-method": "vertical",
         "spatial-until": "auto", "stripe-bwd": True},
        "sp", "resnet", "/tmp/out.json",
    )
    joined = " ".join(argv)
    assert "--slice-method vertical" in joined
    assert "--num-spatial-parts 8" in joined
    assert "--spatial-until auto" in joined
    assert "--stripe-bwd" in joined


def test_supervisor_preempted_resumes_without_backoff(tmp_path):
    slept = []

    def launch(flags, env, attempt):
        if attempt == 1:
            return LegOutcome(rc=0, result={"loss": 1.0, "final_step": 2,
                                            "preempted": True})
        return LegOutcome(rc=0, result={"loss": 1.0, "final_step": 4})

    res = _sup(tmp_path, launch, _sleep=slept.append).run()
    assert res.ok and res.attempts == 2 and not slept
    assert res.incidents[0]["failure_class"] == "preempted"
    assert res.incidents[0]["policy"] == "resume"


def test_supervisor_per_class_bound_gives_up_typed(tmp_path):
    res = _sup(tmp_path, lambda f, e, a: LegOutcome(
        rc=1, marker=_marker_for(OSError("forever")))).run()
    assert not res.ok
    assert "transient_io recurred" in res.reason
    assert res.incidents[-1]["policy"] == "fail"
    # transient_io allows 3 recurrences; the 4th leg's failure trips it
    assert res.attempts == 4


def test_supervisor_global_attempt_cap(tmp_path):
    def launch(flags, env, attempt):
        return LegOutcome(rc=0, result={"loss": 1.0, "final_step": attempt,
                                        "preempted": True})

    res = _sup(tmp_path, launch, max_attempts=3).run()
    assert not res.ok and res.attempts == 3
    assert "MPI4DL_SUPERVISE_MAX_ATTEMPTS" in res.reason


def test_supervisor_degrade_exhaustion_fails_loudly(tmp_path):
    def launch(flags, env, attempt):
        return LegOutcome(rc=1, marker=_marker_for(
            synthetic_oom("oom_step", 2)))

    # probe rejects everything -> the first degrade already has no plan
    from mpi4dl_tpu.resilience.planner import INFEASIBLE

    res = _sup(tmp_path, launch, probe=lambda f, e: INFEASIBLE).run()
    assert not res.ok and "ladder exhausted" in res.reason
    assert res.incidents[-1]["policy"] == "fail"


def test_supervisor_knobs_resolve_from_env(monkeypatch, tmp_path):
    monkeypatch.setenv("MPI4DL_SUPERVISE_MAX_ATTEMPTS", "2")
    monkeypatch.setenv("MPI4DL_SUPERVISE_BACKOFF", "0.5")
    monkeypatch.setenv("MPI4DL_SUPERVISE_BACKOFF_CAP", "4")
    sup = _sup(tmp_path, lambda f, e, a: LegOutcome(rc=0, result={}))
    assert sup.max_attempts == 2
    assert sup.backoff_base == 0.5 and sup.backoff_cap == 4.0


# ---------------------------------------------------------------------------
# The supervisor drill judge (fake launcher factory)
# ---------------------------------------------------------------------------


def _fake_factory(script):
    """``script(flags, env, attempt) -> LegOutcome`` shared by supervised
    legs and the control leg."""

    def factory(family, model, workdir):
        return script

    return factory


def test_supervisor_drill_judge_verified(tmp_path):
    def script(flags, env, attempt):
        if env.get("MPI4DL_FAULT"):
            return LegOutcome(rc=1, marker=_marker_for(OSError("blip")))
        return LegOutcome(rc=0, result={"loss": 1.0, "final_step": 4,
                                        "start_step": 2})

    sc = SupervisorScenario("s", fault="io_error@2", expect="exact",
                            expect_class="transient_io",
                            expect_policy="retry")
    v = run_supervisor_scenario(sc, str(tmp_path), log=lambda s: None,
                                launcher_factory=_fake_factory(script))
    assert v.passed and v.kind == "verified_recovery", v.details


def test_supervisor_drill_judge_misclassification_is_typed(tmp_path):
    def script(flags, env, attempt):
        if env.get("MPI4DL_FAULT"):
            return LegOutcome(rc=1, marker=_marker_for(OSError("blip")))
        return LegOutcome(rc=0, result={"loss": 1.0, "final_step": 4})

    sc = SupervisorScenario("s", fault="io_error@2", expect="exact",
                            expect_class="oom_step")
    v = run_supervisor_scenario(sc, str(tmp_path), log=lambda s: None,
                                launcher_factory=_fake_factory(script))
    assert not v.passed and v.kind == "misclassified"


def test_supervisor_drill_judge_flags_false_positive(tmp_path):
    calls = {"n": 0}

    def script(flags, env, attempt):
        calls["n"] += 1
        if calls["n"] == 1:  # an incident on a CLEAN scenario
            return LegOutcome(rc=1, marker=_marker_for(OSError("noise")))
        return LegOutcome(rc=0, result={"loss": 1.0, "final_step": 4})

    sc = SupervisorScenario("s", fault="", expect="clean")
    v = run_supervisor_scenario(sc, str(tmp_path), log=lambda s: None,
                                launcher_factory=_fake_factory(script))
    assert not v.passed and v.kind == "false_positive"


def test_supervisor_drill_judge_requires_elastic_restore_on_degrade(tmp_path):
    def script(flags, env, attempt):
        if env.get("MPI4DL_FAULT"):
            return LegOutcome(rc=1, marker=_marker_for(
                synthetic_oom("oom_compile", 0), phase="compile"))
        return LegOutcome(rc=0, result={"loss": 1.0, "final_step": 4,
                                        "elastic": False})

    sc = SupervisorScenario("s", fault="oom_compile@0", expect="close",
                            expect_class="oom_compile",
                            expect_policy="degrade", expect_delta=True,
                            overrides=dict(_PP_FLAGS))
    v = run_supervisor_scenario(sc, str(tmp_path), log=lambda s: None,
                                launcher_factory=_fake_factory(script))
    assert not v.passed and v.kind == "fresh_start"


def test_supervisor_scenarios_cover_the_acceptance_matrix():
    names = [s.name for s in supervisor_scenarios()]
    assert names == ["sup_clean", "sup_oom_degrade",
                     "sup_oom_step_degrade", "sup_transient_io"]
    by_name = {s.name: s for s in supervisor_scenarios()}
    assert by_name["sup_oom_degrade"].overrides["parts"] == 4
    assert by_name["sup_oom_degrade"].probe  # feasibility-probed
    assert not by_name["sup_transient_io"].expect_delta


# ---------------------------------------------------------------------------
# obs report renders the incident timeline
# ---------------------------------------------------------------------------


def test_report_renders_incident_timeline(tmp_path):
    from mpi4dl_tpu.obs.report import render_run

    runlog = RunLog(str(tmp_path / "s.jsonl"))
    runlog.write("supervisor", attempt=1, failure_class="oom_compile",
                 policy="degrade",
                 config_delta={"parts": {"from": 4, "to": 2}},
                 probe={"probe_peak_gb": 0.4, "budget_gb": 95.0})
    runlog.write("supervisor", attempt=2, failure_class="transient_io",
                 policy="retry", backoff_s=1.3)
    runlog.write("supervisor_summary", ok=True, attempts=3, incidents=2,
                 reason="")
    runlog.close()
    text = render_run(str(tmp_path / "s.jsonl"))
    assert "supervisor incidents: 2" in text
    assert "oom_compile -> degrade" in text
    assert "probed 0.4 GB <= 95.0 GB" in text
    assert "backoff 1.3 s" in text
    assert "completed after 3 leg(s)" in text


# ---------------------------------------------------------------------------
# End-to-end on the virtual mesh (slow lane: real subprocess legs)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_supervisor_oom_degrade_drill_end_to_end(tmp_path):
    """The acceptance drill: injected oom_compile at SP(2x2)xPP(2) parts=4
    is classified, the planner emits a feasibility-probed degraded config,
    the relaunched leg elastic-restores and finishes, and the final state
    matches a control run at the degraded geometry."""
    from mpi4dl_tpu.resilience import supervisor_scenarios

    sc = next(s for s in supervisor_scenarios()
              if s.name == "sup_oom_degrade")
    v = run_supervisor_scenario(sc, str(tmp_path), log=lambda s: None)
    assert v.passed and v.kind == "verified_recovery", v.details
    assert v.details["incidents"][0]["failure_class"] == "oom_compile"
    assert "probe_peak_gb" in v.details["incidents"][0]["probe"]
