"""Tests for the shard-safety analyzer (mpi4dl_tpu/analysis).

One known-violation fixture (positive) and a clean counterpart (negative)
per rule family, plus the repo gate: the shipped package must be
violation-free modulo the checked-in baseline — this is the test that finds
this bug class without a chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from mpi4dl_tpu.analysis import (
    RULES_BY_NAME,
    analyze_paths,
    apply_baseline,
    load_baseline,
)
from mpi4dl_tpu.analysis.__main__ import default_paths, repo_root


def _run(tmp_path, source, rule=None, filename="mpi4dl_tpu/fix.py"):
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    rules = [RULES_BY_NAME[rule]] if rule else None
    return analyze_paths([str(f)], root=str(tmp_path), rules=rules)


# ---------------------------------------------------------------------------
# (1) collective-axis
# ---------------------------------------------------------------------------


def test_collective_axis_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax
        def f(x):
            return lax.psum(x, "stagee")
        """,
        rule="collective-axis",
    )
    assert len(vs) == 1 and "stagee" in vs[0].message


def test_collective_axis_negative(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from mpi4dl_tpu.mesh import AXIS_STAGE
        def f(x):
            y = lax.psum(x, AXIS_STAGE)
            y = lax.pmean(y, ("data", "sph"))
            spec = P("data", None, ("sph", "spw"))
            return y, spec
        """,
        rule="collective-axis",
    )
    assert vs == []


def test_partition_spec_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax.sharding import PartitionSpec
        SPEC = PartitionSpec("datta", None)
        """,
        rule="collective-axis",
    )
    assert len(vs) == 1 and "datta" in vs[0].message


def test_collective_axis_compat_pcast(tmp_path):
    # pcast routed through the compat shim (how the whole package calls it)
    # must be axis-checked exactly like lax.pcast
    vs = _run(
        tmp_path,
        """
        from mpi4dl_tpu.compat import pcast
        def f(x):
            return pcast(x, ("bogus_axis",), to="varying")
        """,
        rule="collective-axis",
    )
    assert len(vs) == 1 and "bogus_axis" in vs[0].message


def test_ppermute_bijection_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax
        def f(x):
            return lax.ppermute(x, "stage", [(0, 1), (0, 2)])
        """,
        rule="collective-axis",
    )
    assert len(vs) == 1 and "bijection" in vs[0].message


def test_ppermute_bijection_negative(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax
        def f(x):
            y = lax.ppermute(x, "stage", [(0, 1), (1, 0)])
            # dynamic tables are not statically checkable -> no violation
            return lax.ppermute(y, "stage", [(i, i + 1) for i in range(3)])
        """,
        rule="collective-axis",
    )
    assert vs == []


# ---------------------------------------------------------------------------
# (2) tracer-leak
# ---------------------------------------------------------------------------

_LEAKY = """
    import time
    import jax
    import numpy as np

    def inner(x):
        t = time.time()
        return float(x.sum()) + t

    def step(x):
        return inner(x)

    jstep = jax.jit(step)
"""


def test_tracer_leak_positive(tmp_path):
    vs = _run(tmp_path, _LEAKY, rule="tracer-leak")
    msgs = "\n".join(v.message for v in vs)
    assert "time.time" in msgs and "float() host sync" in msgs


def test_tracer_leak_negative_unjitted(tmp_path):
    # identical body, but nothing roots it in a trace -> host syncs are fine
    vs = _run(
        tmp_path,
        """
        import time

        def inner(x):
            t = time.time()
            return float(x.sum()) + t

        def step(x):
            return inner(x)
        """,
        rule="tracer-leak",
    )
    assert vs == []


def test_tracer_leak_control_flow_and_pragma(tmp_path):
    vs = _run(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        def step(x):
            if jnp.any(x > 0):
                x = x + 1
            y = x.item()  # analysis: ok(tracer-leak)
            return x, y

        jstep = jax.jit(step)
        """,
        rule="tracer-leak",
    )
    # the `if` fires; the pragma'd .item() does not
    assert len(vs) == 1 and "`if` on a jnp value" in vs[0].message


def test_tracer_leak_same_named_nested_helpers(tmp_path):
    # two factories each defining a nested `tick` (this codebase's dominant
    # naming pattern): the defect in the FIRST factory's tick must be found —
    # name-keyed collection used to keep only the last definition.
    vs = _run(
        tmp_path,
        """
        from jax import lax

        def factory_a(xs):
            def tick(carry, x):
                return carry + float(x), None
            return lax.scan(tick, 0.0, xs)

        def factory_b(xs):
            def tick(carry, x):
                return carry + x, None
            return lax.scan(tick, 0.0, xs)
        """,
        rule="tracer-leak",
    )
    assert len(vs) == 1 and "float() host sync" in vs[0].message


def test_tracer_leak_shard_map_root(tmp_path):
    vs = _run(
        tmp_path,
        """
        import numpy as np
        from mpi4dl_tpu.compat import shard_map

        def body(x):
            return np.asarray(x)

        smapped = shard_map(body, mesh=None, in_specs=(), out_specs=())
        """,
        rule="tracer-leak",
    )
    assert len(vs) == 1 and "asarray" in vs[0].message


# ---------------------------------------------------------------------------
# (3) dtype-policy
# ---------------------------------------------------------------------------


def test_dtype_policy_positive_hot_path(tmp_path):
    vs = _run(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(n):
            return jnp.zeros((n, n)), jnp.arange(n)
        """,
        rule="dtype-policy",
        filename="mpi4dl_tpu/ops/fix.py",
    )
    assert len(vs) == 2


def test_dtype_policy_negative_hot_path(tmp_path):
    vs = _run(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(n, like):
            a = jnp.zeros((n, n), jnp.float32)
            b = jnp.arange(n, dtype=jnp.int32)
            c = jnp.zeros_like(like)  # inherits dtype: fine
            return a, b, c
        """,
        rule="dtype-policy",
        filename="mpi4dl_tpu/ops/fix.py",
    )
    assert vs == []


def test_dtype_policy_float64(tmp_path):
    vs = _run(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(x):
            return x.astype(jnp.float64)
        """,
        rule="dtype-policy",
    )
    assert len(vs) == 1 and "float64" in vs[0].message


def test_dtype_policy_param_init(tmp_path):
    vs = _run(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        class Layer:
            def init(self, key, shape):
                w = jax.random.normal(key, shape, dtype=jnp.bfloat16)
                b = jnp.zeros((shape[-1],), jnp.float32)
                return w, b
        """,
        rule="dtype-policy",
    )
    assert len(vs) == 1 and "bfloat16" in vs[0].message


# ---------------------------------------------------------------------------
# (4) env-hatch
# ---------------------------------------------------------------------------


def test_env_hatch_undeclared_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        import os
        FLAG = os.environ.get("MPI4DL_NOT_A_REAL_FLAG")
        """,
        rule="env-hatch",
    )
    assert len(vs) == 1 and "MPI4DL_NOT_A_REAL_FLAG" in vs[0].message


def test_env_hatch_declared_negative(tmp_path):
    vs = _run(
        tmp_path,
        """
        import os
        FLAG = os.environ.get("MPI4DL_REMAT_OPS") == "1"
        """,
        rule="env-hatch",
    )
    assert vs == []


def test_env_hatch_dead_flag(tmp_path):
    # a fixture registry whose hatch nothing reads -> dead flag; adding a
    # read clears it.  (The fixture config.py shadows the real registry via
    # the mpi4dl_tpu/config.py suffix match.)
    registry = """
        class Hatch:
            def __init__(self, name, default, doc, internal=False):
                self.name = name
        HATCHES = {h.name: h for h in (
            Hatch("MPI4DL_FIXTURE_FLAG", "0", "unused"),
        )}
    """
    (tmp_path / "mpi4dl_tpu").mkdir(parents=True, exist_ok=True)
    (tmp_path / "mpi4dl_tpu" / "config.py").write_text(
        textwrap.dedent(registry)
    )
    vs = analyze_paths(
        [str(tmp_path / "mpi4dl_tpu")],
        root=str(tmp_path),
        rules=[RULES_BY_NAME["env-hatch"]],
    )
    assert len(vs) == 1 and "never read" in vs[0].message

    (tmp_path / "mpi4dl_tpu" / "user.py").write_text(
        'import os\nX = os.environ.get("MPI4DL_FIXTURE_FLAG")\n'
    )
    vs = analyze_paths(
        [str(tmp_path / "mpi4dl_tpu")],
        root=str(tmp_path),
        rules=[RULES_BY_NAME["env-hatch"]],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# (5) retrace
# ---------------------------------------------------------------------------


def test_retrace_module_array_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        import jax.numpy as jnp
        TABLE = jnp.ones((4, 4))
        """,
        rule="retrace",
    )
    assert len(vs) == 1 and "module-level" in vs[0].message


def test_retrace_module_array_negative(tmp_path):
    vs = _run(
        tmp_path,
        """
        import numpy as np
        TABLE = np.ones((4, 4))  # numpy at module level is fine
        def f():
            import jax.numpy as jnp
            return jnp.ones((4, 4))  # inside a function is fine
        """,
        rule="retrace",
    )
    assert vs == []


def test_retrace_static_arg_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        import jax
        def f(x, cfg=[1, 2]):
            return x
        jf = jax.jit(f, static_argnums=1)
        """,
        rule="retrace",
    )
    assert len(vs) == 1 and "mutable literal" in vs[0].message


def test_retrace_static_arg_negative(tmp_path):
    vs = _run(
        tmp_path,
        """
        import jax
        def f(x, cfg=(1, 2)):
            return x
        jf = jax.jit(f, static_argnums=1)
        jg = jax.jit(f, static_argnames="cfg")
        """,
        rule="retrace",
    )
    assert vs == []


# ---------------------------------------------------------------------------
# repo gate + CLI
# ---------------------------------------------------------------------------


def test_repo_is_violation_free_modulo_baseline():
    root = repo_root()
    violations = analyze_paths(default_paths(root), root=root)
    baseline_path = os.path.join(root, "analysis_baseline.json")
    if os.path.exists(baseline_path):
        violations, _stale = apply_baseline(
            violations, load_baseline(baseline_path)
        )
    assert violations == [], "\n".join(v.render() for v in violations)


def test_readme_hatch_table_in_sync():
    """README claims its env-hatch table is generated from config.HATCHES —
    hold it to that: the exact hatches_markdown() output must appear."""
    from mpi4dl_tpu.config import hatches_markdown

    with open(os.path.join(repo_root(), "README.md"), encoding="utf-8") as fh:
        readme = fh.read()
    assert hatches_markdown() in readme, (
        "README env-hatch table is out of sync with config.HATCHES; "
        "regenerate it with `python -m mpi4dl_tpu.analysis --hatch-docs`"
    )


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        'from jax import lax\n\ndef f(x):\n    return lax.psum(x, "nope")\n'
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "mpi4dl_tpu.analysis", "--json", str(bad)],
        capture_output=True, text=True, env=env, cwd=repo_root(),
    )
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["violations"][0]["rule"] == "collective-axis"

    r = subprocess.run(
        [sys.executable, "-m", "mpi4dl_tpu.analysis", "--list-rules"],
        capture_output=True, text=True, env=env, cwd=repo_root(),
    )
    assert r.returncode == 0
    for name in ("collective-axis", "tracer-leak", "dtype-policy",
                 "env-hatch", "retrace", "print-call", "swallow-except",
                 "thread-shared-state"):
        assert name in r.stdout


# ---------------------------------------------------------------------------
# (7) print-call
# ---------------------------------------------------------------------------


def test_print_call_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        def f():
            print("library chatter")
        """,
        rule="print-call",
    )
    assert len(vs) == 1 and "print()" in vs[0].message


def test_print_call_benchmarks_exempt(tmp_path):
    vs = _run(
        tmp_path,
        """
        def f():
            print("benchmark output line")
        """,
        rule="print-call",
        filename="benchmarks/foo.py",
    )
    assert vs == []


def test_print_call_main_cli_exempt(tmp_path):
    vs = _run(
        tmp_path,
        """
        def main():
            print("the CLI's product is stdout")
        """,
        rule="print-call",
        filename="mpi4dl_tpu/obs/__main__.py",
    )
    assert vs == []


def test_print_call_pragma_suppresses(tmp_path):
    vs = _run(
        tmp_path,
        """
        def f():
            print("accepted")  # analysis: ok(print-call)
        """,
        rule="print-call",
    )
    assert vs == []


def test_print_call_shadowed_print_not_flagged(tmp_path):
    vs = _run(
        tmp_path,
        """
        from rich import print

        def f():
            print("not the builtin")
        """,
        rule="print-call",
    )
    assert vs == []


# ---------------------------------------------------------------------------
# (8) swallow-except
# ---------------------------------------------------------------------------


def test_swallow_except_bare_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        def f():
            try:
                risky()
            except:
                recover()
        """,
        rule="swallow-except",
    )
    assert len(vs) == 1 and "bare" in vs[0].message


def test_swallow_except_exception_pass_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        def f():
            try:
                risky()
            except Exception:
                pass
            try:
                risky()
            except (ValueError, BaseException) as e:
                ...
        """,
        rule="swallow-except",
    )
    assert len(vs) == 2


def test_swallow_except_handled_negative(tmp_path):
    """Narrow types, logged/handled broad catches, and re-raises are all
    deliberate — only SILENT broad swallows are flagged."""
    vs = _run(
        tmp_path,
        """
        import logging

        def f():
            try:
                risky()
            except OSError:
                pass  # narrow type: an explicit decision
            try:
                risky()
            except Exception as e:
                logging.warning("recovering: %s", e)
            try:
                risky()
            except Exception:
                raise RuntimeError("context")
            try:
                risky()
            except Exception:
                return None
        """,
        rule="swallow-except",
    )
    assert vs == []


def test_swallow_except_pragma_suppresses(tmp_path):
    vs = _run(
        tmp_path,
        """
        def f():
            try:
                risky()
            except Exception:  # analysis: ok(swallow-except)
                pass
        """,
        rule="swallow-except",
    )
    assert vs == []


def test_swallow_except_tests_and_benchmarks_exempt(tmp_path):
    vs = _run(
        tmp_path,
        """
        def f():
            try:
                risky()
            except:
                pass
        """,
        rule="swallow-except",
        filename="benchmarks/foo.py",
    )
    assert vs == []


# ---------------------------------------------------------------------------
# (9) thread-shared-state
# ---------------------------------------------------------------------------


def test_thread_state_method_target_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        import threading

        class Collector:
            def __init__(self):
                self.results = []
                self.done = False
                self._t = threading.Thread(target=self._work)

            def _work(self):
                self.results.append(1)
                self.done = True
        """,
        rule="thread-shared-state",
    )
    msgs = "\n".join(v.message for v in vs)
    assert len(vs) == 2
    assert "self.results" in msgs and "self.done" in msgs


def test_thread_state_lock_present_negative(tmp_path):
    vs = _run(
        tmp_path,
        """
        import threading

        class Collector:
            def __init__(self):
                self.results = []
                self._lock = threading.Lock()
                self._t = threading.Thread(target=self._work)

            def _work(self):
                with self._lock:
                    self.results.append(1)
        """,
        rule="thread-shared-state",
    )
    assert vs == []


def test_thread_state_subclass_run_global_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        import threading

        COUNTER = 0

        class Worker(threading.Thread):
            def run(self):
                global COUNTER
                COUNTER += 1
        """,
        rule="thread-shared-state",
    )
    assert len(vs) == 1 and "COUNTER" in vs[0].message


def test_thread_state_module_container_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        import threading

        RESULTS = []

        def work():
            RESULTS.append(1)

        t = threading.Thread(target=work)
        """,
        rule="thread-shared-state",
    )
    assert len(vs) == 1 and "RESULTS" in vs[0].message


def test_thread_state_queue_in_closure_scope_negative(tmp_path):
    # the prefetch-producer pattern (mpi4dl_tpu.data.prefetch_batches):
    # a closure target whose enclosing function owns a Queue/Event
    vs = _run(
        tmp_path,
        """
        import queue
        import threading

        def fetch_all(items):
            q = queue.Queue()

            def producer():
                for i in items:
                    q.put(i)

            t = threading.Thread(target=producer)
            t.start()
            return q
        """,
        rule="thread-shared-state",
    )
    assert vs == []


def test_thread_state_pragma_suppresses(tmp_path):
    vs = _run(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self.x = 0
                self._t = threading.Thread(target=self._work)

            def _work(self):  # analysis: ok(thread-shared-state)
                self.x = 1
        """,
        rule="thread-shared-state",
    )
    assert vs == []


def test_thread_state_tests_exempt(tmp_path):
    vs = _run(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self.x = 0
                threading.Thread(target=self._work).start()

            def _work(self):
                self.x = 1
        """,
        rule="thread-shared-state",
        filename="tests/foo.py",
    )
    assert vs == []


# ---------------------------------------------------------------------------
# Stale-baseline hygiene (--prune-baseline) + --changed-only
# ---------------------------------------------------------------------------


def _write_violating_file(tmp_path):
    f = tmp_path / "bad.py"
    f.write_text(
        'from jax import lax\n\ndef f(x):\n    return lax.psum(x, "nope")\n'
    )
    return f


def test_stale_baseline_reported_and_pruned(tmp_path, capsys):
    from mpi4dl_tpu.analysis.__main__ import main

    f = _write_violating_file(tmp_path)
    live = {
        "rule": "collective-axis",
        "path": os.path.relpath(str(f), repo_root()).replace(os.sep, "/"),
        "message": "psum: axis 'nope' is not a mesh axis "
                   "('data', 'stage', 'sph', 'spw')",
    }
    stale = {"rule": "collective-axis", "path": "gone/file.py",
             "message": "psum: axis 'old' is not a mesh axis ..."}
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([live, stale]))

    # without --prune-baseline: warning surfaced, file untouched
    rc = main([str(f), "--baseline", str(bl)])
    err = capsys.readouterr().err
    assert rc == 0  # the live violation is baselined away
    assert "warning: stale baseline entry" in err
    assert "--prune-baseline" in err
    assert json.loads(bl.read_text()) == [live, stale]

    # with --prune-baseline: file rewritten keeping only the live entry
    rc = main([str(f), "--baseline", str(bl), "--prune-baseline"])
    err = capsys.readouterr().err
    assert rc == 0
    assert "pruned 1 stale baseline entry" in err
    assert json.loads(bl.read_text()) == [live]


def test_prune_baseline_requires_baseline(capsys):
    from mpi4dl_tpu.analysis.__main__ import main

    assert main(["--prune-baseline"]) == 2
    assert "--prune-baseline requires --baseline" in capsys.readouterr().err


def test_changed_only_rejects_explicit_paths(tmp_path, capsys):
    from mpi4dl_tpu.analysis.__main__ import main

    assert main(["--changed-only", str(tmp_path)]) == 2
    assert "mutually exclusive" in capsys.readouterr().err


def test_changed_only_rejects_prune_baseline(tmp_path, capsys):
    # a partial scan would judge nearly every baseline entry stale and
    # destructively prune it
    from mpi4dl_tpu.analysis.__main__ import main

    bl = tmp_path / "baseline.json"
    bl.write_text("[]")
    assert main(["--changed-only", "--baseline", str(bl),
                 "--prune-baseline"]) == 2
    assert "whole-tree scan" in capsys.readouterr().err


def test_thread_state_target_defined_after_call_in_function(tmp_path):
    """A module-level target defined BELOW the function that spawns the
    thread is fully legal Python and must still be analyzed."""
    vs = _run(
        tmp_path,
        """
        import threading

        def start():
            t = threading.Thread(target=work)
            t.start()

        RESULTS = []

        def work():
            RESULTS.append(1)
        """,
        rule="thread-shared-state",
    )
    assert len(vs) == 1 and "RESULTS" in vs[0].message


def test_thread_state_two_spawn_sites_report_once(tmp_path):
    vs = _run(
        tmp_path,
        """
        import threading

        RESULTS = []

        def work():
            RESULTS.append(1)

        t1 = threading.Thread(target=work)
        t2 = threading.Thread(target=work)
        """,
        rule="thread-shared-state",
    )
    assert len(vs) == 1


def test_changed_only_scope_filter():
    from mpi4dl_tpu.analysis.__main__ import scope_filter

    scope = ["/r/mpi4dl_tpu", "/r/tests", "/r/bench.py"]
    assert scope_filter(
        ["/r/mpi4dl_tpu/ops/x.py", "/r/native/helper.py", "/r/bench.py",
         "/r/bench.py.bak", "/r/tests/test_x.py"],
        scope,
    ) == ["/r/mpi4dl_tpu/ops/x.py", "/r/bench.py", "/r/tests/test_x.py"]


def test_thread_state_bare_annotation_not_a_mutation(tmp_path):
    vs = _run(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                threading.Thread(target=self._work).start()

            def _work(self):
                self.buf: list  # declaration only, no store
        """,
        rule="thread-shared-state",
    )
    assert vs == []


def test_thread_state_method_does_not_shadow_module_target(tmp_path):
    """A same-named METHOD elsewhere in the file must not shadow the real
    module-level Thread target (methods are not name-visible)."""
    vs = _run(
        tmp_path,
        """
        import threading

        class Manager:
            def work(self):
                self.jobs = []

        JOBS = []

        def work():
            JOBS.append(1)

        t = threading.Thread(target=work)
        """,
        rule="thread-shared-state",
    )
    # the module-level target's JOBS mutation fires; the method's self.jobs
    # (not a thread body) does not
    assert len(vs) == 1 and "JOBS" in vs[0].message


def test_changed_python_files_sees_worktree_and_untracked(tmp_path):
    from mpi4dl_tpu.analysis.__main__ import changed_python_files

    env = dict(os.environ, GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")

    def git(*args):
        subprocess.run(["git", "-C", str(tmp_path), *args], check=True,
                       capture_output=True, env=env)

    git("init", "-q")
    (tmp_path / "clean.py").write_text("A = 1\n")
    (tmp_path / "tracked.py").write_text("B = 1\n")
    git("add", "clean.py", "tracked.py")
    git("commit", "-qm", "seed")
    (tmp_path / "tracked.py").write_text("B = 2\n")  # worktree change
    (tmp_path / "new.py").write_text("C = 3\n")  # untracked
    (tmp_path / "notes.txt").write_text("not python\n")

    changed = changed_python_files(str(tmp_path))
    names = sorted(os.path.basename(p) for p in changed)
    assert names == ["new.py", "tracked.py"]


def test_changed_python_files_no_git(tmp_path):
    from mpi4dl_tpu.analysis.__main__ import changed_python_files

    # a directory that is not a git repo -> None (caller falls back)
    assert changed_python_files(str(tmp_path)) is None


def test_shared_node_index_matches_full_walk(tmp_path):
    """SourceFile.nodes (the one-pass shared index every rule iterates)
    must see exactly the nodes a fresh ast.walk sees."""
    import ast

    from mpi4dl_tpu.analysis.core import SourceFile

    text = (tmp_path / "m.py")
    text.write_text(
        "import os\n\nclass C:\n    def f(self):\n        return "
        "os.environ.get('X')\n\nY = [c for c in 'ab']\n"
    )
    src = SourceFile(str(text), "m.py", text.read_text())
    walked = [n for n in ast.walk(src.tree) if isinstance(n, ast.Call)]
    assert list(src.nodes(ast.Call)) == walked


# ---------------------------------------------------------------------------
# (10) unscoped-collective
# ---------------------------------------------------------------------------


def test_unscoped_collective_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax

        def handoff(y):
            return lax.ppermute(y, "stage", [(0, 1)])
        """,
        rule="unscoped-collective",
        filename="mpi4dl_tpu/parallel/fix.py",
    )
    assert len(vs) == 1 and "ppermute" in vs[0].message


def test_unscoped_collective_scoped_negative(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax
        from mpi4dl_tpu.obs.scopes import scope

        def handoff(y):
            with scope("stage_handoff"):
                return lax.ppermute(y, "stage", [(0, 1)])
        """,
        rule="unscoped-collective",
        filename="mpi4dl_tpu/parallel/fix.py",
    )
    assert vs == []


def test_unscoped_collective_named_scope_negative(tmp_path):
    vs = _run(
        tmp_path,
        """
        import jax
        from jax import lax

        def reduce(x):
            with jax.named_scope("loss_reduce"):
                return lax.psum(x, "stage")
        """,
        rule="unscoped-collective",
        filename="mpi4dl_tpu/ops/fix.py",
    )
    assert vs == []


def test_unscoped_collective_pragma_suppresses(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax

        def helper(y):
            # caller owns the scope (halo_exchange_*)
            return lax.ppermute(y, "spw", [(0, 1)])  # analysis: ok(unscoped-collective)
        """,
        rule="unscoped-collective",
        filename="mpi4dl_tpu/ops/fix.py",
    )
    assert vs == []


def test_unscoped_collective_outside_comm_layers_exempt(tmp_path):
    """Only parallel/ and ops/ are in scope — train.py, models, tests and
    benchmarks may issue collectives without scopes (their callers are the
    engines, which own the scope vocabulary)."""
    vs = _run(
        tmp_path,
        """
        from jax import lax

        def f(x):
            return lax.pmean(x, "data")
        """,
        rule="unscoped-collective",
        filename="mpi4dl_tpu/train.py",
    )
    assert vs == []


def test_unscoped_collective_local_helper_not_flagged(tmp_path):
    """A local function named like a collective is its own call site, not a
    jax.lax collective."""
    vs = _run(
        tmp_path,
        """
        def psum(x, axis):
            return x

        def f(x):
            return psum(x, "stage")
        """,
        rule="unscoped-collective",
        filename="mpi4dl_tpu/parallel/fix.py",
    )
    assert vs == []


# ---------------------------------------------------------------------------
# (11) unquantized-collective
# ---------------------------------------------------------------------------


def test_unquantized_collective_positive(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax
        from mpi4dl_tpu.obs.scopes import scope

        def junction(x):
            with scope("junction_gather"):
                return lax.all_gather(x, "spw", axis=1, tiled=True)
        """,
        rule="unquantized-collective",
        filename="mpi4dl_tpu/parallel/fix.py",
    )
    assert len(vs) == 1 and "junction_gather" in vs[0].message


def test_unquantized_collective_quant_aware_negative(tmp_path):
    """The raw collective is fine as the policy-off branch of a
    quant-aware function (a `quant` parameter / quantized_* call)."""
    vs = _run(
        tmp_path,
        """
        from jax import lax
        from mpi4dl_tpu.obs.scopes import scope
        from mpi4dl_tpu.quant.collectives import quantized_all_gather

        def junction(x, quant=None):
            with scope("junction_gather"):
                if quant is not None:
                    return quantized_all_gather(x, "spw", 1, "int8", 256)
                return lax.all_gather(x, "spw", axis=1, tiled=True)
        """,
        rule="unquantized-collective",
        filename="mpi4dl_tpu/parallel/fix.py",
    )
    assert vs == []


def test_unquantized_collective_cold_scope_negative(tmp_path):
    """loss_reduce is not on the hot list (scalar payloads stay exact)."""
    vs = _run(
        tmp_path,
        """
        from jax import lax
        from mpi4dl_tpu.obs.scopes import scope

        def reduce_loss(x):
            with scope("loss_reduce"):
                return lax.psum(x, "stage")
        """,
        rule="unquantized-collective",
        filename="mpi4dl_tpu/parallel/fix.py",
    )
    assert vs == []


def test_unquantized_collective_outside_parallel_negative(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax
        from mpi4dl_tpu.obs.scopes import scope

        def junction(x):
            with scope("junction_gather"):
                return lax.all_gather(x, "spw", axis=1, tiled=True)
        """,
        rule="unquantized-collective",
        filename="mpi4dl_tpu/ops/fix.py",
    )
    assert vs == []


def test_unquantized_collective_fstring_scope_positive(tmp_path):
    """Hot-class tokens in f-string scope names (respatial_l{i}) match."""
    vs = _run(
        tmp_path,
        """
        from jax import lax
        from mpi4dl_tpu.obs.scopes import scope

        def reshard(x, li):
            with scope(f"respatial_l{li}"):
                return lax.all_gather(x, "spw", axis=1, tiled=True)
        """,
        rule="unquantized-collective",
        filename="mpi4dl_tpu/parallel/fix.py",
    )
    assert len(vs) == 1


def test_unquantized_collective_pragma_suppresses(tmp_path):
    vs = _run(
        tmp_path,
        """
        from jax import lax
        from mpi4dl_tpu.obs.scopes import scope

        def junction(x):
            with scope("junction_gather"):
                return lax.all_gather(x, "spw", axis=1, tiled=True)  # analysis: ok(unquantized-collective) — exact by design
        """,
        rule="unquantized-collective",
        filename="mpi4dl_tpu/parallel/fix.py",
    )
    assert vs == []


def test_unquantized_collective_per_block_granularity(tmp_path):
    """A quant-aware FUNCTION does not grandfather a second hot block
    without its own quant path (the regression the rule exists for)."""
    vs = _run(
        tmp_path,
        """
        from jax import lax
        from mpi4dl_tpu.obs.scopes import scope
        from mpi4dl_tpu.quant.collectives import quantized_all_gather

        def junction(x, quant=None):
            with scope("junction_gather"):
                if quant is not None:
                    x = quantized_all_gather(x, "spw", 1, "int8", 256)
                else:
                    x = lax.all_gather(x, "spw", axis=1, tiled=True)
            with scope("stage_lineup"):
                return lax.all_gather(x, "stage", axis=0, tiled=True)
        """,
        rule="unquantized-collective",
        filename="mpi4dl_tpu/parallel/fix.py",
    )
    assert len(vs) == 1 and "stage_lineup" in vs[0].message


# ---------------------------------------------------------------------------
# Stale-pragma hygiene (--prune-pragmas)
# ---------------------------------------------------------------------------


def test_stale_pragma_detected_used_pragma_kept(tmp_path):
    from mpi4dl_tpu.analysis import RULE_TABLE, build_project, run_rules
    from mpi4dl_tpu.analysis.core import stale_pragmas

    f = tmp_path / "mpi4dl_tpu" / "fix.py"
    f.parent.mkdir(parents=True)
    f.write_text(textwrap.dedent(
        """
        from jax import lax

        def g(x):
            return lax.psum(x, "nope")  # analysis: ok(collective-axis)

        def h(x):
            return x + 1  # analysis: ok(collective-axis)
        """
    ))
    project = build_project([str(f)], root=str(tmp_path))
    used = set()
    vs = run_rules(project, RULE_TABLE, used_pragmas=used)
    # the first pragma suppressed the real violation; nothing else fires
    assert [v for v in vs if v.rule == "collective-axis"] == []
    stale = stale_pragmas(project, used)
    assert len(stale) == 1, stale
    assert stale[0].rule == "stale-pragma"
    assert stale[0].line == 8  # the h() pragma suppressed nothing
    assert "remove it" in stale[0].message


def test_prune_pragmas_rejects_partial_scans(tmp_path, capsys):
    from mpi4dl_tpu.analysis.__main__ import main

    assert main(["--prune-pragmas", "--changed-only"]) == 2
    assert "whole-tree all-rules scan" in capsys.readouterr().err
    assert main(["--prune-pragmas", "--rule", "collective-axis"]) == 2
    capsys.readouterr()
    assert main(["--prune-pragmas", str(tmp_path)]) == 2


# ---------------------------------------------------------------------------
# SARIF output (--sarif)
# ---------------------------------------------------------------------------


def test_sarif_output_for_violations(tmp_path, capsys):
    from mpi4dl_tpu.analysis.__main__ import main

    f = _write_violating_file(tmp_path)
    sarif = tmp_path / "analysis.sarif"
    rc = main([str(f), "--sarif", str(sarif)])
    capsys.readouterr()
    assert rc == 1
    log = json.loads(sarif.read_text())
    assert log["version"] == "2.1.0"
    run = log["runs"][0]
    results = run["results"]
    assert len(results) == 1
    r = results[0]
    assert r["ruleId"] == "collective-axis"
    loc = r["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["region"]["startLine"] == 4
    # the driver carries a rules entry for every referenced ruleId
    rules = run["tool"]["driver"]["rules"]
    assert rules[r["ruleIndex"]]["id"] == "collective-axis"


# ---------------------------------------------------------------------------
# --changed-only cross-file widening (ground-truth edits)
# ---------------------------------------------------------------------------


def _tmp_pkg(tmp_path):
    pkg = tmp_path / "mpi4dl_tpu"
    pkg.mkdir(parents=True, exist_ok=True)
    return pkg


def test_changed_only_widens_to_ground_truth_dependents(
    tmp_path, monkeypatch, capsys
):
    """Editing a cross-file ground-truth module (mesh.py / config.py) must
    widen --changed-only to a full scan: the evidence for a violation in an
    UNCHANGED module lives in the changed file."""
    import mpi4dl_tpu.analysis.__main__ as amain

    pkg = _tmp_pkg(tmp_path)
    mesh = pkg / "mesh.py"
    mesh.write_text('AXIS_DATA = "data"\n')
    dep = pkg / "dependent.py"
    dep.write_text(
        'from jax import lax\n\ndef f(x):\n    return lax.psum(x, "nope")\n'
    )
    monkeypatch.setattr(amain, "repo_root", lambda: str(tmp_path))
    monkeypatch.setattr(
        amain, "changed_python_files", lambda root: [str(mesh)]
    )
    rc = amain.main(["--changed-only"])
    captured = capsys.readouterr()
    assert "cross-file ground truth changed" in captured.err
    assert "widening to a full scan" in captured.err
    # the violation lives in dependent.py, which git did NOT report changed
    assert rc == 1
    assert "dependent.py" in captured.out


def test_changed_only_stays_file_local_without_ground_truth(
    tmp_path, monkeypatch, capsys
):
    import mpi4dl_tpu.analysis.__main__ as amain

    pkg = _tmp_pkg(tmp_path)
    clean = pkg / "clean.py"
    clean.write_text("def f(x):\n    return x\n")
    dep = pkg / "dependent.py"
    dep.write_text(
        'from jax import lax\n\ndef f(x):\n    return lax.psum(x, "nope")\n'
    )
    monkeypatch.setattr(amain, "repo_root", lambda: str(tmp_path))
    monkeypatch.setattr(
        amain, "changed_python_files", lambda root: [str(clean)]
    )
    rc = amain.main(["--changed-only"])
    captured = capsys.readouterr()
    assert "widening" not in captured.err
    assert rc == 0  # file-local view by design when no ground truth moved


def test_cross_file_ground_truth_matcher():
    from mpi4dl_tpu.analysis.__main__ import cross_file_ground_truth

    assert cross_file_ground_truth(
        ["/abs/repo/mpi4dl_tpu/mesh.py", "/abs/repo/mpi4dl_tpu/ops/halo.py"]
    ) == ["mpi4dl_tpu/mesh.py"]
    assert cross_file_ground_truth(
        ["/r/mpi4dl_tpu/config.py", "/r/mpi4dl_tpu/mesh.py"]
    ) == ["mpi4dl_tpu/config.py", "mpi4dl_tpu/mesh.py"]
    assert cross_file_ground_truth(["/r/notmpi4dl_tpu/mesh.py"]) == []
