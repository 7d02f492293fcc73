"""Span recorder: where the host's time goes, recorded where the work happens.

One recorder a process (:func:`recorder`), on by default, bounded, in memory.
A span is a name, a start and an end in ``time.perf_counter_ns``, its own id,
the id of the span that was open on its thread when it opened (its parent),
the thread, and ``gstep``: the identifier that the spans of one optimizer step
share.  Closed spans go to a bounded ring; what has to outlive the ring (how
often jax built each program, which path each convolution was handed to XLA
by) is counted beside it.

Every span is also a ``jax.profiler.TraceAnnotation`` (``step`` a
``StepTraceAnnotation`` with its ``step_num``): a flag test while no trace
runs, and while one does the span lands in the host plane of the same
``.xplane.pb`` as the device's ``XLA Modules`` line, on the profiler's clock.

jax's own trace / lower / compile-or-load events arrive through
``jax.monitoring``, which delivers them when the work is over and stamps them
with ``time.time()``; the recorder places each on its own clock by the
event's duration, ending at the listener's call.  Being after the fact they
are not annotations (jax's own TraceMes stand for them in a profile).

``MPI4DL_NO_SCOPES=1`` (which turns off ``obs.scope``) turns the recorder off
too: :meth:`Recorder.span` then returns a ``nullcontext`` and keeps nothing
but the name of the span each thread opened last (:meth:`Recorder.at`): the
crash marker's ``phase`` is a resilience matter (``supervisor.
classify_failure`` reads it) and does not hang on an observability switch.

The vocabulary is the one table below; docs/observability.md says who opens
each span and with what attributes.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import Any, Callable, ContextManager, Dict, List, Optional, Tuple

from mpi4dl_tpu.obs.scopes import scopes_enabled

# name -> the crash marker's / flight recorder's phase word once that span is
# the last one the loop's thread opened (``step`` reads ``compile`` on the
# process's first step; see :func:`phase_word`).
# ``supervisor.classify_failure`` reads these words.
VOCABULARY: Dict[str, str] = {
    "setup/build_train": "init",
    "setup/build_model": "init",
    "setup/init_params": "init",
    "setup/make_step": "init",
    "setup/place_state": "init",
    "jax/trace": "init",
    "jax/lower": "init",
    "jax/compile_or_load": "init",
    "run": "init",
    "step": "loop",
    "batch_wait": "fetch",
    "make_batch": "fetch",
    "step_call": "step",
    "loss_wait": "step",
    "guard": "loop",
    "record": "loop",
    "save": "save",
}
SETUP_PREFIXES = ("setup/", "jax/")

# jax.monitoring event -> span name
JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    "/jax/core/compile/backend_compile_duration": "jax/compile_or_load",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# How ``layers.Conv2d.apply`` handed a convolution to XLA: W-folded
# (ops/wfold_conv.py), H-striped (ops/hstripe_conv.py), the phase-decomposed
# strided form (ops/conv_phase.py), as it stands, or, where it is pointwise
# (1x1, stride 1, no padding, one group) with a width that is no multiple of
# the 128 lanes, as a matrix product over channels.
CONV_PATHS = ("wfold", "hstripe", "phase", "xla", "dot")
# Which kernel a site of another kind was traced with: attention (the Pallas
# block kernel of ops/pallas_attention.py or the einsum form; of
# models/lfm2.Attention, and, as ``latent_*``, of
# models/deepseek_v3.LatentAttention, whose keys are wider than its values
# and whose Pallas forward is ops/pallas_latent_attention.py's),
# the routed experts' grouped product (ops/moe.py: ``lax.ragged_dot``, the
# one path) and the shared expert beside them (models/deepseek_v3.py: a
# SwiGLU of dense products, the one path); the state-space recurrence of a
# Mamba-2 mixer (models/granitemoehybrid.py: ``ops/ssd.ssd_chunked``, XLA's
# products over chunks, the one path) and a head that multiplies by the
# embedding's table (models/lfm2.head_cell, ``tied``); the sparse attention
# of models/keye_vl2.py (``sparse_*``: its Pallas kernels or the einsum form
# over a dense mask) and its indexer (ops/sparse_indexer.py: the Pallas
# kernels or XLA's products); and on which form of the activation a
# BatchNorm took its sums and applied its affine: ``[N, H, W/p, p·C]`` inside
# a folded run (``layers.run_fold``), or ``[N, H, W, C]``.  A kind whose
# paths are None takes any path: ``sparse_plane_heads``, how many query heads
# of models/keye_vl2.py's Pallas sparse attention share one decoded plane of
# the selection in a grid step of ``sparse_flash_fwd`` (the key-value group),
# and ``sparse_bwd_plane_heads``, the same of ``sparse_flash_bwd``;
# ``ut_loop``, how many cells of models/ouro.py apply a held layer (its
# passes, ``total_ut_steps``); ``flash_whole_tile_pct``, the share in percent
# of the live tiles of ``block_flash_fwd`` that fold whole (no mask, no
# guard) in a call of models/lfm2.Attention's Pallas path.
SITE_PATHS = {
    "conv": CONV_PATHS,
    "norm": ("folded", "plain"),
    "attention": ("block_flash", "einsum", "latent_block_flash",
                  "latent_einsum", "sparse_block_flash", "sparse_einsum"),
    "experts": ("ragged_dot",),
    "shared_expert": ("swiglu",),
    "ssm_scan": ("chunked",),
    "tied_head": ("table_transposed",),
    "sparse_indexer": ("pallas", "xla"),
    "sparse_plane_heads": None,
    "sparse_bwd_plane_heads": None,
    "ut_loop": None,
    "flash_whole_tile_pct": None,
}

# At least 4,000 steps of the loop's spans (nine a step with the loader's).
DEFAULT_CAPACITY = 65_536


def phase_word(name: Optional[str], first_step: bool = False) -> str:
    """The crash marker's phase for the span ``name`` (None: none opened)."""
    word = VOCABULARY.get(name, "loop") if name is not None else "init"
    return "compile" if word == "step" and first_step else word


class Span:
    """One interval.  A context manager: entering stamps the start and makes
    it the open span of its thread, leaving stamps the end and files it."""

    __slots__ = ("name", "id", "parent", "thread", "gstep", "start_ns",
                 "end_ns", "attrs", "kids_ns", "_rec", "_note")

    def __init__(self, rec: "Recorder", name: str, gstep: Optional[int],
                 attrs: Dict[str, Any]):
        self._rec = rec
        self._note = None
        self.name = name
        self.id = 0
        self.parent: Optional[int] = None
        self.thread = 0
        self.gstep = gstep
        self.start_ns = 0
        self.end_ns: Optional[int] = None
        self.attrs = attrs
        # direct children by name, in ns (summed where a name repeats)
        self.kids_ns: Dict[str, int] = {}

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)

    @property
    def ms(self) -> float:
        return ((self.end_ns or self._rec.clock()) - self.start_ns) / 1e6

    @property
    def kids_ms(self) -> Dict[str, float]:
        return {name: ns / 1e6 for name, ns in self.kids_ns.items()}

    @property
    def self_ms(self) -> float:
        """Duration less the part its direct children cover."""
        return self.ms - sum(self.kids_ns.values()) / 1e6

    def __enter__(self) -> "Span":
        self._rec._open_span(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._rec._close_span(self)


class Recorder:
    """Bounded ring of closed spans, the open spans of every thread, and the
    count of jax's builds by program.  ``clock`` is injectable for tests;
    ``annotate=False`` leaves the profiler out (a recorder on a fake clock has
    nothing to say to it)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 clock: Callable[[], int] = time.perf_counter_ns,
                 enabled: Optional[bool] = None, annotate: bool = True):
        self.enabled = scopes_enabled() if enabled is None else bool(enabled)
        self.clock = clock
        self.annotate = annotate
        self._ids = itertools.count(1)
        self._closed: deque = deque(maxlen=max(1, int(capacity)))
        # (kind, program) -> events: outlives the ring
        self._programs: Dict[Tuple[str, str], int] = {}
        # (kind, id of the layer, path) -> the layer, held so that its id
        # stays its own: a site counts once however often jax traces it
        self._sites: Dict[Tuple[str, int, str], Any] = {}
        # thread id -> stack of open spans
        self._open: Dict[int, List[Span]] = {}
        # thread id -> name of the span it opened last, open or closed, kept
        # with the recorder off too; read from other threads (the watchdog
        # asks where the loop's thread is).
        self._at: Dict[int, str] = {}
        self._cache_hits: Dict[int, int] = {}
        # thread id -> jax events now under way on it: every jnp function
        # traced inside a jitted one, or inside a lowering rule, fires its
        # own trace event (a thousand in a one-block ResNet), all inside
        # the outer event's interval; only the outermost becomes a span.
        self._jax_depth: Dict[int, int] = {}
        self._lock = threading.Lock()  # _programs: read-modify-write
        self._listening = False

    # -- spans -------------------------------------------------------------

    def span(self, name: str, *, gstep: Optional[int] = None,
             **attrs: Any) -> ContextManager[Optional[Span]]:
        """A span to enter, at once (where the thread is, is noted here:
        with the recorder off nothing is entered).  Without ``gstep`` it
        takes its parent's."""
        self._at[threading.get_ident()] = name
        if not self.enabled:
            return contextlib.nullcontext()
        return Span(self, name, gstep, attrs)

    def _open_span(self, span: Span) -> None:
        tid = threading.get_ident()
        stack = self._open.get(tid)
        if stack is None:
            stack = self._open[tid] = []
        span.id = next(self._ids)
        span.thread = tid
        if stack:
            span.parent = stack[-1].id
            if span.gstep is None:
                span.gstep = stack[-1].gstep
        stack.append(span)
        if self.annotate:
            span._note = _annotation(span)
            span._note.__enter__()
        span.start_ns = self.clock()

    def _close_span(self, span: Span) -> None:
        span.end_ns = self.clock()
        if span._note is not None:
            span._note.__exit__(None, None, None)
            span._note = None
        stack = self._open.get(span.thread)
        if stack and stack[-1] is span:
            stack.pop()
        self._file(span, stack)

    def _file(self, span: Span, stack: Optional[List[Span]]) -> None:
        """A closed span into the ring and into its parent's children."""
        if stack:
            kids = stack[-1].kids_ns
            kids[span.name] = (kids.get(span.name, 0)
                               + span.end_ns - span.start_ns)
        elif stack is not None:
            del self._open[span.thread]  # loader threads come and go
        self._closed.append(span)

    def annotate_open(self, **attrs: Any) -> None:
        """Attributes for the innermost open span of the calling thread (a
        layer below says something about the span a layer above opened)."""
        stack = self._open.get(threading.get_ident())
        if stack:
            stack[-1].attrs.update(attrs)

    def at(self, thread: Optional[int] = None) -> Optional[str]:
        """Where ``thread`` (default: the caller's) is: the name of the span
        it opened last, whether or not that has closed.  Sticky, as the
        loop's ``phase`` word was: an ``except`` clause asks after the
        ``with`` blocks have unwound, the preemption dump after its save."""
        return self._at.get(threading.get_ident() if thread is None else thread)

    def closed(self, name: Optional[str] = None, *,
               within: Optional[Span] = None,
               before_ns: Optional[int] = None) -> List[Span]:
        """Closed spans still in the ring, oldest first: by name, within the
        interval of another span (any thread), or ended before an instant."""
        lo = within.start_ns if within is not None else None
        hi = within.end_ns if within is not None else before_ns
        return [s for s in list(self._closed)
                if (name is None or s.name == name)
                and (lo is None or s.start_ns >= lo)
                and (hi is None or s.end_ns <= hi)
                and s is not within]

    def last_run(self, steps: int) -> Optional[Span]:
        """The last closed ``run`` span with ``profile`` false, if it ran
        ``steps`` steps: the window of whoever measured that run from
        outside and counted its steps there."""
        runs = [r for r in self.closed("run") if not r.attrs.get("profile")]
        if not runs or not steps or runs[-1].attrs.get("steps") != steps:
            return None
        return runs[-1]

    def programs(self, kind: str) -> Dict[str, int]:
        """``programs{program, kind}``: how often, in the whole process, jax
        traced, lowered or compiled-or-loaded (``kind``: ``trace``, ``lower``,
        ``compile_or_load``) each program."""
        with self._lock:
            return {program: n for (k, program), n
                    in sorted(self._programs.items()) if k == kind}

    def note_site(self, kind: str, layer: Any, path: str) -> None:
        """At trace time: the site ``layer`` of ``kind`` (a key of
        :data:`SITE_PATHS`) went down ``path``."""
        if self.enabled:
            with self._lock:
                self._sites[(kind, id(layer), path)] = layer

    def site_paths(self, kind: str) -> Dict[str, int]:
        """``<kind>_paths{path}``: the distinct sites of ``kind`` traced so
        far in the process, by the path their dispatch chose."""
        with self._lock:
            paths = [path for k, _, path in self._sites if k == kind]
        return {path: paths.count(path)
                for path in SITE_PATHS[kind] or sorted(set(paths))
                if path in paths}

    def note_conv(self, layer: Any, path: str) -> None:
        """``layers.Conv2d.apply``, at trace time: ``layer`` went down ``path``
        (one of :data:`CONV_PATHS`)."""
        self.note_site("conv", layer, path)

    def conv_paths(self) -> Dict[str, int]:
        """``conv_paths{path}``: the distinct convolution layers traced so far
        in the process, by the path their dispatch chose."""
        return self.site_paths("conv")

    # -- jax's own events --------------------------------------------------

    def listen_to_jax(self) -> None:
        """Install the ``jax.monitoring`` listeners, once."""
        if self._listening or not self.enabled:
            return
        self._listening = True
        import jax.monitoring

        jax.monitoring.register_scalar_listener(self._on_scalar)
        jax.monitoring.register_event_time_span_listener(self._on_time_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_scalar(self, event: str, value: float, **kw: Any) -> None:
        """jax records the start of each timed region as a scalar."""
        if event in JAX_EVENTS:
            tid = threading.get_ident()
            self._jax_depth[tid] = self._jax_depth.get(tid, 0) + 1

    def _on_event(self, event: str, **kw: Any) -> None:
        if event == CACHE_HIT_EVENT:
            tid = threading.get_ident()
            self._cache_hits[tid] = self._cache_hits.get(tid, 0) + 1

    def _on_time_span(self, event: str, start_time: float, end_time: float,
                      **kw: Any) -> None:
        name = JAX_EVENTS.get(event)
        if name is None:
            return
        end_ns = self.clock()
        tid = threading.get_ident()
        depth = self._jax_depth.pop(tid, 1) - 1
        if depth > 0:  # inside another of jax's events
            self._jax_depth[tid] = depth
            return
        program = str(kw.get("fun_name", ""))
        attrs: Dict[str, Any] = {"program": program}
        if name == "jax/compile_or_load":
            attrs["cache_hit"] = self._cache_hits.pop(tid, 0) > 0
        span = Span(self, name, None, attrs)
        span.id = next(self._ids)
        span.thread = tid
        stack = self._open.get(tid)
        if stack:
            span.parent, span.gstep = stack[-1].id, stack[-1].gstep
        span.end_ns = end_ns
        span.start_ns = end_ns - max(int((end_time - start_time) * 1e9), 0)
        self._file(span, stack or None)
        with self._lock:
            key = (name[len("jax/"):], program)
            self._programs[key] = self._programs.get(key, 0) + 1

    # -- written out when the run ends --------------------------------------

    def summary(self) -> Dict[str, Any]:
        """The set-up spans, for ``RunLog.close`` and ``flight.dump`` (``obs
        report`` prints them all): the ``setup/*`` spans one by one, jax's
        events summed by kind with the longest three by program, and the
        programs built or loaded inside a step with its ``gstep`` (a program
        that appears twice was retraced); ``conv_paths`` and ``norm_paths``;
        and, where the model has such sites, ``attention_paths``,
        ``expert_paths``, ``shared_expert_paths``, ``ssm_scan_paths``,
        ``tied_head_paths``, ``sparse_indexer_paths``,
        ``sparse_plane_heads``, ``sparse_bwd_plane_heads``, ``ut_loop`` and
        ``flash_whole_tile_pct``."""
        spans = sorted((s for s in list(self._closed)
                        if s.name.startswith(SETUP_PREFIXES)),
                       key=lambda s: s.start_ns)
        jax_kinds: Dict[str, Dict[str, Any]] = {}
        for s in spans:
            if not s.name.startswith("jax/"):
                continue
            k = jax_kinds.setdefault(s.name, {"count": 0, "ms": 0.0, "top": []})
            k["count"] += 1
            k["ms"] += s.ms
            k["top"].append((s.ms, s.attrs.get("program")))
        for k in jax_kinds.values():
            k["ms"] = round(k["ms"], 3)
            k["top"] = [{"program": p, "ms": round(ms, 3)}
                        for ms, p in sorted(k["top"], key=lambda t: -t[0])[:3]]
        in_loop = [{"program": s.attrs.get("program"), "gstep": s.gstep,
                    "ms": round(s.ms, 3), "cache_hit": s.attrs.get("cache_hit")}
                   for s in spans if s.name == "jax/compile_or_load"
                   and s.gstep is not None]
        out = {
            "setup_ms": {s.name: round(s.ms, 3) for s in spans
                         if s.name.startswith("setup/")},
            "jax": jax_kinds,
            "built_in_loop": in_loop,
            "conv_paths": self.conv_paths(),
            "norm_paths": self.site_paths("norm"),
        }
        # only where the model has such sites
        for key, kind in (("attention_paths", "attention"),
                          ("expert_paths", "experts"),
                          ("shared_expert_paths", "shared_expert"),
                          ("ssm_scan_paths", "ssm_scan"),
                          ("tied_head_paths", "tied_head"),
                          ("sparse_indexer_paths", "sparse_indexer"),
                          ("sparse_plane_heads", "sparse_plane_heads"),
                          ("sparse_bwd_plane_heads", "sparse_bwd_plane_heads"),
                          ("ut_loop", "ut_loop"),
                          ("flash_whole_tile_pct", "flash_whole_tile_pct")):
            paths = self.site_paths(kind)
            if paths:
                out[key] = paths
        return out


def _annotation(span: Span):
    import jax.profiler  # deferred: spans.py is imported where jax is not

    if span.name == "step" and span.gstep is not None:
        return jax.profiler.StepTraceAnnotation("step", step_num=span.gstep)
    return jax.profiler.TraceAnnotation(span.name)


_RECORDER: Optional[Recorder] = None
_RECORDER_LOCK = threading.Lock()


def recorder() -> Recorder:
    """The process's recorder (made on first use; it listens to jax)."""
    global _RECORDER
    if _RECORDER is None:
        with _RECORDER_LOCK:
            if _RECORDER is None:
                _RECORDER = Recorder()
    _RECORDER.listen_to_jax()
    return _RECORDER


def _reset_recorder() -> None:
    """Test hook: forget the process's recorder (its jax listeners go too),
    so that the next :func:`recorder` re-reads ``MPI4DL_NO_SCOPES``."""
    global _RECORDER
    with _RECORDER_LOCK:
        rec, _RECORDER = _RECORDER, None
    if rec is not None and rec._listening:
        import jax.monitoring

        jax.monitoring.unregister_scalar_listener(rec._on_scalar)
        jax.monitoring.unregister_event_time_span_listener(rec._on_time_span)
        jax.monitoring.unregister_event_listener(rec._on_event)
