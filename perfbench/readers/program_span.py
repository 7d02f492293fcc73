"""A statistic of spans the program's own recorder kept (mpi4dl_tpu/obs/
spans.py), read in process: the benchmark runs the program in its own
process.

The window is the recorder's ``last_run``: the last ``run`` span with
``profile`` false, and only if its step count is that of the harness's window
(``record["spans"]["dispatch"]``); set-up is everything that closed before
that run opened.  A program without the recorder (the parent of the PR that
added it), a recorder turned off, or a window that does not match reads None:
the metric is then absent from the line.

``stat``:
  ``median_ms``        median duration of the spans named ``span`` inside the
                       window, on any thread, whose ``gstep`` is one of the
                       window's steps
  ``self_median_ms``   median over the window's ``span`` spans of their
                       duration less their children named in ``less``
  ``attr_pct``         100 x the share of the window's ``span`` spans, the
                       first ``skip`` left out, whose attribute ``attr`` is
                       true
  ``setup_sum_s``      seconds summed over the spans named in ``spans`` that
                       closed before the window opened and whose ``program``
                       attribute matches ``program`` (where one is given)
"""

import re
import statistics


def read(record, stat, span=None, less=(), attr=None, skip=0, spans=(),
         program=None):
    try:
        from mpi4dl_tpu.obs.spans import recorder
    except ImportError:
        return None
    rec = recorder()
    run = rec.last_run(len(record["spans"].get("dispatch") or ()))
    if run is None:
        return None
    if stat == "setup_sum_s":
        values = [s.ms for name in spans
                  for s in rec.closed(name, before_ns=run.start_ns)
                  if program is None
                  or re.search(program, str(s.attrs.get("program")))]
        return sum(values) / 1e3 if values else None
    inside = rec.closed(span, within=run)
    if stat == "attr_pct":
        flags = [bool(s.attrs.get(attr)) for s in inside[skip:]]
        return 100.0 * sum(flags) / len(flags) if flags else None
    if stat == "median_ms":
        gsteps = {s.gstep for s in rec.closed("step", within=run)}
        values = [s.ms for s in inside if s.gstep in gsteps]
    elif stat == "self_median_ms":
        values = [s.ms - sum(s.kids_ms.get(k, 0.0) for k in less)
                  for s in inside]
    else:
        raise ValueError(f"program_span: no stat {stat!r}")
    return statistics.median(values) if values else None
