"""A statistic of what the program's loop wrote on its ``step`` spans
(mpi4dl_tpu/obs/spans.py; the attributes are the step's own metrics beside
the loss, fetched with it), read in process: the median over the window's
steps of ``attr``, or of ``scale * attr / over`` where ``over`` names a second
attribute.  The window is the recorder's ``last_run`` (see program_span).
None where the program has no recorder, the window does not match, or no step
of it carries ``attr`` (a program that does not count it: the metric is then
absent from the line).
"""

import statistics


def read(record, attr, over=None, scale=1.0):
    try:
        from mpi4dl_tpu.obs.spans import recorder
    except ImportError:
        return None
    rec = recorder()
    run = rec.last_run(len(record["spans"].get("dispatch") or ()))
    if run is None:
        return None
    values = []
    for s in rec.closed("step", within=run):
        if attr not in s.attrs or (over is not None and not s.attrs.get(over)):
            continue
        v = float(s.attrs[attr])
        values.append(scale * v / float(s.attrs[over]) if over else scale * v)
    return statistics.median(values) if values else None
