"""A count the program's own recorder kept beside its spans (mpi4dl_tpu/obs/
spans.py), read in process: how often, in the whole process, jax built or
loaded (``kind``: ``compile_or_load``, ``trace`` or ``lower``) a program whose
name matches ``pattern``.  1 is the floor for the step program, 2 is a
retrace, and the recorder's ``jax/<kind>`` span of each holds the ``gstep`` it
fell in.  None where the program has no recorder, the recorder is off or its
``last_run`` is not the harness's window (see program_span).
"""

import re


def read(record, kind, pattern):
    try:
        from mpi4dl_tpu.obs.spans import recorder
    except ImportError:
        return None
    rec = recorder()
    if rec.last_run(len(record["spans"].get("dispatch") or ())) is None:
        return None
    return float(sum(n for program, n in rec.programs(kind).items()
                     if re.search(pattern, program)))
