"""Device time, in ms a step, of one class of instructions of the traced step
program (``cls``: ``product``, ``kernel``, ``reduce``, ``move``,
``elementwise``; ``perfbench.optable`` says what each is), or of the
instructions of every class that one pass runs (``op_pass``: ``recompute``,
what remat costs): their summed durations over the traced window, divided by
its periods.  None without a trace, or where less than 99 % of the traced op
time finds its instruction in the compiled step's text (the trace is then of
another executable), or where nothing of the class ran (a step without a
Mosaic kernel has no ``kernel_ms``: its cells are not in that metric's list)."""

from perfbench import optable


def read(record, cls=None, op_pass=None):
    trace = record.get("trace")
    if not trace or not trace.get("periods"):
        return None
    seconds = optable.seconds_where(trace.get("joined"), cls=cls, pass_=op_pass)
    return seconds * 1e3 / trace["periods"] if seconds else None
