"""Device time, in ms a step, of some instructions of the traced step program:
their summed durations over the traced window, divided by its periods.

``scope`` picks the instructions that carry a named scope of the program
(``perfbench.optable``: a name in the ``op_name`` path of the instruction in
the compiled step, a fusion's read from its fused instructions), forward,
recomputed and backward, whatever implements the work: a Mosaic kernel or
XLA's tiles alike.  ``pattern`` picks by key (``perfbench.trace.op_key``: the
instruction's name without its number, and the type of its first result),
which follows the shapes of one implementation.  A metric's file may give
both: the scope is read where the join of the trace to the compiled step holds
and the program opens that scope, the pattern where it does not (a program
that has not opened the scope yet).  None without a trace, or where nothing
is picked (a program without that kernel)."""

import re

from perfbench import optable


def op_seconds(record, pattern=None, scope=None):
    """Seconds a traced period of the picked instructions, or None."""
    trace = record.get("trace")
    if not trace or not trace.get("periods"):
        return None
    if scope:
        picked = optable.seconds_where(trace.get("joined"), scope=scope)
        if picked:  # None: no join; 0.0: no instruction carries the scope
            return picked / trace["periods"]
    if not pattern:
        return None
    match = re.compile(pattern)
    hit = [s for key, s in trace["op_seconds"].items() if match.search(key)]
    return sum(hit) / trace["periods"] if hit else None


def read(record, pattern=None, scope=None):
    seconds = op_seconds(record, pattern, scope)
    return None if seconds is None else seconds * 1e3
