"""Device time, in ms a step, of the instructions of the traced step program
whose key (``perfbench.trace.op_key``: the instruction's name without its
number, and the type of its first result) matches ``pattern``: their summed
durations over the traced window, divided by its periods.  None without a
trace, or where nothing matches (a program without that kernel)."""

import re


def op_seconds(record, pattern):
    """Seconds a traced period of the matching instructions, or None."""
    trace = record.get("trace")
    if not trace or not trace.get("periods"):
        return None
    match = re.compile(pattern)
    hit = [s for key, s in trace["op_seconds"].items() if match.search(key)]
    return sum(hit) / trace["periods"] if hit else None


def read(record, pattern):
    seconds = op_seconds(record, pattern)
    return None if seconds is None else seconds * 1e3
