"""Median device duration, in ms, of the step program's events on the
device plane's ``XLA Modules`` line (the first chip's)."""

import statistics


def read(record):
    trace = record.get("trace")
    if not trace or not trace["chips"] or not trace["chips"][0]["step_ms"]:
        return None
    return statistics.median(trace["chips"][0]["step_ms"])
