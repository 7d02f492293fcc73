"""Share of the step period in which no program ran on the device, in %:
100 x (1 - device time of the step program / period).  The device time is
from the traced steps, the period from the untraced steps of the same
process: the device's clock does not change under tracing, the host's
period does."""

import statistics


def read(record, span):
    trace = record.get("trace")
    periods = record["spans"].get(span)
    if not trace or not periods or not trace["chips"]:
        return None
    step_ms = trace["chips"][0]["step_ms"]
    if not step_ms:
        return None
    return 100.0 * (1.0 - statistics.median(step_ms)
                    / statistics.median(periods))
