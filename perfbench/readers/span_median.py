"""Median of a host span over the untraced steps of the window, in ms."""

import statistics


def read(record, span):
    values = record["spans"].get(span)
    return statistics.median(values) if values else None
