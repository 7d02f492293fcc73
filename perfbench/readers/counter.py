"""A counter the benchmark kept during the run."""


def read(record, counter):
    value = record["counters"].get(counter)
    return None if value is None else float(value)
