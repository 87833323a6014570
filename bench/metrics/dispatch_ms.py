"""Median host time of the compiled step's call until it returns (before
blocking) over the traced window's steps, in ms."""
import statistics


def read(record, trace):
    if trace is None:
        return None
    return statistics.median(record["window"].dispatch_s) * 1e3
