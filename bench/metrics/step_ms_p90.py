"""90th percentile of the window's step times, in ms; a step runs from the
previous step's completion as the host sees it to its own (the window
keeps steps dispatched ahead, so the host's own gaps fall between them
only where it falls behind).  Needs 100 steps (ten beyond the percentile);
fewer read nothing."""
import statistics

MIN_STEPS = 100


def read(record, trace):
    w = record["window"]
    if w.steps < MIN_STEPS:
        return None
    return statistics.quantiles(w.step_s, n=10)[8] * 1e3
