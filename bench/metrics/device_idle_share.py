"""Share of the traced window in which no op runs on the device, in %,
averaged over the cell's chips."""


def read(record, trace):
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
