"""The most HBM any of the cell's chips held, in GiB, read after the
window: the peak of the buffers in use (``peak_bytes_in_use``: weights,
optimizer state, batches) plus the peak the runtime reserved for the
programs' scratch (``peak_bytes_reserved``: XLA's temporaries, the ring's
blocks and the vjp residuals)."""


def read(record, trace):
    return record["peak_bytes"] / 2 ** 30
