"""Process start to the first timed step: build, weights, compile (or the
persistent cache's load) and the checked first steps."""


def read(record, trace):
    return record["setup_s"]
