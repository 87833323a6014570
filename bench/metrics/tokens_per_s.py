"""Tokens of every step sent in the window over the window's wall time
(host clock): from its start until every step sent, the ones dispatched
ahead included, has finished."""


def read(record, trace):
    w = record["window"]
    return w.steps * record["tokens_per_step"] / w.seconds
