"""Device time of the ring's slot compute, in ms per step: the ops that
``bench/scopes.py`` files under a slot's scope (``rp.slot.F`` plain
forward, ``rp.slot.FB`` fused forward and backward with the head and the
loss, ``rp.slot.B`` backward with its recomputation).  A merge, stash or
codec op inside a slot counts to its own scope.  The names are the
program's (``runtime/spans.py``): a rename there reads nothing here rather
than something else."""
from bench import scopes

SCOPES = ("rp.slot.F", "rp.slot.FB", "rp.slot.B")


def read(record, trace):
    return scopes.ms_per_step(record, trace, SCOPES)
