"""Device time of the ring driver's own work, in ms per step: the ops that
``bench/scopes.py`` files under ``rp.ring`` (``core/dispatch.py``'s
drivers: tick-state zeros, carries, selects, block adds, the final psum)
or ``rp.stash`` (activation stash writes and reads).  A rename of either
scope reads nothing here."""
from bench import scopes

SCOPES = ("rp.ring", "rp.stash")


def read(record, trace):
    return scopes.ms_per_step(record, trace, SCOPES)
