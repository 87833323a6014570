"""Device time of the ring primitives that move weights and gradients, in
ms per step: the ops that ``bench/scopes.py`` files under ``rp.shift``
(the ring hop's ppermute), ``rp.upload`` (standby fills), ``rp.promote``
(standby promote, the dequant kernel, whole-block gathers) or
``rp.deposit`` (gradient deposits), all methods of ``core/ring.py``'s
``RingMachine``.  The requantize has its own metric.  A rename of these
scopes reads nothing here."""
from bench import scopes

SCOPES = ("rp.shift", "rp.upload", "rp.promote", "rp.deposit")


def read(record, trace):
    return scopes.ms_per_step(record, trace, SCOPES)
