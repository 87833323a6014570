"""Device time of the per-step requantize of a quantized pool, in ms per
step: the ops that ``bench/scopes.py`` files under ``rp.quantize``
(``RingMachine.quantize_pool``, with the concatenation and relayouts XLA
makes for it).  Only a cell with a quantized pool has such ops; a rename
reads nothing here."""
from bench import scopes

SCOPES = ("rp.quantize",)


def read(record, trace):
    return scopes.ms_per_step(record, trace, SCOPES)
