"""Device time of the LoRA merge, in ms per step: the ops that
``bench/scopes.py`` files under ``rp.merge`` (``models/lora.merge_layers``:
``W + s*B@A`` for every ring block, its vjp, and the relayout copies XLA
makes for it).  A rename of the scope reads nothing here."""
from bench import scopes

SCOPES = ("rp.merge",)


def read(record, trace):
    return scopes.ms_per_step(record, trace, SCOPES)
