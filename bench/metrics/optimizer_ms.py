"""Device time of the optimizer, in ms per step: the ops that
``bench/scopes.py`` files under ``rp.optimizer`` (``optim/adam``'s
``apply_updates`` and ``merge_trainable``: clipping and AdamW on the
adapters).  A rename of the scope reads nothing here."""
from bench import scopes

SCOPES = ("rp.optimizer",)


def read(record, trace):
    return scopes.ms_per_step(record, trace, SCOPES)
