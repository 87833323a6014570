"""Faults planted under the timed path, to show that the comparison fails
them.  Each wraps the step's call ``call(state, batch) -> (state,
metrics)``.  A cell's file lists the faults it can have (``exchange`` only
where chips exchange data)."""
from __future__ import annotations

import numpy as np

from bench.traffic import IGNORE


def unchanged(call):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    def f(state, batch):
        _, metrics = call(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return f


def half_batch(call):
    """Half of the batch's tokens left out, the mean taken over the rest."""
    def f(state, batch):
        labels = np.array(batch["labels"])
        labels[:, labels.shape[1] // 2:] = IGNORE
        return call(state, dict(batch, labels=labels))
    return f


def token_altered(call):
    """The first token of every row altered where the feed produces it."""
    def f(state, batch):
        tokens = np.array(batch["tokens"])
        tokens[:, 0] = np.where(tokens[:, 0] == 1, 2, 1)
        return call(state, dict(batch, tokens=tokens))
    return f


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
