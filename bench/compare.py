"""The comparison that decides ``correct`` for a training cell.

Four numbers, each against the float32 reference over the same first steps.
A cell's file gives each its limit, set from readings on the chip that
``PERF.md`` lists; a number whose limit is null is read and printed but not
compared, for it has no reading that separates faults from sound runs.

  loss_gap    the relative gap of the first step's loss;
  grad_gap    the worst leaf's gap between the norms of the program's and
              the reference's first gradient, as the optimizer takes it;
  grad_diff   the median leaf's norm of the difference between the two
              first gradients, relative to the reference's norm of the leaf;
  change_gap  the worst leaf's gap between the norms of the change of the
              master adapters over the steps.

The loss is compared at the first step only: Adam's first update moves
every adapter element by about the learning rate whatever the size of its
gradient, so elements whose gradient is at rounding level take a random
sign in the program and in the reference, and the later steps' losses part
by that chance (``PERF.md`` gives the readings).  A leaf is one layer's
part of one adapter matrix.  A gap of norms is measured against the
reference's norm of that leaf or of the median leaf, whichever is larger.  Leaves whose gradient is nought in the reference are
left out, by one rule: a norm under a thousandth of the median leaf's.  For
the first gradient that is the ``A`` factors, whose gradient is exactly
zero while ``B`` is; for the change it is leaves that no step's gradient
moves (Adam moves them by round-off alone).
"""
from __future__ import annotations

import math
import statistics

import numpy as np

NAMES = ("loss_gap", "grad_gap", "grad_diff", "change_gap")
NOUGHT = 1e-3


def _norms(rows: dict) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in rows.items()}


def worst_leaf_gap(got: dict, want: dict, keys) -> float:
    """max over ``keys`` of |‖got‖ − ‖want‖| / max(‖want‖, median ‖want‖)."""
    if set(keys) - set(got):
        return math.inf
    med = statistics.median(want[k] for k in keys)
    worst = 0.0
    for k in keys:
        den = max(want[k], med)
        gap = abs(got[k] - want[k])
        worst = max(worst, gap / den if den > 0 else
                    (0.0 if gap == 0 else math.inf))
    return worst


def moving_leaves(norms: dict) -> list:
    """Leaves whose norm is at least a thousandth of the median leaf's."""
    med = statistics.median(norms.values())
    return sorted(k for k, v in norms.items() if v >= NOUGHT * med)


def median_leaf_diff(got: dict, want: dict, keys) -> float:
    if set(keys) - set(got):
        return math.inf
    return statistics.median(
        float(np.linalg.norm(got[k] - want[k]) / np.linalg.norm(want[k]))
        for k in keys)


def readings(got: dict, want: dict) -> dict:
    g, w = got["losses"][0], want["losses"][0]
    loss_gap = abs(g - w) / abs(w) if math.isfinite(g) else math.inf
    grad_ref = _norms(want["grad1"])
    grad_keys = moving_leaves(grad_ref)
    return {
        "loss_gap": loss_gap,
        "grad_gap": worst_leaf_gap(_norms(got["grad1"]), grad_ref,
                                   grad_keys),
        "grad_diff": median_leaf_diff(got["grad1"], want["grad1"],
                                      grad_keys),
        "change_gap": worst_leaf_gap(_norms(got["change"]),
                                     _norms(want["change"]),
                                     moving_leaves(want["grad_max"])),
    }


def judge(values: dict, limits: dict):
    """(correct, checks): every compared number finite and within its
    limit.  A number whose limit is null has no upper reading and is not
    compared (``PERF.md`` names it with its readings)."""
    checks = {n: {"value": values[n], "limit": limits[n]} for n in NAMES
              if limits[n] is not None}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def describe(values: dict, got: dict, want: dict) -> list:
    """Lines for standard error: each step's loss, the program's beside the
    reference's, and every number read, compared or not."""
    return [
        "losses " + " ".join(f"{g!r}/{w!r}" for g, w in
                             zip(got["losses"], want["losses"])),
        "readings " + " ".join(f"{k} {v!r}" for k, v in values.items()),
    ]
