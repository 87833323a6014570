"""The measured window keeps steps dispatched ahead and, when its time is
up, waits for every step it sent before it reads the clock."""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from bench import harness


class _Feed:
    def __init__(self):
        self.asked = []

    def batch(self, i):
        self.asked.append(i)
        return {"tokens": jnp.full((2,), i, jnp.int32)}


def _call(state, batch):
    time.sleep(0.002)
    return state + 1, {"loss": jnp.float32(batch["tokens"][0])}


@pytest.mark.parametrize("ahead", [1, 4])
def test_every_step_sent_is_finished_and_counted(ahead):
    feed = _Feed()
    t0 = time.perf_counter()
    state, w = harness.timed_window(_call, jnp.int32(0), feed, 3, 0.05,
                                    ahead)
    wall = time.perf_counter() - t0
    assert w.steps == w.sent == len(w.dispatch_s) == len(feed.asked) \
        == int(state)
    assert feed.asked == list(range(3, 3 + w.steps))
    assert w.steps >= ahead and w.failed == 0 and w.ahead == ahead
    assert w.seconds == pytest.approx(sum(w.step_s))
    assert 0.05 <= w.seconds <= wall


def test_a_step_that_raises_is_counted_failed():
    def call(state, batch):
        if int(batch["tokens"][0]) == 2:
            raise RuntimeError("planted")
        return _call(state, batch)

    _, w = harness.timed_window(call, jnp.int32(0), _Feed(), 0, 10.0, 4)
    assert (w.sent, w.failed, w.steps) == (3, 3, 0) and w.seconds < 10.0


def test_a_nan_loss_is_counted_failed():
    def call(state, batch):
        return state + 1, {"loss": jnp.float32(float("nan"))}

    _, w = harness.timed_window(call, jnp.int32(0), _Feed(), 0, 0.01, 3)
    assert w.failed == w.steps >= 3


@pytest.mark.parametrize("step_s,seconds,want", [
    (0.245, 30.0, 21),      # five seconds of steps ahead
    (0.245, 3.0, 13),       # no more than the window's length
    (0.05, 0.05, 1),
    (1e-4, 30.0, harness.MAX_AHEAD),
    (10.0, 30.0, 1),
])
def test_steps_ahead(step_s, seconds, want):
    assert harness.steps_ahead(step_s, seconds) == want
