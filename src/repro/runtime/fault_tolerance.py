"""Fault tolerance: heartbeats, straggler mitigation, checkpoint-restart.

At 1000+-node scale the failure model is: slow step (straggler), hung step
(network/host fault), dead worker (restart required).  The driver reacts per
policy:

  * **straggler**: a step slower than ``straggler_factor`` × the trailing
    median is logged; under RoundPipe the mitigation is structural — a stage
    is data + a slot index, not a device binding, so the next round simply
    advances ``g0`` past the slow worker (the schedule-level re-dispatch in
    ``core.schedule``) while the driver emits the event for the cluster
    scheduler;
  * **hang**: steps run under a watchdog; timeout ⇒ raise for restart;
  * **crash/restart**: training resumes from the newest atomic checkpoint
    (``repro.checkpoint``), on a possibly DIFFERENT mesh (elastic re-place).

Pure-Python driver around any jitted step — exercised with fault injection
in tests.
"""
from __future__ import annotations

import dataclasses
import statistics
import sys
import threading
import time
import traceback
from typing import Callable, Optional

from . import spans


@dataclasses.dataclass
class StragglerPolicy:
    factor: float = 2.0          # step > factor * median ⇒ straggler
    window: int = 20             # trailing steps for the median
    min_samples: int = 5


class StepHungError(RuntimeError):
    """The watchdog declared the monitored step hung: no ``beat()`` arrived
    within ``timeout_s``.  Raised INTO the driver loop (from ``beat()`` or
    the monitor's ``__exit__``) so the checkpoint-restart path runs — the
    module contract "timeout ⇒ raise for restart"."""


class HeartbeatMonitor:
    """Watchdog: if ``beat()`` isn't called within ``timeout_s``, the step is
    declared hung, ``on_timeout`` fires (default: records the event), and the
    hang is RAISED into the monitored loop as :class:`StepHungError` — a
    watchdog thread cannot interrupt a blocking jitted step directly, so the
    raise happens at the first control-flow point the loop reaches:
    the next ``beat()`` call, or the ``with`` block's exit.  Either way the
    driver's except path restores from the newest checkpoint instead of
    silently absorbing the hang into a slow step."""

    def __init__(self, timeout_s: float, on_timeout: Optional[Callable] = None):
        self.timeout_s = timeout_s
        self.on_timeout = on_timeout
        self.events: list[float] = []
        self.hung = False
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self):
        self._raise_if_hung()
        self._last = time.monotonic()

    def _raise_if_hung(self):
        if self.hung:
            raise StepHungError(
                f"step exceeded the {self.timeout_s:.1f}s heartbeat "
                f"timeout ({len(self.events)} watchdog firing(s))")

    def __enter__(self):
        def watch():
            while not self._stop.wait(self.timeout_s / 4):
                if time.monotonic() - self._last > self.timeout_s:
                    self.events.append(time.monotonic())
                    self.hung = True
                    if self.on_timeout:
                        self.on_timeout()
                    self._last = time.monotonic()

        self._thread = threading.Thread(target=watch, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, exc_type, *exc):
        self._stop.set()
        self._thread.join(1.0)
        # don't mask an exception already propagating out of the block
        if exc_type is None:
            self._raise_if_hung()


class FaultTolerantLoop:
    """Checkpoint-restart training driver.

    ``step_fn(state, batch) -> (state, metrics)`` may raise (fault injection /
    real device errors): the loop restores the newest checkpoint and replays
    the data stream deterministically (the pipeline is (seed, step)-pure).
    """

    def __init__(self, step_fn, ckpt_manager, dataset, *,
                 straggler: StragglerPolicy = StragglerPolicy(),
                 max_restarts: int = 3,
                 step_timeout_s: float = 3600.0,
                 profile: Optional[spans.ProfileWindow] = None):
        self.step_fn = step_fn
        self.ckpt = ckpt_manager
        self.dataset = dataset
        self.policy = straggler
        self.max_restarts = max_restarts
        self.step_timeout_s = step_timeout_s
        self.profile = profile
        self.stragglers: list[int] = []
        self.restarts = 0
        self.durations: list[float] = []

    def _check_straggler(self, step: int, dt: float):
        window = self.durations[-self.policy.window:]
        if len(window) >= self.policy.min_samples:
            med = statistics.median(window)
            if dt > self.policy.factor * med:
                self.stragglers.append(step)

    def run(self, init_fn, like, n_steps: int, *, shardings=None,
            metrics_cb=None):
        state, start = self.ckpt.restore_or_init(
            lambda: init_fn(), like, shardings)
        step = start
        while step < n_steps:
            try:
                with HeartbeatMonitor(self.step_timeout_s) as hb, \
                        spans.train_step(step, self.profile):
                    batch = self.dataset.batch(step)
                    t0 = time.monotonic()
                    state, metrics = self.step_fn(state, batch)
                    jax_block((state, metrics))
                    dt = time.monotonic() - t0
                    hb.beat()
            except Exception:
                traceback.print_exc()
                self.restarts += 1
                if self.restarts > self.max_restarts:
                    raise
                print(f"step {step} failed; restart {self.restarts}/"
                      f"{self.max_restarts} from the newest checkpoint",
                      file=sys.stderr, flush=True)
                ckpt_step = self.ckpt.restore_or_init(
                    lambda: init_fn(), like, shardings)
                state, step = ckpt_step
                continue
            self._check_straggler(step, dt)
            self.durations.append(dt)
            if metrics_cb:
                metrics_cb(step, metrics, dt)
            self.ckpt.maybe_save(step, state)
            step += 1
        return state, step


def jax_block(tree):
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
