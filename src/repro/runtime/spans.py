"""Names of the RoundPipe step's profiler scopes, the GC hook and the
operator's profile window.

Device side: each phase of the step is traced under ``jax.named_scope`` with
one of the names below (:func:`scope` decorates a function with one).  A
scope is metadata only: it reaches the compiled HLO's ``op_name`` and the
profiler's view of each op, and changes no instruction.  An op belongs to
the innermost ``rp.*`` scope of its ``op_name``; a vjp's ops carry wrapped
names such as ``transpose(jvp(rp.merge))``.

Host side: :func:`gc_spans` makes each garbage collection a
``jax.profiler.TraceAnnotation`` (``rp.host.gc``), on the device trace's
clock, and :class:`ProfileWindow` traces chosen steps of a training loop
(the launcher's ``--profile-dir``), each an ``rp.train_step``.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import threading

# ---- device scopes (the step's phases) -------------------------------------
RING = "rp.ring"              # the driver's own work: tick state, psum
STASH = "rp.stash"            # activation stash writes and reads
SLOT_F = "rp.slot.F"          # plain forward slot
SLOT_FB = "rp.slot.FB"        # fused forward+backward slot (head and loss)
SLOT_B = "rp.slot.B"          # backward slot, recomputation included
MERGE = "rp.merge"            # W + s*B@A and its vjp
SHIFT = "rp.shift"            # the ring hop's ppermute
UPLOAD = "rp.upload"          # standby fills
PROMOTE = "rp.promote"        # standby promote, dequant, whole-block gather
QUANTIZE = "rp.quantize"      # the per-step requantize of the pool
DEPOSIT = "rp.deposit"        # gradient deposits
OPTIMIZER = "rp.optimizer"    # AdamW on the trainable leaves
DEVICE_SCOPES = (RING, STASH, SLOT_F, SLOT_FB, SLOT_B, MERGE, SHIFT, UPLOAD,
                 PROMOTE, QUANTIZE, DEPOSIT, OPTIMIZER)

# ---- host spans -------------------------------------------------------------
HOST_GC = "rp.host.gc"            # one garbage collection
TRAIN_STEP = "rp.train_step"      # one step of a training loop


def scope(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``, a
    fresh scope per call, so that calls nest and re-enter."""
    import jax

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


@contextlib.contextmanager
def gc_spans():
    """While open, each garbage collection is an ``rp.host.gc`` span: a
    profiler annotation from the collection's start to its end."""
    import jax
    running = {}                      # thread -> open annotation

    def on_gc(phase, info):
        tid = threading.get_ident()
        if phase == "start":
            note = jax.profiler.TraceAnnotation(HOST_GC)
            note.__enter__()
            running[tid] = note
        elif tid in running:
            running.pop(tid).__exit__(None, None, None)

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)


class ProfileWindow:
    """Trace steps ``first`` to ``stop - 1`` of a training loop into
    ``log_dir`` with ``jax.profiler``, garbage collections included as
    ``rp.host.gc`` spans.  The loop wraps each step in :func:`train_step`;
    the window's owner calls :meth:`close` when the loop ends."""

    def __init__(self, log_dir: str, first: int, stop: int):
        if not 0 <= first < stop:
            raise ValueError(f"profile steps {first}:{stop}: need "
                             f"0 <= first < stop")
        self.log_dir, self.first, self.stop = log_dir, first, stop
        self._gc = None
        self.done = False

    @classmethod
    def parse(cls, log_dir: str, steps: str) -> "ProfileWindow":
        """From ``--profile-steps A:B`` (steps A to B-1)."""
        first, _, stop = steps.partition(":")
        try:
            return cls(log_dir, int(first), int(stop))
        except ValueError as e:
            raise ValueError(f"--profile-steps {steps!r}: expected A:B with "
                             f"0 <= A < B ({e})") from None

    def begin(self, step: int):
        if self._gc is None and not self.done \
                and self.first <= step < self.stop:
            import jax
            jax.profiler.start_trace(self.log_dir)
            self._gc = gc_spans()
            self._gc.__enter__()

    def end(self, step: int):
        if self._gc is not None and step + 1 >= self.stop:
            self.close()

    def close(self):
        if self._gc is not None:
            self._gc.__exit__(None, None, None)
            self._gc = None
            import jax
            jax.profiler.stop_trace()
            self.done = True


@contextlib.contextmanager
def train_step(step: int, window: ProfileWindow | None = None):
    """One step of a training loop: a ``StepTraceAnnotation`` named
    ``rp.train_step``, inside ``window``'s trace when it covers the step."""
    import jax
    if window is not None:
        window.begin(step)
    try:
        with jax.profiler.StepTraceAnnotation(TRAIN_STEP, step_num=step):
            yield
    finally:
        if window is not None:
            window.end(step)
