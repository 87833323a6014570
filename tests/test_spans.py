"""The scope decorator, the GC hook and the profile window of
``runtime/spans.py``."""
import gc
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.runtime import FaultTolerantLoop, spans


def _host_events(log_dir) -> list:
    """Names of the host plane's events in the one trace under ``log_dir``."""
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return [e.name for plane in data.planes if plane.name.startswith("/host")
            for line in plane.lines for e in line.events]


def test_gc_spans_annotate_each_collection_while_open(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.gc_spans():
            gc.collect()                    # one collection
        gc.collect()                        # the hook is off again
    finally:
        jax.profiler.stop_trace()
    assert _host_events(tmp_path).count(spans.HOST_GC) == 1


def test_scope_names_the_traced_ops():
    @spans.scope(spans.MERGE)
    def merge(w, d):
        return w + d

    text = jax.jit(merge).lower(jnp.ones(4), jnp.ones(4)).as_text(
        debug_info=True)
    assert "rp.merge" in text


@pytest.mark.parametrize("steps", ["3", "3:3", "a:b", "-1:2"])
def test_profile_steps_must_be_a_range(steps):
    with pytest.raises(ValueError):
        spans.ProfileWindow.parse("/tmp/unused", steps)


class _Ckpt:
    def restore_or_init(self, init_fn, like, shardings=None):
        return init_fn(), 0

    def maybe_save(self, step, state):
        return False


class _Data:
    def batch(self, step):
        return jnp.float32(step)


def test_profile_window_traces_the_chosen_steps(tmp_path):
    """The loop's steps 1 and 2 go into one trace whose host plane holds
    the rp.train_step annotations; the trace closes after step 2."""
    window = spans.ProfileWindow(str(tmp_path), 1, 3)
    step = jax.jit(lambda s, b: (s + b, {"loss": s}))
    loop = FaultTolerantLoop(step, _Ckpt(), _Data(), profile=window)
    loop.run(lambda: jnp.float32(0), None, 4)
    assert window.done
    assert _host_events(tmp_path).count(spans.TRAIN_STEP) == 2
