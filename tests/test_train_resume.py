"""Checkpoint wiring in the training launcher (ISSUE 4 satellite): a run
interrupted and resumed from ``--ckpt-dir`` must be BIT-identical to an
uninterrupted run — same atomic writer (`repro.checkpoint.store`), same
step counter restore, driven through the production `run_training` body
rather than a hand-assembled loop."""
import jax
import numpy as np
import pytest

from repro.launch.train import build_parser, run_training

pytestmark = pytest.mark.slow     # two full jit compiles of the train step


def _args(ckpt_dir, steps, ckpt_every=1):
    return build_parser().parse_args([
        "--arch", "qwen3-1.7b", "--smoke",
        "--steps", str(steps), "--batch", "4", "--seq", "16",
        "--mesh", "1x1", "--strategy", "gspmd",
        "--ckpt-dir", str(ckpt_dir), "--ckpt-every", str(ckpt_every),
        "--log-every", "100",
    ])


def _assert_states_bit_identical(a, b):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert [k for k, _ in fa] == [k for k, _ in fb]
    for (k, va), (_, vb) in zip(fa, fb):
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb),
            err_msg=f"resumed state differs at {jax.tree_util.keystr(k)}")


def test_resumed_run_bit_identical_to_uninterrupted(tmp_path):
    steps = 6
    # uninterrupted reference
    ref = run_training(_args(tmp_path / "ref", steps))
    assert ref["resumed_from"] is None
    assert ref["steps"] == steps

    # interrupted at step 3, then resumed from the newest checkpoint
    first = run_training(_args(tmp_path / "resume", 3))
    assert first["steps"] == 3
    second = run_training(_args(tmp_path / "resume", steps))
    # --ckpt-every 1 saved at step 2; restore_or_init restores it and
    # resumes the counter at 3
    assert second["resumed_from"] == 2
    assert second["steps"] == steps

    _assert_states_bit_identical(second["state"], ref["state"])
    # the resumed process replayed exactly steps 3..5
    assert len(second["losses"]) == 3
    np.testing.assert_allclose(second["losses"], ref["losses"][3:], rtol=0)


def test_fresh_dir_starts_from_scratch(tmp_path):
    out = run_training(_args(tmp_path / "fresh", 2))
    assert out["resumed_from"] is None
    assert len(out["losses"]) == 2


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """``use_compile_cache``: JAX_COMPILATION_CACHE_DIR when set, else the
    fixed ``<checkout>/.jax_cache``; a fresh compile lands there."""
    import os
    import subprocess
    import sys

    from repro.launch.train import CHECKOUT

    want = tmp_path / "cache" if from_env else CHECKOUT / ".jax_cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(CHECKOUT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    before = set(want.iterdir()) if want.is_dir() else set()
    code = (
        "import jax\n"
        "from repro.launch.train import use_compile_cache\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "print(use_compile_cache())\n"
        f"jax.jit(lambda x: x * {hash(str(tmp_path)) % 10**6 + 1})(1.0)"
        ".block_until_ready()\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(want)]
    assert set(want.iterdir()) - before, f"no cache entry written to {want}"
