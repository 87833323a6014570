"""Run one ``roundpipe_subprocess.py`` mode in a child process and require
its ``ROUNDPIPE_DISPATCH_OK``.  The child needs 8 virtual CPU devices set
before jax initializes, which the pytest process cannot provide.

Each proof takes minutes, so where the proofs live decides the suite's
wall time under ``pytest -n 6 --dist loadfile``.  pytest-xdist hands out
whole files, largest test count first, and queues a further file on a
worker once two or fewer of its tests remain.  A proof in a file of its
own therefore starts late and gets another file queued behind it.  So
each proof sits first in the file of its feature's unit tests (many fast
tests), where it starts early and nothing waits behind it."""
import os
import subprocess
import sys

SCRIPT = os.path.join(os.path.dirname(__file__), "roundpipe_subprocess.py")


def run_mode(arch, mode, n_layers=None):
    cmd = [sys.executable, SCRIPT, arch, mode]
    if n_layers is not None:
        cmd.append(str(n_layers))
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ROUNDPIPE_DISPATCH_OK" in r.stdout, r.stdout[-2000:]
