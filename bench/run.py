"""One run of one benchmark cell, from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object; the numbers compared
with the reference, each beside its limit, are the last lines of standard
error.  With no TPU, or fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    # the checkout's root (for ``bench``) and the program's sources, and not
    # this directory, whose module names (``trace``) shadow the stdlib's
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import main
    sys.exit(main(t_start=T_START))
