"""Readings that set a cell's limits: the compared numbers of sound runs,
of the control and of planted faults, over many seeds, in one process and
with no measured window.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \\
        --variants sound,control,half_batch,token_altered [--out f.jsonl]

``sound`` runs the cell as it is timed; ``control`` runs the program's own
lower-precision pool named in the cell's file (``control.pool_dtype``),
compared with the same reference; the other variants are the faults of
``bench/faults.py``.  Each seed's reference (the module that the cell's
configuration names, through ``harness.reference_run``) is computed once and
every variant compared with it.  Prints one JSON line per variant and seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="sound,control")
    ap.add_argument("--out", default=None)
    ap.add_argument("--specs", default=None,
                    help="directory holding configs/, traffic/ and "
                         "workloads/ (default: bench/)")
    args = ap.parse_args(argv)

    import jax

    from bench import compare, faults, harness, traffic
    from repro.launch.train import use_compile_cache

    use_compile_cache()
    cell = harness.load_cell(args.workload, Path(args.specs) if args.specs
                             else harness.BENCH)
    n = cell["mix"]["check_steps"]
    variants = args.variants.split(",")
    progs = {}

    def program(variant):
        pool = cell["control"]["pool_dtype"] if variant == "control" else None
        if pool not in progs:
            progs[pool] = harness.Program(cell, pool_dtype=pool)
        return progs[pool]

    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            for variant in variants:
                prog = program(variant)
                batches = traffic.Batches(cell["mix"], prog.cfg.vocab_size,
                                          seed)
                call = prog.call if variant in ("sound", "control") else \
                    faults.FAULTS[variant](prog.call)
                with prog.mesh:
                    state = prog.init(harness.seed_key(seed))
                    state, got = harness.check_steps(prog, call, state,
                                                     batches, n)
                    del state
                    gc.collect()
                if variant == variants[0]:
                    want = harness.reference_run(cell, seed, batches, n)
                values = compare.readings(got, want)
                line = {"cell": args.workload, "seed": seed,
                        "variant": variant, **values,
                        "losses": got["losses"], "ref_losses": want["losses"],
                        "device": jax.devices()[0].device_kind}
                print(json.dumps(line), flush=True)
                if out:
                    out.write(json.dumps(line) + "\n")
                    out.flush()
            print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
                  file=sys.stderr, flush=True)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    # the checkout's root and the program's sources, not this directory
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
