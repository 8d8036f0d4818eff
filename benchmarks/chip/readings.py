"""Read the numbers ``correct`` compares over many seeds in one process: for
the program (the lower reading of each limit) or for the control, the plain
reference in the program's place computed in bfloat16 (the upper reading).

    python3 benchmarks/chip/readings.py --workload stencil25.bulk --seeds 1,2,3 --seconds 1 [--control]

Each seed is a whole run of the cell with a short window at the cell's own
size; set-up and compiles are shared.  Prints one JSON line per seed.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    started = bench.start(args.workload)
    if started is None:
        return 2
    import jax
    import jax.numpy as jnp

    import harness

    cell, peak = started
    override = None
    if args.control:
        override = jax.jit(lambda d: cell.ref.step(d, jnp.bfloat16))
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(cell, seed, args.seconds, None, time.perf_counter(), peak=peak,
                               step_override=override)
        print(json.dumps({"workload": args.workload, "seed": seed, "control": args.control,
                          "correct": res["correct"], "attempted": res["attempted"],
                          "checks": {k: c["value"] for k, c in res["checks"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
