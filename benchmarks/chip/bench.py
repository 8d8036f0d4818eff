"""Run one benchmark cell on the chip this process finds, and print its result.

    python3 benchmarks/chip/bench.py --workload stencil25.bulk --seed 7 --seconds 10 --trace 0

Sets up (device, fields from the seed, the estimator's pick, compile or cache
load, two warm-up steps), measures for ``--seconds``, checks the outputs of the
timed steps against the configuration's plain reference, and prints one JSON
object as the last line of stdout.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the window with the JAX profiler and
reports its per-layer metrics.  Each number ``correct`` compares is printed
beside its limit as the last lines of stderr, and last in the JSON line.

Exits non-zero and prints no result off a TPU, with fewer chips than the cell
asks for, on a chip the peak table does not hold, or when anything compiles
inside the measured window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
# JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = BENCH_DIR / ".jax_cache"


def start(workload: str):
    """Point JAX at the checkout's compile cache and resolve ``workload``;
    -> (cell, peak table entry), or ``None`` off a TPU, with fewer chips
    than the cell asks for, or on a chip the peak table does not hold."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import harness
    import repro.kernels  # noqa: F401  the system under test; fails where it is absent

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    t_imported = time.perf_counter()
    cell = harness.resolve(ROOT, workload)
    devices = jax.devices()
    harness.log(f"start: imports {t_imported - T_START:.3f} s, device init "
                f"{time.perf_counter() - t_imported:.3f} s")
    if devices[0].platform != "tpu":
        harness.log(f"no TPU: JAX's device is {devices[0].platform!r}; this benchmark runs on a TPU")
        return None
    if len(devices) < cell.chips:
        harness.log(f"{workload} needs {cell.chips} chips, JAX sees {len(devices)}")
        return None
    try:
        return cell, harness.peak_for(devices[0].device_kind, BENCH_DIR)
    except KeyError as e:
        harness.log(str(e))
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    started = start(args.workload)
    if started is None:
        return 2
    import harness

    cell, peak = started
    trace_dir = BENCH_DIR / ".traces" / args.workload if args.trace else None
    result = harness.run_cell(cell, args.seed, args.seconds, trace_dir, T_START, peak=peak)
    for name, c in result["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
