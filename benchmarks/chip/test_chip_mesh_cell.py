"""`correct` of the four-chip two-phase cell (a domain split in y over a
chain of chips): a sound run is correct; a run whose chips step their blocks
alone, with no seam exchange, and the lower-precision control are not.

As in ``test_chip_lbm_cells.py``: whole runs of the harness on the CPU, in
interpret mode at a small size, here on four virtual CPU devices.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time
from pathlib import Path

if "jax" not in sys.modules and "XLA_FLAGS" not in os.environ:  # four devices, before jax starts
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

WORKLOAD = "lbm_twophase_y4.bulk"
# a 16 x 64 x 128 domain, 16 rows of y a chip: two seams cross the
# interior, where the chips' (8, 8) edge tiles read their ghosts
SMALL = {"domain": [16, 64, 128], "domains": 1}


def small_cell():
    return dataclasses.replace(harness.resolve(ROOT, WORKLOAD), traffic=SMALL)


def run(**kw):
    return harness.run_cell(small_cell(), 2**33 + 7, 0.0, None, time.perf_counter(),
                            peak=None, interpret_block=(8, 8), **kw)


def test_sound_run_is_correct():
    import jax

    cell = small_cell()
    assert jax.device_count() >= cell.chips
    res = run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > harness.EARLY_STEPS
    assert set(res["checks"]) == {f"{k}_rel_err" for k in cell.spec["outputs"]}


def test_no_exchange_is_not_correct():
    """Each chip steps its own block with the one-chip step, so its edge
    tiles read their clamped strips in place of the neighbours' rows: the
    rows next to the seams are wrong, and only those."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.kernels.lbm_d3q27 import TwoPhaseParams, twophase_step

    cfg = small_cell().config
    params = TwoPhaseParams(**{k: cfg.SPEC[k] for k in cfg.ONE_CHIP.PARAMS})

    def alone(f, g, phase, vel):
        return twophase_step(f, g, phase, vel, params=params, block=(8, 8), phase_block=(8, 8),
                             interpret=True)

    def split(a):
        return P(*(None,) * (a.ndim - 2), "y", None)

    def no_exchange(d):
        args = tuple(d[k] for k in ("f", "g", "phase", "vel"))
        out = jax.shard_map(alone, mesh=cfg.mesh(), in_specs=tuple(map(split, args)),
                            out_specs=tuple(map(split, args)), check_vma=False)(*args)
        return dict(zip(("f", "g", "phase", "vel"), out))

    res = run(step_override=jax.jit(no_exchange))
    assert not res["correct"], res["checks"]
    assert all(c["value"] > 10 * c["limit"] for c in res["checks"].values()), res["checks"]


def test_bf16_control_is_not_correct():
    """The reference in the program's place, computed in bfloat16, fails
    every output by a wide margin, as on one chip."""
    import jax
    import jax.numpy as jnp

    ref = small_cell().ref
    res = run(step_override=jax.jit(lambda d: ref.step(d, jnp.bfloat16)))
    assert not res["correct"], res["checks"]
    assert min(c["value"] / c["limit"] for c in res["checks"].values()) > 10
