"""`correct` of the two-phase cell and the LBM ensemble cell: a sound run is
correct, a broken timed path and the lower-precision control are not.

As in ``test_chip_correct.py``: whole runs of the harness on the CPU, in
interpret mode at a small size, with the timed path broken underneath.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
from test_chip_correct import altered, half_left_out, not_a_number, unchanged  # noqa: E402

# (workload, small domain, domains): the two-phase state at 16 x 16 x 128 and
# three LBM ensemble members, each checked whole (slabs at the harness's size)
SMALL = {
    "lbm_twophase.bulk": ([16, 16, 128], 1),
    "lbm_d3q15.ensemble": ([16, 16, 128], 3),
}


def small_cell(workload):
    domain, n = SMALL[workload]
    cell = harness.resolve(ROOT, workload)
    return dataclasses.replace(cell, traffic={"domain": domain, "domains": n})


def run(workload, **kw):
    return harness.run_cell(small_cell(workload), 2**33 + 7, 0.0, None, time.perf_counter(),
                            peak=None, interpret_block=(8, 8), **kw)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload):
    res = run(workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > harness.EARLY_STEPS
    assert set(res["checks"]) == {f"{k}_rel_err" for k in small_cell(workload).spec["outputs"]}
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered, not_a_number],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", sorted(SMALL))
def test_fault_is_not_correct(workload, fault):
    res = run(workload, fault=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_bf16_control_is_not_correct(workload):
    """The reference in the program's place, computed in bfloat16, the
    precision below the configurations' float32, fails every output by a
    wide margin, not by rounding at the limit."""
    import jax
    import jax.numpy as jnp

    ref = small_cell(workload).ref
    res = run(workload, step_override=jax.jit(lambda d: ref.step(d, jnp.bfloat16)))
    assert not res["correct"], res["checks"]
    assert min(c["value"] / c["limit"] for c in res["checks"].values()) > 10
