"""`correct` catches a broken timed path, and the lower-precision control.

Each case drives a whole run of the harness on the CPU, in interpret mode at
a small size (the harness's look for a chip is skipped), with the timed path
broken underneath, and sees ``correct`` come out false.  The sound run beside
them comes out true.
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

# (workload, small domain, domains, SLAB_BYTES and EARLY_SLAB_BYTES): the bulk
# stencil's slabs are cut below its domain, so that the early sample is a slab
# of rows and the last step is checked in blocks, as at full size
SMALL = {
    "stencil25.bulk": ([40, 16, 128], 1, 150_000),
    "stencil25.ensemble": ([16, 16, 128], 3, harness.SLAB_BYTES),
    "lbm_d3q15.bulk": ([16, 16, 128], 1, harness.SLAB_BYTES),
}


def unchanged(step_all):
    """A step that returns its state unchanged."""
    return lambda ds: list(ds)


def half_left_out(step_all):
    """Half of the work left out: the second half of the domains, or of a
    single domain's z rows, keeps its input."""
    import jax.numpy as jnp

    def run(ds):
        new = step_all(ds)
        if len(ds) > 1:
            return new[: len(ds) // 2] + list(ds[len(ds) // 2:])
        (d,), (n,) = ds, new
        out = {}
        for k, v in n.items():
            z = v.ndim - 3
            mid = v.shape[z] // 2
            out[k] = jnp.concatenate([jnp.take(v, jnp.arange(mid), axis=z),
                                      jnp.take(d[k], jnp.arange(mid, v.shape[z]), axis=z)], axis=z)
        return [out]
    return run


def altered(step_all):
    """One answer altered where it is produced: a cell of domain 0's outputs."""
    def run(ds):
        new = step_all(ds)
        d0 = {k: v.at[..., v.shape[-3] // 2, v.shape[-2] // 2, v.shape[-1] // 2].add(1.0)
              for k, v in new[0].items()}
        return [d0] + new[1:]
    return run


def not_a_number(step_all):
    """One answer produced as NaN in domain 0, which the checks of the
    well-behaved domains after it must not hide."""
    def run(ds):
        new = step_all(ds)
        d0 = {k: v.at[..., v.shape[-3] // 2, v.shape[-2] // 2, 0].set(float("nan"))
              for k, v in new[0].items()}
        return [d0] + new[1:]
    return run


def small_cell(workload):
    domain, n, _ = SMALL[workload]
    cell = harness.resolve(ROOT, workload)
    return dataclasses.replace(cell, traffic={"domain": domain, "domains": n})


def run(workload, monkeypatch, **kw):
    monkeypatch.setattr(harness, "SLAB_BYTES", SMALL[workload][2])
    monkeypatch.setattr(harness, "EARLY_SLAB_BYTES", SMALL[workload][2])
    return harness.run_cell(small_cell(workload), 2**33 + 5, 0.0, None, time.perf_counter(),
                            peak=None, interpret_block=(8, 8), **kw)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload, monkeypatch):
    res = run(workload, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > harness.EARLY_STEPS
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered, not_a_number],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", sorted(SMALL))
def test_fault_is_not_correct(workload, fault, monkeypatch):
    res = run(workload, monkeypatch, fault=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_bf16_control_is_not_correct(workload, monkeypatch):
    """The reference in the program's place, computed in bfloat16, the
    precision below the configurations' float32."""
    import jax
    import jax.numpy as jnp

    ref = small_cell(workload).ref
    res = run(workload, monkeypatch, step_override=jax.jit(lambda d: ref.step(d, jnp.bfloat16)))
    assert not res["correct"], res["checks"]
    # the control fails by a wide margin, not by rounding at the limit
    assert max(c["value"] / c["limit"] for c in res["checks"].values()) > 10
