"""TPU/Pallas estimator: revisit-rule exactness, VMEM gate, ranking sanity."""
from __future__ import annotations

import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="optional dev dependency; pip install -r requirements-dev.txt")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import tpu_estimator as te
from repro.core.machine import TPU_V5E, device_machine, tpu_machine


def _matmul_cfg(M, N, K, bm, bn, bk, bits=16):
    return te.PallasConfig(
        name=f"mm{bm}x{bn}x{bk}",
        grid=(M // bm, N // bn, K // bk),
        accesses=(
            te.BlockAccess("A", (bm, bk), lambda i, j, k: (i, k), bits),
            te.BlockAccess("B", (bk, bn), lambda i, j, k: (k, j), bits),
            te.BlockAccess("O", (bm, bn), lambda i, j, k: (i, j), bits, True),
        ),
        flops_per_step=2.0 * bm * bn * bk,
    )


def test_matmul_fetch_counts_exact():
    """Pallas revisit rule: A refetches whenever (i,k) changes -> with k innermost,
    A fetches = gi*gj*gk; B same; O unique = gi*gj."""
    M = N = K = 1024
    bm = bn = bk = 256
    cfg = _matmul_cfg(M, N, K, bm, bn, bk)
    est = te.estimate(cfg)
    g = 4
    dA = est.detail["A"]
    dB = est.detail["B"]
    dO = est.detail["O"]
    assert dA["fetches"] == g * g * g
    assert dA["unique_blocks"] == g * g
    assert dB["fetches"] == g * g * g
    assert dO["unique_blocks"] == g * g
    assert est.hbm_redundant > 0


def test_vmem_gate():
    cfg = _matmul_cfg(8192, 8192, 8192, 8192, 8192, 8192, bits=32)
    est = te.estimate(cfg)
    assert not est.feasible
    with pytest.raises(ValueError):
        te.select_config([cfg], TPU_V5E)


def test_ranking_prefers_feasible_and_fast():
    cands = [
        _matmul_cfg(4096, 4096, 4096, b, b, b)
        for b in (128, 256, 512, 1024)
    ]
    ranked = te.rank_configs(cands)
    assert ranked[0][1].feasible
    times = [e.time for _, e in ranked]
    assert times == sorted(times)


@settings(max_examples=30, deadline=None)
@given(
    b=st.sampled_from([128, 256, 512]),
    bits=st.sampled_from([8, 16, 32]),
)
def test_invariants(b, bits):
    cfg = _matmul_cfg(2048, 2048, 2048, b, b, b, bits)
    est = te.estimate(cfg)
    assert est.hbm_compulsory <= est.hbm_bytes + 1e-9
    assert 0 < est.layout_efficiency <= 1.0
    assert est.vmem_bytes > 0


def test_layout_efficiency_penalizes_ragged_lanes():
    good = te.PallasConfig(
        "good", (4,), (te.BlockAccess("x", (8, 128), lambda i: (i, 0), 32),), 0.0
    )
    bad = te.PallasConfig(
        "bad", (4,), (te.BlockAccess("x", (8, 100), lambda i: (i, 0), 32),), 0.0
    )
    eg = te.estimate(good)
    eb = te.estimate(bad)
    assert eg.layout_efficiency == 1.0
    assert eb.layout_efficiency < 0.9


@pytest.mark.parametrize(
    "block,index_map,feasible",
    [
        ((8, 128), lambda i, j: (i, j), True),
        ((4, 128), lambda i, j: (i, j), False),  # sublane dim moves, not a multiple of 8
        ((8, 100), lambda i, j: (i, j), False),  # lane dim moves, not a multiple of 128
        ((4, 100), lambda i, j: (0, 0), True),  # never moves: spans the array
        ((4, 128), lambda i, j: (1, j), False),  # a fixed block that is not the first
    ],
)
def test_tiling_rule_gate(block, index_map, feasible):
    """Mosaic's block-shape rule is a hard gate, like VMEM."""
    cfg = te.PallasConfig("t", (4, 4), (te.BlockAccess("x", block, index_map, 32),), 0.0)
    est = te.estimate(cfg, TPU_V5E)
    assert est.feasible == feasible
    if not feasible:
        assert est.limiter == "TILING" and est.misaligned == ("x",)


def test_select_config_needs_a_machine():
    cfg = _matmul_cfg(1024, 1024, 1024, 256, 256, 256)
    with pytest.raises(ValueError, match="TPUMachine"):
        te.select_config([cfg], None)


def test_device_kind_lookup():
    assert tpu_machine("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="TPU v4"):
        tpu_machine("TPU v4")


def test_device_machine_refuses_a_cpu():
    jax = pytest.importorskip("jax")
    if jax.devices()[0].platform == "tpu":
        pytest.skip("runs where JAX's device is not a TPU")
    with pytest.raises(RuntimeError, match="no TPU"):
        device_machine()
