"""Compulsory work counts, the peak table, and the harness's refusal to run
anywhere but on a TPU it knows."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def spec(name):
    return json.loads((HERE / "configs" / name / "config.json").read_text())


@pytest.mark.parametrize("config,shape,nbytes,flops", [
    # 1024 * 1024 * 512 cells, 4 B read + 4 B written, 25 multiply-adds
    ("stencil25-r4-f32", (1024, 1024, 512), 4_294_967_296, 26_843_545_600),
    ("stencil25-r4-f32", (32, 32, 512), 4_194_304, 26_214_400),
    # 256^3 cells, (15 + 1 + 3) f32 read + (15 + 1) written; 335 FLOP a cell
    ("lbm-d3q15-f32", (256, 256, 256), 2_348_810_240, 5_620_367_360),
])
def test_compulsory_work(config, shape, nbytes, flops):
    assert harness.work(spec(config), shape) == (nbytes, flops)


def test_lbm_flops_per_cell_follow_the_step():
    # phase sum, gradient, norm (3 mul, 3 add, sqrt, divide), normal,
    # sharpening, then per direction cu 6, heq 3, forcing 7, relaxation 4
    assert spec("lbm-d3q15-f32")["flops_per_cell"] == 14 + 6 + 8 + 3 + 4 + 15 * (6 + 3 + 7 + 4)


def test_roofline_is_bound_by_bytes_for_both_kernels():
    peak = harness.peak_for("TPU v5 lite", HERE)
    assert (peak["flops_per_s"], peak["hbm_bytes_per_s"]) == (197e12, 819e9)
    for name, shape in (("stencil25-r4-f32", (1024, 1024, 512)), ("lbm-d3q15-f32", (256, 256, 256))):
        nbytes, flops = harness.work(spec(name), shape)
        assert harness.roofline_s(peak, nbytes, flops) == nbytes / 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        harness.peak_for("TPU v9 imaginary", HERE)


def _bench(cwd: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload", "stencil25.bulk",
         "--seed", str(2**40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_off_a_tpu_without_a_result():
    p = _bench(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "metrics" not in p.stdout and "mlups" not in p.stdout


def test_exits_nonzero_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache", ".traces"))
    p = _bench(tmp_path)
    assert p.returncode != 0
    assert "metrics" not in p.stdout


def test_metric_readers_on_a_made_up_run():
    """Every reader of the stencil bulk cell on a run whose numbers are known."""
    import trace_reduce

    cell = harness.resolve(ROOT, "stencil25.bulk")
    window = trace_reduce.Window(
        seconds=10.0, busy_s=9.5,
        op_seconds={"%stencil25.1 custom-call f32[1024,1024,512]": 7.2,
                    "%pad_maximum_fusion fusion f32[1024,1024,520]": 1.8},
        op_counts={}, idle_gaps=[])
    run = harness.Run(cell, (1024, 1024, 512), 1, steps=200, window_s=10.0, setup_s=12.5,
                      select_s=1.5, pick=(8, 8), peak=harness.peak_for("TPU v5 lite", HERE),
                      trace=window, ladder={(8, 8): 0.036, (8, 64): 0.034})
    roofline = 4_294_967_296 / 819e9  # s per step
    want = {
        "mlups": 200 * 1024 * 1024 * 512 / 10.0 / 1e6,
        "setup_s": 12.5,
        "select_ms": 1500.0,
        "pick_regret": 0.036 / 0.034,
        "wrapper_ms": 1.8 / 200 * 1e3,
        "stencil25_roofline": roofline * 200 / 7.2 * 100,
        "idle_share": 5.0,
        "step_mfu": roofline * 200 / 10.0 * 100,
    }
    got = {m["name"]: r.read(run) for m, r in cell.end_to_end + cell.per_layer}
    assert got == pytest.approx(want)
    # nothing to read: no trace, or another kernel's roofline
    run.trace, run.ladder = None, {}
    assert all(r.read(run) is None for m, r in cell.per_layer if m["name"] != "select_ms")
    lbm_reader = harness.load_module(HERE / "metrics" / "lbm_d3q15_roofline.py", "lbm_reader")
    run.trace = window
    assert lbm_reader.read(run) is None
