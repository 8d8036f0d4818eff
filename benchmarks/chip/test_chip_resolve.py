"""The harness finds configurations, traffic mixes and metrics by name, from
files alone; and ``BENCHMARK.json`` keeps to the shape the harness reads."""
from __future__ import annotations

import json
import re
import shutil
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

TOY_CONFIG = '''
import jax


def make_domain(key, shape):
    return {"u": jax.random.uniform(key, shape)}


def step(domain, block=None, interpret=False):
    return {"u": 0.5 * domain["u"]}
'''
TOY_REF = '''
def step(domain, dtype=None):
    return {"u": domain["u"] * 0.5}
'''
TOY_METRIC = '''
def read(run):
    return float(run.steps)
'''


def test_a_new_cell_resolves_from_new_files_alone(tmp_path):
    """A throwaway configuration, traffic mix and metric, added as files and
    entries beside a copy of the benchmark, run with no edit to a file."""
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache", ".traces"))
    base = tmp_path / "benchmarks" / "chip"
    toy = base / "configs" / "toy-halving"
    toy.mkdir()
    (toy / "config.json").write_text(json.dumps(
        {"name": "toy-halving", "kernel": "toy", "kernel_pattern": "toy", "outputs": ["u"],
         "halo": 1, "bytes_per_cell": 8, "flops_per_cell": 1}))
    (toy / "config.py").write_text(TOY_CONFIG)
    (toy / "ref.py").write_text(TOY_REF)
    (toy / "limits.json").write_text(json.dumps({"u": 1e-6}))
    (base / "traffic" / "toy-4x8x8x128.json").write_text(json.dumps({"domain": [8, 8, 128], "domains": 4}))
    (base / "metrics" / "steps_run.py").write_text(TOY_METRIC)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "toy-halving", "source": "https://example.org/toy",
                             "file": "benchmarks/chip/configs/toy-halving/config.json",
                             "reduced": [], "why": "throwaway"})
    bench["workloads"].append({"name": "toy.small", "config": "toy-halving",
                               "traffic": "toy-4x8x8x128", "chips": 1, "why": "throwaway"})
    bench["end_to_end"].append({"name": "steps_run", "unit": "steps", "better": "higher",
                                "bound": 0.25, "source": "host_clock", "workloads": ["toy.small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    before = {p: p.read_bytes() for p in HERE.rglob("*") if p.is_file() and "__pycache__" not in p.parts}

    cell = harness.resolve(tmp_path, "toy.small")
    assert cell.traffic["domains"] == 4 and cell.limits == {"u": 1e-6}
    assert [m["name"] for m, _ in cell.end_to_end] == ["setup_s", "steps_run"]
    res = harness.run_cell(cell, 3, 0.0, None, time.perf_counter(), peak=None, interpret_block=(8, 8))
    assert res["correct"], res["checks"]
    assert res["metrics"]["steps_run"]["value"] == res["attempted"] / 4
    # the cells already there still resolve as before, and no file changed
    assert harness.resolve(tmp_path, "stencil25.bulk").traffic["domain"] == [1024, 1024, 512]
    assert before == {p: p.read_bytes() for p in before}


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_with_its_metrics(workload):
    cell = harness.resolve(ROOT, workload)
    e2e = [m["name"] for m, _ in cell.end_to_end]
    layer = [m["name"] for m, _ in cell.per_layer]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    # every per-layer metric moves an end-to-end metric that this cell reports
    assert all(m["moves"] in e2e for m, _ in cell.per_layer)
    # a split quantity (``idle_share.ensemble``) counts as its part before the dot
    kernel = cell.spec["kernel"]
    assert {f"{kernel}_roofline", "step_mfu"} <= {n.split(".")[0] for n in layer}
    assert set(cell.limits) == set(cell.spec["outputs"])
    for _, reader in cell.end_to_end + cell.per_layer:
        assert callable(reader.read)


def test_benchmark_json_keeps_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"] and 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("benchmarks/chip/")
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and NAME.match(w["traffic"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    reports = {m["name"]: set(m.get("workloads", cells)) for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        # listed only in cells that report the end-to-end metric it moves
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= reports[m["moves"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]) and m["better"] in ("lower", "higher")
