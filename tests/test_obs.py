"""The repro.obs observability layer (tracing, metrics, explain).

Covers the ISSUE-6 contracts:

* spans nest correctly and aggregate across a process-pool sweep (worker
  events land in the parent trace under their own pid lanes, and the exported
  document passes the Chrome-trace schema check);
* a metrics snapshot round-trips through JSON exactly, merges across
  registries and diffs around a sweep;
* disabled-mode instrumentation stays under a 2% overhead budget on the full
  162-config stencil sweep (generous bound: measured per-span cost x recorded
  span count vs the sweep's wall clock), and records are bit-identical with
  tracing on vs off;
* ``Study.explain`` output is golden-stable for a pinned config on V100 and
  A100, answers "why was this pruned?", and the cross-machine view lines the
  levels up side by side.

Golden regen: ``REPRO_REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest
tests/test_obs.py`` then inspect/commit ``tests/golden/explain_*.txt``.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path

import pytest

from repro.core.machine import TPU_V5E, V100
from repro.explore import Study
from repro.obs import metrics, trace
from repro.obs.explain import CrossMachineExplain, ExplainReport

GOLDEN_DIR = Path(__file__).parent / "golden"
REGEN = os.environ.get("REPRO_REGEN_GOLDEN") == "1"


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Tracing is process-global; never leak an enabled tracer across tests."""
    trace.disable()
    yield
    trace.disable()


def _tpu_cfgs():
    from repro.core import tpu_estimator as te

    def cfg(name, bz):
        return te.PallasConfig(
            name=name,
            grid=(256 // bz,),
            accesses=(
                te.BlockAccess(
                    name="x",
                    block_shape=(bz, 512, 128),
                    index_map=lambda i: (i, 0, 0),
                    dtype_bits=32,
                ),
            ),
            flops_per_step=1.0,
            is_matmul=False,
            meta={"bz": bz},
        )

    return [cfg("small", 8), cfg("mid", 16), cfg("huge", 256)]


# --------------------------------------------------------------------------- #
# tracing


def test_spans_nest_and_measure():
    tracer = trace.enable()
    with trace.span("outer", kind="test") as outer:
        with trace.span("inner") as inner:
            time.sleep(0.002)
        inner2 = trace.span("inner2")
        with inner2:
            pass
    assert outer.duration_s >= inner.duration_s > 0
    by_name = {e["name"]: e for e in tracer.events}
    assert set(by_name) == {"outer", "inner", "inner2"}
    o, i = by_name["outer"], by_name["inner"]
    # containment on the exported timeline: inner starts after outer and ends
    # before outer's end
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
    assert o["args"] == {"kind": "test"}
    assert trace.validate_chrome_trace(tracer.to_chrome()) == []


def test_disabled_spans_still_measure_but_record_nothing():
    assert trace.active() is None
    with trace.span("ghost") as sp:
        time.sleep(0.001)
    assert sp.duration_s > 0
    tracer = trace.enable()
    assert tracer.events == []


def test_span_set_attaches_attributes():
    tracer = trace.enable()
    with trace.span("s") as sp:
        sp.set(hits=3, misses=1)
    assert tracer.events[0]["args"] == {"hits": 3, "misses": 1}


def test_absorb_rebases_worker_timestamps():
    tracer = trace.enable()
    with trace.span("parent"):
        pass
    payload = {
        "epoch_wall": tracer.epoch_wall + 1.5,  # worker started 1.5s later
        "events": [{"name": "w", "ph": "X", "ts": 10.0, "dur": 5.0, "pid": 99, "tid": 0}],
    }
    tracer.absorb(payload)
    ev = next(e for e in tracer.events if e["name"] == "w")
    assert ev["ts"] == pytest.approx(1.5e6 + 10.0)
    doc = tracer.to_chrome()
    names = {
        e["args"]["name"] for e in doc["traceEvents"] if e.get("ph") == "M"
    }
    assert "repro.worker[99]" in names and "repro.estimation" in names


def test_validate_chrome_trace_flags_malformed_docs():
    assert trace.validate_chrome_trace({}) == ["traceEvents missing or not a list"]
    bad = {
        "traceEvents": [
            {"ph": "X", "ts": 0.0},  # no name
            {"name": "b", "ph": "B", "ts": 0.0, "pid": 1, "tid": 1},  # unbalanced
        ]
    }
    problems = trace.validate_chrome_trace(bad)
    assert any("missing 'name'" in p for p in problems)
    assert any("unbalanced" in p for p in problems)


def test_trace_export_is_loadable_json(tmp_path):
    tracer = trace.enable()
    with trace.span("phase"):
        pass
    path = tmp_path / "trace.json"
    n = tracer.export(path)
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == n
    assert doc["displayTimeUnit"] == "ms"
    assert trace.validate_chrome_trace(doc) == []


def _profiled_host_events(log_dir) -> set[str]:
    """Names of every host-plane event in the xplane a profiler session wrote
    under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = Path(log_dir).glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return {e.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


@pytest.mark.parametrize("tracer_on", [False, True], ids=["tracer_off", "tracer_on"])
def test_span_lands_in_the_profiler_trace(tmp_path, tracer_on):
    """One span, both sinks: the profiler records it by name whether or not
    the Chrome-trace tracer is enabled, and the tracer still records it."""
    import jax

    tracer = trace.enable() if tracer_on else None
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("x", size=3):
            pass
    assert "x" in _profiled_host_events(tmp_path)
    if tracer_on:
        assert tracer.span_names() == {"x"}
        assert tracer.events[0]["args"] == {"size": 3}


def test_recording_follows_the_tracer_and_the_profiler(tmp_path):
    import jax

    assert not trace.recording()
    with jax.profiler.trace(str(tmp_path)):
        assert trace.recording()
    assert not trace.recording()
    trace.enable()
    assert trace.recording()


@pytest.mark.parametrize("sink", ["profiler", "tracer"])
def test_entry_point_call_span_reaches_each_sink(tmp_path, sink):
    import jax
    import jax.numpy as jnp

    from repro.kernels.stencil25 import stencil25

    src = jnp.ones((16, 16, 128), jnp.float32)
    if sink == "profiler":
        with jax.profiler.trace(str(tmp_path)):
            jax.block_until_ready(stencil25(src, block=(8, 8), interpret=True))
        assert "stencil25.call" in _profiled_host_events(tmp_path)
    else:
        tracer = trace.enable()
        jax.block_until_ready(stencil25(src, block=(8, 8), interpret=True))
        assert "stencil25.call" in tracer.span_names()


def test_entry_pick_is_counted_once_per_trace_and_select_block_is_not(monkeypatch):
    """The pick inside the jit trace of ``stencil25`` observes
    ``estimator.pick_seconds{entry=stencil25}``; a direct ``select_block``
    (as the benchmark's outside pick makes) does not."""
    import jax
    import jax.numpy as jnp

    from repro.core.machine import tpu_machine
    from repro.kernels import entry
    from repro.kernels.stencil25 import select_block, stencil25

    machine = tpu_machine("TPU v5 lite")
    monkeypatch.setattr(entry, "device_machine", lambda: machine)
    series = "estimator.pick_seconds{entry=stencil25}"

    def count():
        return metrics.snapshot()["histograms"].get(series, {"count": 0})["count"]

    shape = (48, 40, 256)  # traced by no other test of this process
    before = count()
    stencil25.trace(jax.ShapeDtypeStruct(shape, jnp.float32))
    assert count() == before + 1
    select_block(shape, 4, jnp.float32, machine=machine)
    assert count() == before + 1


def test_twophase_step_opens_its_spans_and_counts_its_picks(monkeypatch):
    """While recording, one ``twophase_step`` call opens ``twophase_step.call``
    once; its first trace opens ``lbm_step.pick`` (the D3Q15 step's pick,
    traced into the same jit) and ``lbm_d3q27.pick``, and observes
    ``estimator.pick_seconds{entry=lbm_d3q27}`` once."""
    import jax
    import jax.numpy as jnp

    from repro.core.machine import tpu_machine
    from repro.kernels import entry
    from repro.kernels.lbm_d3q15 import init_fields
    from repro.kernels.lbm_d3q27 import equilibrium, twophase_step

    f, phase, vel = init_fields((16, 16, 128))
    tracer = trace.enable()
    jax.block_until_ready(twophase_step(f, equilibrium(vel), phase, vel, block=(8, 8),
                                        phase_block=(8, 8), interpret=True))
    assert [e["name"] for e in tracer.events].count("twophase_step.call") == 1

    machine = tpu_machine("TPU v5 lite")
    monkeypatch.setattr(entry, "device_machine", lambda: machine)
    series = "estimator.pick_seconds{entry=lbm_d3q27}"

    def count():
        return metrics.snapshot()["histograms"].get(series, {"count": 0})["count"]

    shape = (40, 48, 256)  # traced by no other test of this process
    before = count()
    tracer = trace.enable()
    twophase_step.trace(*(jax.ShapeDtypeStruct((n, *shape), jnp.float32) for n in (15, 27)),
                        jax.ShapeDtypeStruct(shape, jnp.float32),
                        jax.ShapeDtypeStruct((3, *shape), jnp.float32))
    assert {"lbm_step.pick", "lbm_d3q27.pick"} <= tracer.span_names()
    assert count() == before + 1


def test_pool_sweep_aggregates_worker_spans():
    """Every pipeline phase shows up in one trace, including the per-worker
    estimate batches, and worker events keep their own pid lane."""
    tracer = trace.enable()
    res = Study("stencil25", sample=24, seed=7, machine="v100", workers=2).result()
    assert len(res.records) == 24
    names = tracer.span_names()
    for phase in (
        "study.enumerate",
        "study.trace_ir",
        "sweep",
        "sweep.store_lookup",
        "sweep.estimate_pool",
        "worker.chunk",
        "estimate.batch",
        "sweep.sort",
    ):
        assert phase in names, f"phase span {phase!r} missing from {sorted(names)}"
    pids = {e["pid"] for e in tracer.events}
    assert len(pids) >= 2, "worker events did not land in the parent trace"
    worker_batches = [
        e for e in tracer.events
        if e["name"] == "estimate.batch" and e["pid"] != os.getpid()
    ]
    assert worker_batches, "per-worker estimate batches missing"
    assert trace.validate_chrome_trace(tracer.to_chrome()) == []
    # the workers' metrics shipped home too: the per-sweep delta counts every
    # config estimated in the pool
    h = res.stats.metrics["histograms"]["estimate.batch_size{backend=gpu}"]
    assert h["sum"] == 24


def test_sweep_wall_s_is_span_duration_by_construction():
    tracer = trace.enable()
    res = Study("stencil25", sample=12, seed=7, machine="v100").result()
    sweep_ev = next(e for e in tracer.events if e["name"] == "sweep")
    assert res.stats.wall_s == pytest.approx(sweep_ev["dur"] / 1e6)


# --------------------------------------------------------------------------- #
# metrics


def test_metrics_snapshot_roundtrips_json():
    reg = metrics.MetricsRegistry()
    reg.counter("hits").inc()
    reg.counter("hits").inc(2)
    reg.counter("dropped", rule="sanity").inc(3)
    reg.gauge("entries").set(7)
    h = reg.histogram("latency", phase="estimate")
    h.observe(0.5)
    h.observe(1.5)
    reg.histogram("empty")
    snap = reg.snapshot()
    assert snap == json.loads(json.dumps(snap))
    assert snap["counters"] == {"hits": 3.0, "dropped{rule=sanity}": 3.0}
    assert snap["gauges"] == {"entries": 7.0}
    assert snap["histograms"]["latency{phase=estimate}"] == {
        "count": 2, "sum": 2.0, "min": 0.5, "max": 1.5, "mean": 1.0,
    }
    assert snap["histograms"]["empty"]["min"] is None


def test_metrics_merge_and_diff():
    a = metrics.MetricsRegistry()
    a.counter("c").inc(2)
    a.histogram("h").observe(1.0)
    b = metrics.MetricsRegistry()
    b.counter("c").inc(3)
    b.counter("worker_only").inc()
    b.histogram("h").observe(3.0)
    before = a.snapshot()
    a.merge(b.snapshot())
    after = a.snapshot()
    assert after["counters"] == {"c": 5.0, "worker_only": 1.0}
    assert after["histograms"]["h"] == {
        "count": 2, "sum": 4.0, "min": 1.0, "max": 3.0, "mean": 2.0,
    }
    d = metrics.diff(before, after)
    assert d["counters"] == {"c": 3.0, "worker_only": 1.0}
    assert d["histograms"]["h"]["count"] == 1
    assert d["histograms"]["h"]["sum"] == 3.0


def test_sweep_stats_carry_metrics_delta(tmp_path):
    store = tmp_path / "s.jsonl"
    res1 = Study(
        "stencil25", sample=8, seed=7, machine="v100", store=str(store)
    ).result()
    m1 = res1.stats.metrics
    assert m1["counters"]["sweep.cache_misses"] == 8
    assert m1["histograms"]["estimate.batch_size{backend=gpu}"]["sum"] == 8
    assert m1["histograms"]["store.append_seconds"]["count"] == 8
    # warm re-run: all hits, no estimation, and the delta says exactly that
    res2 = Study(
        "stencil25", sample=8, seed=7, machine="v100", store=str(store)
    ).result()
    m2 = res2.stats.metrics
    assert m2["counters"]["sweep.cache_hits"] == 8
    assert "estimate.batch_size{backend=gpu}" not in m2["histograms"]
    assert json.loads(json.dumps(m2)) == m2  # snapshot stays JSON-able


def test_prune_rule_counters():
    before = metrics.snapshot()
    Study(
        "stencil25", sample=24, seed=7, machine="v100",
        prune=True, keep_fraction=0.3,
    ).result()
    d = metrics.diff(before, metrics.snapshot())
    dropped = {
        k: v for k, v in d["counters"].items() if k.startswith("prune.dropped")
    }
    assert dropped.get("prune.dropped{rule=roofline}", 0) > 0


def test_alias_layer_counters_and_warm_trace_free_sweep(tmp_path):
    """Cold aliased sweep: every candidate is an alias miss (then traced);
    warm re-run: all alias hits, zero store misses, and — the service-layer
    contract — NO study.trace_ir span at all."""
    store = tmp_path / "st.jsonl"
    alias = tmp_path / "alias.jsonl"
    before = metrics.snapshot()
    Study("stencil25", sample=4, seed=7, machine=V100, store=store, alias=alias).result()
    d = metrics.diff(before, metrics.snapshot())
    assert d["counters"]["alias.misses"] == 4
    assert d["counters"].get("alias.hits", 0) == 0

    before = metrics.snapshot()
    tracer = trace.enable()
    res = Study(
        "stencil25", sample=4, seed=7, machine=V100, store=store, alias=alias
    ).result()
    names = tracer.span_names()
    trace.disable()
    d = metrics.diff(before, metrics.snapshot())
    assert d["counters"]["alias.hits"] == 4
    assert res.stats.cache_hits == 4 and res.stats.evaluated == 0
    assert "study.trace_ir" not in names
    assert "study.enumerate" in names and "sweep.store_lookup" in names


def test_pallas_probe_metrics():
    before = metrics.snapshot()
    Study("attention", backend="tpu", configs=None, machine=TPU_V5E).result()
    d = metrics.diff(before, metrics.snapshot())
    assert d["counters"]["pallas.probes"] > 0
    assert d["histograms"]["pallas.probes_per_trace"]["count"] > 0


# --------------------------------------------------------------------------- #
# overhead + identity with tracing off


def test_disabled_overhead_under_two_percent_on_full_stencil_sweep():
    """Generous bound: (measured cost of one disabled span) x (span count an
    identical traced sweep records) must stay under 2% of the sweep's wall
    clock.  Direct A/B wall-clock comparison is too noisy for CI; this bounds
    the same quantity from its parts."""
    assert trace.active() is None
    res = Study("stencil25", machine="v100").result()  # full 162-config space
    assert res.stats.candidates == 162

    tracer = trace.enable()
    res_traced = Study("stencil25", machine="v100").result()
    n_spans = len(tracer.events)
    trace.disable()

    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("x"):
            pass
    per_span = (time.perf_counter() - t0) / n
    budget = 0.02 * min(res.stats.wall_s, res_traced.stats.wall_s)
    assert n_spans * per_span < budget, (
        f"{n_spans} spans x {per_span * 1e6:.2f}us = "
        f"{n_spans * per_span * 1e3:.3f}ms exceeds 2% budget {budget * 1e3:.3f}ms"
    )


def test_records_identical_with_tracing_on_and_off():
    off = Study("stencil25", sample=24, seed=7, machine="v100").result()
    trace.enable()
    on = Study("stencil25", sample=24, seed=7, machine="v100").result()
    trace.disable()
    assert [r.config for r in off.records] == [r.config for r in on.records]
    assert [r.metrics for r in off.records] == [r.metrics for r in on.records]
    assert [r.time_s for r in off.records] == [r.time_s for r in on.records]


# --------------------------------------------------------------------------- #
# explain


EXPLAIN_CFG = {"block": (64, 2, 8), "fold": (1, 2, 1)}
EXPLAIN_GOLDENS = {
    "V100": "explain_stencil25_v100.txt",
    "A100": "explain_stencil25_a100.txt",
}


@pytest.mark.parametrize("machine", sorted(EXPLAIN_GOLDENS))
def test_explain_golden_stable(machine):
    study = Study("stencil25", sample=24, seed=7, machine=machine.lower())
    rep = study.explain(dict(EXPLAIN_CFG))
    assert isinstance(rep, ExplainReport)
    got = rep.render() + "\n"
    path = GOLDEN_DIR / EXPLAIN_GOLDENS[machine]
    if REGEN:
        path.write_text(got)
        pytest.skip(f"regenerated {path}")
    assert path.exists(), (
        f"golden file {path} missing — generate with REPRO_REGEN_GOLDEN=1"
    )
    assert got == path.read_text(), (
        f"explain output diverged from {path.name}; regenerate with "
        "REPRO_REGEN_GOLDEN=1 if the change is intended"
    )


def test_explain_report_contents_gpu():
    study = Study("stencil25", sample=24, seed=7, machine="v100")
    rep = study.explain("best")
    assert rep.backend == "gpu" and rep.feasible
    assert rep.limiter.limiter in rep.limiter.terms
    assert rep.limiter.runner_up in rep.limiter.terms
    assert 0.0 <= rep.limiter.margin <= 1.0
    levels = {lv.level: lv for lv in rep.levels}
    assert set(levels) == {"DRAM<->L2", "L2<->L1", "L1->reg"}
    dram = levels["DRAM<->L2"]
    assert dram.total == pytest.approx(sum(dram.parts.values()))
    assert dram.oversubscription > 0
    assert not rep.prune.would_prune
    # matches the ranked record exactly (no second model path)
    best = study.top(1)[0]
    assert rep.score["glups"] == best.metrics["glups"]
    # serializable, and stable once tuples have normalized to lists
    j = json.loads(json.dumps(rep.to_json()))
    assert j == json.loads(json.dumps(j))


def test_explain_rank_and_pruned_config():
    study = Study(
        "stencil25", sample=24, seed=7, machine="v100",
        prune=True, keep_fraction=0.3,
    )
    res = study.result()
    by_rank = study.explain(1)
    assert by_rank.config == res.records[1].config
    # a config the sweep pruned away is estimated on demand and gets the
    # matching prune verdict, cutoff included
    kept = {json.dumps(r.config, sort_keys=True, default=list) for r in res.records}
    pruned = next(
        c.config
        for c in study._candidates()
        if json.dumps(c.config, sort_keys=True, default=list) not in kept
    )
    rep = study.explain(dict(pruned))
    assert rep.prune.would_prune
    assert rep.prune.rule in ("sanity", "roofline")
    if rep.prune.rule == "roofline":
        assert f"{res.prune_report.cutoff_bound:.1f}" in rep.prune.detail
    with pytest.raises(KeyError, match="not a candidate"):
        study.explain({"block": (3, 5, 7), "fold": (1, 1, 1)})
    with pytest.raises(IndexError, match="out of range"):
        study.explain(10_000)


def test_explain_cross_machine_divergence():
    study = Study("stencil25", sample=24, seed=7, machines=["v100", "a100"])
    cm = study.explain(dict(EXPLAIN_CFG))
    assert isinstance(cm, CrossMachineExplain)
    assert cm.machines == ["V100", "A100"]
    div = cm.divergence()
    assert {d["level"] for d in div} == {"DRAM<->L2", "L2<->L1", "L1->reg"}
    for d in div:
        assert set(d["volumes"]) == {"V100", "A100"}
        assert d["ratio"] >= 1.0
    # L1-level traffic is machine-independent; DRAM traffic is not (L2 size
    # differs), so the most divergent level must be a DRAM/L2 one
    assert div[0]["level"] != "L1->reg"
    assert "level divergence" in cm.render()


def test_explain_tpu_feasible_and_vmem_gated():
    study = Study("attention", backend="tpu", configs=_tpu_cfgs(), machine=TPU_V5E)
    rep = study.explain("best")
    assert rep.backend == "tpu" and rep.feasible
    assert rep.limiter.limiter in ("HBM", "COMPUTE", "GRID")
    levels = {lv.level: lv for lv in rep.levels}
    assert set(levels) == {"HBM<->VMEM", "VMEM"}
    hbm = levels["HBM<->VMEM"]
    assert hbm.total == pytest.approx(sum(hbm.parts.values()))
    # the recomputed estimate matches the record (single model path)
    assert rep.score["time_s"] == study.top(1)[0].metrics["time_s"]
    # the VMEM-infeasible candidate gets the hard-gate verdict
    gated = study.explain({"name": "huge", "bz": 256})
    assert not gated.feasible
    assert gated.prune.would_prune and gated.prune.rule == "vmem"
    assert gated.limiter.limiter == "VMEM"
