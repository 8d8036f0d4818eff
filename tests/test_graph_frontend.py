"""Graph frontend: tracing a model step into a KernelDAG, the single-device
bit-identity contract (whole-model time == the exact fold of per-kernel
estimates), fingerprint dedup (each unique kernel estimated once), mesh
spelling round-trips, and sharding-implied collectives."""
from __future__ import annotations

import pytest

from repro.configs import get_arch
from repro.core.estimator import EstimateCache
from repro.core.machine import (
    SINGLE_DEVICE_MESH,
    A100_40GB,
    TPU_V5E,
    MeshSpec,
)
from repro.explore.study import Study
from repro.graph import (
    COLLECTIVE_KINDS,
    backend_for,
    estimate_dag,
    step_time,
    trace_step,
)
from repro.launch.mesh import mesh_spec
from repro.obs import metrics as obs_metrics

RWKV = get_arch("rwkv6-1.6b").smoke()


# --------------------------------------------------------------------------- #
# mesh spelling round-trips
# --------------------------------------------------------------------------- #


def test_mesh_spec_roundtrips():
    want = MeshSpec(axes=(("data", 2), ("model", 2)))
    assert mesh_spec(None) == SINGLE_DEVICE_MESH
    assert mesh_spec(want) is want
    assert mesh_spec("data=2,model=2") == want
    assert mesh_spec({"data": 2, "model": 2}) == want
    assert mesh_spec((("data", 2), ("model", 2))) == want


def test_mesh_spec_reads_jax_mesh_axis_names():
    jax = pytest.importorskip("jax")
    am = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
    spec = mesh_spec(am)
    assert spec.axes == (("data", 4), ("model", 2))
    # and the traced DAG carries those axis names on its collectives
    dag = trace_step(RWKV, batch=8, seq=64, mesh=am, backend="gpu")
    axes = {n.axis for n in dag.collective_nodes}
    assert axes and axes <= {"data", "model"}


def test_mesh_spec_rejects_nonsense():
    with pytest.raises(TypeError):
        mesh_spec(3.14)
    with pytest.raises(ValueError):
        mesh_spec("data:2")


# --------------------------------------------------------------------------- #
# single-device bit-identity + dedup
# --------------------------------------------------------------------------- #


def test_single_device_step_is_exact_sum_of_kernel_estimates():
    rep = Study.step_time(RWKV, A100_40GB, batch=8, seq=128)
    dag = rep.dag
    assert not dag.collective_nodes  # single device: no comm
    # independently estimate every node's kernel, one estimator call each,
    # fresh caches — then fold in schedule order exactly like the replayer
    from repro.explore.registry import get_estimator

    est = get_estimator("gpu", "sym", None)
    expected = 0.0
    for s in rep.replay.schedule:
        node = dag.nodes[s.node_id]
        (rec,) = est.estimate_batch([node.ir], A100_40GB, cache=EstimateCache())
        expected += rec.time_s * node.repeat
    assert rep.step_time_s == expected  # bit-identical, not approx


def test_each_unique_fingerprint_estimated_exactly_once():
    dag = trace_step(RWKV, batch=8, seq=128, backend="gpu")
    fps = dag.unique_fingerprints()
    assert 1 < len(fps) < len(dag.compute_nodes)  # real dedup happens
    before = obs_metrics.snapshot()
    durations, unique = estimate_dag(dag, A100_40GB)
    d = obs_metrics.diff(before, obs_metrics.snapshot())
    assert d["counters"]["graph.estimated{backend=gpu}"] == len(fps)
    assert set(unique) == set(fps)
    # every node's duration is its unique record's time x repeat, exactly
    for node in dag.compute_nodes:
        assert durations[node.id] == unique[node.fingerprint].time_s * node.repeat


def test_step_time_reuses_shared_cache_across_calls():
    cache = EstimateCache()
    a = step_time(RWKV, A100_40GB, batch=8, seq=128, cache=cache)
    misses = cache.misses
    b = step_time(RWKV, A100_40GB, batch=8, seq=128, cache=cache)
    assert b.step_time_s == a.step_time_s
    assert cache.misses == misses  # second pass is all cache hits


# --------------------------------------------------------------------------- #
# multi-device sharding
# --------------------------------------------------------------------------- #


def test_sharded_step_emits_collectives_and_shrinks_kernels():
    mesh = "data=2,model=2"
    dag1 = trace_step(RWKV, batch=8, seq=128, backend="gpu")
    dag4 = trace_step(RWKV, batch=8, seq=128, mesh=mesh, backend="gpu")
    kinds = {n.comm_kind for n in dag4.collective_nodes}
    assert kinds and kinds <= set(COLLECTIVE_KINDS)
    for n in dag4.collective_nodes:
        assert n.comm_bytes > 0 and n.axis in ("data", "model")
    # tp all-reduces ride 'model'; the traced matmuls shrink vs single device
    assert {n.axis for n in dag4.collective_nodes if n.comm_kind == "all-reduce"} == {
        "model"
    }
    m1 = max(n.ir.meta["n"] for n in dag1.compute_nodes if n.ir.meta.get("app") == "matmul")
    m4 = max(n.ir.meta["n"] for n in dag4.compute_nodes if n.ir.meta.get("app") == "matmul")
    assert m4 < m1


def test_train_step_adds_backward_grads_and_optimizer():
    fwd = trace_step(RWKV, batch=8, seq=128, mesh="data=2,model=1", backend="gpu")
    trn = trace_step(RWKV, batch=8, seq=128, mesh="data=2,model=1", backend="gpu",
                     kind="train")
    assert len(trn) > 2 * len(fwd)
    rs = [n for n in trn.collective_nodes if n.comm_kind == "reduce-scatter"]
    assert len(rs) == RWKV.n_layers  # one gradient reduce-scatter per layer
    assert any("optimizer" in nid for nid in trn.nodes)


def test_all_families_trace_and_validate():
    for arch in ("olmo-1b", "zamba2-7b", "dbrx-132b", "rwkv6-1.6b"):
        for backend in ("gpu", "tpu"):
            dag = trace_step(get_arch(arch).smoke(), batch=4, seq=64,
                             mesh="data=2,model=2", backend=backend)
            dag.validate()
            assert dag.compute_nodes and dag.collective_nodes


def test_backend_mismatch_rejected():
    dag = trace_step(RWKV, batch=4, seq=64, backend="gpu")
    assert backend_for(TPU_V5E) == "tpu"
    with pytest.raises(ValueError, match="traced for backend"):
        estimate_dag(dag, TPU_V5E)


def test_tpu_whole_model_step():
    rep = step_time(RWKV, "TPUv5e", mesh="data=4,model=1", batch=8, seq=128)
    assert rep.step_time_s > 0
    assert all(rec.feasible for rec in rep.unique.values())
    doc = rep.replay.to_chrome()
    from repro.obs.trace import validate_chrome_trace

    validate_chrome_trace(doc)


def test_report_render_and_json_shapes():
    rep = step_time(RWKV, "A100", mesh="data=2,model=2", batch=8, seq=128)
    text = rep.render()
    for needle in ("predicted step time", "critical path", "overlap", "limiters"):
        assert needle in text
    doc = rep.to_json()
    assert doc["step_time_s"] == rep.step_time_s
    assert doc["n_nodes"] == len(rep.dag)
    assert doc["critical_path"] and 0.0 <= doc["overlap_fraction"] <= 1.0
    assert set(doc["utilization"]) == {"0", "1", "2", "3"}
    assert abs(sum(doc["limiters"].values()) - 1.0) < 1e-9
