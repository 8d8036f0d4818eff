"""Discrete-event replay semantics: hand-built DAGs with exact expected step
times, plus property tests (always-on seeded-random + optional hypothesis):
the makespan dominates both the per-lane busy sums and the longest weighted
dependency path, and is invariant under topological-order permutation of node
insertion."""
from __future__ import annotations

import random

import numpy as np
import pytest

from repro.core.machine import SINGLE_DEVICE_MESH, MeshSpec
from repro.graph import GraphNode, KernelDAG, Replayer, axis_groups
from repro.obs.trace import validate_chrome_trace

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

MESH_1 = SINGLE_DEVICE_MESH
MESH_D2 = MeshSpec(axes=(("data", 2),))
MESH_2X2 = MeshSpec(axes=(("data", 2), ("model", 2)))


def _compute(nid, t, deps=()):
    return GraphNode(id=nid, kind="compute", time_s=t, deps=tuple(deps))


def _coll(nid, t, axis, deps=(), kind="all-reduce"):
    return GraphNode(
        id=nid, kind="collective", comm_kind=kind, axis=axis, time_s=t,
        deps=tuple(deps),
    )


def _dag(mesh, nodes):
    dag = KernelDAG(mesh=mesh)
    for n in nodes:
        dag.add(n)
    return dag


# --------------------------------------------------------------------------- #
# exact makespans on hand-built DAGs
# --------------------------------------------------------------------------- #


def test_chain_exact():
    dag = _dag(MESH_1, [
        _compute("a", 1.0),
        _compute("b", 2.0, ["a"]),
        _compute("c", 0.5, ["b"]),
    ])
    res = Replayer(dag).run()
    assert res.makespan == 1.0 + 2.0 + 0.5  # exact float fold
    assert [s.node_id for s in res.critical_path()] == ["a", "b", "c"]
    assert res.utilization() == {0: 1.0}
    assert all(v == 0.0 for v in res.slack().values())


def test_diamond_single_device_serializes():
    dag = _dag(MESH_1, [
        _compute("a", 1.0),
        _compute("b", 2.0, ["a"]),
        _compute("c", 3.0, ["a"]),
        _compute("d", 1.0, ["b", "c"]),
    ])
    res = Replayer(dag).run()
    # one compute lane: the diamond degenerates to the exact serial sum,
    # scheduled in id order at equal ready times (a, b, c, d)
    assert res.makespan == 1.0 + 2.0 + 3.0 + 1.0
    order = [s.node_id for s in res.schedule]
    assert order == ["a", "b", "c", "d"]
    # d's binding constraint is its last-finishing dependency c
    d = next(s for s in res.schedule if s.node_id == "d")
    assert d.binding == "dep" and d.pred[0] == "c"


def test_fork_join_spmd_is_device_count_invariant():
    nodes = lambda: [  # noqa: E731
        _compute("a", 1.0),
        _compute("b", 2.0, ["a"]),
        _compute("c", 3.0, ["a"]),
        _compute("d", 1.0, ["b", "c"]),
    ]
    t1 = Replayer(_dag(MESH_1, nodes())).run().makespan
    t2 = Replayer(_dag(MESH_D2, nodes())).run().makespan
    # SPMD compute runs on every device's own lane: adding devices without
    # collectives changes nothing
    assert t1 == t2 == 7.0


def test_comm_overlap_hidden_under_compute():
    dag = _dag(MESH_D2, [
        _compute("a", 4.0),
        _coll("g", 2.0, "data", kind="all-gather"),
        _compute("b", 1.0, ["a", "g"]),
    ])
    res = Replayer(dag).run()
    # comm lane runs g during a; b starts at max(4, 2) = 4
    assert res.makespan == 5.0
    assert res.overlap_fraction() == 1.0  # the gather hides entirely
    g = next(s for s in res.schedule if s.node_id == "g")
    assert g.devices == (0, 1) and g.start == 0.0
    b = next(s for s in res.schedule if s.node_id == "b" and s.devices == (0,))
    assert b.binding == "dep" and b.pred == ("a", 0)


def test_comm_on_dependency_chain_is_exposed():
    dag = _dag(MESH_D2, [
        _compute("a", 1.0),
        _coll("r", 2.0, "data", deps=["a"]),
        _compute("b", 1.0, ["r"]),
    ])
    res = Replayer(dag).run()
    assert res.makespan == 4.0
    assert res.overlap_fraction() == 0.0
    assert [s.node_id for s in res.critical_path()] == ["a", "r", "b"]


def test_collective_groups_by_axis():
    # model-axis collective on a 2x2 mesh: two groups, each over the devices
    # differing only in their model coordinate
    assert axis_groups(MESH_2X2, "model") == [(0, 1), (2, 3)]
    assert axis_groups(MESH_2X2, "data") == [(0, 2), (1, 3)]
    dag = _dag(MESH_2X2, [
        _compute("a", 1.0),
        _coll("r", 0.5, "model", deps=["a"]),
        _compute("b", 1.0, ["r"]),
    ])
    res = Replayer(dag).run()
    assert res.makespan == 2.5
    groups = sorted(s.devices for s in res.schedule if s.node_id == "r")
    assert groups == [(0, 1), (2, 3)]


def test_repeat_is_a_duration_multiplier_via_durations_map():
    dag = KernelDAG(mesh=MESH_1)
    dag.add(GraphNode(id="k", kind="compute", time_s=1.0, repeat=4))
    # the Replayer trusts the durations map (estimate x repeat upstream)
    res = Replayer(dag, {"k": 4 * 0.75}).run()
    assert res.makespan == 3.0


def test_missing_and_negative_durations_rejected():
    bare = _dag(MESH_1, [GraphNode(id="k", kind="compute")])
    with pytest.raises(ValueError, match="neither IR nor time_s"):
        Replayer(bare)  # validate() rejects the undurable node up front
    from repro.graph.kernels import elementwise_ir

    ir, _ = elementwise_ir(256, backend="gpu")
    dag = KernelDAG(mesh=MESH_1)
    dag.compute("k", ir)
    with pytest.raises(ValueError, match="no duration"):
        Replayer(dag)  # has an IR but neither a durations entry nor time_s
    with pytest.raises(ValueError, match="negative"):
        Replayer(dag, {"k": -1.0})


def test_cycle_rejected():
    dag = _dag(MESH_1, [_compute("a", 1.0, ["b"]), _compute("b", 1.0, ["a"])])
    with pytest.raises(ValueError, match="cycle"):
        Replayer(dag)


def test_chrome_export_validates(tmp_path):
    dag = _dag(MESH_D2, [
        _compute("a", 1.0),
        _coll("g", 2.0, "data"),
        _compute("b", 1.0, ["a", "g"]),
    ])
    res = Replayer(dag).run()
    doc = res.to_chrome()
    validate_chrome_trace(doc)
    # one X event per (instance, device) + one process_name meta per device
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 2 * 2 + 2  # a and b on 2 devices, g on both group members
    import json

    p = tmp_path / "replay.json"
    n = res.export(p)
    validate_chrome_trace(json.loads(p.read_text()))
    assert n == len(doc["traceEvents"])


# --------------------------------------------------------------------------- #
# properties (seeded random: always on)
# --------------------------------------------------------------------------- #


def _random_nodes(rng: random.Random, mesh: MeshSpec):
    n = rng.randint(3, 10)
    comm_axes = [a for a, s in mesh.axes if s > 1]
    nodes = []
    for i in range(n):
        nid = f"n{i:02d}"
        deps = tuple(f"n{j:02d}" for j in range(i) if rng.random() < 0.4)
        t = round(rng.uniform(0.05, 2.0), 3)
        if comm_axes and rng.random() < 0.3:
            nodes.append(_coll(nid, t, rng.choice(comm_axes), deps))
        else:
            nodes.append(_compute(nid, t, deps))
    return nodes


def _longest_path(nodes) -> float:
    t = {}
    by_id = {n.id: n for n in nodes}
    def finish(nid):
        if nid not in t:
            n = by_id[nid]
            t[nid] = n.time_s + max((finish(d) for d in n.deps), default=0.0)
        return t[nid]
    return max(finish(n.id) for n in nodes)


@pytest.mark.parametrize("seed", range(25))
def test_makespan_dominates_busy_and_longest_path(seed):
    rng = random.Random(seed)
    mesh = rng.choice([MESH_1, MESH_D2, MESH_2X2])
    nodes = _random_nodes(rng, mesh)
    res = Replayer(_dag(mesh, nodes)).run()
    eps = 1e-9
    assert res.makespan + eps >= max(res.compute_busy.values())
    assert res.makespan + eps >= max(res.comm_busy.values(), default=0.0)
    assert res.makespan + eps >= _longest_path(nodes)
    slack = res.slack()
    assert all(v >= -eps for v in slack.values())
    assert min(slack.values()) <= eps  # the closing chain has zero slack
    validate_chrome_trace(res.to_chrome())


@pytest.mark.parametrize("seed", range(25))
def test_insertion_order_permutation_invariance(seed):
    rng = random.Random(1000 + seed)
    mesh = rng.choice([MESH_1, MESH_D2, MESH_2X2])
    nodes = _random_nodes(rng, mesh)
    base = Replayer(_dag(mesh, nodes)).run()
    for _ in range(3):
        shuffled = list(nodes)
        rng.shuffle(shuffled)  # deps may reference ids added later: allowed
        perm = Replayer(_dag(mesh, shuffled)).run()
        assert perm.makespan == base.makespan  # bit-identical, not approx
        assert [s.node_id for s in perm.critical_path()] == [
            s.node_id for s in base.critical_path()
        ]
        assert perm.compute_busy == base.compute_busy


# --------------------------------------------------------------------------- #
# properties (hypothesis: optional dev dependency)
# --------------------------------------------------------------------------- #

if HAVE_HYPOTHESIS:

    @st.composite
    def dag_strategy(draw):
        mesh = draw(st.sampled_from([MESH_1, MESH_D2, MESH_2X2]))
        n = draw(st.integers(3, 10))
        comm_axes = [a for a, s in mesh.axes if s > 1]
        nodes = []
        for i in range(n):
            deps = tuple(
                f"n{j:02d}" for j in range(i) if draw(st.booleans())
            )
            # width=32 draws float32 values, so the bounds must be float32s
            t = draw(st.floats(float(np.float32(0.05)), 2.0, allow_nan=False, width=32))
            is_comm = comm_axes and draw(st.booleans())
            if is_comm:
                nodes.append(_coll(f"n{i:02d}", t, draw(st.sampled_from(comm_axes)), deps))
            else:
                nodes.append(_compute(f"n{i:02d}", t, deps))
        return mesh, nodes

    @settings(max_examples=50, deadline=None)
    @given(dag_strategy(), st.randoms(use_true_random=False))
    def test_hypothesis_invariants(mesh_nodes, rnd):
        mesh, nodes = mesh_nodes
        res = Replayer(_dag(mesh, nodes)).run()
        eps = 1e-9
        assert res.makespan + eps >= max(res.compute_busy.values())
        assert res.makespan + eps >= _longest_path(nodes)
        shuffled = list(nodes)
        rnd.shuffle(shuffled)
        assert Replayer(_dag(mesh, shuffled)).run().makespan == res.makespan
