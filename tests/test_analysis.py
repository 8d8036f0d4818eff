"""Static access auditor: differential, fixture, and integration tests.

The differential section generates small random affine geometries with a
seeded RNG and checks BOTH analyzer tiers against an independent brute-force
enumeration written here from the race/bounds/coverage/alias definitions —
not against the analyzer's own enumeration code.  ``tests/
test_analysis_property.py`` re-runs the same comparison under hypothesis.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from repro import analysis
from repro.analysis import EXPECTED_RULES, FIXTURES, Finding, LintError
from repro.analysis.passes import field_extent, run_correctness_passes
from repro.frontend.ir import AccessIR, IRAccess, IRField


# --------------------------------------------------------------------------- #
# brute-force reference (independent of repro.analysis.affine)


def _addrs(a: IRAccess, pts) -> list[int]:
    row, off = a.coeffs[0], a.offset[0]
    return [sum(c * p for c, p in zip(row, pt)) + off for pt in pts]


def brute_force(ir: AccessIR) -> dict:
    """Ground-truth verdicts by plain enumeration of every iteration point."""
    fmap = ir.field_map
    pts = list(np.ndindex(*ir.iter_shape))
    vals = {i: _addrs(a, pts) for i, a in enumerate(ir.accesses)}
    extent = {f.name: field_extent(f) for f in ir.fields}

    oob = {
        a.field
        for i, a in enumerate(ir.accesses)
        if any(v < 0 or v >= extent[a.field] for v in vals[i])
    }

    ww, rw, gap = set(), set(), set()
    fields_with_stores = {a.field for a in ir.accesses if a.is_store}
    for name in fields_with_stores:
        writers: dict[int, set[int]] = {}
        for i, a in enumerate(ir.accesses):
            if a.field == name and a.is_store:
                for p, v in enumerate(vals[i]):
                    writers.setdefault(v, set()).add(p)
        if any(len(ps) > 1 for ps in writers.values()):
            ww.add(name)
        for i, a in enumerate(ir.accesses):
            if a.field == name and not a.is_store:
                for p, v in enumerate(vals[i]):
                    if v in writers and (writers[v] - {p}):
                        rw.add(name)
                        break
        covered = {v for v in writers if 0 <= v < extent[name]}
        if len(covered) < extent[name]:
            gap.add(name)

    alias = set()
    per_field_image = {
        f.name: {v for i, a in enumerate(ir.accesses) if a.field == f.name
                 for v in vals[i]}
        for f in ir.fields
    }
    for x in range(len(ir.fields)):
        for y in range(x + 1, len(ir.fields)):
            f, g = ir.fields[x], ir.fields[y]
            if (f.shape, f.dtype_bits, f.alignment, f.components) != (
                g.shape, g.dtype_bits, g.alignment, g.components
            ):
                continue
            fi, gi = per_field_image[f.name], per_field_image[g.name]
            if fi and fi == gi:
                alias.add((f.name, g.name))
    return {"oob": oob, "ww": ww, "rw": rw, "gap": gap, "alias": alias}


def _verdicts(findings) -> dict:
    """Collapse findings to per-field rule verdicts (the differential unit)."""
    out = {"oob": set(), "ww": set(), "rw": set(), "gap": set(),
           "alias": set(), "potential": set()}
    for f in findings:
        if f.rule.startswith("bounds."):
            out["oob"].add(f.field)
        elif f.rule == "race.write_write":
            out["ww"].add(f.field)
        elif f.rule == "race.read_write":
            out["rw"].add(f.field)
        elif f.rule == "race.potential":
            out["potential"].add(f.field)
        elif f.rule == "coverage.gap":
            out["gap"].add(f.field)
        elif f.rule == "alias.identical_field":
            out["alias"].add(f.field)
    return out


def random_ir(rng: np.random.Generator) -> AccessIR:
    ndim = int(rng.integers(1, 3))
    iter_shape = tuple(int(v) for v in rng.integers(1, 7, size=ndim))
    nfields = int(rng.integers(1, 3))
    fields = tuple(
        IRField(name=f"f{k}", shape=(int(rng.integers(4, 40)),))
        for k in range(nfields)
    )
    accesses = []
    for _ in range(int(rng.integers(1, 4))):
        f = fields[int(rng.integers(0, nfields))]
        row = tuple(int(v) for v in rng.integers(-3, 4, size=ndim))
        accesses.append(
            IRAccess(
                field=f.name,
                coeffs=(row,),
                offset=(int(rng.integers(-4, 8)),),
                is_store=bool(rng.integers(0, 2)),
            )
        )
    return AccessIR(
        name="rand", fields=fields, accesses=tuple(accesses),
        iter_shape=iter_shape, block=iter_shape,
    )


@pytest.mark.parametrize("seed", range(4))
def test_differential_enum_vs_brute_force(seed):
    """The enum tier must agree with brute force on every verdict, exactly."""
    rng = np.random.default_rng(seed)
    for _ in range(60):
        ir = random_ir(rng)
        truth = brute_force(ir)
        got = _verdicts(run_correctness_passes(ir, mode="enum"))
        assert got["oob"] == truth["oob"], ir
        assert got["ww"] == truth["ww"], ir
        assert got["rw"] == truth["rw"], ir
        assert got["gap"] == truth["gap"], ir
        assert got["alias"] == {a for a, _ in truth["alias"]}, ir
        assert not got["potential"], ir


@pytest.mark.parametrize("seed", range(4))
def test_differential_structured_vs_brute_force(seed):
    """The structured tier is SOUND on the same geometries: exact bounds /
    coverage / alias / write-write verdicts, and read-write races are never
    silently passed — a load map it cannot prove single-visit degrades to
    ``race.potential`` (warn) instead of a clean bill.

    Sanctioned asymmetries vs brute force:
    * an rw race on a field whose store is already ww-racy may be subsumed by
      the (more severe) ww finding;
    * a non-injective load overlapping a store degrades to ``race.potential``
      whether or not the collision lands on a shared element.
    """
    rng = np.random.default_rng(1000 + seed)
    for _ in range(60):
        ir = random_ir(rng)
        truth = brute_force(ir)
        got = _verdicts(run_correctness_passes(ir, mode="structured"))
        assert got["oob"] == truth["oob"], ir
        assert got["ww"] == truth["ww"], ir
        assert got["rw"] - truth["rw"] == set(), (ir, "rw false positive")
        assert truth["rw"] - truth["ww"] <= got["rw"] | got["potential"], (
            ir, "rw race silently passed"
        )
        # potential only ever fires where a load and a store share a field
        loaded = {a.field for a in ir.accesses if not a.is_store}
        stored = {a.field for a in ir.accesses if a.is_store}
        assert got["potential"] <= (loaded & stored), ir
        assert got["gap"] == truth["gap"], ir
        assert got["alias"] == {a for a, _ in truth["alias"]}, ir


def test_fixtures_fire_expected_rules_in_both_tiers():
    for name, build in FIXTURES.items():
        ir = build()
        want = EXPECTED_RULES[name]
        modes = ("auto",) if ir.granularity == "block" else ("enum", "structured")
        for mode in modes:
            rules = {f.rule for f in run_correctness_passes(ir, mode=mode)}
            assert want in rules, f"{name} [{mode}]: {want} not in {rules}"


def test_fixture_witnesses_actually_collide():
    """A race witness is two iteration points that map to one element —
    re-evaluate the affine maps at the reported points and check."""
    for name in ("racy_store", "inplace_update"):
        ir = FIXTURES[name]()
        findings = run_correctness_passes(ir, mode="enum")
        f = next(f for f in findings if f.rule == EXPECTED_RULES[name])
        assert len(f.witness) == 2
        t, u = f.witness
        assert t != u
        accs = [a for a in ir.accesses if a.field == f.field]
        addrs_t = {_addrs(a, [t])[0] for a in accs}
        addrs_u = {_addrs(a, [u])[0] for a in accs if a.is_store}
        assert f.address in addrs_t and f.address in addrs_u


def test_bounds_witness_is_out_of_bounds():
    ir = FIXTURES["oob_store"]()
    f = next(
        f for f in run_correctness_passes(ir) if f.rule == "bounds.oob"
    )
    (wit,) = f.witness
    addr = _addrs(ir.accesses[f.access], [wit])[0]
    assert addr < 0 or addr >= field_extent(ir.field_map[f.field])


# --------------------------------------------------------------------------- #
# analyze_ir: caching, rule filtering, report schema


def test_analyze_ir_caches_on_structure_not_block():
    analysis.clear_cache()
    ir1 = FIXTURES["racy_store"]()
    rep1 = analysis.analyze_ir(ir1)
    # same maps, different launch block -> same correctness analysis (cached)
    ir2 = AccessIR(
        name="renamed", fields=ir1.fields, accesses=ir1.accesses,
        iter_shape=ir1.iter_shape, block=(4, 4),
    )
    from repro.obs import metrics as obs_metrics

    before = obs_metrics.counter("lint.cache_hits").value
    rep2 = analysis.analyze_ir(ir2)
    assert obs_metrics.counter("lint.cache_hits").value == before + 1
    assert {f.rule for f in rep1.findings} == {f.rule for f in rep2.findings}


def test_analyze_ir_rule_prefix_filter():
    rep = analysis.analyze_ir(
        FIXTURES["racy_store"](), rules=("race",), cache=False
    )
    assert rep.findings and all(f.rule.startswith("race") for f in rep.findings)


def test_report_json_roundtrip_validates():
    rep = analysis.analyze_ir(FIXTURES["oob_halo"](), "V100", cache=False)
    doc = json.loads(json.dumps(rep.to_json()))
    assert analysis.validate_report_json(doc) == []
    assert doc["counts"]["warn"] >= 1
    bad = dict(doc, schema="nope")
    assert analysis.validate_report_json(bad)


def test_findings_coerce_numpy_witnesses():
    f = Finding(
        rule="race.write_write", severity="error", message="m",
        witness=((np.int64(1), np.int64(2)),), address=np.int64(3),
    )
    json.dumps(f.to_json())  # must not raise
    assert f.witness == ((1, 2),) and f.address == 3


# --------------------------------------------------------------------------- #
# Study / DAG gating


def test_study_lint_gate_rejects_racy_ir_before_estimation():
    from repro.explore.study import Study
    from repro.frontend.lower import lower_tpu

    cfg = lower_tpu(FIXTURES["block_revisit_parallel"]())
    study = Study("attention", backend="tpu", configs=[cfg],
                  machine="TPUv5e", lint="error")
    with pytest.raises(LintError) as exc:
        study.run()
    assert "race.write_write" in str(exc.value)
    assert len(study.cache) == 0  # nothing was estimated


def test_study_lint_annotate_and_warn():
    from repro.explore.study import Study

    cfgs = [{"block": (32, 4, 8), "fold": (1, 1, 1)}]
    study = Study("stencil25", configs=cfgs, lint="annotate")
    study.run()
    assert len(study.lint_reports) == 1
    rep = next(iter(study.lint_reports.values()))
    assert rep.ok("error")
    # the stencil halo is a warn -> lint="warn" must gate it
    strict = Study("stencil25", configs=cfgs, lint="warn")
    with pytest.raises(LintError):
        strict.run()


def test_dag_lint_gates_and_annotates():
    from repro.core.machine import MeshSpec
    from repro.graph.dag import KernelDAG

    dag = KernelDAG(mesh=MeshSpec(axes=(("data", 1),)))
    dag.compute("n0", FIXTURES["racy_store"]())
    reports = dag.lint()
    assert set(reports) == {"n0"}
    with pytest.raises(LintError):
        dag.lint(threshold="error")


# --------------------------------------------------------------------------- #
# frontend satellites: IRAccess validation + non-affine provenance


def test_iraccess_normalizes_numpy_and_rejects_floats():
    a = IRAccess(
        field="x", coeffs=np.array([[1, 2]]), offset=(np.int64(3),)
    )
    assert a.coeffs == ((1, 2),) and a.offset == (3,)
    with pytest.raises(TypeError, match="coefficient 1.5"):
        IRAccess(field="x", coeffs=((1.5,),), offset=(0,))
    with pytest.raises(ValueError):
        IRAccess(field="x", coeffs=((1,),), offset=(0,), tile=(0,))


def test_non_affine_error_carries_provenance_and_finding():
    from repro.frontend.pallas import NonAffineIndexMapError, trace_index_map

    clamped = lambda i: (min(i + 1, 2),)  # noqa: E731
    with pytest.raises(NonAffineIndexMapError) as exc:
        trace_index_map(clamped, (4,), kernel="clamped", operand="x")
    e = exc.value
    assert e.kernel == "clamped" and e.operand == "x"
    assert e.point is not None and e.want != e.got
    assert e.finding.rule == "trace.non_affine"
    assert "clamped.x" in str(e)


# --------------------------------------------------------------------------- #
# CLI


def test_cli_lint_fixture_json_fails_and_validates(capsys):
    from repro.explore.cli import main

    assert main(["lint", "--fixture", "racy_store", "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == analysis.SCHEMA and doc["worst"] == "error"
    for rep in doc["reports"]:
        assert analysis.validate_report_json(rep) == []


def test_cli_lint_clean_kernel_passes(capsys):
    from repro.explore.cli import main

    code = main([
        "lint", "--kernel", "stencil25",
        "--config", '{"block": [32, 4, 8], "fold": [1, 1, 1]}',
        "--machine", "V100",
    ])
    out = capsys.readouterr().out
    assert code == 0
    assert "0 error(s)" in out


@pytest.mark.parametrize("kernel", ["lbm_d3q15_tpu", "lbm_d3q27_tpu"])
def test_cli_lint_lbm_tpu_spaces_have_no_errors(capsys, kernel):
    """Every candidate of the LBM Pallas spaces is lint-clean: the halo
    strips' and tiles' walk before block 0 is the halo idiom (a warning the
    kernels answer by clamping), nothing is an error."""
    from repro.explore.cli import main

    assert main(["lint", "--kernel", kernel]) == 0
    out = capsys.readouterr().out
    assert "audited" in out and " 0 error(s)" in out.splitlines()[-1]


def test_cli_lint_requires_a_selection(capsys):
    from repro.explore.cli import main

    assert main(["lint"]) == 2
    assert "required" in capsys.readouterr().err
