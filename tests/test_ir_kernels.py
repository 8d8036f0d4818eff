"""The IR frontend's payoff: attention and wkv rank end-to-end through the GPU
analytic pipeline (estimate_many + Study + cross-machine + CLI), store keys are
canonical AccessIR fingerprints (spelling-invariant, collision-free), and large
stores load in parallel."""
from __future__ import annotations

import json

import pytest

from repro.core import estimator, model
from repro.core.machine import A100_40GB, V100
from repro.explore import Study
from repro.explore.registry import attention_gpu_space, get_kernel, wkv_gpu_space
from repro.explore.store import ResultStore
from repro.frontend import attention_gpu_ir, ir_fingerprint, lower_gpu, wkv_gpu_ir


def sweep(kernel, configs=None, machine=None, store=None):
    """Single-machine Study shorthand (the old ``engine.sweep`` surface)."""
    return Study(kernel, configs=configs, machine=machine, store=store).result()


def compare(kernel, machines, configs=None):
    """Multi-machine Study shorthand (the old ``crossmachine.compare``)."""
    return Study(kernel, configs=configs, machines=machines).compare()

# small problem instances keep each full estimate cheap
ATTN = dict(s=512, heads=8, d=16)
WKV = dict(BH=8, S=512, K=16)


# --------------------------------------------------------------------------- #
# registry + family resolution


def test_registry_families_and_backend_resolution():
    for family in ("stencil25", "lbm_d3q15", "attention", "wkv"):
        gpu = get_kernel(family, backend="gpu")
        tpu = get_kernel(family, backend="tpu")
        assert gpu.backend == "gpu" and gpu.build_ir is not None
        assert tpu.backend == "tpu" and tpu.tpu_configs is not None
        assert gpu.family == tpu.family == family
    # a TPU-only family: the two-phase solver's D3Q27 hydrodynamic kernel
    for family in ("lbm_d3q27",):
        tpu = get_kernel(f"{family}_tpu", backend="tpu")
        assert tpu.family == family and tpu.tpu_configs is not None
        assert len(tpu.tpu_configs()) >= 3
        with pytest.raises(KeyError, match="no 'gpu' backend"):
            get_kernel(f"{family}_tpu", backend="gpu")
    # tpu-named entries resolve back to the gpu variant and vice versa
    assert get_kernel("attention_tpu", backend="gpu").name == "attention"
    assert get_kernel("wkv", backend="tpu").name == "wkv_tpu"
    with pytest.raises(KeyError, match="unknown kernel"):
        get_kernel("attention_gpu")


def test_gpu_spaces_enumerate():
    attn = attention_gpu_space().configs()
    assert len(attn) == 19
    assert all(c["block"][0] * c["block"][1] in (256, 512) for c in attn)
    wkv = wkv_gpu_space().configs()
    assert len(wkv) == 25
    assert all(
        c["block"][0] <= c["chunk"] and c["block"][1] <= c["chunk"] for c in wkv
    )


# --------------------------------------------------------------------------- #
# estimate_many: batched path stays bit-identical on the new kernels


@pytest.mark.parametrize(
    "build_ir,cfgs",
    [
        (
            attention_gpu_ir,
            [{"block": (16, 16, 1), **ATTN}, {"block": (64, 4, 1), **ATTN}],
        ),
        (
            wkv_gpu_ir,
            [
                {"block": (16, 16, 1), "chunk": 32, **WKV},
                {"block": (32, 8, 1), "chunk": 64, **WKV},
            ],
        ),
    ],
    ids=["attention", "wkv"],
)
def test_estimate_many_bitwise_on_ir_kernels(build_ir, cfgs):
    specs = [lower_gpu(build_ir(**c)) for c in cfgs]
    batched = estimator.estimate_many(specs, V100)
    for spec, got in zip(specs, batched):
        ref = estimator.estimate(spec, V100)
        assert got.v_dram_load == ref.v_dram_load
        assert got.v_dram_store == ref.v_dram_store
        assert got.v_l2l1_load == ref.v_l2l1_load
        assert got.l1_cycles == ref.l1_cycles
        assert (
            model.predict(spec, got, V100).glups
            == model.predict(spec, ref, V100).glups
        )


# --------------------------------------------------------------------------- #
# sweep + crossmachine + CLI end-to-end


def test_attention_sweeps_through_gpu_pipeline(tmp_path):
    cfgs = [{"block": b, **ATTN} for b in [(16, 16, 1), (64, 4, 1), (4, 64, 1)]]
    res = sweep("attention", configs=cfgs, machine="a100", store=tmp_path / "a.jsonl")
    assert res.backend == "gpu" and len(res.records) == 3
    assert all(r.metrics["glups"] > 0 for r in res.records)
    glups = [r.metrics["glups"] for r in res.records]
    assert glups == sorted(glups, reverse=True)  # best-first
    assert res.records[0].config in [r.config for r in res.pareto()]
    # resumable: every config is a cache hit on re-sweep
    again = sweep("attention", configs=cfgs, machine="a100", store=tmp_path / "a.jsonl")
    assert again.stats.cache_hits == 3 and again.stats.evaluated == 0


def test_wkv_chunk_ranking_through_gpu_pipeline():
    cfgs = [
        {"block": (16, 16, 1), "chunk": c, **WKV} for c in (16, 32, 64, 128)
    ]
    res = sweep("wkv", configs=cfgs, machine="v100")
    assert len(res.records) == 4
    # the chunk axis must reproduce the chunked-WKV tradeoff analytically:
    # per-token DRAM traffic shrinks monotonically with the chunk length
    # (r/k/v/w rows are reused across the L^2 intra-chunk pairs)
    by_chunk = {r.config["chunk"]: r.metrics["v_dram"] for r in res.records}
    dram = [by_chunk[c] for c in (16, 32, 64, 128)]
    assert dram == sorted(dram, reverse=True) and len(set(dram)) == 4


def test_crossmachine_attention_and_wkv():
    cfgs = [{"block": b, **ATTN} for b in [(16, 16, 1), (64, 4, 1)]]
    cm = compare("attention", ["v100", "a100"], configs=cfgs)
    assert cm.backend == "gpu" and set(cm.results) == {"V100", "A100"}
    assert all(w.placements[w.machine][0] == 0 for w in cm.winners)
    cfgs = [{"block": (16, 16, 1), "chunk": c, **WKV} for c in (16, 64)]
    cm = compare("wkv", ["v100", "a100", "h100"], configs=cfgs)
    assert set(cm.results) == {"V100", "A100", "H100"}


def test_cli_attention_gpu_and_backend_flag(capsys):
    from repro.explore import cli

    rc = cli.main(
        ["--kernel", "attention", "--machine", "a100", "--sample", "4",
         "--no-store", "--json"]
    )
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["backend"] == "gpu" and out["kernel"] == "attention"
    assert out["candidates"] == 4 and len(out["top"]) == 4
    # --backend tpu resolves the family's Pallas entry
    rc = cli.main(
        ["--kernel", "attention", "--backend", "tpu", "--top", "2", "--no-store",
         "--json"]
    )
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["backend"] == "tpu" and out["kernel"] == "attention_tpu"


def test_cli_wkv_gpu_smoke(capsys):
    from repro.explore import cli

    rc = cli.main(["--kernel", "wkv", "--sample", "4", "--no-store"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "chunk=" in out  # config formatting shows the chunk axis


# --------------------------------------------------------------------------- #
# store-key canonicalization (AccessIR fingerprint)


def test_store_key_canonicalizes_benign_spellings(tmp_path):
    """List-vs-tuple blocks and explicitly-spelled default arguments lower to
    the same AccessIR -> one store entry, hit on re-sweep."""
    p = tmp_path / "attn.jsonl"
    first = sweep(
        "attention",
        configs=[{"block": (16, 16, 1), **ATTN}],
        machine="a100",
        store=p,
    )
    assert first.stats.evaluated == 1
    respelled = sweep(
        "attention",
        configs=[
            {
                "block": [16, 16, 1],  # list spelling
                "s": ATTN["s"],
                "heads": ATTN["heads"],
                "d": ATTN["d"],
                "dtype_bits": 32,  # benign: explicitly the default
            }
        ],
        machine="a100",
        store=p,
    )
    assert respelled.stats.cache_hits == 1 and respelled.stats.evaluated == 0
    assert len(ResultStore(p)) == 1


def test_store_key_collision_regression(tmp_path):
    """Aliasing regression: distinct address streams can never share a key —
    block permutations, chunk changes and dtype changes all key apart."""
    variants = [
        {"block": (16, 16, 1), "chunk": 32, **WKV},
        {"block": (32, 8, 1), "chunk": 32, **WKV},  # permuted-ish block
        {"block": (16, 16, 1), "chunk": 64, **WKV},  # different chunk
        {"block": (16, 16, 1), "chunk": 32, **{**WKV, "K": 32}},  # different K
    ]
    fps = {ir_fingerprint(wkv_gpu_ir(**v)) for v in variants}
    assert len(fps) == len(variants)
    p = tmp_path / "wkv.jsonl"
    for v in variants:
        sweep("wkv", configs=[v], machine="v100", store=p)
    assert len(ResultStore(p)) == len(variants)
    # and each re-sweeps as a hit against its own entry
    for v in variants:
        r = sweep("wkv", configs=[v], machine="v100", store=p)
        assert r.stats.cache_hits == 1 and r.stats.evaluated == 0


# --------------------------------------------------------------------------- #
# parallel warm path


def test_store_load_modes_agree(tmp_path):
    """Lazy key-scan (default), eager serial (0) and eager pool (N) loads all
    expose identical contents, including last-write-wins and corrupt-tail
    skipping."""
    p = tmp_path / "big.jsonl"
    w = ResultStore(p, load_workers=0)
    for i in range(500):
        w.put(f"k{i}", {"v": i, "blob": [i] * 8}, machine="V100")
    w.put("k0", {"v": -1, "blob": []}, machine="A100")  # supersede
    with p.open("a") as f:
        f.write('{"key": "trunc')  # killed mid-write
    lazy = ResultStore(p)  # default: lazy key-scan
    serial = ResultStore(p, load_workers=0)
    pooled = ResultStore(p, load_workers=4)
    for s in (lazy, serial, pooled):
        assert len(s) == 500
        assert s.get("k0") == {"v": -1, "blob": []}
        assert s.get("nope") is None
    assert lazy.machines() == serial.machines() == pooled.machines()
    assert {k: lazy.get(k) for k in lazy.keys()} == {
        k: serial.get(k) for k in serial.keys()
    }


def test_store_lazy_load_recovers_superseded_record_behind_corrupt_line(tmp_path):
    """A torn write that still scans a complete key (ends on '}') must not
    shadow an earlier valid record for that key: the lazy path falls back to
    an eager reload and serves exactly what load_workers=0 would."""
    p = tmp_path / "torn.jsonl"
    w = ResultStore(p, load_workers=0)
    w.put("K", {"v": 1}, machine="V100")
    w.put("other", {"v": 2}, machine="V100")
    with p.open("a") as f:
        f.write('{"key": "K", "payload": {"v"}\n')  # torn, but scannable key
    eager = ResultStore(p, load_workers=0)
    lazy = ResultStore(p)
    assert lazy.get("K") == eager.get("K") == {"v": 1}
    assert lazy.get("other") == {"v": 2}
    assert len(lazy) == len(eager) == 2
    assert lazy.machines() == eager.machines()


def test_store_lazy_load_survives_multiple_scannable_corrupt_lines(tmp_path):
    """Two or more torn-but-key-scannable lines: the first materialization
    failure triggers the eager reload (dropping them all); later touches of
    the other dropped keys must return None, and machines()/compact() must not
    crash."""
    p = tmp_path / "torn2.jsonl"
    w = ResultStore(p, load_workers=0)
    w.put("good", {"v": 1}, machine="V100")
    with p.open("a") as f:
        f.write('{"key": "k1", "payload": {"v"}\n')
        f.write('{"key": "k2", "payload": {"v"}\n')
    lazy = ResultStore(p)
    assert lazy.machines() == {"V100": 1}  # reloads; must not KeyError
    assert lazy.get("k1") is None and lazy.get("k2") is None
    assert lazy.get("good") == {"v": 1} and len(lazy) == 1
    lazy2 = ResultStore(p)
    lazy2.compact()
    assert ResultStore(p, load_workers=0).machines() == {"V100": 1}


def test_store_lazy_load_parses_only_touched_payloads(tmp_path):
    """The lazy path's contract: loading is a key scan; a payload deserializes
    on its first hit (and superseded duplicates never deserialize at all)."""
    p = tmp_path / "lazy.jsonl"
    w = ResultStore(p, load_workers=0)
    for i in range(20):
        w.put(f"k{i}", {"v": i}, machine="V100")
    s = ResultStore(p)
    untouched = [v for v in s._mem.values() if isinstance(v, str)]
    assert len(untouched) == 20  # nothing parsed yet
    assert s.get("k3") == {"v": 3}
    assert isinstance(s._mem["k3"], dict)  # materialized in place
    assert sum(isinstance(v, str) for v in s._mem.values()) == 19
    # compact() materializes everything and rewrites a loadable file
    s.compact()
    assert ResultStore(p).get("k19") == {"v": 19}
