"""Compile the Pallas kernels for a TPU v5e that is described, not attached.

Nothing runs: the chip's own compiler accepts or refuses each kernel, which is
what interpret-mode tests cannot show (VMEM limits, Mosaic's block-shape rule,
ops Mosaic cannot lower).  Shapes are the ones ``chip_smoke.py`` runs, and
the chip benchmark's LBM shapes: the bulk cells' 256^3 and the LBM
ensemble's 32x32x256.  The
topology is described inside a fixture, never at import: only one process at
a time may load the TPU library.
"""
from __future__ import annotations

import base64
import hashlib
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import tpu_estimator as te
from repro.core.machine import tpu_machine
from repro.kernels.attention import select_blocks
from repro.kernels.attention.kernel import flash_attention_pallas
from repro.kernels.lbm_d3q15 import config_space as lbm_space
from repro.kernels.lbm_d3q15 import select_block as lbm_select
from repro.kernels.lbm_d3q15.kernel import lbm_step_pallas
from repro.kernels.lbm_d3q27 import config_space as lbm27_space
from repro.kernels.lbm_d3q27 import select_block as lbm27_select
from repro.kernels.lbm_d3q27.kernel import hydro_step_pallas
from repro.kernels.stencil25 import config_space as stencil_space
from repro.kernels.stencil25 import select_block as stencil_select
from repro.kernels.stencil25.kernel import stencil25_pallas
from repro.kernels.wkv import select_chunk
from repro.kernels.wkv.kernel import wkv_pallas

CHIP_BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
STENCIL = (256, 256, 512)  # r=4, f32
LBM = (128, 128, 128)  # f32
LBM27 = (256, 256, 256)  # f32, the bulk LBM cells' domain (D3Q15 and two-phase)
LBM_ENSEMBLE = (32, 32, 256)  # f32, the LBM ensemble cell's domain
ATTN = (4, 32, 8, 8192, 128)  # b, hq, hkv, s, d; bf16
WKV = (64, 4096, 64)  # BH, S, K; f32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """(shape -> ShapeDtypeStruct on one described chip, its TPUMachine), with
    the persistent compilation cache off: entries compiled for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    yield struct, tpu_machine(topo.devices[0].device_kind)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles(fn, *structs) -> bool:
    """True when the chip's compiler accepts ``fn``; False when it refuses the
    kernel (block-shape rule: ValueError; VMEM overflow: JaxRuntimeError)."""
    try:
        jax.jit(fn).lower(*structs).compile()
    except (ValueError, jax.errors.JaxRuntimeError):
        return False
    return True


def _stencil(struct, machine, block):
    return _compiles(
        lambda x: stencil25_pallas(x, r=4, block=block, vmem_limit_bytes=machine.vmem_usable),
        struct(STENCIL),
    )


def _lbm(struct, machine, block, shape=LBM):
    nz, ny, nx = shape
    return _compiles(
        lambda f, p, v: lbm_step_pallas(f, p, v, block=block, vmem_limit_bytes=machine.vmem_usable),
        struct((15, nz, ny, nx)), struct(shape), struct((3, nz, ny, nx)),
    )


def _lbm27(struct, machine, block):
    nz, ny, nx = LBM27
    return _compiles(
        lambda g, p, v: hydro_step_pallas(g, p, v, block=block,
                                          vmem_limit_bytes=machine.vmem_usable),
        struct((27, nz, ny, nx)), struct(LBM27), struct((3, nz, ny, nx)),
    )


def test_device_kind_resolves_to_v5e(chip):
    _, machine = chip
    assert machine.name == "tpu-v5e"


@pytest.mark.parametrize("cfg", stencil_space(STENCIL, 4, 32), ids=lambda c: c.name)
def test_stencil25_candidate_compiles_iff_feasible(chip, cfg):
    struct, machine = chip
    assert _stencil(struct, machine, cfg.meta["block"]) == te.estimate(cfg, machine).feasible


@pytest.mark.parametrize("cfg", lbm_space(LBM, 32), ids=lambda c: c.name)
def test_lbm_candidate_compiles_iff_feasible(chip, cfg):
    struct, machine = chip
    assert _lbm(struct, machine, cfg.meta["block"]) == te.estimate(cfg, machine).feasible


@pytest.mark.parametrize("cfg", lbm_space(LBM_ENSEMBLE, 32), ids=lambda c: c.name)
def test_lbm_ensemble_candidate_compiles_iff_feasible(chip, cfg):
    struct, machine = chip
    assert (_lbm(struct, machine, cfg.meta["block"], LBM_ENSEMBLE)
            == te.estimate(cfg, machine).feasible)


@pytest.mark.parametrize("cfg", lbm_space(LBM27, 32), ids=lambda c: c.name)
def test_lbm_bulk_candidate_compiles_iff_feasible(chip, cfg):
    struct, machine = chip
    assert _lbm(struct, machine, cfg.meta["block"], LBM27) == te.estimate(cfg, machine).feasible


@pytest.mark.parametrize("cfg", lbm27_space(LBM27, 32), ids=lambda c: c.name)
def test_lbm27_candidate_compiles_iff_feasible(chip, cfg):
    struct, machine = chip
    assert _lbm27(struct, machine, cfg.meta["block"]) == te.estimate(cfg, machine).feasible


@pytest.mark.parametrize("kernel", ["stencil25", "lbm_d3q15", "attention", "wkv", "lbm_d3q27"])
def test_estimator_pick_compiles(chip, kernel):
    struct, machine = chip
    limit = machine.vmem_usable
    if kernel == "stencil25":
        pick, _ = stencil_select(STENCIL, 4, jnp.float32, machine=machine)
        assert _stencil(struct, machine, pick)
    elif kernel == "lbm_d3q15":
        pick, _ = lbm_select(LBM, jnp.float32, machine=machine)
        assert _lbm(struct, machine, pick)
    elif kernel == "lbm_d3q27":
        pick, _ = lbm27_select(LBM27, jnp.float32, machine=machine)
        assert _lbm27(struct, machine, pick)
    elif kernel == "attention":
        b, hq, hkv, s, d = ATTN
        (bq, bkv), _ = select_blocks(b, hq, hkv, s, d, jnp.bfloat16, True, machine=machine)
        kv = struct((b, hkv, s, d), jnp.bfloat16)
        assert _compiles(
            lambda q, k, v: flash_attention_pallas(
                q, k, v, block_q=bq, block_kv=bkv, vmem_limit_bytes=limit
            ),
            struct((b, hq, s, d), jnp.bfloat16), kv, kv,
        )
    else:
        BH, S, K = WKV
        chunk, _ = select_chunk(BH, S, K, machine=machine)
        a = struct(WKV)
        assert _compiles(
            lambda r, k, v, w, u: wkv_pallas(r, k, v, w, u, chunk=chunk, vmem_limit_bytes=limit),
            a, a, a, a, struct((K,)),
        )


def _lowered_digest(lowered) -> str:
    """sha256 of ``lowered``'s StableHLO with its Mosaic kernel body decoded,
    both printed without source locations: what the kernel is, not where
    its Python lines sit."""
    module = lowered.compiler_ir("stablehlo")
    text = module.operation.get_asm(enable_debug_info=False)
    (body,) = re.findall(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", text)
    module.context.allow_unregistered_dialects = True
    kernel = type(module).parse(base64.b64decode(body), context=module.context)
    text = text.replace(body, "") + kernel.operation.get_asm(enable_debug_info=False)
    return hashlib.sha256(text.encode()).hexdigest()


# the D3Q27 kernel's lowered module at 256^3; a change meant to alter the
# kernel updates these digests, any other change leaves them
LBM27_DIGESTS = {
    (8, 32): "c558413eafc3ed9d0fe254fa8c1999ea72546b0a791d5e71dca1d966b9fbe754",
    (8, 8): "1198f676108a788a46b936941c58bf2bc3ec1bb8579e5ea9f35efb8b3d5e7a79",
    (16, 16): "1b091b83b1a0241cf7915a63cb1be460c9a9dc21b2137242e90c63177d63944c",
}


@pytest.mark.parametrize("block", list(LBM27_DIGESTS), ids=lambda b: f"{b[0]}x{b[1]}")
def test_lbm27_lowered_kernel_is_pinned(chip, block):
    """The D3Q27 kernel shares its halo layout and plane helpers with the
    D3Q15 kernel (``kernels/tiling.py``); a change to the shared code that is
    meant for the D3Q15 kernel leaves the D3Q27 kernel's lowered module as
    it was, apart from source locations."""
    struct, machine = chip
    nz, ny, nx = LBM27
    lowered = jax.jit(lambda g, p, v: hydro_step_pallas(
        g, p, v, block=block, vmem_limit_bytes=machine.vmem_usable)).lower(
        struct((27, nz, ny, nx)), struct(LBM27), struct((3, nz, ny, nx)))
    assert _lowered_digest(lowered) == LBM27_DIGESTS[block]


def _kernel_instructions(lowered) -> list[str]:
    """The compiled ``tpu_custom_call`` instructions of ``lowered``, each cut
    as the chip benchmark's trace reduction names a device op."""
    sys.path.insert(0, str(CHIP_BENCH))
    from trace_reduce import short_name

    return [short_name(line.strip().removeprefix("ROOT "))
            for line in lowered.compile().as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _in_scope(fn, scoped: bool):
    def call(*args):
        if not scoped:
            return fn(*args)
        with jax.named_scope("xpad"):
            return fn(*args)
    return call


@pytest.mark.parametrize("how", ["entry", "entry_in_scope", "kernel_in_scope"])
@pytest.mark.parametrize("config", ["stencil25-r4-f32", "lbm-d3q15-f32", "lbm-twophase-d3q27-f32"])
def test_entry_kernel_matches_the_benchmark_kernel_pattern(chip, monkeypatch, config, how):
    """The chip benchmark finds the kernel by ``kernel_pattern``.  The kernel
    keeps its name (``pallas_call(name=...)``) when the entry is called inside
    an outer scope, and when the kernel itself is, in a jit of another name.
    The entry runs one kernel, or two in the two-phase step, whose D3Q15
    kernel the pattern must leave out and ``phase_kernel_pattern`` find."""
    from repro.kernels import entry
    from repro.kernels.lbm_d3q15 import lbm_step
    from repro.kernels.lbm_d3q27 import twophase_step
    from repro.kernels.stencil25 import stencil25

    struct, machine = chip
    spec = json.loads((CHIP_BENCH / "configs" / config / "config.json").read_text())
    limit = machine.vmem_usable
    monkeypatch.setattr(entry, "device_machine", lambda: machine)
    if spec["kernel"] == "stencil25":
        fn = (lambda x: stencil25_pallas(x, r=4, block=(8, 8), vmem_limit_bytes=limit)
              ) if how == "kernel_in_scope" else (lambda x: stencil25(x, r=4))
        args = (struct(STENCIL),)
    elif spec["kernel"] == "lbm_d3q15":
        fn = (lambda f, p, v: lbm_step_pallas(f, p, v, block=(8, 8), vmem_limit_bytes=limit)
              ) if how == "kernel_in_scope" else lbm_step
        nz, ny, nx = LBM
        args = struct((15, nz, ny, nx)), struct(LBM), struct((3, nz, ny, nx))
    else:
        fn = (lambda g, p, v: hydro_step_pallas(g, p, v, block=(8, 8), vmem_limit_bytes=limit)
              ) if how == "kernel_in_scope" else twophase_step
        nz, ny, nx = LBM
        args = (struct((15, nz, ny, nx)), struct((27, nz, ny, nx)), struct(LBM),
                struct((3, nz, ny, nx)))
        if how == "kernel_in_scope":
            args = args[1:]
    kernels = _kernel_instructions(jax.jit(_in_scope(fn, how != "entry")).lower(*args))
    two = spec["kernel"] == "lbm_d3q27" and how != "kernel_in_scope"
    assert len(kernels) == (2 if two else 1), kernels
    (kernel,) = [k for k in kernels if re.search(spec["kernel_pattern"], k)]
    if two:  # the D3Q15 kernel, which the cell's phase_kernel_pattern reads
        (other,) = [k for k in kernels if k != kernel]
        assert re.search(spec["phase_kernel_pattern"], other), other


@pytest.mark.parametrize("entry", ["lbm_step", "twophase_step"])
def test_lbm_entries_compile_without_x_pads(chip, monkeypatch, entry):
    """The D3Q15 kernel reads whole x rows and rotates them in lanes, so at
    the benchmark's 256^3 its entry compiles to the kernel alone, with no pad
    op, and the two-phase step, which runs it, holds no pad op either."""
    from repro.kernels import entry as kernel_entry
    from repro.kernels.lbm_d3q15 import lbm_step
    from repro.kernels.lbm_d3q27 import twophase_step

    struct, machine = chip
    monkeypatch.setattr(kernel_entry, "device_machine", lambda: machine)
    nz, ny, nx = LBM27
    f, phase, vel = struct((15, nz, ny, nx)), struct(LBM27), struct((3, nz, ny, nx))
    if entry == "lbm_step":
        lowered = lbm_step.lower(f, phase, vel)
        assert re.search(r"module @jit_lbm_step\b", lowered.as_text())
    else:
        lowered = jax.jit(twophase_step).lower(f, struct((27, nz, ny, nx)), phase, vel)
    ops = [line for line in lowered.compile().as_text().splitlines()
           if re.search(r"\b(pad|custom-call)\(", line)]
    assert not [op for op in ops if re.search(r"\bpad\(", op)], ops
    assert len(ops) == (1 if entry == "lbm_step" else 2), ops


@pytest.mark.parametrize("name", ["flash_attention", "wkv"])
def test_model_kernel_instruction_is_named_by_its_pallas_call(chip, name):
    """Called inside a scope, in a jit of another name, the kernel still
    compiles to an instruction named after its ``pallas_call(name=...)``."""
    struct, machine = chip
    limit = machine.vmem_usable
    if name == "flash_attention":
        b, hq, hkv, s, d = ATTN
        kv = struct((b, hkv, s, d), jnp.bfloat16)
        fn = lambda q, k, v: flash_attention_pallas(  # noqa: E731
            q, k, v, block_q=512, block_kv=512, vmem_limit_bytes=limit)
        args = struct((b, hq, s, d), jnp.bfloat16), kv, kv
    else:
        fn = lambda r, k, v, w, u: wkv_pallas(r, k, v, w, u, chunk=64, vmem_limit_bytes=limit)  # noqa: E731
        a = struct(WKV)
        args = a, a, a, a, struct((WKV[2],))
    (kernel,) = _kernel_instructions(jax.jit(_in_scope(fn, True)).lower(*args))
    assert re.match(rf"^%{name}(\.\d+)? custom-call", kernel), kernel
