"""Sharding-policy tests across the full (arch x shape) matrix, using
AbstractMesh (no devices needed): every spec this framework would hand to jit
must be divisibility-safe and duplicate-free on both production meshes."""
from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_arch
from repro.configs.base import SHAPES, shape_applicable
from repro.models import build_model
from repro.models.params import param_pspecs
from repro.train.sharding import batch_pspecs, cache_pspecs, rules_for_mesh

MESHES = {
    "single": AbstractMesh((16, 16), ("data", "model")),
    "multi": AbstractMesh((2, 16, 16), ("pod", "data", "model")),
}


def _axes_size(mesh, entry) -> int:
    if entry is None:
        return 1
    names = (entry,) if isinstance(entry, str) else entry
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def _check_tree(mesh, shapes_tree, pspec_tree, where: str):
    flat_shapes, tdef = jax.tree.flatten(
        shapes_tree, is_leaf=lambda x: hasattr(x, "shape")
    )
    flat_specs = tdef.flatten_up_to(pspec_tree)
    for sds, spec in zip(flat_shapes, flat_specs):
        assert isinstance(spec, P), f"{where}: non-PartitionSpec {spec}"
        used = []
        for dim, entry in zip(sds.shape, tuple(spec)):
            size = _axes_size(mesh, entry)
            assert dim % size == 0, (
                f"{where}: dim {dim} not divisible by {entry} ({size}) "
                f"for shape {sds.shape} spec {spec}"
            )
            if entry is not None:
                used += [entry] if isinstance(entry, str) else list(entry)
        assert len(used) == len(set(used)), f"{where}: duplicate axes in {spec}"


@pytest.mark.parametrize("mesh_kind", ["single", "multi"])
@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_param_specs_divisible(mesh_kind, arch_id):
    mesh = MESHES[mesh_kind]
    arch = get_arch(arch_id)
    model = build_model(arch)
    rules = rules_for_mesh(mesh)
    bp = model.blueprint()
    from repro.models.params import param_structs

    _check_tree(mesh, param_structs(bp), param_pspecs(bp, rules), f"{arch_id} params")


@pytest.mark.parametrize("mesh_kind", ["single", "multi"])
@pytest.mark.parametrize("arch_id", ARCH_IDS)
@pytest.mark.parametrize("shape_id", list(SHAPES))
def test_batch_and_cache_specs_divisible(mesh_kind, arch_id, shape_id):
    mesh = MESHES[mesh_kind]
    arch = get_arch(arch_id)
    shape = SHAPES[shape_id]
    ok, _ = shape_applicable(arch, shape)
    if not ok:
        pytest.skip("cell skipped by policy")
    rules = rules_for_mesh(mesh)
    from repro.configs.base import input_specs

    b_specs = batch_pspecs(arch, shape, mesh, rules)
    ins = input_specs(arch, shape)
    _check_tree(
        mesh,
        {k: v for k, v in ins.items() if k in b_specs},
        {k: b_specs[k] for k in ins if k in b_specs},
        f"{arch_id}/{shape_id} batch",
    )
    if shape.kind == "decode":
        model = build_model(arch)
        cache = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        c_specs = cache_pspecs(arch, shape, mesh, rules)
        _check_tree(mesh, cache, c_specs, f"{arch_id}/{shape_id} cache")
