"""Sharding-rule unit tests (1-device safe; full meshes live in dryrun)."""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, smoke_config
from repro.distributed.sharding import hint, make_mesh, param_pspecs
from repro.launch.specs import abstract_params, batch_pspecs, input_specs
from repro.configs.base import SHAPES


def test_hint_noop_without_mesh(key):
    x = jax.random.normal(key, (4, 4))
    y = hint(x, "dp", "tp")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_param_pspecs_structure_matches():
    cfg = smoke_config("qwen2.5-14b")
    params = abstract_params(cfg)
    specs = param_pspecs(params, cfg.num_experts)
    jax.tree.map(lambda a, b: None, params, specs,
                 is_leaf=lambda x: isinstance(x, (P, jax.ShapeDtypeStruct)))


def test_param_pspecs_under_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = smoke_config("dbrx-132b")
    params = abstract_params(cfg)
    with jax.set_mesh(mesh):
        specs = param_pspecs(params, cfg.num_experts)
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(s, P) for s in flat)


def test_input_specs_cover_all_cells():
    """Every (arch × shape) cell defines a complete, consistent spec set."""
    from repro.configs import ARCH_NAMES, cell_is_runnable

    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            ok, _ = cell_is_runnable(cfg, sname)
            if not ok:
                continue
            specs = input_specs(cfg, shape)
            if shape.kind == "train":
                assert "tokens" in specs and "labels" in specs
                assert specs["tokens"].shape[0] == shape.global_batch
            elif shape.kind == "prefill":
                assert "tokens" in specs and "labels" not in specs
            else:
                assert {"token", "caches", "pos"} <= set(specs)
                assert specs["token"].shape == (shape.global_batch, 1)


def test_batch_pspecs_divisibility():
    """No pspec may demand a finer split than the dim allows (the
    production-mesh sizes, via AbstractMesh — no devices needed)."""
    mesh = jax.sharding.AbstractMesh(
        (16, 16), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("jamba-v0.1-52b")
    specs = input_specs(cfg, SHAPES["long_500k"])
    ps = batch_pspecs(specs, mesh)
    assert ps["token"] == P(None, None)  # batch 1 < dp 16: dp dropped
    cfg2 = get_config("qwen2.5-14b")
    specs2 = input_specs(cfg2, SHAPES["decode_32k"])
    ps2 = batch_pspecs(specs2, mesh)
    assert ps2["token"] == P(("data",), None) or ps2["token"] == P("data", None)
    # GQA kv heads (8) don't divide model (16): cache falls to seq sharding
    kspec = jax.tree.leaves(ps2["caches"],
                            is_leaf=lambda x: isinstance(x, P))[0]
    assert "model" in str(kspec)
