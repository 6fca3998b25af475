"""Compile the main-path kernels for a TPU v5e that is described, not
attached.

The TPU compiler refuses what the Pallas interpreter accepts: blocks not
aligned to the (8, 128) tiling, 1-D iotas and reshapes inside a kernel,
scatters, and kernels that GSPMD would have to partition.  These tests
compile every kernel the trainers and the server launch, at the shapes
they launch it with, so a regression fails here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.distributed.sharding import batch_collectives
from repro.kernels import brownian as bk
from repro.kernels import ops
from repro.kernels import reversible_heun_step as rh

F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _data_mesh(topo):
    return Mesh(topo.devices, ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (kernel, number of state-shaped operands)
PHASES = {
    "phase1": (rh.rev_heun_phase1, 5),
    "phase2": (rh.rev_heun_phase2, 6),
    "bwd1": (rh.rev_heun_bwd_phase1, 4),
    "bwd2": (rh.rev_heun_bwd_phase2, 3),
}
# GAN/Latent batch x hidden, the Latent-SDE posterior's hidden + 1, a row
# count that is no multiple of 8, and a 1-D state
STATE_SHAPES = [(1024, 16), (1024, 17), (20, 16), (1000,)]


@pytest.mark.parametrize("shape", STATE_SHAPES, ids=str)
@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_kernel_compiles(phase, shape, one_chip, no_persistent_cache):
    kernel, n = PHASES[phase]
    hlo = _compile(lambda *a: kernel(*a), *[_spec(one_chip, shape)] * n,
                   _spec(one_chip, ()))
    assert "tpu_custom_call" in hlo


def _increment(shape, k1, k2, n, dt):
    return bk.brownian_increment(k1, k2, n, shape, F32, dt)


def _phase1_gen(z, zh, mu, sig, k1, k2, n, dt_grid, dt):
    return bk.rev_heun_phase1_gen(z, zh, mu, sig, k1, k2, n, dt_grid, dt)


@pytest.mark.parametrize("shape", [(1024, 3), (1024, 4), (1024, 17)],
                         ids=str)
@pytest.mark.parametrize("kernel", ["increment", "phase1_gen"])
def test_brownian_kernel_compiles(kernel, shape, one_chip,
                                  no_persistent_cache):
    u32 = _spec(one_chip, (), jnp.uint32)
    i32 = _spec(one_chip, (), jnp.int32)
    scalar = _spec(one_chip, ())
    if kernel == "increment":
        hlo = _compile(functools.partial(_increment, shape), u32, u32, i32,
                       scalar)
    else:
        hlo = _compile(_phase1_gen, *[_spec(one_chip, shape)] * 4, u32, u32,
                       i32, scalar, scalar)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["increment", "phase1_gen"])
def test_brownian_kernel_compiles_per_row_under_vmap(kernel, one_chip,
                                                     no_persistent_cache):
    """The server draws a (noise_dim,) path per row, vmapped over the
    bucket's row keys."""
    rows = 64
    keys = _spec(one_chip, (rows,), jnp.uint32)
    i32 = _spec(one_chip, (), jnp.int32)
    scalar = _spec(one_chip, ())
    if kernel == "increment":
        fn = jax.vmap(functools.partial(_increment, (4,)),
                      in_axes=(0, 0, None, None))
        hlo = _compile(fn, keys, keys, i32, scalar)
    else:
        fn = jax.vmap(_phase1_gen, in_axes=(0,) * 6 + (None,) * 3)
        hlo = _compile(fn, *[_spec(one_chip, (rows, 4))] * 4, keys, keys,
                       i32, scalar, scalar)
    assert "tpu_custom_call" in hlo


def test_brownian_value_compiles(one_chip, no_persistent_cache):
    u32 = _spec(one_chip, (), jnp.uint32)
    hlo = _compile(
        lambda k1, k2, t: bk.brownian_value(k1, k2, t, 0.0, 1.0, (1024, 17),
                                            F32),
        u32, u32, _spec(one_chip, ()))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["phase1", "increment"])
def test_batch_sharded_kernel_compiles_on_4_chips(kernel, topo, monkeypatch,
                                                  no_persistent_cache):
    """Under a data-parallel mesh the kernels run per shard: Mosaic kernels
    cannot be partitioned automatically, and the batch must not be
    gathered onto every chip."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)  # compile for the chip
    mesh = _data_mesh(topo)
    rows = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())
    state = jax.ShapeDtypeStruct((1024, 17), F32, sharding=rows)
    scalar = jax.ShapeDtypeStruct((), F32, sharding=replicated)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    with jax.set_mesh(mesh):
        if kernel == "phase1":
            hlo = _compile(lambda *a: ops.rev_heun_phase1(*a), *[state] * 5,
                           scalar)
        else:
            hlo = _compile(
                lambda k, dt: ops.brownian_increment(k, 3, (1024, 17), F32,
                                                     dt), key, scalar)
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo
    assert batch_collectives(hlo, 1024) == []


def test_fused_latent_sde_step_compiles_on_4_chips(topo, monkeypatch,
                                                   no_persistent_cache):
    """The whole fused Latent-SDE training step at the trainer's widths and
    a path batch of 1024, data-parallel over the 2x2 mesh: every kernel
    per shard, and no all-gather of the batch."""
    from repro.launch.train import latent_sde_setup

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)  # compile for the chip
    mesh = _data_mesh(topo)
    replicated = NamedSharding(mesh, P())
    _, state, step = latent_sde_setup(1024, use_pallas=True)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        (*state, jax.random.PRNGKey(1)))
    with jax.set_mesh(mesh):
        hlo = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo
    assert batch_collectives(hlo, 1024) == []
    assert batch_collectives(hlo, 1024 // 4) == []


def _serving_program(kind):
    """``(program, params, per-row operands, scalar operands)`` of one
    serving program at the server's widths (``serving/service.py``)."""
    from repro.core.sde import (LatentSDEConfig, NeuralSDEConfig,
                                generator_init, latent_sde_init)
    from repro.launch import steps

    key = jax.random.PRNGKey(0)
    if kind == "latent_prior":  # fused reversible-Heun phases per row
        cfg = LatentSDEConfig(data_dim=2, hidden_dim=16, context_dim=16,
                              width=32, num_steps=16, use_pallas_kernels=True)
        return (steps.make_sample_step("latent-sde", cfg),
                latent_sde_init(key, cfg), [((2,), jnp.uint32)], [])
    cfg = NeuralSDEConfig(data_dim=1, hidden_dim=16, noise_dim=4, width=32,
                          num_steps=63)
    params = generator_init(key, cfg)
    if kind == "chunk":  # the Scheduler's continuous-batching rollout
        return (steps.make_stream_chunk_step(cfg, cfg.t1 / 7, 9), params,
                [((2,), jnp.uint32), ((16,), F32), ((), F32)], [])
    if kind == "terminal":  # adaptive, bridge-value kernel per attempt
        return (steps.make_adaptive_terminal_step(cfg), params,
                [((2,), jnp.uint32)], [((), F32)])
    return steps.make_sample_step("sde-gan", cfg), params, [((2,), jnp.uint32)], []


@pytest.mark.parametrize("kind", ["chunk", "gan_paths", "latent_prior",
                                  "terminal"])
def test_serving_program_compiles_on_4_chips(kind, topo, monkeypatch,
                                             no_persistent_cache):
    """The server solves one vmapped row per key.  Under the 2x2 mesh the
    rows are split over the chips, so each chip draws and solves only its
    own rows: the kernels run, and no collective moves the row batch."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)  # compile for the chip
    mesh = _data_mesh(topo)
    rows, replicated = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    n = 64
    program, params, per_row, scalars = _serving_program(kind)
    args = [jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=replicated), params)]
    args += [jax.ShapeDtypeStruct((n, *shape), dtype, sharding=rows)
             for shape, dtype in per_row]
    args += [jax.ShapeDtypeStruct(shape, dtype, sharding=replicated)
             for shape, dtype in scalars]
    with jax.set_mesh(mesh):
        hlo = jax.jit(program).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert batch_collectives(hlo, n) == []
