"""Each configuration's plain reference against the program's normal path
at a small size on the CPU: the data, the weights' layout, one training
step (``make_latent_sde_step``, ``make_sde_gan_step``) and the scheduler's
chunked rollout."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare
from bench.layout import Layout
from bench.tests import tiny


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return Layout(tiny.checkout(tmp_path_factory.mktemp("ref")))


def _close(a, b, rtol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= rtol * max(np.max(np.abs(b)), 1e-30)


def test_reference_data_is_the_programs():
    from repro.data.synthetic import air_quality_like, ou_process

    from bench.reference import airq_latent, ou_gan

    k = jax.random.PRNGKey(2**31 + 9)
    assert np.array_equal(ou_gan.ou_data(k, 16, 12), ou_process(k, 16, 12))
    assert np.array_equal(airq_latent.data(k, 16, 24),
                          air_quality_like(k, 16, 24)[0])


def test_benchmark_weights_have_the_programs_layout(layout):
    from repro.core.sde import (LatentSDEConfig, NeuralSDEConfig,
                                discriminator_init, generator_init,
                                latent_sde_init)

    k = jax.random.PRNGKey(4)
    lat = layout.config("airq_latent")
    mine = layout.reference("airq_latent").init(k, lat)
    theirs = latent_sde_init(k, LatentSDEConfig(**lat["model"]))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)))
    gan = layout.config("ou_gan")
    mine = layout.reference("ou_gan").init(k, gan)
    cfg = NeuralSDEConfig(**gan["model"])
    theirs = {"gen": generator_init(k, cfg),
              "disc": discriminator_init(jax.random.fold_in(k, 1), cfg)}
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)))


@pytest.mark.parametrize("cell", ["airq_latent.train_b1024",
                                  "ou_gan.train_b1024"])
def test_training_step_matches_reference(layout, cell):
    c = layout.cell(cell)
    cfg = layout.config(c["config"])
    ref = layout.reference(cfg["name"])
    prog = layout.model(cfg["kind"]).train_program(cfg, c["params"], "highest")
    key = jax.random.PRNGKey(2**31 + 1)
    params = ref.init(jax.random.fold_in(key, 0), cfg)
    keys = [jax.random.fold_in(key, 1 + i) for i in range(2)]
    state = prog.state(params)
    losses = []
    for k in keys:
        state, loss = prog.step(state, k)
        losses.append(loss)
    want = ref.train(params, cfg, c["params"], keys, c["params"]["batch"])
    assert _close(losses, want["losses"], 1e-5)
    got, exp = compare.leaf_norms(prog.params(state)), compare.leaf_norms(
        want["params"])
    assert compare.worst_leaf_gap(got, exp)[0] < 1e-5


def test_scheduler_rollout_matches_reference(layout):
    from repro.serving import ModelRegistry, Request, Scheduler

    c = layout.cell("ou_gan.serve_poisson")
    cfg = layout.config("ou_gan")
    ref = layout.reference("ou_gan")
    gen = ref.init(jax.random.PRNGKey(3), cfg)["gen"]
    registry = ModelRegistry()
    registry.register(layout.model("sde_gan").serving_model(cfg, gen,
                                                            "highest"))
    chunks = c["params"]["chunks"]
    sched = Scheduler(registry, max_batch=8, chunks=chunks, collect=True)
    reqs = [Request(rid=i, size=1 + i, seed=2**31 - 7 - i) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    served = {r.rid: np.asarray(r.samples) for r in sched.run()}
    roll = jax.vmap(functools.partial(ref.rollout, config=cfg, chunks=chunks),
                    in_axes=(None, None, 0))
    for r in reqs:
        want = np.moveaxis(np.asarray(roll(gen, r.seed, jnp.arange(r.size))),
                           0, 1)
        assert served[r.rid].shape == want.shape
        assert compare.rows_gap(served[r.rid], want) < 1e-5
