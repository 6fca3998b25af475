"""SDE-GAN training subsystem tests (paper §5; DESIGN.md §4).

Careful clipping as an optimiser-chain transform, the Lipschitz-constrained
CDE discriminator stack, the shared WGAN step, and the launch CLI on 1 and
2 (simulated) devices.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn, optim
from repro.core.clipping import (clip_lipschitz, clip_pytree,
                                 lipschitz_bound_mlp, max_lipschitz_bound,
                                 per_layer_violation)
from repro.core.sde import (NeuralSDEConfig, discriminator_init, gan_losses,
                            generator_init)
from repro.data.synthetic import ou_process
from repro.distributed.sharding import shard_time_major
from repro.launch.steps import make_gan_optimizers, make_sde_gan_step

TINY = dict(num_steps=8)          # 8 solver steps per solve
BATCH, SEQ = 16, 9                # data paths: (9, 16, 1)


def _tiny_setup(key, constraint="clip"):
    cfg = NeuralSDEConfig(**TINY)
    params = {"gen": generator_init(key, cfg),
              "disc": discriminator_init(jax.random.fold_in(key, 1), cfg)}
    (gi, gu), (di, du) = make_gan_optimizers(lr=1.0, constraint=constraint)
    step = jax.jit(make_sde_gan_step(cfg, gu, du, BATCH, SEQ,
                                     constraint=constraint))
    return cfg, params, gi(params["gen"]), di(params["disc"]), step


# -----------------------------------------------------------------------------
# the constraint set: init, projection, per-layer bound after a real update
# -----------------------------------------------------------------------------


def test_lipswish_is_lipschitz_one_at_init(key):
    """LipSwish + the clipped init: the discriminator's vector fields start
    with Lipschitz bound ≤ 1 — no first-step clip slam needed."""
    x = jnp.linspace(-20, 20, 4_001)
    g = jax.vmap(jax.grad(nn.lipswish))(x)
    assert float(jnp.max(jnp.abs(g))) <= 1.0 + 1e-4
    disc = discriminator_init(key, NeuralSDEConfig(**TINY))
    assert float(max_lipschitz_bound(disc)) <= 1.0 + 1e-6
    for name in ("f", "g", "xi"):
        assert float(lipschitz_bound_mlp(disc[name])) <= 1.0 + 1e-6
        assert float(per_layer_violation(disc[name])) <= 1.0 + 1e-6


def test_clipped_disc_satisfies_per_layer_bound_after_update(key):
    """One *real* optimiser update (Adadelta → projection) from far outside
    the constraint set must land every layer of f/g/xi back inside its
    [-1/fan_in, 1/fan_in] box; the readout m stays unconstrained."""
    cfg, params, g_state, d_state, step = _tiny_setup(key)
    params["disc"] = jax.tree.map(lambda x: x * 10.0, params["disc"])
    m_before = np.asarray(params["disc"]["m"]["w"])
    params, _, _, _ = step(params, g_state, d_state, jax.random.fold_in(key, 2))
    for name in ("f", "g", "xi"):
        assert float(per_layer_violation(params["disc"][name])) <= 1.0 + 1e-6
        assert float(lipschitz_bound_mlp(params["disc"][name])) <= 1.0 + 1e-6
    # m moved by the optimiser but was not projected to the tiny clip box
    m_after = np.asarray(params["disc"]["m"]["w"])
    assert not np.array_equal(m_before, m_after)
    assert float(np.max(np.abs(m_after))) > 1.0 / m_after.shape[0]


def test_projection_transform_equals_manual_clip(key):
    """chain(adadelta, lipschitz_projection) ≡ clip(params + adadelta-update):
    the transform is exactly clip-after-update, rearranged to compose."""
    disc = discriminator_init(key, NeuralSDEConfig(**TINY))
    grads = jax.tree.map(
        lambda x: jax.random.normal(key, x.shape, x.dtype), disc)

    ai, au = optim.adadelta(lr=1.0)
    ci, cu = optim.chain(optim.adadelta(lr=1.0),
                         optim.lipschitz_projection(clip_lipschitz))

    upd, _ = au(grads, ai(disc), disc)
    want = clip_lipschitz(optim.apply_updates(disc, upd))
    upd2, _ = cu(grads, ci(disc), disc)
    got = optim.apply_updates(disc, upd2)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_clip_pytree_structural(key):
    """The structural projection clips every MLP in an arbitrary tree and
    leaves bare Linears / non-MLP leaves alone."""
    tree = {
        "vf": {"layers": [{"w": jnp.full((8, 4), 3.0), "b": jnp.ones((4,))}]},
        "nested": [{"layers": [{"w": jnp.full((2, 2), -5.0)}]}],
        "readout": {"w": jnp.full((4, 1), 7.0)},
        "scalar": jnp.float32(2.0),
    }
    out = clip_pytree(tree)
    assert float(jnp.max(jnp.abs(out["vf"]["layers"][0]["w"]))) <= 1 / 8
    np.testing.assert_array_equal(np.asarray(out["vf"]["layers"][0]["b"]),
                                  np.ones(4))
    assert float(jnp.max(jnp.abs(out["nested"][0]["layers"][0]["w"]))) <= 1 / 2
    np.testing.assert_array_equal(np.asarray(out["readout"]["w"]),
                                  np.full((4, 1), 7.0))
    assert float(out["scalar"]) == 2.0


# -----------------------------------------------------------------------------
# training behaviour
# -----------------------------------------------------------------------------


def test_two_step_loop_decreases_wasserstein_deterministically(key):
    """Two WGAN steps on a fixed batch decrease the Wasserstein estimate
    (disc_loss = E[fake] − E[real]), and the whole trajectory is a pure
    function of the seed (bitwise-identical on re-run)."""

    def run():
        cfg, params, g_state, d_state, step = _tiny_setup(key)
        k = jax.random.fold_in(key, 2)
        out = []
        for _ in range(3):  # metrics are pre-update ⇒ 3 calls see 2 updates
            params, g_state, d_state, m = step(params, g_state, d_state, k)
            out.append(float(m["disc_loss"]))
        return out

    a, b = run(), run()
    assert a == b, f"nondeterministic trajectory: {a} vs {b}"
    assert a[1] < a[0] and a[2] < a[1], f"W estimate not decreasing: {a}"


def test_clip_step_dispatch_buffer_count(key):
    """The jitted step takes and returns three buffers per player's
    optimiser state, not one per parameter leaf twice over: the host pays
    its dispatch cost per buffer.  Out: the params, 2 x 3 state arrays and
    3 metrics; in: the params, 2 x 3 state arrays and the key."""
    cfg, params, g_state, d_state, step = _tiny_setup(key)
    n_params = len(jax.tree.leaves(params))
    args = (params, g_state, d_state, key)
    out = jax.eval_shape(step, *args)
    assert len(jax.tree.leaves(out)) == n_params + 2 * 3 + 3
    assert len(jax.tree.leaves(args)) == n_params + 2 * 3 + 1
    assert len(jax.tree.leaves(out)) <= 37   # 28 parameter leaves, as at ou_gan's size


def _losses(cfg, gen, disc, k):
    """The clip step's ``(gen_loss, disc_loss)`` under the step's key."""
    y_real = shard_time_major(ou_process(jax.random.fold_in(k, 0), BATCH,
                                         SEQ, dtype=cfg.dtype))
    gl, dl, _ = gan_losses({"gen": gen, "disc": disc}, cfg,
                           jax.random.fold_in(k, 1), y_real, BATCH)
    return gl, dl


def _two_pull_grads(cfg, params, k):
    """The clip step's gradients as two cotangent pulls, one per player, on
    ``(gen_loss, disc_loss)``: the oracle for the one-pull step."""
    (gl, dl), vjp = jax.vjp(lambda g, d: _losses(cfg, g, d, k),
                            params["gen"], params["disc"])
    one, zero = jnp.ones_like(gl), jnp.zeros_like(gl)
    gg, _ = vjp((one, zero))
    _, dg = vjp((zero, one))
    return gl, dl, gg, dg


def _grads_as_state(grads, state, params):
    """An "optimiser" that hands the step's gradient back as its state and
    leaves the parameters where they are."""
    return jax.tree.map(jnp.zeros_like, grads), grads


def _count_scans(jaxpr):
    return sum((e.primitive.name == "scan")
               + sum(_count_scans(sub)
                     for sub in jax.core.jaxprs_in_params(e.params))
               for e in jaxpr.eqns)


@pytest.mark.parametrize("seed", [0, 1])
def test_clip_step_one_pull_equals_two_pulls(seed):
    """The clip step pulls one cotangent, the critic loss's, and negates
    the generator's part: gan_losses has gen_loss = -mean(fake_score) and
    disc_loss = mean(fake_score) - mean(real_score), so d gen_loss / d gen
    = -d disc_loss / d gen.  Both players' gradients equal the two-pull
    form bitwise, and per-player jax.grad to float32 round-off; the losses
    are the same.  A change of loss that breaks the identity fails here."""
    key = jax.random.PRNGKey(seed)
    cfg, params, _, _, _ = _tiny_setup(key)
    k = jax.random.fold_in(key, 2)
    step = jax.jit(make_sde_gan_step(cfg, _grads_as_state, _grads_as_state,
                                     BATCH, SEQ))
    out, gg, dg, m = step(params, None, None, k)
    gl, dl, want_gg, want_dg = jax.jit(
        lambda p, k: _two_pull_grads(cfg, p, k))(params, k)

    assert float(m["gen_loss"]) == float(gl)
    assert float(m["disc_loss"]) == float(dl)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for got, want in ((gg, want_gg), (dg, want_dg)):
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    ref_gg = jax.jit(jax.grad(
        lambda g: _losses(cfg, g, params["disc"], k)[0]))(params["gen"])
    ref_dg = jax.jit(jax.grad(
        lambda d: _losses(cfg, params["gen"], d, k)[1]))(params["disc"])
    for got, ref in ((gg, ref_gg), (dg, ref_dg)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            a, b = np.asarray(a), np.asarray(b)
            assert np.linalg.norm(a - b) <= 5e-4 * np.linalg.norm(b)


def test_clip_step_runs_each_backward_solve_once(key):
    """Structural, no clock: the clip step's jaxpr holds one backward scan
    per forward solve (joint generator/discriminator solve, real-path CDE
    solve), where pulling one cotangent per player held two."""
    cfg, params, g_state, d_state, step = _tiny_setup(key)
    k = jax.random.fold_in(key, 2)

    def scans(fn, *args):
        return _count_scans(jax.make_jaxpr(fn)(*args).jaxpr)

    n_data = scans(lambda k: ou_process(k, BATCH, SEQ, dtype=cfg.dtype), k)
    n_solves = scans(lambda p, k: _losses(cfg, p["gen"], p["disc"], k),
                     params, k) - n_data
    assert n_solves == 2
    assert scans(step, params, g_state, d_state, k) - n_data == 2 * n_solves
    assert scans(lambda p, k: _two_pull_grads(cfg, p, k), params, k) \
        - n_data == 3 * n_solves


def test_gp_step_runs_and_matches_metric_keys(key):
    """The WGAN-GP baseline path of the shared step builder is runnable and
    reports the same metric schema (benchmarks/clipping.py relies on it)."""
    cfg = NeuralSDEConfig(num_steps=4, solver="midpoint", exact_adjoint=False)
    params = {"gen": generator_init(key, cfg),
              "disc": discriminator_init(jax.random.fold_in(key, 1), cfg)}
    (gi, gu), (di, du) = make_gan_optimizers(lr=1.0, constraint="gp")
    step = jax.jit(make_sde_gan_step(cfg, gu, du, 8, 5, constraint="gp"))
    params, _, _, m = step(params, gi(params["gen"]), di(params["disc"]),
                           jax.random.fold_in(key, 2))
    assert set(m) == {"gen_loss", "disc_loss", "wasserstein"}
    assert all(np.isfinite(float(v)) for v in m.values())
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(params))


# -----------------------------------------------------------------------------
# the launch CLI, 1 and 2 (simulated) devices
# -----------------------------------------------------------------------------


def _run_train_cli(extra_env=None, extra_args=()):
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))
    env.update(extra_env or {})
    cmd = [sys.executable, "-m", "launch.train", "--workload", "sde-gan",
           "--steps", "2", "--batch", "8", "--sde-steps", "8",
           "--seq-len", "9", *extra_args]
    return subprocess.run(cmd, cwd=repo, env=env, capture_output=True,
                          text=True, timeout=600)


def test_train_cli_single_device():
    r = _run_train_cli()
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[sde-gan] done" in r.stdout


def test_train_cli_two_simulated_devices():
    r = _run_train_cli(
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "data-parallel over 2 devices" in r.stdout
    assert "[sde-gan] done" in r.stdout
