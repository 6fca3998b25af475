"""Latent-SDE (VAE) training subsystem tests (paper Appendix B; DESIGN.md §8).

The grid-misalignment regression (the eager ValueError replacing the old
broadcast TypeError / zero-stride crash), the one-``jax.vjp`` ELBO step,
fused-vs-unfused equivalence, the backsolve baseline, and the launch CLI on
1 and 2 (simulated) devices.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.sde import (LatentSDEConfig, latent_sde_init, latent_sde_loss,
                            latent_sde_loss_terminal, validate_latent_grid)
from repro.data.synthetic import air_quality_like
from repro.launch.steps import make_latent_sde_optimizer, make_latent_sde_step

BATCH, SEQ = 8, 9  # data grid: 9 observations => T = 8 intervals


def _tiny_setup(key, num_steps=8, adjoint="exact", **cfg_kw):
    cfg_kw.setdefault("solver",
                      "midpoint" if adjoint == "backsolve" else "reversible_heun")
    cfg_kw.setdefault("exact_adjoint", adjoint == "exact")
    cfg = LatentSDEConfig(data_dim=2, hidden_dim=8, context_dim=8, width=16,
                          num_steps=num_steps, kl_weight=0.1, **cfg_kw)
    params = latent_sde_init(key, cfg)
    oi, ou = make_latent_sde_optimizer(lr=1e-2)
    step = jax.jit(make_latent_sde_step(cfg, ou, BATCH, SEQ, adjoint=adjoint))
    return cfg, params, oi(params), step


# -----------------------------------------------------------------------------
# grid misalignment: the bugfix regression tests
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("num_steps", [30, 4])
def test_latent_sde_loss_rejects_misaligned_grid(key, num_steps):
    """num_steps=30, T=8 used to die in a broadcast TypeError; num_steps=4,
    T=8 in 'slice step cannot be zero'.  Both must now raise an eager
    ValueError naming cfg.num_steps and T."""
    cfg = LatentSDEConfig(data_dim=2, hidden_dim=8, context_dim=8, width=16,
                          num_steps=num_steps)
    params = latent_sde_init(key, cfg)
    ys, _ = air_quality_like(jax.random.fold_in(key, 1), BATCH, SEQ)
    with pytest.raises(ValueError, match=rf"num_steps \({num_steps}\).*T \(8"):
        latent_sde_loss(params, cfg, key, ys)
    with pytest.raises(ValueError, match=rf"num_steps \({num_steps}\).*T \(8"):
        latent_sde_loss_terminal(params, cfg, key, ys)


def test_validate_latent_grid_accepts_multiples():
    for T in (4, 8, 23):
        for k in (1, 2, 5):
            assert validate_latent_grid(k * T, T) == k
    with pytest.raises(ValueError, match=r"at least two observations"):
        validate_latent_grid(8, 0)


def test_misaligned_grid_raises_under_jit(key):
    """Shapes are static, so the named error surfaces at trace time even
    inside jit — not an opaque XLA failure."""
    cfg = LatentSDEConfig(data_dim=2, hidden_dim=8, context_dim=8, width=16,
                          num_steps=30)
    params = latent_sde_init(key, cfg)
    ys, _ = air_quality_like(jax.random.fold_in(key, 1), BATCH, SEQ)
    f = jax.jit(lambda p: latent_sde_loss(p, cfg, key, ys)[0])
    with pytest.raises(ValueError, match=r"num_steps \(30\)"):
        f(params)


# -----------------------------------------------------------------------------
# the step builder: eager config validation
# -----------------------------------------------------------------------------


def test_step_builder_validates_eagerly(key):
    cfg = LatentSDEConfig(data_dim=2, hidden_dim=8, context_dim=8, width=16,
                          num_steps=8)
    _, ou = make_latent_sde_optimizer()
    # misaligned grid at build time (before any data exists)
    with pytest.raises(ValueError, match=r"num_steps \(8\).*T \(6"):
        make_latent_sde_step(cfg, ou, BATCH, 7)
    # wrong data dimensionality for the air-quality workload
    bad = LatentSDEConfig(data_dim=3, num_steps=8)
    with pytest.raises(ValueError, match="data_dim"):
        make_latent_sde_step(bad, ou, BATCH, SEQ)
    # unknown adjoint name
    with pytest.raises(ValueError, match="adjoint"):
        make_latent_sde_step(cfg, ou, BATCH, SEQ, adjoint="magic")
    # backsolve needs a continuous-adjoint-capable solver
    with pytest.raises(ValueError, match="backsolve"):
        make_latent_sde_step(cfg, ou, BATCH, SEQ, adjoint="backsolve")
    # fusion is exact-adjoint-only
    fused_backsolve = LatentSDEConfig(data_dim=2, num_steps=8,
                                      solver="midpoint", exact_adjoint=False,
                                      use_pallas_kernels=True)
    with pytest.raises(ValueError, match="use_pallas_kernels"):
        make_latent_sde_step(fused_backsolve, ou, BATCH, SEQ,
                             adjoint="backsolve")
    with pytest.raises(ValueError, match="use_pallas_kernels"):
        make_latent_sde_step(fused_backsolve, ou, BATCH, SEQ)


# -----------------------------------------------------------------------------
# training behaviour
# -----------------------------------------------------------------------------


def test_elbo_step_decreases_loss_deterministically(key):
    """A few ELBO steps on a fixed batch decrease -ELBO, and the whole
    trajectory is a pure function of the seed (bitwise-identical re-run)."""

    def run():
        cfg, params, state, step = _tiny_setup(key)
        k = jax.random.fold_in(key, 2)
        out = []
        for _ in range(6):  # metrics are pre-update ⇒ 6 calls see 5 updates
            params, state, m = step(params, state, k)
            out.append(float(m["loss"]))
        return out

    a, b = run(), run()
    assert a == b, f"nondeterministic trajectory: {a} vs {b}"
    assert a[-1] < a[0], f"-ELBO not decreasing: {a}"


def test_fused_step_matches_unfused(key):
    """cfg.use_pallas_kernels routes the posterior solve through the fused
    path (jnp oracle on CPU, compiled kernels on TPU) — one optimiser step
    must agree with the unfused path to float tolerance."""
    outs = {}
    for fused in (False, True):
        cfg, params, state, step = _tiny_setup(key, use_pallas_kernels=fused)
        p1, _, m = step(params, state, jax.random.fold_in(key, 2))
        outs[fused] = (p1, float(m["loss"]))
    assert outs[True][1] == pytest.approx(outs[False][1], abs=1e-6)
    for a, b in zip(jax.tree.leaves(outs[True][0]),
                    jax.tree.leaves(outs[False][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_backsolve_step_runs_and_matches_metric_schema(key):
    """The continuous-adjoint baseline path of the shared step builder is
    runnable and reports the same metric schema as the exact path
    (benchmarks/latent_sde.py relies on both)."""
    for adjoint in ("exact", "backsolve"):
        cfg, params, state, step = _tiny_setup(key, adjoint=adjoint)
        params, _, m = step(params, state, jax.random.fold_in(key, 2))
        assert set(m) == {"loss", "grad_norm", "recon", "kl_path", "kl_v"}
        assert all(np.isfinite(float(v)) for v in m.values())
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree.leaves(params))


def test_terminal_and_trajectory_elbo_agree_roughly(key):
    """The terminal-form ELBO (recon as a state channel) is a quadrature of
    the same objective the trajectory form sums over observations — the two
    must agree to solver-truncation accuracy on an aligned grid."""
    cfg = LatentSDEConfig(data_dim=2, hidden_dim=8, context_dim=8, width=16,
                          num_steps=64, kl_weight=0.1)
    params = latent_sde_init(key, cfg)
    ys, _ = air_quality_like(jax.random.fold_in(key, 1), 16, SEQ)
    l_traj, _ = latent_sde_loss(params, cfg, jax.random.fold_in(key, 2), ys)
    l_term, _ = latent_sde_loss_terminal(params, cfg,
                                         jax.random.fold_in(key, 2), ys)
    assert float(l_term) == pytest.approx(float(l_traj), rel=0.25)


# -----------------------------------------------------------------------------
# the launch CLI, 1 and 2 (simulated) devices
# -----------------------------------------------------------------------------


def _run_train_cli(extra_env=None, extra_args=()):
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))
    env.update(extra_env or {})
    cmd = [sys.executable, "-m", "launch.train", "--workload", "latent-sde",
           "--steps", "2", "--batch", "8", "--sde-steps", "8",
           "--seq-len", "9", *extra_args]
    return subprocess.run(cmd, cwd=repo, env=env, capture_output=True,
                          text=True, timeout=600)


def test_train_cli_single_device():
    r = _run_train_cli()
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[latent-sde] done" in r.stdout


def test_train_cli_two_simulated_devices():
    r = _run_train_cli(
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "data-parallel over 2 devices" in r.stdout
    assert "[latent-sde] done" in r.stdout


def test_train_cli_rejects_misaligned_grid():
    """The CLI surfaces the named grid error, not a crash."""
    r = _run_train_cli(extra_args=("--sde-steps", "30"))
    assert r.returncode != 0
    assert "num_steps (30)" in r.stderr and "T (8" in r.stderr
