"""Front-end tests: registry dispatch, batched solving, Pallas-fused paths.

Covers the unified ``repro.solve()`` surface:

* every registered solver × gradient-mode combination accepts or rejects
  exactly as its :class:`repro.core.solve.SolverSpec` declares;
* vmapped multi-trajectory ``solve_batched`` matches a Python loop of
  single solves bitwise;
* the Pallas-fused reversible Heun (interpret mode on CPU) matches the
  unfused path on forward trajectories AND parameter gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.brownian import BrownianPath
from repro.core.solve import (GRADIENT_MODES, SOLVERS, SolverSpec,
                              get_solver, register_solver, solve,
                              solve_batched)
from repro.core.solvers import NFE_PER_STEP


def _ou():
    params = {"theta": jnp.float32(1.2), "mu": jnp.float32(0.5),
              "sigma": jnp.float32(0.3)}
    drift = lambda p, t, x: p["theta"] * (p["mu"] - x)
    diffusion = lambda p, t, x: p["sigma"] * jnp.ones_like(x)
    return params, drift, diffusion


def _neural(key, x_dim=6, dtype=jnp.float32):
    from repro import nn

    k1, k2 = jax.random.split(key)
    p = {"f": nn.mlp_init(k1, [x_dim, 16, x_dim], dtype=dtype),
         "g": nn.mlp_init(k2, [x_dim, 16, x_dim], dtype=dtype)}
    drift = lambda p_, t, x: nn.mlp(p_["f"], x, nn.lipswish, jnp.tanh)
    diffusion = lambda p_, t, x: 0.2 * nn.mlp(p_["g"], x, nn.lipswish, jnp.tanh)
    return p, drift, diffusion


# -----------------------------------------------------------------------------
# registry dispatch
# -----------------------------------------------------------------------------


def test_registry_contains_all_solvers():
    assert repro.available_solvers() == (
        "euler_maruyama", "heun", "midpoint", "reversible_heun", "srk")
    for spec in SOLVERS.values():
        assert spec.nfe_per_step == NFE_PER_STEP[spec.name]
        assert spec.gradient_modes  # never empty


@pytest.mark.parametrize("solver", sorted(SOLVERS))
@pytest.mark.parametrize("mode", GRADIENT_MODES)
def test_every_solver_mode_combination_dispatches_or_rejects(key, solver, mode):
    """Supported combos run and return the right shape; unsupported combos
    raise ValueError naming the solver — never silently fall back."""
    params, drift, diffusion = _ou()
    z0 = jnp.ones((4, 3))
    spec = get_solver(solver)
    bm = BrownianPath(key, 0.0, 1.0, (4, 3),
                      levy_area="space-time" if spec.needs_levy_area else None)
    save_traj = mode not in ("continuous_adjoint", "checkpoint")
    run = lambda: solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 8,
                        solver=solver, gradient_mode=mode,
                        save_trajectory=save_traj)
    if mode in get_solver(solver).gradient_modes:
        out = run()
        assert out.shape == ((9, 4, 3) if save_traj else (4, 3))
        # and the gradient path is actually wired
        g = jax.grad(lambda p: jnp.sum(solve(
            drift, diffusion, p, z0, bm, 0.0, 1.0, 8, solver=solver,
            gradient_mode=mode, save_trajectory=save_traj)[-1]))(params)
        assert all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(g))
    else:
        with pytest.raises(ValueError, match=solver):
            run()


def test_registry_embedded_pairs():
    """Adaptive capability is registry data: every solver except
    euler_maruyama carries an embedded error estimate (reversible Heun's
    z−ẑ gap increment; Heun/midpoint's Euler pair)."""
    for name, spec in SOLVERS.items():
        if name == "euler_maruyama":
            assert spec.embedded_stepper is None
        else:
            assert spec.embedded_stepper is not None, name


def test_unknown_solver_and_mode_rejected(key):
    params, drift, diffusion = _ou()
    z0 = jnp.ones((2, 2))
    bm = BrownianPath(key, 0.0, 1.0, (2, 2))
    with pytest.raises(ValueError, match="unknown solver"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4, solver="rk45")
    with pytest.raises(ValueError, match="unknown gradient_mode"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              gradient_mode="magic")
    with pytest.raises(ValueError, match="unknown noise"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4, noise="weird")


def test_pallas_flag_validation(key):
    params, drift, diffusion = _ou()
    z0 = jnp.ones((2, 4))
    bm = BrownianPath(key, 0.0, 1.0, (2, 4))
    # discretise + pallas: AD can't trace pallas_call -> eager rejection
    with pytest.raises(ValueError, match="discretise"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="reversible_heun", use_pallas_kernels=True)
    # non-reversible solver has no fused path
    with pytest.raises(ValueError, match="no fused Pallas path"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="midpoint", use_pallas_kernels=True)
    # general noise unsupported by the elementwise kernels
    with pytest.raises(ValueError, match="diagonal"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="reversible_heun", gradient_mode="reversible_adjoint",
              noise="general", use_pallas_kernels=True)


def test_pallas_flag_validation_is_mode_not_adaptivity(key):
    """The pallas rejection table is about gradient mode and noise, NOT
    adaptivity: adaptive × pallas × discretise is still rejected (plain AD
    cannot trace pallas_call), while the same flags under
    reversible_adjoint are legal — the fused kernels take the controller's
    dt as a traced scalar operand (covered end-to-end in
    tests/test_adaptive.py)."""
    params, drift, diffusion = _ou()
    z0 = jnp.ones((2, 4))
    bm = BrownianPath(key, 0.0, 1.0, (2, 4))
    with pytest.raises(ValueError, match="discretise"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="reversible_heun", use_pallas_kernels=True,
              save_trajectory=False, adaptive=True)
    with pytest.raises(ValueError, match="diagonal"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="reversible_heun", gradient_mode="reversible_adjoint",
              noise="general", use_pallas_kernels=True,
              save_trajectory=False, adaptive=True)


def test_bridge_depth_validation(key):
    """bridge_depth is an adaptive-only BrownianPath-only option; every
    invalid use is rejected eagerly with an actionable message."""
    from repro.core.brownian import DenseBrownianPath

    params, drift, diffusion = _ou()
    z0 = jnp.ones((2, 4))
    bm = BrownianPath(key, 0.0, 1.0, (2, 4))
    # fixed-grid solve would silently ignore it
    with pytest.raises(ValueError, match="adaptive-mode options"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              bridge_depth=10)
    # a fixed-resolution path has no descent to cap
    dbm = DenseBrownianPath.sample(key, 0.0, 1.0, 16, (2, 4))
    with pytest.raises(ValueError, match="fixed resolution"):
        solve(drift, diffusion, params, z0, dbm, 0.0, 1.0, 4,
              adaptive=True, save_trajectory=False, bridge_depth=10)
    # nonsensical depths
    with pytest.raises(ValueError, match="positive int"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              adaptive=True, save_trajectory=False, bridge_depth=0)
    # and the valid case runs (depth caps the descent, still converges)
    out = solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 16,
                adaptive=True, save_trajectory=False, bridge_depth=12)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_register_solver_validates_specs():
    with pytest.raises(ValueError, match="unknown gradient mode"):
        register_solver(SolverSpec(
            "bad", lambda *a: None, None, 1, 0.5, ("nope",)))
    with pytest.raises(ValueError, match="reverse_stepper"):
        register_solver(SolverSpec(
            "bad", lambda *a: None, None, 1, 0.5, ("reversible_adjoint",)))
    assert "bad" not in SOLVERS


def test_registered_custom_solver_dispatches(key):
    """A solver added via register_solver() is actually runnable through
    solve() — the registry's stepper is dispatched, not a hardcoded dict."""
    calls = {"n": 0}

    def drifted_euler(z, t, dt, dw, drift, diffusion, params, noise):
        calls["n"] += 1
        from repro.core.solvers import apply_diffusion
        return z + drift(params, t, z) * dt + apply_diffusion(
            diffusion(params, t, z), dw, noise)

    register_solver(SolverSpec(
        "custom_euler", drifted_euler, None, nfe_per_step=1, strong_order=0.5,
        gradient_modes=("discretise",), notes="test-only"))
    try:
        params, drift, diffusion = _ou()
        z0 = jnp.ones((2, 3))
        bm = BrownianPath(key, 0.0, 1.0, (2, 3))
        out = solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 8,
                    solver="custom_euler")
        ref = solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 8,
                    solver="euler_maruyama")
        assert calls["n"] > 0
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)
    finally:
        del SOLVERS["custom_euler"]


def test_custom_solver_rejected_for_unimplemented_adjoints(key):
    """Adjoint backends that only exist for the builtin steppers refuse
    custom solvers eagerly instead of silently integrating with the wrong
    numerics (backward-Euler fallback / reversible-Heun machinery)."""
    step = lambda z, t, dt, dw, dr, di, p, n: z
    register_solver(SolverSpec(
        "custom_ca", step, None, nfe_per_step=1, strong_order=0.5,
        gradient_modes=("discretise", "continuous_adjoint")))
    register_solver(SolverSpec(
        "custom_ra", step, step, nfe_per_step=1, strong_order=0.5,
        gradient_modes=("reversible_adjoint",)))
    try:
        params, drift, diffusion = _ou()
        z0 = jnp.ones((2, 2))
        bm = BrownianPath(key, 0.0, 1.0, (2, 2))
        with pytest.raises(ValueError, match="continuous-adjoint backward"):
            solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
                  solver="custom_ca", gradient_mode="continuous_adjoint",
                  save_trajectory=False)
        with pytest.raises(ValueError, match="reversible-Heun stepper pair"):
            solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
                  solver="custom_ra", gradient_mode="reversible_adjoint")
    finally:
        del SOLVERS["custom_ca"], SOLVERS["custom_ra"]


def test_continuous_adjoint_requires_terminal_only(key):
    params, drift, diffusion = _ou()
    z0 = jnp.ones((2, 2))
    bm = BrownianPath(key, 0.0, 1.0, (2, 2))
    with pytest.raises(ValueError, match="save_trajectory"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="midpoint", gradient_mode="continuous_adjoint")


# -----------------------------------------------------------------------------
# batched multi-trajectory solving
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("solver", ["euler_maruyama", "reversible_heun"])
def test_batched_matches_looped_single_solves(key, solver):
    """solve_batched == a Python loop of solves, per-trajectory."""
    params, drift, diffusion = _neural(key)
    B = 5
    z0 = jax.random.normal(jax.random.fold_in(key, 1), (B, 6))
    keys = jax.random.split(jax.random.fold_in(key, 2), B)

    batched = solve_batched(drift, diffusion, params, z0, keys, 0.0, 1.0, 16,
                            solver=solver)
    assert batched.shape == (B, 17, 6)
    for i in range(B):
        bm = BrownianPath(keys[i], 0.0, 1.0, (6,))
        single = solve(drift, diffusion, params, z0[i], bm, 0.0, 1.0, 16,
                       solver=solver)
        np.testing.assert_allclose(np.asarray(batched[i]), np.asarray(single),
                                   rtol=1e-6, atol=1e-6)


def test_batched_gradients_through_exact_adjoint(key):
    """grad of a vmapped exact-adjoint ensemble equals the sum of
    per-trajectory grads."""
    params, drift, diffusion = _neural(key)
    B = 3
    z0 = jax.random.normal(jax.random.fold_in(key, 1), (B, 6))
    keys = jax.random.split(jax.random.fold_in(key, 2), B)

    def batched_loss(p):
        traj = solve_batched(drift, diffusion, p, z0, keys, 0.0, 1.0, 8,
                             solver="reversible_heun",
                             gradient_mode="reversible_adjoint")
        return jnp.sum(traj[:, -1] ** 2)

    def looped_loss(p):
        tot = 0.0
        for i in range(B):
            bm = BrownianPath(keys[i], 0.0, 1.0, (6,))
            traj = solve(drift, diffusion, p, z0[i], bm, 0.0, 1.0, 8,
                         solver="reversible_heun",
                         gradient_mode="reversible_adjoint")
            tot = tot + jnp.sum(traj[-1] ** 2)
        return tot

    gb = jax.grad(batched_loss)(params)
    gl = jax.grad(looped_loss)(params)
    for a, b in zip(jax.tree.leaves(gb), jax.tree.leaves(gl)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_batched_shape_mismatch_rejected(key):
    params, drift, diffusion = _ou()
    with pytest.raises(ValueError, match="batch"):
        solve_batched(drift, diffusion, params, jnp.ones((4, 2)),
                      jax.random.split(key, 3), 0.0, 1.0, 4)


# -----------------------------------------------------------------------------
# Pallas-fused reversible Heun (interpret mode on CPU)
# -----------------------------------------------------------------------------


def test_pallas_fused_forward_matches_unfused(key):
    params, drift, diffusion = _neural(key)
    z0 = jax.random.normal(jax.random.fold_in(key, 1), (4, 6))
    bm = BrownianPath(jax.random.fold_in(key, 2), 0.0, 1.0, (4, 6))

    kw = dict(solver="reversible_heun", gradient_mode="reversible_adjoint")
    fused = solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 32,
                  use_pallas_kernels=True, **kw)
    unfused = solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 32, **kw)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-5, atol=1e-5)


def test_pallas_fused_gradients_match_unfused(key):
    """Acceptance bar: fused forward + fused backward reconstruction agree
    with the unfused exact adjoint on parameter gradients to <= 1e-5."""
    params, drift, diffusion = _neural(key)
    z0 = jax.random.normal(jax.random.fold_in(key, 1), (4, 6))
    bm = BrownianPath(jax.random.fold_in(key, 2), 0.0, 1.0, (4, 6))

    def loss(p, fused):
        traj = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 32,
                     solver="reversible_heun",
                     gradient_mode="reversible_adjoint",
                     use_pallas_kernels=fused)
        return jnp.mean(traj[-1] ** 2)

    gf = jax.grad(lambda p: loss(p, True))(params)
    gu = jax.grad(lambda p: loss(p, False))(params)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_pallas_fused_under_jit_and_vmap(key):
    """The fused path composes with jit and with batched solving."""
    params, drift, diffusion = _ou()
    B = 3
    z0 = jnp.zeros((B, 4))
    keys = jax.random.split(key, B)
    f = jax.jit(lambda p: solve_batched(
        drift, diffusion, p, z0, keys, 0.0, 1.0, 8,
        solver="reversible_heun", gradient_mode="reversible_adjoint",
        use_pallas_kernels=True))
    out = f(params)
    ref = solve_batched(drift, diffusion, params, z0, keys, 0.0, 1.0, 8,
                        solver="reversible_heun",
                        gradient_mode="reversible_adjoint")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_fused_step_dispatch_interpret_matches_oracle(key, monkeypatch):
    """The fused-step dispatch (kernels/ops.py, DESIGN.md §5): off-TPU the
    default runs the fused jnp oracle, and ``REPRO_FORCE_PALLAS_INTERPRET``
    forces the Pallas interpreter — both must agree with the plain unfused
    stepper, for the forward step AND the sign=-1 reverse reconstruction."""
    from repro.core.solvers import (RevHeunState, reversible_heun_reverse_step,
                                    reversible_heun_step)

    k1, k2 = jax.random.split(key)
    drift = lambda p, t, z: -p * z
    diffusion = lambda p, t, z: 0.3 * jnp.ones_like(z)
    p = jnp.float32(0.7)
    z = jax.random.normal(k1, (4, 8))
    state = RevHeunState(z, z, drift(p, 0.0, z), diffusion(p, 0.0, z))
    dw = 0.1 * jax.random.normal(k2, (4, 8))

    variants = {}
    for name, use_pallas, force in (("unfused", False, False),
                                    ("oracle", True, False),   # auto: off-TPU
                                    ("interpret", True, True)):
        if force:
            monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
        else:
            monkeypatch.delenv("REPRO_FORCE_PALLAS_INTERPRET", raising=False)
        fwd = reversible_heun_step(state, 0.0, 0.125, dw, drift, diffusion,
                                   p, "diagonal", use_pallas=use_pallas)
        rev = reversible_heun_reverse_step(fwd, 0.125, 0.125, dw, drift,
                                           diffusion, p, "diagonal",
                                           use_pallas=use_pallas)
        variants[name] = (fwd, rev)
    for name in ("oracle", "interpret"):
        for got, want in zip(jax.tree.leaves(variants[name]),
                             jax.tree.leaves(variants["unfused"])):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-6)
    # the reverse step must reconstruct the pre-step state (Algorithm 2)
    for got, want in zip(jax.tree.leaves(variants["oracle"][1]),
                         jax.tree.leaves(tuple(state))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
