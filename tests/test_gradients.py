"""Gradient-backend registry tests (DESIGN.md §12).

Three claims pinned here:

1. **Porting was a move, not a rewrite** — dispatching through
   ``solve(gradient_mode=...)`` is BITWISE identical (f64, values and
   gradients) to calling the moved backend functions directly, on the
   fixed-grid, terminal-only, and adaptive paths.
2. **Checkpointing is exact for every registered solver** — recursive
   binomial checkpointing replays the same discrete steps, so its
   gradients match discretise-then-optimise to floating-point noise for
   every solver × noise type, on fixed and adaptive (accepted) grids, and
   its cost schedule follows the nested-scan model.
3. **Invalid combinations fail eagerly by name** — unknown backends,
   unknown precision policies, and illegal solver × mode × flag cells
   raise named ValueErrors at dispatch time, never from inside jit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.brownian import BrownianPath
from repro.core.gradients import (
    GRADIENT_BACKENDS,
    GradientBackend,
    checkpoint_schedule,
    continuous_adjoint_solve,
    register_backend,
    resolve_precision,
    reversible_heun_solve,
    reversible_heun_solve_adaptive,
    reversible_heun_solve_final,
)
from repro.core.solve import (
    GRADIENT_MODES,
    SOLVERS,
    get_solver,
    gradient_capabilities,
    solve,
)


@pytest.fixture(autouse=True)
def _x64_scope():
    """Bitwise-parity claims need f64; scope it to this module."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


def _problem(key, batch=4, x_dim=4, w_dim=3, noise="general",
             dtype=jnp.float64, levy_area=None):
    from repro import nn

    k1, k2, kz, kw = jax.random.split(key, 4)
    params = {"f": nn.mlp_init(k1, [x_dim, 8, x_dim], dtype=dtype),
              "g": nn.mlp_init(k2, [x_dim, 8, x_dim * w_dim], dtype=dtype)}
    drift = lambda p, t, x: nn.mlp(p["f"], x, nn.lipswish, jnp.tanh)

    if noise == "general":
        def diffusion(p, t, x):
            out = nn.mlp(p["g"], x, nn.lipswish, jnp.tanh)
            return 0.2 * out.reshape(x.shape[:-1] + (x_dim, w_dim))
        w_shape = (batch, w_dim)
    else:
        def diffusion(p, t, x):
            out = nn.mlp(p["g"], x, nn.lipswish, jnp.tanh)
            return 0.2 * out[..., :x_dim]
        w_shape = (batch, x_dim)

    z0 = jax.random.normal(kz, (batch, x_dim), dtype)
    bm = BrownianPath(kw, 0.0, 1.0, w_shape, dtype, levy_area=levy_area)
    return params, drift, diffusion, z0, bm


def _grads_equal(g1, g2):
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _max_grad_diff(g1, g2):
    return max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))


# =============================================================================
# Registry contents
# =============================================================================


def test_registry_modes_and_capabilities():
    assert GRADIENT_MODES == ("discretise", "reversible_adjoint",
                              "continuous_adjoint", "checkpoint")
    caps = gradient_capabilities()
    assert set(caps) == set(GRADIENT_MODES)
    # checkpoint and discretise serve EVERY solver; the exact adjoint only
    # the reversible pair; backsolve only the three with a backward
    # integrator
    assert set(caps["checkpoint"]) == set(SOLVERS)
    assert caps["checkpoint"] == caps["discretise"]
    assert caps["reversible_adjoint"] == ("reversible_heun",)
    assert set(caps["continuous_adjoint"]) == {
        "euler_maruyama", "midpoint", "heun"}


def test_backend_terminal_only_flags():
    assert not GRADIENT_BACKENDS["discretise"].terminal_only
    assert not GRADIENT_BACKENDS["reversible_adjoint"].terminal_only
    assert GRADIENT_BACKENDS["continuous_adjoint"].terminal_only
    assert GRADIENT_BACKENDS["checkpoint"].terminal_only


def test_register_backend_requires_adaptive_impl():
    with pytest.raises(ValueError, match="solve_adaptive"):
        register_backend(GradientBackend(
            name="broken", summary="", terminal_only=False,
            supports_adaptive=True, solve=lambda *a, **k: None,
            solve_adaptive=None, validate=lambda *a, **k: None))


# =============================================================================
# Bitwise parity: solve() dispatch vs the moved backend functions
# =============================================================================


def test_reversible_adjoint_dispatch_bitwise_trajectory(key):
    params, drift, diffusion, z0, bm = _problem(key)

    def via_solve(p):
        traj = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 8,
                     solver="reversible_heun",
                     gradient_mode="reversible_adjoint", noise="general")
        return jnp.sum(traj ** 2)

    def direct(p):
        traj = reversible_heun_solve(drift, diffusion, p, z0, bm, 0.0, 1.0,
                                     8, noise="general")
        return jnp.sum(traj ** 2)

    (l1, g1) = jax.value_and_grad(via_solve)(params)
    (l2, g2) = jax.value_and_grad(direct)(params)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    _grads_equal(g1, g2)


def test_reversible_adjoint_dispatch_bitwise_final(key):
    params, drift, diffusion, z0, bm = _problem(key)

    def via_solve(p):
        zT = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 8,
                   solver="reversible_heun",
                   gradient_mode="reversible_adjoint", noise="general",
                   save_trajectory=False)
        return jnp.sum(zT ** 2)

    def direct(p):
        zT = reversible_heun_solve_final(drift, diffusion, p, z0, bm, 0.0,
                                         1.0, 8, noise="general")
        return jnp.sum(zT ** 2)

    (l1, g1) = jax.value_and_grad(via_solve)(params)
    (l2, g2) = jax.value_and_grad(direct)(params)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    _grads_equal(g1, g2)


@pytest.mark.parametrize("noise,use_pallas", [
    ("diagonal", False), ("diagonal", True),
    ("general", False), ("general", True)])
def test_trajectory_and_terminal_forms_share_the_sweep(key, noise,
                                                       use_pallas):
    """The trajectory form under a loss that reads only ``traj[-1]`` is the
    terminal form, bit for bit: one forward scan and one backward sweep
    serve both (general noise with ``use_pallas`` runs unfused)."""
    params, drift, diffusion, z0, bm = _problem(key, noise=noise)

    def via_traj(p, z):
        traj = reversible_heun_solve(drift, diffusion, p, z, bm, 0.0, 1.0, 8,
                                     noise, use_pallas)
        return jnp.sum(traj[-1] ** 2)

    def via_final(p, z):
        zT = reversible_heun_solve_final(drift, diffusion, p, z, bm, 0.0,
                                         1.0, 8, noise, use_pallas)
        return jnp.sum(zT ** 2)

    l1, g1 = jax.value_and_grad(via_traj, argnums=(0, 1))(params, z0)
    l2, g2 = jax.value_and_grad(via_final, argnums=(0, 1))(params, z0)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    _grads_equal(g1, g2)


def test_reversible_adjoint_dispatch_bitwise_adaptive(key):
    params, drift, diffusion, z0, bm = _problem(key)
    kw = dict(rtol=1e-2, atol=1e-4, max_steps=64, dt0=1.0 / 8)

    def via_solve(p):
        zT = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 8,
                   solver="reversible_heun",
                   gradient_mode="reversible_adjoint", noise="general",
                   save_trajectory=False, adaptive=True, **kw)
        return jnp.sum(zT ** 2)

    def direct(p):
        zT, converged = reversible_heun_solve_adaptive(
            drift, diffusion, p, z0, bm, kw["rtol"], kw["atol"], 0.0, 1.0,
            kw["max_steps"], kw["dt0"], noise="general")
        # same NaN-poisoning solve() applies (identity when converged)
        zT = jnp.where(converged, zT, jnp.nan)
        return jnp.sum(zT ** 2)

    (l1, g1) = jax.value_and_grad(via_solve)(params)
    (l2, g2) = jax.value_and_grad(direct)(params)
    assert bool(jnp.isfinite(l1))  # the grid converged — parity is real
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    _grads_equal(g1, g2)


def test_continuous_adjoint_dispatch_bitwise(key):
    params, drift, diffusion, z0, bm = _problem(key)

    def via_solve(p):
        zT = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 8,
                   solver="midpoint", gradient_mode="continuous_adjoint",
                   noise="general", save_trajectory=False)
        return jnp.sum(zT ** 2)

    def direct(p):
        zT = continuous_adjoint_solve(drift, diffusion, p, z0, bm, 0.0, 1.0,
                                      8, solver="midpoint", noise="general")
        return jnp.sum(zT ** 2)

    (l1, g1) = jax.value_and_grad(via_solve)(params)
    (l2, g2) = jax.value_and_grad(direct)(params)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    _grads_equal(g1, g2)


# =============================================================================
# Checkpoint backend: exact for every solver x noise, fixed and adaptive
# =============================================================================


@pytest.mark.parametrize("solver,noise", [
    (s, n) for s in sorted(SOLVERS) for n in ("diagonal", "general")
    if n in SOLVERS[s].noise_types])  # capability-aware: srk is diagonal-only
def test_checkpoint_matches_discretise(key, solver, noise):
    params, drift, diffusion, z0, bm = _problem(
        key, noise=noise,
        levy_area="space-time" if SOLVERS[solver].needs_levy_area else None)

    def loss(mode, save_traj):
        def f(p):
            out = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 8,
                        solver=solver, gradient_mode=mode, noise=noise,
                        save_trajectory=save_traj)
            return jnp.sum((out[-1] if save_traj else out) ** 2)
        return f

    l1, g1 = jax.value_and_grad(loss("discretise", True))(params)
    l2, g2 = jax.value_and_grad(loss("checkpoint", False))(params)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert _max_grad_diff(g1, g2) <= 1e-10


def test_checkpoint_non_pow2_horizon(key):
    """Padding/masking for n != 2^k must not perturb the real steps."""
    params, drift, diffusion, z0, bm = _problem(key)

    def loss(mode, save_traj, n):
        def f(p):
            out = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, n,
                        solver="heun", gradient_mode=mode, noise="general",
                        save_trajectory=save_traj)
            return jnp.sum((out[-1] if save_traj else out) ** 2)
        return f

    for n in (1, 3, 13):
        l1, g1 = jax.value_and_grad(loss("discretise", True, n))(params)
        l2, g2 = jax.value_and_grad(loss("checkpoint", False, n))(params)
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        assert _max_grad_diff(g1, g2) <= 1e-10


def test_checkpoint_adaptive_matches_reversible_adjoint(key):
    """On the controller's accepted grid, checkpoint's freeze-and-replay
    gradients must match the exact adjoint to floating-point noise."""
    params, drift, diffusion, z0, bm = _problem(key)
    kw = dict(adaptive=True, rtol=1e-2, atol=1e-4, max_steps=64,
              dt0=1.0 / 8, save_trajectory=False, noise="general")

    def loss(mode):
        def f(p):
            zT = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 8,
                       solver="reversible_heun", gradient_mode=mode, **kw)
            return jnp.sum(zT ** 2)
        return f

    l1, g1 = jax.value_and_grad(loss("reversible_adjoint"))(params)
    l2, g2 = jax.value_and_grad(loss("checkpoint"))(params)
    assert bool(jnp.isfinite(l1))  # converged — the comparison is real
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert _max_grad_diff(g1, g2) <= 1e-10


def test_checkpoint_adaptive_non_reversible_solver(key):
    """The capability the backend exists for: adaptive gradients for a
    solver with NO reversible pair (midpoint has an embedded estimate but
    no exact adjoint)."""
    params, drift, diffusion, z0, bm = _problem(key)

    def f(p):
        zT = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 8,
                   solver="midpoint", gradient_mode="checkpoint",
                   noise="general", save_trajectory=False, adaptive=True,
                   rtol=1e-2, atol=1e-4, max_steps=64, dt0=1.0 / 8)
        return jnp.sum(zT ** 2)

    l, g = jax.value_and_grad(f)(params)
    assert bool(jnp.isfinite(l))
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(g))


def test_checkpoint_schedule_model():
    """Pin the nested-scan cost recursion (the benchmark's memory gate)."""
    s1 = checkpoint_schedule(1)
    assert (s1["depth"], s1["peak_live_states"], s1["recompute_steps"]) == \
        (0, 1, 0)
    for n, depth in ((2, 1), (13, 4), (16, 4), (64, 6), (100, 7)):
        s = checkpoint_schedule(n)
        assert s["depth"] == depth
        assert s["padded_steps"] == 2 ** depth
        # L(k) = 2k + 1; R(2^k) = k 2^k — O(log n) memory, O(n log n) work
        assert s["peak_live_states"] == 2 * depth + 1
        assert s["recompute_steps"] == depth * 2 ** depth
    with pytest.raises(ValueError, match="num_steps"):
        checkpoint_schedule(0)


# =============================================================================
# Precision policy
# =============================================================================


def test_precision_policies_resolve():
    assert resolve_precision("highest").compute_dtype is None
    assert resolve_precision("bf16_compute").compute_dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="unknown precision"):
        resolve_precision("f8_compute")
    with pytest.raises(ValueError, match="unknown precision"):
        solve(lambda p, t, z: z, lambda p, t, z: z, {}, jnp.ones(3),
              BrownianPath(jax.random.PRNGKey(0), 0.0, 1.0, (3,),
                           jnp.float64),
              0.0, 1.0, 4, precision="f8_compute")


def test_precision_highest_is_identity(key):
    params, drift, diffusion, z0, bm = _problem(key)

    def loss(**kw):
        def f(p):
            traj = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 8,
                         noise="general", **kw)
            return jnp.sum(traj[-1] ** 2)
        return f

    l1, g1 = jax.value_and_grad(loss())(params)
    l2, g2 = jax.value_and_grad(loss(precision="highest"))(params)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    _grads_equal(g1, g2)


@pytest.mark.parametrize("mode,solver", [
    ("discretise", "heun"),
    ("reversible_adjoint", "reversible_heun"),
    ("checkpoint", "midpoint"),
])
def test_bf16_compute_composes_with_backends(key, mode, solver):
    """The policy wraps fields BEFORE the backend sees them, so every mode
    runs under it; gradients stay in the state dtype (accumulation is not
    degraded) and move by a small nonzero amount (the cast is real)."""
    params, drift, diffusion, z0, bm = _problem(key)
    save_traj = mode not in ("continuous_adjoint", "checkpoint")

    def loss(precision):
        def f(p):
            out = solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 8,
                        solver=solver, gradient_mode=mode, noise="general",
                        save_trajectory=save_traj, precision=precision)
            return jnp.sum((out[-1] if save_traj else out) ** 2)
        return f

    g_hi = jax.grad(loss("highest"))(params)
    g_lo = jax.grad(loss("bf16_compute"))(params)
    for v in jax.tree.leaves(g_lo):
        assert v.dtype == jnp.float64
        assert bool(jnp.all(jnp.isfinite(v)))
    diff = _max_grad_diff(g_hi, g_lo)
    assert 0.0 < diff < 1.0


# =============================================================================
# Eager named errors
# =============================================================================


def test_unknown_gradient_mode_lists_registry(key):
    params, drift, diffusion, z0, bm = _problem(key)
    with pytest.raises(ValueError, match="unknown gradient_mode") as e:
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              gradient_mode="bogus", noise="general")
    for mode in GRADIENT_MODES:  # the error must name every backend
        assert mode in str(e.value)


def test_checkpoint_rejects_trajectory_and_pallas(key):
    params, drift, diffusion, z0, bm = _problem(key)
    with pytest.raises(ValueError, match="save_trajectory"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="heun", gradient_mode="checkpoint", noise="general",
              save_trajectory=True)
    with pytest.raises(ValueError, match="pallas"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="reversible_heun", gradient_mode="checkpoint",
              noise="diagonal", save_trajectory=False,
              use_pallas_kernels=True)


def test_mode_not_served_names_capable_solvers(key):
    """A solver x mode miss names the solver AND the solvers that do serve
    the mode — the error is the capability table, not a dead end."""
    params, drift, diffusion, z0, bm = _problem(key)
    with pytest.raises(ValueError) as e:
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="euler_maruyama", gradient_mode="reversible_adjoint",
              noise="general")
    msg = str(e.value)
    assert "euler_maruyama" in msg and "reversible_heun" in msg


def test_continuous_adjoint_adaptive_error_mentions_checkpoint(key):
    """The backsolve/adaptive rejection now points at the backend that CAN
    do adaptive terminal gradients."""
    params, drift, diffusion, z0, bm = _problem(key)
    with pytest.raises(ValueError, match="checkpoint"):
        solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 4,
              solver="midpoint", gradient_mode="continuous_adjoint",
              noise="general", save_trajectory=False, adaptive=True,
              rtol=1e-2, atol=1e-4, max_steps=16)


def test_launch_step_adjoint_validation():
    from repro.core.sde import LatentSDEConfig
    from repro.launch.steps import make_latent_sde_step

    cfg = LatentSDEConfig(data_dim=2, num_steps=4, use_pallas_kernels=True,
                          exact_adjoint=False)
    with pytest.raises(ValueError, match="pallas"):
        make_latent_sde_step(cfg, lambda g, s, p: (g, s), 4, 5,
                             adjoint="checkpoint")
    with pytest.raises(ValueError, match="adjoint"):
        make_latent_sde_step(cfg, lambda g, s, p: (g, s), 4, 5,
                             adjoint="bogus")
