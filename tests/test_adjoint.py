"""Gradient tests — the paper's headline claim (Fig. 2 / Table 6).

The reversible-Heun exact adjoint must match discretise-then-optimise to
floating-point error; the continuous adjoint for midpoint/Heun must show
truncation error that DECREASES with step size.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gradients import continuous_adjoint_solve, reversible_heun_solve
from repro.core.brownian import BrownianPath
from repro.core.solvers import sde_solve


@pytest.fixture(autouse=True)
def _x64_scope():
    """These tests need f64 (FP-exactness claims); scope it to this module
    so x64 never leaks into the bf16 model tests that run later."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)



def _problem(key, batch=8, x_dim=8, w_dim=4, dtype=jnp.float64):
    from repro import nn

    k1, k2, kz, kw = jax.random.split(key, 4)
    params = {"f": nn.mlp_init(k1, [x_dim, 8, x_dim], dtype=dtype),
              "g": nn.mlp_init(k2, [x_dim, 8, x_dim * w_dim], dtype=dtype)}
    drift = lambda p, t, x: nn.mlp(p["f"], x, nn.lipswish, jnp.tanh)

    def diffusion(p, t, x):
        out = nn.mlp(p["g"], x, nn.lipswish, jnp.tanh)
        return 0.2 * out.reshape(x.shape[:-1] + (x_dim, w_dim))

    z0 = jax.random.normal(kz, (batch, x_dim), dtype)
    bm = BrownianPath(kw, 0.0, 1.0, (batch, w_dim), dtype)
    return params, drift, diffusion, z0, bm


def _rel_err(g1, g2):
    n = sum(float(jnp.sum(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
    d = max(sum(float(jnp.sum(jnp.abs(a))) for a in jax.tree.leaves(g1)), 1e-300)
    return n / d


def test_exact_adjoint_matches_dto(key):
    """reversible_heun_solve gradients == autodiff-through-the-solver."""
    params, drift, diffusion, z0, bm = _problem(key)
    n = 64

    def loss_exact(p, z):
        traj = reversible_heun_solve(drift, diffusion, p, z, bm, 0.0, 1.0, n, "general")
        return jnp.sum(traj[-1] ** 2) + jnp.sum(jnp.abs(traj[n // 2]))

    def loss_dto(p, z):
        traj = sde_solve(drift, diffusion, p, z, bm, 0.0, 1.0, n,
                         solver="reversible_heun", noise="general")
        return jnp.sum(traj[-1] ** 2) + jnp.sum(jnp.abs(traj[n // 2]))

    g1 = jax.grad(loss_exact, argnums=(0, 1))(params, z0)
    g2 = jax.grad(loss_dto, argnums=(0, 1))(params, z0)
    assert _rel_err(g1, g2) < 1e-12  # float64 roundoff — 'accurate to FP error'


def test_exact_adjoint_under_jit_and_vmap(key):
    params, drift, diffusion, z0, bm = _problem(key)

    @jax.jit
    def g(p):
        traj = reversible_heun_solve(drift, diffusion, p, z0, bm, 0.0, 1.0, 16, "general")
        return jnp.sum(traj[-1] ** 2)

    out = jax.jit(jax.grad(g))(params)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(out))


@pytest.mark.parametrize("solver", ["midpoint", "heun"])
def test_continuous_adjoint_error_decreases(key, solver):
    """Standard continuous adjoints: O(h^p) gradient error, shrinking in h."""
    params, drift, diffusion, z0, bm = _problem(key)
    errs = []
    for n in (4, 64):
        def loss_otd(p):
            zT = continuous_adjoint_solve(drift, diffusion, p, z0, bm, 0.0, 1.0, n,
                                          solver=solver, noise="general")
            return jnp.sum(zT ** 2)

        def loss_dto(p):
            traj = sde_solve(drift, diffusion, p, z0, bm, 0.0, 1.0, n,
                             solver=solver, noise="general")
            return jnp.sum(traj[-1] ** 2)

        g1 = jax.grad(loss_otd)(params)
        g2 = jax.grad(loss_dto)(params)
        errs.append(_rel_err(g1, g2))
    assert errs[1] < errs[0], f"{solver} adjoint error did not decrease: {errs}"
    assert errs[0] > 1e-10, "standard adjoint should NOT be exact"


def test_exact_adjoint_memory_scaling(key):
    """The custom-vjp backward stores O(1) residuals in depth: the saved
    residual pytree must not grow with num_steps."""
    params, drift, diffusion, z0, bm = _problem(key)

    def residual_count(n):
        def loss(p):
            traj = reversible_heun_solve(drift, diffusion, p, z0, bm, 0.0, 1.0, n, "general")
            return jnp.sum(traj[-1] ** 2)

        # residuals = everything saved between fwd and bwd; measure via the
        # linearized jaxpr of the fwd rule
        _, f_vjp = jax.vjp(loss, params)
        leaves = jax.tree.leaves(f_vjp)
        return sum(x.size for x in leaves if hasattr(x, "size"))

    # trajectory output itself is O(n); residuals beyond it must stay flat.
    r16 = residual_count(16)
    r256 = residual_count(256)
    traj_bytes_16 = 17 * z0.size
    traj_bytes_256 = 257 * z0.size
    # subtract the cotangent-trajectory contribution before comparing
    assert (r256 - traj_bytes_256) <= (r16 - traj_bytes_16) * 1.5 + 1024, \
        f"residuals grew with steps: {r16} -> {r256}"


# -----------------------------------------------------------------------------
# fused (Pallas) exact adjoint: gradient-exactness regressions
# -----------------------------------------------------------------------------


def _diag_problem(key, batch=4, x_dim=8, dtype=jnp.float64):
    """Diagonal-noise problem — the fused kernels' supported layout."""
    from repro import nn

    k1, k2, kz, kw = jax.random.split(key, 4)
    params = {"f": nn.mlp_init(k1, [x_dim, 8, x_dim], dtype=dtype),
              "g": nn.mlp_init(k2, [x_dim, 8, x_dim], dtype=dtype)}
    drift = lambda p, t, x: nn.mlp(p["f"], x, nn.lipswish, jnp.tanh)
    diffusion = lambda p, t, x: 0.2 * nn.mlp(p["g"], x, nn.lipswish, jnp.tanh)
    z0 = jax.random.normal(kz, (batch, x_dim), dtype)
    bm = BrownianPath(kw, 0.0, 1.0, (batch, x_dim), dtype)
    return params, drift, diffusion, z0, bm


def _assert_tree_equal(g1, g2, msg):
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=msg)


@pytest.mark.parametrize("save_trajectory", [True, False],
                         ids=["trajectory", "final"])
def test_fused_adjoint_bitwise_matches_unfused(key, save_trajectory):
    """use_pallas_kernels=True must not change the gradient AT ALL: the
    hand-derived backward kernels are bitwise the jax.vjp transpose of the
    unfused step, so fused and unfused exact adjoints agree to 0.0 in
    float64 — not merely to round-off."""
    from repro.core.solve import solve

    params, drift, diffusion, z0, bm = _diag_problem(key)
    n = 32

    def loss(p, z, fused):
        out = solve(drift, diffusion, p, z, bm, 0.0, 1.0, n,
                    gradient_mode="reversible_adjoint",
                    save_trajectory=save_trajectory,
                    use_pallas_kernels=fused)
        return jnp.sum(out ** 2)

    v_f, g_f = jax.value_and_grad(loss, argnums=(0, 1))(params, z0, True)
    v_u, g_u = jax.value_and_grad(loss, argnums=(0, 1))(params, z0, False)
    np.testing.assert_array_equal(np.asarray(v_f), np.asarray(v_u),
                                  err_msg="fused forward value drifted")
    _assert_tree_equal(g_f, g_u, "fused gradient != unfused gradient")


def test_fused_adjoint_matches_plain_ad(key):
    """Fused exact adjoint vs plain AD through the unfused frozen-grid scan
    — float64 round-off, same bar the unfused adjoint meets."""
    from repro.core.solve import solve

    params, drift, diffusion, z0, bm = _diag_problem(key)
    n = 64

    def loss_fused(p, z):
        traj = solve(drift, diffusion, p, z, bm, 0.0, 1.0, n,
                     gradient_mode="reversible_adjoint",
                     use_pallas_kernels=True)
        return jnp.sum(traj[-1] ** 2)

    def loss_dto(p, z):
        traj = sde_solve(drift, diffusion, p, z, bm, 0.0, 1.0, n,
                         solver="reversible_heun", noise="diagonal")
        return jnp.sum(traj[-1] ** 2)

    g1 = jax.grad(loss_fused, argnums=(0, 1))(params, z0)
    g2 = jax.grad(loss_dto, argnums=(0, 1))(params, z0)
    assert _rel_err(g1, g2) < 1e-12
