"""Continuous adjoint (optimise-then-discretise) baseline — eq. (6).

The backsolve of Li et al. 2020: the backward pass re-integrates the state
backwards in time alongside the adjoint SDE.  The recomputed ``z`` differs
from the forward pass by the solver truncation error, so gradients carry
O(√h) error — the failure mode the paper eliminates, kept here as the
measured baseline (benchmarks/gradient_error.py charts it).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from .. import scopes
from ..brownian import BrownianPath
from ..solvers import apply_diffusion
from .base import GradientBackend, register_backend

#: Solvers the continuous-adjoint backward integrator actually implements
#: a time-reversed stepper for.  A registered solver outside this set
#: would silently fall back to backward Euler — reject instead.
_CONTINUOUS_ADJOINT_BACKWARDS = ("euler_maruyama", "midpoint", "heun")


def continuous_adjoint_solve(
    drift: Callable,
    diffusion: Callable,
    params,
    z0: jax.Array,
    bm: BrownianPath,
    t0: float,
    t1: float,
    num_steps: int,
    solver: str = "midpoint",
    noise: str = "diagonal",
):
    """Terminal value ``z_T`` whose VJP solves the adjoint SDE (6) backwards.

    The backward pass re-integrates ``z`` *backwards in time with the same
    solver and the same Brownian sample* while integrating the adjoint
    ``a_t = dL/dz_t`` and parameter adjoint.  The recomputed ``z`` differs
    from the forward pass by the truncation error — the gradient error the
    paper measures in Fig. 2 / Table 6.
    """

    @jax.custom_vjp
    @scopes.scoped(scopes.SOLVE)
    def solve(params, z0):
        from ..solvers import sde_solve

        return sde_solve(
            drift, diffusion, params, z0, bm, t0, t1, num_steps,
            solver=solver, noise=noise, save_trajectory=False,
        )

    def fwd(params, z0):
        zT = solve(params, z0)
        return zT, (params, zT)

    @scopes.scoped(scopes.ADJOINT)
    def bwd(residuals, g_zT):
        params, zT = residuals
        dt = (t1 - t0) / num_steps
        dtype = zT.dtype
        g_params0 = jax.tree.map(jnp.zeros_like, params)

        # Augmented backward dynamics.  State: (z, a, g_params).
        #   dz      =  μ dt + σ∘dW                     (re-integrated, backwards)
        #   da      = -aᵀ ∂μ/∂z dt - aᵀ ∂σ/∂z ∘ dW     (eq. (6))
        #   dθ_adj  = -aᵀ ∂μ/∂θ dt - aᵀ ∂σ/∂θ ∘ dW
        # Implemented as drift/"diffusion·dW" of the augmented system so that
        # any two-evaluation Stratonovich solver below can integrate it.
        def aug_drift(t, aug):
            z, a, _ = aug
            mu, vjp = jax.vjp(lambda p, z_: drift(p, t, z_), params, z)
            d_theta, d_z = vjp(a)
            return (mu, jax.tree.map(jnp.negative, d_z), jax.tree.map(jnp.negative, d_theta))

        def aug_diff_dw(t, aug, dw):
            z, a, _ = aug
            sdw, vjp = jax.vjp(
                lambda p, z_: apply_diffusion(diffusion(p, t, z_), dw, noise), params, z
            )
            d_theta, d_z = vjp(a)
            return (sdw, jax.tree.map(jnp.negative, d_z), jax.tree.map(jnp.negative, d_theta))

        def add(u, v, scale=1.0):
            return jax.tree.map(lambda x, y: x + scale * y, u, v)

        def step_back(aug, n):
            # integrate from t_{n+1} down to t_n: effective dt is -dt, dW is
            # -dW_n (time reversal of the Stratonovich integral).
            t_hi = t0 + (n + 1) * dt
            dw = bm.increment(n, num_steps).astype(dtype)
            ndt, ndw = -dt, -dw
            if solver == "midpoint":
                k1 = add(add(aug, aug_drift(t_hi, aug), 0.5 * ndt),
                         aug_diff_dw(t_hi, aug, 0.5 * ndw))
                tm = t_hi + 0.5 * ndt
                new = add(add(aug, aug_drift(tm, k1), ndt), aug_diff_dw(tm, k1, ndw))
            elif solver == "heun":
                f0 = aug_drift(t_hi, aug)
                s0 = aug_diff_dw(t_hi, aug, ndw)
                pred = add(add(aug, f0, ndt), s0)
                t_lo = t_hi + ndt
                f1 = aug_drift(t_lo, pred)
                s1 = aug_diff_dw(t_lo, pred, ndw)
                new = add(add(add(add(aug, f0, 0.5 * ndt), f1, 0.5 * ndt),
                              s0, 0.5), s1, 0.5)
            else:  # euler_maruyama backwards (for completeness)
                new = add(add(aug, aug_drift(t_hi, aug), ndt), aug_diff_dw(t_hi, aug, ndw))
            return new, None

        aug0 = (zT, g_zT, g_params0)
        (z_rec, a0, g_params), _ = lax.scan(step_back, aug0, jnp.arange(num_steps - 1, -1, -1))
        del z_rec  # reconstructed z0 — differs from true z0 by truncation error
        return (g_params, a0)

    solve.defvjp(fwd, bwd)
    return solve(params, z0)


# =============================================================================
# Backend registration
# =============================================================================


def _validate(spec, *, noise, save_trajectory, use_pallas, adaptive):
    if spec.name not in _CONTINUOUS_ADJOINT_BACKWARDS:
        raise ValueError(
            f"solver {spec.name!r} declares continuous_adjoint but the "
            f"continuous-adjoint backward integrator only implements "
            f"{_CONTINUOUS_ADJOINT_BACKWARDS} (repro.core.gradients."
            f"continuous); extend continuous_adjoint_solve before "
            f"registering this combination")
    if save_trajectory:
        raise ValueError(
            "continuous_adjoint backpropagates a terminal-value cotangent "
            "only — call solve(..., save_trajectory=False)")
    if adaptive:
        raise ValueError(
            "adaptive=True is incompatible with gradient_mode="
            "'continuous_adjoint': the eq.-(6) backward integrator "
            "re-integrates on the forward's fixed uniform grid; use "
            "'reversible_adjoint' (exact adjoint replaying the accepted "
            "grid), 'checkpoint' (recursive rematerialisation of the "
            "accepted grid), or 'discretise' (forward simulation only)")


def _solve(spec, drift, diffusion, params, z0, bm, t0, t1, num_steps, *,
           noise, save_trajectory, use_pallas):
    return continuous_adjoint_solve(
        drift, diffusion, params, z0, bm, t0, t1, num_steps,
        solver=spec.name, noise=noise)


register_backend(GradientBackend(
    name="continuous_adjoint",
    summary="optimise-then-discretise backsolve (eq. 6), O(√h) gradient error",
    terminal_only=True,
    supports_adaptive=False,
    solve=_solve,
    validate=_validate,
))
