"""Discretise-then-optimise: JAX AD straight through the solver scan.

The reference gradient path (§2.3): residuals are the scan's O(n)
activations and the backward rule is whatever ``jax.vjp`` derives.  Every
registered stepper serves it — the spec's stepper is dispatched into
``sde_solve``'s scan.  Adaptive solves run forward-only under this mode
(``lax.while_loop`` has no reverse-mode rule; use ``reversible_adjoint``
or ``checkpoint`` for adaptive gradients).
"""

from __future__ import annotations

from .. import scopes
from ..solvers import sde_solve
from .base import GradientBackend, register_backend


def _validate(spec, *, noise, save_trajectory, use_pallas, adaptive):
    if use_pallas:
        raise ValueError(
            "use_pallas_kernels is incompatible with gradient_mode="
            "'discretise': the fused kernels' derivative is the "
            "hand-derived backward kernel pair registered through the "
            "reversible-adjoint custom_vjp, not a pallas_call VJP rule "
            "plain AD could trace.  Use gradient_mode="
            "'reversible_adjoint' instead — its forward pass is the "
            "identical fused scan (so this also covers pure forward "
            "simulation), and differentiating it runs the fused exact "
            "adjoint")


@scopes.scoped(scopes.SOLVE)
def _solve(spec, drift, diffusion, params, z0, bm, t0, t1, num_steps, *,
           noise, save_trajectory, use_pallas):
    return sde_solve(
        drift, diffusion, params, z0, bm, t0, t1, num_steps,
        solver=spec.name, noise=noise, save_trajectory=save_trajectory,
        use_pallas_kernels=use_pallas,
        # registry-registered steppers (z-carried) dispatch through here;
        # "reversible_heun" keeps sde_solve's carried-state fast path.
        step_fn=None if spec.name == "reversible_heun" else spec.stepper)


@scopes.scoped(scopes.SOLVE)
def _solve_adaptive(spec, drift, diffusion, params, z0, bm, rtol, atol,
                    t0, t1, max_steps, dt0, *, noise, use_pallas,
                    bridge_depth):
    # late import: the adaptive driver lives in the front-end module, which
    # imports this package at load time; by call time it is loaded
    from ..solve import _adaptive_loop
    from ..solvers import reversible_heun_step

    carry, stats = _adaptive_loop(
        spec, drift, diffusion, params, z0, bm, t0, t1, rtol, atol,
        max_steps, dt0, noise, use_pallas=use_pallas,
        bridge_depth=bridge_depth)
    z = carry.z if spec.stepper is reversible_heun_step else carry
    return z, stats.converged


register_backend(GradientBackend(
    name="discretise",
    summary="AD through the scan, O(n) activation memory",
    terminal_only=False,
    supports_adaptive=True,
    solve=_solve,
    solve_adaptive=_solve_adaptive,
    validate=_validate,
))
