"""The paper's exact adjoint (§2.4, §3, Appendix C): ``reversible_adjoint``.

A ``jax.custom_vjp`` whose backward pass *algebraically reverses* the
solver (Algorithm 2): it reconstructs ``(z_n, ẑ_n, μ_n, σ_n)`` in closed
form from the step-``n+1`` state, replays the local forward, and
accumulates local VJPs.  Activation memory is **O(1) in the number of
steps** (only the terminal state is saved) and the resulting gradients
match discretise-then-optimise **to floating-point error** (paper Fig. 2).

One forward scan (:func:`_scan_forward`) and one backward sweep serve the
trajectory and terminal forms; one reverse-and-pull step
(:func:`_reverse_and_pull`) serves that sweep and the adaptive replay.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import scopes
from ..brownian import BrownianPath
from ..solvers import (
    RevHeunState,
    reversible_heun_reverse_at,
    reversible_heun_reverse_step,
    reversible_heun_step,
)
from .base import GradientBackend, register_backend


def _float0_zeros(tree):
    """Cotangents for non-differentiable (integer) leaves, e.g. PRNG keys."""

    def z(x):
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.inexact):
            return jnp.zeros_like(x)
        return np.zeros(x.shape, jax.dtypes.float0)

    return jax.tree.map(z, tree)


def _gen_spec(bm, z0, noise, use_pallas):
    """``(key, dt_grid_fn)`` for in-kernel ΔW generation, or ``None``.

    The fused forward scan may draw each step's increment *inside* the
    phase-1 kernel (counter-based Threefry keyed on the step index) instead
    of calling ``bm.increment`` — but only when the in-kernel draw is
    bitwise what ``bm.increment(n, num_steps).astype(z.dtype)`` produces:
    the path must be the counter-keyed :class:`BrownianPath` (not a dense
    or tree sampler), already in the solve dtype (no conversion to mimic),
    and shaped like the state (diagonal noise).
    """
    if not (use_pallas and noise == "diagonal"
            and type(bm) is BrownianPath):
        return None
    if jnp.dtype(bm.dtype) != jnp.dtype(z0.dtype):
        return None
    if tuple(bm.shape) != tuple(z0.shape):
        return None
    return bm.key, lambda num_steps: (bm.t1 - bm.t0) / num_steps


def _fused_local_vjp(drift, diffusion, params, state0, cts, t_left, dt, dw):
    """Hand-derived VJP of one Algorithm-1 step (the fused exact adjoint).

    Bitwise identical to ``jax.vjp`` of the unfused stepper (the grouping
    every term is accumulated in is the transpose's own — DESIGN.md §3
    derives it), with the elementwise cotangent phases running through the
    kernels/ops.py policy: backward Pallas kernels on TPU, the jnp oracle
    elsewhere.  One vector-field VJP per step, exactly like the unfused
    path — only the elementwise algebra around it is fused.

    ``state0`` is the step's *left* state (already reconstructed);
    ``cts = (g_z, g_zh, g_mu, g_sigma)`` the step-``n+1`` cotangents.
    Returns ``(dparams, (d_z, d_zh, d_mu, d_sigma))``.
    """
    from ...kernels import ops

    g_z, g_zh, g_mu, g_sigma = cts
    # ẑ_{n+1} recomputed from the left state — the same bits the unfused
    # local forward produces internally (state1.zh has drifted bits after
    # the round-trip through reconstruction).
    zh1 = ops.rev_heun_phase1(state0.z, state0.zh, state0.mu, state0.sigma,
                              dw, dt)
    c_mu1, c_sig1 = ops.rev_heun_bwd_phase1(g_z, g_mu, g_sigma, dw, dt)
    t_right = t_left + dt
    # Returning ``x`` first makes the g_zh seed enter the ẑ₁-cotangent sum
    # before the field contributions — the same accumulation order as the
    # unfused transpose, keeping the identity bitwise.
    _, vjp_fields = jax.vjp(
        lambda p, x: (x, drift(p, t_right, x), diffusion(p, t_right, x)),
        params, zh1)
    dparams, ghat = vjp_fields((g_zh, c_mu1, c_sig1))
    d_z, d_zh, d_mu, d_sigma = ops.rev_heun_bwd_phase2(g_z, ghat, dw, dt)
    return dparams, (d_z, d_zh, d_mu, d_sigma)


def _reverse_and_pull(drift, diffusion, params, noise, use_pallas):
    """One backward step: ``(state1, cts, t_left, dt, dw) -> (state0, cts0,
    dparams)``.

    Reconstructs the step's left state (Algorithm 2, anchored on the
    ``t_left`` the caller passes), then pulls the step-``n+1`` cotangents
    ``cts = (g_z, g_zh, g_mu, g_sigma)`` back through the local forward:
    the hand-derived :func:`_fused_local_vjp` when fused (``use_pallas``
    with diagonal noise), else ``jax.vjp`` of the unfused stepper (1 NFE).
    The fixed-grid sweep and the adaptive replay both step through this.
    """
    fused = use_pallas and noise == "diagonal"

    def pull(state1, cts, t_left, dt, dw):
        state0 = reversible_heun_reverse_at(state1, t_left, dt, dw, drift,
                                            diffusion, params, noise,
                                            use_pallas=fused)
        if fused:
            dparams, cts0 = _fused_local_vjp(drift, diffusion, params, state0,
                                             cts, t_left, dt, dw)
            return state0, cts0, dparams

        def local_forward(p, z, zh, mu, sigma):
            """Algorithm 1 as a pure function of the carried state."""
            return tuple(reversible_heun_step(
                RevHeunState(z, zh, mu, sigma), t_left, dt, dw, drift,
                diffusion, p, noise))

        _, vjp = jax.vjp(local_forward, params, *state0)
        dparams, *cts0 = vjp(cts)
        return state0, tuple(cts0), dparams

    return pull


def _zero_carry(final, g_zT, params):
    """Backward carry at the terminal state: only ``g_z`` is seeded."""
    g_params0 = jax.tree.map(jnp.zeros_like, params)
    zeros = jnp.zeros_like(final.z)
    return (final, (g_zT, zeros, zeros, jnp.zeros_like(final.sigma)),
            g_params0)


def _pull_initial(drift, diffusion, params, t0, z0, cts, g_params):
    """Close the sweep at ``t0``: ``ẑ₀ = z₀``, ``μ₀ = drift(params, t0,
    z₀)``, ``σ₀ = diffusion(params, t0, z₀)``.  -> ``(g_params, g_z0)``."""

    def init_fn(params_, z0_):
        return z0_, z0_, drift(params_, t0, z0_), diffusion(params_, t0, z0_)

    _, vjp0 = jax.vjp(init_fn, params, z0)
    dparams0, g_z0 = vjp0(cts)
    return jax.tree.map(jnp.add, g_params, dparams0), g_z0


# =============================================================================
# Reversible Heun with exact O(1)-memory adjoint
# =============================================================================


@scopes.scoped(scopes.SOLVE)
def _scan_forward(drift, diffusion, params, z0, bm, t0, t1, num_steps, noise,
                  use_pallas, save_trajectory):
    """Algorithm 1 over the fixed grid -> ``(traj or None, final state)``.

    ``traj`` is ``(num_steps+1, *z0.shape)`` (index 0 is ``z0``) when
    ``save_trajectory``; otherwise nothing O(num_steps) is built.
    """
    dt = (t1 - t0) / num_steps
    dtype = z0.dtype
    state0 = RevHeunState(z0, z0, drift(params, t0, z0), diffusion(params, t0, z0))
    gen = _gen_spec(bm, z0, noise, use_pallas)

    def body(state, n):
        t = t0 + n * dt
        if gen is not None:
            # ΔW generated inside the fused phase-1 kernel (bitwise
            # bm.increment(n, num_steps)); no host-side draw per step.
            key, dt_grid_fn = gen
            new = reversible_heun_step(state, t, dt, None, drift, diffusion,
                                       params, noise, use_pallas=use_pallas,
                                       gen=(key, n, dt_grid_fn(num_steps)))
        else:
            dw = bm.increment(n, num_steps).astype(dtype)
            new = reversible_heun_step(state, t, dt, dw, drift, diffusion, params, noise,
                                       use_pallas=use_pallas)
        return new, (new.z if save_trajectory else None)

    final, zs = lax.scan(body, state0, jnp.arange(num_steps))
    if not save_trajectory:
        return None, final
    return jnp.concatenate([z0[None], zs], axis=0), final


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 5, 6, 7, 8, 9, 10))
def _exact_solve(drift, diffusion, params, z0, bm, t0, t1, num_steps, noise,
                 use_pallas, save_trajectory):
    traj, final = _scan_forward(drift, diffusion, params, z0, bm, t0, t1,
                                num_steps, noise, use_pallas, save_trajectory)
    return traj if save_trajectory else final.z


def _fwd_rule(drift, diffusion, params, z0, bm, t0, t1, num_steps, noise,
              use_pallas, save_trajectory):
    traj, final = _scan_forward(drift, diffusion, params, z0, bm, t0, t1,
                                num_steps, noise, use_pallas, save_trajectory)
    # O(1)-in-depth residuals: terminal solver state only (+ params, bm key).
    return (traj if save_trajectory else final.z), (params, final, bm)


@scopes.scoped(scopes.ADJOINT)
def _bwd_rule(drift, diffusion, t0, t1, num_steps, noise, use_pallas,
              save_trajectory, residuals, g_out):
    """The fixed-grid backward sweep, right to left.  ``g_out`` is the
    trajectory's cotangent (each step's slice is injected into ``g_z`` as
    the sweep passes it) or, for the terminal form, ``z_N``'s."""
    params, final, bm = residuals
    dt = (t1 - t0) / num_steps
    dtype = final.z.dtype
    g_traj = g_out if save_trajectory else None
    g_zT = g_out[num_steps] if save_trajectory else g_out
    pull = _reverse_and_pull(drift, diffusion, params, noise, use_pallas)

    def body(carry, n):
        state1, cts, g_params = carry
        t1_local = t0 + (n + 1) * dt
        dw = bm.increment(n, num_steps).astype(dtype)
        state0, (d_z, d_zh, d_mu, d_sigma), dparams = pull(
            state1, cts, t1_local - dt, dt, dw)
        g_params = jax.tree.map(jnp.add, g_params, dparams)
        if g_traj is not None:
            d_z = d_z + g_traj[n]
        return (state0, (d_z, d_zh, d_mu, d_sigma), g_params), None

    (state0, cts, g_params), _ = lax.scan(
        body, _zero_carry(final, g_zT, params),
        jnp.arange(num_steps - 1, -1, -1))
    g_params, g_z0 = _pull_initial(drift, diffusion, params, t0, state0.z,
                                   cts, g_params)
    return (g_params, g_z0, _float0_zeros(bm))


_exact_solve.defvjp(_fwd_rule, _bwd_rule)


def reversible_heun_solve(
    drift: Callable,
    diffusion: Callable,
    params,
    z0: jax.Array,
    bm: BrownianPath,
    t0: float,
    t1: float,
    num_steps: int,
    noise: str = "diagonal",
    use_pallas: bool = False,
):
    """Solve the Stratonovich SDE with Algorithm 1; exact-gradient backward.

    Returns the trajectory ``(num_steps+1, *z0.shape)`` (index 0 is ``z0``).
    Losses may consume any subset of the trajectory; the backward pass
    injects each step's cotangent as it sweeps right-to-left.

    ``use_pallas`` runs the *whole* per-step pipeline fused (diagonal noise
    only): the forward scan (with ΔW generated inside the phase-1 kernel
    when the path allows it — see :func:`_gen_spec`), the backward's
    closed-form state reconstruction, and the hand-derived per-step
    cotangent phases (:func:`_fused_local_vjp`, bitwise the unfused
    ``jax.vjp``).  AD never traces through a Pallas op — the backward
    kernels ARE the derivative, registered through the solve's ``custom_vjp``.
    """
    return _exact_solve(drift, diffusion, params, z0, bm, t0, t1, num_steps,
                        noise, use_pallas, True)


def reversible_heun_solve_final(
    drift: Callable,
    diffusion: Callable,
    params,
    z0: jax.Array,
    bm: BrownianPath,
    t0: float,
    t1: float,
    num_steps: int,
    noise: str = "diagonal",
    use_pallas: bool = False,
):
    """Terminal-value-only variant of :func:`reversible_heun_solve`.

    Same exact O(1)-memory backward, but the primal output is just ``z_N`` —
    so nothing O(num_steps) is ever materialised.  This is the form the
    reversible *residual-stack* wrapper (models/reversible.py) uses: there
    ``num_steps`` is the network depth and the saving is activation memory.
    """
    return _exact_solve(drift, diffusion, params, z0, bm, t0, t1, num_steps,
                        noise, use_pallas, False)


# =============================================================================
# Adaptive reversible Heun with exact adjoint over the accepted grid
# =============================================================================
#
# The adaptive forward (repro.core.solve._adaptive_loop) accepts steps on a
# controller-chosen non-uniform grid.  The replay contract (DESIGN.md §10):
# the forward stores ONLY the accepted-step scalars ``(ts, dts)`` —
# O(max_steps) scalar memory, no trajectory storage — and the backward
# re-derives each step's Brownian increment as ``bm.evaluate(ts[i],
# ts[i] + dts[i])``, the bit-identical expression the forward evaluated,
# then algebraically reverses the step (Algorithm 2).  Rejected attempts
# never enter the buffers: gradients see exactly the accepted sequence.


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 7, 8, 9, 10, 11, 12, 13))
def reversible_heun_solve_adaptive(
    drift: Callable,
    diffusion: Callable,
    params,
    z0: jax.Array,
    bm: BrownianPath,
    rtol,
    atol,
    t0: float,
    t1: float,
    max_steps: int,
    dt0: float,
    noise: str = "diagonal",
    use_pallas: bool = False,
    bridge_depth: Optional[int] = None,
):
    """``(z_T, converged)`` of the adaptive reversible-Heun solve; exact
    adjoint on ``z_T``.

    ``converged`` rides along so the caller can refuse to treat a
    budget-exhausted state at ``t_final < t1`` as ``z_T`` (solve()
    NaN-poisons it); its cotangent is ignored.  ``rtol``/``atol`` sit in
    differentiable positions so they may be traced scalars (per-request
    tolerance in serving) — their cotangents are zero.  ``use_pallas``
    fuses the embedded stepper's state updates and the backward replay's
    reconstruction + cotangent phases — the kernels take the controller's
    traced ``dt`` as a scalar operand, so adaptivity and fusion compose.
    ``bridge_depth`` caps the dyadic descent of Brownian queries (see
    ``repro.solve``); the backward replay descends to the SAME depth, so
    replay stays bit-identical at any setting.  Callers go through
    ``repro.solve(..., adaptive=True, gradient_mode="reversible_adjoint")``.
    """
    final, stats = _adaptive_forward(drift, diffusion, params, z0, bm,
                                     rtol, atol, t0, t1, max_steps, dt0,
                                     noise, use_pallas, bridge_depth)
    return final.z, stats.converged


@scopes.scoped(scopes.SOLVE)
def _adaptive_forward(drift, diffusion, params, z0, bm, rtol, atol,
                      t0, t1, max_steps, dt0, noise, use_pallas=False,
                      bridge_depth=None):
    # late import: solve.py imports this package at load time (the driver
    # lives there per the front-end layering; by call time it is loaded)
    from ..solve import _adaptive_loop, get_solver

    return _adaptive_loop(get_solver("reversible_heun"), drift, diffusion,
                          params, z0, bm, t0, t1, rtol, atol, max_steps,
                          dt0, noise, use_pallas=use_pallas,
                          bridge_depth=bridge_depth)


def _fwd_rule_adaptive(drift, diffusion, params, z0, bm, rtol, atol,
                       t0, t1, max_steps, dt0, noise, use_pallas,
                       bridge_depth):
    final, stats = _adaptive_forward(drift, diffusion, params, z0, bm,
                                     rtol, atol, t0, t1, max_steps, dt0,
                                     noise, use_pallas, bridge_depth)
    # O(max_steps)-scalar residuals: terminal solver state + the accepted
    # (t, dt) sequence (+ params, bm key).  rtol/atol ride along only to
    # shape their zero cotangents.
    return (final.z, stats.converged), (
        params, final, bm, stats.dts, stats.ts,
        stats.num_accepted, jnp.asarray(rtol), jnp.asarray(atol))


@scopes.scoped(scopes.ADJOINT)
def _bwd_rule_adaptive(drift, diffusion, t0, t1, max_steps, dt0, noise,
                       use_pallas, bridge_depth, residuals, g_out):
    g_zT, _g_converged = g_out  # bool output: float0 cotangent, discarded
    params, final, bm, dts, ts, n_acc, rtol, atol = residuals
    dtype = final.z.dtype
    dkw = {} if bridge_depth is None else {"depth": bridge_depth}
    pull = _reverse_and_pull(drift, diffusion, params, noise, use_pallas)

    def body(loop_carry):
        i, carry = loop_carry

        def replay(carry):
            state1, cts, g_params = carry
            # ``i`` can sit below 0 on vmap lanes that finished early (the
            # batched while_loop keeps stepping them; lax.cond lowers to
            # select there) — clamp so the discarded computation stays
            # in-bounds and finite
            j = jnp.maximum(i, 0)
            dt = dts[j]
            t_left = ts[j]
            # same value-difference (astype order AND bridge depth) as the
            # forward driver, so dw is bit-identical to what the accepted
            # step saw
            if hasattr(bm, "value"):
                dw = (bm.value(t_left + dt, **dkw).astype(dtype)
                      - bm.value(t_left, **dkw).astype(dtype))
            else:
                dw = bm.evaluate(t_left, t_left + dt, **dkw).astype(dtype)
            # anchored on the STORED left endpoint so the vector fields are
            # evaluated at bit-identical times (``t_right - dt`` would
            # reintroduce fp drift)
            state0, cts0, dparams = pull(state1, cts, t_left, dt, dw)
            return (state0, cts0, jax.tree.map(jnp.add, g_params, dparams))

        return (i - 1, lax.cond(i >= 0, replay, lambda c: c, carry))

    # walk i = n_acc-1 .. 0: the trip count is the ACCEPTED count, not
    # max_steps — under vmap the batched loop runs max(n_acc) iterations
    # instead of paying the full padded buffer per trajectory (cond lowers
    # to select there, so padded slots would otherwise do real work)
    _, (state0, cts, g_params) = lax.while_loop(
        lambda c: c[0] >= 0, body, (n_acc - 1, _zero_carry(final, g_zT, params)))
    g_params, g_z0 = _pull_initial(drift, diffusion, params, t0, state0.z,
                                   cts, g_params)
    return (g_params, g_z0, _float0_zeros(bm),
            jnp.zeros_like(rtol), jnp.zeros_like(atol))


reversible_heun_solve_adaptive.defvjp(_fwd_rule_adaptive, _bwd_rule_adaptive)


# =============================================================================
# Backend registration
# =============================================================================


def _validate(spec, *, noise, save_trajectory, use_pallas, adaptive):
    if (spec.stepper is not reversible_heun_step
            or spec.reverse_stepper is not reversible_heun_reverse_step):
        raise ValueError(
            f"solver {spec.name!r} declares reversible_adjoint but the exact "
            f"adjoint is implemented for the reversible-Heun stepper pair "
            f"(repro.core.gradients.reversible); a custom reversible solver "
            f"needs its own custom_vjp there")


def _solve(spec, drift, diffusion, params, z0, bm, t0, t1, num_steps, *,
           noise, save_trajectory, use_pallas):
    return _exact_solve(drift, diffusion, params, z0, bm, t0, t1, num_steps,
                        noise, use_pallas, save_trajectory)


def _solve_adaptive(spec, drift, diffusion, params, z0, bm, rtol, atol,
                    t0, t1, max_steps, dt0, *, noise, use_pallas,
                    bridge_depth):
    return reversible_heun_solve_adaptive(
        drift, diffusion, params, z0, bm, rtol, atol, t0, t1, max_steps,
        dt0, noise, use_pallas, bridge_depth)


register_backend(GradientBackend(
    name="reversible_adjoint",
    summary="paper's exact adjoint: algebraic reversal, O(1) memory",
    terminal_only=False,
    supports_adaptive=True,
    solve=_solve,
    solve_adaptive=_solve_adaptive,
    validate=_validate,
))
