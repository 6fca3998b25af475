"""SDE solvers (paper §3) as `lax.scan` steppers.

All Stratonovich solvers share the calling convention::

    drift(params, t, z)      -> dz/dt                    (shape of z)
    diffusion(params, t, z)  -> sigma                    (diagonal: shape of z;
                                                          general: (*z.shape, w))

and consume a :class:`repro.core.brownian.BrownianPath` so that the forward
and backward passes see bit-identical noise without storing it.

Solver inventory (paper §3 "Computational efficiency"):

=================  ============  =====================  ====================
solver             SDE type      drift+diffusion evals  notes
=================  ============  =====================  ====================
euler_maruyama     Itô           1 / step               order 0.5 baseline
midpoint           Stratonovich  2 / step               paper's main baseline
heun               Stratonovich  2 / step               trapezoidal
reversible_heun    Stratonovich  **1 / step**           algebraically
                                                        reversible (paper §3)
srk                Itô           5 / step               strong order **1.5**;
                                                        consumes (ΔW, ΔH)
                                                        space–time Lévy pairs
=================  ============  =====================  ====================

`reversible_heun` here is the *plain scan* version: differentiating through
it with standard JAX AD gives discretise-then-optimise gradients (and O(N)
activation memory).  The O(1)-memory exact adjoint lives in
:mod:`repro.core.gradients.reversible`.

The reversible-Heun hot loop optionally runs through the fused Pallas
kernels (:mod:`repro.kernels.reversible_heun_step`) via
``use_pallas=True`` — see the kernel module docstring for the contract
(diagonal noise; ``dt`` may be traced, so this includes the adaptive
driver; plain AD must not trace through the fused ops — gradients use the
hand-derived backward kernels via :mod:`repro.core.gradients.reversible`).
Which implementation a fused op runs (Pallas kernel or jnp oracle) is
decided by :mod:`repro.kernels.ops` alone.  Callers should normally go
through the :func:`repro.core.solve.solve` front-end, which validates the
flag against the solver registry.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .brownian import BrownianPath

Drift = Callable  # (params, t, z) -> z-shaped
Diffusion = Callable  # (params, t, z) -> z-shaped (diagonal) or (*z, w) (general)

#: drift+diffusion evaluations per step, per solver (paper's NFE accounting).
NFE_PER_STEP = {
    "euler_maruyama": 1,
    "midpoint": 2,
    "heun": 2,
    "reversible_heun": 1,
    "srk": 5,
}


def _tree_cast(x, dtype):
    """``astype`` over a pytree — identical to ``x.astype`` for plain arrays.

    The Brownian layer returns a bare ``ΔW`` array in ``levy_area=None`` mode
    and a ``(ΔW, ΔH)`` pair in ``levy_area="space-time"`` mode; every dw
    consumer casts through this so both shapes flow.
    """
    return jax.tree.map(lambda a: a.astype(dtype), x)


def apply_diffusion(sigma: jax.Array, dw: jax.Array, noise: str) -> jax.Array:
    """``sigma · dW`` for diagonal or general (matrix) noise."""
    if noise == "diagonal":
        return sigma * dw
    if noise == "general":
        return jnp.einsum("...ij,...j->...i", sigma, dw)
    raise ValueError(f"unknown noise type: {noise}")


def dw_shape(z_shape, w_dim: Optional[int], noise: str):
    if noise == "diagonal":
        return tuple(z_shape)
    return tuple(z_shape[:-1]) + (w_dim,)


class RevHeunState(NamedTuple):
    """Carried state of the reversible Heun method (Algorithm 1)."""

    z: jax.Array
    zh: jax.Array  # ẑ — the auxiliary (midpoint-propagated) track
    mu: jax.Array
    sigma: jax.Array


def reversible_heun_step(state: RevHeunState, t, dt, dw, drift, diffusion, params, noise,
                         use_pallas: bool = False, gen=None):
    """One step of Algorithm 1.  Exactly one drift+diffusion evaluation.

    With ``use_pallas=True`` (diagonal noise) the two elementwise state
    updates run as fused Pallas kernels; ``dt`` may be a traced scalar (the
    kernels take it as a scalar operand), so the adaptive driver's
    controller-chosen step sizes work fused too.  AD must not trace through
    this path — gradients go through the hand-derived backward kernels via
    :mod:`repro.core.gradients.reversible`.

    ``gen=(key, n, dt_grid)`` generates this step's ``ΔW`` *inside* the
    phase-1 kernel (counter-based Threefry keyed on ``n``, bitwise
    ``BrownianPath.increment(n)`` with grid spacing ``dt_grid``) instead of
    consuming ``dw`` — the fixed-grid time loop then never leaves the fused
    path between noise generation and state update.  ``dw`` is ignored
    when ``gen`` is given.
    """
    z, zh, mu, sigma = state
    if use_pallas and noise == "diagonal":
        from ..kernels import ops

        if gen is not None:
            key, n, dt_grid = gen
            zh1, dw = ops.rev_heun_phase1_gen(z, zh, mu, sigma, key, n,
                                              dt_grid, dt)
        else:
            zh1 = ops.rev_heun_phase1(z, zh, mu, sigma, dw, dt)
        mu1 = drift(params, t + dt, zh1)
        sigma1 = diffusion(params, t + dt, zh1)
        z1 = ops.rev_heun_phase2(z, mu, mu1, sigma, sigma1, dw, dt)
        return RevHeunState(z1, zh1, mu1, sigma1)
    zh1 = 2.0 * z - zh + mu * dt + apply_diffusion(sigma, dw, noise)
    mu1 = drift(params, t + dt, zh1)
    sigma1 = diffusion(params, t + dt, zh1)
    z1 = z + 0.5 * (mu + mu1) * dt + apply_diffusion(0.5 * (sigma + sigma1), dw, noise)
    return RevHeunState(z1, zh1, mu1, sigma1)


def reversible_heun_reverse_at(state: RevHeunState, t_left, dt, dw, drift, diffusion,
                               params, noise, use_pallas: bool = False):
    """Algorithm 2 anchored on the step's left time ``t_left``.

    Reconstructs ``(z_n, ẑ_n, μ_n, σ_n)`` from ``(z_{n+1}, ẑ_{n+1}, μ_{n+1},
    σ_{n+1})`` in closed form — the paper's key property — evaluating the
    vector fields at ``t_left`` exactly as the caller passes it: the
    adaptive replay passes the stored left endpoint, so the fields see the
    bit-identical time the forward step saw.  ``use_pallas`` runs the same
    fused kernels as the forward step with ``sign=-1``.
    """
    z1, zh1, mu1, sigma1 = state
    if use_pallas and noise == "diagonal":
        from ..kernels import ops

        zh = ops.rev_heun_phase1(z1, zh1, mu1, sigma1, dw, dt, sign=-1.0)
        mu = drift(params, t_left, zh)
        sigma = diffusion(params, t_left, zh)
        z = ops.rev_heun_phase2(z1, mu, mu1, sigma, sigma1, dw, dt, sign=-1.0)
        return RevHeunState(z, zh, mu, sigma)
    zh = 2.0 * z1 - zh1 - mu1 * dt - apply_diffusion(sigma1, dw, noise)
    mu = drift(params, t_left, zh)
    sigma = diffusion(params, t_left, zh)
    z = z1 - 0.5 * (mu + mu1) * dt - apply_diffusion(0.5 * (sigma + sigma1), dw, noise)
    return RevHeunState(z, zh, mu, sigma)


def reversible_heun_reverse_step(state: RevHeunState, t1, dt, dw, drift, diffusion, params, noise,
                                 use_pallas: bool = False):
    """Algebraic inverse of :func:`reversible_heun_step` (Algorithm 2, reverse).

    ``t1`` is the step's right time; :func:`reversible_heun_reverse_at`
    with ``t_left = t1 - dt``.
    """
    return reversible_heun_reverse_at(state, t1 - dt, dt, dw, drift, diffusion,
                                      params, noise, use_pallas=use_pallas)


# -----------------------------------------------------------------------------
# Embedded error estimates (adaptive stepping; DESIGN.md §10)
# -----------------------------------------------------------------------------
#
# Uniform interface: ``(carry, t, dt, dw, drift, diffusion, params, noise)
# -> (carry_new, err)`` where ``err`` is an elementwise local-error estimate
# with the shape of ``z``.  None of these cost extra vector-field
# evaluations over the plain stepper:
#
# * reversible Heun: the gap ``z − ẑ`` between the two carried tracks is
#   *free* — but it alternates sign and persists across steps
#   (δ_{n+1} = −δ_n + ½Δμ·dt + ½Δσ·dW), so the raw gap measures the
#   accumulated track distance, not this step's error.  The *increment*
#   of the gap, ``δ_{n+1} + δ_n = ½(μ(ẑ₁)−μ(ẑ₀))dt + ½(σ(ẑ₁)−σ(ẑ₀))dW``,
#   is the genuine local quantity (→ 0 as dt → 0) and costs nothing;
# * heun: the Euler predictor ``z + μ₀dt + σ₀dW`` is the embedded
#   lower-order solution; the corrector − predictor gap estimates the
#   error;
# * midpoint: same Euler pair, reusing the two evaluations the step
#   already makes.
#
# euler_maruyama has no second solution to compare against — it carries no
# embedded pair and the front-end rejects ``adaptive=True`` for it eagerly.


def reversible_heun_embedded_step(state: RevHeunState, t, dt, dw, drift, diffusion,
                                  params, noise, use_pallas: bool = False):
    new = reversible_heun_step(state, t, dt, dw, drift, diffusion, params, noise,
                               use_pallas=use_pallas)
    return new, (new.z - new.zh) + (state.z - state.zh)


def _heun_embedded_step(z, t, dt, dw, drift, diffusion, params, noise):
    mu0 = drift(params, t, z)
    s0 = diffusion(params, t, z)
    zp = z + mu0 * dt + apply_diffusion(s0, dw, noise)  # Euler (embedded)
    mu1 = drift(params, t + dt, zp)
    s1 = diffusion(params, t + dt, zp)
    z1 = z + 0.5 * (mu0 + mu1) * dt + apply_diffusion(0.5 * (s0 + s1), dw, noise)
    return z1, z1 - zp


def _midpoint_embedded_step(z, t, dt, dw, drift, diffusion, params, noise):
    mu0 = drift(params, t, z)
    s0 = diffusion(params, t, z)
    euler = mu0 * dt + apply_diffusion(s0, dw, noise)
    half = z + 0.5 * euler
    tm = t + 0.5 * dt
    z1 = z + drift(params, tm, half) * dt + apply_diffusion(
        diffusion(params, tm, half), dw, noise)
    return z1, z1 - (z + euler)


def _srk_embedded_step(z, t, dt, dw, drift, diffusion, params, noise):
    """Strong-order-1.5 explicit SRK step (Kloeden–Platen, Itô, diagonal noise).

    ``dw`` must be the ``(ΔW, ΔH)`` pair from a ``levy_area="space-time"``
    Brownian path: the I_{(1,0)} = ∫∫ dW ds iterated integral that separates
    order 1.5 from order 1.0 is ``dt·(H + ΔW/2)`` and cannot be recovered
    from ``ΔW`` alone.  The scheme is the explicit strong order-1.5 method of
    Kloeden & Platen (1992, §11.2) specialised to diagonal noise, with every
    supporting value evaluated at ``t+dt`` so non-autonomous fields pick up
    the L⁰-operator time derivatives:

        Υ± = z + a·dt ± b·√dt          Φ± = Υ₊ ± b(Υ₊)·√dt

        z₁ = z + ¼(a(Υ₊) + 2a + a(Υ₋))dt + b·ΔW
               + (b(Υ₊) − b(Υ₋))/(2√dt) · I₍₁,₁₎
               + (a(Υ₊) − a(Υ₋))/(2√dt) · I₍₁,₀₎
               + (b(Υ₊) − 2b + b(Υ₋))/(2dt) · I₍₀,₁₎
               + (b(Φ₊) − b(Φ₋) − b(Υ₊) + b(Υ₋))/(2dt) · I₍₁,₁,₁₎

    with I₍₁,₁₎ = (ΔW²−dt)/2, I₍₁,₀₎ = dt(H + ΔW/2), I₍₀,₁₎ = ΔW·dt −
    I₍₁,₀₎, I₍₁,₁,₁₎ = (ΔW³ − 3dt·ΔW)/6.  Strong order 1.5 requires the
    diffusion to be strictly diagonal (∂bᵢ/∂zⱼ = 0 for i≠j) — same
    restriction as torchsde's ``srk``; for additive noise the scheme keeps
    order 1.5 with the I₍₁,₀₎ drift-area term doing the work.

    The embedded estimate is the Euler–Maruyama step from the stage-1
    evaluations — zero extra NFE, the same pattern as heun/midpoint.

    ``dt == 0`` (the adaptive checkpoint replay's padding slots) is guarded
    with a ``where``-substituted divisor so no inf·0 NaN enters the forward
    values or their VJP.
    """
    if not isinstance(dw, (tuple, list)):
        raise TypeError(
            "solver 'srk' needs (dW, dH) pairs — construct the Brownian path "
            "with levy_area='space-time'")
    if noise != "diagonal":
        raise ValueError(
            "solver 'srk' supports diagonal noise only (general noise needs "
            "full Lévy areas, which space-time H does not provide)")
    w, h = dw
    dt_safe = jnp.where(dt == 0, jnp.ones_like(dt), dt)
    sq = jnp.sqrt(dt_safe)

    a0 = drift(params, t, z)
    b0 = diffusion(params, t, z)
    up = z + a0 * dt + b0 * sq
    um = z + a0 * dt - b0 * sq
    t1 = t + dt
    ap = drift(params, t1, up)
    am = drift(params, t1, um)
    bp = diffusion(params, t1, up)
    bm_ = diffusion(params, t1, um)
    pp = up + bp * sq
    pm = up - bp * sq
    bpp = diffusion(params, t1, pp)
    bpm = diffusion(params, t1, pm)

    i10 = dt * (h + 0.5 * w)           # I_{(1,0)} = ∫ (W_s − W_t) ds
    i01 = w * dt - i10                 # I_{(0,1)} = ∫ s dW
    i11 = 0.5 * (w * w - dt)           # I_{(1,1)}
    i111 = (w * w * w - 3.0 * dt * w) / 6.0

    z1 = (z
          + 0.25 * (ap + 2.0 * a0 + am) * dt
          + b0 * w
          + (bp - bm_) * (0.5 / sq) * i11
          + (ap - am) * (0.5 / sq) * i10
          + (bp - 2.0 * b0 + bm_) * (0.5 / dt_safe) * i01
          + (bpp - bpm - bp + bm_) * (0.5 / dt_safe) * i111)
    return z1, z1 - (z + a0 * dt + b0 * w)


def _srk_step(z, t, dt, dw, drift, diffusion, params, noise):
    return _srk_embedded_step(z, t, dt, dw, drift, diffusion, params, noise)[0]


def _euler_maruyama_step(z, t, dt, dw, drift, diffusion, params, noise):
    return z + drift(params, t, z) * dt + apply_diffusion(diffusion(params, t, z), dw, noise)


def _midpoint_step(z, t, dt, dw, drift, diffusion, params, noise):
    # the fixed-grid stepper IS the embedded pair minus the error output
    # (XLA dead-code-eliminates the unused estimate) — one scheme, not two
    return _midpoint_embedded_step(z, t, dt, dw, drift, diffusion, params, noise)[0]


def _heun_step(z, t, dt, dw, drift, diffusion, params, noise):
    return _heun_embedded_step(z, t, dt, dw, drift, diffusion, params, noise)[0]


def sde_solve(
    drift: Drift,
    diffusion: Diffusion,
    params,
    z0: jax.Array,
    bm: BrownianPath,
    t0: float,
    t1: float,
    num_steps: int,
    solver: str = "reversible_heun",
    noise: str = "diagonal",
    save_trajectory: bool = True,
    use_pallas_kernels: bool = False,
    step_fn: Optional[Callable] = None,
):
    """Solve ``dZ = μ dt + σ ∘ dW`` from ``t0`` to ``t1`` in ``num_steps`` steps.

    Returns the trajectory ``(num_steps+1, *z0.shape)`` if ``save_trajectory``
    else the terminal value.  Differentiating through this function gives
    discretise-then-optimise gradients (O(N) memory).  For the paper's O(1)
    exact adjoint use :func:`repro.core.gradients.reversible_heun_solve`.

    ``use_pallas_kernels`` fuses the reversible-Heun state updates
    (diagonal noise only).  The fused ops have no VJP rule, so this flag is
    for forward simulation; for fused *training* use the exact adjoint via
    :func:`repro.core.solve.solve` with ``gradient_mode="reversible_adjoint"``.
    """
    dt = (t1 - t0) / num_steps
    dtype = z0.dtype

    if solver == "reversible_heun":
        state0 = RevHeunState(z0, z0, drift(params, t0, z0), diffusion(params, t0, z0))

        def body(state, n):
            t = t0 + n * dt
            dw = _tree_cast(bm.increment(n, num_steps), dtype)
            new = reversible_heun_step(state, t, dt, dw, drift, diffusion, params, noise,
                                       use_pallas=use_pallas_kernels)
            return new, (new.z if save_trajectory else None)

        final, traj = lax.scan(body, state0, jnp.arange(num_steps))
        if save_trajectory:
            return jnp.concatenate([z0[None], traj], axis=0)
        return final.z

    # ``step_fn`` lets the registry (repro.core.solve) dispatch solvers this
    # module doesn't know about: any ``(z, t, dt, dw, drift, diffusion,
    # params, noise) -> z`` stepper that carries the state itself.
    step = step_fn or {
        "euler_maruyama": _euler_maruyama_step,
        "midpoint": _midpoint_step,
        "heun": _heun_step,
    }.get(solver)
    if step is None:
        raise ValueError(
            f"solver {solver!r} has no builtin stepper; pass step_fn= "
            f"(repro.core.solve does this from the registry)")

    def body(z, n):
        t = t0 + n * dt
        dw = _tree_cast(bm.increment(n, num_steps), dtype)
        z1 = step(z, t, dt, dw, drift, diffusion, params, noise)
        return z1, (z1 if save_trajectory else None)

    final, traj = lax.scan(body, z0, jnp.arange(num_steps))
    if save_trajectory:
        return jnp.concatenate([z0[None], traj], axis=0)
    return final


def ode_solve(f, params, z0, t0, t1, num_steps, solver="reversible_heun"):
    """Deterministic limit (σ=0) — used for the stability tests (App. D.5)."""
    zero_diff = lambda p, t, z: jnp.zeros_like(z)
    key = jax.random.PRNGKey(0)
    bm = BrownianPath(key, t0, t1, z0.shape, z0.dtype)
    return sde_solve(f, zero_diff, params, z0, bm, t0, t1, num_steps, solver=solver, noise="diagonal")
