"""Recursive binomial checkpointing: memory-bounded exact gradients for
EVERY registered solver (``gradient_mode="checkpoint"``).

The capability gap this closes: ``reversible_adjoint`` is exact and O(1)
memory but exists only for the algebraically reversible Heun pair, while
euler-maruyama/midpoint/heun had to choose between O(n) activations
(``discretise``) and O(√h) gradient *error* (``continuous_adjoint``).
Recursive checkpointing (McCallum & Foster, arXiv:2410.11648) is the
frontier between those: gradients are **exact to floating point** (they
are discretise-then-optimise gradients, just rematerialised) at O(log n)
live residuals and O(n log n) recompute.

The schedule is recursive halving, built as ``ceil(log2 n)`` nested
levels of two-iteration ``lax.scan`` whose bodies run under
:func:`jax.checkpoint`: a level-``k`` runner advances ``2^k`` steps by
scanning its rematerialised level-``k-1`` runner twice.  A checkpointed
body saves only its entry carry, so the forward stores two carries per
level and the backward re-runs one half at a time — at any moment at most
one root-to-leaf path of segment carries is live: ``O(log2 n)`` solver
states, each step recomputed once per level above it
(:func:`checkpoint_schedule` derives the exact counts; the benchmark
gates against them).  Nesting scans instead of unrolling the recursion
keeps the *program* O(log n) too — compile time does not grow with the
horizon.  Brownian increments are drawn *inside* the checkpointed regions
from the counter-based path, so noise is regenerated, never stored — the
same principle as the exact adjoint's replay (paper §4).  Horizons that
are not a power of two pad the step index up and mask the surplus steps
to the identity (their field evaluations get zero cotangent, so gradients
see exactly the ``n`` real steps).

Adaptive solves compose via a freeze-and-replay split: the accept/reject
controller runs once under ``stop_gradient`` (``lax.while_loop`` has no
reverse rule, and gradients must not flow through the controller's
discrete accept decisions anyway), fixing the accepted ``(ts, dts,
num_accepted)`` scalars; the differentiable path then *replays* the
accepted grid over the padded ``max_steps`` buffer under the same
recursive schedule, masking padding slots with ``jnp.where``.  Each
replayed step re-derives its increment with the driver's own
value-difference expression, so the replayed terminal state is
bit-identical to the controller's.  Cost: one extra (gradient-free)
forward pass.

Terminal-value cotangents only: a trajectory output is itself O(n)
memory, which is exactly what this backend exists to avoid —
``save_trajectory=True`` is rejected eagerly.
"""

from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax

from .. import scopes
from ..brownian import stlevy_difference
from ..solvers import RevHeunState, _tree_cast, reversible_heun_step
from .base import GradientBackend, register_backend

__all__ = [
    "checkpoint_schedule",
    "checkpoint_solve",
    "checkpoint_solve_adaptive",
]


def _carry_init(spec, drift, diffusion, params, z0, t0):
    """Solver carry at ``t0`` — stepper-generic, like the adaptive driver."""
    if spec.stepper is reversible_heun_step:
        return RevHeunState(z0, z0, drift(params, t0, z0),
                            diffusion(params, t0, z0))
    return z0


def _carry_z(spec, carry):
    return carry.z if spec.stepper is reversible_heun_step else carry


def _chain(step, num_steps):
    """Compose ``num_steps`` steps under the recursive-halving schedule.

    Returns ``(carry, params) -> carry``.  ``step`` is ``(carry, params,
    i) -> carry`` with ``i`` a traced int32 step index.  ``params`` is
    threaded as an explicit argument so ``jax.checkpoint`` treats it as an
    input (always available to the backward pass) rather than a
    per-segment residual.  Non-power-of-two horizons are the caller's
    problem: ``num_steps`` is padded up and ``step`` must mask ``i >=
    num_steps`` to the identity.
    """
    depth = max(0, math.ceil(math.log2(num_steps))) if num_steps > 1 else 0

    def runner(k):
        """``(carry, params, base) -> carry`` advancing steps
        ``[base, base + 2^k)``."""
        if k == 0:
            return lambda carry, params, base: step(carry, params, base)
        half = 2 ** (k - 1)
        inner = jax.checkpoint(runner(k - 1))

        def run(carry, params, base):
            def body(c, j):
                return inner(c, params, base + j * half), None

            out, _ = lax.scan(body, carry, jnp.arange(2, dtype=jnp.int32))
            return out

        return run

    top = runner(depth)
    return lambda carry, params: top(
        carry, params, jnp.asarray(0, jnp.int32))


@scopes.scoped(scopes.SOLVE)
def checkpoint_solve(spec, drift, diffusion, params, z0, bm, t0, t1,
                     num_steps, noise):
    """Terminal value ``z_T``; AD through it follows the halving schedule.

    The per-step math is ``spec.stepper`` verbatim on the uniform grid —
    the same ops, in the same order, as the discretise-mode scan — so the
    gradients agree with discretise-then-optimise to floating-point error
    while peak residual memory follows :func:`checkpoint_schedule`.
    """
    dt = (t1 - t0) / num_steps
    dtype = z0.dtype

    def step(carry, params_, i):
        j = jnp.minimum(i, num_steps - 1)  # pad-to-pow2 slots clamp in-range
        t = t0 + j * dt
        # drawn inside the checkpointed region: regenerated on remat, not
        # stored (counter-based threefry — cheap relative to a field eval)
        dw = _tree_cast(bm.increment(j, num_steps), dtype)
        new = spec.stepper(carry, t, dt, dw, drift, diffusion, params_,
                           noise)
        return jax.tree.map(
            lambda a, b: jnp.where(i < num_steps, a, b), new, carry)

    carry0 = _carry_init(spec, drift, diffusion, params, z0, t0)
    return _carry_z(spec, _chain(step, num_steps)(carry0, params))


@scopes.scoped(scopes.SOLVE)
def checkpoint_solve_adaptive(spec, drift, diffusion, params, z0, bm,
                              rtol, atol, t0, t1, max_steps, dt0, noise,
                              bridge_depth=None):
    """``(z_T, converged)`` over the controller's accepted grid.

    Freeze-and-replay: the PI-controlled driver fixes the accepted
    ``(ts, dts)`` under ``stop_gradient``; the checkpointed replay over
    the padded buffer is the differentiable path.  ``dw`` uses the same
    value-difference (astype order AND bridge depth) as the forward
    driver, so each replayed step is bit-identical to the accepted one.
    """
    from ..solve import _adaptive_loop

    _, stats = _adaptive_loop(
        spec, drift, diffusion, lax.stop_gradient(params),
        lax.stop_gradient(z0), bm, t0, t1, lax.stop_gradient(rtol),
        lax.stop_gradient(atol), max_steps, dt0, noise,
        bridge_depth=bridge_depth)
    ts = lax.stop_gradient(stats.ts)
    dts = lax.stop_gradient(stats.dts)
    n_acc = lax.stop_gradient(stats.num_accepted)

    dtype = z0.dtype
    has_value = hasattr(bm, "value")
    levy = getattr(bm, "levy_area", None) == "space-time"
    dkw = {} if bridge_depth is None else {"depth": bridge_depth}

    def step(carry, params_, i):
        j = jnp.minimum(i, max_steps - 1)  # pad-to-pow2 slots clamp in-range
        t_left = ts[j]
        dt = dts[j]
        if has_value:
            val_l = _tree_cast(bm.value(t_left, **dkw), dtype)
            val_r = _tree_cast(bm.value(t_left + dt, **dkw), dtype)
            if levy:
                dw = stlevy_difference(val_l, val_r, t_left, t_left + dt,
                                       bm.t0)
            else:
                dw = val_r - val_l
        else:
            dw = _tree_cast(bm.evaluate(t_left, t_left + dt, **dkw), dtype)
        new = spec.stepper(carry, t_left, dt, dw, drift, diffusion,
                           params_, noise)
        # padding slots (dt = 0, dw = 0) still evaluate the fields — at
        # the carried state, so they stay finite — and are masked out here
        return jax.tree.map(
            lambda a, b: jnp.where(i < n_acc, a, b), new, carry)

    carry0 = _carry_init(spec, drift, diffusion, params, z0, t0)
    z = _carry_z(spec, _chain(step, max_steps)(carry0, params))
    return z, stats.converged


# =============================================================================
# Schedule cost model (the benchmark's memory gate)
# =============================================================================


@lru_cache(maxsize=None)
def _peak_live(depth: int) -> int:
    """Max simultaneously-live solver carries while differentiating a
    level-``depth`` runner (the leaf's own step residuals count as 1).

    A scan over a checkpointed body stores exactly the per-iteration
    entry carries (2 of them); the backward holds those while recursing
    into one half at a time: ``L(k) = 2 + L(k-1)``, ``L(0) = 1``.
    """
    if depth <= 0:
        return 1
    return 2 + _peak_live(depth - 1)


@lru_cache(maxsize=None)
def _recompute(depth: int) -> int:
    """Extra forward step evaluations the backward over a level-``depth``
    runner performs: each of the scan's 2 iterations re-runs its remat'd
    inner forward (``2^(k-1)`` steps) before differentiating it —
    ``R(k) = 2 * (2^(k-1) + R(k-1))``, ``R(0) = 0``, i.e. ``k * 2^k``.
    """
    if depth <= 0:
        return 0
    return 2 * (2 ** (depth - 1) + _recompute(depth - 1))


def checkpoint_schedule(num_steps: int) -> dict:
    """Exact cost model of the nested-scan halving schedule.

    Non-power-of-two horizons run padded to ``padded = 2^depth`` with the
    surplus steps masked to identity (they still cost recompute — the
    schedule is shape-static).  Returns ``depth`` (= ceil(log2 n)),
    ``peak_live_states`` (solver carries simultaneously resident during
    the backward sweep — the O(log n) bound: ``2 * depth + 1``), and
    ``recompute_steps`` (extra step evaluations beyond the forward's
    ``padded`` — the O(n log n) bound: ``depth * padded``).
    benchmarks/gradient_error.py multiplies ``peak_live_states`` by the
    carry byte-size and gates the product against the log-model; tests
    pin the recursion itself.
    """
    if num_steps < 1:
        raise ValueError(f"num_steps must be >= 1, got {num_steps}")
    depth = max(0, math.ceil(math.log2(num_steps))) if num_steps > 1 else 0
    return {
        "num_steps": num_steps,
        "padded_steps": 2 ** depth,
        "depth": depth,
        "peak_live_states": _peak_live(depth),
        "recompute_steps": _recompute(depth),
    }


# =============================================================================
# Backend registration
# =============================================================================


def _validate(spec, *, noise, save_trajectory, use_pallas, adaptive):
    if save_trajectory:
        raise ValueError(
            "gradient_mode='checkpoint' backpropagates a terminal-value "
            "cotangent only (a trajectory output is itself the O(n) "
            "memory this backend exists to avoid) — call solve(..., "
            "save_trajectory=False)")
    if use_pallas:
        raise ValueError(
            "use_pallas_kernels is incompatible with gradient_mode="
            "'checkpoint': the rematerialised segments are differentiated "
            "by plain AD, which cannot trace a pallas_call (the fused "
            "derivative lives in the reversible-adjoint custom_vjp).  Use "
            "gradient_mode='reversible_adjoint' for the fused path")


def _solve(spec, drift, diffusion, params, z0, bm, t0, t1, num_steps, *,
           noise, save_trajectory, use_pallas):
    return checkpoint_solve(spec, drift, diffusion, params, z0, bm, t0, t1,
                            num_steps, noise)


def _solve_adaptive(spec, drift, diffusion, params, z0, bm, rtol, atol,
                    t0, t1, max_steps, dt0, *, noise, use_pallas,
                    bridge_depth):
    return checkpoint_solve_adaptive(
        spec, drift, diffusion, params, z0, bm, rtol, atol, t0, t1,
        max_steps, dt0, noise, bridge_depth=bridge_depth)


register_backend(GradientBackend(
    name="checkpoint",
    summary="recursive binomial checkpointing: exact gradients, "
            "O(log n) memory, O(n log n) recompute",
    terminal_only=True,
    supports_adaptive=True,
    solve=_solve,
    solve_adaptive=_solve_adaptive,
    validate=_validate,
))
