"""Unified SDE-solve front-end: one entry point, a solver registry, and
first-class batched multi-trajectory solving.

This is the `sdeint`-style surface the paper's pieces plug into
(cf. Li et al. 2020's ``sdeint(..., method=, adjoint=)``): callers pick a
``solver`` × ``gradient_mode`` × ``noise`` × ``precision`` combination and
:func:`solve` dispatches to the matching gradient backend
(:mod:`repro.core.gradients`):

* plain ``lax.scan`` + JAX AD (``gradient_mode="discretise"``,
  discretise-then-optimise, O(N) activation memory),
* the paper's algebraically-reversible exact adjoint
  (``"reversible_adjoint"``, O(1) memory, FP-exact gradients — §3/App. C),
* the optimise-then-discretise continuous adjoint baseline
  (``"continuous_adjoint"``, eq. (6), O(√h) gradient error),
* recursive binomial checkpointing (``"checkpoint"``, FP-exact gradients
  at O(log n) memory / O(n log n) recompute — works for every registered
  stepper, including the non-reversible ones and adaptive accepted grids).

Both sides of the dispatch are data.  Every solver is described by a
:class:`SolverSpec` in :data:`SOLVERS`: the stepper, its algebraic inverse
(when one exists), the NFE accounting the paper's Tables 1/4/5 report, the
strong order, and which gradient modes / fused-kernel paths are legal.
Every gradient mode is a :class:`~repro.core.gradients.GradientBackend` in
its own registry: a forward residual policy plus a backward rule, with
backend-specific constraints validated eagerly (``spec.gradient_modes``
names backends, so "which solver serves which mode" is a join over the two
tables — see :func:`gradient_capabilities`).  Adding a solver or a
gradient path means registering a spec or a backend, not editing dispatch
chains; an unsupported pairing raises a named error rather than producing
another solver's numerics silently.

``precision="bf16_compute"`` applies the solve-stack precision policy
(:func:`repro.core.gradients.resolve_precision`): vector-field evaluation
is cast to bf16 while solver state, Brownian increments, and adjoint
accumulators stay in the state dtype.  The wrap happens before any
backend sees the fields, so every gradient mode is mixed-precision-capable
by construction; benchmarks/gradient_error.py gates the induced gradient
error against a pinned tolerance.  The default ``"highest"`` is the
identity — bitwise the pre-policy behaviour.

``use_pallas_kernels=True`` routes the reversible-Heun hot loop through the
fused Pallas kernels (:mod:`repro.kernels.reversible_heun_step`): the
forward scan (with in-kernel Brownian generation where the path allows),
the backward's closed-form state reconstruction, AND the per-step local
VJP all run fused — the hand-derived backward kernel pair is the
derivative, registered through the reversible-adjoint ``custom_vjp``.
Because the kernels take ``dt`` as a traced scalar operand this composes
with ``adaptive=True``.  On non-TPU backends the same steps run the
kernels' jnp oracles (:mod:`repro.kernels.ref`).

Batched multi-trajectory solving (:func:`solve_batched`) vmaps a batch of
initial states against a batch of Brownian seeds — one fused XLA program
for the whole ensemble instead of a Python loop of solves.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .brownian import BrownianPath, stlevy_difference
from .gradients import (
    GRADIENT_BACKENDS,
    PRECISION_POLICIES,
    available_gradient_modes,
    get_backend,
    resolve_precision,
)
from .solvers import (
    RevHeunState,
    _euler_maruyama_step,
    _heun_embedded_step,
    _heun_step,
    _midpoint_embedded_step,
    _midpoint_step,
    _srk_embedded_step,
    _srk_step,
    _tree_cast,
    reversible_heun_embedded_step,
    reversible_heun_reverse_step,
    reversible_heun_step,
)

__all__ = [
    "GRADIENT_MODES",
    "PRECISION_POLICIES",
    "SOLVERS",
    "AdaptiveStats",
    "SolverSpec",
    "available_solvers",
    "get_solver",
    "gradient_capabilities",
    "register_solver",
    "solve",
    "solve_adaptive",
    "solve_batched",
]

#: The registered gradient paths, in inventory order: the paper landscape's
#: three (§2.3/§2.4) plus recursive checkpointing.  Derived from the
#: backend registry — registering a new backend extends this tuple.
GRADIENT_MODES = available_gradient_modes()


@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """Registry entry describing one solver's capabilities.

    Attributes:
        name: registry key (the ``solver=`` string).
        stepper: ``(z_or_state, t, dt, dw, drift, diffusion, params, noise)``
            single-step function.
        reverse_stepper: algebraic inverse of ``stepper`` or ``None`` for
            non-reversible solvers.
        nfe_per_step: drift+diffusion evaluations per step (paper §3).
        strong_order: strong convergence order (multiplicative noise).
        gradient_modes: subset of :data:`GRADIENT_MODES` this solver serves.
        supports_pallas: whether the fused Pallas step kernels apply.
        sde_type: "ito" or "stratonovich".
        notes: one-line description (surfaced in README's inventory table).
        embedded_stepper: ``(carry, t, dt, dw, drift, diffusion, params,
            noise) -> (carry_new, err)`` embedded-pair step for adaptive
            error control, or ``None`` for solvers with no free embedded
            estimate (``adaptive=True`` is rejected for those).
        needs_levy_area: the stepper consumes ``(ΔW, ΔH)`` space–time
            Lévy-area pairs instead of plain ``ΔW`` increments; the
            Brownian path must be constructed with
            ``levy_area="space-time"`` (checked eagerly both ways).
        noise_types: noise layouts the stepper accepts; ``noise=`` values
            outside this tuple are rejected eagerly.
    """

    name: str
    stepper: Callable
    reverse_stepper: Optional[Callable]
    nfe_per_step: int
    strong_order: float
    gradient_modes: Tuple[str, ...]
    supports_pallas: bool = False
    sde_type: str = "stratonovich"
    notes: str = ""
    embedded_stepper: Optional[Callable] = None
    needs_levy_area: bool = False
    noise_types: Tuple[str, ...] = ("diagonal", "general")

    @property
    def reversible(self) -> bool:
        return self.reverse_stepper is not None


SOLVERS: dict = {}


def register_solver(spec: SolverSpec) -> SolverSpec:
    """Add (or replace) a solver spec in the registry.

    ``spec.gradient_modes`` must name registered gradient backends — the
    join the capability table (:func:`gradient_capabilities`) is built on.
    """
    for m in spec.gradient_modes:
        if m not in GRADIENT_BACKENDS:
            raise ValueError(
                f"{spec.name}: unknown gradient mode {m!r}; registered "
                f"backends: {available_gradient_modes()}")
    if "reversible_adjoint" in spec.gradient_modes and not spec.reversible:
        raise ValueError(
            f"{spec.name}: reversible_adjoint requires a reverse_stepper")
    SOLVERS[spec.name] = spec
    return spec


def get_solver(name: str) -> SolverSpec:
    try:
        return SOLVERS[name]
    except KeyError:
        raise ValueError(
            f"unknown solver {name!r}; registered: {sorted(SOLVERS)}") from None


def available_solvers() -> Tuple[str, ...]:
    return tuple(sorted(SOLVERS))


register_solver(SolverSpec(
    "euler_maruyama", _euler_maruyama_step, None,
    nfe_per_step=1, strong_order=0.5,
    gradient_modes=("discretise", "continuous_adjoint", "checkpoint"),
    sde_type="ito", notes="order-0.5 Itô baseline"))

register_solver(SolverSpec(
    "midpoint", _midpoint_step, None,
    nfe_per_step=2, strong_order=0.5,
    gradient_modes=("discretise", "continuous_adjoint", "checkpoint"),
    notes="paper's main baseline",
    embedded_stepper=_midpoint_embedded_step))

register_solver(SolverSpec(
    "heun", _heun_step, None,
    nfe_per_step=2, strong_order=0.5,
    gradient_modes=("discretise", "continuous_adjoint", "checkpoint"),
    notes="trapezoidal",
    embedded_stepper=_heun_embedded_step))

register_solver(SolverSpec(
    "reversible_heun", reversible_heun_step, reversible_heun_reverse_step,
    nfe_per_step=1, strong_order=0.5,
    gradient_modes=("discretise", "reversible_adjoint", "checkpoint"),
    supports_pallas=True,
    notes="algebraically reversible; O(1)-memory exact adjoint (paper §3)",
    embedded_stepper=reversible_heun_embedded_step))

register_solver(SolverSpec(
    "srk", _srk_step, None,
    nfe_per_step=5, strong_order=1.5,
    gradient_modes=("discretise", "checkpoint"),
    sde_type="ito",
    notes="strong-order-1.5 SRK (Kloeden–Platen) on (W, H) space–time "
          "Lévy-area pairs; diagonal noise",
    embedded_stepper=_srk_embedded_step,
    needs_levy_area=True,
    noise_types=("diagonal",)))


def gradient_capabilities() -> dict:
    """The capability table: ``gradient_mode -> tuple of solver names``.

    The join of the two registries, in backend-inventory order — this is
    what gradient-mode error messages and the README inventory are built
    from, so both always reflect what is actually registered.
    """
    return {
        mode: tuple(s.name for s in SOLVERS.values()
                    if mode in s.gradient_modes)
        for mode in available_gradient_modes()
    }


def _validate(spec: SolverSpec, gradient_mode: str, noise: str,
              use_pallas_kernels: bool, save_trajectory: bool,
              adaptive: bool = False) -> None:
    backend = get_backend(gradient_mode)  # unknown mode: lists the registry
    if gradient_mode not in spec.gradient_modes:
        raise ValueError(
            f"solver {spec.name!r} does not support gradient_mode="
            f"{gradient_mode!r} (supported: {spec.gradient_modes}; solvers "
            f"serving {gradient_mode!r}: "
            f"{gradient_capabilities()[gradient_mode]})")
    if noise not in ("diagonal", "general"):
        raise ValueError(f"unknown noise type {noise!r}")
    if noise not in spec.noise_types:
        raise ValueError(
            f"solver {spec.name!r} supports noise={spec.noise_types}, got "
            f"{noise!r} (the order-1.5 scheme needs full Lévy areas for "
            f"general noise, which space-time H does not provide)")
    if use_pallas_kernels:
        if not spec.supports_pallas:
            raise ValueError(
                f"solver {spec.name!r} has no fused Pallas path "
                f"(only: {[s.name for s in SOLVERS.values() if s.supports_pallas]})")
        if noise != "diagonal":
            raise ValueError(
                "use_pallas_kernels requires diagonal noise (the fused "
                "kernels are elementwise; general noise needs an einsum)")
    if adaptive:
        if spec.embedded_stepper is None:
            raise ValueError(
                f"solver {spec.name!r} has no embedded error estimate, so "
                f"adaptive=True has nothing to control the step size with "
                f"(embedded pairs: "
                f"{[s.name for s in SOLVERS.values() if s.embedded_stepper is not None]}"
                f"); use a fixed grid or switch solver")
        if save_trajectory:
            raise ValueError(
                "adaptive=True accepts steps on a solver-chosen non-uniform "
                "grid, which save_trajectory's fixed (num_steps+1)-point "
                "output grid cannot represent — call solve(..., "
                "save_trajectory=False) for the terminal value (or "
                "solve_adaptive for the accepted-grid stats)")
    # backend-specific constraints (terminal-only outputs, pallas
    # compatibility, backward-integrator coverage, ...) live with the
    # backend — adaptive × use_pallas_kernels in general is legal: the
    # fused step kernels take dt as a traced scalar operand, so the
    # controller's per-attempt dt flows straight into the kernels.
    if backend.validate is not None:
        backend.validate(spec, noise=noise, save_trajectory=save_trajectory,
                         use_pallas=use_pallas_kernels, adaptive=adaptive)


# =============================================================================
# Adaptive stepping: PI-controlled accept/reject driver (DESIGN.md §10)
# =============================================================================

#: PI step-size controller gains (Gustafsson; DESIGN.md §10).  With the
#: normalised error ratio r_n (accept iff r_n <= 1) the next step is
#:   dt' = dt * clip(SAFETY * r_n^-BETA1 * r_prev^BETA2, FMIN, FMAX)
#: where r_prev is the ratio of the last *accepted* step.  BETA1 = kI + kP
#: and BETA2 = kP with kI = 0.3/k, kP = 0.4/k for embedded-pair order k = 2.
_PI_SAFETY = 0.9
_PI_BETA1 = 0.35
_PI_BETA2 = 0.2
_PI_FACTOR_MIN = 0.2
_PI_FACTOR_MAX = 5.0
_MIN_ERR_RATIO = 1e-10  # a zero error estimate must not produce dt = inf


class AdaptiveStats(NamedTuple):
    """Controller diagnostics of one adaptive solve (all in-graph arrays).

    ``dts``/``ts`` are ``(max_steps,)`` scalar buffers: entry ``i <
    num_accepted`` holds accepted step ``i``'s size and left endpoint; the
    tail is zero-padding.  ``nfe`` counts drift+diffusion evaluation pairs
    including rejected attempts (the cost the paper's tables report).
    ``converged`` is False when the step budget ran out before ``t1`` —
    the terminal value then sits at ``t_final``, not ``t1``.
    """

    num_accepted: jax.Array
    num_rejected: jax.Array
    nfe: jax.Array
    t_final: jax.Array
    converged: jax.Array
    dts: jax.Array
    ts: jax.Array


def _adaptive_loop(spec, drift, diffusion, params, z0, bm, t0, t1,
                   rtol, atol, max_steps: int, dt0, noise,
                   use_pallas: bool = False,
                   bridge_depth: Optional[int] = None):
    """Bounded ``lax.while_loop`` accept/reject driver.

    Brownian increments come from ``bm.evaluate(t, t + dt)`` — arbitrary-
    interval queries on ONE underlying sample path, so a rejected step and
    its halved retry see pathwise-consistent noise (the Lévy-bridge
    conditioning of the paper's eq. (8)).  ``bridge_depth`` caps the dyadic
    descent of those queries (paths that take a ``depth`` argument only);
    ``None`` keeps each path's own default.  The loop runs at most
    ``2 * max_steps`` iterations (``max_steps`` accepts + ``max_steps``
    rejects); if the budget is exhausted the solve stops early and
    ``stats.converged`` is False.

    Returns ``(final_carry, AdaptiveStats)``.  The accepted ``(ts, dts)``
    scalars are the replay contract consumed by the exact adjoint
    (repro.core.adjoint): the backward pass re-derives every accepted
    step's ``(t, dt, dw)`` bit-identically from them.
    """
    dtype = z0.dtype
    step = spec.embedded_stepper
    rev = spec.stepper is reversible_heun_step
    if use_pallas and rev:
        # fused state updates; legal because dt rides into the kernels as a
        # traced scalar operand (see repro.kernels.reversible_heun_step)
        step = functools.partial(step, use_pallas=True)
    if rev:
        carry0 = RevHeunState(z0, z0, drift(params, t0, z0),
                              diffusion(params, t0, z0))
        get_z = lambda c: c.z
    else:
        carry0 = z0
        get_z = lambda c: c
    rtol = jnp.asarray(rtol, dtype)
    atol = jnp.asarray(atol, dtype)
    t1a = jnp.asarray(t1, dtype)
    zeros = jnp.zeros((max_steps,), dtype)
    # Carrying W(t_left) halves the per-attempt Brownian cost when the path
    # offers single-point queries: one bridge descent (the right endpoint)
    # instead of evaluate's two.  Relies on the documented contract
    # ``evaluate(s, t) == value(t) - value(s)`` bitwise, which keeps the
    # backward replay (via evaluate) bit-identical to the forward.
    has_value = hasattr(bm, "value")
    # space-time mode: single-point queries return (W(t), H_{t0,t}) pairs;
    # the interval pair is recovered through the SAME op graph evaluate()
    # uses (stlevy_difference), so the backward replay stays bit-identical.
    levy = getattr(bm, "levy_area", None) == "space-time"
    dkw = {} if bridge_depth is None else {"depth": bridge_depth}
    w_left0 = (_tree_cast(bm.value(t0, **dkw), dtype) if has_value
               else jnp.zeros((), dtype))
    state0 = (carry0, jnp.asarray(t0, dtype), jnp.asarray(dt0, dtype),
              jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32),
              jnp.asarray(0, jnp.int32), zeros, zeros, w_left0,
              jnp.asarray(False))

    def cond(s):
        _, _, _, _, n_acc, n_rej, _, _, _, done = s
        return (~done) & (n_acc < max_steps) & (n_rej < max_steps)

    def body(s):
        carry, t, dt, prev_ratio, n_acc, n_rej, dts, ts, w_left, done = s
        # ``done`` lanes only arise under vmap (the batched while_loop keeps
        # stepping finished lanes until every lane finishes) — guard them.
        active = ~done
        remaining = t1a - t
        is_last = dt >= remaining
        dt_eff = jnp.minimum(dt, remaining)
        if has_value:
            w_right = _tree_cast(bm.value(t + dt_eff, **dkw), dtype)
            if levy:
                dw = stlevy_difference(w_left, w_right, t, t + dt_eff, bm.t0)
            else:
                dw = w_right - w_left
        else:
            w_right = w_left
            dw = _tree_cast(bm.evaluate(t, t + dt_eff, **dkw), dtype)
        cand, err = step(carry, t, dt_eff, dw, drift, diffusion, params, noise)
        scale = atol + rtol * jnp.maximum(jnp.abs(get_z(carry)),
                                          jnp.abs(get_z(cand)))
        ratio = jnp.sqrt(jnp.mean(jnp.square(err / scale)))
        ratio = jnp.maximum(ratio, _MIN_ERR_RATIO)
        accept = (ratio <= 1.0) & active
        # PI controller; a rejected step must shrink (safety < 1 and both
        # ratio powers <= 1 there), an accepted one may grow up to FMAX.
        factor = _PI_SAFETY * ratio ** (-_PI_BETA1) * prev_ratio ** _PI_BETA2
        factor = jnp.clip(factor, _PI_FACTOR_MIN, _PI_FACTOR_MAX)
        factor = jnp.where(accept, factor, jnp.minimum(factor, 1.0))
        carry_new = jax.tree.map(lambda a, b: jnp.where(accept, a, b),
                                 cand, carry)
        dts = dts.at[n_acc].set(jnp.where(accept, dt_eff, dts[n_acc]))
        ts = ts.at[n_acc].set(jnp.where(accept, t, ts[n_acc]))
        return (carry_new,
                jnp.where(accept, jnp.where(is_last, t1a, t + dt_eff), t),
                jnp.where(active, dt_eff * factor, dt),
                jnp.where(accept, ratio, prev_ratio),
                n_acc + accept.astype(jnp.int32),
                n_rej + (active & ~accept).astype(jnp.int32),
                dts, ts,
                jax.tree.map(lambda a, b: jnp.where(accept, a, b),
                             w_right, w_left),
                done | (accept & is_last))

    carry, t, _, _, n_acc, n_rej, dts, ts, _, done = lax.while_loop(
        cond, body, state0)
    nfe = (n_acc + n_rej) * spec.nfe_per_step + (1 if rev else 0)
    stats = AdaptiveStats(n_acc, n_rej, nfe, t, done, dts, ts)
    return carry, stats


def _check_levy_area(spec: SolverSpec, bm) -> None:
    """(W, H)-pair solvers need a space-time path, and vice versa — eagerly.

    A mismatch either way would fail deep inside a scan (tuple vs array
    ``dw``) or, worse for the None-mode direction, silently feed a ``(W,
    H)`` tuple into steppers written for bare ``ΔW``.
    """
    mode = getattr(bm, "levy_area", None)
    if spec.needs_levy_area and mode != "space-time":
        raise ValueError(
            f"solver {spec.name!r} consumes (W, H) space-time Lévy-area "
            f"pairs — construct the Brownian path with "
            f"levy_area='space-time' (got levy_area={mode!r} on "
            f"{type(bm).__name__})")
    if not spec.needs_levy_area and mode == "space-time":
        raise ValueError(
            f"solver {spec.name!r} consumes plain ΔW increments but the "
            f"Brownian path was built with levy_area='space-time' — drop "
            f"the flag (solvers consuming (W, H) pairs: "
            f"{[s.name for s in SOLVERS.values() if s.needs_levy_area]})")


def _check_adaptive_bm(bm) -> None:
    if not hasattr(bm, "evaluate"):
        raise ValueError(
            f"adaptive=True queries Brownian increments over solver-chosen "
            f"intervals via bm.evaluate(s, t); {type(bm).__name__} has no "
            f"evaluate method — use BrownianPath, VirtualBrownianTree or "
            f"DenseBrownianPath")


def _check_bridge_depth(bm, bridge_depth) -> None:
    if bridge_depth is None:
        return
    if not (isinstance(bridge_depth, int) and bridge_depth >= 1):
        raise ValueError(
            f"bridge_depth must be a positive int (dyadic descent levels), "
            f"got {bridge_depth!r}")
    probe = bm.value if hasattr(bm, "value") else bm.evaluate
    if "depth" not in inspect.signature(probe).parameters:
        raise ValueError(
            f"bridge_depth requires a Brownian path whose point queries "
            f"take a depth argument (BrownianPath); {type(bm).__name__} "
            f"has a fixed resolution — drop bridge_depth")


def solve_adaptive(
    drift: Callable,
    diffusion: Callable,
    params,
    z0: jax.Array,
    bm: BrownianPath,
    t0: float,
    t1: float,
    *,
    solver: str = "reversible_heun",
    rtol: float = 1e-3,
    atol: float = 1e-6,
    max_steps: int = 4096,
    dt0: Optional[float] = None,
    noise: str = "diagonal",
    bridge_depth: Optional[int] = None,
    precision: str = "highest",
):
    """Adaptive solve returning ``(z_T, AdaptiveStats)``.

    The diagnostics-bearing sibling of ``solve(..., adaptive=True)``:
    benchmarks read NFE and the accepted grid off the stats.  Forward
    simulation only — for gradients call :func:`solve` with
    ``gradient_mode="reversible_adjoint"`` or ``"checkpoint"`` (the stats
    buffers live inside the backend's residuals there).
    """
    spec = get_solver(solver)
    _validate(spec, "discretise", noise, False, False, adaptive=True)
    _check_levy_area(spec, bm)
    _check_adaptive_bm(bm)
    _check_bridge_depth(bm, bridge_depth)
    drift, diffusion = resolve_precision(precision).wrap_fields(
        drift, diffusion)
    if dt0 is None:
        dt0 = (t1 - t0) / 16
    carry, stats = _adaptive_loop(spec, drift, diffusion, params, z0, bm,
                                  t0, t1, rtol, atol, max_steps, dt0, noise,
                                  bridge_depth=bridge_depth)
    z = carry.z if spec.stepper is reversible_heun_step else carry
    return z, stats


def solve(
    drift: Callable,
    diffusion: Callable,
    params,
    z0: jax.Array,
    bm: BrownianPath,
    t0: float,
    t1: float,
    num_steps: int,
    *,
    solver: str = "reversible_heun",
    gradient_mode: str = "discretise",
    noise: str = "diagonal",
    save_trajectory: bool = True,
    use_pallas_kernels: bool = False,
    adaptive: bool = False,
    rtol: Optional[float] = None,
    atol: Optional[float] = None,
    max_steps: Optional[int] = None,
    dt0: Optional[float] = None,
    bridge_depth: Optional[int] = None,
    precision: str = "highest",
):
    """Solve ``dZ = μ_θ dt + σ_θ ∘ dW`` on ``[t0, t1]`` in ``num_steps`` steps.

    The single front door to the solver subsystem::

        traj = repro.solve(drift, diffusion, params, z0, bm, 0.0, 1.0, 64,
                           solver="reversible_heun",
                           gradient_mode="reversible_adjoint")

    Args:
        drift: ``(params, t, z) -> dz/dt`` (shape of ``z``).
        diffusion: ``(params, t, z) -> σ`` — shape of ``z`` for diagonal
            noise, ``(*z.shape, w)`` for general noise.
        params: pytree of parameters passed to both vector fields.
        z0: initial state.
        bm: Brownian sample path (:class:`repro.core.brownian.BrownianPath`
            or anything exposing ``increment(n, num_steps)``).
        t0, t1, num_steps: uniform time grid.
        solver: registry key — see :func:`available_solvers`.
        gradient_mode: "discretise" (AD through the scan, O(N) memory),
            "reversible_adjoint" (paper's exact O(1)-memory adjoint),
            "continuous_adjoint" (optimise-then-discretise baseline), or
            "checkpoint" (recursive binomial checkpointing: exact
            gradients for every registered solver at O(log n) memory).
        noise: "diagonal" or "general".
        save_trajectory: return the full ``(num_steps+1, *z0.shape)``
            trajectory (index 0 is ``z0``) instead of the terminal value.
            Must be ``False`` for the terminal-only gradient modes
            ("continuous_adjoint", "checkpoint") and for adaptive mode
            (the accepted grid is non-uniform).
        use_pallas_kernels: fuse the reversible-Heun per-step pipeline
            through the Pallas kernels — state updates, in-kernel Brownian
            generation (fixed-grid ``BrownianPath``), and the hand-derived
            backward cotangent phases (diagonal noise; forbidden with
            "discretise", whose plain AD cannot trace ``pallas_call`` —
            the fused derivative lives in the reversible-adjoint
            ``custom_vjp``).  Composes with ``adaptive=True``: dt is a
            traced kernel operand.
        adaptive: embedded-error-controlled stepping (DESIGN.md §10)
            instead of the fixed ``num_steps`` grid.  ``num_steps`` then
            only seeds the initial step ``dt0 = (t1-t0)/num_steps`` and the
            default budget ``max_steps``.  Requires a solver with an
            embedded pair (every registered solver except euler_maruyama)
            and a ``bm`` with arbitrary-interval ``evaluate``.  Gradients:
            ``"reversible_adjoint"`` replays the accepted grid exactly;
            ``"checkpoint"`` freezes the accepted grid under
            ``stop_gradient`` and differentiates a rematerialised replay;
            ``"discretise"`` is forward-only (``lax.while_loop`` has no
            reverse-mode rule); ``"continuous_adjoint"`` is rejected.
        rtol, atol: accept tolerance (defaults 1e-3 / 1e-6) — a step is
            accepted when the RMS of ``err / (atol + rtol * max(|z|,
            |z'|))`` is <= 1.  May be traced scalars (e.g. a per-request
            tolerance in serving).  Passing either without
            ``adaptive=True`` is an error — a fixed-grid solve would
            silently ignore the requested tolerance.
        max_steps: accepted-step budget (also bounds rejections); the
            backward replay buffers are ``(max_steps,)`` scalars.
            Defaults to ``max(4 * num_steps, 256)``.  A budget-exhausted
            solve returns **NaN** (its state sits at ``t_final < t1``,
            which must not pass silently as ``z_T``) — raise ``max_steps``
            or loosen the tolerance, or use :func:`solve_adaptive` to
            observe ``stats.converged`` gracefully.
        dt0: initial step size; defaults to ``(t1 - t0) / num_steps``.
        bridge_depth: cap on the dyadic Lévy-bridge descent of each
            adaptive Brownian query (``BrownianPath`` only; adaptive mode
            only).  The default (``None``) keeps the path's own depth-24
            resolution.  Each level costs one conditional-normal draw per
            attempted step, so on CPU the descent dominates adaptive wall
            clock; a solve run to tolerance ``rtol`` only needs the bridge
            residual — std ``<= 0.5 * 2^(-depth/2)`` in units of
            ``sqrt(t1-t0)`` — to sit well below ``rtol``, e.g. depth 10
            gives 1.6e-2, which scaled by a diffusion of 0.05 is ~8e-4 of
            state per unit time, comfortably inside a 2e-3 tolerance.  The
            SAME depth is used by the exact adjoint's backward replay, so
            replay stays bit-identical to the forward at any setting.
            Truncating the descent is a controlled approximation of the
            sample path — convergence-order studies should keep the
            default.
        precision: "highest" (default — fields run in the state dtype,
            bitwise the pre-policy behaviour) or "bf16_compute" (the
            mixed-precision policy: vector-field evaluation in bf16,
            solver state / Brownian increments / adjoint accumulators in
            the state dtype).  Applied before the gradient backend sees
            the fields, so it composes with every ``gradient_mode``.

    Returns:
        Trajectory or terminal value, differentiable w.r.t. ``params`` and
        ``z0`` according to ``gradient_mode``.

    The serving sampler contract: every adaptive *batch* sampler built on
    this subsystem (``repro.core.sde.generator_sample_terminal``, exposed
    per-bucket via ``repro.launch.steps.make_adaptive_terminal_step``)
    returns a ``(samples, converged)`` pair — ``samples`` of shape
    ``(batch, data_dim)`` and ``converged`` a ``(batch,)`` bool marking
    rows whose controller reached ``t1`` within ``max_steps``.
    Non-converged rows carry the state at ``t_final < t1`` (NOT NaN — the
    serving tier must return *something* to the client) and the flag rides
    back structurally on ``repro.serving.ServeResult.converged``.  For
    single-solve diagnostics (NFE, acceptance counts, the accepted grid)
    use :func:`solve_adaptive`, which returns the richer
    ``(z_T, repro.AdaptiveStats)`` instead.
    """
    spec = get_solver(solver)
    _validate(spec, gradient_mode, noise, use_pallas_kernels, save_trajectory,
              adaptive)
    _check_levy_area(spec, bm)
    if not adaptive and any(
            v is not None for v in (rtol, atol, max_steps, dt0,
                                    bridge_depth)):
        raise ValueError(
            "rtol/atol/max_steps/dt0/bridge_depth are adaptive-mode options "
            "but adaptive=False — pass adaptive=True (a fixed-grid solve "
            "would silently ignore the requested tolerance)")

    backend = get_backend(gradient_mode)
    # the precision policy wraps the fields BEFORE the backend sees them,
    # so adjoint replays/backsolves evaluate the same (wrapped) fields as
    # the forward; "highest" is the identity wrap
    drift, diffusion = resolve_precision(precision).wrap_fields(
        drift, diffusion)

    if adaptive:
        _check_adaptive_bm(bm)
        _check_bridge_depth(bm, bridge_depth)
        rtol = 1e-3 if rtol is None else rtol
        atol = 1e-6 if atol is None else atol
        if max_steps is None:
            max_steps = max(4 * num_steps, 256)
        if dt0 is None:
            dt0 = (t1 - t0) / num_steps
        z, converged = backend.solve_adaptive(
            spec, drift, diffusion, params, z0, bm, rtol, atol, t0, t1,
            max_steps, dt0, noise=noise, use_pallas=use_pallas_kernels,
            bridge_depth=bridge_depth)
        # a budget-exhausted solve sits at t_final < t1 — poison it rather
        # than hand back a truncated-horizon state as z_T (select-based, so
        # converged solves keep their gradient untouched); callers wanting
        # graceful access go through solve_adaptive's stats
        return jnp.where(converged, z, jnp.asarray(jnp.nan, z.dtype))

    return backend.solve(
        spec, drift, diffusion, params, z0, bm, t0, t1, num_steps,
        noise=noise, save_trajectory=save_trajectory,
        use_pallas=use_pallas_kernels)


def solve_batched(
    drift: Callable,
    diffusion: Callable,
    params,
    z0: jax.Array,
    keys: jax.Array,
    t0: float,
    t1: float,
    num_steps: int,
    *,
    w_dim: Optional[int] = None,
    **kwargs,
):
    """Vmapped multi-trajectory :func:`solve`: batch of initial states ×
    batch of Brownian seeds, as one XLA program.

    Args:
        z0: ``(B, *state_shape)`` initial states.
        keys: ``(B,)`` PRNG keys — one independent Brownian path per
            trajectory (pass ``jax.random.split(key, B)``).
        w_dim: Brownian dimension for general noise (defaults to the
            trailing state dim, i.e. diagonal layout).
        **kwargs: forwarded to :func:`solve` (solver / gradient_mode /
            noise / save_trajectory / use_pallas_kernels / adaptive /
            rtol / atol / max_steps / dt0); validated once before vmapping
            so errors surface eagerly.  With ``adaptive=True`` every
            trajectory runs its own controller (per-trajectory accepted
            grids — the batched while_loop runs until the slowest lane
            finishes).

    Returns:
        ``(B, num_steps+1, *state_shape)`` trajectories (or ``(B, *state)``
        terminal values with ``save_trajectory=False``).
    """
    if z0.ndim < 1 or keys.shape[0] != z0.shape[0]:
        raise ValueError(
            f"leading (batch) dims must agree: z0 {z0.shape} vs keys "
            f"{keys.shape}")
    spec = get_solver(kwargs.get("solver", "reversible_heun"))
    _validate(spec,
              kwargs.get("gradient_mode", "discretise"),
              kwargs.get("noise", "diagonal"),
              kwargs.get("use_pallas_kernels", False),
              kwargs.get("save_trajectory", True),
              kwargs.get("adaptive", False))
    resolve_precision(kwargs.get("precision", "highest"))

    state_shape = z0.shape[1:]
    if kwargs.get("noise", "diagonal") == "general":
        if w_dim is None:
            raise ValueError("general noise needs w_dim= for the Brownian shape")
        bm_shape = state_shape[:-1] + (w_dim,)
    else:
        bm_shape = state_shape

    def single(z0_i, key_i):
        bm = BrownianPath(key_i, t0, t1, bm_shape, z0.dtype,
                          levy_area="space-time" if spec.needs_levy_area
                          else None)
        return solve(drift, diffusion, params, z0_i, bm, t0, t1, num_steps,
                     **kwargs)

    return jax.vmap(single)(z0, keys)
