"""Brownian motion sampling — in-graph (XLA/TPU-native) implementations.

Three samplers, mirroring the paper's landscape (Section 4):

* :class:`BrownianPath` — the TPU-native adaptation of the paper's Brownian
  Interval.  JAX's counter-based splittable PRNG (Threefry; the paper's own
  reference [34] for splittable PRNGs) lets us derive the increment of *any*
  solver step from ``fold_in(key, step_index)``: exact, O(1) memory, O(1)
  time, and bit-identical on the forward and backward passes with **zero**
  storage.  Off-grid queries use Lévy-bridge bisection over a virtual dyadic
  tree, conditioning exactly as the paper's eq. (8).

* :class:`VirtualBrownianTree` — the Li et al. [15] baseline the paper beats:
  fixed-depth dyadic bisection to a tolerance ``eps``; approximate.

* :func:`brownian_increments` — dense pregenerated increments (the
  "store everything" O(T)-memory baseline).

The *faithful* host-side Brownian Interval (binary tree + LRU cache + search
hints, Algorithms 3/4) lives in :mod:`repro.core.brownian_interval`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import scopes


def _normal_like(key: jax.Array, shape: Tuple[int, ...], dtype) -> jax.Array:
    return jax.random.normal(key, shape, dtype=dtype)


#: Valid values of the paths' ``levy_area`` mode.  ``None`` keeps the
#: original scalar-increment behaviour (bit-identical to before the mode
#: existed); ``"space-time"`` makes ``increment``/``evaluate``/``value``
#: return ``(W, H)`` pairs, where ``H`` is the space-time Lévy area of the
#: queried interval (Foster et al. [54]; paper App. E) — the extra
#: integral the strong-order-1.5 SRK solver consumes.
LEVY_AREAS = (None, "space-time")


def _check_levy_mode(levy_area) -> None:
    if levy_area not in LEVY_AREAS:
        raise ValueError(
            f"unknown levy_area mode {levy_area!r}; supported: {LEVY_AREAS}")


def stlevy_difference(val_s, val_t, s, t, t0):
    """``(W, H)`` over ``[s, t]`` from two space-time path *values*.

    ``val_s``/``val_t`` are ``(W, H)`` pairs as returned by a path's
    ``value`` in ``levy_area="space-time"`` mode — both components
    relative to ``t0``.  The W component is the literal difference
    ``val_t[0] - val_s[0]`` (so ``evaluate(s,t)[0] == value(t)[0] -
    value(s)[0]`` stays bitwise).  The H component inverts Chen's
    relation exactly: with the running time-integral ``I(u) =
    (u - t0)·(H_u + W_u/2) = ∫_{t0}^u (W_r - W_{t0}) dr``, the interval's
    raw time-area is ``A_{s,t} = I(t) - I(s) - (t-s)·W_s`` and
    ``H_{s,t} = A_{s,t}/(t-s) - W_{s,t}/2``.  Because every query is this
    difference of per-point values, H additivity (the chen-combine rule)
    holds over adjacent intervals by construction.

    The same op graph serves the adaptive driver, the checkpoint
    backend's freeze-and-replay, and ``evaluate`` itself — the bitwise-
    replay requirement (DESIGN.md §10).  A zero-length query (padding
    slots in the checkpoint replay) returns exact zeros instead of 0/0.
    """
    w_s, h_s = val_s
    w_t, h_t = val_t
    dtype = jnp.result_type(w_t)
    s = jnp.asarray(s, dtype)
    t = jnp.asarray(t, dtype)
    t0 = jnp.asarray(t0, dtype)
    dw = w_t - w_s
    i_s = (s - t0) * (h_s + 0.5 * w_s)
    i_t = (t - t0) * (h_t + 0.5 * w_t)
    span = t - s
    area = i_t - i_s - span * w_s
    safe = jnp.where(span == 0, jnp.ones_like(span), span)
    dh = jnp.where(span == 0, jnp.zeros_like(dw), area / safe - 0.5 * dw)
    return dw, dh


def _h_from_wi(w, i, span, dtype):
    """``H = I/span - W/2`` with the zero-length query guarded to 0."""
    span = jnp.asarray(span, dtype)
    safe = jnp.where(span == 0, jnp.ones_like(span), span)
    return jnp.where(span == 0, jnp.zeros_like(w), i / safe - 0.5 * w)


def brownian_increments(
    key: jax.Array,
    t0: float,
    t1: float,
    num_steps: int,
    shape: Tuple[int, ...],
    dtype=jnp.float32,
) -> jax.Array:
    """Dense iid increments ``W_{t_{n+1}} - W_{t_n}`` — O(T) memory baseline."""
    dt = (t1 - t0) / num_steps
    keys = jax.random.split(key, num_steps)
    out = jax.vmap(lambda k: _normal_like(k, shape, dtype))(keys)
    return out * jnp.sqrt(jnp.asarray(dt, dtype))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BrownianPath:
    """Exact, stateless, counter-based Brownian sample path on ``[t0, t1]``.

    The path is *defined* by ``key``: every query is a pure function of
    ``(key, query)``, so forward and backward passes of a solver see the same
    sample without storing anything (the paper's core requirement, §4).

    ``increment(n, num_steps)`` is the fast path used by fixed-step solvers:
    step ``n`` of an ``num_steps``-step grid.  Different grids over the same
    key are *different* refinements consistent in distribution but not
    pathwise; solvers must use one grid per solve (as torchsde's fixed-step
    solvers do).  ``evaluate(s, t)`` offers pathwise-consistent arbitrary
    queries via dyadic Lévy-bridge descent (exact at dyadic points, depth-
    limited elsewhere like the Virtual Brownian Tree but reusing the same
    conditioning as the paper's eq. (8)).

    ``levy_area="space-time"`` switches every query to ``(W, H)`` pairs
    (paper App. E; DESIGN.md §13): ``increment`` draws iid pairs per grid
    step, and ``evaluate``/``value`` run a joint ``(W, ∫W)`` Lévy-bridge
    descent whose per-level conditioning extends eq. (8) with the interval
    time-integral, so H combines exactly over adjacent intervals (Chen's
    relation) while the W component keeps the bitwise
    ``evaluate(s,t) == value(t) - value(s)`` contract.  ``levy_area=None``
    paths are bit-identical to the pre-mode implementation — the H-mode
    descent is a separate key stream and code path.
    """

    key: jax.Array
    t0: float
    t1: float
    shape: Tuple[int, ...]
    dtype: object = jnp.float32
    levy_area: Optional[str] = None

    def __post_init__(self):
        _check_levy_mode(self.levy_area)

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.key,), (self.t0, self.t1, self.shape, self.dtype,
                             self.levy_area)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (key,) = children
        t0, t1, shape, dtype, levy_area = aux
        return cls(key=key, t0=t0, t1=t1, shape=shape, dtype=dtype,
                   levy_area=levy_area)

    # -- fixed-grid exact increments ----------------------------------------
    @scopes.scoped(scopes.BROWNIAN)
    def increment(self, n: jax.Array, num_steps: int) -> jax.Array:
        """Exact increment of step ``n`` on the ``num_steps`` uniform grid.

        Dispatches through :mod:`repro.kernels.ops`: on TPU the draw runs
        *inside* a Pallas kernel (counter-based Threefry keyed on ``n``,
        bit-identical to the ``jax.random`` scheme — see
        :mod:`repro.kernels.prng`); elsewhere the pure-jnp oracle runs.
        """
        from ..kernels import ops

        dt = (self.t1 - self.t0) / num_steps
        if self.levy_area == "space-time":
            # iid (W, H) pair for this grid cell — the fold_in(key, n)
            # schedule mirrors the scalar stream but is a distinct draw
            # (the H-mode key is consumed by space_time_levy_area's split)
            return space_time_levy_area(jax.random.fold_in(self.key, n),
                                        dt, self.shape, self.dtype)
        return ops.brownian_increment(self.key, n, self.shape, self.dtype, dt)

    def increments(self, num_steps: int) -> jax.Array:
        """All increments on the grid, stacked (for dense baselines/tests)."""
        return jax.vmap(lambda n: self.increment(n, num_steps))(
            jnp.arange(num_steps)
        )

    # -- arbitrary-interval queries (Lévy bridge descent) --------------------
    def evaluate(self, s, t, depth: int = 24):
        """``W_t - W_s`` via ``W(t) - W(s)`` with dyadic bridge descent.

        In ``levy_area="space-time"`` mode: the ``(W, H)`` pair of
        ``[s, t]`` via :func:`stlevy_difference` over the two point
        values — W stays the literal value difference (bitwise), H obeys
        chen-combine additivity by construction."""
        if self.levy_area == "space-time":
            return stlevy_difference(self.value(s, depth),
                                     self.value(t, depth),
                                     s, t, self.t0)
        return self._w(t, depth) - self._w(s, depth)

    def value(self, t, depth: int = 24):
        """``W(t) - W(t0)`` — one bridge descent.  Contract (relied on by
        the adaptive driver, which carries the left-endpoint value):
        ``evaluate(s, t) == value(t) - value(s)`` bitwise.  In
        ``levy_area="space-time"`` mode returns the pair
        ``(W(t) - W(t0), H_{t0,t})``."""
        if self.levy_area == "space-time":
            dtype = jnp.dtype(self.dtype)
            w, i = self._wh(t, depth)
            span = jnp.asarray(t, dtype) - jnp.asarray(self.t0, dtype)
            return w, _h_from_wi(w, i, span, dtype)
        return self._w(t, depth)

    @scopes.scoped(scopes.BROWNIAN)
    def _w(self, t, depth: int) -> jax.Array:
        """Sample W(t) by descending the virtual dyadic tree to ``depth``.

        Invariant per level: the current interval ``[a, b]`` has endpoint
        values ``(wa, wb)``; the midpoint value is bridge-sampled from the
        interval's splittable seed (the Lévy bridge of the paper's eq. (8):
        mean = linear interpolant, std = sqrt((b-m)(m-a)/(b-a))), then we
        recurse into the half containing ``t``.  At dyadic ``t`` this
        terminates exactly; otherwise the depth bound gives a
        2^-depth * (t1-t0) resolution (the VBT trade-off, but sharing seeds
        with ``increment`` queries is not required — a BrownianPath used
        with bridge queries should use ``evaluate`` only).

        Dispatches through :mod:`repro.kernels.ops`: on TPU the whole
        descent runs as ONE Pallas kernel (in-kernel Threefry + a single
        batched midpoint draw); elsewhere the vectorised jnp oracle
        (:func:`repro.kernels.ref.brownian_value`) runs — same per-element
        op sequence, so both produce identical bits.
        """
        from ..kernels import ops

        return ops.brownian_value(self.key, t, self.t0, self.t1, self.shape,
                                  self.dtype, depth=depth)

    @scopes.scoped(scopes.BROWNIAN)
    def _wh(self, t, depth: int):
        """Joint ``(W(t) - W(t0), I(t))`` descent, where ``I(t) =
        ∫_{t0}^t (W_r - W_{t0}) dr`` is the running time-integral.

        Each level of the dyadic descent carries the current interval's
        ``(w, A)`` — increment and *raw time-area* ``A = ∫ (W_r - W_a) dr``
        — plus the prefix ``(W(a) - W(t0), I(a))`` accumulated on
        right-descents.  The midpoint conditional (joint Gaussian
        conditioning of ``(W_m, ∫_a^m W)`` on ``(w, A)``; the H extension
        of the paper's eq. (8)) is, with ``h = b - a`` and ``l = h/2``::

            w_left = (3/2)·A/h - w/4 + sqrt(l/8)  · ξ0
            a_left = -l·w/4 + A/2   + sqrt(l³/24) · ξ1

        with ``w_left ⊥ a_left`` given ``(w, A)`` (the conditional
        cross-covariance vanishes exactly at the midpoint), and::

            w_right = w - w_left
            a_right = A - a_left - l·w_left

        At the depth bound the cell tail is closed with the conditional
        *mean* given the cell's ``(w, A)`` (θ = in-cell fraction)::

            W += (3θ² - 2θ)·w + 6θ(1-θ)·A/h
            I += θh·prefix_W + h(θ³ - θ²)·w + (3θ² - 2θ³)·A

        — deterministic, so queries stay exactly additive (the same
        truncation trade-off as the scalar descent's linear tail).

        A fresh key stream (root tag 0xB0BA, midpoints ``fold_in(·, 1)``
        then a split for the two conditional normals) keeps the
        ``levy_area=None`` draws untouched.
        """
        dtype = jnp.dtype(self.dtype)
        shape = self.shape
        t = jnp.asarray(t, dtype)
        span = self.t1 - self.t0
        root_key = jax.random.fold_in(self.key, 0xB0BA)
        w_root, h_root = space_time_levy_area(root_key, span, shape, dtype)
        a_root = jnp.asarray(span, dtype) * (h_root + 0.5 * w_root)

        def body(_, c):
            a, b, w, area, pw, pi, key = c
            h = b - a
            half = 0.5 * h
            m = a + half
            k0, k1 = jax.random.split(jax.random.fold_in(key, 1))
            xi0 = _normal_like(k0, shape, dtype)
            xi1 = _normal_like(k1, shape, dtype)
            w_l = 1.5 * area / h - 0.25 * w + jnp.sqrt(half / 8.0) * xi0
            a_l = -0.25 * half * w + 0.5 * area + jnp.sqrt(
                half ** 3 / 24.0) * xi1
            w_r = w - w_l
            a_r = area - a_l - half * w_l
            go_left = t <= m
            key_next = jax.random.fold_in(
                key, jnp.where(go_left, jnp.uint32(2), jnp.uint32(3)))
            sel = lambda x, y: jnp.where(go_left, x, y)
            return (sel(a, m), sel(m, b), sel(w_l, w_r), sel(a_l, a_r),
                    sel(pw, pw + w_l), sel(pi, pi + half * pw + a_l),
                    key_next)

        zeros = jnp.zeros(shape, dtype)
        a, b, w, area, pw, pi, _ = lax.fori_loop(
            0, depth, body,
            (jnp.asarray(self.t0, dtype), jnp.asarray(self.t1, dtype),
             w_root, a_root, zeros, zeros, root_key))
        h = b - a
        theta = jnp.clip((t - a) / jnp.maximum(h, jnp.finfo(dtype).tiny),
                         0.0, 1.0)
        w_t = pw + (3.0 * theta ** 2 - 2.0 * theta) * w \
            + 6.0 * theta * (1.0 - theta) * area / h
        i_t = pi + theta * h * pw + h * (theta ** 3 - theta ** 2) * w \
            + (3.0 * theta ** 2 - 2.0 * theta ** 3) * area
        return w_t, i_t


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DenseBrownianPath:
    """Pregenerated fine-grid increments with *pathwise-consistent*
    coarsening: ``increment(n, N)`` sums the fine increments inside coarse
    step ``n``.  This is the O(T)-memory baseline — and the right tool for
    strong-convergence measurements, where coarse and fine solves must see
    the SAME sample path (the counter-based :class:`BrownianPath` gives
    per-grid refinements that agree in law but not pathwise)."""

    w: jax.Array  # (fine_steps, *shape) increments on the finest grid
    t0: float = 0.0
    t1: float = 1.0
    #: (fine_steps, *shape) per-cell space-time Lévy areas (H-mode only) —
    #: a leaf so vmap-constructed paths slice it alongside ``w``
    hh: Optional[jax.Array] = None
    levy_area: Optional[str] = None

    def __post_init__(self):
        _check_levy_mode(self.levy_area)
        if (self.levy_area == "space-time") != (self.hh is not None):
            raise ValueError(
                "DenseBrownianPath: levy_area='space-time' requires the "
                "per-cell areas hh (use sample(..., "
                "levy_area='space-time')); hh without the mode is a bug")

    def tree_flatten(self):
        return (self.w, self.hh), (self.t0, self.t1, self.levy_area)

    @classmethod
    def tree_unflatten(cls, aux, children):
        t0, t1, levy_area = aux
        return cls(w=children[0], hh=children[1], t0=t0, t1=t1,
                   levy_area=levy_area)

    @classmethod
    def sample(cls, key, t0: float, t1: float, fine_steps: int, shape,
               dtype=jnp.float32, levy_area: Optional[str] = None):
        # ``w`` is drawn from ``key`` exactly as in scalar mode, so the
        # H-mode path shares its W component bitwise with the
        # ``levy_area=None`` path of the same key — strong-convergence
        # studies can compare (W)-solvers and (W, H)-solvers on the SAME
        # sample path.  The per-cell areas come from a fold_in-tagged key.
        _check_levy_mode(levy_area)
        w = brownian_increments(key, t0, t1, fine_steps, shape, dtype)
        hh = None
        if levy_area == "space-time":
            dt = (t1 - t0) / fine_steps
            hh = jax.random.normal(
                jax.random.fold_in(key, 0xB0BA),
                (fine_steps,) + tuple(shape), dtype,
            ) * jnp.sqrt(jnp.asarray(dt, dtype) / 12.0)
        return cls(w, t0=t0, t1=t1, hh=hh, levy_area=levy_area)

    @property
    def fine_steps(self) -> int:
        return self.w.shape[0]

    @property
    def _dt_fine(self):
        return (self.t1 - self.t0) / self.fine_steps

    @scopes.scoped(scopes.BROWNIAN)
    def increment(self, n: jax.Array, num_steps: int):
        r = self.fine_steps // num_steps
        assert r * num_steps == self.fine_steps, \
            f"{num_steps} must divide fine_steps={self.fine_steps}"
        if self.levy_area == "space-time":
            return self._increment_wh(n, r)
        if r == 1:
            return lax.dynamic_index_in_dim(self.w, n, 0, keepdims=False)
        return jnp.sum(lax.dynamic_slice_in_dim(self.w, n * r, r, 0), axis=0)

    def _increment_wh(self, n: jax.Array, r: int):
        """Coarse ``(W, H)`` by chen-combining the ``r`` fine cells of
        coarse step ``n``: raw areas add after shifting each cell's to the
        coarse left endpoint, ``A = Σ_i (A_i + dt_f · W_{prefix,i})``."""
        dtype = self.w.dtype
        dt_f = jnp.asarray(self._dt_fine, dtype)
        if r == 1:
            return (lax.dynamic_index_in_dim(self.w, n, 0, keepdims=False),
                    lax.dynamic_index_in_dim(self.hh, n, 0, keepdims=False))
        ws = lax.dynamic_slice_in_dim(self.w, n * r, r, 0)
        hs = lax.dynamic_slice_in_dim(self.hh, n * r, r, 0)
        w = jnp.sum(ws, axis=0)
        cells = dt_f * (hs + 0.5 * ws)                    # per-cell raw areas
        prefix = jnp.cumsum(ws, axis=0) - ws              # exclusive W prefix
        area = jnp.sum(cells + dt_f * prefix, axis=0)
        return w, area / (r * dt_f) - 0.5 * w

    # -- arbitrary-interval queries (adaptive solvers) -----------------------
    @scopes.scoped(scopes.BROWNIAN)
    def _w_at(self, t) -> jax.Array:
        """W(t) from the stored fine increments: exact at fine-grid nodes
        (prefix sums of ``w``), linearly interpolated inside a fine cell.
        The interpolation is the bridge *mean* — deterministic, so
        ``evaluate`` stays exactly additive — but it under-resolves
        variation below the fine grid; size ``fine_steps`` well above the
        expected adaptive step count.

        The prefix sum is recomputed per query rather than cached on the
        pytree: under jit it is a loop constant (XLA hoists it out of the
        adaptive while_loop), and the eager payers are tests/benchmarks —
        a second ``cum`` leaf would complicate every vmap-constructed
        ``DenseBrownianPath(w_i, ...)`` for an O(fine_steps) win nothing
        on the hot path needs."""
        dtype = self.w.dtype
        t = jnp.asarray(t, dtype)
        pos = (t - self.t0) / (self.t1 - self.t0) * self.fine_steps
        pos = jnp.clip(pos, 0.0, float(self.fine_steps))
        i = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, self.fine_steps - 1)
        frac = pos - i.astype(dtype)
        cum = jnp.cumsum(self.w, axis=0)  # cum[k] = W(node k+1) − W(t0)
        w_lo = jnp.where(i > 0, lax.dynamic_index_in_dim(
            cum, jnp.maximum(i - 1, 0), 0, keepdims=False), jnp.zeros_like(self.w[0]))
        inc = lax.dynamic_index_in_dim(self.w, i, 0, keepdims=False)
        return w_lo + frac * inc

    @scopes.scoped(scopes.BROWNIAN)
    def _wi_at(self, t):
        """H-mode point query: ``(W(t) - W(t0), I(t))`` with ``I`` the
        running time-integral.  Exact at fine-grid nodes (prefix sums of
        the per-cell increments and raw areas); inside a cell both
        components close with the conditional mean given the cell's
        ``(w, H)`` — the same deterministic-tail policy as the scalar
        linear interpolation, but H-aware (``θw + 6θ(1-θ)H`` instead of
        ``θw``), so W and I stay mutually consistent."""
        dtype = self.w.dtype
        t = jnp.asarray(t, dtype)
        dt_f = jnp.asarray(self._dt_fine, dtype)
        pos = (t - self.t0) / (self.t1 - self.t0) * self.fine_steps
        pos = jnp.clip(pos, 0.0, float(self.fine_steps))
        i = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, self.fine_steps - 1)
        theta = pos - i.astype(dtype)
        zero = jnp.zeros_like(self.w[0])
        cum_w = jnp.cumsum(self.w, axis=0)
        cells = dt_f * (self.hh + 0.5 * self.w)           # per-cell raw areas
        # I at node k = Σ_{j<k} (A_j + dt_f · (W(node j) − W(t0)))
        cum_i = jnp.cumsum(cells + dt_f * (cum_w - self.w), axis=0)
        at = lambda arr, k: lax.dynamic_index_in_dim(arr, k, 0, keepdims=False)
        w_lo = jnp.where(i > 0, at(cum_w, jnp.maximum(i - 1, 0)), zero)
        i_lo = jnp.where(i > 0, at(cum_i, jnp.maximum(i - 1, 0)), zero)
        w_c = at(self.w, i)
        a_c = at(cells, i)
        w_t = w_lo + (3.0 * theta ** 2 - 2.0 * theta) * w_c \
            + 6.0 * theta * (1.0 - theta) * a_c / dt_f
        i_t = i_lo + theta * dt_f * w_lo \
            + dt_f * (theta ** 3 - theta ** 2) * w_c \
            + (3.0 * theta ** 2 - 2.0 * theta ** 3) * a_c
        return w_t, i_t

    def evaluate(self, s, t):
        """``W_t − W_s``; pathwise-consistent with :meth:`increment` (sums of
        the same fine increments) and exactly additive over adjacent
        intervals, because every query is a difference of ``W(·)``.  In
        ``levy_area="space-time"`` mode: the ``(W, H)`` pair via
        :func:`stlevy_difference` over the two point values."""
        if self.levy_area == "space-time":
            return stlevy_difference(self.value(s), self.value(t),
                                     s, t, self.t0)
        return self._w_at(t) - self._w_at(s)

    def value(self, t):
        """``W(t) − W(t0)`` (see :meth:`BrownianPath.value` for the
        ``evaluate(s,t) == value(t) − value(s)`` contract); the
        ``(W, H_{t0,t})`` pair in ``levy_area="space-time"`` mode."""
        if self.levy_area == "space-time":
            dtype = self.w.dtype
            w, i = self._wi_at(t)
            span = jnp.asarray(t, dtype) - jnp.asarray(self.t0, dtype)
            return w, _h_from_wi(w, i, span, dtype)
        return self._w_at(t)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class VirtualBrownianTree:
    """Li et al. [15] baseline: approximate dyadic bisection to tolerance.

    Every query pays the *full* ``O(log(1/eps))`` descent from the root —
    exactly the cost profile the Brownian Interval removes (paper Table 2).
    """

    key: jax.Array
    t0: float
    t1: float
    shape: Tuple[int, ...]
    tol: float = 1e-5
    dtype: object = jnp.float32
    levy_area: Optional[str] = None

    def __post_init__(self):
        _check_levy_mode(self.levy_area)

    def tree_flatten(self):
        return (self.key,), (self.t0, self.t1, self.shape, self.tol,
                             self.dtype, self.levy_area)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (key,) = children
        t0, t1, shape, tol, dtype, levy_area = aux
        return cls(key=key, t0=t0, t1=t1, shape=shape, tol=tol, dtype=dtype,
                   levy_area=levy_area)

    @property
    def _depth(self) -> int:
        import math

        span = self.t1 - self.t0
        return max(1, int(math.ceil(math.log2(max(span / self.tol, 2.0)))))

    def _path(self) -> BrownianPath:
        return BrownianPath(self.key, self.t0, self.t1, self.shape,
                            self.dtype, levy_area=self.levy_area)

    def _w(self, t) -> jax.Array:
        return self._path()._w(t, depth=self._depth)

    def evaluate(self, s, t):
        if self.levy_area == "space-time":
            return stlevy_difference(self.value(s), self.value(t),
                                     s, t, self.t0)
        return self._w(t) - self._w(s)

    def value(self, t):
        if self.levy_area == "space-time":
            return self._path().value(t, depth=self._depth)
        return self._w(t)

    def increment(self, n: jax.Array, num_steps: int):
        dt = (self.t1 - self.t0) / num_steps
        s = self.t0 + n * dt
        return self.evaluate(s, s + dt)


def space_time_levy_area(key: jax.Array, dt, shape, dtype=jnp.float32):
    """Sample ``(W, H)`` on an interval: increment + space-time Lévy area.

    ``H`` (Foster et al. [54]) is N(0, dt/12) independent of W — the pair
    the strong-order-1.5 SRK solver consumes (paper App. E; DESIGN.md §13).
    This is the primitive draw behind the paths' ``levy_area="space-time"``
    mode (:meth:`BrownianPath.increment`, :meth:`DenseBrownianPath.sample`)
    and a building block for the ``W̃`` Lévy-area approximation of
    Davie/Foster (Appendix E, eq. for W̃; :func:`davie_levy_area`).
    """
    kw, kh = jax.random.split(key)
    dt = jnp.asarray(dt, dtype)
    w = jax.random.normal(kw, shape, dtype) * jnp.sqrt(dt)
    h = jax.random.normal(kh, shape, dtype) * jnp.sqrt(dt / 12.0)
    return w, h


def davie_levy_area(key: jax.Array, w: jax.Array, h: jax.Array, dt) -> jax.Array:
    """Davie/Foster approximation of the second iterated integral W̃ (App. E).

    ``W̃ = 0.5 W⊗W + H⊗W − W⊗H + λ`` with antisymmetric λ, λ_ij ~ N(0, dt²/12).
    ``w, h`` have shape (..., d); returns (..., d, d).
    """
    d = w.shape[-1]
    dtype = w.dtype
    lam_flat = jax.random.normal(key, w.shape[:-1] + (d, d), dtype)
    lam = (jnp.tril(lam_flat, -1) - jnp.swapaxes(jnp.tril(lam_flat, -1), -1, -2)) * jnp.sqrt(
        jnp.asarray(dt, dtype) ** 2 / 12.0
    )
    outer = lambda a, b: a[..., :, None] * b[..., None, :]
    return 0.5 * outer(w, w) + outer(h, w) - outer(w, h) + lam
