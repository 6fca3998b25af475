"""Space-time Lévy area tests: the (W, H) path contract, the bitwise
``levy_area=None`` freeze, and the moment structure of the samplers.

Three layers of guarantee (DESIGN.md §13):

* **None-mode freeze** — adding the ``levy_area`` mode must not move a
  single bit of the existing draws; pinned against the installed
  ``jax.random`` draws of the documented key schedule.
* **(W, H) contract** — the W component keeps the bitwise
  ``evaluate(s, t) == value(t) - value(s)`` identity (under ``jit(vmap)``,
  at non-dyadic points, under ``bridge_depth`` caps), and H satisfies the
  chen-combine rule over adjacent intervals.
* **Moments** — H ~ N(0, dt/12) independent of W at the path level, and
  λ-antisymmetry in :func:`davie_levy_area`.

Float64 assertions pin x64 for their scope (the x64-truncation trap:
without it the requested dtype silently truncates to float32).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.brownian import (
    BrownianPath,
    DenseBrownianPath,
    VirtualBrownianTree,
    davie_levy_area,
    space_time_levy_area,
    stlevy_difference,
)
from repro.core.brownian_interval import BrownianInterval


def _chen_h(w_st, h_st, w_tu, h_tu, h1, h2):
    """Chen-combine rule for space-time Lévy area over adjacent intervals."""
    h = h1 + h2
    return (h1 * h_st + h2 * h_tu) / h + (h2 * w_st - h1 * w_tu) / (2.0 * h)


# -----------------------------------------------------------------------------
# None-mode bitwise freeze (references drawn with the installed jax.random)
# -----------------------------------------------------------------------------


def _bridge_reference(key, t, t0, t1, shape, dtype, depth):
    """``W(t) - W(t0)`` by the sequential Lévy-bridge descent, written with
    nothing but ``jax.random``: root draw under ``fold_in(key, 0xB0B)``,
    each level's midpoint under ``fold_in(node, 1)``, children under
    ``fold_in(node, 2 | 3)``.  Runs under ``jit`` like the path's own
    descent; the two may still round a midpoint combine differently (one
    fuses it into an FMA where the other does not): one ulp of the
    combine, two of the result (measured: one element of
    ``value(0.2)`` on the CPU)."""
    a, b, t = (jnp.asarray(x, dtype) for x in (t0, t1, t))
    levels = []  # (bridge std, went left) per level
    for _ in range(depth):
        m = 0.5 * (a + b)
        levels.append((jnp.sqrt((b - m) * (m - a) / (b - a)), bool(t <= m)))
        a, b = (a, m) if levels[-1][1] else (m, b)
    frac = jnp.clip((t - a) / (b - a), 0.0, 1.0)

    @jax.jit
    def draw(key):
        node = jax.random.fold_in(key, 0xB0B)
        wa = jnp.zeros(shape, dtype)
        wb = jax.random.normal(node, shape, dtype) * jnp.sqrt(
            jnp.asarray(t1 - t0, dtype))
        for std, left in levels:
            wm = 0.5 * (wa + wb) + std * jax.random.normal(
                jax.random.fold_in(node, 1), shape, dtype)
            wa, wb = (wa, wm) if left else (wm, wb)
            node = jax.random.fold_in(node, 2 if left else 3)
        return wa + frac * (wb - wa)

    return draw(key)


def test_levy_none_mode_bitwise_unchanged():
    """``levy_area=None`` draws are the installed ``jax.random`` draws of
    the documented key schedule, bitwise: grid increments are
    ``normal(fold_in(key, n)) · sqrt(dt)``; bridge values and VBT
    increments are the plain sequential descent (within the two ulps an
    FMA may move them); dense paths are
    ``normal(split(key, N)[i]) · sqrt(dt)`` per fine cell, summed over
    coarse steps and linearly interpolated inside a cell.  A changed
    key-derivation chain, draw order or interpolation fails here."""
    jax.config.update("jax_enable_x64", True)
    try:
        f64 = jnp.float64
        key = jax.random.PRNGKey(1234)
        bm = BrownianPath(key, 0.0, 1.0, (3,), f64)
        np.testing.assert_array_equal(
            np.asarray(bm.increment(jnp.int32(5), 16)),
            np.asarray(jax.random.normal(jax.random.fold_in(key, 5), (3,),
                                         f64) * jnp.sqrt(1.0 / 16)))
        for t in (0.2, 0.37, 0.9):
            np.testing.assert_array_max_ulp(
                np.asarray(bm.value(t)),
                np.asarray(_bridge_reference(key, t, 0.0, 1.0, (3,), f64, 24)),
                maxulp=2)
        np.testing.assert_array_equal(
            np.asarray(bm.evaluate(0.2, 0.9)),
            np.asarray(bm.value(0.9) - bm.value(0.2)))

        dkey = jax.random.PRNGKey(7)
        dp = DenseBrownianPath.sample(dkey, 0.0, 1.0, 32, (2,), f64)
        w = [jax.random.normal(k, (2,), f64) * jnp.sqrt(1.0 / 32)
             for k in jax.random.split(dkey, 32)]
        np.testing.assert_array_equal(np.asarray(dp.w), np.stack(w))
        np.testing.assert_array_equal(
            np.asarray(dp.increment(jnp.int32(3), 8)),
            np.asarray(((w[12] + w[13]) + w[14]) + w[15]))
        # 0.55 lies 0.6 of the way through fine cell 17
        pos = jnp.asarray(0.55, f64) * 32
        prefix = w[0]
        for wi in w[1:17]:
            prefix = prefix + wi
        np.testing.assert_array_equal(
            np.asarray(dp.value(0.55)),
            np.asarray(prefix + (pos - 17.0) * w[17]))

        vkey = jax.random.PRNGKey(99)
        vb = VirtualBrownianTree(vkey, 0.0, 1.0, (2,), tol=1e-3, dtype=f64)
        np.testing.assert_array_max_ulp(
            np.asarray(vb.evaluate(0.25, 0.8)),
            np.asarray(_bridge_reference(vkey, 0.8, 0.0, 1.0, (2,), f64, 10)
                       - _bridge_reference(vkey, 0.25, 0.0, 1.0, (2,), f64,
                                           10)),
            maxulp=2)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_levy_mode_rejected_eagerly():
    with pytest.raises(ValueError, match="levy_area"):
        BrownianPath(jax.random.PRNGKey(0), 0.0, 1.0, (2,),
                     levy_area="space-time-time")
    with pytest.raises(ValueError, match="levy_area"):
        BrownianInterval(0.0, 1.0, (2,), levy_area="full")
    # Dense: hh and the mode must travel together
    with pytest.raises(ValueError, match="hh"):
        DenseBrownianPath(jnp.zeros((4, 2)), t0=0.0, t1=1.0,
                          levy_area="space-time")


# -----------------------------------------------------------------------------
# (W, H) contract
# -----------------------------------------------------------------------------


def test_wh_value_evaluate_contract_bitwise_w():
    """W component of ``evaluate(s, t)`` == ``value(t) - value(s)`` bitwise,
    including non-dyadic query points; ``value(t0) == (0, 0)``."""
    jax.config.update("jax_enable_x64", True)
    try:
        for path in (
            BrownianPath(jax.random.PRNGKey(3), 0.0, 1.0, (4,), jnp.float64,
                         levy_area="space-time"),
            DenseBrownianPath.sample(jax.random.PRNGKey(4), 0.0, 1.0, 64,
                                     (4,), jnp.float64,
                                     levy_area="space-time"),
            VirtualBrownianTree(jax.random.PRNGKey(5), 0.0, 1.0, (4,),
                                tol=1e-4, dtype=jnp.float64,
                                levy_area="space-time"),
        ):
            w0, h0 = path.value(0.0)
            np.testing.assert_array_equal(np.asarray(w0), np.zeros(4))
            np.testing.assert_array_equal(np.asarray(h0), np.zeros(4))
            for s, t in ((0.0, 0.3), (0.21, 0.77), (0.5, 1.0),
                         (0.137, 0.1371)):
                dw, dh = path.evaluate(s, t)
                vs, vt = path.value(s), path.value(t)
                np.testing.assert_array_equal(np.asarray(dw),
                                              np.asarray(vt[0] - vs[0]))
                assert np.all(np.isfinite(np.asarray(dh)))
    finally:
        jax.config.update("jax_enable_x64", False)


def test_wh_contract_under_jit_vmap():
    """The bitwise W contract survives ``jit(vmap(...))`` — the form the
    adaptive driver's left-endpoint carry actually runs in."""
    jax.config.update("jax_enable_x64", True)
    try:
        bm = BrownianPath(jax.random.PRNGKey(11), 0.0, 1.0, (3,),
                          jnp.float64, levy_area="space-time")
        ss = jnp.asarray([0.1, 0.23, 0.4], jnp.float64)
        ts = jnp.asarray([0.35, 0.81, 0.93], jnp.float64)

        ev = jax.jit(jax.vmap(lambda s, t: bm.evaluate(s, t)))
        vd = jax.jit(jax.vmap(
            lambda s, t: stlevy_difference(bm.value(s), bm.value(t),
                                           s, t, bm.t0)))
        dw_e, dh_e = ev(ss, ts)
        dw_v, dh_v = vd(ss, ts)
        np.testing.assert_array_equal(np.asarray(dw_e), np.asarray(dw_v))
        np.testing.assert_array_equal(np.asarray(dh_e), np.asarray(dh_v))
    finally:
        jax.config.update("jax_enable_x64", False)


def test_wh_contract_under_bridge_depth_cap():
    """``bridge_depth`` caps keep both components' value/evaluate identity
    (the capped descent is a consistent path approximation, not a skew)."""
    jax.config.update("jax_enable_x64", True)
    try:
        bm = BrownianPath(jax.random.PRNGKey(13), 0.0, 1.0, (3,),
                          jnp.float64, levy_area="space-time")
        for depth in (6, 10):
            for s, t in ((0.2, 0.9), (0.31, 0.57)):
                dw, dh = bm.evaluate(s, t, depth=depth)
                ref = stlevy_difference(bm.value(s, depth=depth),
                                        bm.value(t, depth=depth),
                                        s, t, bm.t0)
                np.testing.assert_array_equal(np.asarray(dw),
                                              np.asarray(ref[0]))
                np.testing.assert_array_equal(np.asarray(dh),
                                              np.asarray(ref[1]))
    finally:
        jax.config.update("jax_enable_x64", False)


def test_wh_chen_combine_over_adjacent_intervals():
    """H combines over adjacent intervals by the chen rule
    ``H_{s,u} = (h₁H_{s,t} + h₂H_{t,u})/h + (h₂W_{s,t} - h₁W_{t,u})/(2h)``
    — exact by construction (H is derived from the additive running
    integral), so the tolerance is f64-roundoff-tight."""
    jax.config.update("jax_enable_x64", True)
    try:
        for path in (
            BrownianPath(jax.random.PRNGKey(17), 0.0, 1.0, (4,),
                         jnp.float64, levy_area="space-time"),
            DenseBrownianPath.sample(jax.random.PRNGKey(18), 0.0, 1.0, 64,
                                     (4,), jnp.float64,
                                     levy_area="space-time"),
        ):
            for s, t, u in ((0.1, 0.456, 0.83), (0.0, 0.25, 1.0),
                            (0.3, 0.31, 0.42)):
                w_st, h_st = (np.asarray(x) for x in path.evaluate(s, t))
                w_tu, h_tu = (np.asarray(x) for x in path.evaluate(t, u))
                w_su, h_su = (np.asarray(x) for x in path.evaluate(s, u))
                np.testing.assert_allclose(w_st + w_tu, w_su,
                                           rtol=1e-12, atol=1e-12)
                np.testing.assert_allclose(
                    _chen_h(w_st, h_st, w_tu, h_tu, t - s, u - t), h_su,
                    rtol=1e-9, atol=1e-12)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_dense_wh_shares_w_bitwise_with_none_mode():
    """Dense H-mode draws W from the same stream as None-mode — shared-path
    solver comparisons (the convergence frontier) rely on it."""
    k = jax.random.PRNGKey(21)
    plain = DenseBrownianPath.sample(k, 0.0, 1.0, 32, (3,))
    levy = DenseBrownianPath.sample(k, 0.0, 1.0, 32, (3,),
                                    levy_area="space-time")
    np.testing.assert_array_equal(np.asarray(plain.w), np.asarray(levy.w))
    for n, num in ((0, 8), (5, 16), (31, 32)):
        np.testing.assert_array_equal(
            np.asarray(plain.increment(jnp.int32(n), num)),
            np.asarray(levy.increment(jnp.int32(n), num)[0]))


# -----------------------------------------------------------------------------
# Moments (the dead-helpers satellite: the samplers behind the path API)
# -----------------------------------------------------------------------------


def test_path_level_h_moments():
    """Path-level increments: H ~ N(0, dt/12), independent of W."""
    bm = BrownianPath(jax.random.PRNGKey(0), 0.0, 1.0, (100_000,),
                      levy_area="space-time")
    w, h = bm.increment(jnp.int32(2), 8)
    dt = 1.0 / 8.0
    assert abs(float(jnp.var(h)) / (dt / 12.0) - 1.0) < 0.05
    assert abs(float(jnp.var(w)) / dt - 1.0) < 0.05
    assert abs(float(jnp.mean(w * h))) < 3.0 * dt / jnp.sqrt(12.0 * 100_000)


def test_bridged_h_moments():
    """After the Lévy-bridge descent (non-dyadic interval) the conditional
    pieces still recombine to the unconditional law: H ~ N(0, dt/12),
    uncorrelated with W."""
    bm = BrownianPath(jax.random.PRNGKey(1), 0.0, 1.0, (60_000,),
                      levy_area="space-time")
    w, h = bm.evaluate(0.21, 0.74)
    w, h = np.asarray(w), np.asarray(h)
    dt = 0.74 - 0.21
    assert abs(np.var(w) / dt - 1.0) < 0.05
    assert abs(np.var(h) / (dt / 12.0) - 1.0) < 0.05
    assert abs(np.corrcoef(w, h)[0, 1]) < 0.02


def test_space_time_levy_area_moments():
    w, h = space_time_levy_area(jax.random.PRNGKey(2), 0.25, (120_000,))
    assert abs(float(jnp.var(w)) / 0.25 - 1.0) < 0.05
    assert abs(float(jnp.var(h)) / (0.25 / 12.0) - 1.0) < 0.05


def test_davie_levy_area_lambda_antisymmetry():
    """``W̃ + W̃ᵀ == w⊗w`` exactly: the 0.5·w⊗w symmetric part doubles, the
    (H⊗W - W⊗H) part and antisymmetric λ cancel against their transposes.
    Also ``diag(W̃) = w²/2`` (λ has a zero diagonal)."""
    key = jax.random.PRNGKey(3)
    dt = 0.3
    w, h = space_time_levy_area(jax.random.fold_in(key, 0), dt, (64, 5))
    wt = davie_levy_area(jax.random.fold_in(key, 1), w, h, dt)
    assert wt.shape == (64, 5, 5)
    sym = np.asarray(wt + jnp.swapaxes(wt, -1, -2))
    outer = np.asarray(w)[..., :, None] * np.asarray(w)[..., None, :]
    np.testing.assert_allclose(sym, outer, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(jnp.diagonal(wt, axis1=-2, axis2=-1)),
        0.5 * np.asarray(w) ** 2, rtol=1e-5, atol=1e-6)
    # λ scale: off-diagonal variance is dt²/12 above the structured part
    lam = np.asarray(wt) - (0.5 * outer
                            + np.asarray(h)[..., :, None] * np.asarray(w)[..., None, :]
                            - np.asarray(w)[..., :, None] * np.asarray(h)[..., None, :])
    off = lam[..., ~np.eye(5, dtype=bool)]
    assert abs(np.var(off) / (dt ** 2 / 12.0) - 1.0) < 0.1


# -----------------------------------------------------------------------------
# Host-side Brownian Interval pairs
# -----------------------------------------------------------------------------


def test_interval_wh_chen_and_determinism():
    bi = BrownianInterval(0.0, 1.0, (4,), seed=7, levy_area="space-time")
    w_su, h_su = bi(0.1, 0.9)
    w_st, h_st = bi(0.1, 0.4)
    w_tu, h_tu = bi(0.4, 0.9)
    np.testing.assert_allclose(w_st + w_tu, w_su, rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(_chen_h(w_st, h_st, w_tu, h_tu, 0.3, 0.5),
                               h_su, rtol=1e-9, atol=1e-12)
    w2, h2 = bi(0.1, 0.9)  # replay through the grown tree
    np.testing.assert_array_equal(w_su, w2)
    np.testing.assert_array_equal(h_su, h2)


def test_interval_wh_moments_after_conditioning():
    """Sub-interval queries on a grown tree go through the general-split
    conditional (w, A) sampler; the recombined law must stay N(0, dt) ×
    N(0, dt/12) uncorrelated."""
    n = 40_000
    bi = BrownianInterval(0.0, 1.0, (n,), seed=3, levy_area="space-time",
                          cache_size=512)
    bi(0.13, 0.61)  # grow a non-dyadic tree first
    w, h = bi(0.25, 0.37)
    dt = 0.37 - 0.25
    assert abs(np.var(w) / dt - 1.0) < 0.06
    assert abs(np.var(h) / (dt / 12.0) - 1.0) < 0.06
    assert abs(np.corrcoef(w, h)[0, 1]) < 0.03
