"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematical definition, written with no regard for
memory movement — tests sweep shapes/dtypes and assert the kernels match.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..nn.core import lipswish
from . import prng


# -----------------------------------------------------------------------------
# reversible Heun fused state updates (diagonal noise)
# -----------------------------------------------------------------------------


def rev_heun_phase1(z, zh, mu, sigma, dw, dt, sign: float = 1.0):
    """ẑ_{n+1} = 2 z_n − ẑ_n + μ_n Δt + σ_n ΔW_n   (Algorithm 1, line 3).

    ``sign=-1.0`` is the algebraic inverse (Algorithm 2), matching the
    fused kernel's contract.  ``dt`` may be a Python float or a traced
    scalar (the adaptive driver's step size).
    """
    return 2.0 * z - zh + mu * (sign * dt) + (sign * sigma) * dw


def rev_heun_phase2(z, mu, mu1, sigma, sigma1, dw, dt, sign: float = 1.0):
    """z_{n+1} = z_n + ½(μ_n+μ_{n+1})Δt + ½(σ_n+σ_{n+1})ΔW_n."""
    return z + (sign * 0.5 * dt) * (mu + mu1) + (sign * 0.5) * (sigma + sigma1) * dw


# -----------------------------------------------------------------------------
# reversible Heun hand-derived backward (cotangent) phases
# -----------------------------------------------------------------------------
#
# The transpose of one Algorithm-1 step, factored around the single
# vector-field VJP exactly as DESIGN.md §3 derives it.  Op order is chosen
# so each output is BITWISE what ``jax.vjp`` of the unfused stepper
# produces (power-of-two scalings commute with IEEE rounding; two-term sums
# keep the transpose's grouping) — tests/test_adjoint.py pins fused ≡
# unfused gradients to 0.0 in f64 on the strength of this.


def rev_heun_bwd_phase1(g_z1, g_mu1, g_sig1, dw, dt):
    """Pre-field cotangents: seed the vector-field VJP.

    ``c_mu1 = ḡ_mu1 + ½Δt·ḡ_z1`` and ``c_sig1 = ḡ_sig1 + ½ΔW·ḡ_z1`` —
    the phase-2 (z₁) transpose contributions joined with the direct
    output cotangents of μ₁/σ₁.
    """
    c_mu1 = g_mu1 + 0.5 * (g_z1 * dt)
    c_sig1 = g_sig1 + 0.5 * (g_z1 * dw)
    return c_mu1, c_sig1


def rev_heun_bwd_phase2(g_z1, ghat, dw, dt):
    """Post-field cotangents: distribute ``ĝ`` (the total ẑ₁ cotangent,
    i.e. ``ḡ_zh1`` + the field VJP's ẑ₁ contribution) onto the step-``n``
    state.  Returns ``(d_z, d_zh, d_mu, d_sigma)``.
    """
    d_z = g_z1 + 2.0 * ghat
    d_zh = -ghat
    d_mu = 0.5 * (g_z1 * dt) + ghat * dt
    d_sigma = 0.5 * (g_z1 * dw) + ghat * dw
    return d_z, d_zh, d_mu, d_sigma


# -----------------------------------------------------------------------------
# counter-based Brownian generation (bitwise jax.random / BrownianPath)
# -----------------------------------------------------------------------------
#
# Each oracle is split the way the kernel is: scalar work (key folding,
# the bridge descent) runs in XLA, and a ``*_block`` function computes one
# 2-D block of the draw from those scalars and the flat index ``start`` of
# the block's first element.  The kernel bodies call the same ``*_block``
# functions on values loaded from their refs, so kernel and oracle are one
# op sequence (tests/test_kernel_parity.py pins them bitwise).


def increment_block(f1, f2, block_shape, dtype, scale, start=0):
    """Elements ``start ..`` of a grid increment: normals under the folded
    key ``(f1, f2)`` times ``scale = sqrt(dt)``."""
    return prng.normal_block(f1, f2, block_shape, dtype, start) * scale


def brownian_increment(k1, k2, n, shape, dtype, dt):
    """Step-``n`` increment of a ``num_steps`` uniform grid — bitwise
    ``BrownianPath.increment(n, num_steps)`` with ``dt = span/num_steps``.

    ``k1, k2``: the path key's raw uint32 scalars (``prng.key_data_pair``).
    """
    dtype = jnp.dtype(dtype)
    f1, f2 = prng.fold_in(k1, k2, n)
    scale = jnp.sqrt(jnp.asarray(dt, dtype))
    return increment_block(f1, f2, prng.as_rows(shape), dtype,
                           scale).reshape(tuple(shape))


def bridge_descent(k1, k2, t, t0, t1, dtype, depth: int = 24):
    """The scalar half of ``BrownianPath.value(t)``: everything in the
    Lévy-bridge descent that depends on ``t`` alone.

    Returns ``(keys, floats, gos)``: ``keys`` is uint32 ``(2 + 2·depth,)``
    — the root key, then each level's midpoint key; ``floats`` is
    ``(2 + depth,)`` in ``dtype`` — ``sqrt(t1 - t0)``, the in-cell fraction
    of ``t`` at the depth bound, then each level's bridge std; ``gos`` is
    int32 ``(depth,)``, 1 where the descent went left.  These are the SMEM
    operands of the ``brownian_value`` kernel.
    """
    dtype = jnp.dtype(dtype)
    t = jnp.asarray(t, dtype)
    r1, r2 = prng.fold_in(k1, k2, jnp.uint32(0xB0B))

    def body(c, _):
        a, b, c1, c2 = c
        m = 0.5 * (a + b)
        std = jnp.sqrt(jnp.asarray((b - m) * (m - a) / (b - a), dtype))
        go_left = t <= m
        f1, f2 = prng.fold_in(c1, c2, jnp.uint32(1))
        n1, n2 = prng.fold_in(
            c1, c2, jnp.where(go_left, jnp.uint32(2), jnp.uint32(3)))
        a2 = jnp.where(go_left, a, m)
        b2 = jnp.where(go_left, m, b)
        return (a2, b2, n1, n2), (std, go_left.astype(jnp.int32), f1, f2)

    (a, b, _, _), (stds, gos, km1s, km2s) = lax.scan(
        body, (jnp.asarray(t0, dtype), jnp.asarray(t1, dtype), r1, r2),
        None, length=depth)
    frac = jnp.clip((t - a) / jnp.maximum(b - a, jnp.finfo(dtype).tiny),
                    0.0, 1.0)
    keys = jnp.concatenate([jnp.stack([r1, r2]),
                            jnp.stack([km1s, km2s], 1).reshape(-1)])
    floats = jnp.concatenate([
        jnp.stack([jnp.sqrt(jnp.asarray(t1 - t0, dtype)), frac]), stds])
    return keys, floats, gos


def bridge_block(keys, floats, gos, block_shape, dtype, depth: int,
                 start=0):
    """Elements ``start ..`` of ``W(t) − W(t0)`` from
    :func:`bridge_descent`'s scalars: the root draw, one midpoint draw per
    level, and the elementwise combine.  ``keys``/``floats``/``gos`` may be
    arrays or kernel refs — both index the same way."""
    w_t1 = prng.normal_block(keys[0], keys[1], block_shape, dtype,
                             start) * floats[0]

    def body(i, c):
        wa, wb = c
        zm = prng.normal_block(keys[2 + 2 * i], keys[3 + 2 * i], block_shape,
                               dtype, start)
        wm = 0.5 * (wa + wb) + floats[2 + i] * zm
        go_left = gos[i] != 0
        return (jnp.where(go_left, wa, wm), jnp.where(go_left, wm, wb))

    wa, wb = lax.fori_loop(0, depth, body,
                           (jnp.zeros(block_shape, dtype), w_t1))
    return wa + floats[1] * (wb - wa)


def brownian_value(k1, k2, t, t0, t1, shape, dtype, depth: int = 24):
    """``W(t) − W(t0)`` by Lévy-bridge descent — bitwise
    ``BrownianPath.value(t, depth)``.

    The interval sequence, per-level bridge stds and midpoint keys depend
    only on ``t`` (:func:`bridge_descent`, scalar work); the full-shape
    part is one draw per level and an elementwise combine
    (:func:`bridge_block`).
    """
    dtype = jnp.dtype(dtype)
    # the barrier hands the combine opaque scalars, as the kernel's SMEM
    # operands are: XLA may not fold the descent into the combine's FMAs
    keys, floats, gos = lax.optimization_barrier(
        bridge_descent(k1, k2, t, t0, t1, dtype, depth))
    return bridge_block(keys, floats, gos, prng.as_rows(shape), dtype,
                        depth).reshape(tuple(shape))


# -----------------------------------------------------------------------------
# fused vector-field MLP (Linear → LipSwish → Linear)
# -----------------------------------------------------------------------------


def fused_mlp(x, w1, b1, w2, b2):
    h = lipswish(x @ w1 + b1)
    return h @ w2 + b2


# -----------------------------------------------------------------------------
# causal GQA flash attention
# -----------------------------------------------------------------------------


def flash_attention(q, k, v, causal: bool = True, scale: float | None = None):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D); Hq % Hkv == 0."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv)


# -----------------------------------------------------------------------------
# Mamba2 SSD chunk scan
# -----------------------------------------------------------------------------


def ssd_scan(x, a, b, c):
    """Naive sequential SSD recurrence (the definition).

    x: (B, H, S, P) inputs, a: (B, H, S) log-decay (<= 0),
    b, c: (B, H, S, N) input/output projections.
    h_t = exp(a_t)·h_{t-1} + b_t ⊗ x_t ;  y_t = cᵀ_t h_t.  Returns (B,H,S,P).
    """
    Bb, H, S, P = x.shape
    N = b.shape[-1]

    def per_head(xh, ah, bh, ch):
        def step(h, inp):
            xt, at, bt, ct = inp
            h = jnp.exp(at) * h + bt[:, None] * xt[None, :]
            return h, ct @ h

        h0 = jnp.zeros((N, P), jnp.float32)
        _, ys = jax.lax.scan(step, h0, (xh.astype(jnp.float32), ah.astype(jnp.float32),
                                        bh.astype(jnp.float32), ch.astype(jnp.float32)))
        return ys.astype(x.dtype)

    f = jax.vmap(jax.vmap(per_head))
    return f(x, a, b, c)


# -----------------------------------------------------------------------------
# fused softmax cross entropy
# -----------------------------------------------------------------------------


def fused_xent(logits, labels):
    """Per-token next-token cross entropy; logsumexp in f32.
    logits: (..., V); labels: (...) int32 -> (...) f32."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return lse - ll
