"""Plain float32 building blocks shared by the configurations' references.

Written from the published equations in straightforward ``jax.numpy``: no
kernels, no batching tricks, nothing imported from the system under test.
Callers run them under ``jax.default_matmul_precision("highest")``.  Every
matrix product goes through a ``dot``: :data:`HIGHEST` (float32), or
:data:`THREE_PASS`, which computes each product from bfloat16 halves the
way a TPU does at matmul precision ``"high"`` (the correctness checks'
control: the step down from ``"highest"`` that would tempt a change).

The weight makers here are the benchmark's own: they draw each layer the
way the trainers initialise it (uniform in ``±scale``, zero bias) and lay
the pytree out as the program expects, so the same arrays feed the program
and the reference.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


# -- matrix products ---------------------------------------------------------


def _split(x):
    """``x = hi + lo + rest`` with ``hi`` and ``lo`` bfloat16 values."""
    hi = x.astype(jnp.bfloat16).astype(F32)
    lo = (x - hi).astype(jnp.bfloat16).astype(F32)
    return hi, lo


def HIGHEST(spec, a, b):
    return jnp.einsum(spec, a, b)


def THREE_PASS(spec, a, b):
    """bf16_3x: ``hi*hi + hi*lo + lo*hi``, each product exact in float32."""
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return (jnp.einsum(spec, a_lo, b_hi) + jnp.einsum(spec, a_hi, b_lo)
            + jnp.einsum(spec, a_hi, b_hi))


DOTS = {"highest": HIGHEST, "high": THREE_PASS}


# -- layers ------------------------------------------------------------------


def lipswish(x):
    """LipSwish, 0.909 x sigmoid(x): Lipschitz 1 (Chen et al. 2019)."""
    return 0.909 * x * jax.nn.sigmoid(x)


def linear(p, x, dot=HIGHEST):
    y = dot("...i,io->...o", x, p["w"])
    return y + p["b"] if "b" in p else y


def mlp(p, x, final=None, dot=HIGHEST):
    """LipSwish between layers, optional final activation."""
    layers = p["layers"]
    for layer in layers[:-1]:
        x = lipswish(linear(layer, x, dot))
    x = linear(layers[-1], x, dot)
    return x if final is None else final(x)


def tcat(t, z):
    """``(t, z)``: the time channel first, as both papers' fields take it."""
    tt = jnp.broadcast_to(jnp.asarray(t, z.dtype), z.shape[:-1] + (1,))
    return jnp.concatenate([tt, z], -1)


def gru_scan_reverse(p, xs, dot=HIGHEST):
    """A GRU run backwards in time over axis 0 of ``xs`` (T, B, d):
    ``out[t]`` has read ``xs[t:]``.  Gates ordered (reset, update, new)."""
    h0 = jnp.broadcast_to(p["h0"], xs.shape[1:-1] + p["h0"].shape)

    def cell(h, x):
        i_r, i_z, i_n = jnp.split(linear(p["wi"], x, dot), 3, -1)
        h_r, h_z, h_n = jnp.split(linear(p["wh"], h, dot), 3, -1)
        r = jax.nn.sigmoid(i_r + h_r)
        z = jax.nn.sigmoid(i_z + h_z)
        n = jnp.tanh(i_n + r * h_n)
        h = (1 - z) * n + z * h
        return h, h

    return jax.lax.scan(cell, h0, xs, reverse=True)[1]


# -- weights -----------------------------------------------------------------


def init_linear(key, fan_in, fan_out, scale=None, bias=True):
    kw, _ = jax.random.split(key)
    s = 1.0 / math.sqrt(fan_in) if scale is None else scale
    p = {"w": jax.random.uniform(kw, (fan_in, fan_out), F32, -s, s)}
    if bias:
        p["b"] = jnp.zeros((fan_out,), F32)
    return p


def init_mlp(key, sizes, box=False):
    """``box``: entries in ``±1/fan_in``, the Lipschitz box that weight
    clipping keeps (the discriminator's initialisation)."""
    keys = jax.random.split(key, len(sizes) - 1)
    return {"layers": [init_linear(k, a, b, scale=1.0 / a if box else None)
                       for k, a, b in zip(keys, sizes[:-1], sizes[1:])]}


def init_gru(key, d_in, hidden):
    k1, k2, _ = jax.random.split(key, 3)
    return {"wi": init_linear(k1, d_in, 3 * hidden),
            "wh": init_linear(k2, hidden, 3 * hidden, bias=False),
            "h0": jnp.zeros((hidden,), F32)}


# -- Brownian motion and the reversible Heun method --------------------------


def brownian_increment(key, n, shape, dt):
    """Step ``n`` of a uniform grid: ``N(0, dt)`` drawn under
    ``fold_in(key, n)`` (a counter-based path, exact on every replay)."""
    return jax.random.normal(jax.random.fold_in(key, n), shape, F32) * \
        jnp.sqrt(jnp.asarray(dt, F32))


def reversible_heun(drift, diffusion, apply_noise, z0, increments, t0, t1,
                    num_steps):
    """The reversible Heun method (Kidger et al. 2021, Algorithm 1) on a
    uniform grid; returns the trajectory ``(num_steps + 1, *z0.shape)``.

    ``increments(n)`` gives the noise (or control) increment of step ``n``
    (``n`` traced); ``apply_noise(sigma, dw)`` contracts the diffusion with
    it.  Step ``n`` starts at ``t0 + n * dt``."""
    dt = (t1 - t0) / num_steps
    h = jnp.asarray(dt, z0.dtype)  # the step in the state's precision

    def step(carry, n):
        z, zh, mu, sig = carry
        t = t0 + n * dt
        dw = increments(n).astype(z0.dtype)
        zh1 = 2.0 * z - zh + mu * h + apply_noise(sig, dw)
        mu1, sig1 = drift(t + dt, zh1), diffusion(t + dt, zh1)
        z1 = z + 0.5 * (mu + mu1) * h + apply_noise(0.5 * (sig + sig1), dw)
        return (z1, zh1, mu1, sig1), z1

    carry = (z0, z0, drift(t0, z0), diffusion(t0, z0))
    _, zs = jax.lax.scan(step, carry, jnp.arange(num_steps))
    return jnp.concatenate([z0[None], zs], 0)


def diagonal_noise(sig, dw):
    return sig * dw


def general_noise(sig, dw):
    return jnp.einsum("...ij,...j->...i", sig, dw)


# -- optimisers --------------------------------------------------------------


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"step": 0, "m": zeros, "v": zeros}


def adam_update(params, grads, state, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam (Kingma & Ba 2015) with bias correction."""
    step = state["step"] + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"], grads)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    new = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps),
        params, m, v)
    return new, {"step": step, "m": m, "v": v}


def adadelta_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"acc_g": zeros, "acc_d": zeros}


def adadelta_update(params, grads, state, lr=1.0, rho=0.9, eps=1e-6):
    """Adadelta (Zeiler 2012)."""
    acc_g = jax.tree.map(lambda a, g: rho * a + (1 - rho) * g * g,
                         state["acc_g"], grads)
    upd = jax.tree.map(
        lambda g, ag, ad: -lr * g * jnp.sqrt(ad + eps) / jnp.sqrt(ag + eps),
        grads, acc_g, state["acc_d"])
    acc_d = jax.tree.map(lambda a, u: rho * a + (1 - rho) * u * u,
                         state["acc_d"], upd)
    new = jax.tree.map(jnp.add, params, upd)
    return new, {"acc_g": acc_g, "acc_d": acc_d}


def normalise_initial(ys):
    """Zero mean and unit variance of the initial value (the paper's App. F
    normalisation)."""
    m = jnp.mean(ys[0])
    s = jnp.std(ys[0]) + 1e-6
    return (ys - m) / s
