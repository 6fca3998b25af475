"""Plain reference for ``latent_sde_air``: the Latent SDE (Li et al. 2020)
in the structure of Kidger et al. 2021, App. B, trained on air-quality-shaped
series by its ELBO with Adam.

Forward in float32 by the reversible Heun method, gradients by ordinary
automatic differentiation through that forward (discretise-then-optimise,
which the exact reversible adjoint must equal to rounding).  At solver step
``n`` the posterior drift reads the encoder's context row ``n // stride``,
found from the grid index nearest its time, not by truncating a float that
rounding may leave an ulp short.  Nothing here comes from the system under
test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import plain as P


def dims(config: dict) -> dict:
    return config["model"]


def init(key, config: dict):
    """Weights in the program's layout (the ``LatentSDEConfig`` trainers')."""
    d = dims(config)
    x, c, v, y = d["hidden_dim"], d["context_dim"], d["initial_noise_dim"], \
        d["data_dim"]
    hid = [d["width"]] * d["depth"]
    kz, km, ks, kl, ke, kn, kq = jax.random.split(key, 7)
    return {
        "zeta": P.init_mlp(kz, [v] + hid + [x]),
        "mu": P.init_mlp(km, [1 + x] + hid + [x]),
        "sigma": P.init_mlp(ks, [1 + x] + hid + [x]),
        "ell": P.init_linear(kl, x, y),
        "enc": P.init_gru(ke, y, c),
        "nu": P.init_mlp(kn, [1 + x + c] + hid + [x]),
        "qz0": P.init_mlp(kq, [c] + hid + [2 * v]),
    }


def data(key, batch: int, length: int, num_labels: int = 12):
    """Bivariate daily profiles (a PM2.5-like channel and an O3-like one
    peaking in the latter half), ``(length, batch, 2)``, normalised."""
    kl, kp, ko, _ = jax.random.split(key, 4)
    labels = jax.random.randint(kl, (batch,), 0, num_labels)
    ts = jnp.linspace(0.0, 1.0, length, dtype=P.F32)[:, None, None]
    base = (labels.astype(P.F32) / num_labels)[None, :, None]
    pm = base + 0.3 * jnp.sin(2 * jnp.pi * (ts + 0.2 * base)) \
        + 0.15 * jax.random.normal(kp, (length, batch, 1), P.F32)
    peak_t = 0.55 + 0.25 * base
    o3 = 0.8 * jnp.exp(-((ts - peak_t) ** 2) / 0.02) + base * 0.2 \
        + 0.1 * jax.random.normal(ko, (length, batch, 1), P.F32)
    return P.normalise_initial(jnp.concatenate([pm, o3], -1))


def sigma(nets, t, x, dot):
    """The diagonal diffusion, bounded in (0.05, 0.55)."""
    return jax.nn.sigmoid(P.mlp(nets["sigma"], P.tcat(t, x), dot=dot)) * 0.5 + 0.05


def loss(params, config: dict, key, ys, keep: int, dot=P.HIGHEST):
    """Negative ELBO: reconstruction at the observation times, the initial
    value's error, and the KL of posterior to prior (the path integral
    carried as an extra state channel).  Batch means run over the first
    ``keep`` rows (all of them unless a fault is planted)."""
    d = dims(config)
    X, t1, N = d["hidden_dim"], d["t1"], d["num_steps"]
    T = ys.shape[0] - 1
    B = ys.shape[1]
    stride = N // T
    kz0, kw = jax.random.split(key)

    ctx = P.gru_scan_reverse(params["enc"], ys, dot)
    m, log_s = jnp.split(P.mlp(params["qz0"], ctx[0], dot=dot), 2, -1)
    s = jnp.exp(jnp.clip(log_s, -8, 4))
    v = m + s * jax.random.normal(kz0, m.shape, P.F32)
    kl_v = 0.5 * jnp.sum(m**2 + s**2 - 2.0 * jnp.log(s) - 1.0, -1)
    x0 = P.mlp(params["zeta"], v, dot=dot)

    def ctx_at(t):
        # the reversible Heun method evaluates the fields on the grid only
        n = jnp.round(jnp.asarray(t, P.F32) / t1 * N).astype(jnp.int32)
        return jax.lax.dynamic_index_in_dim(ctx, jnp.clip(n // stride, 0, T),
                                            0, keepdims=False)

    def drift(t, u):
        x = u[..., :X]
        nu = P.mlp(params["nu"], jnp.concatenate([P.tcat(t, x), ctx_at(t)], -1),
                   jnp.tanh, dot)
        mu = P.mlp(params["mu"], P.tcat(t, x), jnp.tanh, dot)
        r = (mu - nu) / sigma(params, t, x, dot)
        return jnp.concatenate([nu, 0.5 * jnp.sum(r * r, -1, keepdims=True)], -1)

    def diffusion(t, u):
        sig = sigma(params, t, u[..., :X], dot)
        return jnp.concatenate([sig, jnp.zeros(sig.shape[:-1] + (1,), P.F32)], -1)

    u0 = jnp.concatenate([x0, jnp.zeros((B, 1), P.F32)], -1)
    traj = P.reversible_heun(
        drift, diffusion, P.diagonal_noise, u0,
        lambda n: P.brownian_increment(kw, n, (B, X + 1), t1 / N), 0.0, t1, N)

    rows = slice(0, keep)
    y_hat = P.linear(params["ell"], traj[::stride, rows, :X], dot)
    y_obs = ys[:, rows]
    recon = jnp.sum(jnp.mean((y_hat - y_obs) ** 2, axis=(1, 2))) * (t1 / T)
    recon0 = jnp.mean(jnp.sum((y_hat[0] - y_obs[0]) ** 2, -1))
    kl = jnp.mean(traj[-1, rows, -1] + kl_v[rows])
    return recon + recon0 + d["kl_weight"] * kl


def train(params, config: dict, traffic: dict, keys, keep: int,
          dot=P.HIGHEST):
    """The first ``len(keys)`` steps of the ELBO trainer from ``params``,
    every matrix product by ``dot``.

    Returns the loss of each step, the first step's gradient, and the
    parameters after the last step."""
    batch, seq_len = traffic["batch"], traffic["seq_len"]
    lr = config["optimiser"]["lr"]
    opt = P.adam_init(params)
    losses, first = [], None
    for k in keys:
        ys = data(jax.random.fold_in(k, 0), batch, seq_len)
        value, grads = jax.value_and_grad(loss)(
            params, config, jax.random.fold_in(k, 1), ys, keep, dot)
        losses.append([value])
        first = grads if first is None else first
        params, opt = P.adam_update(params, grads, opt, lr)
    return {"losses": losses, "grads": first, "params": params}
