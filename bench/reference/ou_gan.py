"""Plain reference for ``ou_gan``: the SDE-GAN of Kidger et al. 2021 (§5,
the torchsde ``examples/sde_gan.py`` shape) on Ornstein-Uhlenbeck data.

The generator is a Neural SDE with general noise; the discriminator a
Neural CDE driven by the time-augmented path.  Fake paths are scored by
one joint solve of generator and discriminator, real paths by a CDE solve
over their piecewise-linear interpolation.  Both players step with
Adadelta; the discriminator's fields are clipped to the Lipschitz box
after its update.  Trajectory serving rolls the generator out chunk by
chunk, each row keyed by its request's seed and its index.  Nothing here
comes from the system under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import plain as P

#: The server's published keying: row ``j`` of a request with seed ``s``
#: draws its initial noise under ``fold_in(PRNGKey(s), j)`` and chunk
#: ``c``'s Brownian path under ``fold_in(that key, 1000 + c)``.
CHUNK_FOLD = 1000


def dims(config: dict) -> dict:
    return config["model"]


def init(key, config: dict):
    """Generator and discriminator weights in the program's layout."""
    d = dims(config)
    X, W, V, Y = d["hidden_dim"], d["noise_dim"], d["initial_noise_dim"], \
        d["data_dim"]
    H = d["disc_hidden_dim"]
    hid = [d["width"]] * d["depth"]
    dhid = [d["disc_width"]] * d["disc_depth"]
    kz, km, ks, kl = jax.random.split(key, 4)
    gen = {
        "zeta": P.init_mlp(kz, [V] + hid + [X]),
        "mu": P.init_mlp(km, [1 + X] + hid + [X]),
        "sigma": P.init_mlp(ks, [1 + X] + hid + [X * W]),
        "ell": P.init_linear(kl, X, Y),
    }
    kx, kf, kg, kr = jax.random.split(jax.random.fold_in(key, 1), 4)
    disc = {
        "xi": P.init_mlp(kx, [1 + Y] + dhid + [H], box=True),
        "f": P.init_mlp(kf, [1 + H] + dhid + [H], box=True),
        "g": P.init_mlp(kg, [1 + H] + dhid + [H * (1 + Y)], box=True),
        "m": P.init_linear(kr, H, 1),
    }
    return {"gen": gen, "disc": disc}


def ou_data(key, batch: int, length: int, rho=0.02, kappa=0.1, chi=0.4):
    """Kidger et al. 2021, App. F.7: dY = (rho t - kappa Y) dt + chi dW on
    t = 0, 1, ..., length - 1; ``(length, batch, 1)``, normalised."""
    k0, key = jax.random.split(key)
    y0 = jax.random.normal(k0, (batch, 1), P.F32)
    eps = jax.random.normal(key, (length - 1, batch, 1), P.F32)

    def body(y, inp):
        t, e = inp
        y1 = y + (rho * t - kappa * y) + chi * e
        return y1, y1

    ts = jnp.arange(length, dtype=P.F32)
    _, ys = jax.lax.scan(body, y0, (ts[:-1], eps))
    return P.normalise_initial(jnp.concatenate([y0[None], ys], 0))


def _gen_fields(gen, d, dot):
    X, W = d["hidden_dim"], d["noise_dim"]

    def mu(t, x):
        return P.mlp(gen["mu"], P.tcat(t, x), jnp.tanh, dot)

    def sigma(t, x):
        return P.mlp(gen["sigma"], P.tcat(t, x), jnp.tanh, dot).reshape(
            x.shape[:-1] + (X, W))

    return mu, sigma


def _disc_fields(disc, d, dot):
    H, Y = d["disc_hidden_dim"], d["data_dim"]

    def f(t, h):
        return P.mlp(disc["f"], P.tcat(t, h), jnp.tanh, dot)

    def g(t, h):
        return P.mlp(disc["g"], P.tcat(t, h), jnp.tanh, dot).reshape(
            h.shape[:-1] + (H, 1 + Y))

    return f, g


def scores(params, config: dict, key, y_real, dot=P.HIGHEST):
    """Discriminator scores of ``B`` generated paths (one joint solve) and
    of the real paths ``y_real`` (T+1, B, Y)."""
    d = dims(config)
    X, W, t1, N = d["hidden_dim"], d["noise_dim"], d["t1"], d["num_steps"]
    gen, disc = params["gen"], params["disc"]
    B = y_real.shape[1]
    mu, sigma = _gen_fields(gen, d, dot)
    f, g = _disc_fields(disc, d, dot)
    wl = gen["ell"]["w"]

    kv, kw = jax.random.split(key)
    x0 = P.mlp(gen["zeta"], jax.random.normal(kv, (B, d["initial_noise_dim"]),
                                              P.F32), dot=dot)
    h0 = P.mlp(disc["xi"], P.tcat(0.0, P.linear(gen["ell"], x0, dot)), dot=dot)

    def drift(t, u):
        x, h = u[..., :X], u[..., X:]
        m = mu(t, x)
        dy = jnp.concatenate([jnp.ones(m.shape[:-1] + (1,), P.F32),
                              dot("...x,xy->...y", m, wl)], -1)
        return jnp.concatenate(
            [m, f(t, h) + dot("...hy,...y->...h", g(t, h), dy)], -1)

    def diffusion(t, u):
        x, h = u[..., :X], u[..., X:]
        s = sigma(t, x)
        # dY = ell'(X) dX: the noise into h is g[:, 1:] (W_ell^T sigma)
        gh = dot("...hy,...yw->...hw", g(t, h)[..., 1:],
                 dot("xy,...xw->...yw", wl, s))
        return jnp.concatenate([s, gh], -2)

    traj = P.reversible_heun(
        drift, diffusion, P.general_noise, jnp.concatenate([x0, h0], -1),
        lambda n: P.brownian_increment(kw, n, (B, W), t1 / N), 0.0, t1, N)
    fake = P.linear(disc["m"], traj[-1][..., X:], dot)[..., 0]

    T = y_real.shape[0] - 1
    ts = jnp.linspace(0.0, t1, T + 1, dtype=P.F32)
    control = jnp.concatenate(
        [jnp.broadcast_to(ts[:, None, None], y_real.shape[:-1] + (1,)), y_real],
        -1)
    hr = P.reversible_heun(
        f, g, P.general_noise,
        P.mlp(disc["xi"], P.tcat(ts[0], y_real[0]), dot=dot),
        lambda n: jax.lax.dynamic_index_in_dim(control, n + 1, 0, False)
        - jax.lax.dynamic_index_in_dim(control, n, 0, False),
        0.0, t1, T)
    real = P.linear(disc["m"], hr[-1], dot)[..., 0]
    return fake, real


def clip_lipschitz(disc):
    """Careful clipping: every weight of the discriminator's vector fields
    and initial network into ``±1/fan_in``; the readout is free."""
    out = dict(disc)
    for name in ("xi", "f", "g"):
        out[name] = {"layers": [
            {**layer, "w": jnp.clip(layer["w"], -1.0 / layer["w"].shape[0],
                                    1.0 / layer["w"].shape[0])}
            for layer in disc[name]["layers"]]}
    return out


def train(params, config: dict, traffic: dict, keys, keep: int,
          dot=P.HIGHEST):
    """The first ``len(keys)`` Wasserstein-GAN steps from ``params``:
    generator loss ``-mean(fake)``, discriminator loss ``mean(fake) -
    mean(real)``, means over the first ``keep`` rows; every matrix product
    by ``dot``."""
    batch, seq_len = traffic["batch"], traffic["seq_len"]
    lr = config["optimiser"]["lr"]
    g_opt = P.adadelta_init(params["gen"])
    d_opt = P.adadelta_init(params["disc"])
    rows = slice(0, keep)

    def losses_of(gen, disc, k, y_real):
        fake, real = scores({"gen": gen, "disc": disc}, config, k, y_real, dot)
        gl = -jnp.mean(fake[rows])
        return gl, jnp.mean(fake[rows]) - jnp.mean(real[rows])

    losses, first = [], None
    for k in keys:
        y_real = ou_data(jax.random.fold_in(k, 0), batch, seq_len)
        kf = jax.random.fold_in(k, 1)
        gl, gg = jax.value_and_grad(
            lambda gen: losses_of(gen, params["disc"], kf, y_real)[0])(
                params["gen"])
        dl, dg = jax.value_and_grad(
            lambda disc: losses_of(params["gen"], disc, kf, y_real)[1])(
                params["disc"])
        losses.append([gl, dl])
        grads = {"gen": gg, "disc": dg}
        first = grads if first is None else first
        disc, d_opt = P.adadelta_update(params["disc"], dg, d_opt, lr)
        gen, g_opt = P.adadelta_update(params["gen"], gg, g_opt, lr)
        params = {"gen": gen, "disc": clip_lipschitz(disc)}
    return {"losses": losses, "grads": first, "params": params}


def rollout(gen, seed, j, config: dict, chunks: int, dot=P.HIGHEST):
    """Row ``j`` of a request with seed ``seed``, served in ``chunks``
    chunks: ``(num_steps + 1, data_dim)`` on the solver grid."""
    d = dims(config)
    t1, N, W = d["t1"], d["num_steps"], d["noise_dim"]
    span, steps = t1 / chunks, N // chunks
    mu, sigma = _gen_fields(gen, d, dot)
    base = jax.random.fold_in(jax.random.PRNGKey(seed), j)
    kv, _ = jax.random.split(base)
    x = P.mlp(gen["zeta"], jax.random.normal(kv, (d["initial_noise_dim"],),
                                              P.F32), dot=dot)
    pieces = []
    for c in range(chunks):
        kc = jax.random.fold_in(base, CHUNK_FOLD + c)
        t0 = jnp.asarray(c * span, P.F32)
        traj = P.reversible_heun(
            mu, sigma, P.general_noise, x,
            lambda n, kc=kc: P.brownian_increment(kc, n, (W,), span / steps),
            t0, t0 + span, steps)
        pieces.append(P.linear(gen["ell"], traj if c == 0 else traj[1:], dot))
        x = traj[-1]
    return jnp.concatenate(pieces, 0)
