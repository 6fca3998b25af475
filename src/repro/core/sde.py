"""Neural SDE models (paper §2): generator, SDE-GAN, Latent SDE.

Follows the paper's "certain minimal amount of structure" (eq. (1)):

    X_0 = ζ_θ(V),   dX_t = μ_θ(t, X_t) dt + σ_θ(t, X_t) ∘ dW_t,   Y_t = ℓ_θ(X_t)

with ζ_θ, μ_θ, σ_θ MLPs and ℓ_θ affine.  The SDE-GAN discriminator is the
Neural CDE of eq. (2); generator+discriminator are solved as a *single* joint
SDE so the Wasserstein loss is a function of the terminal state and the
reversible-Heun exact adjoint applies end-to-end (paper §2.4: "the loss is an
integral ... computed as part of Z in a single SDE solve").
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..kernels.ops import vmap_rows
from ..nn.core import tcat as _tcat  # the shared time-augmentation convention
from .brownian import BrownianPath
from .paths import LinearPathControl
from .solve import get_solver, solve


@dataclasses.dataclass(frozen=True)
class NeuralSDEConfig:
    data_dim: int = 1          # y
    hidden_dim: int = 16       # x
    noise_dim: int = 4         # w
    initial_noise_dim: int = 4  # v
    width: int = 32
    depth: int = 1
    disc_hidden_dim: int = 16  # h (discriminator CDE state)
    disc_width: int = 32
    disc_depth: int = 1
    num_steps: int = 32
    t1: float = 1.0
    solver: str = "reversible_heun"
    exact_adjoint: bool = True
    gradient_mode: Optional[str] = None  # explicit backend; None = derive
    precision: str = "highest"  # field-eval compute policy (solve stack)
    use_pallas_kernels: bool = False  # fused reversible-Heun hot loop
    dtype: object = jnp.float32


def _cfg_solve(cfg, drift, diffusion, params, z0, bm, num_steps, noise,
               gradient_mode=None, solver=None, save_trajectory=True):
    """All SDE-GAN / Latent-SDE solves go through the unified front-end.

    ``gradient_mode``/``solver`` default to the config's derivation
    (``cfg.gradient_mode`` when set, else exact reversible adjoint when
    configured, discretise otherwise); explicit values let the Latent-SDE
    backsolve baseline request ``"continuous_adjoint"`` without a second
    dispatch path.  Terminal-only backends (``"continuous_adjoint"``,
    ``"checkpoint"``) configured via ``cfg.gradient_mode`` pair with the
    terminal-form objectives (:func:`latent_sde_loss_terminal`);
    trajectory-consuming entry points surface the registry's eager named
    error rather than silently falling back.

    ``cfg.precision`` rides along unconditionally — the policy wraps the
    vector fields inside :func:`repro.core.solve.solve`, so it composes
    with every backend.

    ``use_pallas_kernels`` only applies where the fused kernels are legal:
    diagonal noise under the exact adjoint (see the registry validation in
    repro.core.solve) — e.g. the Latent SDE's posterior solve.  General
    (matrix) noise falls back to the unfused path with a warning.
    """
    solver = cfg.solver if solver is None else solver
    # (W, H)-consuming solvers (srk): rebuild the path in space-time mode so
    # cfg.solver="srk" works on every diagonal-noise config path without each
    # call site knowing about Lévy areas.  General-noise solves fall through
    # to the registry's eager noise_types error.
    if (get_solver(solver).needs_levy_area and isinstance(bm, BrownianPath)
            and bm.levy_area is None):
        bm = dataclasses.replace(bm, levy_area="space-time")
    if gradient_mode is None:
        gradient_mode = getattr(cfg, "gradient_mode", None)
    if gradient_mode is None:
        exact = cfg.exact_adjoint and solver == "reversible_heun"
        gradient_mode = "reversible_adjoint" if exact else "discretise"
    wants_fuse = getattr(cfg, "use_pallas_kernels", False)
    fuse = (wants_fuse and noise == "diagonal"
            and gradient_mode == "reversible_adjoint")
    if wants_fuse and not fuse:
        import warnings

        warnings.warn(
            f"use_pallas_kernels requested but this solve cannot fuse "
            f"(noise={noise!r}, gradient_mode={gradient_mode!r}) — running "
            f"unfused",
            stacklevel=3)
    return solve(drift, diffusion, params, z0, bm, 0.0, cfg.t1, num_steps,
                 solver=solver, gradient_mode=gradient_mode, noise=noise,
                 save_trajectory=save_trajectory, use_pallas_kernels=fuse,
                 precision=getattr(cfg, "precision", "highest"))


# =============================================================================
# Generator
# =============================================================================


def generator_init(key, cfg: NeuralSDEConfig):
    kz, km, ks, kl = jax.random.split(key, 4)
    hid = [cfg.width] * cfg.depth
    d = cfg.dtype
    return {
        "zeta": nn.mlp_init(kz, [cfg.initial_noise_dim] + hid + [cfg.hidden_dim], dtype=d),
        "mu": nn.mlp_init(km, [1 + cfg.hidden_dim] + hid + [cfg.hidden_dim], dtype=d),
        "sigma": nn.mlp_init(ks, [1 + cfg.hidden_dim] + hid + [cfg.hidden_dim * cfg.noise_dim], dtype=d),
        "ell": nn.linear_init(kl, cfg.hidden_dim, cfg.data_dim, dtype=d),
    }


def gen_drift(cfg):
    def mu(params, t, x):
        return nn.mlp(params["mu"], _tcat(t, x), nn.lipswish, jnp.tanh)
    return mu


def gen_diffusion(cfg):
    def sigma(params, t, x):
        out = nn.mlp(params["sigma"], _tcat(t, x), nn.lipswish, jnp.tanh)
        return out.reshape(x.shape[:-1] + (cfg.hidden_dim, cfg.noise_dim))
    return sigma


def generator_sample(params, cfg: NeuralSDEConfig, key, batch: int):
    """Sample ``Y`` paths: returns (num_steps+1, batch, data_dim)."""
    kv, kw = jax.random.split(key)
    v = jax.random.normal(kv, (batch, cfg.initial_noise_dim), cfg.dtype)
    x0 = nn.mlp(params["zeta"], v, nn.lipswish)
    bm = BrownianPath(kw, 0.0, cfg.t1, (batch, cfg.noise_dim), cfg.dtype)
    traj = _cfg_solve(cfg, gen_drift(cfg), gen_diffusion(cfg), params, x0, bm,
                      cfg.num_steps, "general")
    return nn.linear(params["ell"], traj)


# =============================================================================
# Discriminator (Neural CDE, eq. (2))
# =============================================================================


def _disc_spec(cfg: NeuralSDEConfig) -> nn.CDEDiscriminatorSpec:
    return nn.CDEDiscriminatorSpec(
        data_dim=cfg.data_dim, hidden_dim=cfg.disc_hidden_dim,
        width=cfg.disc_width, depth=cfg.disc_depth, dtype=cfg.dtype)


def discriminator_init(key, cfg: NeuralSDEConfig):
    """Init the Lipschitz-constrained CDE stack (repro.nn.cde): xi/f/g start
    inside the careful-clipping box, the readout m is unconstrained."""
    return nn.cde_discriminator_init(key, _disc_spec(cfg))


def disc_f(cfg):
    return nn.cde_drift(_disc_spec(cfg))


def disc_g(cfg):
    """g_φ maps h -> (h, 1+y): the CDE is driven by the time-augmented path
    (t, Y_t) so the vector field sees dt through the control as well."""
    return nn.cde_control_field(_disc_spec(cfg))


def discriminate_path(params, cfg: NeuralSDEConfig, ys, exact_adjoint: Optional[bool] = None):
    """Score an observed path ``ys`` (T+1, batch, y): F_φ(Y) = m·H_T.

    Drives the CDE with the piecewise-linear time-augmented control (t, Y).
    """
    T = ys.shape[0] - 1
    ts = jnp.linspace(0.0, cfg.t1, T + 1, dtype=ys.dtype)
    tt = jnp.broadcast_to(ts[:, None, None], ys.shape[:-1] + (1,))
    control = LinearPathControl(jnp.concatenate([tt, ys], -1))
    h0 = nn.cde_initial(params, ts[0], ys[0])
    exact = cfg.exact_adjoint if exact_adjoint is None else exact_adjoint
    mode = "reversible_adjoint" if exact else "discretise"
    solver = "reversible_heun" if exact else cfg.solver
    traj = solve(disc_f(cfg), disc_g(cfg), params, h0, control, 0.0, cfg.t1, T,
                 solver=solver, gradient_mode=mode, noise="general")
    return nn.cde_readout(params, traj[-1])


# =============================================================================
# Joint generator+discriminator SDE (fake-sample scoring, end-to-end)
# =============================================================================


def joint_drift(cfg):
    mu_f, f_f, g_f = gen_drift(cfg), disc_f(cfg), disc_g(cfg)

    def drift(params, t, u):
        x, h = jnp.split(u, [cfg.hidden_dim], axis=-1)
        mu = mu_f(params["gen"], t, x)
        f = f_f(params["disc"], t, h)
        g = g_f(params["disc"], t, h)           # (..., h, 1+y)
        wl = params["gen"]["ell"]["w"]          # (x, y)
        dy_dt = jnp.concatenate(
            [jnp.ones(mu.shape[:-1] + (1,), mu.dtype), mu @ wl], -1)  # (…, 1+y)
        dh = f + jnp.einsum("...hy,...y->...h", g, dy_dt)
        return jnp.concatenate([mu, dh], -1)

    return drift


def joint_diffusion(cfg):
    sig_f, g_f = gen_diffusion(cfg), disc_g(cfg)

    def diffusion(params, t, u):
        x, h = jnp.split(u, [cfg.hidden_dim], axis=-1)
        sig = sig_f(params["gen"], t, x)        # (..., x, w)
        g = g_f(params["disc"], t, h)           # (..., h, 1+y)
        wl = params["gen"]["ell"]["w"]          # (x, y)
        #   dY = ℓ'(X) dX  ⇒  noise into h is g[:, 1:]·(Wᵀσ)
        gh = jnp.einsum("...hy,xy,...xw->...hw", g[..., 1:], wl, sig)
        return jnp.concatenate([sig, gh], -2)   # (..., x+h, w)

    return diffusion


def gan_score_fake(params, cfg: NeuralSDEConfig, key, batch: int):
    """F_φ(Y) for generated Y, via a single joint SDE solve (exact adjoint)."""
    kv, kw = jax.random.split(key)
    v = jax.random.normal(kv, (batch, cfg.initial_noise_dim), cfg.dtype)
    x0 = nn.mlp(params["gen"]["zeta"], v, nn.lipswish)
    y0 = nn.linear(params["gen"]["ell"], x0)
    h0 = nn.cde_initial(params["disc"], 0.0, y0)
    u0 = jnp.concatenate([x0, h0], -1)
    bm = BrownianPath(kw, 0.0, cfg.t1, (batch, cfg.noise_dim), cfg.dtype)
    traj = _cfg_solve(cfg, joint_drift(cfg), joint_diffusion(cfg), params, u0, bm,
                      cfg.num_steps, "general")
    hT = traj[-1][..., cfg.hidden_dim:]
    score = nn.cde_readout(params["disc"], hT)
    ys = nn.linear(params["gen"]["ell"], traj[..., : cfg.hidden_dim])
    return score, ys


def gan_losses(params, cfg: NeuralSDEConfig, key, y_real, batch: int):
    """Wasserstein losses (eq. (3)): returns (gen_loss, disc_loss, fake_ys)."""
    fake_score, fake_ys = gan_score_fake(params, cfg, key, batch)
    real_score = discriminate_path(params["disc"], cfg, y_real)
    gen_loss = -jnp.mean(fake_score)
    disc_loss = jnp.mean(fake_score) - jnp.mean(real_score)
    return gen_loss, disc_loss, fake_ys


def gradient_penalty(params_disc, cfg: NeuralSDEConfig, key, y_real, y_fake):
    """WGAN-GP baseline (Gulrajani et al. [36]) — the double-backward the
    paper's clipping removes.  Differentiates the CDE solve w.r.t. the input
    path (discretise-then-optimise; continuous double-adjoint is exactly the
    error source §5 describes)."""
    eps = jax.random.uniform(key, (1, y_real.shape[1], 1), y_real.dtype)
    y_mix = eps * y_real + (1 - eps) * y_fake

    def score_of_path(y):
        return jnp.sum(discriminate_path(params_disc, cfg, y, exact_adjoint=False))

    g = jax.grad(score_of_path)(y_mix)
    gnorm = jnp.sqrt(jnp.sum(g * g, axis=(0, 2)) + 1e-12)
    return jnp.mean((gnorm - 1.0) ** 2)


# =============================================================================
# Latent SDE (Li et al. [15]; paper Appendix B)
# =============================================================================


@dataclasses.dataclass(frozen=True)
class LatentSDEConfig:
    data_dim: int = 1
    hidden_dim: int = 16
    context_dim: int = 16
    initial_noise_dim: int = 8
    width: int = 32
    depth: int = 1
    num_steps: int = 32
    t1: float = 1.0
    solver: str = "reversible_heun"
    exact_adjoint: bool = True
    gradient_mode: Optional[str] = None  # explicit backend; None = derive
    precision: str = "highest"  # field-eval compute policy (solve stack)
    kl_weight: float = 1.0
    use_pallas_kernels: bool = False  # fused diagonal-noise hot loop
    dtype: object = jnp.float32


def validate_latent_grid(num_steps: int, T: int) -> int:
    """Check the solver grid aligns with the observation grid; return stride.

    The reconstruction term reads the solver trajectory at the ``T + 1``
    observation times, so ``num_steps`` must be a positive multiple of ``T``
    (the number of observation intervals) for every observation to land
    exactly on a solver step.  Validated eagerly — shapes are static — so
    callers get a named error instead of an opaque broadcast ``TypeError``
    (``num_steps=30, T=8``) or ``slice step cannot be zero``
    (``num_steps < T``) from deep inside the solve.
    """
    if T < 1:
        raise ValueError(
            f"latent-SDE data must contain at least two observations; got "
            f"T = {T} observation intervals")
    if num_steps < T or num_steps % T != 0:
        reason = (f"num_steps < T" if num_steps < T
                  else f"num_steps % T == {num_steps % T} != 0")
        raise ValueError(
            f"latent-SDE solver grid is misaligned with the observation "
            f"grid: cfg.num_steps ({num_steps}) must be a positive multiple "
            f"of the data grid T ({T}, the number of observation intervals "
            f"= len(y) - 1) so every observation lands on a solver step "
            f"(valid: {T}, {2 * T}, {3 * T}, ...); got {reason}")
    return num_steps // T


def latent_sde_init(key, cfg: LatentSDEConfig):
    kz, km, ks, kl, ke, kn, kq = jax.random.split(key, 7)
    hid = [cfg.width] * cfg.depth
    d = cfg.dtype
    return {
        "zeta": nn.mlp_init(kz, [cfg.initial_noise_dim] + hid + [cfg.hidden_dim], dtype=d),
        "mu": nn.mlp_init(km, [1 + cfg.hidden_dim] + hid + [cfg.hidden_dim], dtype=d),        # prior drift
        "sigma": nn.mlp_init(ks, [1 + cfg.hidden_dim] + hid + [cfg.hidden_dim], dtype=d),     # diagonal
        "ell": nn.linear_init(kl, cfg.hidden_dim, cfg.data_dim, dtype=d),
        "enc": nn.gru_init(ke, cfg.data_dim, cfg.context_dim, dtype=d),                        # ν_φ² (bwd GRU)
        "nu": nn.mlp_init(kn, [1 + cfg.hidden_dim + cfg.context_dim] + hid + [cfg.hidden_dim], dtype=d),
        "qz0": nn.mlp_init(kq, [cfg.context_dim] + hid + [2 * cfg.initial_noise_dim], dtype=d),  # ξ_φ
    }


def _lsde_sigma(params, t, x):
    raw = nn.mlp(params["sigma"], _tcat(t, x), nn.lipswish)
    return jax.nn.sigmoid(raw) * 0.5 + 0.05  # bounded positive diagonal


def _latent_encode(params, cfg: LatentSDEConfig, key, y_true):
    """Backward-GRU context + initial-latent sample.

    Returns ``(ctx, x0, kl_v)``: the (T+1, B, c) context path ν_φ², the
    initial hidden state ζ_θ(V̂) with V̂ ~ N(m, s) from ξ_φ(ctx_0), and the
    per-sample KL(N(m, s) ‖ N(0, 1)) of the initial latent.
    """
    ctx = nn.gru_scan(params["enc"], y_true, reverse=True)  # (T+1, B, c)
    ms = nn.mlp(params["qz0"], ctx[0], nn.lipswish)
    m, log_s = jnp.split(ms, 2, -1)
    s = jnp.exp(jnp.clip(log_s, -8, 4))
    v = m + s * jax.random.normal(key, m.shape, cfg.dtype)
    kl_v = 0.5 * jnp.sum(m**2 + s**2 - 2.0 * jnp.log(s) - 1.0, -1)
    x0 = nn.mlp(params["zeta"], v, nn.lipswish)
    return ctx, x0, kl_v


def _step_index_lookup(t1: float, T: int):
    """``(path, t) -> path[round(t / t1 * T)]`` — index a (T+1, ...) tensor
    (encoder context, observations) by solver time.  Shared by the training
    posterior fields and the serving posterior decode."""

    def at(p, t):
        idx = jnp.clip(jnp.asarray(t / t1 * T).astype(jnp.int32), 0, T)
        return jax.lax.dynamic_index_in_dim(p, idx, 0, keepdims=False)

    return at


def _latent_posterior_fields(cfg: LatentSDEConfig, T: int, n_aux: int,
                             with_recon: bool = False):
    """Posterior drift/diffusion over the augmented state ``[x, kl(, recon)]``.

    The KL path integrand ½‖(μ−ν)/σ‖² always rides as a state channel
    (paper eq. (4) / Appendix B).  ``with_recon`` adds a second channel
    integrating the squared reconstruction error against the (step-indexed)
    observations — the form the terminal-only ELBO needs.  Aux channels
    carry zero diffusion rows.
    """

    _ctx_at = _step_index_lookup(cfg.t1, T)

    def post_drift(p, t, u):
        x = u[..., : cfg.hidden_dim]
        nets = p["nets"]
        c = _ctx_at(p["ctx"], t)
        nu = nn.mlp(nets["nu"], jnp.concatenate([_tcat(t, x), c], -1),
                    nn.lipswish, jnp.tanh)
        mu = nn.mlp(nets["mu"], _tcat(t, x), nn.lipswish, jnp.tanh)
        sig = _lsde_sigma(nets, t, x)
        u_ratio = (mu - nu) / sig
        dkl = 0.5 * jnp.sum(u_ratio * u_ratio, -1, keepdims=True)
        chans = [nu, dkl]
        if with_recon:
            y_hat = nn.linear(nets["ell"], x)
            y_t = _ctx_at(p["y"], t)
            chans.append(jnp.mean((y_hat - y_t) ** 2, -1, keepdims=True))
        return jnp.concatenate(chans, -1)

    def post_diffusion(p, t, u):
        x = u[..., : cfg.hidden_dim]
        sig = _lsde_sigma(p["nets"], t, x)
        return jnp.concatenate(
            [sig, jnp.zeros(sig.shape[:-1] + (n_aux,), sig.dtype)], -1)

    return post_drift, post_diffusion


def latent_sde_loss(params, cfg: LatentSDEConfig, key, y_true):
    """Negative ELBO (paper eq. (4) / Appendix B).  ``y_true``: (T+1, B, y).

    The KL path integral rides along as an extra state channel so the whole
    objective is a function of one SDE solve's trajectory; the
    reconstruction term reads that trajectory at the observation times,
    which is why ``cfg.num_steps`` must be a positive multiple of the data
    grid ``T`` (checked eagerly by :func:`validate_latent_grid`).
    """
    T = y_true.shape[0] - 1
    B = y_true.shape[1]
    stride = validate_latent_grid(cfg.num_steps, T)
    dt_data = cfg.t1 / T
    kz0, kw = jax.random.split(key)

    ctx, x0, kl_v = _latent_encode(params, cfg, kz0, y_true)
    aug_params = {"nets": params, "ctx": ctx}
    post_drift, post_diffusion = _latent_posterior_fields(cfg, T, n_aux=1)

    u0 = jnp.concatenate([x0, jnp.zeros((B, 1), cfg.dtype)], -1)
    bm = BrownianPath(kw, 0.0, cfg.t1, (B, cfg.hidden_dim + 1), cfg.dtype)
    traj = _cfg_solve(cfg, post_drift, post_diffusion, aug_params, u0, bm,
                      cfg.num_steps, "diagonal")

    xs = traj[..., : cfg.hidden_dim]                       # (N+1, B, x)
    kl_path = traj[-1][..., -1]                            # (B,)
    y_hat = nn.linear(params["ell"], xs)                   # (N+1, B, y)
    y_hat_obs = y_hat[::stride]                            # (T+1, B, y)
    recon = jnp.sum(jnp.mean((y_hat_obs - y_true) ** 2, axis=(1, 2))) * dt_data
    recon0 = jnp.mean(jnp.sum((y_hat_obs[0] - y_true[0]) ** 2, -1))
    loss = recon + recon0 + cfg.kl_weight * jnp.mean(kl_path + kl_v)
    return loss, {"recon": recon, "kl_path": jnp.mean(kl_path), "kl_v": jnp.mean(kl_v)}


def latent_sde_loss_terminal(params, cfg: LatentSDEConfig, key, y_true,
                             gradient_mode=None, solver=None):
    """Negative ELBO as a function of the *terminal* augmented state only.

    Both the KL path integral and the reconstruction error ride as state
    channels, so the whole objective is ``f(u_T)`` — the form the
    continuous-adjoint ("backsolve") baseline requires: eq. (6)
    backpropagates a terminal-value cotangent only, so it cannot consume a
    trajectory the way :func:`latent_sde_loss` does.  (The exact reversible
    adjoint has no such restriction — that asymmetry is the point of the
    paper; see DESIGN.md §8.)  The recon channel integrates the squared
    error against the step-indexed observations, so the grid-alignment rule
    is the same as the trajectory form's.

    ``gradient_mode``/``solver`` override the config's derivation — e.g.
    ``("continuous_adjoint", "midpoint")`` for the backsolve baseline,
    ``None`` for the config default (exact adjoint when configured).
    """
    T = y_true.shape[0] - 1
    B = y_true.shape[1]
    validate_latent_grid(cfg.num_steps, T)
    kz0, kw = jax.random.split(key)

    ctx, x0, kl_v = _latent_encode(params, cfg, kz0, y_true)
    aug_params = {"nets": params, "ctx": ctx, "y": y_true}
    post_drift, post_diffusion = _latent_posterior_fields(
        cfg, T, n_aux=2, with_recon=True)

    u0 = jnp.concatenate([x0, jnp.zeros((B, 2), cfg.dtype)], -1)
    bm = BrownianPath(kw, 0.0, cfg.t1, (B, cfg.hidden_dim + 2), cfg.dtype)
    uT = _cfg_solve(cfg, post_drift, post_diffusion, aug_params, u0, bm,
                    cfg.num_steps, "diagonal", gradient_mode=gradient_mode,
                    solver=solver, save_trajectory=False)

    kl_path = uT[..., cfg.hidden_dim]                      # (B,)
    recon = jnp.mean(uT[..., cfg.hidden_dim + 1])          # ∫‖ŷ−y‖² dt, mean B
    y_hat0 = nn.linear(params["ell"], x0)
    recon0 = jnp.mean(jnp.sum((y_hat0 - y_true[0]) ** 2, -1))
    loss = recon + recon0 + cfg.kl_weight * jnp.mean(kl_path + kl_v)
    return loss, {"recon": recon, "kl_path": jnp.mean(kl_path), "kl_v": jnp.mean(kl_v)}


def latent_prior_drift(p, t, x):
    """Prior drift μ_θ — shared by training-time prior sampling and serving."""
    return nn.mlp(p["mu"], _tcat(t, x), nn.lipswish, jnp.tanh)


def latent_prior_diffusion(p, t, x):
    """Diagonal prior diffusion (bounded positive, shared with the posterior)."""
    return _lsde_sigma(p, t, x)


def latent_sde_sample(params, cfg: LatentSDEConfig, key, batch: int):
    """Sample from the prior: returns (num_steps+1, batch, y)."""
    kv, kw = jax.random.split(key)
    v = jax.random.normal(kv, (batch, cfg.initial_noise_dim), cfg.dtype)
    x0 = nn.mlp(params["zeta"], v, nn.lipswish)

    bm = BrownianPath(kw, 0.0, cfg.t1, (batch, cfg.hidden_dim), cfg.dtype)
    traj = solve(latent_prior_drift, latent_prior_diffusion, params, x0, bm,
                 0.0, cfg.t1, cfg.num_steps,
                 solver=cfg.solver, gradient_mode="discretise", noise="diagonal")
    return nn.linear(params["ell"], traj)


# =============================================================================
# Inference-only sampling entry points (serving; DESIGN.md §9)
# =============================================================================
#
# No loss plumbing: these produce trajectories, nothing else.  The serving
# contract is that **every trajectory row is a pure function of its own PRNG
# key** (plus params), so the bucket-padding in launch/serve.py — padding an
# off-size request batch up to the nearest compiled bucket — can never
# perturb the rows a client actually asked for.  Rows are vmapped with
# ``kernels.ops.vmap_rows``, which splits them over a data-parallel mesh.
# All solves dispatch through :func:`_cfg_solve`, i.e. the unified
# ``repro.solve`` front-end: any registered solver × noise type is
# servable.


def generator_sample_paths(params, cfg: NeuralSDEConfig, keys):
    """SDE-GAN generator rollout for serving, one trajectory per key.

    ``keys``: (B,) PRNG keys.  Returns (num_steps+1, B, data_dim),
    time-major like every path tensor in the repo.
    """

    def one(k):
        kv, kw = jax.random.split(k)
        v = jax.random.normal(kv, (cfg.initial_noise_dim,), cfg.dtype)
        x0 = nn.mlp(params["zeta"], v, nn.lipswish)
        bm = BrownianPath(kw, 0.0, cfg.t1, (cfg.noise_dim,), cfg.dtype)
        traj = _cfg_solve(cfg, gen_drift(cfg), gen_diffusion(cfg), params,
                          x0, bm, cfg.num_steps, "general")
        return nn.linear(params["ell"], traj)

    return vmap_rows(one, len(keys), out_axes=1)(keys)


def generator_sample_terminal(params, cfg: NeuralSDEConfig, keys, rtol, atol,
                              max_steps: Optional[int] = None):
    """Adaptive terminal-distribution sampling for serving: one terminal
    sample ``Y_T`` per key, solved to a *requested accuracy* instead of a
    fixed grid (DESIGN.md §10).

    ``rtol``/``atol`` may be **traced scalars** — one compiled sampler
    serves every tolerance, which is how launch/serve.py offers per-request
    tolerance without a recompile per tolerance.  The same bucket-padding
    invariant as the other serving entry points holds: each row is a pure
    function of ``(params, keys[i], rtol, atol)``.

    Returns ``(samples, converged)``: ``(B, data_dim)`` terminal samples
    plus a ``(B,)`` bool marking rows whose controller reached ``t1``
    within the step budget — a row with ``converged[i] == False`` is the
    state at ``t_final < t1``, and the serving loop must surface it rather
    than hand it to a client as ``Y_T`` (solver × adaptive validation
    itself happens inside :func:`repro.core.solve.solve_adaptive`).
    """
    if max_steps is None:
        max_steps = max(4 * cfg.num_steps, 256)

    def one(k):
        kv, kw = jax.random.split(k)
        v = jax.random.normal(kv, (cfg.initial_noise_dim,), cfg.dtype)
        x0 = nn.mlp(params["zeta"], v, nn.lipswish)
        bm = BrownianPath(kw, 0.0, cfg.t1, (cfg.noise_dim,), cfg.dtype)
        from .solve import solve_adaptive

        xT, stats = solve_adaptive(
            gen_drift(cfg), gen_diffusion(cfg), params, x0, bm, 0.0, cfg.t1,
            solver=cfg.solver, rtol=rtol, atol=atol, max_steps=max_steps,
            dt0=cfg.t1 / cfg.num_steps, noise="general")
        return nn.linear(params["ell"], xT), stats.converged

    return vmap_rows(one, len(keys))(keys)


def generator_initial_state(params, cfg: NeuralSDEConfig, keys):
    """x₀ = ζ_θ(V) per key — the entry state for the streamed (time-chunked)
    rollout in launch/serve.py.  Returns (B, hidden_dim)."""

    def one(k):
        kv, _ = jax.random.split(k)
        v = jax.random.normal(kv, (cfg.initial_noise_dim,), cfg.dtype)
        return nn.mlp(params["zeta"], v, nn.lipswish)

    return jax.vmap(one)(keys)


def generator_rollout_chunk(params, cfg: NeuralSDEConfig, keys, x0, t_start,
                            span: float, num_steps: int):
    """Continue generator trajectories over one time chunk
    ``[t_start, t_start + span]`` of a streamed horizon.

    ``t_start`` may be a *traced* scalar — or, since PR 7, a traced
    ``(B,)`` **per-row vector**: the drift/diffusion consume it
    arithmetically only, so one compiled program serves every chunk of the
    stream AND every mix of horizon positions inside one batch — the
    property the continuous-batching scheduler (``repro.serving``) builds
    on, where rows admitted at different chunk boundaries share a compiled
    batch.  ``keys`` must be pre-folded per chunk by the caller — the
    Brownian sample is keyed per (row, chunk), keeping the stream
    deterministic, rows independent, and a mid-flight join bitwise
    identical to the same request run solo.  Runs
    ``gradient_mode="discretise"`` (plain scan): serving takes no
    gradients, and the traced ``t_start`` rules out the fused path's
    static-``dt`` contract.

    Returns ``(ys, xT)``: ys (num_steps+1, B, data_dim) with row 0 the
    chunk-entry state (== previous chunk's final row, for continuity
    checks), and xT (B, hidden_dim) to carry into the next chunk.
    """
    t_start = jnp.asarray(t_start, cfg.dtype)
    t_axis = 0 if t_start.ndim == 1 else None
    if t_start.ndim > 1:
        raise ValueError(
            f"t_start must be a scalar or a (B,) per-row vector, got shape "
            f"{t_start.shape}")

    def one(k, x0_i, t0_i):
        bm = BrownianPath(k, 0.0, span, (cfg.noise_dim,), cfg.dtype)
        traj = solve(gen_drift(cfg), gen_diffusion(cfg), params, x0_i, bm,
                     t0_i, t0_i + span, num_steps,
                     solver=cfg.solver, gradient_mode="discretise",
                     noise="general")
        return nn.linear(params["ell"], traj), traj[-1]

    return vmap_rows(one, len(keys), in_axes=(0, 0, t_axis),
                     out_axes=(1, 0))(keys, x0, t_start)


def latent_sde_sample_paths(params, cfg: LatentSDEConfig, keys):
    """Latent-SDE prior decode for serving, one trajectory per key.

    Diagonal noise, so with ``cfg.use_pallas_kernels`` the solve runs the
    fused reversible-Heun forward scan.  Returns (num_steps+1, B, data_dim).
    """

    def one(k):
        kv, kw = jax.random.split(k)
        v = jax.random.normal(kv, (cfg.initial_noise_dim,), cfg.dtype)
        x0 = nn.mlp(params["zeta"], v, nn.lipswish)
        bm = BrownianPath(kw, 0.0, cfg.t1, (cfg.hidden_dim,), cfg.dtype)
        traj = _cfg_solve(cfg, latent_prior_drift, latent_prior_diffusion,
                          params, x0, bm, cfg.num_steps, "diagonal")
        return nn.linear(params["ell"], traj)

    return vmap_rows(one, len(keys), out_axes=1)(keys)


def latent_sde_posterior_decode(params, cfg: LatentSDEConfig, keys, y_obs):
    """Latent-SDE posterior decode for serving: encode observed paths, solve
    the posterior SDE (no KL/recon channels), return ŷ on the solver grid.

    ``keys``: (B,); ``y_obs``: (T+1, B, data_dim) observations.  Row ``i``
    depends only on ``(params, keys[i], y_obs[:, i])`` — the same
    bucket-padding invariant as the other serving entry points.  Returns
    (num_steps+1, B, data_dim).
    """
    T = y_obs.shape[0] - 1
    validate_latent_grid(cfg.num_steps, T)
    ctx_at = _step_index_lookup(cfg.t1, T)

    def drift(p, t, x):
        c = ctx_at(p["ctx"], t)
        return nn.mlp(p["nets"]["nu"],
                      jnp.concatenate([_tcat(t, x), c], -1),
                      nn.lipswish, jnp.tanh)

    def diffusion(p, t, x):
        return _lsde_sigma(p["nets"], t, x)

    def one(k, y):  # y: (T+1, data_dim)
        ctx, x0, _ = _latent_encode(params, cfg, jax.random.fold_in(k, 0), y)
        bm = BrownianPath(jax.random.fold_in(k, 1), 0.0, cfg.t1,
                          (cfg.hidden_dim,), cfg.dtype)
        traj = _cfg_solve(cfg, drift, diffusion, {"nets": params, "ctx": ctx},
                          x0, bm, cfg.num_steps, "diagonal")
        return nn.linear(params["ell"], traj)

    return vmap_rows(one, len(keys), in_axes=(0, 1), out_axes=1)(
        keys, y_obs)
