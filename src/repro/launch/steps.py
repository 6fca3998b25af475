"""Step functions: train_step / prefill_step / serve_step builders.

Pure functions of (state, batch) suitable for pjit with donated buffers.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .. import optim
from ..configs.base import ArchConfig

# NOTE: the transformer zoo (repro.models) is imported lazily inside the
# LM step builders below — launch/serve.py imports this module for the
# Neural-SDE samplers, and the SDE workloads must never touch the LM stack.


def make_optimizer(cfg: ArchConfig, peak_lr: float = 3e-4, warmup: int = 100,
                   total: int = 10_000, weight_decay: float = 0.1):
    sched = optim.cosine_schedule(peak_lr, warmup, total)
    moment_dtype = None if cfg.adam_dtype == "param" else cfg.adam_dtype
    return optim.adamw(sched, weight_decay=weight_decay, moment_dtype=moment_dtype)


def make_train_step(cfg: ArchConfig, opt_update=None, grad_clip: float = 1.0):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""
    from ..models import transformer as T

    if opt_update is None:
        _, opt_update = make_optimizer(cfg)

    def train_step(params, opt_state, batch: Dict[str, Any]):
        (loss, parts), grads = jax.value_and_grad(T.lm_loss, has_aux=True)(
            params, cfg, batch)
        grads, gnorm = optim.clip_by_global_norm(grads, grad_clip)
        updates, opt_state = opt_update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        metrics = {"loss": loss, "grad_norm": gnorm, **parts}
        return params, opt_state, metrics

    return train_step


# -----------------------------------------------------------------------------
# SDE-GAN (paper §5; DESIGN.md §4)
# -----------------------------------------------------------------------------


def make_gan_optimizers(lr: float = 1.0, constraint: str = "clip"):
    """Paper Appendix F: Adadelta for both players.  Under ``"clip"`` the
    discriminator chain ends in the careful-clipping projection — clip
    applied *after* the optimiser update, as a composable transform rather
    than a hand-written post-step, so swapping the optimiser never silently
    drops the constraint.  ``"gp"`` (the baseline) leaves the discriminator
    unconstrained — the penalty lives in the loss instead.

    Each player's Adadelta keeps its state as three flat arrays
    (:func:`repro.optim.flatten`): the jitted step then takes and returns 3
    optimiser buffers per player instead of one per parameter leaf twice
    over, and the host's per-buffer dispatch cost falls with them.

    Returns ``((g_init, g_update), (d_init, d_update))``.
    """
    from ..core.clipping import clip_lipschitz

    if constraint not in ("clip", "gp"):
        raise ValueError(f"constraint must be 'clip' or 'gp', got {constraint!r}")
    gen_opt = optim.flatten(optim.adadelta(lr))
    if constraint == "clip":
        disc_opt = optim.chain(
            optim.flatten(optim.adadelta(lr)),
            optim.lipschitz_projection(clip_lipschitz),
        )
    else:
        disc_opt = optim.flatten(optim.adadelta(lr))
    return gen_opt, disc_opt


def make_sde_gan_step(cfg, g_update, d_update, batch: int, seq_len: int,
                      constraint: str = "clip", gp_weight: float = 10.0):
    """Build the WGAN step: ``(params, g_state, d_state, key) ->
    (params, g_state, d_state, metrics)``.

    ``constraint="clip"`` (the paper's recipe) runs the generator forward —
    generator solve + joint generator/discriminator solve + real-path CDE
    solve — exactly **once** per step via ``jax.vjp``, and pulls **one**
    cotangent, the critic loss's, through the reversible-Heun exact
    adjoint.  ``gan_losses`` has ``gen_loss = -mean(fake_score)`` and
    ``disc_loss = mean(fake_score) - mean(real_score)``; the real term does
    not depend on the generator, so ``d gen_loss / d gen = -(d disc_loss /
    d gen)``, and the one pull returns both players' gradients.  The
    negation is exact: every VJP is linear in its cotangent and
    round-to-nearest is symmetric under sign, so the generator's gradient
    is bitwise the one a separate pull of ``gen_loss`` would give
    (``tests/test_gan_training.py`` pins it against that two-pull form).
    Each backward solve (joint reconstruction, real-path CDE) runs once a
    step, and the Lipschitz constraint costs one elementwise projection
    inside ``d_update``.

    ``constraint="gp"`` is the WGAN-GP baseline the paper replaces: the
    penalty term is a gradient *of a gradient* through the CDE solve, so it
    cannot share the forward and must run discretise-then-optimise
    (``benchmarks/clipping.py`` measures the difference).

    Batch-parallel: path tensors are constrained to the time-major layout
    (batch on the mesh's data axes, time replicated) so GSPMD shards all
    solves by batch while parameters stay replicated.
    """
    from ..core.sde import gan_losses, gradient_penalty
    from ..data.synthetic import ou_process
    from ..distributed.sharding import shard_time_major

    def clip_step(params, g_state, d_state, k):
        y_real = shard_time_major(ou_process(jax.random.fold_in(k, 0),
                                             batch, seq_len, dtype=cfg.dtype))

        # One shared forward and one cotangent pull, the critic loss's: it
        # rests on gan_losses' gen_loss = -mean(fake_score) being minus the
        # only generator-dependent term of disc_loss (see the docstring).
        def critic_loss(gen, disc):
            p = {"gen": gen, "disc": disc}
            gl, dl, _ = gan_losses(p, cfg, jax.random.fold_in(k, 1), y_real, batch)
            return dl, gl

        dl, vjp, gl = jax.vjp(critic_loss, params["gen"], params["disc"],
                              has_aux=True)
        neg_gg, dg = vjp(jnp.ones_like(dl))
        gg = jax.tree.map(jnp.negative, neg_gg)

        upd, d_state = d_update(dg, d_state, params["disc"])
        disc = optim.apply_updates(params["disc"], upd)  # projection folded in
        upd, g_state = g_update(gg, g_state, params["gen"])
        gen = optim.apply_updates(params["gen"], upd)
        metrics = {"gen_loss": gl, "disc_loss": dl, "wasserstein": -dl}
        return {"gen": gen, "disc": disc}, g_state, d_state, metrics

    def gp_step(params, g_state, d_state, k):
        y_real = shard_time_major(ou_process(jax.random.fold_in(k, 0),
                                             batch, seq_len, dtype=cfg.dtype))

        def d_loss(disc):
            p = {"gen": params["gen"], "disc": disc}
            _, dl, fake = gan_losses(p, cfg, jax.random.fold_in(k, 1), y_real, batch)
            # reuse the fake paths the loss already solved for (no second
            # generator solve); GP interpolates are constants w.r.t. φ
            fake = jax.lax.stop_gradient(fake)
            return dl + gp_weight * gradient_penalty(
                disc, cfg, jax.random.fold_in(k, 3), y_real, fake), dl

        def g_loss(gen):
            p = {"gen": gen, "disc": params["disc"]}
            gl, _, _ = gan_losses(p, cfg, jax.random.fold_in(k, 1), y_real, batch)
            return gl

        (_, dl), dg = jax.value_and_grad(d_loss, has_aux=True)(params["disc"])
        upd, d_state = d_update(dg, d_state, params["disc"])
        disc = optim.apply_updates(params["disc"], upd)
        gl, gg = jax.value_and_grad(g_loss)(params["gen"])
        upd, g_state = g_update(gg, g_state, params["gen"])
        gen = optim.apply_updates(params["gen"], upd)
        metrics = {"gen_loss": gl, "disc_loss": dl, "wasserstein": -dl}
        return {"gen": gen, "disc": disc}, g_state, d_state, metrics

    if constraint not in ("clip", "gp"):
        raise ValueError(f"constraint must be 'clip' or 'gp', got {constraint!r}")
    if constraint == "gp" and seq_len != cfg.num_steps + 1:
        # the GP interpolates eps*y_real + (1-eps)*y_fake need both paths on
        # the same grid; fail eagerly instead of a broadcast error inside jit
        raise ValueError(
            f"gp constraint requires seq_len == num_steps + 1 so real and "
            f"fake paths share a grid; got seq_len={seq_len}, "
            f"num_steps={cfg.num_steps}")
    return clip_step if constraint == "clip" else gp_step


# -----------------------------------------------------------------------------
# Latent SDE / VAE (Li et al. [15]; paper Appendix B; DESIGN.md §8)
# -----------------------------------------------------------------------------


def make_latent_sde_optimizer(lr: float = 1e-2):
    """Adam, per the paper's Latent-SDE recipe (Appendix F).  Returns the
    ``(init, update)`` pair; no projection tail — the VAE has no Lipschitz
    constraint to maintain (that is the GAN discriminator's problem).

    The state is three flat arrays (:func:`repro.optim.flatten`, bitwise
    the per-leaf Adam): the jitted step takes and returns 3 optimiser
    buffers instead of 1 + 2 x 36 at the paper's widths, and the host pays
    its dispatch cost per buffer."""
    return optim.flatten(optim.adam(lr))


def make_latent_sde_step(cfg, opt_update, batch: int, seq_len: int,
                         adjoint: str = "exact"):
    """Build the ELBO step: ``(params, opt_state, key) ->
    (params, opt_state, metrics)``.

    One forward per step via ``jax.vjp`` — encoder GRU + posterior SDE
    solve, with the KL path integral riding as a state channel — and one
    cotangent pull through the solver's adjoint:

    * ``adjoint="exact"`` (the paper's recipe): the reversible-Heun exact
      O(1)-memory adjoint via :func:`repro.core.sde.latent_sde_loss`.  The
      reconstruction term reads the trajectory at the observation times —
      only the exact adjoint can backpropagate a whole-trajectory loss with
      O(1) memory.  This is the workload the fused diagonal-noise Pallas
      kernels were built for: set ``cfg.use_pallas_kernels=True`` and the
      posterior solve's forward scan and backward reconstruction run fused.
    * ``adjoint="backsolve"`` (the Li et al. baseline): the
      continuous-adjoint eq. (6), which only accepts terminal-value
      cotangents — so the step switches to
      :func:`repro.core.sde.latent_sde_loss_terminal`, where the recon
      integral rides as a second state channel.  Gradients carry the
      O(√h) truncation error the paper eliminates
      (``benchmarks/latent_sde.py`` measures it).
    * ``adjoint="checkpoint"``: recursive binomial checkpointing over the
      same terminal-form objective — gradients exact to floating point
      (unlike backsolve) at O(log n) memory (unlike discretise), and
      available for EVERY registered solver, not just the reversible pair.
      The frontier cell for non-reversible steppers; see DESIGN.md §12.

    All shape/config mismatches are validated **here, eagerly** — a
    misaligned solver grid or an illegal solver × adjoint × fusion cell
    raises a named ``ValueError`` at build time, not a broadcast error from
    inside jit.

    Batch-parallel: the observation paths are constrained to the
    time-major layout (``sharding.shard_time_major``) so GSPMD shards the
    encoder scan and the posterior solve by batch while the (tiny, shared)
    parameters stay replicated — identical layout to the SDE-GAN step.
    """
    from ..core.sde import (latent_sde_loss, latent_sde_loss_terminal,
                            validate_latent_grid)
    from ..core.solve import get_solver
    from ..data.synthetic import air_quality_like
    from ..distributed.sharding import shard_time_major

    if adjoint not in ("exact", "backsolve", "checkpoint"):
        raise ValueError(
            f"adjoint must be 'exact', 'backsolve', or 'checkpoint', "
            f"got {adjoint!r}")
    if seq_len < 2:
        raise ValueError(f"seq_len must be >= 2 observations, got {seq_len}")
    validate_latent_grid(cfg.num_steps, seq_len - 1)
    if cfg.data_dim != 2:
        raise ValueError(
            f"the latent-SDE workload trains on the bivariate air-quality "
            f"dataset (PM2.5-like, O₃-like); cfg.data_dim must be 2, got "
            f"{cfg.data_dim}")
    if adjoint == "backsolve":
        spec = get_solver(cfg.solver)
        if "continuous_adjoint" not in spec.gradient_modes:
            raise ValueError(
                f"adjoint='backsolve' needs a solver with a "
                f"continuous-adjoint backward integrator; {cfg.solver!r} "
                f"serves {spec.gradient_modes} — use midpoint/heun/"
                f"euler_maruyama (or adjoint='exact' for reversible_heun)")
        if cfg.use_pallas_kernels:
            raise ValueError(
                "use_pallas_kernels requires the exact reversible-Heun "
                "adjoint (the fused kernels have no VJP rule and the "
                "backsolve path is plain AD over eq. (6)); drop --pallas "
                "or use adjoint='exact'")
    elif adjoint == "checkpoint":
        if cfg.use_pallas_kernels:
            raise ValueError(
                "use_pallas_kernels requires the exact reversible-Heun "
                "adjoint (checkpointing differentiates the rematerialised "
                "segments by plain AD, which cannot trace a pallas_call); "
                "drop --pallas or use adjoint='exact'")
    elif cfg.use_pallas_kernels and not (
            cfg.solver == "reversible_heun" and cfg.exact_adjoint):
        raise ValueError(
            f"use_pallas_kernels requires solver='reversible_heun' with "
            f"exact_adjoint=True (got solver={cfg.solver!r}, "
            f"exact_adjoint={cfg.exact_adjoint}) — the fused kernels only "
            f"apply to the exact-adjoint hot loop")

    def step(params, opt_state, k):
        ys, _ = air_quality_like(jax.random.fold_in(k, 0), batch, seq_len,
                                 dtype=cfg.dtype)
        ys = shard_time_major(ys)

        def elbo(p):
            if adjoint == "exact":
                return latent_sde_loss(p, cfg, jax.random.fold_in(k, 1), ys)
            mode = ("continuous_adjoint" if adjoint == "backsolve"
                    else "checkpoint")
            return latent_sde_loss_terminal(
                p, cfg, jax.random.fold_in(k, 1), ys, gradient_mode=mode)

        loss, vjp, parts = jax.vjp(elbo, params, has_aux=True)
        (grads,) = vjp(jnp.ones_like(loss))
        upd, opt_state = opt_update(grads, opt_state, params)
        params = optim.apply_updates(params, upd)
        metrics = {"loss": loss, "grad_norm": optim.global_norm(grads),
                   **parts}
        return params, opt_state, metrics

    return step


# -----------------------------------------------------------------------------
# Neural-SDE serving (DESIGN.md §9)
# -----------------------------------------------------------------------------

SERVE_WORKLOADS = ("sde-gan", "latent-sde")


def make_sample_step(workload: str, cfg, latent_mode: str = "prior",
                     obs_len: Optional[int] = None):
    """Build the batched trajectory sampler for one serving bucket:
    ``(params, keys) -> (num_steps+1, len(keys), data_dim)``.

    launch/serve.py AOT-compiles this once per bucket shape; an off-size
    coalesced request batch pads its key array up to the nearest bucket
    instead of triggering a recompile.  Padding is safe by construction:
    every row of the output is a pure function of ``(params, keys[i])``
    alone (see the serving entry points in repro.core.sde), which
    tests/test_serving.py pins bitwise.

    The trajectory tensor is constrained to the repo's time-major layout
    (``sharding.shard_time_major``), so under a data-parallel mesh GSPMD
    shards every per-row solve by batch while the (tiny) parameters stay
    replicated — the same layout as both training steps.

    ``workload="latent-sde"`` serves the prior decode by default;
    ``latent_mode="posterior"`` serves the encode→posterior-solve decode,
    synthesising the observation payload (``obs_len`` points) per row key —
    the smoke-shaped stand-in for a real observation channel, which would
    ride as a second AOT argument with the same bucket shape.

    All config/solver validation is eager: an illegal workload, latent
    mode, or observation grid raises a named ValueError here, at build
    time, never from inside the compiled sampler.
    """
    from ..core import sde as S
    from ..distributed.sharding import shard_time_major

    if workload not in SERVE_WORKLOADS:
        raise ValueError(
            f"workload must be one of {SERVE_WORKLOADS}, got {workload!r} "
            f"(the transformer-LM decode loop lives behind launch/serve.py "
            f"--workload lm, not this builder)")

    if workload == "sde-gan":
        def sample(params, keys):
            return shard_time_major(
                S.generator_sample_paths(params, cfg, keys))
        return sample

    if latent_mode not in ("prior", "posterior"):
        raise ValueError(
            f"latent_mode must be 'prior' or 'posterior', got {latent_mode!r}")
    if latent_mode == "prior":
        def sample(params, keys):
            return shard_time_major(
                S.latent_sde_sample_paths(params, cfg, keys))
        return sample

    if obs_len is None or obs_len < 2:
        raise ValueError(
            f"latent_mode='posterior' needs obs_len >= 2 observation points "
            f"per request, got {obs_len!r}")
    S.validate_latent_grid(cfg.num_steps, obs_len - 1)

    def sample(params, keys):
        from ..data.synthetic import air_quality_like

        def obs_row(k):  # -> (obs_len, data_dim), a pure function of k
            ys, _ = air_quality_like(jax.random.fold_in(k, 2), 1, obs_len,
                                     dtype=cfg.dtype)
            return ys[:, 0]

        y_obs = jax.vmap(obs_row, out_axes=1)(keys)
        return shard_time_major(
            S.latent_sde_posterior_decode(params, cfg, keys, y_obs))

    return sample


def make_adaptive_terminal_step(cfg, atol: float = 1e-6,
                                max_steps: int = 4096):
    """Build the adaptive terminal-distribution sampler for one serving
    bucket: ``(params, keys, rtol) -> ((len(keys), data_dim) samples,
    (len(keys),) converged)``.

    The per-request tolerance surface (DESIGN.md §10): ``rtol`` is a
    *traced* scalar, so launch/serve.py AOT-compiles ONE program per bucket
    and every tolerance a client asks for runs through it — the adaptive
    ``lax.while_loop`` simply takes more (or fewer) steps.  A coalesced
    batch runs at the tolerance :func:`repro.serving.route_rtol` picks —
    the loosest rtol the batch's tightest deadline allows, with explicit
    per-request asks as accuracy floors (the PR 7 SLO rule; the PR 5
    tightest-ask minimum is gone).  Rows whose
    controller exhausted its step budget come back ``converged=False`` —
    the serving loop reports them instead of passing them off as ``Y_T``.
    ``max_steps`` defaults to a production-sized 4096 (forward-only — no
    O(max_steps) adjoint buffers ride along here, and the while_loop only
    pays for iterations actually taken), so tight client tolerances don't
    starve at the library default budget.

    SDE-GAN generator only — it is the terminal-value workload; the
    trajectory-serving samplers keep their fixed grids (an adaptive solve
    has no fixed output grid to return).
    """
    from ..core import sde as S
    from ..core.solve import SOLVERS, get_solver

    spec = get_solver(cfg.solver)
    if spec.embedded_stepper is None:
        raise ValueError(
            f"--adaptive needs a solver with an embedded error estimate; "
            f"{cfg.solver!r} has none (embedded pairs: "
            f"{sorted(s.name for s in SOLVERS.values() if s.embedded_stepper)})")

    def sample(params, keys, rtol):
        return S.generator_sample_terminal(params, cfg, keys, rtol, atol,
                                           max_steps=max_steps)

    return sample


def make_stream_chunk_step(cfg, span: float, num_steps: int):
    """Build the streamed-rollout chunk step for long-horizon serving:
    ``(params, keys, x0, t_start) -> (ys_chunk, xT)``.

    ``t_start`` is a traced scalar — or a traced ``(B,)`` per-row vector,
    the continuous-batching form: rows admitted at different chunk
    boundaries sit at different horizon positions yet share ONE compiled
    program per bucket (``repro.serving.Scheduler``).  The stream loop
    passes a scalar (every row at the same chunk); either way the serving
    loop carries ``xT`` into the next chunk and emits each ``ys_chunk`` as
    it completes (first-chunk latency instead of full-horizon).  ``keys``
    must be pre-folded per chunk by the caller.  SDE-GAN generator only —
    the chunk carry is the generator hidden state.
    """
    from ..core import sde as S
    from ..distributed.sharding import shard_time_major

    def chunk_step(params, keys, x0, t_start):
        ys, xT = S.generator_rollout_chunk(params, cfg, keys, x0, t_start,
                                           span, num_steps)
        return shard_time_major(ys), xT

    return chunk_step


def make_prefill_step(cfg: ArchConfig, max_len: Optional[int] = None):
    """(params, batch) -> (last-token logits, populated caches)."""
    from ..models import transformer as T

    def prefill_step(params, batch: Dict[str, Any]):
        if cfg.family == "encdec":
            return T.encdec_prefill(params, cfg, batch["tokens"],
                                    batch["src_embeds"], max_len=max_len)
        return T.lm_prefill(params, cfg, batch["tokens"],
                            embeds=batch.get("embeds"), max_len=max_len)

    return prefill_step


def make_serve_step(cfg: ArchConfig):
    """(params, caches, token, pos) -> (logits, new caches).  One new token
    against a KV/state cache — the ``decode_*`` / ``long_*`` dry-run target."""
    from ..models import transformer as T

    def serve_step(params, caches, token, pos):
        if cfg.family == "encdec":
            return T.encdec_decode(params, cfg, token, caches, pos)
        return T.lm_decode(params, cfg, token, caches, pos)

    return serve_step


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
