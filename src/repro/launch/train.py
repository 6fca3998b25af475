"""Fault-tolerant training driver.

Three workloads behind one driver (``--workload``):

* ``lm`` (default) — the transformer zoo (repro.models) train loop below;
* ``sde-gan`` — the paper's Neural SDE-GAN (repro.core.sde), every solve
  dispatched through the unified :func:`repro.solve` front-end
  (reversible Heun + exact O(1)-memory adjoint);
* ``latent-sde`` — the paper's Latent SDE / VAE (Li et al., Appendix B):
  one-``jax.vjp`` ELBO steps through the exact adjoint (or the
  ``--backsolve`` continuous-adjoint baseline), diagonal noise — the
  workload the Pallas-fused hot loop (``--pallas``) was built for.

Runs for real on whatever devices exist (CPU smoke configs here; the same
loop pjit-scales to the production mesh).  Demonstrates the full
large-scale-runnability posture:

* **step-granular atomic checkpoints** with auto-resume from the newest
  valid manifest (repro.checkpoint);
* **deterministic data** — the batch for step *n* is a pure function of
  (data_key, n), so restart/elastic replays identical samples;
* **simulated failure drill** (``--fail-at-step``): the process raises at a
  chosen step; re-running the same command resumes from the last checkpoint
  and reaches the same final step (tests/test_fault_tolerance.py asserts
  loss-trajectory equality);
* **elastic re-planning** (``--lose-devices``): on restart the mesh is
  re-planned from the surviving device count (distributed/elastic.py) and
  the global batch is re-sharded;
* **straggler monitor**: an EWMA per-step deadline; steps breaching it are
  logged (on a real fleet this triggers re-scheduling — here it exercises
  the control path).

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp

from .. import checkpoint as ckpt
from ..configs import get_config, smoke_config
from ..data.synthetic import token_batches
from ..distributed.elastic import plan_mesh, surviving_devices
from .cache import enable_compilation_cache
from ..models import transformer as T
from .steps import make_optimizer, make_train_step


class StragglerMonitor:
    """EWMA step-time deadline: flags steps slower than ``factor``× the mean."""

    def __init__(self, factor: float = 3.0, alpha: float = 0.2):
        self.factor = factor
        self.alpha = alpha
        self.mean: Optional[float] = None
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        straggle = self.mean is not None and dt > self.factor * self.mean
        self.mean = dt if self.mean is None else (1 - self.alpha) * self.mean + self.alpha * dt
        if straggle:
            self.flagged += 1
        return straggle


def train(arch: str, steps: int, batch: int, seq: int, ckpt_dir: Optional[str],
          ckpt_every: int = 20, smoke: bool = True, seed: int = 0,
          fail_at_step: Optional[int] = None, lose_devices: int = 0,
          log_every: int = 10, peak_lr: float = 3e-4):
    cfg = smoke_config(arch) if smoke else get_config(arch)
    key = jax.random.PRNGKey(seed)
    data_key = jax.random.fold_in(key, 1)

    # --- elastic planning: size the (data, model) grid to surviving devices
    n_dev = surviving_devices(len(jax.devices()), 0) - lose_devices
    data_deg, model_deg = plan_mesh(max(n_dev, 1), model_parallel=1)
    print(f"[train] mesh plan: data={data_deg} model={model_deg} "
          f"({n_dev} devices)", flush=True)

    params = T.init_lm(key, cfg)
    opt_init, opt_update = make_optimizer(cfg, peak_lr=peak_lr, total=steps)
    opt_state = opt_init(params)
    step_fn = jax.jit(make_train_step(cfg, opt_update), donate_argnums=(0, 1))

    start = 0
    if ckpt_dir is not None:
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            (params, opt_state), start = ckpt.restore_checkpoint(
                ckpt_dir, (params, opt_state))
            print(f"[train] resumed from step {start}", flush=True)

    monitor = StragglerMonitor()
    losses = []
    for step in range(start, steps):
        if fail_at_step is not None and step == fail_at_step:
            raise RuntimeError(f"simulated node failure at step {step}")
        t0 = time.time()
        batch_data = token_batches(data_key, jnp.int32(step), batch, seq, cfg.vocab)
        if cfg.frontend and cfg.family != "encdec":
            f = cfg.frontend_len
            batch_data = {
                "embeds": jax.random.normal(
                    jax.random.fold_in(data_key, step + 10_000),
                    (batch, f, cfg.d_model), cfg.dtype),
                "tokens": batch_data["tokens"][:, f:],
                "labels": batch_data["labels"][:, f:],
            }
        elif cfg.family == "encdec":
            batch_data = {
                "src_embeds": jax.random.normal(
                    jax.random.fold_in(data_key, step + 10_000),
                    (batch, seq, cfg.d_model), cfg.dtype),
                "tokens": batch_data["tokens"],
                "labels": batch_data["labels"],
            }
        params, opt_state, metrics = step_fn(params, opt_state, batch_data)
        loss = float(metrics["loss"])
        losses.append(loss)
        dt = time.time() - t0
        if monitor.observe(dt):
            print(f"[train] straggler: step {step} took {dt:.2f}s "
                  f"(mean {monitor.mean:.2f}s)", flush=True)
        if step % log_every == 0:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms",
                  flush=True)
        if ckpt_dir is not None and (step + 1) % ckpt_every == 0:
            ckpt.save_checkpoint(ckpt_dir, step + 1, (params, opt_state))
    if ckpt_dir is not None:
        ckpt.save_checkpoint(ckpt_dir, steps, (params, opt_state))
    return params, losses


def _data_parallel_mesh(batch: int, tag: str):
    """Data-parallel mesh over every visible device (1-device ⇒ no mesh).

    Both Neural-SDE workloads are pure batch parallelism (DESIGN.md §4/§8):
    parameters are tiny and replicated; only the sample batch shards.
    Simulate a multi-device host with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the
    ``--host-devices`` flag below does this for you).
    """
    from ..distributed.sharding import data_parallel_mesh

    mesh = data_parallel_mesh(batch)
    if mesh is None and len(jax.devices()) > 1:
        print(f"[{tag}] batch {batch} not divisible by "
              f"{len(jax.devices())} devices — running unsharded", flush=True)
    return mesh


def _restore_or_fresh(ckpt_dir: Optional[str], template, tag: str):
    """Resume from the newest checkpoint into ``template`` (fresh state,
    start step 0, when there is none).  A layout mismatch — a checkpoint
    saved under different flags or an older code version — dies here with
    a named error instead of deep inside pytree leaf lookup."""
    if ckpt_dir is None or ckpt.latest_step(ckpt_dir) is None:
        return template, 0
    try:
        state, start = ckpt.restore_checkpoint(ckpt_dir, template)
    except (KeyError, ValueError) as e:
        raise ValueError(
            f"checkpoint in {ckpt_dir} does not match the current "
            f"parameter/optimiser-state layout — it was saved under "
            f"different flags (e.g. --constraint) or an older code version; "
            f"use a fresh --ckpt-dir or rerun with matching flags") from e
    print(f"[{tag}] resumed from step {start}", flush=True)
    return state, start


def _sde_training_loop(tag: str, start: int, steps: int, batch: int, state,
                       step_fn, data_key, ckpt_dir: Optional[str],
                       ckpt_every: int, on_step, serving=None):
    """Shared step-loop scaffold for the Neural-SDE workloads (DESIGN.md
    §4/§8): data-parallel mesh over visible devices, straggler monitoring,
    periodic logging, step-granular atomic checkpoints.

    ``step_fn``: ``(state, key) -> (state, metrics)`` with ``state`` the
    checkpointed pytree.  ``on_step(step, state, metrics, dt)`` handles
    logging and returns a scalar to record in the returned history (or
    ``None`` to record nothing for this step).

    ``serving``: optional ``(workload, cfg, extract_params)`` handshake —
    every checkpoint save also writes the params-only serving bundle
    (``<ckpt_dir>/serving/``) that launch/serve.py restores from
    (DESIGN.md §9).  ``extract_params(state)`` picks the servable subtree
    (the generator for the GAN, the full VAE params for the latent SDE).
    """
    import contextlib

    mesh = _data_parallel_mesh(batch, tag)
    if mesh is not None:
        print(f"[{tag}] data-parallel over {len(jax.devices())} devices",
              flush=True)
    mesh_ctx = jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()

    def save(step, state):
        ckpt.save_checkpoint(ckpt_dir, step, state)
        if serving is not None:
            workload, cfg, extract_params = serving
            ckpt.save_serving_bundle(ckpt_dir, step, extract_params(state),
                                     workload, cfg)

    monitor = StragglerMonitor()
    history = []
    with mesh_ctx:
        for step in range(start, steps):
            t0 = time.time()
            state, metrics = step_fn(state, jax.random.fold_in(data_key, step))
            dt = time.time() - t0
            if monitor.observe(dt):
                print(f"[{tag}] straggler: step {step} took {dt:.2f}s",
                      flush=True)
            rec = on_step(step, state, metrics, dt)
            if rec is not None:
                history.append(rec)
            if ckpt_dir is not None and (step + 1) % ckpt_every == 0:
                save(step + 1, state)
    if ckpt_dir is not None:
        save(steps, state)
    return state, history


def sde_gan_setup(batch: int, seed: int = 0, solver: str = "reversible_heun",
                  use_pallas: bool = False, num_steps: int = 31,
                  seq_len: int = 32, constraint: str = "clip",
                  precision: str = "highest"):
    """The SDE-GAN trainer's config, fresh state and jitted step:
    ``(cfg, (params, g_state, d_state), step)`` with ``step(params, g_state,
    d_state, key) -> (params, g_state, d_state, metrics)``."""
    from ..core.sde import NeuralSDEConfig, discriminator_init, generator_init
    from .steps import make_gan_optimizers, make_sde_gan_step

    cfg = NeuralSDEConfig(
        data_dim=1, hidden_dim=16, noise_dim=4, width=32, num_steps=num_steps,
        solver=solver, exact_adjoint=solver == "reversible_heun",
        use_pallas_kernels=use_pallas, precision=precision)
    key = jax.random.PRNGKey(seed)
    params = {"gen": generator_init(key, cfg),
              "disc": discriminator_init(jax.random.fold_in(key, 1), cfg)}
    (gi, gu), (di, du) = make_gan_optimizers(lr=1.0, constraint=constraint)
    step = jax.jit(make_sde_gan_step(cfg, gu, du, batch, seq_len,
                                     constraint=constraint))
    return cfg, (params, gi(params["gen"]), di(params["disc"])), step


def train_sde_gan(steps: int, batch: int, ckpt_dir: Optional[str] = None,
                  ckpt_every: int = 50, seed: int = 0, log_every: int = 10,
                  solver: str = "reversible_heun", use_pallas: bool = False,
                  num_steps: int = 31, seq_len: int = 32,
                  constraint: str = "clip", precision: str = "highest"):
    """SDE-GAN training (paper §5) through the :func:`repro.solve` front-end.

    The generator sample, joint generator+discriminator solve, and CDE
    discriminator all dispatch through the solver registry — reversible
    Heun with the exact adjoint by default (``gradient_mode`` is derived
    from the config inside repro.core.sde).  The step itself comes from
    :func:`repro.launch.steps.make_sde_gan_step`: one shared forward per
    step via ``jax.vjp``, careful clipping as the tail of the discriminator
    optimiser chain, batch sharded over the data-parallel mesh.
    """
    from ..core.losses import signature_mmd
    from ..core.sde import generator_sample
    from ..data.synthetic import ou_process

    cfg, fresh, step_fn = sde_gan_setup(
        batch, seed, solver=solver, use_pallas=use_pallas,
        num_steps=num_steps, seq_len=seq_len, constraint=constraint,
        precision=precision)
    key = jax.random.PRNGKey(seed)
    data_key = jax.random.fold_in(key, 2)
    state, start = _restore_or_fresh(ckpt_dir, fresh, "sde-gan")

    def gan_step(state, k):
        params, g_state, d_state = state
        params, g_state, d_state, metrics = step_fn(params, g_state,
                                                    d_state, k)
        return (params, g_state, d_state), metrics

    def on_step(step, state, metrics, dt):
        if step % log_every != 0:
            return None
        y_real = ou_process(jax.random.fold_in(key, 777), 256, seq_len)
        fake = generator_sample(state[0]["gen"], cfg,
                                jax.random.fold_in(key, 778), 256)
        mmd = float(signature_mmd(y_real, fake))
        print(f"[sde-gan] step {step:5d} sig-MMD {mmd:.4f} "
              f"W {float(metrics['wasserstein']):.4f} {dt*1e3:.0f}ms",
              flush=True)
        return mmd

    (params, _, _), mmds = _sde_training_loop(
        "sde-gan", start, steps, batch, state, gan_step, data_key,
        ckpt_dir, ckpt_every, on_step,
        serving=("sde-gan", cfg, lambda s: s[0]["gen"]))
    return params, mmds


def latent_sde_setup(batch: int, seed: int = 0,
                     solver: str = "reversible_heun", use_pallas: bool = False,
                     num_steps: int = 23, seq_len: int = 24,
                     adjoint: str = "exact", kl_weight: float = 0.1,
                     lr: float = 1e-2, precision: str = "highest"):
    """The Latent-SDE trainer's config, fresh state and jitted step:
    ``(cfg, (params, opt_state), step)`` with ``step(params, opt_state,
    key) -> (params, opt_state, metrics)``.  Grid alignment and the
    solver × adjoint × fusion cell are validated here, before jit (see
    :func:`repro.launch.steps.make_latent_sde_step`)."""
    from ..core.sde import LatentSDEConfig, latent_sde_init
    from .steps import make_latent_sde_optimizer, make_latent_sde_step

    cfg = LatentSDEConfig(
        data_dim=2, hidden_dim=16, context_dim=16, width=32,
        num_steps=num_steps, solver=solver, kl_weight=kl_weight,
        exact_adjoint=adjoint == "exact" and solver == "reversible_heun",
        use_pallas_kernels=use_pallas, precision=precision)
    params = latent_sde_init(jax.random.PRNGKey(seed), cfg)
    oi, ou = make_latent_sde_optimizer(lr)
    step = jax.jit(make_latent_sde_step(cfg, ou, batch, seq_len,
                                        adjoint=adjoint))
    return cfg, (params, oi(params)), step


def train_latent_sde(steps: int, batch: int, ckpt_dir: Optional[str] = None,
                     ckpt_every: int = 50, seed: int = 0, log_every: int = 10,
                     solver: str = "reversible_heun", use_pallas: bool = False,
                     num_steps: int = 23, seq_len: int = 24,
                     adjoint: str = "exact", kl_weight: float = 0.1,
                     lr: float = 1e-2, precision: str = "highest"):
    """Latent-SDE (VAE) training (paper Appendix B) at parity with the
    SDE-GAN path: same data-parallel mesh machinery, checkpointing,
    straggler monitoring — and the first workload whose training hot loop
    actually runs the Pallas-fused diagonal-noise kernels (``--pallas``).

    The step comes from :func:`repro.launch.steps.make_latent_sde_step`:
    one ``jax.vjp`` ELBO forward (encoder GRU + posterior solve with KL as
    a state channel), one cotangent pull through the reversible-Heun exact
    adjoint (or the continuous-adjoint "backsolve" baseline).
    """
    cfg, fresh, step_fn = latent_sde_setup(
        batch, seed, solver=solver, use_pallas=use_pallas,
        num_steps=num_steps, seq_len=seq_len, adjoint=adjoint,
        kl_weight=kl_weight, lr=lr, precision=precision)
    data_key = jax.random.fold_in(jax.random.PRNGKey(seed), 2)
    state, start = _restore_or_fresh(ckpt_dir, fresh, "latent-sde")

    def vae_step(state, k):
        params, opt_state = state
        params, opt_state, metrics = step_fn(params, opt_state, k)
        return (params, opt_state), metrics

    def on_step(step, state, metrics, dt):
        loss = float(metrics["loss"])
        if step % log_every == 0:
            print(f"[latent-sde] step {step:5d} -ELBO {loss:.4f} "
                  f"recon {float(metrics['recon']):.4f} "
                  f"kl_path {float(metrics['kl_path']):.4f} "
                  f"{dt*1e3:.0f}ms", flush=True)
        return loss

    (params, _), losses = _sde_training_loop(
        "latent-sde", start, steps, batch, state, vae_step, data_key,
        ckpt_dir, ckpt_every, on_step,
        serving=("latent-sde", cfg, lambda s: s[0]))
    return params, losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "sde-gan", "latent-sde"),
                    default="lm")
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="lm: shrink the arch to a CPU-runnable smoke "
                         "config (default).  The sde-gan/latent-sde "
                         "defaults are already smoke-scale, so the flag is "
                         "a no-op there")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--lose-devices", type=int, default=0)
    ap.add_argument("--solver", default="reversible_heun",
                    help="sde-gan/latent-sde: any solver registered with "
                         "repro.solve")
    ap.add_argument("--pallas", action="store_true",
                    help="request the fused reversible-Heun hot loop.  The "
                         "latent-sde workload is diagonal-noise, so its "
                         "posterior solve runs genuinely fused (forward "
                         "scan + backward reconstruction); the sde-gan "
                         "workload's general-noise solves warn and run "
                         "unfused")
    ap.add_argument("--constraint", choices=("clip", "gp"), default="clip",
                    help="sde-gan Lipschitz control: 'clip' = the paper's "
                         "careful clipping, 'gp' = WGAN-GP baseline")
    ap.add_argument("--backsolve", action="store_true",
                    help="latent-sde: use the continuous-adjoint backsolve "
                         "baseline (Li et al. eq. (6), O(√h) gradient "
                         "error) instead of the exact reversible adjoint; "
                         "pairs with --solver midpoint (auto-selected if "
                         "the solver is left at reversible_heun)")
    ap.add_argument("--adjoint", choices=("exact", "backsolve", "checkpoint"),
                    default=None,
                    help="latent-sde gradient derivation: 'exact' (the "
                         "paper's reversible adjoint), 'backsolve' (same as "
                         "--backsolve), or 'checkpoint' (recursive binomial "
                         "checkpointing — exact gradients at O(log n) "
                         "memory, any solver).  Default: exact, or "
                         "backsolve when --backsolve is given")
    ap.add_argument("--precision", choices=("highest", "bf16_compute"),
                    default="highest",
                    help="sde-gan/latent-sde field-eval compute policy: "
                         "'bf16_compute' casts drift/diffusion evaluation "
                         "to bfloat16 while gradient accumulation stays in "
                         "the state dtype; 'highest' (default) is bitwise "
                         "unchanged")
    ap.add_argument("--kl-weight", type=float, default=0.1,
                    help="latent-sde: ELBO KL term weight")
    ap.add_argument("--lr", type=float, default=1e-2,
                    help="latent-sde: Adam learning rate")
    ap.add_argument("--sde-steps", type=int, default=None,
                    help="solver steps per solve (default: 31 for sde-gan; "
                         "23 for latent-sde, which must be a positive "
                         "multiple of seq_len - 1)")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="observed path length (default: 32 for sde-gan, "
                         "24 for latent-sde)")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="simulate N CPU devices (sets "
                         "--xla_force_host_platform_device_count before the "
                         "backend initialises; must come before any jax use)")
    args = ap.parse_args(argv)
    if args.host_devices is not None:
        from ..distributed.sharding import force_host_device_count

        force_host_device_count(args.host_devices)
    enable_compilation_cache()
    if args.workload == "sde-gan":
        _, mmds = train_sde_gan(
            args.steps, args.batch, args.ckpt_dir, args.ckpt_every, args.seed,
            solver=args.solver, use_pallas=args.pallas,
            num_steps=31 if args.sde_steps is None else args.sde_steps,
            seq_len=32 if args.seq_len is None else args.seq_len,
            constraint=args.constraint, precision=args.precision)
        if mmds:
            print(f"[sde-gan] done: first sig-MMD {mmds[0]:.4f} -> "
                  f"last {mmds[-1]:.4f}")
        else:  # e.g. resumed a finished run: no steps executed
            print("[sde-gan] done: no steps run")
        return
    if args.workload == "latent-sde":
        adjoint = args.adjoint
        if adjoint is None:
            adjoint = "backsolve" if args.backsolve else "exact"
        elif args.backsolve and adjoint != "backsolve":
            ap.error(f"--backsolve conflicts with --adjoint {adjoint}")
        solver = args.solver
        if adjoint == "backsolve" and solver == "reversible_heun":
            solver = "midpoint"  # the backsolve baseline's solver (paper's)
            print("[latent-sde] --backsolve: using midpoint (reversible_heun "
                  "has no continuous-adjoint backward)", flush=True)
        seq_len = 24 if args.seq_len is None else args.seq_len
        num_steps = seq_len - 1 if args.sde_steps is None else args.sde_steps
        _, losses = train_latent_sde(
            args.steps, args.batch, args.ckpt_dir, args.ckpt_every, args.seed,
            solver=solver, use_pallas=args.pallas,
            num_steps=num_steps, seq_len=seq_len, adjoint=adjoint,
            kl_weight=args.kl_weight, lr=args.lr,
            precision=args.precision)
        if losses:
            print(f"[latent-sde] done: first -ELBO {losses[0]:.4f} -> "
                  f"last {losses[-1]:.4f}")
        else:
            print("[latent-sde] done: no steps run")
        return
    _, losses = train(args.arch, args.steps, args.batch, args.seq,
                      args.ckpt_dir, args.ckpt_every, args.smoke, args.seed,
                      args.fail_at_step, args.lose_devices)
    print(f"[train] done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
