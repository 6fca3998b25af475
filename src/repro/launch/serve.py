"""Neural-SDE serving CLI (DESIGN.md §9/§11).

A thin argparse front-end over the public :mod:`repro.serving` API —
restore/bucket/mesh/scheduling all live in the package; this module only
parses flags, plus hosts the quarantined transformer-LM decode loop from
the seed scaffold (``--workload lm`` — the only place serve.py touches
``repro.models``/``repro.configs``).

Usage::

    PYTHONPATH=src python -m repro.launch.serve --workload sde-gan \
        --host-devices 2 --smoke
    PYTHONPATH=src python -m repro.launch.serve --workload sde-gan \
        --scheduler continuous --requests 24
    PYTHONPATH=src python -m repro.launch.serve --workload latent-sde \
        --ckpt-dir /tmp/ckpt --requests 64 --max-batch 32

Back-compat: the names PR 4-6 exposed here (``Request``,
``synthetic_requests``, ``serve_buckets``, ``restore_for_serving``,
``serve_sde``, ``_coalesce``, ``_compile_pool``, ``_batch_loop``,
``_percentile``) are re-exported from :mod:`repro.serving`.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from .cache import enable_compilation_cache
from ..serving import (  # noqa: F401  (re-exports: the PR 4-6 surface)
    Request,
    _adaptive_terminal_loop,
    _batch_loop,
    _coalesce,
    _compile_pool,
    _percentile,
    _request_keys,
    _stream_loop,
    restore_for_serving,
    serve_buckets,
    serve_sde,
    synthetic_requests,
)
from .steps import SERVE_WORKLOADS

# -----------------------------------------------------------------------------
# the quarantined transformer-LM decode loop (seed scaffold)
# -----------------------------------------------------------------------------


def serve_lm(arch: str, batch: int, prompt_len: int, gen: int,
             smoke: bool = True, seed: int = 0):
    """Prefill + greedy-decode smoke loop for the transformer zoo.

    Kept behind ``--workload lm``: this is the only place serve.py touches
    ``repro.models``/``repro.configs`` — the SDE workloads never import the
    transformer stack.
    """
    from ..configs import get_config, smoke_config
    from ..models import transformer as T
    from .steps import greedy_sample, make_prefill_step, make_serve_step

    cfg = smoke_config(arch) if smoke else get_config(arch)
    if cfg.family == "encdec":
        raise SystemExit("use --arch with a decoder-only config for serve.py")

    key = jax.random.PRNGKey(seed)
    params = T.init_lm(key, cfg)
    max_len = prompt_len + gen + (cfg.frontend_len if cfg.frontend else 0)

    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (batch, prompt_len), 0, cfg.vocab)
    batch_in = {"tokens": prompts}
    pos0 = prompt_len
    if cfg.frontend:
        batch_in["embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2),
            (batch, cfg.frontend_len, cfg.d_model), cfg.dtype)
        pos0 += cfg.frontend_len

    prefill = jax.jit(make_prefill_step(cfg, max_len=max_len))
    decode = jax.jit(make_serve_step(cfg), donate_argnums=(1,))

    t0 = time.time()
    logits, caches = prefill(params, batch_in)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    token = greedy_sample(logits)
    out_tokens = [token]
    t0 = time.time()
    for i in range(gen - 1):
        logits, caches = decode(params, caches, token,
                                jnp.asarray(pos0 + i, jnp.int32))
        token = greedy_sample(logits)
        out_tokens.append(token)
    jax.block_until_ready(token)
    t_decode = time.time() - t0

    gen_tokens = jnp.concatenate(out_tokens, axis=1)
    tps = batch * (gen - 1) / max(t_decode, 1e-9)
    print(f"[serve] {arch}: batch={batch} prefill({prompt_len} tok) "
          f"{t_prefill * 1e3:.1f}ms; decode {gen - 1} steps @ {tps:.1f} tok/s")
    print(f"[serve] sample generation (row 0): {gen_tokens[0].tolist()}")
    return gen_tokens


# -----------------------------------------------------------------------------
# CLI
# -----------------------------------------------------------------------------


_EPILOG = """\
tolerance routing (DESIGN.md §11):
  Adaptive terminal batches are coalesced per deadline class and run at
  the LOOSEST rtol the batch's tightest deadline allows (route_rtol).
  This replaced the PR 5 tightest-ask rule — one accuracy-hungry request
  no longer slows every deadline-bound request sharing its batch.
  Explicit per-request rtol asks survive as accuracy floors only.
  SLO ladder: realtime <=50ms -> 1e-2, interactive <=250ms -> 3e-3,
  standard <=1000ms -> 1e-3, relaxed (no SLO) -> 3e-4.

scheduler extras (DESIGN.md §14, all require --scheduler):
  --preempt          cross-lane preemption: under realtime-class pressure
                     on any lane, other lanes' relaxed rollouts yield at
                     chunk boundaries (bitwise-invisible to them).
  --pool-budget-mb   LRU cap on the AOT compile pools: cold
                     (model, kind, bucket) programs are evicted and
                     transparently recompiled on next use.
  --async-front      drive the drain through the asyncio ingestion
                     front-end (repro.serving.AsyncFrontend).
"""


def main(argv=None):
    ap = argparse.ArgumentParser(
        epilog=_EPILOG, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", choices=SERVE_WORKLOADS + ("lm",),
                    default="sde-gan")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint dir written by launch/train.py (the "
                         "serving bundle lives under <ckpt-dir>/serving/); "
                         "omit with --smoke for a fresh-init service")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="simulate N CPU devices (must be processed before "
                         "the XLA backend initialises)")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="largest serving bucket (rows per compiled batch)")
    ap.add_argument("--requests", type=int, default=12,
                    help="synthetic requests to drain through the queue")
    ap.add_argument("--request-max", type=int, default=4,
                    help="largest per-request trajectory count")
    ap.add_argument("--latent-mode", choices=("prior", "posterior"),
                    default="prior",
                    help="latent-sde: decode from the prior, or encode "
                         "observations and decode the posterior")
    ap.add_argument("--obs-len", type=int, default=9,
                    help="latent-sde posterior: observation points per "
                         "request (num_steps must be a multiple of "
                         "obs_len - 1)")
    ap.add_argument("--stream-chunks", type=int, default=0,
                    help="sde-gan: stream the horizon in K time chunks "
                         "(0/1 = whole trajectories)")
    ap.add_argument("--adaptive", action="store_true",
                    help="sde-gan: serve adaptive terminal samples at the "
                         "deadline-routed tolerance (rtol is traced — one "
                         "compiled program per bucket serves every rtol)")
    ap.add_argument("--atol", type=float, default=1e-6,
                    help="adaptive serving: absolute tolerance floor")
    ap.add_argument("--scheduler", choices=("continuous", "fifo"),
                    default=None,
                    help="sde-gan: drive the continuous-batching scheduler "
                         "(repro.serving.Scheduler) — 'fifo' runs the same "
                         "chunked programs under the PR 4 drain-then-"
                         "coalesce baseline for comparison")
    ap.add_argument("--preempt", action="store_true",
                    help="scheduler: yield relaxed-class rollouts at chunk "
                         "boundaries while any lane has realtime-class work "
                         "(see epilog; bitwise-invisible)")
    ap.add_argument("--pool-budget-mb", type=float, default=None,
                    help="scheduler: LRU-evict cold compiled programs once "
                         "the pools exceed this many MB (XLA "
                         "memory_analysis accounting; recompile on reuse)")
    ap.add_argument("--async-front", action="store_true",
                    help="scheduler: drive the drain through the asyncio "
                         "ingestion front-end instead of a direct step loop")
    ap.add_argument("--solver", default="reversible_heun",
                    help="fresh-init (--smoke) solver; restored bundles "
                         "carry their own")
    ap.add_argument("--pallas", action="store_true",
                    help="fresh-init: request the fused hot loop (diagonal-"
                         "noise latent decode fuses; sde-gan warns + runs "
                         "unfused)")
    ap.add_argument("--sde-steps", type=int, default=None,
                    help="fresh-init solver steps (default 16)")
    ap.add_argument("--seed", type=int, default=0)
    # --workload lm (quarantined transformer decode loop)
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)

    if args.host_devices is not None:
        from ..distributed.sharding import force_host_device_count

        force_host_device_count(args.host_devices)
    enable_compilation_cache()
    if args.workload == "lm":
        return serve_lm(args.arch, args.batch, args.prompt_len, args.gen,
                        args.smoke, args.seed)
    return serve_sde(args.workload, args.ckpt_dir, args.smoke,
                     args.max_batch, args.requests, args.request_max,
                     latent_mode=args.latent_mode, obs_len=args.obs_len,
                     stream_chunks=args.stream_chunks,
                     adaptive=args.adaptive, atol=args.atol,
                     seed=args.seed, scheduler=args.scheduler,
                     preempt=args.preempt,
                     pool_budget_mb=args.pool_budget_mb,
                     async_front=args.async_front, args=args)


if __name__ == "__main__":
    main()
