"""The serving driver: bundle restore, AOT pools, coalescing loops
(DESIGN.md §9/§11).

This is the engine ``launch/serve.py`` is now a thin argparse CLI over.
Three drain loops share the restore/bucket/mesh scaffolding:

- :func:`_batch_loop` — FIFO coalescing over whole-trajectory samplers
  (the PR 4 prototype, kept as the baseline and the latent-sde path);
- :func:`_adaptive_terminal_loop` — terminal sampling with **SLO-aware
  tolerance routing**: requests are bucketed by deadline class and each
  batch runs at the loosest rtol its tightest deadline allows
  (:func:`repro.serving.route_rtol` — replacing PR 5's tightest-ask
  rule); per-row convergence rides back on :class:`ServeResult`;
- :func:`_stream_loop` — chunked long-horizon streaming;

plus :func:`_scheduler_loop`, which drives the continuous-batching
:class:`~repro.serving.Scheduler` over the same synthetic request stream.
"""

from __future__ import annotations

import contextlib
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp

from .. import checkpoint as ckpt
from ..distributed.sharding import data_parallel_mesh
from .registry import (LoadedModel, ModelRegistry, _init_params,
                       restore_for_serving)
from .scheduler import Scheduler, latency_summary, serve_buckets
from .types import (DEADLINE_CLASSES, PAD_SEED, deadline_class_for,
                    percentile, route_rtol, synthetic_requests)

#: Stable private names (the PR 7 API promise): these helpers moved here
#: from launch/serve.py and downstream code may rely on them.
_percentile = percentile


def _fresh_cfg(workload: str, args):
    """Smoke-mode config from the CLI flags (no checkpoint to read one from)."""
    from ..core.sde import LatentSDEConfig, NeuralSDEConfig

    num_steps = 16 if args.sde_steps is None else args.sde_steps
    exact = args.solver == "reversible_heun"
    if workload == "sde-gan":
        return NeuralSDEConfig(
            data_dim=1, hidden_dim=16, noise_dim=4, width=32,
            num_steps=num_steps, solver=args.solver, exact_adjoint=exact,
            use_pallas_kernels=args.pallas)
    return LatentSDEConfig(
        data_dim=2, hidden_dim=16, context_dim=16, width=32,
        num_steps=num_steps, solver=args.solver, exact_adjoint=exact,
        use_pallas_kernels=args.pallas)


def _request_keys(requests, pad_to: int):
    """Key array for a coalesced batch: per-request seeds fanned out per
    row, padded to the bucket size with throwaway keys."""
    parts = [
        jax.vmap(lambda j, s=r.seed: jax.random.fold_in(
            jax.random.PRNGKey(s), j))(jnp.arange(r.size))
        for r in requests
    ]
    used = sum(r.size for r in requests)
    if pad_to > used:
        parts.append(jax.vmap(lambda j: jax.random.fold_in(
            jax.random.PRNGKey(PAD_SEED), j))(jnp.arange(pad_to - used)))
    return jnp.concatenate(parts, axis=0)


def _compile_pool(sampler, params, buckets, *example_args, tag: str = ""):
    """AOT-compile the sampler once per bucket shape.

    ``example_args``: extra example operands after ``(params, keys)`` —
    e.g. the adaptive loop's traced-rtol scalar (shape, not value, is what
    the compile caches on).
    """
    jitted = jax.jit(sampler)
    pool = {}
    for b in buckets:
        keys = jax.random.split(jax.random.PRNGKey(0), b)
        t0 = time.perf_counter()
        pool[b] = jitted.lower(params, keys, *example_args).compile()
        print(f"[serve] compiled {tag}bucket {b} in "
              f"{time.perf_counter() - t0:.2f}s", flush=True)
    return pool


def _coalesce(pending, cap: int):
    """Pop pending requests FIFO until the next one would overflow ``cap``."""
    batch, rows = [], 0
    while pending and rows + pending[0].size <= cap:
        r = pending.popleft()
        batch.append(r)
        rows += r.size
    return batch, rows


def _report(tag: str, stats: dict, total_rows: int, n_batches: int,
            latencies, wall: float) -> None:
    tps = total_rows / max(wall, 1e-9)
    p50, p99 = _percentile(latencies, 0.50), _percentile(latencies, 0.99)
    stats.update(trajectories=total_rows, batches=n_batches,
                 traj_per_s=tps, p50_s=p50, p99_s=p99)
    print(f"[serve] {tag}: {total_rows} trajectories in {n_batches} "
          f"batches @ {tps:.1f} traj/s", flush=True)
    print(f"[serve] latency p50 {p50 * 1e3:.1f}ms p99 {p99 * 1e3:.1f}ms "
          f"(n={len(latencies)} requests, closed-loop)", flush=True)


# -----------------------------------------------------------------------------
# the service entry point
# -----------------------------------------------------------------------------


def serve_sde(workload: str, ckpt_dir: Optional[str], smoke: bool,
              max_batch: int, requests: int, request_max: int,
              latent_mode: str = "prior", obs_len: int = 9,
              stream_chunks: int = 0, adaptive: bool = False,
              atol: float = 1e-6, seed: int = 0,
              scheduler: Optional[str] = None, preempt: bool = False,
              pool_budget_mb: Optional[float] = None,
              async_front: bool = False, args=None) -> dict:
    """Run the trajectory-sampling service; returns the stats dict it prints.

    With ``--smoke`` and no ``--ckpt-dir``, a fresh-initialised model is
    saved to (and restored from) a throwaway serving bundle — the same
    restore path a trained checkpoint takes, exercised end to end.
    ``scheduler`` selects the continuous-batching path (``"continuous"``
    or its ``"fifo"`` baseline) instead of the drain loops; ``preempt``
    (cross-lane preemption), ``pool_budget_mb`` (LRU compile-pool cap)
    and ``async_front`` (drive the drain through
    :class:`~repro.serving.AsyncFrontend` instead of a direct step loop)
    ride on it and require it.
    """
    from ..launch.steps import SERVE_WORKLOADS

    if workload not in SERVE_WORKLOADS:
        raise ValueError(f"serve_sde serves {SERVE_WORKLOADS}, got {workload!r}")
    if adaptive and workload != "sde-gan":
        raise ValueError(
            "--adaptive serves terminal samples from the SDE-GAN generator; "
            "the latent-sde decoders serve whole trajectories, which have no "
            "fixed output grid under adaptive stepping")
    if adaptive and stream_chunks > 1:
        raise ValueError(
            "--adaptive and --stream-chunks are mutually exclusive: "
            "streaming emits a fixed per-chunk grid, adaptive solving "
            "chooses its own")
    if scheduler is not None and workload != "sde-gan":
        raise ValueError(
            "--scheduler drives the continuous-batching chunked rollout, "
            "which is the SDE-GAN generator's carry machinery; latent-sde "
            "serves through the coalescing loop")
    if scheduler is None and (preempt or pool_budget_mb is not None
                              or async_front):
        opts = [n for n, on in (("--preempt", preempt),
                                ("--pool-budget-mb", pool_budget_mb
                                 is not None),
                                ("--async-front", async_front)) if on]
        raise ValueError(
            f"{', '.join(opts)} require the continuous-batching path — "
            f"pass --scheduler continuous (or fifo)")
    if pool_budget_mb is not None and pool_budget_mb <= 0:
        raise ValueError(f"--pool-budget-mb must be positive, got "
                         f"{pool_budget_mb}")
    if requests < 1 or request_max < 1:
        raise ValueError(
            f"--requests ({requests}) and --request-max ({request_max}) "
            f"must both be >= 1 — an empty queue has no latency to report")
    if ckpt_dir is None:
        if not smoke:
            raise ValueError("--ckpt-dir is required without --smoke (a "
                             "production service has a trained model)")
        ckpt_dir = tempfile.mkdtemp(prefix="repro-serve-smoke-")
        cfg = _fresh_cfg(workload, args)
        ckpt.save_serving_bundle(ckpt_dir, 0, _init_params(workload, cfg, seed),
                                 workload, cfg)
        print(f"[serve] --smoke: fresh {workload} bundle at {ckpt_dir}",
              flush=True)
    params, cfg, step = restore_for_serving(workload, ckpt_dir)
    print(f"[serve] restored {workload} serving bundle (train step {step}, "
          f"solver={cfg.solver}, num_steps={cfg.num_steps})", flush=True)

    n_dev = len(jax.devices())
    mesh = data_parallel_mesh()
    if mesh is not None and max_batch < n_dev:
        # a bucket must hold >= one row per device to shard; a tiny
        # --max-batch on a big host serves unsharded instead of dying
        print(f"[serve] --max-batch {max_batch} < {n_dev} devices — "
              f"serving unsharded", flush=True)
        mesh = None
    buckets = serve_buckets(max_batch, n_dev if mesh is not None else 1)
    request_max = min(request_max, buckets[-1])
    mesh_ctx = jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()

    stats: dict = {"workload": workload, "restored_step": step,
                   "buckets": buckets, "devices": n_dev}
    with mesh_ctx:
        if mesh is not None:
            print(f"[serve] data-parallel over {n_dev} devices", flush=True)
        if scheduler is not None:
            _scheduler_loop(cfg, params, buckets, requests, request_max,
                            scheduler, seed, stats,
                            shard_base=n_dev if mesh is not None else 1,
                            preempt=preempt, pool_budget_mb=pool_budget_mb,
                            async_front=async_front)
        elif adaptive:
            _adaptive_terminal_loop(cfg, params, buckets, requests,
                                    request_max, atol, seed, stats)
        elif stream_chunks > 1:
            _stream_loop(workload, cfg, params, buckets, requests,
                         request_max, stream_chunks, seed, stats)
        else:
            _batch_loop(workload, cfg, params, buckets, requests, request_max,
                        latent_mode, obs_len, seed, stats)
    return stats


# -----------------------------------------------------------------------------
# drain loops
# -----------------------------------------------------------------------------


def _batch_loop(workload, cfg, params, buckets, requests, request_max,
                latent_mode, obs_len, seed, stats):
    from ..launch.steps import make_sample_step

    sampler = make_sample_step(workload, cfg, latent_mode=latent_mode,
                               obs_len=obs_len)
    pool = _compile_pool(sampler, params, buckets)

    pending = synthetic_requests(requests, request_max, seed)
    latencies, total_rows, n_batches = [], 0, 0
    t_start = time.perf_counter()
    while pending:
        batch, rows = _coalesce(pending, buckets[-1])
        bucket = next(b for b in buckets if b >= rows)
        keys = _request_keys(batch, bucket)
        ys = pool[bucket](params, keys)
        jax.block_until_ready(ys)
        t_now = time.perf_counter()
        latencies += [t_now - t_start] * len(batch)  # closed-loop: all at t0
        total_rows += rows
        n_batches += 1
    wall = time.perf_counter() - t_start
    _report(f"{workload}" + (f"/{latent_mode}" if workload == "latent-sde"
                             else ""),
            stats, total_rows, n_batches, latencies, wall)


def _adaptive_terminal_loop(cfg, params, buckets, requests, request_max,
                            atol, seed, stats):
    """Per-deadline-class terminal sampling (DESIGN.md §10/§11).

    One compiled program per bucket serves EVERY tolerance — ``rtol`` is a
    traced scalar argument of the sampler, so tolerance never enters the
    AOT cache key.  Requests are coalesced *within a deadline class* and
    each batch runs at the loosest rtol its tightest deadline allows
    (:func:`route_rtol` — the SLO routing rule that replaced PR 5's
    tightest-ask minimum).  Budget-exhausted rows come back on
    ``ServeResult.converged`` per request, not only as a log line.
    """
    import collections

    import numpy as np

    from ..launch.steps import make_adaptive_terminal_step

    pool = _compile_pool(make_adaptive_terminal_step(cfg, atol=atol), params,
                         buckets, jnp.asarray(1e-3, cfg.dtype),
                         tag="adaptive ")

    all_pending = synthetic_requests(requests, request_max, seed,
                                     adaptive=True)
    # bucket by deadline class FIRST (tightest first), FIFO within a class
    by_class = collections.OrderedDict(
        (c.name, collections.deque()) for c in DEADLINE_CLASSES)
    for r in all_pending:
        by_class[deadline_class_for(r.deadline_ms).name].append(r)

    results, latencies, total_rows, n_batches, non_converged = [], [], 0, 0, 0
    rtols_served = set()
    t_start = time.perf_counter()
    for cls_name, pending in by_class.items():
        while pending:
            batch, rows = _coalesce(pending, buckets[-1])
            bucket = next(b for b in buckets if b >= rows)
            keys = _request_keys(batch, bucket)
            batch_rtol = route_rtol(batch)  # loosest the deadlines allow
            rtols_served.add(batch_rtol)
            ys, conv = pool[bucket](params, keys,
                                    jnp.asarray(batch_rtol, cfg.dtype))
            jax.block_until_ready(ys)
            t_now = time.perf_counter()
            conv = np.asarray(conv)
            i = 0
            for r in batch:
                results.append(_terminal_result(r, conv[i:i + r.size],
                                                t_now - t_start, batch_rtol))
                i += r.size
            # padding rows don't count; a real non-converged row is a sample
            # at t_final < t1, not Y_T — carried per request on ServeResult
            non_converged += int((~conv[:rows]).sum())
            latencies += [t_now - t_start] * len(batch)
            total_rows += rows
            n_batches += 1
    wall = time.perf_counter() - t_start
    _report("sde-gan/adaptive", stats, total_rows, n_batches, latencies, wall)
    stats["rtols_served"] = sorted(rtols_served)
    stats["classes_served"] = [c for c, q in by_class.items() if not q]
    stats["compiled_programs"] = len(pool)
    stats["non_converged"] = non_converged
    stats["results"] = results
    print(f"[serve] adaptive: {len(rtols_served)} distinct tolerances "
          f"(deadline-routed across {len(by_class)} classes) served by "
          f"{len(pool)} compiled program(s) "
          f"(rtol is traced — no recompiles)", flush=True)
    if non_converged:
        print(f"[serve] WARNING: {non_converged}/{total_rows} rows exhausted "
              f"the adaptive step budget before t1 (served state is at "
              f"t_final < t1) — marked converged=False on their "
              f"ServeResult; raise max_steps or loosen the tolerance",
              flush=True)


def _terminal_result(request, conv, latency_s, rtol):
    from .types import ServeResult

    return ServeResult(rid=request.rid, model_id=request.model_id,
                       size=request.size, converged=conv,
                       latency_s=latency_s, deadline_ms=request.deadline_ms,
                       rtol=rtol)


def _stream_loop(workload, cfg, params, buckets, requests, request_max,
                 stream_chunks, seed, stats):
    """Long-horizon streaming: emit the trajectory in time chunks."""
    from ..core.sde import generator_initial_state
    from ..launch.steps import make_stream_chunk_step

    if workload != "sde-gan":
        raise ValueError("--stream-chunks streams the SDE-GAN generator "
                         "rollout; the latent decoder serves whole "
                         "trajectories")
    if cfg.num_steps % stream_chunks != 0:
        raise ValueError(
            f"--stream-chunks ({stream_chunks}) must divide the solver "
            f"horizon num_steps ({cfg.num_steps}) so chunks share a grid")
    span = cfg.t1 / stream_chunks
    steps_per_chunk = cfg.num_steps // stream_chunks
    jit_chunk = jax.jit(make_stream_chunk_step(cfg, span, steps_per_chunk))
    jit_init = jax.jit(lambda p, keys: generator_initial_state(p, cfg, keys))
    # AOT-compile both programs per bucket BEFORE the clock starts — the
    # t_start scalar is traced, so one chunk program covers every chunk
    init_pool, chunk_pool = {}, {}
    for b in buckets:
        keys = jax.random.split(jax.random.PRNGKey(0), b)
        t0 = time.perf_counter()
        init_pool[b] = jit_init.lower(params, keys).compile()
        x0 = init_pool[b](params, keys)
        chunk_pool[b] = jit_chunk.lower(
            params, keys, x0, jnp.asarray(0.0, cfg.dtype)).compile()
        print(f"[serve] compiled stream bucket {b} in "
              f"{time.perf_counter() - t0:.2f}s", flush=True)

    pending = synthetic_requests(requests, request_max, seed)
    latencies, first_chunk_ms, total_rows, n_batches = [], [], 0, 0
    t_start = time.perf_counter()
    while pending:
        batch, rows = _coalesce(pending, buckets[-1])
        bucket = next(b for b in buckets if b >= rows)
        keys = _request_keys(batch, bucket)
        x = init_pool[bucket](params, keys)
        t_batch0 = time.perf_counter()
        for c in range(stream_chunks):
            ckeys = jax.vmap(
                lambda k, c=c: jax.random.fold_in(k, 1000 + c))(keys)
            ys_c, x = chunk_pool[bucket](params, ckeys, x,
                                         jnp.asarray(c * span, cfg.dtype))
            jax.block_until_ready(ys_c)  # "emitted" to the client here
            if c == 0:
                first_chunk_ms.append((time.perf_counter() - t_batch0) * 1e3)
        t_now = time.perf_counter()
        latencies += [t_now - t_start] * len(batch)
        total_rows += rows
        n_batches += 1
    wall = time.perf_counter() - t_start
    _report(f"sde-gan/stream×{stream_chunks}", stats, total_rows, n_batches,
            latencies, wall)
    stats["first_chunk_ms"] = sum(first_chunk_ms) / len(first_chunk_ms)
    print(f"[serve] stream: mean first-chunk latency "
          f"{stats['first_chunk_ms']:.1f}ms "
          f"({steps_per_chunk}/{cfg.num_steps} steps per chunk)", flush=True)


def _scheduler_loop(cfg, params, buckets, requests, request_max, mode, seed,
                    stats, shard_base: int = 1, preempt: bool = False,
                    pool_budget_mb: Optional[float] = None,
                    async_front: bool = False):
    """Drive the continuous-batching :class:`Scheduler` over the synthetic
    stream (closed-loop: everything arrives at t0; the open-loop Poisson
    driver lives in benchmarks/serving.py).  With ``async_front`` the same
    stream is pushed through :class:`~repro.serving.AsyncFrontend` — N
    concurrent ``submit`` coroutines over the asyncio ingestion path —
    instead of calling ``step`` directly."""
    budget = (None if pool_budget_mb is None
              else int(pool_budget_mb * 2 ** 20))
    registry = ModelRegistry(pool_budget_bytes=budget)
    registry.register(LoadedModel("default", "sde-gan", cfg, params))
    chunks = 4 if cfg.num_steps % 4 == 0 else 1
    sched = Scheduler(registry, max_batch=buckets[-1], chunks=chunks,
                      mode=mode, shard_base=shard_base, preempt=preempt)
    sched.warm("default")
    pending = synthetic_requests(requests, request_max, seed)
    t_start = time.perf_counter()
    if async_front:
        results, n_iter = _drain_async(sched, pending)
    else:
        for r in pending:
            sched.submit(r, arrival_s=0.0)
        results, n_iter = [], 0
        while sched.busy:
            results += sched.step()
            n_iter += 1
    wall = time.perf_counter() - t_start
    _report(f"sde-gan/scheduler-{mode}×{chunks}chunks", stats,
            sum(r.size for r in results), n_iter,
            [r.latency_s for r in results], wall)
    stats.update(latency_summary(results), scheduler=mode, chunks=chunks)
    stats.update(preempt=preempt, frontend="asyncio" if async_front
                 else "direct")
    if budget is not None:
        stats.update(pool_budget_bytes=budget,
                     pool_bytes=registry.pool_bytes(),
                     pool_evictions=registry.evictions)
        print(f"[serve] pool budget {pool_budget_mb:g} MB: "
              f"{registry.pool_bytes()} B resident, "
              f"{registry.evictions} evictions", flush=True)
    print(f"[serve] scheduler: mode={mode}, {len(results)} requests, "
          f"pools={len(registry.pool_keys('default'))} compiled programs "
          f"(chunk t_start per-row traced — admission at chunk boundaries)",
          flush=True)


def _drain_async(sched, pending):
    """Closed-loop drain over the asyncio frontend: one ``submit``
    coroutine per request (all arrivals stamped t=0), gathered to
    completion.  Returns ``(results, engine iterations)``."""
    import asyncio

    from .frontend import AsyncFrontend

    async def drive():
        front = AsyncFrontend(sched)
        await front.start()
        try:
            results = await asyncio.gather(
                *(front.submit(r, arrival_s=0.0) for r in pending))
        finally:
            await front.close()
        return list(results), front.steps

    return asyncio.run(drive())
