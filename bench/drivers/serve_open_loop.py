"""Open-loop trajectory serving: requests arrive on a schedule fixed by the
traffic file and the seed, whether or not earlier ones have finished, and
go in through ``repro.serving.AsyncFrontend.submit`` to one continuous-
batching ``Scheduler`` over a ``ModelRegistry``.

The traffic is one multiset of request sizes and inter-arrival gaps per
cell, the same for every seed: sizes at the quantiles of a log-uniform law
and gaps at the quantiles of an exponential one (Poisson arrivals at the
cell's rate).  The seed orders both, draws the deadline classes and keys
the rows, so every run does the same work in another order.

Each request is timed from when it was due to when its future resolved.
After the window the rest of the requests are waited for (a minute at
most); a sample of them, drawn from the seed and holding the largest, is
compared row by row with the reference rollout of each row's key.
"""

from __future__ import annotations

import asyncio
import functools
import gc
import math
import time

import numpy as np

from bench import compare, device, stats
from bench.reference import plain

#: How long after the window closes the run waits for answers.
DRAIN_S = 60.0


def traffic(params: dict, seed: int, seconds: float) -> list:
    """``[(due_s, size, deadline_ms, request_seed)]`` sorted by due time."""
    n = max(1, round(params["rate_per_s"] * seconds))
    rng = np.random.default_rng(seed)
    q = (np.arange(n) + 0.5) / n
    lo, hi = params["size_min"], params["size_max"]
    sizes = np.rint(np.exp(np.log(lo) + (np.log(hi) - np.log(lo)) * q))
    gaps = -np.log1p(-q) / params["rate_per_s"]
    sizes = rng.permutation(sizes).astype(int)
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    shares = [c["share"] for c in params["classes"]]
    deadlines = [math.inf if c["deadline_ms"] is None else c["deadline_ms"]
                 for c in params["classes"]]
    cls = rng.choice(len(shares), size=n, p=np.asarray(shares) / sum(shares))
    seeds = rng.integers(0, 2**31 - 1, size=n)
    return [(float(due[i]), int(sizes[i]), deadlines[cls[i]], int(seeds[i]))
            for i in range(n)]


class _TimedStep:
    """The benchmark's own wrapper around ``Scheduler.step``: host seconds
    of each call while ``on``."""

    def __init__(self, step):
        self._step = step
        self.seconds = []
        self.on = False

    def __call__(self):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.scheduler_step"):
            out = self._step()
        if self.on:
            self.seconds.append(time.perf_counter() - t0)
        return out


def _planted(registry, variant: str):
    """Wrap the registry's chunk programs with a fault: rows altered where
    they are produced, or a state that never advances."""
    compiled = registry.compiled

    def faulty(model_id, kind, bucket, builder, **kw):
        program = compiled(model_id, kind, bucket, builder, **kw)
        if kind != "chunk":
            return program

        def run(params, keys, x0, t_start):
            ys, x_next = program(params, keys, x0, t_start)
            if variant == "altered":
                return ys.at[-1, 0].add(1.0), x_next
            return ys, x0

        return run

    registry.compiled = faulty


def _warm(sched, bucket_sizes, max_request: int, seed: int) -> None:
    """Compile every program and every eager operation the window can
    reach: each bucket once through admission and a whole horizon."""
    from repro.serving import Request

    rid = -1
    sched.warm("default")
    for b in bucket_sizes:
        left = b
        while left:
            size = min(left, max_request)
            sched.submit(Request(rid=rid, size=size, seed=seed - rid))
            rid -= 1
            left -= size
        sched.run()


async def _drive(sched, reqs, seconds: float, timed: _TimedStep):
    from repro.serving import AsyncFrontend, Request

    front = AsyncFrontend(sched)
    await front.start()
    results = {}
    late = []
    loop_t0 = time.perf_counter()
    sched_t0 = sched.now()

    async def client(i, due, size, deadline_ms, rseed):
        await asyncio.sleep(max(0.0, loop_t0 + due - time.perf_counter()))
        late.append(time.perf_counter() - (loop_t0 + due))
        try:
            res = await front.submit(
                Request(rid=i, size=size, seed=rseed, deadline_ms=deadline_ms),
                arrival_s=sched_t0 + due)
        except Exception as e:  # noqa: BLE001 - a failed request is counted
            results[i] = e
            return
        results[i] = (res, time.perf_counter() - (loop_t0 + due))

    timed.on = True
    tasks = [asyncio.ensure_future(client(i, *r)) for i, r in enumerate(reqs)]
    await asyncio.sleep(max(0.0, loop_t0 + seconds - time.perf_counter()))
    window_end = time.perf_counter()
    done, _ = await asyncio.wait(tasks, timeout=DRAIN_S)
    drained = time.perf_counter()
    timed.on = False
    for t in tasks:
        if t not in done:
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    await front.close()
    return results, late, loop_t0, window_end, drained


def run(ctx) -> dict:
    import jax
    from repro.serving import ModelRegistry, Scheduler

    params = ctx.cell["params"]
    reference = ctx.layout.reference(ctx.config["name"])
    model = ctx.layout.model(ctx.config["kind"])
    weights = jax.jit(functools.partial(reference.init, config=ctx.config))(
        jax.random.PRNGKey(ctx.seed))
    registry = ModelRegistry()
    registry.register(model.serving_model(ctx.config, weights["gen"],
                                          ctx.config["precision"]["fields"]))
    if ctx.variant in ("altered", "unchanged"):
        _planted(registry, ctx.variant)
    sched = Scheduler(registry, max_batch=params["max_batch"],
                      chunks=params["chunks"], mode=params["mode"],
                      collect=True)
    _warm(sched, sched.buckets, params["size_max"], 2**30 + ctx.seed % 2**29)
    timed = _TimedStep(sched.step)
    sched.step = timed
    reqs = traffic(params, ctx.seed, ctx.seconds)
    batches0 = sched.counters["chunk_batches"]

    with ctx.compiles.counting(), ctx.profiled() as trace_dir:
        results, late, t0, window_end, drained = asyncio.run(
            _drive(sched, reqs, ctx.seconds, timed))
    memory = device.memory_peak(ctx.chips)
    batches = sched.counters["chunk_batches"] - batches0

    answered = {i: r for i, r in results.items() if isinstance(r, tuple)}
    latencies = [lat for _, lat in answered.values()]
    failed = len(reqs) - len(answered)

    # the sample: the largest request and others drawn from the seed, up
    # to some hundreds of rows
    rng = np.random.default_rng(ctx.seed + 1)
    order = sorted(answered, key=lambda i: -reqs[i][1])
    sample, rows = order[:1], reqs[order[0]][1] if order else 0
    for i in rng.permutation(order[1:]):
        if rows + reqs[i][1] > params["check_rows"]:
            continue
        sample.append(int(i))
        rows += reqs[i][1]
    served = [np.asarray(answered[i][0].samples) for i in sample]
    gen = jax.tree.map(np.asarray, weights["gen"])
    del sched, registry, weights, results
    gc.collect()

    # every sampled row in one call: (request seed, row index), padded to a
    # fixed count so the reference compiles once
    seeds = np.zeros(params["check_rows"], np.int32)
    rows_j = np.zeros(params["check_rows"], np.int32)
    at = 0
    for i in sample:
        seeds[at:at + reqs[i][1]] = reqs[i][3]
        rows_j[at:at + reqs[i][1]] = np.arange(reqs[i][1])
        at += reqs[i][1]
    def reference_rows(dot):
        with jax.default_matmul_precision("highest"):
            roll = jax.jit(jax.vmap(functools.partial(
                reference.rollout, config=ctx.config, chunks=params["chunks"],
                dot=dot), in_axes=(None, 0, 0)))
            return np.asarray(roll(gen, seeds, rows_j))  # (rows, time, data)

    want = reference_rows(plain.HIGHEST)
    if ctx.variant == "control":
        # the reference at three bf16 passes, put in the program's place
        got = np.moveaxis(reference_rows(plain.THREE_PASS), 0, 1)
        starts = np.cumsum([0] + [reqs[i][1] for i in sample])
        served = [got[:, a:a + reqs[i][1]] for a, i in zip(starts, sample)]
    gaps, at = [], 0
    for i, got in zip(sample, served):
        n = reqs[i][1]
        gaps.append(compare.rows_gap(got, np.moveaxis(want[at:at + n], 0, 1)))
        at += n
    limits = ctx.cell["limits"]
    checks = [compare.Check("rows", max(gaps) if gaps else math.nan,
                            limits["rows"]),
              compare.Check("unanswered", float(failed), 0.0)]

    sizes = sum(reqs[i][1] for i in answered)
    return {
        "window_start": t0,
        "window_s": drained - t0,
        "end_to_end": ({"serve_p95_ms": 1e3 * stats.percentile(latencies, 0.95),
                        "serve_p50_ms": 1e3 * stats.percentile(latencies, 0.5)}
                       if latencies else {}),
        "checks": checks,
        "attempted": len(reqs),
        "failed": failed,
        "memory_peak_bytes": memory,
        "trace_dir": trace_dir,
        "facts": {
            "step_seconds": list(timed.seconds),
            "chunk_batches": batches,
            "rows_advanced": sizes * params["chunks"],
            "generator_late_p95_ms": 1e3 * stats.percentile(late, 0.95)
            if late else None,
            "offered_per_s": len(reqs) / ctx.seconds,
            "drain_s": drained - window_end,
        },
    }
