"""Continuous-batching scheduler (DESIGN.md §11).

Orca-style iteration-level scheduling mapped onto the compiled-chunk
rollout machinery: a long-horizon trajectory advances chunk-by-chunk
through ONE AOT-compiled chunk program per ``(model_id, bucket)``
(``t_start`` is a *per-row traced vector*, so rows at different horizon
positions share a batch), and newly admitted requests join the in-flight
batch at the next chunk boundary instead of waiting for it to drain.

The admission rule: free slots = largest bucket − active rows; pending
rollout requests are admitted in arrival order (head-of-line, no
skipping) whenever slots are free.  ``mode="fifo"`` degrades admission to
the PR 4 baseline — a batch drains fully before the next coalesce — with
the SAME compiled programs, so the two modes differ only in WHEN
admission happens (the comparison ``benchmarks/serving.py`` gates on).

Joining mid-flight is bitwise-invisible: every row is a pure function of
``(params, request seed, row index, chunk index)`` — base key
``fold_in(PRNGKey(seed), j)``, chunk key ``fold_in(base, 1000 + c)`` —
the identical keying the PR 4 stream loop used, so a request admitted
into a half-full in-flight batch produces the trajectories it would have
produced solo (tests/test_serving_scheduler.py pins this bitwise).  Open
defect: programs of different bucket sizes may round a row differently
(DESIGN.md §11), so a request served alone through a smaller bucket can
differ from its rows in a fuller batch by a few ulps.

Adaptive *terminal* requests ride the same scheduler: they are coalesced
per deadline class and each batch runs at the tolerance
:func:`repro.serving.route_rtol` picks — the loosest rtol the batch's
tightest deadline allows — through one traced-rtol compiled program per
``(model_id, bucket)``.

PR 10 adds **per-model admission quotas** and **cross-lane preemption**
(DESIGN.md §14).  A quota caps how many rows one model may hold in
flight, so a burst on one lane cannot monopolise the iteration.  With
``preempt=True``, whenever any lane has realtime-class work pending or in
flight, every *other* lane's relaxed-class rollout rows yield at their
next chunk boundary: they move from ``active`` to ``paused`` (their
carried state and chunk index travel with them) and the lane's
loosest-class terminal batches are deferred, so the iteration's device
time goes to the deadline-bound work.  Because every chunk is a pure
function of ``(params, seed, row, chunk index)`` and a paused row resumes
at exactly the chunk it yielded before, preemption — like mid-flight
admission — is bitwise-invisible to the preempted trajectory
(tests/test_serving_async.py pins this against the solo scheduler).
"""

from __future__ import annotations

import itertools
import math
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .registry import ModelRegistry
from .types import (DEADLINE_CLASSES, PAD_SEED, Request, ServeResult,
                    deadline_class_for, route_rtol)

#: Chunk-key fold offset — MUST stay equal to the stream loop's constant
#: so scheduler rollouts are bitwise the PR 4 streamed rollouts.
_CHUNK_FOLD = 1000


def serve_buckets(max_batch: int, shard_base: int) -> list:
    """Bucket sizes: shard_base × powers of two, up to ``max_batch``.

    ``shard_base`` is the device count when a mesh is active (every bucket
    must divide exactly for the data-parallel in_sharding), else 1.  The
    largest bucket caps how many rows one coalesced batch may hold — it is
    the scheduler's admission slot grid.
    """
    sizes = []
    b = max(shard_base, 1)
    while b <= max_batch:
        sizes.append(b)
        b *= 2
    if not sizes:
        raise ValueError(
            f"--max-batch {max_batch} is below the shard base {shard_base}; "
            f"the smallest servable bucket is one row per device")
    return sizes


def _row_base_key(seed: int, j: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed), j)


def _pad_keys(n: int, offset: int = 0):
    return [jax.random.fold_in(jax.random.PRNGKey(PAD_SEED), offset + i)
            for i in range(n)]


class _InFlight:
    """Book-keeping for one admitted request."""

    def __init__(self, request: Request, arrival_s: float):
        self.request = request
        self.arrival_s = arrival_s
        self.rows_left = request.size
        self.chunks: dict = {}  # j -> list of (steps_per, data_dim) arrays


class _Row:
    """One in-flight trajectory row: its request, row index, carried
    hidden state, and how many chunks it has completed."""

    __slots__ = ("flight", "j", "x", "chunk_idx")

    def __init__(self, flight: _InFlight, j: int, x):
        self.flight = flight
        self.j = j
        self.x = x
        self.chunk_idx = 0


class _Lane:
    """Per-model scheduling state (models never share a compiled batch)."""

    def __init__(self, model, chunks: int, quota: Optional[int] = None):
        cfg = model.cfg
        if cfg.num_steps % chunks != 0:
            raise ValueError(
                f"model {model.model_id!r}: chunks ({chunks}) must divide "
                f"the solver horizon num_steps ({cfg.num_steps}) so chunks "
                f"share a grid")
        if quota is not None and quota < 1:
            raise ValueError(
                f"model {model.model_id!r}: admission quota must be >= 1 "
                f"(got {quota}) — a zero quota can never serve")
        self.model = model
        self.chunks = chunks
        self.quota = quota
        self.span = cfg.t1 / chunks
        self.steps_per = cfg.num_steps // chunks
        self.pending_roll: list = []   # (sort_key, seq, _InFlight)
        self.pending_term: list = []   # (seq, Request, arrival_s)
        self.active: list = []         # [_Row]
        self.paused: list = []         # [_Row] preempted at a chunk boundary

    @property
    def busy(self) -> bool:
        return bool(self.pending_roll or self.pending_term or self.active
                    or self.paused)


class Scheduler:
    """The continuous-batching serving scheduler (public API).

    Drives one or more registry models; every compiled program is cached
    in the registry keyed ``(model_id, kind, bucket)``.

    Args:
        registry: the :class:`~repro.serving.ModelRegistry` to serve from.
        max_batch: largest bucket (the admission slot grid's width).
        chunks: time chunks per rollout horizon — the admission quantum.
            Must divide every served model's ``num_steps``.
        mode: ``"continuous"`` (admit at every chunk boundary) or
            ``"fifo"`` (PR 4 baseline: drain fully, then coalesce).
        classes: the deadline→tolerance SLO ladder for terminal requests.
        atol / max_steps: adaptive terminal sampling controller limits.
        collect: keep per-row payloads and attach them to
            :class:`ServeResult` (tests want trajectories; load tests
            don't want the host round-trip).
        shard_base: bucket granularity (device count under a mesh).
        clock: injectable time source (seconds) for deterministic tests.
        preempt: enable cross-lane preemption (DESIGN.md §14) — while any
            lane has realtime-class work pending or in flight, other
            lanes' relaxed-class rollout rows pause at their next chunk
            boundary and their relaxed terminal batches defer.  Bitwise-
            invisible to the preempted trajectories.
        quota: per-model admission cap on in-flight rows — an int applies
            to every lane, a ``{model_id: int}`` dict per lane (models
            absent from the dict fall back to the bundle's ``serving``
            hint, then to unlimited).  Pending requests over quota wait
            in arrival order; they are never dropped.
    """

    def __init__(self, registry: ModelRegistry, *, max_batch: int = 16,
                 chunks: int = 4, mode: str = "continuous",
                 classes=DEADLINE_CLASSES, atol: float = 1e-6,
                 max_steps: int = 4096, collect: bool = False,
                 shard_base: int = 1, clock=time.perf_counter,
                 preempt: bool = False, quota=None):
        if mode not in ("continuous", "fifo"):
            raise ValueError(
                f"mode must be 'continuous' or 'fifo', got {mode!r}")
        if chunks < 1:
            raise ValueError(f"chunks must be >= 1, got {chunks}")
        if quota is not None and not isinstance(quota, (int, dict)):
            raise TypeError(
                f"quota must be an int (every model), a dict "
                f"{{model_id: int}}, or None, got {type(quota).__name__}")
        self.registry = registry
        self.buckets = serve_buckets(max_batch, shard_base)
        self.chunks = chunks
        self.mode = mode
        self.classes = classes
        self.atol = atol
        self.max_steps = max_steps
        self.collect = collect
        self.preempt = preempt
        self.quota = quota
        #: Observable scheduling counters (benchmarks charge virtual time
        #: per executed batch; tests assert preemption really engaged).
        self.counters = {"chunk_batches": 0, "terminal_batches": 0,
                         "preempted_rows": 0, "resumed_rows": 0}
        self._clock = clock
        self._t0 = clock()
        self._seq = itertools.count()
        self._lanes: dict = {}
        # Every batch operand is re-stacked on the host each iteration, so
        # its sharding must be pinned explicitly — the compiled programs are
        # lowered AND called through _put, keeping AOT input shardings and
        # runtime arrays bitwise in agreement under a data-parallel mesh.
        self._mesh = None
        if shard_base > 1:
            from ..distributed.sharding import data_parallel_mesh

            self._mesh = data_parallel_mesh()

    def _put(self, arr):
        """Pin a batch-major array to the data-parallel sharding (no-op
        unsharded)."""
        if self._mesh is None:
            return arr
        spec = P("data") if arr.ndim >= 1 else P()
        return jax.device_put(arr, NamedSharding(self._mesh, spec))

    # -- submission ---------------------------------------------------------

    def now(self) -> float:
        """Seconds since scheduler construction on the injectable clock
        (virtual under the benchmark drivers, wall time by default)."""
        return self._clock() - self._t0

    def _quota_for(self, model) -> Optional[int]:
        """Resolve one model's admission quota: the scheduler's explicit
        ``quota`` argument wins, then the bundle's ``serving: {quota: N}``
        hint (:attr:`LoadedModel.hints`), then unlimited."""
        if isinstance(self.quota, int):
            return self.quota
        if isinstance(self.quota, dict) and model.model_id in self.quota:
            return self.quota[model.model_id]
        hint = getattr(model, "hints", None) or {}
        return hint.get("quota")

    def _lane(self, model_id: str) -> _Lane:
        if model_id not in self._lanes:
            model = self.registry.get(model_id)
            if model.workload != "sde-gan":
                raise ValueError(
                    f"model {model_id!r} is a {model.workload!r} workload; "
                    f"the continuous-batching scheduler serves the SDE-GAN "
                    f"generator (chunked rollouts / adaptive terminal "
                    f"samples) — serve latent-sde decodes through "
                    f"repro.serving.serve_sde's coalescing loop")
            self._lanes[model_id] = _Lane(model, self.chunks,
                                          quota=self._quota_for(model))
        return self._lanes[model_id]

    def submit(self, request: Request,
               arrival_s: Optional[float] = None) -> None:
        """Enqueue one request (``arrival_s`` defaults to the scheduler
        clock's now — open-loop drivers pass the synthetic arrival time so
        reported latency includes queueing delay)."""
        if request.size > self.buckets[-1]:
            raise ValueError(
                f"request {request.rid}: size {request.size} exceeds the "
                f"largest bucket {self.buckets[-1]} — raise max_batch or "
                f"split the request")
        lane = self._lane(request.model_id)
        arrival = self.now() if arrival_s is None else arrival_s
        seq = next(self._seq)
        if request.kind == "terminal":
            lane.pending_term.append((seq, request, arrival))
        else:
            # rollouts admit in arrival order in BOTH modes — deliberately
            # no deadline reordering (EDF starves the relaxed class under
            # sustained tight-deadline load), so the continuous-vs-fifo
            # comparison isolates WHEN admission happens (chunk boundaries
            # vs full drain).  Deadlines instead drive the terminal
            # batches' tolerance routing (route_rtol).
            lane.pending_roll.append(((seq,), seq, _InFlight(request,
                                                             arrival)))

    @property
    def busy(self) -> bool:
        """True while any lane holds pending, in-flight, or paused work."""
        return any(lane.busy for lane in self._lanes.values())

    # -- compiled programs (registry-cached) --------------------------------

    def _bucket_for(self, rows: int) -> int:
        return next(b for b in self.buckets if b >= rows)

    def _init_pool(self, lane: _Lane, bucket: int):
        from ..core.sde import generator_initial_state

        model, cfg = lane.model, lane.model.cfg

        def build():
            keys = self._put(jax.random.split(jax.random.PRNGKey(0), bucket))
            fn = jax.jit(lambda p, k: generator_initial_state(p, cfg, k))
            return fn.lower(model.params, keys).compile()

        return self.registry.compiled(model.model_id, "init", bucket, build)

    def _chunk_pool(self, lane: _Lane, bucket: int):
        from ..launch.steps import make_stream_chunk_step

        model, cfg = lane.model, lane.model.cfg

        def build():
            keys = self._put(jax.random.split(jax.random.PRNGKey(0), bucket))
            x0 = self._put(self._init_pool(lane, bucket)(model.params, keys))
            ts = self._put(jnp.zeros((bucket,), cfg.dtype))
            fn = jax.jit(make_stream_chunk_step(cfg, lane.span,
                                                lane.steps_per))
            return fn.lower(model.params, keys, x0, ts).compile()

        return self.registry.compiled(model.model_id, "chunk", bucket, build)

    def _terminal_pool(self, lane: _Lane, bucket: int):
        from ..launch.steps import make_adaptive_terminal_step

        model, cfg = lane.model, lane.model.cfg

        def build():
            keys = self._put(jax.random.split(jax.random.PRNGKey(0), bucket))
            fn = jax.jit(make_adaptive_terminal_step(
                cfg, atol=self.atol, max_steps=self.max_steps))
            return fn.lower(model.params, keys,
                            jnp.asarray(1e-3, cfg.dtype)).compile()

        return self.registry.compiled(model.model_id, "terminal", bucket,
                                      build)

    def warm(self, model_id: str, kinds=("init", "chunk")) -> None:
        """Pre-compile a model's pools for every bucket (load tests call
        this so compiles never ride the latency measurements)."""
        lane = self._lane(model_id)
        for b in self.buckets:
            if "init" in kinds:
                self._init_pool(lane, b)
            if "chunk" in kinds:
                self._chunk_pool(lane, b)
            if "terminal" in kinds:
                self._terminal_pool(lane, b)

    # -- the iteration ------------------------------------------------------

    def step(self) -> List[ServeResult]:
        """One scheduler iteration: per lane, serve at most one terminal
        batch, admit pending rollouts into free slots, and advance every
        in-flight row one chunk.  With ``preempt=True``, lanes without
        realtime-class work first yield their relaxed-class rows (pause /
        defer) whenever any other lane has realtime work outstanding, and
        paused rows resume once the pressure clears.  Returns the requests
        completed by this iteration."""
        results: List[ServeResult] = []
        urgent = self._urgent_lanes() if self.preempt else frozenset()
        for model_id, lane in self._lanes.items():
            yield_now = bool(urgent) and model_id not in urgent
            if self.preempt:
                if yield_now:
                    self._pause_relaxed(lane)
                else:
                    self._resume(lane)
            results += self._step_terminal(lane, defer_relaxed=yield_now)
            self._admit(lane)
            results += self._advance(lane)
        return results

    # -- preemption (DESIGN.md §14) -----------------------------------------

    def _is_realtime(self, request: Request) -> bool:
        return (deadline_class_for(request.deadline_ms, self.classes)
                is self.classes[0])

    def _is_relaxed(self, request: Request) -> bool:
        return (deadline_class_for(request.deadline_ms, self.classes)
                is self.classes[-1])

    def _urgent_lanes(self) -> frozenset:
        """Model ids with realtime-class work pending or in flight.  A
        pending realtime deadline (≤ the tightest class bound) is always
        treated as at-risk: one full drain of another lane's chunk batch
        already costs a realtime-scale budget, so the policy does not try
        to predict the miss — it yields whenever realtime work exists."""
        urgent = set()
        for model_id, lane in self._lanes.items():
            if (any(self._is_realtime(f.request)
                    for _, _, f in lane.pending_roll)
                    or any(self._is_realtime(req)
                           for _, req, _ in lane.pending_term)
                    or any(self._is_realtime(r.flight.request)
                           for r in lane.active)):
                urgent.add(model_id)
        return frozenset(urgent)

    def _pause_relaxed(self, lane: _Lane) -> None:
        """Move the lane's relaxed-class rollout rows from ``active`` to
        ``paused`` — the chunk-boundary yield.  Rows carry their hidden
        state and chunk index, so resuming is bitwise-invisible."""
        still, paused = [], []
        for row in lane.active:
            (paused if self._is_relaxed(row.flight.request)
             else still).append(row)
        if paused:
            lane.active = still
            lane.paused += paused
            self.counters["preempted_rows"] += len(paused)

    def _resume(self, lane: _Lane) -> None:
        """Re-activate paused rows (pause order — they were admitted
        before anything still pending) while bucket capacity allows."""
        while lane.paused and len(lane.active) < self.buckets[-1]:
            lane.active.append(lane.paused.pop(0))
            self.counters["resumed_rows"] += 1

    def run(self) -> List[ServeResult]:
        """Drain every queue; returns all results (completion order)."""
        results: List[ServeResult] = []
        while self.busy:
            results += self.step()
        return results

    def _admit(self, lane: _Lane) -> None:
        if self.mode == "fifo" and (lane.active or lane.paused):
            return  # baseline: the in-flight batch drains before coalescing
        in_flight = len(lane.active) + len(lane.paused)
        capacity = self.buckets[-1] - in_flight
        if lane.quota is not None:
            # the per-model admission quota: paused rows still hold their
            # admission (they yielded compute, not their slot)
            capacity = min(capacity, lane.quota - in_flight)
        admitted: list = []
        while (lane.pending_roll
               and lane.pending_roll[0][2].request.size <= capacity):
            _, _, flight = lane.pending_roll.pop(0)
            admitted.append(flight)
            capacity -= flight.request.size
        if not admitted:
            return
        # initial states for every newly admitted row, in one padded batch
        new_keys = [_row_base_key(f.request.seed, j)
                    for f in admitted for j in range(f.request.size)]
        bucket = self._bucket_for(len(new_keys))
        keys = self._put(jnp.stack(new_keys
                                   + _pad_keys(bucket - len(new_keys))))
        x0 = self._init_pool(lane, bucket)(lane.model.params, keys)
        i = 0
        for flight in admitted:
            for j in range(flight.request.size):
                lane.active.append(_Row(flight, j, x0[i]))
                i += 1

    def _advance(self, lane: _Lane) -> List[ServeResult]:
        if not lane.active:
            return []
        cfg = lane.model.cfg
        bucket = self._bucket_for(len(lane.active))
        n = len(lane.active)
        keys = self._put(jnp.stack(
            [jax.random.fold_in(_row_base_key(r.flight.request.seed, r.j),
                                _CHUNK_FOLD + r.chunk_idx)
             for r in lane.active] + _pad_keys(bucket - n, offset=1)))
        x = self._put(jnp.stack(
            [r.x for r in lane.active]
            + [jnp.zeros_like(lane.active[0].x)] * (bucket - n)))
        t_starts = self._put(jnp.asarray(
            [r.chunk_idx * lane.span for r in lane.active]
            + [0.0] * (bucket - n), cfg.dtype))
        ys, x_next = self._chunk_pool(lane, bucket)(
            lane.model.params, keys, x, t_starts)
        jax.block_until_ready(x_next)
        self.counters["chunk_batches"] += 1

        results: List[ServeResult] = []
        still_active: list = []
        if self.collect:
            ys_host = np.asarray(ys)
        for i, row in enumerate(lane.active):
            if self.collect:
                # chunk 0 contributes its entry row; later chunks' entry
                # rows duplicate the previous chunk's final row
                lo = 0 if row.chunk_idx == 0 else 1
                row.flight.chunks.setdefault(row.j, []).append(
                    ys_host[lo:, i])
            row.x = x_next[i]
            row.chunk_idx += 1
            if row.chunk_idx < lane.chunks:
                still_active.append(row)
                continue
            flight = row.flight
            flight.rows_left -= 1
            if flight.rows_left == 0:
                results.append(self._finish(flight))
        lane.active = still_active
        return results

    def _finish(self, flight: _InFlight) -> ServeResult:
        req = flight.request
        samples = None
        if self.collect:
            samples = np.stack(
                [np.concatenate(flight.chunks[j]) for j in range(req.size)],
                axis=1)
        return ServeResult(
            rid=req.rid, model_id=req.model_id, size=req.size,
            converged=np.ones(req.size, bool),
            latency_s=self.now() - flight.arrival_s,
            deadline_ms=req.deadline_ms, rtol=None, samples=samples)

    # -- adaptive terminal batches (SLO-routed) -----------------------------

    def _step_terminal(self, lane: _Lane,
                       defer_relaxed: bool = False) -> List[ServeResult]:
        if not lane.pending_term:
            return []
        # coalesce within ONE deadline class per iteration, tightest class
        # first — the class keys both the admission grouping and (via
        # route_rtol) the tolerance the batch runs at
        by_class: dict = {}
        for seq, req, arrival in lane.pending_term:
            by_class.setdefault(
                deadline_class_for(req.deadline_ms, self.classes).name,
                []).append((seq, req, arrival))
        for cls in self.classes:
            if cls.name in by_class:
                entries = by_class[cls.name]
                break
        if defer_relaxed and cls is self.classes[-1]:
            # preemption pressure: the lane's best pending terminal work is
            # relaxed-class — defer it so the urgent lane gets this
            # iteration's device time (deadline-bound classes still serve)
            return []
        batch, rows = [], 0
        while entries and rows + entries[0][1].size <= self.buckets[-1]:
            batch.append(entries.pop(0))
            rows += batch[-1][1].size
        taken = {seq for seq, _, _ in batch}
        lane.pending_term = [e for e in lane.pending_term
                             if e[0] not in taken]
        reqs = [req for _, req, _ in batch]
        rtol = route_rtol(reqs, self.classes)

        cfg = lane.model.cfg
        bucket = self._bucket_for(rows)
        keys = self._put(jnp.stack(
            [_row_base_key(r.seed, j) for r in reqs for j in range(r.size)]
            + _pad_keys(bucket - rows)))
        samples, conv = self._terminal_pool(lane, bucket)(
            lane.model.params, keys, jnp.asarray(rtol, cfg.dtype))
        jax.block_until_ready(conv)
        self.counters["terminal_batches"] += 1
        conv = np.asarray(conv)
        samples = np.asarray(samples) if self.collect else None

        results, i = [], 0
        now = self.now()
        for _, req, arrival in batch:
            results.append(ServeResult(
                rid=req.rid, model_id=req.model_id, size=req.size,
                converged=conv[i:i + req.size], latency_s=now - arrival,
                deadline_ms=req.deadline_ms, rtol=rtol,
                samples=None if samples is None else samples[i:i + req.size]))
            i += req.size
        return results


def run_open_loop(scheduler: Scheduler, requests, arrivals_s) -> list:
    """Open-loop driver: feed ``requests`` at their synthetic ``arrivals_s``
    offsets (seconds from start) regardless of service progress — offered
    load is fixed by the arrival process, not by completions (the
    closed-loop fallacy the load generator exists to avoid).  Returns every
    :class:`ServeResult`; latencies include queueing delay."""
    feed = sorted(zip(arrivals_s, range(len(requests))))
    results = []
    i = 0
    while i < len(feed) or scheduler.busy:
        now = scheduler.now()
        while i < len(feed) and feed[i][0] <= now:
            arrival, idx = feed[i]
            scheduler.submit(requests[idx], arrival_s=arrival)
            i += 1
        if scheduler.busy:
            results += scheduler.step()
        elif i < len(feed):
            time.sleep(max(0.0, min(feed[i][0] - scheduler.now(), 0.01)))
    return results


def latency_summary(results, q=(0.5, 0.99)) -> dict:
    """p50/p99 (nearest-rank) + throughput off a result list."""
    from .types import percentile

    lat = [r.latency_s for r in results]
    rows = sum(r.size for r in results)
    out = {f"p{int(100 * x)}_s": percentile(lat, x) for x in q}
    out["requests"] = len(results)
    out["rows"] = rows
    out["deadline_misses"] = sum(
        1 for r in results if not r.deadline_met
        and math.isfinite(r.deadline_ms))
    return out


def class_latency_summary(results, classes=DEADLINE_CLASSES) -> dict:
    """Per-deadline-class :func:`latency_summary`: ``{class name: summary}``
    over the classes that actually appear in ``results``.  The per-class
    tails are what the preemption gate reads — an aggregate p99 hides a
    realtime-class miss behind the relaxed-class bulk."""
    by_cls: dict = {}
    for r in results:
        by_cls.setdefault(deadline_class_for(r.deadline_ms, classes).name,
                          []).append(r)
    return {name: latency_summary(rs) for name, rs in by_cls.items()}
