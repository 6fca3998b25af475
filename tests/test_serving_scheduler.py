"""Continuous-batching scheduler tests (DESIGN.md §11).

The PR 7 contracts: the ``repro-serving/v1`` → ``v2`` bundle upgrade is
bitwise; SLO routing serves the loosest rtol the tightest deadline
allows (explicit asks only ever tighten); a request admitted into a
half-full in-flight batch produces bitwise the trajectories it produces
solo (and bitwise the PR 4 stream loop's); two registry models never
share params or compile pools; budget-exhausted adaptive rows come back
``converged=False`` on their :class:`ServeResult`.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core.sde import (LatentSDEConfig, NeuralSDEConfig, generator_init,
                            generator_initial_state, latent_sde_init)
from repro.serving import (DEADLINE_CLASSES, LoadedModel, ModelRegistry,
                           Request, Scheduler, deadline_class_for, load_model,
                           route_rtol)

GAN_CFG = dict(data_dim=1, hidden_dim=8, noise_dim=4, width=16, num_steps=8)


def _registry(key, model_ids=("default",)):
    """Fresh registry (and so fresh compile pools) per test — the pool key
    is (model_id, kind, bucket), deliberately NOT the controller limits."""
    reg = ModelRegistry()
    cfg = NeuralSDEConfig(**GAN_CFG)
    for i, mid in enumerate(model_ids):
        params = generator_init(jax.random.fold_in(key, i), cfg)
        reg.register(LoadedModel(mid, "sde-gan", cfg, params))
    return reg


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# -----------------------------------------------------------------------------
# bundle handshake: v1 -> v2 upgrade, v2 registry round trip, unknown schema
# -----------------------------------------------------------------------------


def test_v1_bundle_upgrades_to_v2_bitwise(key, tmp_path):
    """A PR 4-era v1 bundle reads back as a single-entry v2 registry under
    model_id="default", params bitwise-identical to what was saved."""
    cfg = NeuralSDEConfig(**GAN_CFG)
    params = generator_init(key, cfg)
    ckpt.save_serving_bundle_v1(tmp_path, 5, params, "sde-gan", cfg)

    meta, step = ckpt.load_serving_manifest(tmp_path)
    assert step == 5
    assert meta["schema"] == ckpt.SERVING_SCHEMA_V2
    assert meta["upgraded_from"] == ckpt.SERVING_SCHEMA_V1
    assert [m["model_id"] for m in meta["models"]] == [ckpt.DEFAULT_MODEL_ID]

    model = load_model(tmp_path)
    assert model.model_id == ckpt.DEFAULT_MODEL_ID
    assert model.workload == "sde-gan" and model.step == 5
    assert model.cfg.num_steps == cfg.num_steps
    _assert_trees_equal(model.params, params)

    # and the registry loader takes the same upgrade path
    reg = ModelRegistry()
    assert reg.load(tmp_path) == (ckpt.DEFAULT_MODEL_ID,)
    _assert_trees_equal(reg.get(ckpt.DEFAULT_MODEL_ID).params, params)


def test_v2_multi_model_bundle_roundtrip_bitwise(key, tmp_path):
    cfg = NeuralSDEConfig(**GAN_CFG)
    params = {mid: generator_init(jax.random.fold_in(key, i), cfg)
              for i, mid in enumerate(("a", "b"))}
    ckpt.save_serving_registry(
        tmp_path, 7, {mid: (p, "sde-gan", cfg) for mid, p in params.items()})

    reg = ModelRegistry()
    assert reg.load(tmp_path) == ("a", "b")
    for mid, p in params.items():
        _assert_trees_equal(reg.get(mid).params, p)
    # the single-model loader must refuse to guess among two entries
    with pytest.raises(ValueError, match="model_id"):
        load_model(tmp_path)


def test_unknown_bundle_schema_raises_named_error(key, tmp_path):
    cfg = NeuralSDEConfig(**GAN_CFG)
    ckpt.save_checkpoint(tmp_path / "serving", 1, generator_init(key, cfg),
                         meta={"schema": "repro-serving/v99"})
    with pytest.raises(ckpt.UnknownServingSchemaError, match="v99"):
        ckpt.load_serving_manifest(tmp_path)


# -----------------------------------------------------------------------------
# SLO routing: deadline class table and the loosest-admissible rule
# -----------------------------------------------------------------------------


def test_deadline_class_boundaries():
    """The table is contiguous and upper-bound inclusive."""
    assert deadline_class_for(1.0).name == "realtime"
    assert deadline_class_for(50.0).name == "realtime"
    assert deadline_class_for(50.1).name == "interactive"
    assert deadline_class_for(250.0).name == "interactive"
    assert deadline_class_for(1000.0).name == "standard"
    assert deadline_class_for(math.inf).name == "relaxed"


def test_route_rtol_serves_loosest_admissible():
    realtime = Request(rid=0, size=1, seed=0, deadline_ms=40.0)
    relaxed = Request(rid=1, size=1, seed=1)  # deadline inf
    # a lone unbounded request gets the most accurate tier
    assert route_rtol([relaxed]) == DEADLINE_CLASSES[-1].rtol
    # the tightest deadline in the batch picks the (loosest) tier rtol
    assert route_rtol([relaxed, realtime]) == DEADLINE_CLASSES[0].rtol
    # an explicit ask is an accuracy FLOOR: it tightens ...
    asked = Request(rid=2, size=1, seed=2, deadline_ms=40.0, rtol=1e-4)
    assert route_rtol([realtime, asked]) == 1e-4
    # ... but never loosens past the class rtol
    loose_ask = Request(rid=3, size=1, seed=3, rtol=1e-1)
    assert route_rtol([loose_ask]) == DEADLINE_CLASSES[-1].rtol
    with pytest.raises(ValueError, match="non-empty"):
        route_rtol([])


def test_scheduler_routes_terminal_batches_by_deadline_class(key):
    """End to end: one terminal request per deadline class drains as one
    batch per class, each at its class rtol (requests carry no explicit
    ask, so the deadline alone picks the served tolerance)."""
    sched = Scheduler(_registry(key), max_batch=4, chunks=4)
    for i, cls in enumerate(DEADLINE_CLASSES):
        dl = cls.max_deadline_ms  # upper bound is inclusive
        sched.submit(Request(rid=i, size=1, seed=10 + i, kind="terminal",
                             deadline_ms=dl))
    results = sched.run()
    assert len(results) == len(DEADLINE_CLASSES)
    for r in results:
        assert r.rtol == DEADLINE_CLASSES[r.rid].rtol
        assert r.num_converged == r.size  # default budget is ample here


def test_scheduler_host_second_counters(key):
    """``admit_s``, ``advance_s`` and ``terminal_s`` sum host seconds
    inside the scheduler's own phases: after a run with rollouts and
    terminal requests each is positive, and together they fit inside the
    wall time around the ``step`` calls."""
    import time

    sched = Scheduler(_registry(key), max_batch=4, chunks=2)
    sched.warm("default", kinds=("init", "chunk", "terminal"))
    for i in range(3):
        sched.submit(Request(rid=i, size=1 + i, seed=20 + i))
    sched.submit(Request(rid=9, size=1, seed=9, kind="terminal",
                         deadline_ms=DEADLINE_CLASSES[0].max_deadline_ms))
    wall, done = 0.0, []
    while sched.busy:
        t0 = time.perf_counter()
        done += sched.step()
        wall += time.perf_counter() - t0
    assert sorted(r.rid for r in done) == [0, 1, 2, 9]
    spent = [sched.counters[k] for k in ("admit_s", "advance_s", "terminal_s")]
    assert all(s > 0.0 for s in spent), spent
    assert sum(spent) <= wall


# -----------------------------------------------------------------------------
# continuous batching: mid-flight admission is bitwise-invisible
# -----------------------------------------------------------------------------


def test_mid_flight_admission_bitwise_equals_solo(key):
    """A request admitted into a half-drained in-flight batch produces
    bitwise the trajectories it produces alone — every row is a pure
    function of (params, request seed, row index, chunk index)."""
    reg = _registry(key)
    first = Request(rid=0, size=3, seed=7)
    late = Request(rid=1, size=2, seed=123)

    def solo(req):
        sched = Scheduler(reg, max_batch=8, chunks=4, collect=True)
        sched.submit(req)
        (res,) = sched.run()
        return res.samples

    sched = Scheduler(reg, max_batch=8, chunks=4, collect=True)
    sched.submit(first)
    results = sched.step()  # `first` is now in flight, one chunk deep
    assert results == [] and sched.busy
    sched.submit(late)      # joins at the next chunk boundary
    results += sched.run()

    cfg = reg.get("default").cfg
    by_rid = {r.rid: r for r in results}
    assert by_rid[1].samples.shape == (cfg.num_steps + 1, 2, cfg.data_dim)
    np.testing.assert_array_equal(by_rid[0].samples, solo(first))
    np.testing.assert_array_equal(by_rid[1].samples, solo(late))


def test_single_bucket_scheduler_bitwise_independent_of_load(key):
    """With the bucket granularity equal to ``max_batch`` the ladder is one
    bucket, and every batch, whatever its occupancy, runs that one
    compiled program: requests served together are bitwise what each gets
    served alone, and no other bucket compiles."""
    reg = _registry(key)
    reqs = [Request(rid=i, size=1 + i % 3, seed=50 + i) for i in range(5)]
    one_bucket = {"max_batch": 8, "shard_base": 8, "chunks": 4,
                  "collect": True}
    sched = Scheduler(reg, **one_bucket)
    assert sched.buckets == [8]
    for r in reqs:
        sched.submit(r)
    by_rid = {r.rid: r for r in sched.run()}
    for r in reqs:
        solo = Scheduler(reg, **one_bucket)
        solo.submit(Request(rid=99, size=r.size, seed=r.seed))
        (res,) = solo.run()
        np.testing.assert_array_equal(by_rid[r.rid].samples, res.samples)
    assert {k[2] for k in reg.pool_keys()} == {8}


def test_scheduler_rollout_bitwise_matches_stream_loop(key):
    """Independent oracle: the scheduler's chunked rollout reproduces the
    PR 4 stream loop bit for bit — same base key fold_in(PRNGKey(seed), j),
    same chunk key fold_in(base, 1000 + c), same chunk stitching."""
    from repro.launch.steps import make_stream_chunk_step

    reg = _registry(key)
    model = reg.get("default")
    cfg, size, seed = model.cfg, 2, 42

    sched = Scheduler(reg, max_batch=2, chunks=4, collect=True)
    sched.submit(Request(rid=0, size=size, seed=seed))
    (res,) = sched.run()

    chunks, steps_per = 4, cfg.num_steps // 4
    span = cfg.t1 / chunks
    chunk_fn = jax.jit(make_stream_chunk_step(cfg, span, steps_per))
    keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(seed), j)
                      for j in range(size)])
    x = generator_initial_state(model.params, cfg, keys)
    expect = []
    for c in range(chunks):
        ckeys = jax.vmap(lambda k, c=c: jax.random.fold_in(k, 1000 + c))(keys)
        ys, x = chunk_fn(model.params, ckeys, x,
                         jnp.asarray(c * span, cfg.dtype))
        expect.append(np.asarray(ys if c == 0 else ys[1:]))
    np.testing.assert_array_equal(res.samples, np.concatenate(expect))


# -----------------------------------------------------------------------------
# multi-model isolation
# -----------------------------------------------------------------------------


def test_two_model_registry_isolation(key):
    """Two models serve side by side from one scheduler: same-seed requests
    get different (per-model) trajectories, each bitwise what a single-model
    scheduler produces, and the compile pools never mix ids — unloading one
    model leaves the other's programs untouched."""
    reg = _registry(key, ("a", "b"))
    sched = Scheduler(reg, max_batch=4, chunks=4, collect=True)
    sched.submit(Request(rid=0, size=2, seed=9, model_id="a"))
    sched.submit(Request(rid=1, size=2, seed=9, model_id="b"))
    by_rid = {r.rid: r for r in sched.run()}
    assert by_rid[0].model_id == "a" and by_rid[1].model_id == "b"
    assert not np.array_equal(by_rid[0].samples, by_rid[1].samples)

    solo = Scheduler(reg, max_batch=4, chunks=4, collect=True)
    solo.submit(Request(rid=2, size=2, seed=9, model_id="a"))
    (res_a,) = solo.run()
    np.testing.assert_array_equal(by_rid[0].samples, res_a.samples)

    keys_a, keys_b = reg.pool_keys("a"), reg.pool_keys("b")
    assert keys_a and keys_b
    assert all(k[0] == "a" for k in keys_a)
    assert set(reg.pool_keys()) == set(keys_a) | set(keys_b)
    reg.unload("a")
    assert "a" not in reg
    assert reg.pool_keys("a") == ()
    assert reg.pool_keys("b") == keys_b


# -----------------------------------------------------------------------------
# per-row convergence + named scheduler errors
# -----------------------------------------------------------------------------


def test_serve_result_reports_budget_exhausted_rows(key):
    """A starved adaptive controller (max_steps=2 at the relaxed tier's
    tight rtol) marks every row converged=False on the ServeResult —
    structural, not a log line."""
    sched = Scheduler(_registry(key), max_batch=2, chunks=4, max_steps=2)
    sched.submit(Request(rid=0, size=2, seed=3, kind="terminal"))
    (res,) = sched.run()
    assert res.rtol == DEADLINE_CLASSES[-1].rtol
    assert res.converged.shape == (2,)
    assert res.num_converged == 0
    assert res.deadline_met  # deadline inf: slow but never missed


def test_scheduler_named_errors(key):
    reg = _registry(key)
    with pytest.raises(ValueError, match="mode"):
        Scheduler(reg, mode="bogus")
    with pytest.raises(ValueError, match="chunks"):
        Scheduler(reg, max_batch=4, chunks=3).submit(
            Request(rid=0, size=1, seed=0))  # 3 doesn't divide num_steps=8
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        Scheduler(reg, max_batch=4).submit(Request(rid=1, size=16, seed=0))
    lcfg = LatentSDEConfig(data_dim=2, hidden_dim=8, context_dim=8, width=16,
                           num_steps=16)
    reg.register(LoadedModel("lat", "latent-sde", lcfg,
                             latent_sde_init(key, lcfg)))
    with pytest.raises(ValueError, match="latent-sde"):
        Scheduler(reg).submit(Request(rid=2, size=1, seed=0, model_id="lat"))
    with pytest.raises(ValueError, match="size"):
        Request(rid=3, size=0, seed=0)
    with pytest.raises(ValueError, match="kind"):
        Request(rid=4, size=1, seed=0, kind="magic")
