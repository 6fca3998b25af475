"""Run the Neural-SDE main path on a TPU and check what comes out.

    python chip_smoke.py              # one chip: kernels, training, serving
    python chip_smoke.py --chips 4    # data-parallel training steps: 4 chips
                                      # against one of them, nothing else

One process, no children.  Each phase prints ``[phase] ...`` lines with its
numbers; the last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

printed only when every phase passed.  Without a TPU, or when a phase
fails, the script exits non-zero and prints no such line.

Phases (one chip):

* ``kernels`` — every main-path kernel on the chip against the
  :mod:`repro.kernels.ref` oracle on the same chip: in-kernel Threefry bits
  exactly equal to ``jax.random.bits``; floats within ``ULPS`` units in the
  last place of the array's magnitude.
* ``train`` — a few steps of ``train_latent_sde`` (fused, ``use_pallas``)
  and of ``train_sde_gan`` at the trainers' widths (hidden 16, width 32)
  and a path batch of 1024, the scale of torchsde's ``examples/sde_gan.py``
  (hidden 16, batch 1024, 64 time points; the GAN runs 64 observation
  times).  Losses must be finite, the fused Latent-SDE step must contain a
  Mosaic kernel, and its loss and gradient norm must agree with the unfused
  step within ``STEP_RTOL``.
* ``serve`` — the GAN's freshly saved serving bundle behind a
  ``Scheduler`` and ``AsyncFrontend``; every request must come back with
  finite trajectories, and one request's rows must be bitwise those it
  gets served alone through the same scheduler, whose one bucket
  (granularity ``max_batch``) gives every batch the same program.  The
  gap to the default bucket ladder, whose solo run uses a smaller
  program, is printed, not gated (an open defect).  Samples are
  ``(time, rows, data_dim)``.

``--chips 4``: the data-parallel Latent-SDE (fused) and SDE-GAN training
steps, and the server's continuous-batching chunk rollout, over a 4-chip
mesh, against the same program on one chip in the same process, at the
default and at the highest matmul precision, within ``DP_RTOL``; the
compiled 4-chip programs must hold a Mosaic kernel and no collective that
moves the path batch (the batch stays split).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import tempfile
import time
import traceback
from pathlib import Path

#: Largest kernel-vs-oracle gap, in ulps of the array's largest magnitude.
#: Elementwise phases and single draws differ only by FMA contraction and
#: the erf_inv lowering; the bridge value compounds 24 levels of draws.
ULPS = {"elementwise": 4, "draw": 4, "bridge": 16}
#: Relative tolerance between two computations of one training step that
#: may differ only by rounding (fused vs unfused kernels).
STEP_RTOL = 1e-4
#: 4 chips against 1, per matmul precision.  At "highest" the two steps
#: differ only by the reassociated batch reductions.  At the TPU default,
#: f32 matmul inputs round to bfloat16, and an input that the reassociated
#: upstream sums moved by one f32 ulp may round to the neighbouring bf16
#: value (2**-9 relative) — the default-precision bound is looser.
DP_RTOL = {"highest": STEP_RTOL, "default": 1e-3}
BATCH = 1024
TRAIN_STEPS = 3
GAN_SEQ_LEN = 64
SERVE_BUCKET = 16


def _log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _ulps(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    scale = np.spacing(np.max(np.abs(want)).astype(want.dtype))
    return float(np.max(np.abs(got.astype(np.float64) - want)) / scale)


def _close(a, b, rtol: float) -> bool:
    return abs(float(a) - float(b)) <= rtol * max(abs(float(b)), 1e-30)


# -----------------------------------------------------------------------------
# kernels
# -----------------------------------------------------------------------------


def _bits_kernel(k1, k2, shape, block_rows: int):
    """Threefry bits drawn inside a Pallas kernel over a row grid — the
    counter layout the Brownian kernels use."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import prng

    def kernel(k_ref, o_ref):
        row0 = pl.program_id(0) * block_rows
        hi, lo = prng.counters(o_ref.shape, row0)
        o_ref[...] = prng.bits(k_ref[0, 0], k_ref[0, 1], hi, lo, 32)

    rows, cols = shape
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(rows, block_rows),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.uint32),
    )(jnp.stack([k1, k2])[None].astype(jnp.uint32))


def phase_kernels(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import brownian as bk
    from repro.kernels import prng, ref
    from repro.kernels import reversible_heun_step as rh

    shape, f32 = (BATCH, 17), jnp.float32
    key = jax.random.PRNGKey(seed)
    k1, k2 = prng.key_data_pair(key)
    out = {}

    bits = jax.jit(lambda a, b: _bits_kernel(a, b, shape, 64))(k1, k2)
    want = jax.random.bits(key, shape, jnp.uint32)
    out["threefry_bits_equal"] = bool(np.array_equal(np.asarray(bits),
                                                     np.asarray(want)))
    _log("kernels", f"in-kernel threefry bits == jax.random.bits: "
         f"{out['threefry_bits_equal']}")

    ops_ = jax.random.split(jax.random.fold_in(key, 1), 7)
    z, zh, mu, sig, mu1, sig1, g = (jax.random.normal(k, shape, f32)
                                    for k in ops_)
    dt, n = jnp.float32(1 / 63), jnp.int32(5)
    dw = jax.random.normal(jax.random.fold_in(key, 2), shape, f32)
    def phase1_gen_oracle(z_, zh_, mu_, sig_, a, b, c, dt_grid, dt_):
        dw_ = ref.brownian_increment(a, b, c, shape, f32, dt_grid)
        return ref.rev_heun_phase1(z_, zh_, mu_, sig_, dw_, dt_), dw_

    cases = {
        "phase1": ("elementwise", rh.rev_heun_phase1, ref.rev_heun_phase1,
                   (z, zh, mu, sig, dw, dt)),
        "phase2": ("elementwise", rh.rev_heun_phase2, ref.rev_heun_phase2,
                   (z, mu, mu1, sig, sig1, dw, dt)),
        "bwd_phase1": ("elementwise", rh.rev_heun_bwd_phase1,
                       ref.rev_heun_bwd_phase1, (g, mu, sig, dw, dt)),
        "bwd_phase2": ("elementwise", rh.rev_heun_bwd_phase2,
                       ref.rev_heun_bwd_phase2, (g, zh, dw, dt)),
        "brownian_increment": (
            "draw",
            lambda a, b, c, d: bk.brownian_increment(a, b, c, shape, f32, d),
            lambda a, b, c, d: ref.brownian_increment(a, b, c, shape, f32, d),
            (k1, k2, n, dt)),
        "phase1_gen": ("draw", bk.rev_heun_phase1_gen, phase1_gen_oracle,
                       (z, zh, mu, sig, k1, k2, n, dt, dt)),
        "brownian_value": (
            "bridge",
            lambda a, b, t: bk.brownian_value(a, b, t, 0.0, 1.0, shape, f32),
            lambda a, b, t: ref.brownian_value(a, b, t, 0.0, 1.0, shape, f32),
            (k1, k2, jnp.float32(0.3))),
    }
    ok = out["threefry_bits_equal"]
    for name, (kind, kernel, oracle, args) in cases.items():
        t0 = time.perf_counter()
        got = jax.block_until_ready(jax.jit(kernel)(*args))
        secs = time.perf_counter() - t0
        want = jax.jit(oracle)(*args)
        gap = max(_ulps(a, b) for a, b in zip(jax.tree.leaves(got),
                                              jax.tree.leaves(want)))
        passed = gap <= ULPS[kind] and all(
            bool(np.all(np.isfinite(np.asarray(a))))
            for a in jax.tree.leaves(got))
        ok = ok and passed
        out[name] = {"ulps": gap, "limit": ULPS[kind],
                     "first_call_s": secs}
        _log("kernels", f"{name}: {gap} ulps (limit {ULPS[kind]}), "
             f"compile+first call {secs:.3f} s, {'ok' if passed else 'FAIL'}")

    # the same increment drawn by the installed jax.random in XLA
    inc = jax.jit(cases["brownian_increment"][1])(k1, k2, n, dt)
    jr = jax.random.normal(jax.random.fold_in(key, n), shape, f32) * jnp.sqrt(dt)
    out["increment_vs_jax_random_ulps"] = _ulps(inc, jr)
    ok = ok and out["increment_vs_jax_random_ulps"] <= ULPS["draw"]
    _log("kernels", f"brownian_increment vs jax.random.normal: "
         f"{out['increment_vs_jax_random_ulps']} ulps")
    out["ok"] = ok
    return out


# -----------------------------------------------------------------------------
# training
# -----------------------------------------------------------------------------


def _finite_tree(tree) -> bool:
    import jax
    import numpy as np

    return all(bool(np.all(np.isfinite(np.asarray(x))))
               for x in jax.tree.leaves(tree))


def _timed_compile(step, *args):
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _timed_call(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def phase_train(seed: int, ckpt_dir: str) -> dict:
    import jax

    from repro.launch.train import (latent_sde_setup, train_latent_sde,
                                    train_sde_gan)

    out = {}
    t0 = time.perf_counter()
    _, losses = train_latent_sde(TRAIN_STEPS, BATCH, seed=seed,
                                 use_pallas=True, log_every=1)
    out["latent_sde_s"] = time.perf_counter() - t0
    out["latent_sde_losses"] = losses
    out["latent_finite"] = all(math.isfinite(x) for x in losses)
    _log("train", f"train_latent_sde(--pallas) {TRAIN_STEPS} steps, batch "
         f"{BATCH}: -ELBO {losses}, {out['latent_sde_s']:.2f} s "
         f"(compile included)")

    # the fused step holds Mosaic kernels, and agrees with the unfused one
    _, state, fused = latent_sde_setup(BATCH, seed, use_pallas=True)
    _, _, unfused = latent_sde_setup(BATCH, seed, use_pallas=False)
    key = jax.random.PRNGKey(seed + 1)
    compiled, secs = _timed_compile(fused, *state, key)
    out["fused_has_kernel"] = "tpu_custom_call" in compiled.as_text()
    (_, _, m_fused), step_s = _timed_call(compiled, *state, key)
    (_, _, m_unfused), _ = _timed_call(unfused, *state, key)
    out["fused_step"] = {"compile_s": secs, "step_s": step_s}
    out["fused_vs_unfused"] = {
        k: (float(m_fused[k]), float(m_unfused[k]))
        for k in ("loss", "grad_norm")}
    out["fused_agrees"] = all(_close(a, b, STEP_RTOL)
                              for a, b in out["fused_vs_unfused"].values())
    _log("train", f"fused Latent-SDE step: tpu_custom_call "
         f"{out['fused_has_kernel']}, compile {secs:.2f} s, step "
         f"{step_s:.4f} s; (fused, unfused) {out['fused_vs_unfused']} "
         f"rtol {STEP_RTOL}: {out['fused_agrees']}")

    t0 = time.perf_counter()
    params, mmds = train_sde_gan(TRAIN_STEPS, BATCH, ckpt_dir=ckpt_dir,
                                 seed=seed, num_steps=GAN_SEQ_LEN - 1,
                                 seq_len=GAN_SEQ_LEN, log_every=1)
    out["sde_gan_s"] = time.perf_counter() - t0
    out["sde_gan_sig_mmd"] = mmds
    out["gan_finite"] = (all(math.isfinite(x) for x in mmds)
                         and _finite_tree(params))
    _log("train", f"train_sde_gan {TRAIN_STEPS} steps, batch {BATCH}, "
         f"seq-len {GAN_SEQ_LEN}: sig-MMD {mmds}, params finite "
         f"{out['gan_finite']}, {out['sde_gan_s']:.2f} s (compile included)")
    out["ok"] = (out["latent_finite"] and out["fused_has_kernel"]
                 and out["fused_agrees"] and out["gan_finite"])
    return out


# -----------------------------------------------------------------------------
# serving
# -----------------------------------------------------------------------------


def phase_serve(seed: int, ckpt_dir: str) -> dict:
    import numpy as np

    from repro.serving import (AsyncFrontend, ModelRegistry, Request,
                               Scheduler, load_model)

    model = load_model(ckpt_dir)
    registry = ModelRegistry()
    registry.register(model)
    # a bucket granularity of max_batch leaves one bucket: every batch runs
    # one compiled program, so served rows must be bitwise their solo rows
    # whatever else shares the batch
    sched_kw = {"max_batch": SERVE_BUCKET, "shard_base": SERVE_BUCKET,
                "chunks": 7, "collect": True}  # 7 divides 63 steps
    reqs = [Request(rid=i, size=1 + i % 4, seed=seed + 100 + i)
            for i in range(8)]

    async def drive():
        front = AsyncFrontend(Scheduler(registry, **sched_kw))
        await front.start()
        try:
            return await asyncio.wait_for(
                asyncio.gather(*(front.submit(r) for r in reqs),
                               return_exceptions=True),
                timeout=900)
        finally:
            await front.close()

    t0 = time.perf_counter()
    results = asyncio.run(drive())
    out = {"requests": len(reqs), "serve_s": time.perf_counter() - t0}
    errors = [repr(r) for r in results if isinstance(r, BaseException)]
    out["errors"] = errors
    answered = {r.rid: r for r in results if not isinstance(r, BaseException)}
    shapes_ok = all(
        answered[q.rid].samples is not None
        and np.asarray(answered[q.rid].samples).shape[1] == q.size
        and bool(np.all(np.isfinite(np.asarray(answered[q.rid].samples))))
        for q in reqs if q.rid in answered)
    _log("serve", f"{len(answered)}/{len(reqs)} requests answered, "
         f"{len(errors)} errors, finite {shapes_ok}, "
         f"{out['serve_s']:.2f} s (compile included)")

    probe = reqs[3]

    def alone(**kw):
        solo = Scheduler(registry, **kw)
        solo.submit(Request(rid=999, size=probe.size, seed=probe.seed))
        (res,) = solo.run()
        return np.asarray(res.samples)

    served = (np.asarray(answered[probe.rid].samples)
              if probe.rid in answered else None)
    out["bitwise_equals_solo"] = (served is not None and np.array_equal(
        served, alone(**sched_kw)))
    _log("serve", f"request {probe.rid} ({probe.size} rows) bitwise equal "
         f"to itself served alone: {out['bitwise_equals_solo']}")
    if served is not None:
        # not gated: the default ladder serves the solo request through a
        # smaller bucket's program
        ladder = alone(max_batch=SERVE_BUCKET, chunks=sched_kw["chunks"],
                       collect=True)
        out["ladder_solo_max_abs_gap"] = float(np.max(np.abs(served - ladder)))
        _log("serve", f"same request alone through the default bucket "
             f"ladder: max abs gap {out['ladder_solo_max_abs_gap']!r}")
    out["ok"] = (not errors and len(answered) == len(reqs) and shapes_ok
                 and out["bitwise_equals_solo"])
    return out


# -----------------------------------------------------------------------------
# four chips
# -----------------------------------------------------------------------------


def _compare_steps(one, four):
    """Largest relative gap (to the leaf's largest magnitude) over every
    leaf of two step outputs, and the path of the leaf it is in."""
    import jax
    import numpy as np

    gap, where = 0.0, ""
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(one)[0],
                            jax.tree.leaves(four)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        g = float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(a))), 1e-30))
        if g > gap:
            gap, where = g, jax.tree_util.keystr(path)
    return gap, where


def _serving_chunk(seed: int):
    """The Scheduler's chunk rollout (``make_stream_chunk_step``) for a
    bucket of ``BATCH`` rows of the GAN generator: ``(program, args)``."""
    import jax
    import jax.numpy as jnp

    from repro.core.sde import generator_initial_state
    from repro.launch.steps import make_stream_chunk_step
    from repro.launch.train import sde_gan_setup

    cfg, (params, _, _), _ = sde_gan_setup(BATCH, seed,
                                           num_steps=GAN_SEQ_LEN - 1,
                                           seq_len=GAN_SEQ_LEN)
    chunks = 7  # as in phase_serve
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), BATCH)
    x0 = generator_initial_state(params["gen"], cfg, keys)
    program = jax.jit(make_stream_chunk_step(cfg, cfg.t1 / chunks,
                                             cfg.num_steps // chunks))
    return program, (params["gen"], keys, x0, jnp.zeros((BATCH,), cfg.dtype))


def _split_rows(mesh, args):
    """Every operand with a leading dim of ``BATCH`` split over the mesh, as
    the Scheduler places its batches; the rest as it was."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows = NamedSharding(mesh, P("data"))
    return jax.tree.map(
        lambda x: (jax.device_put(x, rows)
                   if x.ndim and x.shape[0] == BATCH else x), args)


def phase_data_parallel(seed: int, n_chips: int) -> dict:
    import jax

    from repro.distributed.sharding import batch_collectives, data_parallel_mesh
    from repro.launch.train import latent_sde_setup, sde_gan_setup

    mesh = data_parallel_mesh(BATCH)
    if mesh is None or mesh.devices.size != n_chips:
        raise RuntimeError(f"the data-parallel mesh spans "
                           f"{0 if mesh is None else mesh.devices.size} "
                           f"devices, not {n_chips}")
    out = {"mesh_devices": int(mesh.devices.size)}
    key = jax.random.PRNGKey(seed + 1)
    _, lat_state, lat_step = latent_sde_setup(BATCH, seed, use_pallas=True)
    _, gan_state, gan_step = sde_gan_setup(BATCH, seed,
                                           num_steps=GAN_SEQ_LEN - 1,
                                           seq_len=GAN_SEQ_LEN)
    programs = {
        "latent_sde": (lat_step, (*lat_state, key)),
        "sde_gan": (gan_step, (*gan_state, key)),
        "serve_chunk": _serving_chunk(seed),
    }
    ok = True
    for name, (step, args) in programs.items():
        for precision, rtol in DP_RTOL.items():
            with jax.default_matmul_precision(precision):
                one, one_s = _timed_call(step, *args)
                with jax.set_mesh(mesh):
                    mesh_args = _split_rows(mesh, args)
                    compiled, compile_s = _timed_compile(step, *mesh_args)
                    four, four_s = _timed_call(compiled, *mesh_args)
            hlo = compiled.as_text()
            gap, where = _compare_steps(one, four)
            moved = (batch_collectives(hlo, BATCH)
                     + batch_collectives(hlo, BATCH // n_chips))
            rec = {"rel_gap": gap, "worst_leaf": where, "rtol": rtol,
                   "all_gather": "all-gather" in hlo,
                   "batch_collectives": [" ".join(c) for c in moved],
                   "kernel": "tpu_custom_call" in hlo,
                   "one_chip_first_call_s": one_s,
                   "mesh_compile_s": compile_s, "mesh_step_s": four_s}
            rec["ok"] = (gap <= rtol and not rec["all_gather"] and not moved
                         and rec["kernel"])
            ok = ok and rec["ok"]
            out[f"{name}/{precision}"] = rec
            _log("dp", f"{name}, matmul precision {precision}: {n_chips} "
                 f"chips vs 1, largest relative gap {gap!r} in {where} "
                 f"(rtol {rtol}), all-gather {rec['all_gather']}, "
                 f"collectives moving the batch {rec['batch_collectives']}, "
                 f"tpu_custom_call {rec['kernel']}, mesh compile "
                 f"{compile_s:.2f} s, mesh step {four_s:.4f} s")
    out["ok"] = ok
    return out


# -----------------------------------------------------------------------------


def _run_phase(name, fn, *args):
    t0 = time.perf_counter()
    try:
        rec = fn(*args)
    except Exception:  # noqa: BLE001 - report the phase, run the others
        traceback.print_exc()
        rec = {"ok": False}
    rec["seconds"] = time.perf_counter() - t0
    _log(name, f"{'passed' if rec['ok'] else 'FAILED'} in "
         f"{rec['seconds']:.2f} s")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel training steps "
                         "and serving rollout, 4 chips against 1")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: the repro package is not at {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch.cache import enable_compilation_cache

    _log("smoke", f"{len(devices)} x {devices[0].device_kind}, compile "
         f"cache {enable_compilation_cache()}")

    if args.chips == 4:
        phases = {"dp": _run_phase("dp", phase_data_parallel, args.seed, 4)}
    else:
        with tempfile.TemporaryDirectory() as ckpt_dir:
            phases = {
                "kernels": _run_phase("kernels", phase_kernels, args.seed),
                "train": _run_phase("train", phase_train, args.seed,
                                    ckpt_dir),
            }
            phases["serve"] = (
                _run_phase("serve", phase_serve, args.seed, ckpt_dir)
                if phases["train"]["ok"] else {"ok": False})
    if not all(p["ok"] for p in phases.values()):
        print(f"chip_smoke: failed phases "
              f"{[k for k, p in phases.items() if not p['ok']]}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
