"""Closed-loop training: the configuration's compiled step, driven back to
back on rows that all differ, for the window.

Set-up builds the step with its state from the seed and drives it through
its first ``check_steps`` steps with the window's own call and feed; those
steps compile it and give the readings the reference is compared with.
The window then carries on from the same object.  No value is read back
to the host inside the window; a few steps may be in flight at once, and
the window ends when the last one has finished.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import gc
import time

from bench import compare, device, stats
from bench.flops import pallas
from bench.reference import plain

#: Steps the host may dispatch ahead of the device.
IN_FLIGHT = 4


def _in_program_place(variant: str, batch: int):
    """``(dot, keep)`` of the reference put in the program's place, on the
    same rows: the control (three bfloat16 passes), or the loss's mean
    taken over the first half of the batch; ``None`` for the program."""
    return {"control": (plain.THREE_PASS, batch),
            "half_batch": (plain.HIGHEST, batch // 2)}.get(variant)


def _host(tree):
    import jax
    import numpy as np

    return jax.tree.map(lambda a: np.asarray(a), tree)


def _mesh(chips: int, batch: int):
    if chips == 1:
        return None
    from repro.distributed.sharding import data_parallel_mesh

    mesh = data_parallel_mesh(batch)
    if mesh is None or mesh.devices.size != chips:
        raise RuntimeError(f"a data-parallel mesh over {chips} chips for a "
                           f"batch of {batch} could not be built")
    return mesh


def run(ctx) -> dict:
    import jax

    traffic = ctx.cell["params"]
    batch, n_check = traffic["batch"], traffic["check_steps"]
    reference = ctx.layout.reference(ctx.config["name"])
    mesh = _mesh(ctx.chips, batch)
    on_mesh = jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()

    key = jax.random.PRNGKey(ctx.seed)
    data_key = jax.random.fold_in(key, 1)
    params0 = jax.jit(functools.partial(reference.init, config=ctx.config))(
        jax.random.fold_in(key, 0))
    keys = [jax.random.fold_in(data_key, i) for i in range(n_check)]

    with on_mesh:
        prog = ctx.layout.model(ctx.config["kind"]).train_program(
            ctx.config, traffic, ctx.config["precision"]["fields"])
        step = prog.unchanged if ctx.variant == "unchanged" else prog.step
        state = prog.state(params0)
        losses = []
        for i, k in enumerate(keys):
            state, loss = step(state, k)
            losses.append(loss)
            if i == 0:
                grads = _host(prog.first_grads(state))
        readings = {"losses": _host(losses), "grads": grads,
                    "params0": _host(params0),
                    "params": _host(prog.params(state))}
        facts = {}
        if ctx.trace:
            facts["pallas_bytes_per_step"] = pallas.bytes_per_step(
                lambda s, k: prog.step(s, k)[0], state, keys[0])

        inflight = collections.deque()
        counter = ctx.compiles
        with counter.counting(), ctx.profiled() as trace_dir:
            t0 = time.perf_counter()
            deadline = t0 + ctx.seconds
            steps = 0
            while True:
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, loss = step(state, jax.random.fold_in(
                        data_key, n_check + steps))
                steps += 1
                inflight.append(loss[0])
                if len(inflight) > IN_FLIGHT:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        jax.block_until_ready(inflight.popleft())
                if time.perf_counter() >= deadline:
                    break
            jax.block_until_ready(state)
            t1 = time.perf_counter()
    window = t1 - t0
    memory = device.memory_peak(ctx.chips)
    del state, prog, step, inflight, params0
    gc.collect()

    def reference_run(dot, keep):
        with jax.default_matmul_precision("highest"):
            train = jax.jit(functools.partial(
                reference.train, config=ctx.config, traffic=traffic,
                keep=keep, dot=dot))
            out = _host(train(readings["params0"], keys=keys))
        out["params0"] = readings["params0"]
        return out

    ref = reference_run(plain.HIGHEST, batch)
    in_place = _in_program_place(ctx.variant, batch)
    if in_place is not None:
        readings = reference_run(*in_place)
    checks, uncompared = compare.training_checks(readings, ref,
                                                 ctx.cell["limits"])
    facts.update(uncompared)

    facts.update({"steps": steps, "window_s": window, "batch": batch,
                  "model_flops_per_step": ctx.layout.flops(
                      ctx.config["kind"]).model_flops_per_step(
                          ctx.config, traffic)})
    return {
        "window_start": t0,
        "window_s": window,
        "end_to_end": {"train_paths_per_s": stats.rate(steps * batch,
                                                           window)},
        "checks": checks,
        "attempted": steps + n_check,
        "failed": 0,
        "memory_peak_bytes": memory,
        "trace_dir": trace_dir,
        "facts": facts,
    }
