"""Run one benchmark cell once on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: check that the devices are TPUs, enough of them, and name them;
build everything from the seed on the device; warm the cell's own shapes
(JAX's persistent compilation cache keeps them for the next run); measure
for ``--seconds``; then compare what the timed path produced with the plain
reference.  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``), its last key ``checks``: each number
compared beside its limit, also printed as the last lines of standard
error.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiler trace of a window of at most
``TRACE_WINDOW_S`` seconds.

Without a TPU, or with fewer chips than the cell needs, or outside a
checkout that holds the program, the run exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import device  # noqa: E402
from bench.layout import Layout  # noqa: E402

#: Exit codes: no usable chip, and a checkout without the program.
NO_CHIP, NO_PROGRAM = 3, 4

#: A traced run measures at most this long: the trace of a longer window
#: outgrows the profiler's buffers and the time to read it back.
TRACE_WINDOW_S = 2.0


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, its configuration and the run's
    settings.  ``variant`` is ``None`` for a benchmark run; the limit-setting
    runs and the tests set it to ``"control"`` or to a planted fault."""

    layout: Layout
    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    scratch: Path
    variant: Optional[str] = None
    compiles: device.CompileCounter = dataclasses.field(
        default_factory=device.CompileCounter)

    def profiled(self):
        return device.profiled(self.scratch / "trace", self.trace)


@dataclasses.dataclass
class Run:
    """What a per-layer reader gets."""

    trace: Optional[dict]
    facts: dict
    peaks: dict
    chips: int


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says; every program,
    however quick to compile, is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def measure(layout: Layout, name: str, seed: int, seconds: float, trace: bool,
            variant: Optional[str] = None, identity: Optional[dict] = None,
            peaks: Optional[dict] = None) -> dict:
    """Run cell ``name`` once and return its result object (without
    printing).  ``identity``/``peaks`` are looked up from the devices when
    not given."""
    cell = layout.cell(name)
    config = layout.config(cell["config"])
    identity = identity or device.identify(cell["chips"])
    peaks = peaks or layout.peaks(identity["kind"])
    scratch = layout.root / "bench_out"
    shutil.rmtree(scratch / "trace", ignore_errors=True)
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
    ctx = Context(layout=layout, cell=cell, config=config, seed=seed,
                  seconds=seconds, trace=trace, chips=cell["chips"],
                  scratch=scratch, variant=variant)
    import jax

    # the whole process at the configuration's matmul precision: every
    # thread, every program and every eager operation alike
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      config["precision"]["matmul"])
    try:
        out = layout.driver(cell["driver"]).run(ctx)
    finally:
        jax.config.update("jax_default_matmul_precision", before)
    setup_s = out["window_start"] - PROCESS_START

    result = {"correct": all(c.ok for c in out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    dev = {**identity, "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    if not trace:
        e2e = dict(out["end_to_end"], setup_s=setup_s)
        for m in layout.end_to_end(name):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from bench import trace as tr

        window_ns = out["window_s"] * 1e9
        reduced = tr.reduce(tr.load(out["trace_dir"]), window_ns)
        shutil.rmtree(out["trace_dir"], ignore_errors=True)
        run = Run(trace=reduced, facts=out["facts"], peaks=peaks,
                  chips=cell["chips"])
        for m in layout.per_layer(name):
            value = layout.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced.get("devices"):
            dev["busy_s"] = reduced["busy_ns_mean"] / 1e9
            dev["window_s"] = out["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = dev
    result["compiles_in_window"] = ctx.compiles.count
    result["facts"] = {k: v for k, v in out["facts"].items()
                       if not isinstance(v, list)}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out["checks"]}
    return result


def finite(obj):
    """``obj`` with every non-finite float replaced by ``None``: the result
    line stays strict JSON, and a missing number is not a number."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"the program (src/repro) is not in this checkout ({ROOT})")
        return NO_PROGRAM
    sys.path.insert(0, str(src))
    log(f"compile cache {enable_cache(ROOT)}")
    try:
        identity = device.identify(cell["chips"])
        peaks = layout.peaks(identity["kind"])
    except (device.NoChip, KeyError) as e:
        log(str(e))
        return NO_CHIP
    log(f"{identity['count']} x {identity['kind']}; cell {args.workload}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")

    result = measure(layout, args.workload, args.seed, args.seconds,
                     bool(args.trace), identity=identity, peaks=peaks)
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
