"""Benchmark suite entrypoint: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--preset tiny|quick|full] [--only NAME]

Each suite prints ``table,label,value`` CSV lines and, on success, emits a
schema-checked ``BENCH_<name>.json`` in the repo root (see
benchmarks/report.py) — the machine-readable perf trajectory that CI's
``bench-smoke`` job gates on.  The roofline harness
(benchmarks/roofline.py) is run separately — it needs the 512-device XLA
flag and hour-scale compiles; see EXPERIMENTS.md.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.launch.cache import enable_compilation_cache

from . import (brownian, clipping, convergence, gradient_error, latent_sde,
               report, serving, solver_speed)

SUITES = {
    "gradient_error": gradient_error.main,   # paper Fig. 2 / Table 6
    "solver_speed": solver_speed.main,       # paper Tables 1/4/5 (speed)
    "brownian": brownian.main,               # paper Table 2 / Tables 7-10
    "clipping": clipping.main,               # paper Tables 3/11 (speed)
    "convergence": convergence.main,         # paper Figs. 5/6 (App. D.4)
    "latent_sde": latent_sde.main,           # paper Fig. 2 / App. B on the ELBO
    "serving": serving.main,                 # trajectory-sampling throughput
    "serving_load": serving.main_load,       # open-loop continuous-batching gate
    "serving_async": serving.main_async,     # async front + preemption + pools
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=report.PRESETS, default="full",
                    help="tiny = CI smoke; quick = laptop scale; full = "
                         "paper scale")
    ap.add_argument("--quick", action="store_true",
                    help="alias for --preset quick (back-compat)")
    ap.add_argument("--only", choices=sorted(SUITES), default=None)
    ap.add_argument("--list-suites", action="store_true",
                    help="print the suite names, comma-joined, and exit — "
                         "the single source of truth CI's expect-list "
                         "consumes (report.py --validate)")
    args = ap.parse_args(argv)
    if args.list_suites:
        print(",".join(SUITES))
        return 0
    preset = "quick" if args.quick and args.preset == "full" else args.preset
    enable_compilation_cache()

    names = [args.only] if args.only else list(SUITES)
    failures = 0
    for name in names:
        print(f"=== {name} ({preset}) ===", flush=True)
        t0 = time.time()
        try:
            rows = SUITES[name](preset=preset)
            report.write_bench(name, rows, preset)
            print(f"=== {name} done in {time.time()-t0:.1f}s ===", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            import traceback

            traceback.print_exc()
            print(f"=== {name} FAILED: {e} ===", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
