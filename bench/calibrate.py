"""Readings the limits of a cell's correctness check are set from, and the
serving knee; run on the chip, not by the benchmark's own runs.

    python3 -m bench.calibrate --workload <cell> --seeds 12 --seconds 2 \\
        --variants control:3,half_batch:3
    python3 -m bench.calibrate --workload ou_gan.serve_poisson --seconds 10 \\
        --rates 10,20,40,80

The first form runs the program on ``--seeds`` seeds (the lower readings)
and each variant on as many seeds as it names: ``control`` puts the
reference, at three bfloat16 passes, in the program's place; the rest are
planted faults (see the drivers).
The second sweeps offered load.  Everything runs in one process, one
JSON line per run on standard output, and a summary line last: for each
number, the largest the program read and the smallest each variant read.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run as harness
from bench.layout import ROOT, Layout


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=0)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--variants", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    harness.enable_cache(ROOT)
    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    from bench import device

    identity = device.identify(cell["chips"])
    peaks = layout.peaks(identity["kind"])

    def one(seed, variant=None, **extra):
        t0 = time.perf_counter()
        r = harness.measure(layout, args.workload, seed, args.seconds, False,
                            variant=variant, identity=identity, peaks=peaks)
        r.update(seed=seed, variant=variant, run_s=time.perf_counter() - t0,
                 **extra)
        print(json.dumps(r), flush=True)
        return r

    summary = {}
    seed = args.first_seed
    for _ in range(args.seeds):
        r = one(seed)
        seed += 1
        for k, c in r["checks"].items():
            summary.setdefault(k, {}).setdefault("program_max", -1.0)
            summary[k]["program_max"] = max(summary[k]["program_max"],
                                            c["value"])
    for spec in filter(None, args.variants.split(",")):
        variant, n = spec.split(":")
        for _ in range(int(n)):
            r = one(seed, variant)
            seed += 1
            for k, c in r["checks"].items():
                cur = summary.setdefault(k, {}).get(f"{variant}_min")
                summary[k][f"{variant}_min"] = (c["value"] if cur is None
                                                else min(cur, c["value"]))
    if args.rates:
        base = layout.cell
        for rate in (float(x) for x in args.rates.split(",")):
            layout.cell = (lambda name, _r=rate: {
                **base(name), "params": {**base(name)["params"],
                                         "rate_per_s": _r}})
            one(seed, rate=rate)
            seed += 1
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
