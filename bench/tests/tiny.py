"""A copy of the benchmark at sizes a CPU test run can hold.

``checkout(tmp_path)`` copies ``BENCHMARK.json`` and ``bench/`` into
``tmp_path``, points ``src`` at the program, and cuts every configuration
and cell to a few rows, units and steps; ``measure`` runs a cell there with
the harness's look for a chip skipped.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: Every cell's and configuration's cut, by name.
CONFIGS = {
    "airq_latent": {"hidden_dim": 2, "context_dim": 3, "initial_noise_dim": 2,
                    "width": 4, "num_steps": 3},
    "ou_gan": {"hidden_dim": 2, "noise_dim": 2, "initial_noise_dim": 2,
               "width": 4, "disc_hidden_dim": 2, "disc_width": 4,
               "num_steps": 3, "t1": 3.0},
}
CELLS = {
    "airq_latent.train_b1024": {"batch": 8, "seq_len": 4},
    "airq_latent.train_b4096_dp4": {"batch": 16, "seq_len": 4},
    "ou_gan.train_b1024": {"batch": 8, "seq_len": 4},
    "ou_gan.serve_poisson": {"max_batch": 8, "chunks": 3, "size_max": 4,
                             "rate_per_s": 16.0, "check_rows": 8},
}
#: The limits at this size, between what the program and the control read
#: here on the CPU (seeds ``2**31 + 5``, 7, 123456789): program loss1 /
#: grad1 / dparam3_median at most 0 / 1.9e-7 / 6.4e-6 (latent) and
#: 1.1e-7 / 1.2e-5 / 1.2e-7 (GAN); the control (three bf16 passes) reads
#: grad1 8.3e-6 (latent) and 1.7e-4 (GAN); half the batch reads loss1
#: 0.14 and 0.15; rows 8.3e-8 against the control's 1.3e-5.  The cells'
#: own limits are set on the chip at their own sizes.
LIMITS = {
    "airq_latent.train_b1024": {"loss1": 1e-5, "grad1": 2e-6, "dparam3_median": 1e-3},
    "airq_latent.train_b4096_dp4": {"loss1": 1e-5, "grad1": 2e-6, "dparam3_median": 1e-3},
    "ou_gan.train_b1024": {"loss1": 1e-4, "grad1": 6e-5, "dparam3_median": 1e-3},
    "ou_gan.serve_poisson": {"rows": 1e-6},
}
IDENTITY = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

#: Cells whose files are in ``bench/`` but which BENCHMARK.json leaves out
#: until they can pass on the chip (PERF.md, Open questions); the tests
#: still drive them, so their harness stays sound.
_TRAIN = ["airq_latent.train_b1024", "airq_latent.train_b4096_dp4"]
_SERVE = ["ou_gan.serve_poisson"]
HELD_BACK = {
    "configs": [{"name": "airq_latent", "file": "bench/configs/airq_latent.json",
                 "source": ("https://github.com/google-research/torchsde/blob/"
                            "master/examples/latent_sde_lorenz.py"),
                 "reduced": [], "why": "held back"}],
    "workloads": [{"name": n, "config": c, "traffic": n.split(".")[1],
                   "chips": 4 if n.endswith("dp4") else 1, "why": "held back"}
                  for n, c in [(n, "airq_latent") for n in _TRAIN]
                  + [(n, "ou_gan") for n in _SERVE]],
    "end_to_end": [
        {"name": "serve_p95_ms", "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": _SERVE},
        {"name": "serve_p50_ms", "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": _SERVE}],
    "per_layer": [
        {"name": "idle_share.serve", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device", "moves": "serve_p95_ms",
         "workloads": _SERVE},
        {"name": "sched_step_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "moves": "serve_p95_ms", "workloads": _SERVE,
         "layer": "serving scheduler (serving/scheduler.py)"},
        {"name": "chunk_batch_rows", "unit": "rows", "better": "higher",
         "source": "program_counter", "moves": "serve_p95_ms",
         "workloads": _SERVE, "layer": "serving scheduler (serving/scheduler.py)"},
        {"name": "collective_share.train", "unit": "%", "better": "lower",
         "source": "device_trace", "moves": "train_paths_per_s",
         "workloads": _TRAIN[1:],
         "layer": "data-parallel exchange (distributed/sharding.py)"}],
    # metrics BENCHMARK.json has, extended to the held-back training cells
    "extend": {"train_paths_per_s": _TRAIN, "train_mfu": _TRAIN,
               "kernel_roofline.train": _TRAIN, "idle_share.train": _TRAIN},
}

def _edit(path: Path, change) -> None:
    data = json.loads(path.read_text())
    change(data)
    path.write_text(json.dumps(data))


def checkout(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {x["name"] for x in spec[key]}
        spec[key] += [x for x in HELD_BACK[key] if x["name"] not in have]
    for m in spec["end_to_end"] + spec["per_layer"]:
        for cell in HELD_BACK["extend"].get(m["name"], []):
            if cell not in m["workloads"]:
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "src").symlink_to(REPO / "src")
    for name, cut in CONFIGS.items():
        _edit(root / "bench" / "configs" / f"{name}.json",
              lambda d, cut=cut: d["model"].update(cut))
    for name, cut in CELLS.items():
        _edit(root / "bench" / "workloads" / f"{name}.json",
              lambda d, cut=cut, name=name: (d["params"].update(cut),
                                             d["limits"].update(LIMITS[name])))
    return root


def measure(root: Path, cell: str, seed: int = 2**31 + 5, seconds=0.3,
            trace=False, variant=None) -> dict:
    from bench import run
    from bench.layout import Layout

    layout = Layout(root)
    return run.measure(layout, cell, seed, seconds, trace, variant=variant,
                       identity=IDENTITY, peaks=layout.peaks(IDENTITY["kind"]))
