"""Find the benchmark's pieces by name.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: sizes, source, cuts, departures;
- ``workloads/<cell>.json``: the cell's driver, traffic parameters and
  the limits of its correctness check;
- ``drivers/<driver>.py``: ``run(ctx)`` drives one kind of traffic;
- ``models/<kind>.py``: the program's entry points for one model kind;
- ``reference/<config>.py``: the configuration's plain reference;
- ``flops/<config>.py``: its model operations per step;
- ``metrics/<metric>.py``: one reader per per-layer metric.

Adding a configuration, a cell or a metric adds files and entries; no file
here changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    return json.loads(path.read_text(encoding="utf-8"))


def _module(path: Path, kind: str):
    if not path.is_file():
        raise FileNotFoundError(f"benchmark {kind} {path} is missing")
    name = f"bench_{kind}_{path.stem.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Layout:
    """The benchmark rooted at ``root`` (a checkout: ``BENCHMARK.json``
    beside ``bench/``)."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.spec = _json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        entries = [w for w in self.spec["workloads"] if w["name"] == name]
        if not entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json (cells: "
                           f"{sorted(w['name'] for w in self.spec['workloads'])})")
        return {**entries[0], **_json(self.bench / "workloads" / f"{name}.json")}

    def config(self, name: str) -> dict:
        return _json(self.bench / "configs" / f"{name}.json")

    def driver(self, name: str):
        return _module(self.bench / "drivers" / f"{name}.py", "driver")

    def model(self, kind: str):
        return _module(self.bench / "models" / f"{kind}.py", "model")

    def reference(self, config: str):
        return _module(self.bench / "reference" / f"{config}.py", "reference")

    def flops(self, config: str):
        return _module(self.bench / "flops" / f"{config}.py", "flops")

    def reader(self, metric: str):
        return _module(self.bench / "metrics" / f"{metric}.py", "metric")

    def peaks(self, device_kind: str) -> dict:
        table = _json(self.bench / "peaks.json")
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"bench/peaks.json (known: {sorted(table)})")
        return table[device_kind]

    def _applies(self, metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.spec["end_to_end"] if self._applies(m, cell)]

    def per_layer(self, cell: str) -> list:
        return [m for m in self.spec["per_layer"] if self._applies(m, cell)]
