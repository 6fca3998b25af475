"""The benchmark finds its pieces by name, BENCHMARK.json and the files
agree, and a new cell, configuration and metric take new files only."""

import json
import shutil

import pytest

from bench.layout import Layout
from bench.tests import tiny

LAYOUT = Layout(tiny.REPO)
SPEC = LAYOUT.spec


def test_every_named_piece_is_found():
    for c in SPEC["configs"]:
        cfg = LAYOUT.config(c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert (tiny.REPO / c["file"]).is_file()
        LAYOUT.reference(c["name"])
        LAYOUT.model(cfg["kind"])
        LAYOUT.flops(cfg["kind"])
    for w in SPEC["workloads"]:
        cell = LAYOUT.cell(w["name"])
        assert cell["config"] in {c["name"] for c in SPEC["configs"]}
        LAYOUT.driver(cell["driver"])
        assert set(cell["limits"])


def test_readers_declare_what_benchmark_json_says():
    for m in SPEC["per_layer"]:
        r = LAYOUT.reader(m["name"])
        assert (r.LAYER, r.UNIT, r.MOVES, r.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), m["name"]


def test_every_cell_reports_what_it_must():
    for w in SPEC["workloads"]:
        e2e = {m["name"] for m in LAYOUT.end_to_end(w["name"])}
        layer = LAYOUT.per_layer(w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2 and layer, w["name"]
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        LAYOUT.cell("no_such.cell")
    with pytest.raises(FileNotFoundError):
        LAYOUT.reader("no_such_metric")
    with pytest.raises(KeyError):
        LAYOUT.peaks("TPU v0 imaginary")


def test_a_cell_config_and_metric_added_as_files_only(tmp_path):
    root = tiny.checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "ou_gan.json").read_text())
    cfg["name"] = "ou_gan_wide"
    cfg["model"]["width"] = 6
    (bench / "configs" / "ou_gan_wide.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "reference" / "ou_gan.py",
                bench / "reference" / "ou_gan_wide.py")
    shutil.copy(bench / "workloads" / "ou_gan.train_b1024.json",
                bench / "workloads" / "ou_gan_wide.train_b8.json")
    (bench / "metrics" / "steps_in_window.py").write_text(
        'LAYER = "training step (launch/steps.py)"\nUNIT = "steps"\n'
        'MOVES = "train_paths_per_s"\nSOURCE = "host_clock"\n\n'
        'def read(run):\n    return run.facts.get("steps")\n')
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ou_gan_wide", "source": cfg["source"],
                            "file": "bench/configs/ou_gan_wide.json",
                            "reduced": [], "why": "throwaway"})
    spec["workloads"].append({"name": "ou_gan_wide.train_b8",
                              "config": "ou_gan_wide", "traffic": "train_b8",
                              "chips": 1, "why": "throwaway"})
    spec["end_to_end"][0]["workloads"].append("ou_gan_wide.train_b8")
    spec["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "training step (launch/steps.py)",
                              "moves": "train_paths_per_s",
                              "workloads": ["ou_gan_wide.train_b8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    r = tiny.measure(root, "ou_gan_wide.train_b8", trace=True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["steps_in_window"]["value"] > 0
    r = tiny.measure(root, "ou_gan_wide.train_b8")
    assert {"train_paths_per_s", "setup_s"} <= set(r["metrics"])
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file the benchmark had was touched
