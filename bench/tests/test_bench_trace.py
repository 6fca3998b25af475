"""The reduction from a profiler trace to busy time, idle share, kernel and
collective time: by hand on a made-up record, and on a small trace recorded
on a v5e (``data/``)."""

import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_nesting():
    assert trace.union([(0, 10), (2, 3), (5, 12), (20, 25), (25, 30)]) == [
        [0, 12], [20, 30]]
    assert trace.union([]) == []


def test_kinds():
    assert trace.kind_of("all-reduce.3") == trace.COLLECTIVE
    assert trace.kind_of("all-gather-start") == trace.COLLECTIVE
    assert trace.kind_of("fusion.12", "custom-call") == trace.KERNEL
    assert trace.kind_of("custom-call.7") == trace.KERNEL
    assert trace.kind_of("fusion.12", "loop fusion") == trace.OTHER


def test_reduce_by_hand():
    # device 0: busy [0,40) and [60,80) -> 60 of 100 ns; device 1: [0,50)
    record = {
        "devices": {
            "0": [["fusion.1", 0, 30, "other"], ["custom-call.2", 10, 30, "kernel"],
                  ["all-reduce.1", 60, 20, "collective"]],
            "1": [["fusion.1", 0, 50, "other"]],
        },
        "host": [["bench.dispatch", 35, 30], ["bench.window", 0, 100]],
    }
    r = trace.reduce(record, 100.0)
    assert r["devices"] == 2
    assert r["busy_ns_total"] == 110 and r["busy_ns_mean"] == 55
    assert r["idle_share"] == pytest.approx(0.45)
    assert r["kernel_ns_total"] == 30 and r["collective_ns_total"] == 20
    # the one gap on device 0, [40, 60), is inside the shorter host span
    assert r["idle_gaps"] == [["bench.dispatch", 20e-9]]
    assert r["device_ops"][0] == ["fusion.1", 80e-9]


RECORDED = sorted(DATA.glob("*.trace.json"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.stem for p in RECORDED])
def test_recorded_trace(path):
    rec = json.loads(path.read_text())
    expect = rec.pop("expect")
    r = trace.reduce(rec, rec["window_ns"])
    for key in ("devices", "busy_ns_total", "kernel_ns_total",
                "collective_ns_total"):
        assert r[key] == expect[key], key
    assert r["idle_share"] == pytest.approx(expect["idle_share"])
    assert 0.0 <= r["idle_share"] < 1.0
    # busy time is a union: never more than the summed operation time
    total = sum(d for ops in rec["devices"].values() for _, _, d, _ in ops)
    assert r["busy_ns_total"] <= total
