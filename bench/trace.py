"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load` reads the ``.xplane.pb`` file JAX's profiler wrote into a
compact record: per device, the operations that ran (name, start, length,
and which kind of work they are), and the benchmark's own host spans
(``bench.*`` annotations).  :func:`reduce` turns that record into busy
time (the union of operation intervals, so nested or overlapping events
count once), idle share, Mosaic-kernel time, collective time, the longest
leaf operations (loops and calls around them are left out) and the
longest idle gaps, each gap named by the host span that covers it.  The
record is plain JSON, so a small one is kept with the tests.
"""

from __future__ import annotations

import bisect
import collections
from pathlib import Path

#: Names the benchmark's host spans start with.
HOST_SPAN = "bench."

#: Operation kinds: a Pallas (Mosaic) kernel, a collective, a loop or call
#: around other operations, anything else.
KERNEL, COLLECTIVE, CONTAINER, OTHER = "kernel", "collective", "container", "other"

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_CONTAINERS = (" while(", " conditional(", " call(")


def short_name(name: str) -> str:
    """``%rev_heun_phase2.18 = f32[...] custom-call(...)`` -> the HLO
    instruction's name, ``rev_heun_phase2.18``."""
    return name.split(" = ", 1)[0].lstrip("%")


def kind_of(name: str, category: str = "") -> str:
    """Classify a device operation by its HLO text (the TPU trace names an
    operation by its whole instruction) and category."""
    low, cat = name.lower(), category.lower()
    op = low.split(" = ", 1)[-1]
    if any(c in op for c in _CONTAINERS):
        return CONTAINER
    if any(short_name(low).startswith(c) or f" {c}" in op or c in cat
           for c in _COLLECTIVES):
        return COLLECTIVE
    if "custom-call" in cat or " custom-call(" in op \
            or short_name(low).startswith("custom-call"):
        return KERNEL
    return OTHER


def _stats(event) -> dict:
    try:
        return {str(k): v for k, v in event.stats}
    except Exception:  # noqa: BLE001 - stats are optional decoration
        return {}


def load(trace_dir, window_ns=None) -> dict:
    """The compact record of the first ``.xplane.pb`` under ``trace_dir``:
    ``{"devices": {id: [[name, start_ns, dur_ns, kind], ...]},
    "host": [[name, start_ns, dur_ns], ...], "window_ns": ...}``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb trace under {trace_dir}")
    data = ProfileData.from_file(str(files[0]))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            ops = devices.setdefault(int(plane.name.rsplit(":", 1)[1]), [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    cat = str(_stats(ev).get("hlo_category", ""))
                    ops.append([short_name(ev.name), ev.start_ns,
                                ev.duration_ns, kind_of(ev.name, cat)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"devices": {str(k): v for k, v in sorted(devices.items())},
            "host": host, "window_ns": window_ns}


def union(intervals) -> list:
    """Merge ``[(start, end)]`` into disjoint sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _covering(spans, starts, t) -> str:
    """The shortest of the (start-sorted) host spans that began within the
    last few before ``t`` and still cover it."""
    best = None
    for name, s, d in spans[max(0, bisect.bisect_right(starts, t) - 8):
                            bisect.bisect_right(starts, t)]:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "host:no bench span"


def reduce(record: dict, window_ns: float) -> dict:
    """Busy, idle, kernel and collective time over a window of
    ``window_ns``, per device and summed, plus the breakdown lists."""
    per_device = {}
    op_time = collections.Counter()
    gaps = []
    spans = sorted(record["host"], key=lambda h: h[1])
    starts = [h[1] for h in spans]
    for dev, all_ops in record["devices"].items():
        # loops and calls hold other operations: the leaves are the work
        ops = [o for o in all_ops if o[3] != CONTAINER]
        if not ops:
            continue
        busy = union((s, s + d) for _, s, d, _ in ops)
        busy_ns = sum(e - s for s, e in busy)
        kinds = collections.Counter()
        for name, s, d, k in ops:
            kinds[k] += d
            op_time[name] += d
        per_device[dev] = {"busy_ns": busy_ns,
                           "kernel_ns": kinds[KERNEL],
                           "collective_ns": kinds[COLLECTIVE]}
        if dev == min(record["devices"]):
            for (_, e0), (s1, _) in zip(busy, busy[1:]):
                gaps.append((s1 - e0, _covering(spans, starts, (e0 + s1) / 2)))
    n = len(per_device)
    if not n:
        return {"devices": 0}
    total = {k: sum(d[k] for d in per_device.values())
             for k in ("busy_ns", "kernel_ns", "collective_ns")}
    by_span = collections.Counter()
    for length, span in gaps:
        by_span[span] += length
    return {
        "devices": n,
        "window_ns": window_ns,
        "busy_ns_mean": total["busy_ns"] / n,
        "busy_ns_total": total["busy_ns"],
        "kernel_ns_total": total["kernel_ns"],
        "collective_ns_total": total["collective_ns"],
        "idle_share": 1.0 - total["busy_ns"] / n / window_ns,
        "device_ops": [[k, v / 1e9] for k, v in op_time.most_common(10)],
        "idle_gaps": [[k, v / 1e9] for k, v in by_span.most_common(10)],
    }
