"""Device time by the program's named scopes, and the time between
executions of the step program, from a profiler trace.

:func:`load` reads what :func:`bench.trace.load` reads and keeps two more
things where the trace holds them: each operation's name stack (the
``op_name`` of its HLO instruction, from the HLO protos the profiler stores
in its metadata plane; a v5e's "XLA Ops" events carry no ``tf_op`` stat)
and the executions of each compiled module ("XLA Modules" line).
:func:`reduce` sums leaf device time by the program's scopes
(``repro.core.scopes``) and measures the window less the executions of the
step program.  Both only add keys to what :mod:`bench.trace` gives.
"""

from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

from bench import trace

#: The program's named scopes, the layers whose device time :func:`reduce`
#: sums.
SOLVE, ADJOINT, BROWNIAN, FIELD = ("sde.solve", "sde.adjoint", "sde.brownian",
                                   "sde.field")
SCOPES = (SOLVE, ADJOINT, BROWNIAN, FIELD)

#: The profiler's line of compiled-module executions on a device plane,
#: and the stat of its metadata plane that holds each module's HLO.
MODULES_LINE, HLO_PROTO_STAT = "XLA Modules", "Hlo Proto"


def load(trace_dir, window_ns=None) -> dict:
    """:func:`bench.trace.load`'s record of the first ``.xplane.pb`` under
    ``trace_dir``, and, where the trace holds them, ``"modules": {id:
    [[module, start_ns, dur_ns], ...]}`` and ``"name_stacks": {"names":
    [stack, ...], "devices": {id: [index into names or None, ...]}}``, the
    latter parallel to ``devices``."""
    from jax.profiler import ProfileData

    record = trace.load(trace_dir, window_ns)
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[0]
    modules = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            runs = modules.setdefault(plane.name.rsplit(":", 1)[1], [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    runs.extend([ev.name, ev.start_ns, ev.duration_ns]
                                for ev in line.events)
    if any(modules.values()):
        record["modules"] = dict(sorted(modules.items()))
        stacks = name_stacks(record, hlo_op_names(path.read_bytes()))
        if stacks is not None:
            record["name_stacks"] = stacks
    return record


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, span=None):
    """``(field number, value)`` of the protobuf message in ``buf[span]``:
    an int for a varint, the ``(start, end)`` span of a length-delimited
    field (a string, bytes or a nested message)."""
    i, end = span or (0, len(buf))
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield number, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def hlo_op_names(xspace: bytes) -> dict:
    """``{module: {instruction: op_name}}`` from the HLO protos in a
    serialized XSpace's metadata plane (``XPlane.event_metadata``, one
    entry per compiled module, named as its executions are on the
    ``XLA Modules`` line).  Field numbers are those of
    ``tsl/profiler/protobuf/xplane.proto`` and ``xla/service/hlo.proto``."""
    buf = memoryview(xspace)
    out = {}
    for number, plane in _fields(buf):
        if number != 1:                                     # XSpace.planes
            continue
        name = next((_text(buf, v) for n, v in _fields(buf, plane) if n == 2),
                    None)
        if name != "/host:metadata":
            continue
        fields = list(_fields(buf, plane))
        stat_names = {}
        for n, entry in fields:
            if n == 5:                                      # stat_metadata
                key = value = None
                for k, v in _fields(buf, entry):
                    if k == 1:
                        key = v
                    elif k == 2:
                        value = next((_text(buf, s) for f, s in _fields(buf, v)
                                      if f == 2), None)
                stat_names[key] = value
        for n, entry in fields:
            if n != 4:                                      # event_metadata
                continue
            name, proto = None, None
            for k, meta in _fields(buf, entry):
                if k != 2:
                    continue
                for f, v in _fields(buf, meta):
                    if f == 2:                              # name
                        name = _text(buf, v)
                    elif f == 5:                            # stats
                        stat = dict(_fields(buf, v))
                        if stat_names.get(stat.get(1)) == HLO_PROTO_STAT \
                                and 6 in stat:              # bytes_value
                            proto = stat[6]
            if name is not None and proto is not None:
                out[name] = _instruction_op_names(buf, proto)
    return out


def _instruction_op_names(buf, hlo_proto) -> dict:
    names = {}
    for n, module in _fields(buf, hlo_proto):
        if n != 1:                                          # HloProto.hlo_module
            continue
        for m, computation in _fields(buf, module):
            if m != 3:                                      # computations
                continue
            for c, instruction in _fields(buf, computation):
                if c != 2:                                  # instructions
                    continue
                name = op_name = None
                for f, v in _fields(buf, instruction):
                    if f == 1:
                        name = _text(buf, v)
                    elif f == 7:                            # metadata
                        op_name = next((_text(buf, s) for g, s in
                                        _fields(buf, v) if g == 2), None)
                if name is not None and op_name:
                    names[name] = op_name
    return names


def name_stacks(record: dict, op_names: dict):
    """Each device operation's name stack, by the module execution that
    holds its start, as ``{"names": [...], "devices": {id: [index or
    None, ...]}}``; ``None`` where the trace named no module's HLO."""
    if not op_names:
        return None
    names, index, per_device = [], {}, {}
    for dev, ops in record["devices"].items():
        runs = sorted(record.get("modules", {}).get(dev, []),
                      key=lambda m: m[1])
        starts = [m[1] for m in runs]
        out = per_device[dev] = []
        for name, start, _, _ in ops:
            i = bisect.bisect_right(starts, start) - 1
            stack = None
            if i >= 0 and start <= runs[i][1] + runs[i][2]:
                stack = op_names.get(runs[i][0], {}).get(name)
            if stack is not None and stack not in index:
                index[stack] = len(names)
                names.append(stack)
            out.append(None if stack is None else index[stack])
    return {"names": names, "devices": per_device}


_WRAPPED = re.compile(r"^[\w.]*\((.*)\)$")


def scopes_of(stack: str) -> set:
    """The scopes an operation counts for, from its name stack, e.g.
    ``jit(step)/transpose(jvp(sde.adjoint))/while/body/sde.brownian/add``.
    A component (``/`` separates them, ``;`` joins the stacks of merged
    operations) counts for the scope it names once transformation wrappers
    such as ``jvp(...)`` and ``transpose(...)`` are taken off.  Forward
    work (``sde.solve``) at or under a ``transpose(...)`` component is
    plain autodiff's backward of the loop, and counts as adjoint work, so
    ``sde.solve`` and ``sde.adjoint`` never overlap."""
    found, transposed = set(), False
    for comp in re.split(r"[/;]", stack):
        transposed = transposed or comp.startswith("transpose(")
        while (m := _WRAPPED.match(comp)) is not None:
            comp = m.group(1)
        if comp == SOLVE:
            found.add(ADJOINT if transposed else SOLVE)
        elif comp in SCOPES:
            found.add(comp)
    if ADJOINT in found:
        found.discard(SOLVE)
    return found


def reduce(record: dict, window_ns: float) -> dict:
    """``leaf_ns_total``, the summed time of the leaf operations (loops and
    calls left out, as :func:`bench.trace.reduce` leaves them out) over all
    devices; ``scope_ns_total``, that time by scope, where the program names
    a scope; and ``step_module``/``step_gap_ns_mean``, where the record
    holds module executions."""
    stacks = record.get("name_stacks")
    scope_ns = collections.Counter({s: 0 for s in SCOPES})
    memo = {}
    leaf_ns, devices = 0, []
    for dev, all_ops in record["devices"].items():
        leaves = [o[2] for o in all_ops if o[3] != trace.CONTAINER]
        if not leaves:
            continue
        devices.append(dev)
        leaf_ns += sum(leaves)
        if stacks is None:
            continue
        for (_, _, d, k), i in zip(all_ops, stacks["devices"][dev]):
            if k == trace.CONTAINER or i is None:
                continue
            if i not in memo:
                memo[i] = scopes_of(stacks["names"][i])
            for scope in memo[i]:
                scope_ns[scope] += d
    if not devices:
        return {}
    out = {"leaf_ns_total": leaf_ns}
    if any(scope_ns.values()):
        # a program that names no scopes leaves the table out: a share of
        # it then reads nothing
        out["scope_ns_total"] = dict(scope_ns)
    if record.get("modules"):
        out.update(_step_gap(record["modules"], devices, window_ns))
    return out


def _step_gap(modules: dict, devices: list, window_ns: float) -> dict:
    """The step program (the module with the most device time) and the
    window less the union of its executions, averaged over ``devices``."""
    time_in = collections.Counter()
    for runs in modules.values():
        for name, _, d in runs:
            time_in[name] += d
    if not time_in:
        return {}
    step = max(time_in, key=time_in.get)
    gap = [window_ns - sum(e - s for s, e in trace.union(
        (s, s + d) for name, s, d in modules.get(dev, []) if name == step))
        for dev in devices]
    return {"step_module": step, "step_gap_ns_mean": sum(gap) / len(gap)}
