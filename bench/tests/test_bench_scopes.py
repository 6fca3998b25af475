"""Device time by the program's named scopes and the time between step
executions: by hand on made-up records, on a serialized trace built field
by field, and on a trace recorded on a v5e (``data/``)."""

import json
from pathlib import Path

import pytest

from bench import scopes, trace

DATA = Path(__file__).resolve().parent / "data"


def test_scopes_of_name_stacks():
    assert scopes.scopes_of(
        "jit(step)/jvp(sde.solve)/while/body/sde.field/tanh") == {
            scopes.SOLVE, scopes.FIELD}
    # a custom_vjp backward rule's own scope, wrapped by the transforms
    assert scopes.scopes_of(
        "jit(step)/transpose(jvp(sde.adjoint))/while/body/sde.brownian/add"
    ) == {scopes.ADJOINT, scopes.BROWNIAN}
    assert scopes.scopes_of(
        "jit(step)/transpose(jvp(sde.adjoint))/transpose(jvp(sde.field))/mul"
    ) == {scopes.ADJOINT, scopes.FIELD}
    # plain autodiff: the forward loop's scope under a transpose is adjoint
    assert scopes.scopes_of("jit(step)/transpose(jvp(sde.solve))/while/add") \
        == {scopes.ADJOINT}
    assert scopes.scopes_of(
        "jit(loss)/transpose(jvp(outer))/sde.solve/while/body/add") == {
            scopes.ADJOINT}
    # merged operations join their stacks with ';'
    assert scopes.scopes_of("jit(step)/sde.brownian/max;sde.brownian/x") == {
        scopes.BROWNIAN}
    assert scopes.scopes_of("jit(step)/jvp(jit(_normal))/erf_inv") == set()


def test_reduce_scopes_and_step_gap_by_hand():
    # device 0 over a 100 ns window: the step program runs [0, 40) and
    # [50, 90); a small eager module [42, 45) between them
    stacks = ["jit(step)/jvp(sde.solve)/while/body/sde.field/dot",
              "jit(step)/jvp(sde.solve)/while/body/sde.brownian/brownian",
              "jit(step)/transpose(jvp(sde.adjoint))/while/body/sub",
              "jit(step)/transpose(jvp(sde.solve))/while/body/sde.field/mul",
              "jit(step)/adadelta/mul",
              "jit(_threefry_fold_in)/xor"]
    record = {
        "devices": {"0": [["while.1", 0, 40, "container"],
                          ["fusion.1", 0, 10, "other"],
                          ["brownian_increment.2", 10, 10, "kernel"],
                          ["fusion.3", 20, 20, "other"],
                          ["xor.1", 42, 3, "other"],
                          ["fusion.4", 50, 30, "other"],
                          ["fusion.5", 80, 5, "other"]]},
        "host": [],
        "modules": {"0": [["jit_step(7)", 0, 40],
                          ["jit__threefry_fold_in(9)", 42, 3],
                          ["jit_step(7)", 50, 40]]},
        "name_stacks": {"names": stacks,
                        "devices": {"0": [0, 0, 1, 2, 5, 3, 4]}},
    }
    r = scopes.reduce(record, 100.0)
    assert r["leaf_ns_total"] == 78
    assert r["scope_ns_total"] == {scopes.SOLVE: 20, scopes.ADJOINT: 50,
                                   scopes.BROWNIAN: 10, scopes.FIELD: 40}
    assert r["step_module"] == "jit_step(7)"
    assert r["step_gap_ns_mean"] == 20
    # the leaves are those bench.trace counts as busy
    assert trace.reduce(record, 100.0)["idle_share"] == pytest.approx(
        1 - r["leaf_ns_total"] / 100)
    # without name stacks or module executions only the leaf time is left
    old = {k: v for k, v in record.items() if k in ("devices", "host")}
    assert scopes.reduce(old, 100.0) == {"leaf_ns_total": 78}
    assert scopes.reduce({"devices": {}, "host": []}, 100.0) == {}


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message of ``(number, int | bytes | str)`` fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def test_hlo_op_names_and_name_stacks_from_a_serialized_trace():
    def instruction(name, op_name):
        return _msg((1, name), (2, "add"), (7, _msg((1, "add"), (2, op_name))))

    hlo = _msg((1, _msg((1, "jit_step"), (3, _msg(
        (1, "main"), (2, instruction("fusion.3", "jit(step)/sde.field/add")),
        (2, instruction("add.1", "jit(step)/transpose(jvp(sde.adjoint))/add"))
    )))))
    metadata = _msg(
        (1, 3), (2, "/host:metadata"),
        (4, _msg((1, 7), (2, _msg((1, 7), (2, "jit_step(7)"),
                                  (5, _msg((1, 1), (6, hlo))))))),
        (5, _msg((1, 1), (2, _msg((1, 1), (2, scopes.HLO_PROTO_STAT))))))
    device = _msg((1, 1), (2, "/device:TPU:0"), (3, _msg((1, 1), (2, "XLA Ops"))))
    names = scopes.hlo_op_names(_msg((1, device), (1, metadata)))
    assert names == {"jit_step(7)": {
        "fusion.3": "jit(step)/sde.field/add",
        "add.1": "jit(step)/transpose(jvp(sde.adjoint))/add"}}
    record = {"devices": {"0": [["fusion.3", 10, 5, "other"],
                                ["add.1", 20, 5, "other"],
                                ["add.1", 40, 5, "other"]]},
              "modules": {"0": [["jit_step(7)", 0, 30]]}}
    stacks = scopes.name_stacks(record, names)
    # the op after the module's execution ended belongs to no module
    assert stacks == {"names": ["jit(step)/sde.field/add",
                                "jit(step)/transpose(jvp(sde.adjoint))/add"],
                      "devices": {"0": [0, 1, None]}}
    assert scopes.name_stacks(record, {}) is None


RECORDED = sorted(DATA.glob("*.trace.json"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.stem for p in RECORDED])
def test_recorded_trace_scopes(path):
    rec = json.loads(path.read_text())
    expect = rec.pop("expect")
    r = scopes.reduce(rec, rec["window_ns"])
    if "name_stacks" not in rec:
        # recorded before name stacks and module executions were kept
        assert "scope_ns_total" not in r and "step_gap_ns_mean" not in r
        return
    assert r["leaf_ns_total"] == expect["leaf_ns_total"]
    assert r["scope_ns_total"] == expect["scope_ns_total"]
    assert r["step_module"] == expect["step_module"]
    assert r["step_gap_ns_mean"] == pytest.approx(expect["step_gap_ns_mean"])
    # the scopes lie inside the leaf time, and solve and adjoint never
    # overlap; the gap between steps is part of the idle time
    shares = {k: v / r["leaf_ns_total"] for k, v in r["scope_ns_total"].items()}
    assert all(0.0 <= v <= 1.0 for v in shares.values())
    assert shares[scopes.SOLVE] + shares[scopes.ADJOINT] <= 1.0
    idle = trace.reduce(rec, rec["window_ns"])["idle_share"]
    assert 0.0 <= r["step_gap_ns_mean"] / rec["window_ns"] <= idle
