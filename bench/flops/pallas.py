"""Bytes the Pallas kernels of one step move, from the step's program.

Every ``pallas_call`` in the step's jaxpr is charged the logical
(unpadded) sizes of its operands and results, times the trip counts of the
loops around it and the number of shards of a ``shard_map`` around it.
"""

from __future__ import annotations

import math


def _nbytes(aval) -> int:
    return math.prod(aval.shape) * aval.dtype.itemsize


def _subjaxprs(eqn):
    """``(jaxpr, multiplier)`` for every sub-program of an equation."""
    name = eqn.primitive.name
    if name == "scan":
        yield eqn.params["jaxpr"].jaxpr, eqn.params["length"]
        return
    if name == "while":
        raise ValueError("a while loop around Pallas kernels has no static "
                         "trip count")
    if name == "cond":
        branches = eqn.params["branches"]
        if any(calls(b.jaxpr) for b in branches):
            raise ValueError("a cond around Pallas kernels: which branch "
                             "runs is not known from shapes")
        return
    mult = 1
    if name == "shard_map":
        mult = math.prod(eqn.params["mesh"].shape.values())
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            inner = getattr(j, "jaxpr", j)
            if hasattr(inner, "eqns"):
                yield inner, mult


def calls(jaxpr, mult: int = 1) -> list:
    """``[(kernel name, bytes moved per step)]``, one entry per call site."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            moved = sum(_nbytes(v.aval) for v in eqn.invars + eqn.outvars)
            name = str(eqn.params.get("name_and_src_info", "pallas_call"))
            out.append((name.split(" ")[0], moved * mult))
            continue
        for sub, m in _subjaxprs(eqn):
            out += calls(sub, mult * m)
    return out


def bytes_per_step(fn, *args) -> int:
    """Total bytes of every Pallas kernel call in one call of ``fn``."""
    import jax

    return sum(b for _, b in calls(jax.make_jaxpr(fn)(*args).jaxpr))
