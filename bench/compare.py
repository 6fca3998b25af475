"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes, each against its limit."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        # NaN compares false: a missing or broken number fails
        return bool(self.value <= self.limit)


def leaf_norms(tree) -> dict:
    """``{path: L2 norm}`` of every leaf, in float64."""
    return {jax.tree_util.keystr(path): float(np.linalg.norm(
        np.asarray(leaf, np.float64).ravel()))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def leaf_gaps(program: dict, reference: dict, skip=()) -> dict:
    """``{path: |‖p‖ - ‖r‖| / max(‖r‖, median ‖r‖)}`` over the leaves not
    in ``skip``."""
    keep = [k for k in reference if k not in skip]
    median = float(np.median([reference[k] for k in keep]))
    return {k: abs(program[k] - reference[k]) / max(reference[k], median)
            for k in keep}


def worst_leaf_gap(program: dict, reference: dict, skip=()) -> tuple:
    """The largest of :func:`leaf_gaps` (a NaN is the largest); returns
    ``(gap, path)``."""
    gaps = leaf_gaps(program, reference, skip)
    where = max(gaps, key=lambda k: np.inf if np.isnan(gaps[k]) else gaps[k])
    return gaps[where], where


def median_leaf_gap(program: dict, reference: dict, skip=()) -> float:
    """The median of :func:`leaf_gaps`; NaN if any leaf's gap is NaN."""
    gaps = list(leaf_gaps(program, reference, skip).values())
    return float(np.nan if np.isnan(gaps).any() else np.median(gaps))


def negligible_leaves(ref_grads: dict, share: float = 1e-3) -> set:
    """Leaves whose reference gradient is under ``share`` of the median
    leaf's: nought to rounding, so an adaptive optimiser moves them by
    round-off alone."""
    median = float(np.median(list(ref_grads.values())))
    return {k for k, v in ref_grads.items() if v < share * median}


def loss_gap(program, reference) -> float:
    """Largest ``|p - r|`` over the losses given, over the largest ``|r|``
    among them."""
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    return float(np.max(np.abs(p - r)) / np.max(np.abs(r)))


def training_checks(prog: dict, ref: dict, limits: dict) -> tuple:
    """``prog``/``ref``: ``losses`` (steps x losses), ``grads`` (first
    step's gradient tree) and ``params0``/``params`` (before the first step
    and after the last).

    Returns the checks and, not compared, the worst-leaf change and every
    step's losses.  Round-off in the reversible adjoint's reconstruction
    grows by a factor that depends on the weights, and the later steps
    compound it, so those two swing from seed to seed; the first step's
    losses and the median leaf's change do not."""
    g_p, g_r = leaf_norms(prog["grads"]), leaf_norms(ref["grads"])
    skip = negligible_leaves(g_r)

    def change(r):
        return leaf_norms(jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                                       - np.asarray(b, np.float64),
                                       r["params"], r["params0"]))

    d_p, d_r = change(prog), change(ref)
    checks = [
        Check("loss1", loss_gap(prog["losses"][0], ref["losses"][0]),
              limits["loss1"]),
        Check("grad1", worst_leaf_gap(g_p, g_r)[0], limits["grad1"]),
        Check("dparam3_median", median_leaf_gap(d_p, d_r, skip),
              limits["dparam3_median"]),
    ]
    worst, where = worst_leaf_gap(d_p, d_r, skip)
    return checks, {"loss_all_steps": loss_gap(prog["losses"], ref["losses"]),
                    "dparam3_worst": worst, "dparam3_worst_leaf": where}


def rows_gap(program, reference) -> float:
    """Largest ``|p - r|`` over every served value, over the largest
    ``|r|``."""
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    return float(np.max(np.abs(p - r)) / np.max(np.abs(r)))
