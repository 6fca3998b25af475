"""Adapters from a configuration's ``model`` kind to the system under test:
which of the program's entry points builds the timed path, and how its
state is read back for the comparison."""

from __future__ import annotations

import dataclasses
from typing import Callable


@dataclasses.dataclass(frozen=True)
class TrainProgram:
    """One training configuration's timed path.

    ``state(params)`` builds the optimiser state around the benchmark's
    weights; ``step(state, key) -> (state, losses)`` is the program's
    compiled step; ``params``/``first_grads`` read a state back (the
    gradient as the optimiser received it, recovered from its state after
    one step); ``unchanged`` is the planted fault of a step that returns
    its state untouched."""

    state: Callable
    step: Callable
    params: Callable
    first_grads: Callable
    unchanged: Callable
