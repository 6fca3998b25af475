"""Names of the solve stack's layers inside the compiled program.

Each name is a ``jax.named_scope``: it lands in the ``op_name`` metadata of
every HLO operation traced inside it, and a profiler trace carries that
name beside the operation's device time.  A scope is metadata only; the
compiled program is the same with or without it.

========================  =================================================
scope                     covers
========================  =================================================
``sde.solve``             the forward time loop of every gradient backend
``sde.adjoint``           the exact adjoint's backward rules (state
                          reconstruction and local VJPs)
``sde.brownian``          Brownian increments and point values
``sde.field``             drift and diffusion evaluations, and their VJPs
``sde.encode``            the Latent SDE's encoder: the GRU over the
                          observations and the ``qz0`` and ``zeta`` heads
========================  =================================================

Under plain autodiff (the ``discretise`` and ``checkpoint`` backends) the
backward of a forward loop keeps the ``sde.solve`` component and gains a
``transpose(...)`` component before it; a reader counts such operations as
adjoint work.
"""

from __future__ import annotations

import functools

import jax

SOLVE = "sde.solve"
ADJOINT = "sde.adjoint"
BROWNIAN = "sde.brownian"
FIELD = "sde.field"
ENCODE = "sde.encode"


def scope(name: str):
    """The context that names the operations traced inside it."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: every call of the function is traced inside ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)

        return call

    return wrap
