"""Beyond-paper: the reversible Heun update as a *residual-stack* wrapper.

The paper (Appendix A) notes that residual networks are discretised ODEs.
We close the loop: treat the transformer layer stack as an ODE with a
layer-indexed vector field F(θ_n, ·) = unit_n(x) − x and integrate it with
the paper's OWN reversible Heun scheme (σ = 0, Δt = 1):

    ẑ_{n+1} = 2 z_n − ẑ_n + F(θ_n, ẑ_n)
    z_{n+1} = z_n + ½ (F(θ_n, ẑ_n) + F(θ_{n+1}, ẑ_{n+1}))

Because the update is algebraically reversible, the backward pass
reconstructs every intermediate activation in closed form — training stores
O(1) activations in depth (vs O(L) carried residual-streams under
scan+remat), at the cost of one extra F evaluation per unit on the backward
(same extra count as remat).  Gradients are exact (same custom_vjp as the
SDE adjoint — ``reversible_heun_solve_final``).

Enabled per-arch with ``cfg.reversible_residual=True``; the two-track
scheme is a (slightly) different architecture than the vanilla stack, so it
is a model choice, not a pure execution knob.  Used as the memory-term
hillclimb lever in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..core.gradients import reversible_heun_solve_final


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ZeroPath:
    """A Brownian-path stand-in whose increments are identically zero —
    turns the SDE machinery into the deterministic (ODE/resnet) case."""

    dtype: object = jnp.float32

    def tree_flatten(self):
        return (), (self.dtype,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(dtype=aux[0])

    def increment(self, n, num_steps: int):
        return jnp.zeros((), self.dtype)


def reversible_stack(cfg: ArchConfig, stacked_units, x, unit_residual) -> jax.Array:
    """Run the unit stack reversibly.  ``unit_residual(uparams, cfg, x) -> F``
    must return the residual delta of one unit.  Returns the final hidden
    state (terminal value only — nothing O(depth) is materialised)."""
    from .transformer import num_units

    n = num_units(cfg)

    def drift(p, t, z):
        idx = jnp.clip(jnp.asarray(t, jnp.float32).astype(jnp.int32), 0, n - 1)
        uparams = jax.tree.map(lambda a: a[idx], p)
        return unit_residual(uparams, cfg, z)

    def diffusion(p, t, z):
        return jnp.zeros((), z.dtype)   # σ = 0: deterministic stack

    bm = ZeroPath(x.dtype)
    return reversible_heun_solve_final(
        drift, diffusion, stacked_units, x, bm, 0.0, float(n), n, "diagonal")
