"""Optimisers as (init, update) pairs over parameter pytrees.

Paper Appendix F: Adam [81] for Latent SDEs, Adadelta [82] for SDE-GANs,
stochastic weight averaging (Cesàro mean over the last 50% of steps) [83, 84]
for GAN generators.  AdamW + cosine schedule serve the LM training path.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def _zeros_like_tree(t):
    return jax.tree.map(jnp.zeros_like, t)


class OptState(NamedTuple):
    step: jax.Array
    m: object
    v: object


def adam(lr, b1=0.9, b2=0.999, eps=1e-8, moment_dtype=None):
    """``moment_dtype`` ("bfloat16" halves optimizer HBM at 100B+ scale; see
    EXPERIMENTS.md §Perf) defaults to the parameter dtype."""

    def _moments(params):
        if moment_dtype is None:
            return _zeros_like_tree(params)
        dt = jnp.dtype(moment_dtype)
        return jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params)

    def init(params):
        return OptState(jnp.zeros((), jnp.int32), _moments(params), _moments(params))

    def update(grads, state, params=None):
        step = state.step + 1
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(m_.dtype),
                         state.m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * (g * g).astype(v_.dtype),
                         state.v, grads)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        lr_t = lr(step) if callable(lr) else lr
        upd = jax.tree.map(
            lambda m_, v_, g: (-lr_t * (m_.astype(jnp.float32) / bc1)
                               / (jnp.sqrt(v_.astype(jnp.float32) / bc2) + eps)
                               ).astype(g.dtype),
            m, v, grads)
        return upd, OptState(step, m, v)

    return init, update


def adamw(lr, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, moment_dtype=None):
    ai, au = adam(lr, b1, b2, eps, moment_dtype=moment_dtype)

    def update(grads, state, params):
        upd, state = au(grads, state, params)
        lr_t = lr(state.step) if callable(lr) else lr
        upd = jax.tree.map(lambda u, p: u - lr_t * weight_decay * p, upd, params)
        return upd, state

    return ai, update


def adadelta(lr=1.0, rho=0.9, eps=1e-6):
    """Adadelta [82] — the paper's SDE-GAN optimiser."""

    def init(params):
        return OptState(jnp.zeros((), jnp.int32), _zeros_like_tree(params), _zeros_like_tree(params))

    def update(grads, state, params=None):
        acc_g = jax.tree.map(lambda a, g: rho * a + (1 - rho) * g * g, state.m, grads)
        upd = jax.tree.map(
            lambda g, ag, ad: -lr * g * jnp.sqrt(ad + eps) / jnp.sqrt(ag + eps),
            grads, acc_g, state.v)
        acc_d = jax.tree.map(lambda a, u: rho * a + (1 - rho) * u * u, state.v, upd)
        return upd, OptState(state.step + 1, acc_g, acc_d)

    return init, update


# -----------------------------------------------------------------------------
# flat optimiser state (optax.flatten)
# -----------------------------------------------------------------------------


class _Layout(NamedTuple):
    """How a flat vector splits back into a pytree: hashable, so it rides
    as a pytree's static aux data."""
    treedef: Any
    shapes: tuple
    dtypes: tuple


def _ravel(tree):
    """``tree``'s leaves raveled and concatenated in leaf order, and its
    :class:`_Layout`."""
    leaves, treedef = jax.tree.flatten(tree)
    layout = _Layout(treedef, tuple(jnp.shape(x) for x in leaves),
                     tuple(jnp.result_type(x) for x in leaves))
    return jnp.concatenate([jnp.ravel(x) for x in leaves]), layout


def _unravel(vec, layout: _Layout):
    cuts = np.cumsum([math.prod(s) for s in layout.shapes])[:-1]
    return jax.tree.unflatten(layout.treedef, [
        p.reshape(s).astype(d)
        for p, s, d in zip(jnp.split(vec, cuts), layout.shapes,
                           layout.dtypes)])


@jax.tree_util.register_pytree_with_keys_class
class FlatOptState:
    """An :class:`OptState` whose moments are each one raveled vector.

    Three device arrays however many parameter leaves there are, so a
    jitted step takes and returns three buffers for the optimiser state
    instead of ``1 + 2 × leaves``.  ``m`` and ``v`` read back as
    parameter-shaped trees; the pytree's leaves are ``step``, ``m_flat``
    and ``v_flat``, keyed by those names (checkpoint leaf paths).
    """

    def __init__(self, step, m_flat, v_flat, layout: _Layout):
        self.step, self.m_flat, self.v_flat = step, m_flat, v_flat
        self.layout = layout

    @property
    def m(self):
        return _unravel(self.m_flat, self.layout)

    @property
    def v(self):
        return _unravel(self.v_flat, self.layout)

    def tree_flatten_with_keys(self):
        key = jax.tree_util.GetAttrKey
        return (((key("step"), self.step), (key("m_flat"), self.m_flat),
                 (key("v_flat"), self.v_flat)), self.layout)

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(*children, layout)


def flatten(transform):
    """``transform`` (an :class:`OptState` optimiser) run on the raveled
    parameter vector: the ``optax.flatten`` idea.

    The arithmetic is the inner optimiser's, elementwise on one vector
    instead of per leaf, so updates and moments are bitwise the per-leaf
    ones; the update is unraveled to the parameters' tree, so per-leaf
    transforms chained after it (:func:`lipschitz_projection`) see leaves
    as before.  The state is a :class:`FlatOptState`.
    """
    inner_init, inner_update = transform

    def init(params):
        flat, layout = _ravel(params)
        s = inner_init(flat)
        return FlatOptState(s.step, s.m, s.v, layout)

    def update(grads, state, params=None):
        g, layout = _ravel(grads)
        p = None if params is None else _ravel(params)[0]
        upd, s = inner_update(g, OptState(state.step, state.m_flat,
                                          state.v_flat), p)
        return _unravel(upd, layout), FlatOptState(s.step, s.m, s.v,
                                                   state.layout)

    return init, update


def apply_updates(params, updates):
    return jax.tree.map(jnp.add, params, updates)


# -----------------------------------------------------------------------------
# optax-style composition
# -----------------------------------------------------------------------------
#
# Every optimiser here is an ``(init, update)`` pair with
# ``update(updates, state, params) -> (updates, state)`` — the optax
# GradientTransformation protocol minus the NamedTuple wrapper.  ``chain``
# composes them left-to-right, so real optax transforms interoperate:
# ``chain(optax.clip(1.0), adadelta(1.0), lipschitz_projection())`` is legal
# (optax's extra-args update signature matches).


def chain(*transforms):
    """Compose ``(init, update)`` transforms; states are carried as a tuple."""
    inits, updates = zip(*transforms)

    def init(params):
        return tuple(i(params) for i in inits)

    def update(grads, state, params=None):
        new_state = []
        for u, s in zip(updates, state):
            grads, s = u(grads, s, params)
            new_state.append(s)
        return grads, tuple(new_state)

    return init, update


def lipschitz_projection(clip_fn=None):
    """Careful clipping (paper §5) as a pytree transform in the update chain.

    The paper applies the clip to the *parameters after* the optimiser
    update.  Expressed on updates — so it composes with any optax-style
    chain — that is ``upd ← clip(params + upd) − params``: applying the
    returned update lands exactly on the projected parameters, with no
    second backward pass anywhere (DESIGN.md §4).

    Place it *last* in the chain (it must see the final update).  Stateless.
    ``clip_fn`` defaults to the structural :func:`repro.core.clipping.clip_pytree`;
    pass e.g. ``clip_lipschitz`` to restrict to named discriminator MLPs.
    """
    from ..core.clipping import clip_pytree

    project = clip_fn if clip_fn is not None else clip_pytree

    def init(params):
        return ()

    def update(upd, state, params):
        if params is None:
            raise ValueError("lipschitz_projection needs params: the clip is "
                             "a projection of params + update, not of the "
                             "update alone")
        stepped = apply_updates(params, upd)
        clipped = project(stepped)
        new_upd = jax.tree.map(jnp.subtract, clipped, params)
        return new_upd, state

    return init, update


def global_norm(tree):
    """L2 norm of every leaf of ``tree`` together, in float32."""
    return jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-9))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), gnorm


def cosine_schedule(peak_lr: float, warmup: int, total: int, floor: float = 0.1):
    def lr(step):
        step = step.astype(jnp.float32) if hasattr(step, "astype") else jnp.float32(step)
        warm = peak_lr * step / max(warmup, 1)
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = peak_lr * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
        return jnp.where(step < warmup, warm, cos)

    return lr


def swa_update(avg_params, params, num_avged):
    """Cesàro/Polyak averaging (paper: mean over latter 50% of GAN steps)."""
    w = 1.0 / (num_avged + 1)
    return jax.tree.map(lambda a, p: a + w * (p - a), avg_params, params)
