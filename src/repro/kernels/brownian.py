"""In-kernel Brownian generation (counter-based Threefry) as Pallas kernels.

Moving increment generation on-device removes the per-step host round-trip
the solver loop otherwise pays: a fixed-grid step's ``ΔW`` and an adaptive
attempt's bridge descent each become ONE kernel launch whose body runs the
bit-exact ``jax.random`` op sequence (:mod:`repro.kernels.prng`).

Three kernels:

* :func:`brownian_increment` — shaped normal draw under ``fold_in(key, n)``
  scaled by ``sqrt(dt)``; bitwise ``BrownianPath.increment(n, num_steps)``.
* :func:`brownian_value` — ``BrownianPath.value(t)``: the scalar descent
  (per-level stds, directions, midpoint keys) runs in XLA and rides in as
  SMEM operands; the kernel holds the full-shape work — one draw per level
  and the elementwise combine.  One launch per attempted adaptive step.
* :func:`rev_heun_phase1_gen` — Algorithm 1's first state update with the
  step's ``ΔW`` generated *inside the same kernel* (returns ``(ẑ_{n+1},
  ΔW)`` so phase 2 reuses the increment without re-deriving it).

Kernel contract
===============

* The kernel bodies call the :mod:`repro.kernels.ref` ``*_block`` oracles
  on loaded values — kernel and oracle are the SAME traced op sequence, so
  bitwise parity (tests/test_kernel_parity.py) holds by construction and
  the tests pin that the Pallas lowering/interpreter preserves it.
* Row grid: a draw of shape ``(..., cols)`` is laid out as ``(rows,
  cols)`` and tiled by :func:`repro.kernels.reversible_heun_step.row_block`.
  Each block derives its counters from its first global row — ``row0 +
  program_id · block`` — so the bits depend neither on the tiling nor on
  the data-parallel sharding: a shard passes its global row offset as
  ``row0`` (:mod:`repro.kernels.ops` does this under a mesh).
* Key folding (``fold_in(key, n)``, 20 scalar Threefry rounds) runs in XLA
  before the launch; the kernel receives the folded key and ``sqrt(dt)``
  as SMEM scalars.
* ``interpret=True`` runs the body under the Pallas interpreter as one
  block — the CPU validation path (DESIGN.md §5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import prng, ref
from .reversible_heun_step import as_2d, row_block

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _smem_row(*xs, dtype):
    """Scalars as one ``(1, n)`` SMEM row.  The leading unit dim keeps the
    block legal when ``vmap`` batches the operand (per-row keys in
    serving): the batched ``(B, 1, n)`` array gets block ``(·, 1, n)``."""
    return jnp.stack([jnp.asarray(x, dtype).reshape(()) for x in xs])[None]


class _Row:
    """``row[i]`` reads ``ref[0, i]`` — a ``(1, n)`` SMEM ref indexed like
    the 1-D arrays :func:`repro.kernels.ref.bridge_block` takes."""

    def __init__(self, ref_):
        self.ref = ref_

    def __getitem__(self, i):
        return self.ref[0, i]


def _row0(off_ref, br):
    return off_ref[0, 0] + pl.program_id(0) * br


def _increment_kernel(dtype, br, off_ref, k_ref, s_ref, o_ref):
    o_ref[...] = ref.increment_block(k_ref[0, 0], k_ref[0, 1], o_ref.shape,
                                     dtype, s_ref[0, 0], _row0(off_ref, br))


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "interpret"))
def brownian_increment(k1, k2, n, shape, dtype, dt, row0=0,
                       interpret: bool = False):
    """Step-``n`` grid increment, generated in-kernel.

    ``k1, k2``: raw uint32 key halves; ``n``: step counter; ``dt``: the
    grid spacing (scalar, may be traced); ``row0``: the global row of this
    draw's first row (non-zero only for a shard of a larger draw).
    """
    dtype = jnp.dtype(dtype)
    shape = tuple(shape)
    rows, cols = prng.as_rows(shape)
    br = row_block(rows, cols, dtype, interpret)
    f1, f2 = prng.fold_in(k1, k2, n)
    out = pl.pallas_call(
        functools.partial(_increment_kernel, dtype, br),
        grid=(pl.cdiv(rows, br),),
        in_specs=[_SMEM] * 3,
        out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        interpret=interpret,
    )(_smem_row(row0, dtype=jnp.int32), _smem_row(f1, f2, dtype=jnp.uint32),
      _smem_row(jnp.sqrt(jnp.asarray(dt, dtype)), dtype=dtype))
    return out.reshape(shape)


def _value_kernel(dtype, br, depth, off_ref, k_ref, f_ref, g_ref, o_ref):
    o_ref[...] = ref.bridge_block(_Row(k_ref), _Row(f_ref), _Row(g_ref),
                                  o_ref.shape, dtype, depth,
                                  _row0(off_ref, br))


@functools.partial(
    jax.jit, static_argnames=("t0", "t1", "shape", "dtype", "depth", "interpret"))
def brownian_value(k1, k2, t, t0, t1, shape, dtype, depth: int = 24, row0=0,
                   interpret: bool = False):
    """``W(t) − W(t0)``: the bridge descent's scalars in XLA, its draws and
    combine in one kernel."""
    dtype = jnp.dtype(dtype)
    shape = tuple(shape)
    rows, cols = prng.as_rows(shape)
    br = row_block(rows, cols, dtype, interpret)
    keys, floats, gos = ref.bridge_descent(k1, k2, t, t0, t1, dtype, depth)
    out = pl.pallas_call(
        functools.partial(_value_kernel, dtype, br, depth),
        grid=(pl.cdiv(rows, br),),
        in_specs=[_SMEM] * 4,
        out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        interpret=interpret,
    )(_smem_row(row0, dtype=jnp.int32), keys[None], floats[None], gos[None])
    return out.reshape(shape)


def _phase1_gen_kernel(dtype, br, sign, off_ref, k_ref, s_ref,
                       z_ref, zh_ref, mu_ref, sig_ref, zh1_ref, dw_ref):
    dw = ref.increment_block(k_ref[0, 0], k_ref[0, 1], dw_ref.shape, dtype,
                             s_ref[0, 0], _row0(off_ref, br))
    zh1_ref[...] = ref.rev_heun_phase1(z_ref[...], zh_ref[...], mu_ref[...],
                                       sig_ref[...], dw, s_ref[0, 1], sign)
    dw_ref[...] = dw


@functools.partial(jax.jit, static_argnames=("sign", "interpret"))
def rev_heun_phase1_gen(z, zh, mu, sigma, k1, k2, n, dt_grid, dt,
                        sign: float = 1.0, row0=0, interpret: bool = False):
    """Fused Algorithm-1 phase 1 + in-kernel ΔW generation.

    Returns ``(ẑ_{n+1}, ΔW_n)`` from one kernel launch: the increment is
    drawn inside the grid (``fold_in(key, n)`` Threefry, scaled by
    ``sqrt(dt_grid)``) and immediately consumed by the state update, so the
    solver's time loop never leaves the kernel between noise generation and
    state propagation.  ``dt_grid`` is the Brownian grid spacing (the
    ``sqrt``-scaling), ``dt`` the integration step — identical for the
    uniform fixed-step solvers that use this kernel.
    """
    dtype = z.dtype
    shape = tuple(z.shape)
    flat = [as_2d(a) for a in (z, zh, mu, sigma)]
    rows, cols = flat[0].shape
    br = row_block(rows, cols, dtype, interpret)
    f1, f2 = prng.fold_in(k1, k2, n)
    spec = pl.BlockSpec((br, cols), lambda i: (i, 0))
    out = jax.ShapeDtypeStruct((rows, cols), dtype)
    zh1, dw = pl.pallas_call(
        functools.partial(_phase1_gen_kernel, dtype, br, sign),
        grid=(pl.cdiv(rows, br),),
        in_specs=[_SMEM] * 3 + [spec] * 4,
        out_specs=(spec, spec),
        out_shape=(out, out),
        interpret=interpret,
    )(_smem_row(row0, dtype=jnp.int32), _smem_row(f1, f2, dtype=jnp.uint32),
      _smem_row(jnp.sqrt(jnp.asarray(dt_grid, dtype)), dt, dtype=dtype),
      *flat)
    return zh1.reshape(shape), dw.reshape(shape)
