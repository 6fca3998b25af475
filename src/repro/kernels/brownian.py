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
  :func:`brownian_increment` lays a narrow draw (``cols`` under 128) out
  lane-dense instead, where that takes fewer ``(sublanes, 128)`` vector
  tiles: its C-order elements 128 to a row, the tail of the last row cut
  off outside the kernel, so a ``(1024, 3)`` draw fills every lane
  instead of 3 of 128.  Each block derives its counters from the flat
  index of its first element — the draw's offset plus ``program_id``
  blocks — so the bits depend neither on the layout, nor on the tiling,
  nor on the data-parallel sharding: a shard passes its global row
  offset as ``row0`` (:mod:`repro.kernels.ops` does this under a mesh).
* Key folding (``fold_in(key, n)``, 20 scalar Threefry rounds) runs in XLA
  before the launch; the kernel receives the folded key and ``sqrt(dt)``
  as SMEM scalars.
* ``interpret=True`` runs the body under the Pallas interpreter as one
  block — the CPU validation path (DESIGN.md §5).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import prng, ref
from .reversible_heun_step import as_2d, row_block

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
#: The vector unit's lane count: the row width of a lane-dense draw.
_LANES = 128


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


def _offset(row0, cols):
    """The SMEM operand holding the flat index of a draw's first element:
    global row ``row0`` of a draw ``cols`` wide."""
    return _smem_row(jnp.asarray(row0, jnp.int32) * cols, dtype=jnp.int32)


def _start(off_ref, block_size):
    """Flat index of this grid cell's first element, for blocks of
    ``block_size`` elements."""
    return off_ref[0, 0] + pl.program_id(0) * block_size


def _lane_dense(rows: int, cols: int, dtype) -> bool:
    """Whether a ``(rows, cols)`` draw takes fewer vector tiles laid out
    128 elements to a row than in its own rows: a narrow draw of more rows
    than one tile holds.  A tile is 8 rows of 32-bit values (16 of
    16-bit ones) by 128 lanes."""
    sublanes = 8 * 4 // dtype.itemsize
    dense_rows = pl.cdiv(rows * cols, _LANES)
    return (cols < _LANES
            and pl.cdiv(dense_rows, sublanes) < pl.cdiv(rows, sublanes))


def _increment_kernel(dtype, off_ref, k_ref, s_ref, o_ref):
    o_ref[...] = ref.increment_block(k_ref[0, 0], k_ref[0, 1], o_ref.shape,
                                     dtype, s_ref[0, 0],
                                     _start(off_ref, math.prod(o_ref.shape)))


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "interpret"))
def brownian_increment(k1, k2, n, shape, dtype, dt, row0=0,
                       interpret: bool = False):
    """Step-``n`` grid increment, generated in-kernel.

    ``k1, k2``: raw uint32 key halves; ``n``: step counter; ``dt``: the
    grid spacing (scalar, may be traced); ``row0``: the global row of this
    draw's first row (non-zero only for a shard of a larger draw).  A
    narrow draw is generated lane-dense (module docstring).
    """
    dtype = jnp.dtype(dtype)
    shape = tuple(shape)
    rows, cols = prng.as_rows(shape)
    offset = _offset(row0, cols)
    size = rows * cols
    if _lane_dense(rows, cols, dtype):
        rows, cols = pl.cdiv(size, _LANES), _LANES
    br = row_block(rows, cols, dtype, interpret)
    f1, f2 = prng.fold_in(k1, k2, n)
    out = pl.pallas_call(
        functools.partial(_increment_kernel, dtype),
        grid=(pl.cdiv(rows, br),),
        in_specs=[_SMEM] * 3,
        out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        interpret=interpret,
    )(offset, _smem_row(f1, f2, dtype=jnp.uint32),
      _smem_row(jnp.sqrt(jnp.asarray(dt, dtype)), dtype=dtype))
    return out.reshape(-1)[:size].reshape(shape)


def _value_kernel(dtype, depth, off_ref, k_ref, f_ref, g_ref, o_ref):
    o_ref[...] = ref.bridge_block(_Row(k_ref), _Row(f_ref), _Row(g_ref),
                                  o_ref.shape, dtype, depth,
                                  _start(off_ref, math.prod(o_ref.shape)))


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
        functools.partial(_value_kernel, dtype, depth),
        grid=(pl.cdiv(rows, br),),
        in_specs=[_SMEM] * 4,
        out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        interpret=interpret,
    )(_offset(row0, cols), keys[None], floats[None], gos[None])
    return out.reshape(shape)


def _phase1_gen_kernel(dtype, sign, off_ref, k_ref, s_ref,
                       z_ref, zh_ref, mu_ref, sig_ref, zh1_ref, dw_ref):
    dw = ref.increment_block(k_ref[0, 0], k_ref[0, 1], dw_ref.shape, dtype,
                             s_ref[0, 0], _start(off_ref, math.prod(dw_ref.shape)))
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
        functools.partial(_phase1_gen_kernel, dtype, sign),
        grid=(pl.cdiv(rows, br),),
        in_specs=[_SMEM] * 3 + [spec] * 4,
        out_specs=(spec, spec),
        out_shape=(out, out),
        interpret=interpret,
    )(_offset(row0, cols), _smem_row(f1, f2, dtype=jnp.uint32),
      _smem_row(jnp.sqrt(jnp.asarray(dt_grid, dtype)), dt, dtype=dtype),
      *flat)
    return zh1.reshape(shape), dw.reshape(shape)
