"""Counter-based PRNG primitives, bit-exact vs ``jax.random`` (Threefry-2x32).

The Brownian kernels (:mod:`repro.kernels.brownian`) generate increments
*inside* the Pallas grid, so the solver's time loop no longer round-trips
to a host-side ``jax.random`` call per step.  For that to be legal the
in-kernel draws must be **bitwise identical** to what
:class:`repro.core.brownian.BrownianPath` produces via ``jax.random`` —
the forward/backward replay contract (DESIGN.md §10) is bitwise, so even
1-ulp drift in the noise would break gradient exactness.

This module transcribes the op sequence of JAX's Threefry path
(``jax._src.prng``) under the installed default
``jax_threefry_partitionable=True``, written only with primitives that are
legal inside a Pallas TPU kernel body (elementwise ``lax`` ops, 2-D
``broadcasted_iota``, bitcasts — no ``jax.random``, no key pytrees, no
1-D iota, concatenate or reshape):

* :func:`threefry2x32` — the 20-round hash (5 × 4 rounds, rotation
  schedule ``(13,15,26,6)/(17,29,16,24)``, key schedule
  ``k0, k1, k0^k1^0x1BD11BDA`` with round-index injections);
* :func:`fold_in` — ``threefry2x32(key, seed_pair(n))``, matching
  ``jax.random.fold_in``'s counter scheme;
* :func:`counters` — each element's counter is the ``(hi, lo)`` word pair
  of its C-order flat index (``iota_2x32_shape``).  A draw of any shape is
  laid out as ``(rows, cols)`` with ``cols = shape[-1]``; a block of rows
  starting at ``row0`` derives its counters from ``row0`` alone, so the
  bits do not depend on how the rows are tiled or sharded;
* :func:`bits` — 32-bit draws are ``y1 ^ y2``, 64-bit draws
  ``y1 << 32 | y2``, of the two hash lanes;
* :func:`normal_block` / :func:`normal_like` — the mantissa-shift bitcast
  and ``sqrt(2)·erf_inv`` transform of ``jax.random.normal``, op for op.

tests/test_kernel_parity.py pins every function here bitwise against its
``jax.random`` counterpart across dtypes and shapes.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
_PARITY = np.uint32(0x1BD11BDA)


def _rotl(x, d: int):
    d = np.uint32(d)
    return lax.shift_left(x, d) | lax.shift_right_logical(x, np.uint32(32 - d))


def _round4(x0, x1, rots):
    for r in rots:
        x0 = x0 + x1
        x1 = _rotl(x1, r)
        x1 = x0 ^ x1
    return x0, x1


def threefry2x32(k1, k2, x1, x2) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The Threefry-2x32 hash; all args uint32, broadcastable.

    Bitwise identical to ``jax._src.prng.threefry2x32_p`` (both the rolled
    and unrolled XLA lowerings compute this same sequence).
    """
    k1 = jnp.asarray(k1, jnp.uint32)
    k2 = jnp.asarray(k2, jnp.uint32)
    x1 = jnp.asarray(x1, jnp.uint32)
    x2 = jnp.asarray(x2, jnp.uint32)
    ks = (k1, k2, k1 ^ k2 ^ _PARITY)
    x1 = x1 + ks[0]
    x2 = x2 + ks[1]
    # 5 groups of 4 rounds; after group i (1-based) inject (ks[i], ks[i+1] + i)
    schedule = ((_ROT_A, 1, 2), (_ROT_B, 2, 0), (_ROT_A, 0, 1),
                (_ROT_B, 1, 2), (_ROT_A, 2, 0))
    for i, (rots, ka, kb) in enumerate(schedule):
        x1, x2 = _round4(x1, x2, rots)
        x1 = x1 + ks[ka]
        x2 = x2 + ks[kb] + np.uint32(i + 1)
    return x1, x2


def seed_pair(data) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(hi, lo)`` uint32 pair for an integer counter — ``threefry_seed``."""
    data = jnp.asarray(data)
    if data.dtype.itemsize <= 4:
        hi = jnp.zeros((), jnp.uint32)
        lo = lax.convert_element_type(data, jnp.uint32)
    else:
        hi = lax.convert_element_type(
            lax.shift_right_logical(data, np.int64(32)), jnp.uint32)
        lo = lax.convert_element_type(
            jnp.bitwise_and(data, np.uint32(0xFFFFFFFF)), jnp.uint32)
    return hi, lo


def fold_in(k1, k2, data) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """New raw key pair — bitwise ``jax.random.fold_in(key, data)``."""
    hi, lo = seed_pair(data)
    return threefry2x32(k1, k2, hi, lo)


def as_rows(shape) -> Tuple[int, int]:
    """The ``(rows, cols)`` layout of a draw: ``cols`` is the last dim, a
    1-D draw is one row, a scalar one element.  Raises for draws whose flat
    index needs the high counter word (more than 2**32 elements)."""
    shape = tuple(shape)
    cols = shape[-1] if shape else 1
    rows = math.prod(shape[:-1]) if shape else 1
    if rows * cols >= 2 ** 32:
        raise ValueError(f"draw of shape {shape} exceeds 2**32 elements")
    return rows, cols


def counters(block_shape, row0=0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(hi, lo)`` counters of a ``(rows, cols)`` block whose first row is
    global row ``row0`` (an int32 scalar, possibly traced): the flat index
    ``(row0 + r) * cols + c`` — JAX's ``iota_2x32_shape`` restricted to the
    block.  ``hi`` is zero because :func:`as_rows` bounds the draw size."""
    cols = block_shape[-1]
    r = lax.broadcasted_iota(jnp.int32, block_shape, 0) + row0
    c = lax.broadcasted_iota(jnp.int32, block_shape, 1)
    lo = (lax.convert_element_type(r, jnp.uint32) * np.uint32(cols)
          + lax.convert_element_type(c, jnp.uint32))
    return jnp.zeros(block_shape, jnp.uint32), lo


def bits(k1, k2, hi, lo, bit_width: int) -> jnp.ndarray:
    """uint{32,64} draws at the given counters — the partitionable
    ``_threefry_random_bits``: ``y1 ^ y2`` for 32 bits, ``y1 << 32 | y2``
    for 64."""
    y1, y2 = threefry2x32(k1, k2, hi, lo)
    if bit_width == 32:
        return y1 ^ y2
    if bit_width == 64:
        return (lax.shift_left(lax.convert_element_type(y1, jnp.uint64),
                               np.uint64(32))
                | lax.convert_element_type(y2, jnp.uint64))
    raise ValueError(f"bit_width must be 32 or 64, got {bit_width}")


def normal_from_bits(b, dtype) -> jnp.ndarray:
    """Standard normals from random bits — ``jax.random.normal``'s
    transform: uniforms on ``[nextafter(-1, 0), 1)`` by the mantissa-shift
    bitcast, then ``sqrt(2)·erf_inv``."""
    dtype = jnp.dtype(dtype)
    finfo = jnp.finfo(dtype)
    nbits, nmant = finfo.bits, finfo.nmant
    uint_dtype = jnp.uint32 if nbits == 32 else jnp.uint64
    float_bits = lax.bitwise_or(
        lax.shift_right_logical(b, np.array(nbits - nmant, uint_dtype)),
        np.array(1.0, dtype).view(uint_dtype))
    floats = lax.bitcast_convert_type(float_bits, dtype) - np.array(1.0, dtype)
    minval = np.nextafter(np.array(-1.0, dtype), np.array(0.0, dtype),
                          dtype=dtype)
    maxval = np.array(1.0, dtype)
    u = lax.max(jnp.full(b.shape, minval, dtype),
                floats * (maxval - minval) + minval)
    return lax.mul(np.array(np.sqrt(2), dtype), lax.erf_inv(u))


def normal_block(k1, k2, block_shape, dtype, row0=0) -> jnp.ndarray:
    """Rows ``row0 .. row0 + block_shape[0]`` of a ``(rows, cols)`` normal
    draw — what a kernel's grid cell computes for its block."""
    dtype = jax.dtypes.canonicalize_dtype(dtype)  # as jax.random does
    hi, lo = counters(block_shape, row0)
    return normal_from_bits(bits(k1, k2, hi, lo, dtype.itemsize * 8), dtype)


def normal_like(k1, k2, shape: Tuple[int, ...], dtype) -> jnp.ndarray:
    """Shaped standard normals — bitwise ``jax.random.normal(key, shape)``."""
    return normal_block(k1, k2, as_rows(shape), dtype).reshape(tuple(shape))


def key_data_pair(key) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split a JAX PRNG key (typed or raw ``(2,) uint32``) into scalars."""
    if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    key = jnp.asarray(key)
    return key[..., 0], key[..., 1]
