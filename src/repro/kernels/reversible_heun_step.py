"""Fused reversible-Heun state updates (Algorithm 1/2) as Pallas TPU kernels.

The solver's per-step arithmetic is pure elementwise VPU work: without
fusion, XLA materialises each intermediate (2z, −ẑ, μΔt, σΔW, …) through
HBM.  One VMEM-resident kernel per phase turns ~6 HBM round-trips into one
read + one write per operand — the solver loop is memory-bound, so this is
the hot spot the paper's 1-NFE-per-step advantage exposes.

Phase 1 computes ẑ_{n+1} (before the vector-field evaluation); phase 2
computes z_{n+1} (after).  Both take a static ``sign``: ``+1.0`` is the
forward step (Algorithm 1) and ``-1.0`` the algebraic inverse (Algorithm 2,
used by the O(1)-memory backward reconstruction in
:mod:`repro.core.adjoint`), which negates the Δt and ΔW terms in-kernel so
no extra negated operand ever touches HBM.

The backward (cotangent) phases are the hand-derived transpose of one
Algorithm-1 step, factored around the single vector-field VJP exactly as
DESIGN.md §3 derives it: :func:`rev_heun_bwd_phase1` builds the seeds of
the field VJP, :func:`rev_heun_bwd_phase2` distributes its result onto the
step-``n`` state cotangents.  Their op order is chosen so every output is
BITWISE what ``jax.vjp`` of the unfused stepper produces — the fused exact
adjoint in :mod:`repro.core.adjoint` rests on that identity, and
tests/test_kernel_parity.py pins it.

Kernel contract
===============

* **Noise layout**: diagonal noise only — ``z, ẑ, μ, σ, ΔW`` all share the
  state shape.  General (matrix) noise needs an ``einsum`` per step and is
  served by the unfused path in :mod:`repro.core.solvers`.
* **Shapes/tiling**: operands are flattened to ``(rows, cols)`` with
  ``cols = shape[-1]`` (1-D states become ``(1, n)``).  A block spans all
  ``cols`` and a multiple of 8 (f32) / 16 (bf16) rows, over a
  ``pl.cdiv`` row grid whose edge block Pallas masks (:func:`row_block`),
  so every state shape compiles — including the Latent-SDE's
  ``hidden + 1`` width.  Narrow states still occupy full 128-lane vregs.
* **dt is a traced scalar operand**: ``dt`` rides in as a ``(1, 1)`` block
  broadcast to every grid cell, so one compiled kernel serves every step
  size — this is what lets the *adaptive* driver (traced, per-attempt
  ``dt``) use the fused path.  ``sign`` stays static (±1.0 is a branch of
  the algorithm, not data).
* **Interpret mode**: ``interpret=True`` runs the kernel body under the
  Pallas interpreter — how the tests validate the kernels without a TPU
  (tests/test_kernel_parity.py, tests/test_solve.py).  The default is the
  compiled kernel.  Off-TPU the solver hot loop runs the jnp oracle in
  :mod:`repro.kernels.ref` (the kernels/ops.py policy) and reaches the
  interpreter only when a caller asks for it explicitly.
* **Differentiability**: ``pallas_call`` still has no *automatic* VJP rule
  — but it no longer needs one: the backward phases above ARE the
  derivative, registered through the solver-level ``custom_vjp`` in
  :mod:`repro.core.adjoint`.  AD never traces through a kernel; the
  adjoint rules call the backward kernels directly.  ``jax.vmap``
  (batched multi-trajectory solving) IS supported.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _phase1_kernel(sign, z_ref, zh_ref, mu_ref, sig_ref, dw_ref, dt_ref, o_ref):
    dt = dt_ref[0, 0]
    o_ref[...] = (
        2.0 * z_ref[...]
        - zh_ref[...]
        + mu_ref[...] * (sign * dt)
        + (sign * sig_ref[...]) * dw_ref[...]
    )


def _phase2_kernel(sign, z_ref, mu_ref, mu1_ref, sig_ref, sig1_ref, dw_ref,
                   dt_ref, o_ref):
    dt = dt_ref[0, 0]
    o_ref[...] = (
        z_ref[...]
        + (sign * 0.5 * dt) * (mu_ref[...] + mu1_ref[...])
        + (sign * 0.5) * (sig_ref[...] + sig1_ref[...]) * dw_ref[...]
    )


def _bwd_phase1_kernel(gz1_ref, gmu1_ref, gsig1_ref, dw_ref, dt_ref,
                       cmu1_ref, csig1_ref):
    dt = dt_ref[0, 0]
    g_z1 = gz1_ref[...]
    cmu1_ref[...] = gmu1_ref[...] + 0.5 * (g_z1 * dt)
    csig1_ref[...] = gsig1_ref[...] + 0.5 * (g_z1 * dw_ref[...])


def _bwd_phase2_kernel(gz1_ref, ghat_ref, dw_ref, dt_ref,
                       dz_ref, dzh_ref, dmu_ref, dsig_ref):
    dt = dt_ref[0, 0]
    g_z1 = gz1_ref[...]
    ghat = ghat_ref[...]
    dw = dw_ref[...]
    dz_ref[...] = g_z1 + 2.0 * ghat
    dzh_ref[...] = -ghat
    dmu_ref[...] = 0.5 * (g_z1 * dt) + ghat * dt
    dsig_ref[...] = 0.5 * (g_z1 * dw) + ghat * dw


#: VMEM bytes one operand's row block may take (lane-padded), per buffer.
#: Blocks are double-buffered, and the backward phase 2 moves 7 operands:
#: 7 × 2 × 512 KiB = 7 MiB, inside the 16 MiB scoped-VMEM default.
_BLOCK_BYTES = 512 * 1024


def row_block(rows: int, cols: int, dtype, interpret: bool) -> int:
    """Rows per block of a ``(rows, cols)`` operand.

    The column block is always the whole last dim (Mosaic accepts a block
    dim equal to the array dim, so odd state widths like ``hidden + 1``
    compile).  The row block is a multiple of the dtype's sublane count
    (8 for 32-bit, 16 for 16-bit) sized to :data:`_BLOCK_BYTES`, or all
    rows when they fit in one block; callers grid over ``pl.cdiv(rows,
    block)`` and Pallas masks the edge block.  Interpret mode always runs
    one block: the interpreter's per-cell loop compiles each block as a
    separate XLA subcomputation whose FMA contraction can differ from the
    plain jnp oracle graph by ±1 ulp, and one block keeps the parity
    contract of tests/test_kernel_parity.py bitwise.
    """
    if interpret:
        return rows
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * 4 // itemsize
    lanes = -(-cols // 128) * 128
    cap = max(sublanes, _BLOCK_BYTES // (lanes * itemsize) // sublanes * sublanes)
    return rows if rows <= cap else cap


def as_2d(a):
    """The kernels' ``(rows, cols)`` view: ``cols`` is the last dim, a 1-D
    state is one row."""
    return a.reshape(-1, a.shape[-1]) if a.ndim > 1 else a.reshape(1, -1)


def _call_elementwise(kernel, args, scalars, interpret: bool, n_out: int = 1):
    """Row-tiled elementwise pallas_call: tensor ``args`` share one block
    grid (:func:`row_block`), ``scalars`` ride along as (1, 1) blocks
    mapped to every grid cell."""
    x = args[0]
    orig_shape = x.shape
    flat = [as_2d(a) for a in args]
    rows, cols = flat[0].shape
    br = row_block(rows, cols, x.dtype, interpret)
    spec = pl.BlockSpec((br, cols), lambda i: (i, 0))
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    svals = [jnp.asarray(s, x.dtype).reshape(1, 1) for s in scalars]
    shape = jax.ShapeDtypeStruct((rows, cols), x.dtype)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[spec] * len(flat) + [sspec] * len(svals),
        out_specs=spec if n_out == 1 else (spec,) * n_out,
        out_shape=shape if n_out == 1 else (shape,) * n_out,
        interpret=interpret,
    )(*flat, *svals)
    if n_out == 1:
        return out.reshape(orig_shape)
    return tuple(o.reshape(orig_shape) for o in out)


@functools.partial(jax.jit, static_argnames=("sign", "interpret"))
def rev_heun_phase1(z, zh, mu, sigma, dw, dt, sign: float = 1.0,
                    interpret: bool = False):
    """ẑ_{n+1} = 2z − ẑ + sign·(μΔt + σΔW) — fused, one HBM pass."""
    return _call_elementwise(
        functools.partial(_phase1_kernel, sign), (z, zh, mu, sigma, dw), (dt,),
        interpret)


@functools.partial(jax.jit, static_argnames=("sign", "interpret"))
def rev_heun_phase2(z, mu, mu1, sigma, sigma1, dw, dt, sign: float = 1.0,
                    interpret: bool = False):
    """z_{n+1} = z + sign·(½(μ+μ′)Δt + ½(σ+σ′)ΔW) — fused, one HBM pass."""
    return _call_elementwise(
        functools.partial(_phase2_kernel, sign), (z, mu, mu1, sigma, sigma1, dw),
        (dt,), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rev_heun_bwd_phase1(g_z1, g_mu1, g_sig1, dw, dt, interpret: bool = False):
    """Backward pre-field phase: ``(c_mu1, c_sig1)`` seeds for the single
    vector-field VJP — ``c_mu1 = ḡ_mu1 + ½Δt·ḡ_z1``,
    ``c_sig1 = ḡ_sig1 + ½ΔW·ḡ_z1``."""
    return _call_elementwise(
        _bwd_phase1_kernel, (g_z1, g_mu1, g_sig1, dw), (dt,), interpret,
        n_out=2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rev_heun_bwd_phase2(g_z1, ghat, dw, dt, interpret: bool = False):
    """Backward post-field phase: distribute the total ẑ₁ cotangent ``ĝ``
    onto the step-``n`` state — ``(d_z, d_zh, d_mu, d_sigma)``."""
    return _call_elementwise(
        _bwd_phase2_kernel, (g_z1, ghat, dw), (dt,), interpret, n_out=4)
