"""Dispatching wrappers: Pallas kernel on TPU, jnp reference elsewhere.

Policy: on a TPU backend the compiled kernels run natively; on CPU/GPU the
pure-jnp oracle runs (fast + lets XLA fuse).  ``use_kernel=True`` forces the
Pallas path with ``interpret=True`` off-TPU — this is what the kernel tests
exercise.  On a TPU the interpreter is never used.  Setting
``REPRO_FORCE_PALLAS_INTERPRET=1`` in the environment flips the default
(``use_kernel=None``) to the forced path too — CI's kernel-parity job uses
it to sweep the whole differential suite through the Pallas interpreter
without touching call sites.  The dry-run/roofline path uses the reference
implementations so `cost_analysis()` reflects the XLA graph (see
DESIGN.md §5).

Under a mesh (``jax.set_mesh``) whose ``data`` axis is automatic, the
reversible-Heun and Brownian kernels run inside ``jax.shard_map`` over that
axis: Mosaic kernels cannot be partitioned by GSPMD.  Tensors whose leading
dim divides the axis are split on it, and each Brownian shard draws its own
rows at their global row offset, so the draws do not depend on the device
count; anything else runs replicated.  Per-row solves vmapped with
:func:`vmap_rows` split their rows over the axis the same way.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import brownian as _bk
from . import flash_attention as _fa
from . import fused_mlp as _fm
from . import prng
from . import ref
from . import reversible_heun_step as _rh
from . import ssd_chunk as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _decide(use_kernel: Optional[bool]):
    """-> (run_kernel, interpret)."""
    if use_kernel is None:
        use_kernel = (_on_tpu()
                      or bool(os.environ.get("REPRO_FORCE_PALLAS_INTERPRET")))
    return use_kernel, not _on_tpu()


def _data_axis_size() -> int:
    """Size of the ambient mesh's automatic ``data`` axis; 0 without one
    (no mesh, or already inside a ``shard_map`` over it)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "data" not in mesh.axis_names or "data" in mesh.manual_axes:
        return 0
    return mesh.shape["data"]


def _per_shard(f, shape, tensors, scalars):
    """``f(row0, local_shape, *tensors, *scalars)``, once per shard of the
    data axis.

    ``shape`` is the kernel's tensor shape (all ``tensors`` and outputs
    share it); ``local_shape`` is one shard's part of it and ``row0`` the
    global row, in the kernels' ``(rows, cols)`` view, of the shard's first
    row.  Without a data axis this is one call with ``row0 = 0``.
    """
    shape = tuple(shape)
    n = _data_axis_size()
    scalars = tuple(jnp.asarray(s) for s in scalars)
    if not n:
        return f(0, shape, *tensors, *scalars)
    split = len(shape) >= 2 and shape[0] % n == 0
    spec = P("data") if split else P()
    local = (shape[0] // n,) + shape[1:] if split else shape

    def body(*args):
        row0 = (jax.lax.axis_index("data") * math.prod(local[:-1])
                if split else 0)
        return f(row0, local, *args)

    return jax.shard_map(
        body, in_specs=(spec,) * len(tensors) + (P(),) * len(scalars),
        out_specs=spec, check_vma=False)(*tensors, *scalars)


def vmap_rows(f, rows: int, **vmap_kw):
    """``jax.vmap(f, **vmap_kw)`` over ``rows`` independent rows, each a
    1-D per-row state (the serving samplers' form).

    Inside a vmap the kernels see one row's shape, which has no batch dim
    to split, so :func:`_per_shard` would run them replicated: every
    device drawing every row, with the batch's keys gathered onto each.
    Under a mesh whose automatic ``data`` axis divides ``rows``, the mapped
    dim is put on that axis instead (``spmd_axis_name``), so each device
    runs the kernels on its own rows only.  A row's draws depend on its own
    key alone, so they do not depend on the device count.
    """
    n = _data_axis_size()
    spmd = "data" if n and rows % n == 0 else None
    return jax.vmap(f, spmd_axis_name=spmd, **vmap_kw)


def flash_attention(q, k, v, causal=True, scale=None, block_q=128, block_k=128,
                    use_kernel: Optional[bool] = None):
    run, interp = _decide(use_kernel)
    if run:
        return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k, interpret=interp)
    return ref.flash_attention(q, k, v, causal=causal, scale=scale)


def fused_mlp(x, w1, b1, w2, b2, use_kernel: Optional[bool] = None):
    run, interp = _decide(use_kernel)
    if run:
        return _fm.fused_mlp(x, w1, b1, w2, b2, interpret=interp)
    return ref.fused_mlp(x, w1, b1, w2, b2)


def ssd_chunk(x, a, b, c, chunk=64, use_kernel: Optional[bool] = None):
    run, interp = _decide(use_kernel)
    if run:
        return _ssd.ssd_chunk(x, a, b, c, chunk=chunk, interpret=interp)
    return ref.ssd_scan(x, a, b, c)


def _elementwise(kernel, tensors, dt, interpret: bool):
    """An elementwise reversible-Heun kernel on each data shard."""
    return _per_shard(lambda _r, _s, *a: kernel(*a, interpret=interpret),
                      tensors[0].shape, tensors, (dt,))


def rev_heun_phase1(z, zh, mu, sigma, dw, dt, sign: float = 1.0,
                    use_kernel: Optional[bool] = None):
    run, interp = _decide(use_kernel)
    if run:
        return _elementwise(functools.partial(_rh.rev_heun_phase1, sign=sign),
                            (z, zh, mu, sigma, dw), dt, interp)
    return ref.rev_heun_phase1(z, zh, mu, sigma, dw, dt, sign)


def rev_heun_phase2(z, mu, mu1, sigma, sigma1, dw, dt, sign: float = 1.0,
                    use_kernel: Optional[bool] = None):
    run, interp = _decide(use_kernel)
    if run:
        return _elementwise(functools.partial(_rh.rev_heun_phase2, sign=sign),
                            (z, mu, mu1, sigma, sigma1, dw), dt, interp)
    return ref.rev_heun_phase2(z, mu, mu1, sigma, sigma1, dw, dt, sign)


def rev_heun_bwd_phase1(g_z1, g_mu1, g_sig1, dw, dt,
                        use_kernel: Optional[bool] = None):
    """Backward pre-field cotangents ``(c_mu1, c_sig1)`` — fused adjoint."""
    run, interp = _decide(use_kernel)
    if run:
        return _elementwise(_rh.rev_heun_bwd_phase1, (g_z1, g_mu1, g_sig1, dw),
                            dt, interp)
    return ref.rev_heun_bwd_phase1(g_z1, g_mu1, g_sig1, dw, dt)


def rev_heun_bwd_phase2(g_z1, ghat, dw, dt, use_kernel: Optional[bool] = None):
    """Backward post-field cotangents ``(d_z, d_zh, d_mu, d_sigma)``."""
    run, interp = _decide(use_kernel)
    if run:
        return _elementwise(_rh.rev_heun_bwd_phase2, (g_z1, ghat, dw), dt,
                            interp)
    return ref.rev_heun_bwd_phase2(g_z1, ghat, dw, dt)


def rev_heun_phase1_gen(z, zh, mu, sigma, key, n, dt_grid, dt, sign=1.0,
                        use_kernel: Optional[bool] = None):
    """Phase 1 with in-kernel ΔW generation — ``(ẑ_{n+1}, ΔW_n)``."""
    run, interp = _decide(use_kernel)
    k1, k2 = prng.key_data_pair(key)
    if run:
        return _per_shard(
            lambda row0, _s, *a: _bk.rev_heun_phase1_gen(
                *a, sign=sign, row0=row0, interpret=interp),
            z.shape, (z, zh, mu, sigma), (k1, k2, n, dt_grid, dt))
    dw = ref.brownian_increment(k1, k2, n, z.shape, z.dtype, dt_grid)
    return ref.rev_heun_phase1(z, zh, mu, sigma, dw, dt, sign), dw


def brownian_increment(key, n, shape, dtype, dt,
                       use_kernel: Optional[bool] = None):
    """Step-``n`` uniform-grid increment, counter-keyed on ``n``."""
    run, interp = _decide(use_kernel)
    k1, k2 = prng.key_data_pair(key)
    shape = tuple(shape)
    if run:
        return _per_shard(
            lambda row0, local, k1, k2, n, dt: _bk.brownian_increment(
                k1, k2, n, local, dtype, dt, row0=row0, interpret=interp),
            shape, (), (k1, k2, n, dt))
    return ref.brownian_increment(k1, k2, n, shape, dtype, dt)


def brownian_value(key, t, t0, t1, shape, dtype, depth: int = 24,
                   use_kernel: Optional[bool] = None):
    """``W(t) − W(t0)`` via single-kernel Lévy-bridge descent."""
    run, interp = _decide(use_kernel)
    k1, k2 = prng.key_data_pair(key)
    shape = tuple(shape)
    if run:
        return _per_shard(
            lambda row0, local, k1, k2, t: _bk.brownian_value(
                k1, k2, t, float(t0), float(t1), local, dtype, depth=depth,
                row0=row0, interpret=interp),
            shape, (), (k1, k2, jnp.asarray(t, dtype)))
    return ref.brownian_value(k1, k2, t, t0, t1, shape, dtype, depth=depth)


def fused_xent(logits, labels, use_kernel: Optional[bool] = None):
    from . import xent as _xent

    run, interp = _decide(use_kernel)
    if run:
        return _xent.fused_xent(logits, labels, interpret=interp)
    return ref.fused_xent(logits, labels)


# =============================================================================
# Precision-policy casts (the solve stack's bf16-compute / f32-state policy)
# =============================================================================
#
# These live in the dispatch layer because the compute dtype is a dispatch
# decision of the same kind as kernel-vs-oracle: the canonical cast the
# whole solve stack shares (repro.core.gradients.resolve_precision builds
# on it), so a future low-precision kernel path changes one place.


def cast_to_compute(tree, compute_dtype):
    """Cast every inexact-float leaf of ``tree`` to ``compute_dtype``.

    Integer leaves (PRNG keys, counters) pass through untouched.
    """
    import jax.numpy as jnp

    def cast(x):
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.inexact):
            return x.astype(compute_dtype)
        return x

    return jax.tree.map(cast, tree)


def wrap_vector_field(field, compute_dtype):
    """``(params, t, z) -> f`` evaluated in ``compute_dtype``, output cast
    back to the state dtype.

    The casts are linear, so under AD the parameter/state cotangents are
    up-cast on the way out — gradient *accumulation* (adjoint sums, scan
    carries, optimiser updates) stays in the state dtype; only the field
    arithmetic itself runs low-precision.  ``t`` is left in its own dtype:
    time resolution must not degrade with the compute policy.
    """
    import jax.numpy as jnp

    def wrapped(params, t, z):
        z = jnp.asarray(z)
        out = field(cast_to_compute(params, compute_dtype), t,
                    z.astype(compute_dtype))
        return jnp.asarray(out).astype(z.dtype)

    return wrapped
