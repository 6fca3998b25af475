"""GSPMD sharding rules: 2D "FSDP × TP" with an optional pod axis.

Axis roles on the production mesh (see launch/mesh.py):

* ``pod``   — outermost data/FSDP axis across pods (DCN-connected).
* ``data``  — intra-pod data/FSDP axis.
* ``model`` — tensor/expert-parallel axis (ICI-connected).

Weights carry ``P(fsdp, 'model')`` on (in, out)-style matrices with the TP
axis on the head/ffn/vocab dimension (Megatron layout); the other dimension
is FSDP-sharded over (pod, data) so optimizer state and parameters scale
with the full device count.  Activations are batch-sharded over (pod, data).

Everything is *rule-driven off parameter names*, so new modules compose by
following the naming convention rather than hand-annotating every tensor.
All helpers degrade to no-ops when no mesh is active — CPU smoke tests and
the Neural-SDE path run unsharded through identical code.
"""

from __future__ import annotations

import os
import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def ambient_mesh():
    """The mesh activated by ``jax.set_mesh`` (abstract under tracing), or
    ``None`` when unsharded."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with Auto axes: the sharding constraints in this
    module steer GSPMD, which Explicit axes (the JAX default) would not
    take."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names))


def force_host_device_count(n: int) -> None:
    """Simulate ``n`` CPU devices (the ``--host-devices`` flag of
    launch/train.py and launch/serve.py) by appending
    ``--xla_force_host_platform_device_count`` to ``XLA_FLAGS``.

    Must run before the XLA backend initialises — once it is up the flag
    would be silently ignored, so this raises instead."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError("--host-devices must be processed before jax "
                           "initialises; set XLA_FLAGS instead")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}")


def active_mesh_axes() -> Tuple[str, ...]:
    am = ambient_mesh()
    return tuple(am.axis_names) if am is not None else ()


def dp_axes(axes: Optional[Tuple[str, ...]] = None):
    axes = active_mesh_axes() if axes is None else axes
    got = tuple(a for a in ("pod", "data") if a in axes)
    return got if got else None


def tp_axis(axes: Optional[Tuple[str, ...]] = None) -> Optional[str]:
    axes = active_mesh_axes() if axes is None else axes
    return "model" if "model" in axes else None


def tp_size() -> int:
    am = ambient_mesh()
    if am is None:
        return 1
    return dict(am.shape).get("model", 1)


def hint(x: jax.Array, *dims) -> jax.Array:
    """with_sharding_constraint(x, P(*dims)) under the ambient mesh; no-op
    when unsharded.  ``dims`` entries: "dp", "tp", None."""
    axes = active_mesh_axes()
    if not axes:
        return x
    spec = []
    for d in dims:
        if d == "dp":
            spec.append(dp_axes(axes))
        elif d == "tp":
            spec.append(tp_axis(axes))
        else:
            spec.append(d)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def batch_pspec(batch_dim_first: bool = True) -> P:
    return P(dp_axes()) if batch_dim_first else P(None, dp_axes())


def time_major_pspec() -> P:
    """Spec for (T+1, batch, ...) path tensors — the SDE/CDE layout.

    Batch on the data axes, the time axis replicated: every solver step is a
    sequential dependency, so sharding time would serialise cross-device.
    The SDE-GAN step (DESIGN.md §4) shards *only* batch; parameters are tiny
    MLPs and stay replicated, so the per-step collective cost is one psum of
    scalar losses + parameter-sized gradient all-reduces.
    """
    return P(None, dp_axes())


def shard_time_major(x: jax.Array) -> jax.Array:
    """Constrain a (T+1, batch, ...) tensor to the time-major layout; no-op
    without a mesh.  Use inside jit (the GAN step) so GSPMD propagates the
    batch sharding through all three SDE/CDE solves."""
    axes = active_mesh_axes()
    if not axes or dp_axes(axes) is None:
        return x
    return jax.lax.with_sharding_constraint(x, time_major_pspec())


def data_parallel_mesh(batch: Optional[int] = None):
    """Pure data-parallel mesh over every visible device, or ``None`` when
    there is a single device (or ``batch`` is given and not divisible — a
    constraint GSPMD in_shardings cannot satisfy).

    Both Neural-SDE training steps and the serving sampler are pure batch
    parallelism (DESIGN.md §4/§8/§9): parameters are tiny and replicated,
    only the sample batch shards.  Callers activate the mesh with
    ``jax.set_mesh``.
    """
    n_dev = len(jax.devices())
    if n_dev <= 1:
        return None
    if batch is not None and batch % n_dev != 0:
        return None
    return make_mesh((n_dev,), ("data",))


_COLLECTIVE = re.compile(r"= (.*?) (all-gather|all-reduce|all-to-all|"
                         r"collective-permute|reduce-scatter)(?:-start)?\(")


def batch_collectives(hlo: str, rows: int) -> list:
    """The collectives in compiled HLO text whose result has a dim of
    ``rows``: the path batch moved between devices, which a data-parallel
    step should never do.  Every collective kind counts, since GSPMD may
    gather a sharded operand with an all-reduce of a zero-padded buffer.
    Returns ``[(kind, result shape), ...]``."""
    found = []
    for line in hlo.splitlines():
        m = _COLLECTIVE.search(line)
        if m is None:
            continue
        dims = [int(d) for group in re.findall(r"\[([\d,]*)\]", m.group(1))
                for d in group.split(",") if d]
        if rows in dims:
            found.append((m.group(2), re.sub(r"\{[^}]*\}", "", m.group(1))))
    return found


# -----------------------------------------------------------------------------
# parameter sharding rules (by name, innermost path component)
# -----------------------------------------------------------------------------

# name -> spec over the *trailing* dims (leading stacked-layer dims get None)
_RULES = {
    # embeddings / head: vocab on TP, d_model on FSDP
    "embed": ("tp", "dp"),
    "head": ("dp", "tp"),
    "pos_embed": (None, "dp"),
    # attention
    "wq": ("dp", "tp"), "wk": ("dp", "tp"), "wv": ("dp", "tp"), "wo": ("tp", "dp"),
    "bq": ("tp",), "bk": ("tp",), "bv": ("tp",),
    # MLA
    "wq_a": ("dp", None), "wq_b": (None, "tp"),
    "wkv_a": ("dp", None), "wkv_b": (None, "tp"), "wo_mla": ("tp", "dp"),
    # dense ffn
    "gate": ("dp", "tp"), "up": ("dp", "tp"), "down": ("tp", "dp"),
    # moe (leading expert dim handled in param_pspecs).  The router is
    # deliberately ABSENT (=> replicated): it is tiny (d_model × E) and
    # sharding its contraction dim forces a f32 (B,S,D) partial-sum
    # all-reduce per MoE layer in the backward (§Perf iteration 3).
    "e_gate": ("ep", "dp", "tp_or_none"), "e_up": ("ep", "dp", "tp_or_none"),
    "e_down": ("ep", "tp_or_none", "dp"),
    # mamba2
    "in_proj": ("dp", "tp"), "out_proj": ("tp", "dp"),
    "conv_w": (None, "tp"), "conv_b": ("tp",),
    "A_log": ("tp",), "Dskip": ("tp",), "dt_bias": ("tp",), "norm_g": ("tp",),
}

_REPLICATED = {"g", "b", "ln1", "ln2", "ln3", "final_norm", "scale"}


def _axis_product(entry, sizes) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= sizes.get(a, 1)
        return n
    return sizes.get(entry, 1)


def _spec_for(name: str, shape, axes, sizes, ep_ok: bool):
    if name in _RULES:
        raw = _RULES[name]
        spec = []
        for r in raw:
            if r == "dp":
                spec.append(dp_axes(axes))
            elif r == "tp":
                spec.append(tp_axis(axes))
            elif r == "ep":
                spec.append(tp_axis(axes) if ep_ok else None)
            elif r == "tp_or_none":
                spec.append(None if ep_ok else tp_axis(axes))
            else:
                spec.append(None)
        # leading stacked-layer dims (scan over layers / blocks)
        pad = len(shape) - len(spec)
        spec = [None] * pad + spec
        # shape-aware fallback: jit in_shardings need exact divisibility.
        # Drop any entry whose mesh-axis product doesn't divide the dim
        # (e.g. vocab 73448 on a 16-way model axis) — production frameworks
        # pad such tables; we keep exact configs and replicate that dim.
        spec = [s if d % _axis_product(s, sizes) == 0 else None
                for s, d in zip(spec, shape)]
        return P(*spec)
    return P()  # replicate (norms, biases, small vectors)


def param_pspecs(params, num_experts: int = 0, serve_pure_tp: bool = False):
    """Tree of PartitionSpec matching ``params`` (a pytree of arrays or
    ShapeDtypeStructs), using the naming convention of repro.models.

    ``serve_pure_tp`` drops the FSDP (dp) factor — pure tensor parallelism.
    Decode moves one token against all weights, so ZeRO-3 weight gathers
    dominate its collective term (§Perf iteration D1); when params/TP fit
    HBM, serving replicates over dp and keeps only the model-axis shards.
    """
    axes = active_mesh_axes()
    am = ambient_mesh()
    sizes = dict(am.shape) if am is not None else {}
    tp_n = sizes.get("model", 1)
    ep_ok = num_experts > 0 and tp_n > 1 and num_experts % tp_n == 0

    def walk(tree, name=""):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = [walk(v, name) for v in tree]
            return type(tree)(t)
        spec = _spec_for(name, tree.shape, axes, sizes, ep_ok)
        if serve_pure_tp:
            dp = dp_axes(axes)
            spec = P(*[None if (s == dp or s in ("pod", "data")) else s
                       for s in spec])
        return spec

    return walk(params)


def named_shardings(mesh, pspecs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))
