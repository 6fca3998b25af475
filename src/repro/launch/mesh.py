"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (smoke tests see 1 device; only dryrun.py sets the
512-placeholder XLA flag before first jax init).

Mesh layout (TPU v5e pods):
  single pod : (data=16, model=16)              = 256 chips
  multi-pod  : (pod=2, data=16, model=16)       = 512 chips
``pod`` and ``data`` jointly carry batch/FSDP sharding (DCN across pods);
``model`` carries tensor/expert/sequence parallelism (ICI).
"""

from __future__ import annotations

from ..distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_from_devices(num_devices: int, model_parallel: int = 16):
    """Elastic fallback: best (data, model) factorisation of a surviving
    device count (see distributed/elastic.py for the planning logic)."""
    from ..distributed.elastic import plan_mesh

    data, model = plan_mesh(num_devices, model_parallel)
    return make_mesh((data, model), ("data", "model"))
