"""repro — 'Efficient and Accurate Gradients for Neural SDEs' as a
production-grade multi-pod JAX framework.

The front door is :func:`repro.solve` (re-exported from
:mod:`repro.core.solve`): one entry point dispatching solver ×
gradient-mode × noise-type through a solver registry, with
:func:`repro.solve_batched` for vmapped multi-trajectory ensembles.

Paper ↔ module cross-reference:

=====================  =====================================================
paper                  module
=====================  =====================================================
§2 (Neural SDE/GAN)    repro.core.sde (generator / CDE discriminator / joint
                       solve), repro.core.losses (Wasserstein, sig-MMD)
§3 / Alg. 1–2          repro.core.solvers (reversible Heun + inverse),
                       repro.kernels.reversible_heun_step (fused steps)
§3 / App. C (adjoint)  repro.core.gradients (exact O(1)-memory custom VJP;
                       continuous-adjoint baseline, eq. (6))
§4 / Alg. 3–4          repro.core.brownian_interval (host Brownian Interval,
                       LRU + search hints), repro.core.brownian
                       (counter-based TPU-native BrownianPath) — DESIGN.md §2
§5 (Lipschitz clip)    repro.core.clipping (hard projection, LipSwish in
                       repro.nn)
App. D (orders)        tests/test_solvers.py (strong order, stability region)
App. E (Lévy area)     repro.core.brownian (space-time Lévy area, Davie W̃)
=====================  =====================================================

Framework substrates: repro.nn, repro.models (10-arch zoo), repro.optim,
repro.data, repro.distributed, repro.checkpoint, repro.kernels (Pallas),
repro.launch (mesh / dryrun / train / serve).
"""

from .core.solve import (  # noqa: F401
    GRADIENT_MODES,
    PRECISION_POLICIES,
    SOLVERS,
    AdaptiveStats,
    SolverSpec,
    available_solvers,
    gradient_capabilities,
    solve,
    solve_adaptive,
    solve_batched,
)

__version__ = "1.1.0"
