"""The paper's primary contributions as composable JAX modules."""

from .brownian import (  # noqa: F401
    BrownianPath,
    DenseBrownianPath,
    VirtualBrownianTree,
    brownian_increments,
    davie_levy_area,
    space_time_levy_area,
    stlevy_difference,
)
from .brownian_interval import BrownianInterval, HostVirtualBrownianTree  # noqa: F401
from .clipping import clip_lipschitz, clip_linear, clip_mlp, lipschitz_bound_mlp  # noqa: F401
from .losses import signature, signature_mmd, time_augment, wasserstein_losses  # noqa: F401
from .paths import LinearPathControl  # noqa: F401
from .gradients import (  # noqa: F401
    GRADIENT_BACKENDS,
    GradientBackend,
    PrecisionPolicy,
    checkpoint_schedule,
    continuous_adjoint_solve,
    register_backend,
    resolve_precision,
    reversible_heun_solve,
)
from .solve import (  # noqa: F401
    GRADIENT_MODES,
    SOLVERS,
    SolverSpec,
    available_solvers,
    get_solver,
    gradient_capabilities,
    register_solver,
    solve,
    solve_batched,
)
from .solvers import (  # noqa: F401
    NFE_PER_STEP,
    RevHeunState,
    ode_solve,
    reversible_heun_reverse_step,
    reversible_heun_step,
    sde_solve,
)
