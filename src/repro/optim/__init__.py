from .optimizers import (  # noqa: F401
    adadelta,
    adam,
    adamw,
    apply_updates,
    chain,
    clip_by_global_norm,
    cosine_schedule,
    global_norm,
    lipschitz_projection,
    swa_update,
)
from .compression import compress_int8, decompress_int8, ef_compress_update  # noqa: F401
