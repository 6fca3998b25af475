"""Operations and bytes computed from shapes: the model FLOPs of a step
(one module per model kind) and the bytes the step's Pallas kernels move
(:mod:`bench.flops.pallas`)."""
