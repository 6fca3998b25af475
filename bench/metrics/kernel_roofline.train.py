"""The Pallas kernels' share of their roofline in a training step: the
least time the chip needs to move the kernels' logical operand and result
bytes at its HBM peak, over the kernels' device time in the trace.  The
kernels do a few operations per element, so bandwidth is the bound."""

LAYER = "Pallas kernels (kernels/reversible_heun_step.py, kernels/brownian.py)"
UNIT = "%"
MOVES = "train_paths_per_s"
SOURCE = "device_trace"


def read(run):
    t, f = run.trace, run.facts
    if not t or not t.get("kernel_ns_total") or not f.get("pallas_bytes_per_step"):
        return None
    least_s = f["pallas_bytes_per_step"] * f["steps"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["kernel_ns_total"] / 1e9)
