"""Model FLOP/s utilisation of the whole training step: the model's
forward operations per step times 3, times steps per second over the
window, over chips times the bf16 peak.  The work runs in float32."""

LAYER = "training step (launch/steps.py)"
UNIT = "%"
MOVES = "train_paths_per_s"
SOURCE = "host_clock"


def read(run):
    f = run.facts
    if not f.get("steps") or "model_flops_per_step" not in f:
        return None
    per_s = f["model_flops_per_step"] * f["steps"] / f["window_s"]
    return 100.0 * per_s / (run.chips * run.peaks["bf16_flops_per_s"])
