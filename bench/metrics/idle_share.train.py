"""Share of the traced training window in which no operation ran on the
device, averaged over the chips used."""

LAYER = "device"
UNIT = "%"
MOVES = "train_paths_per_s"
SOURCE = "device_trace"


def read(run):
    return None if not run.trace or not run.trace.get("devices") \
        else 100.0 * run.trace["idle_share"]
