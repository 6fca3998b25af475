"""Share of the devices' busy time spent in collectives (the gradient
all-reduce of data-parallel training), summed over the chips."""

LAYER = "data-parallel exchange (distributed/sharding.py)"
UNIT = "%"
MOVES = "train_paths_per_s"
SOURCE = "device_trace"


def read(run):
    t = run.trace
    if not t or not t.get("collective_ns_total"):
        return None
    return 100.0 * t["collective_ns_total"] / t["busy_ns_total"]
