"""Share of the traced serving window (arrivals and drain) in which no operation ran on the
device, averaged over the chips used."""

LAYER = "device"
UNIT = "%"
MOVES = "serve_p95_ms"
SOURCE = "device_trace"


def read(run):
    return None if not run.trace or not run.trace.get("devices") \
        else 100.0 * run.trace["idle_share"]
