"""Host milliseconds per ``Scheduler.step`` call in the window, timed by
the benchmark's own wrapper around the call."""

LAYER = "serving scheduler (serving/scheduler.py)"
UNIT = "ms"
MOVES = "serve_p95_ms"
SOURCE = "host_clock"


def read(run):
    s = run.facts.get("step_seconds")
    return 1e3 * sum(s) / len(s) if s else None
