"""Rows advanced per executed chunk batch: every finished request's rows
times its chunks, over ``Scheduler.counters["chunk_batches"]`` in the
window."""

LAYER = "serving scheduler (serving/scheduler.py)"
UNIT = "rows"
MOVES = "serve_p95_ms"
SOURCE = "program_counter"


def read(run):
    f = run.facts
    return f["rows_advanced"] / f["chunk_batches"] if f.get("chunk_batches") else None
