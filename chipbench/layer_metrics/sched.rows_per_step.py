"""Rows in flight in a step (``engine.num_active``, read by the driver
around each step of the window), mean.

In a closed loop every row is held, so the value is the loop's clients.
In an open loop under its knee it follows the offered load: a faster step
finishes each request in fewer steps, so the same arrivals leave fewer rows
a step. Read it there against the sweep that set the cell's rate (the
workload file's ``rate_check``), not as a gain or a loss of its own."""

LAYER = "engine scheduler (models/serving.py)"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"


def compute(run):
    rows = [s["rows"] for s in run.steps]
    return sum(rows) / len(rows) if rows else None
