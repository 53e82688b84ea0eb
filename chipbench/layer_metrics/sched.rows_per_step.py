"""Rows in flight in a step (``engine.num_active``, read by the driver
around each step of the window), mean."""

LAYER = "engine scheduler (models/serving.py)"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"


def compute(run):
    rows = [s["rows"] for s in run.steps]
    return sum(rows) / len(rows) if rows else None
