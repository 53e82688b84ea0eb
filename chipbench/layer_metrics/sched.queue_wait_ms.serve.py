"""Time a request waited in the engine's queue, from ``add_request`` to the
row slot: the program's ``serving.queue`` spans that end inside the window;
95th percentile over the requests admitted in it."""

LAYER = "engine scheduler (models/serving.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"


def compute(run):
    from chipbench import program_spans
    from chipbench.drivers.common import quantile
    if not run.steps:
        return None
    waits = [(s.t1 - s.t0) * 1e3 for s in program_spans.read(
        "serving.queue", run.steps[0]["t_begin"], run.steps[-1]["t_end"],
        whole=False)]
    return quantile(waits, 0.95) if waits else None
