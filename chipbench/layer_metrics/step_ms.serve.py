"""Host clock around ``engine.step()``, which ends in the sampled tokens'
transfer to the host; median over the window's steps."""

LAYER = "model step (models/llama.py through ops/dispatcher.py)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"
DRIVER = "serve"


def compute(run):
    import statistics
    ms = [(s["t_end"] - s["t_begin"]) * 1e3 for s in run.steps]
    return statistics.median(ms) if ms else None
