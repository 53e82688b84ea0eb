"""Host clock per training step, batch fetch to ``block_until_ready`` of
the updated parameters; median over the window's steps."""

LAYER = "train step (jit/api.py TrainStep)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"
DRIVER = "train"


def compute(run):
    import statistics
    ms = [(s["t_end"] - s["t_begin"]) * 1e3 for s in run.steps]
    return statistics.median(ms) if ms else None
