"""Host clock per training step: from one step's loss found ready to the
next's, median over the window's steps. With steps launched ahead of the
one waited for, that is the period at which the device finishes steps."""

LAYER = "train step (jit/api.py TrainStep)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"
DRIVER = "train"


def compute(run):
    import statistics
    done = [s["t_done"] for s in run.steps]
    ms = [(b - a) * 1e3 for a, b in zip(done, done[1:])]
    return statistics.median(ms) if ms else None
