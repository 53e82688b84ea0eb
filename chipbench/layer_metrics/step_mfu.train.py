"""The whole training step's share of the chip's bf16 peak: the operations
forward and backward need for a step's tokens (``model_math``; recomputed
ones would not count) over the host's clock for the window's steps, from one step's loss found
ready to the next's, the median step (``step_ms.train``). ``kernel.flops_share.train`` is
the same operations over the device's busy time alone."""

LAYER = "train step (jit/api.py TrainStep)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"
DRIVER = "train"


def compute(run):
    import statistics
    from chipbench import model_math
    done = [s["t_done"] for s in run.steps]
    if len(done) < 2:
        return None
    t = run.cell["traffic"]
    tokens = t["sequences_per_step"] * t["sequence_tokens"]
    flops = tokens * model_math.train_flops_per_token(
        run.config, t["sequence_tokens"])
    step_s = statistics.median(b - a for a, b in zip(done, done[1:]))
    try:
        peak = model_math.peaks(run.device_kind)["bf16_flops_per_s"]
    except model_math.UnknownDevice:    # a rehearsal: no chip, no share
        return None
    return 100.0 * flops / (peak * step_s)
