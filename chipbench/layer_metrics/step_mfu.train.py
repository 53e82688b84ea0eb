"""The whole training step's share of the chip's bf16 peak: the operations
forward and backward need for a step's tokens (``model_math``; recomputed
ones would not count) over the host's clock for the window's steps, fetch
to ``block_until_ready``, the median step. ``kernel.flops_share.train`` is
the same operations over the device's busy time alone."""

LAYER = "train step (jit/api.py TrainStep)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"
DRIVER = "train"


def compute(run):
    import statistics
    from chipbench import model_math
    if not run.steps:
        return None
    t = run.cell["traffic"]
    tokens = t["sequences_per_step"] * t["sequence_tokens"]
    flops = tokens * model_math.train_flops_per_token(
        run.config, t["sequence_tokens"])
    step_s = statistics.median(s["t_end"] - s["t_begin"] for s in run.steps)
    try:
        peak = model_math.peaks(run.device_kind)["bf16_flops_per_s"]
    except model_math.UnknownDevice:    # a rehearsal: no chip, no share
        return None
    return 100.0 * flops / (peak * step_s)
