"""The operations forward and backward need per step (6 per matmul
parameter and the causal attention, per token; ``model_math``) over the
chip's bf16 peak, divided by the device's busy time per traced step. With 2
of 32 layers the optimizer's update is a larger part of the busy time than
in a deployment, so this reads lower than a full model would."""

LAYER = "kernels (ops/kernels/pallas)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
DRIVER = "train"


def compute(run):
    from chipbench import model_math
    r = run.reduced
    if r is None or r.busy_s <= 0 or not run.traced_steps:
        return None
    t = run.cell["traffic"]
    tokens = t["sequences_per_step"] * t["sequence_tokens"]
    flops = tokens * model_math.train_flops_per_token(
        run.config, t["sequence_tokens"])
    # the spans that bound the slice are whole steps: count those
    steps = sum(1 for s in r.spans if s.name == "chipbench.train_step")
    if steps == 0:
        return None
    least_s = flops / model_math.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * least_s / (r.busy_s / steps)
