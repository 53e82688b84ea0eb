"""The whole serving step's share of the chip's bf16 peak in a cell that is
judged by tokens a second, for a family that counts its own matrices:
``step_mfu.serve``'s reckoning (the operations the traced steps' tokens
need over the host's clock from the first traced step's begin to the last
one's end, idle time included) with the family's ``serve_flops``, since
``model_math.matmul_params`` counts a Llama layer's seven matrices and a
layer that keeps row state has others. It bounds the scan's roofline as
``step_mfu.serve`` bounds the ragged kernel's. Nothing for a family that
brings no count of its own."""

LAYER = "model step (models/llama.py through ops/dispatcher.py)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"


def compute(run):
    from chipbench import model_math
    from chipbench.drivers.common import family
    fam = family(run.config)
    steps = run.traced_steps
    if not hasattr(fam, "serve_flops") or not steps:
        return None
    span_s = steps[-1]["t_end"] - steps[0]["t_begin"]
    if span_s <= 0:
        return None
    flops = sum(fam.serve_flops(run.config, s["tokens"], s["rows"])
                for s in steps)
    try:
        peak = model_math.peaks(run.device_kind)["bf16_flops_per_s"]
    except model_math.UnknownDevice:    # a rehearsal: no chip, no share
        return None
    return 100.0 * flops / (peak * span_s)
