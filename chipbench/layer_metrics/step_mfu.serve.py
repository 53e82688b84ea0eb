"""The whole serving step's share of the chip's bf16 peak in the traced
steps: the operations the packed tokens need (``model_math.serve_flops``:
the layers' matrices for every token, the head for the rows in flight) over
the host's clock from the first traced step's begin to the last one's end,
idle time and the time between steps included. It bounds the kernels'
rooflines: a kernel taken off the path leaves its own share silent, and a
gain then shows here or nowhere."""

LAYER = "model step (models/llama.py through ops/dispatcher.py)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"
DRIVER = "serve"


def compute(run):
    from chipbench import model_math
    steps = run.traced_steps
    if not steps:
        return None
    span_s = steps[-1]["t_end"] - steps[0]["t_begin"]
    if span_s <= 0:
        return None
    flops = sum(model_math.serve_flops(run.config, s["tokens"], s["rows"])
                for s in steps)
    try:
        peak = model_math.peaks(run.device_kind)["bf16_flops_per_s"]
    except model_math.UnknownDevice:    # a rehearsal: no chip, no share
        return None
    return 100.0 * flops / (peak * span_s)
