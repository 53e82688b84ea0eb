"""The ragged paged-attention kernel's share of the HBM roofline in the
traced steps: the bytes it must move (each row's live context of K and V,
the queries and the outputs, per attention layer and step; ``model_math``)
over the chip's bandwidth, divided by the device time of the kernel's
events."""

LAYER = "kernels (ops/kernels/pallas)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"

# the Pallas call's name in the trace: today the kernel function's, and the
# name a later PR is asked to give it
KERNEL_NAMES = ("fwd_flat", "ragged_paged_attention")


def compute(run):
    from chipbench import model_math
    if run.reduced is None or not run.traced_steps:
        return None
    kernel_s = sum(s for name, s in run.reduced.ops if name in KERNEL_NAMES)
    if kernel_s <= 0:
        return None
    layers = model_math.attention_layers(run.config)
    need = sum(model_math.ragged_attention_bytes(
        run.config, s["live_context"], s["tokens"]) for s in run.traced_steps)
    least_s = layers * need / model_math.peaks(
        run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
