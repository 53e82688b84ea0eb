"""The row-state update's share of the device's busy time in the traced
slice: the device time of ``ragged_selective_scan`` and
``ragged_causal_conv`` over the union of all device operations. It says
whether the mechanism does the work its cell was sized for. Nothing where
neither kernel ran (a family without row state, or a program without
them)."""

LAYER = "kernels (ops/kernels/pallas)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"

KERNELS = ("ragged_selective_scan", "ragged_causal_conv")


def compute(run):
    r = run.reduced
    if r is None or r.busy_s <= 0:
        return None
    kernel_s = sum(s for name, s in r.ops if name in KERNELS)
    return 100.0 * kernel_s / r.busy_s if kernel_s > 0 else None
