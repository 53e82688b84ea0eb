"""Share of the traced slice in which no operation ran on the device:
1 - union of the device-operation intervals over the slice."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
DRIVER = "train"


def compute(run):
    from chipbench import trace
    return trace.idle_share_pct(run.reduced)
