"""The share of the device's busy time whose events the program's records
do not name: no record of the instruction, or records that disagree
(``chipbench/device_scopes.join``). It says how far the other ``device.*_ms``
readings can be trusted."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"


def compute(run):
    from chipbench import device_scopes
    return device_scopes.unnamed_share_pct(run)
