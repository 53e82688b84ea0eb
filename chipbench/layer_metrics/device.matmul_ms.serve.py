"""Device time of the step programs' matrix products, ms a traced step:
the events of the slice whose instruction is, or whose fused computation
holds, a ``dot`` or ``convolution`` (the record's ``has_matmul``;
``chipbench/device_scopes.py`` joins events to the program's records). The
``device_scopes`` line splits it by module scope."""

LAYER = "model step (models/llama.py through ops/dispatcher.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"


def compute(run):
    from chipbench import device_scopes
    return device_scopes.ms_where(run, lambda fact: fact.has_matmul)
