"""Device time of the backward pass, ms a traced step: the events whose
instruction's ``op_name`` has JAX's ``transpose(``
(``chipbench/device_scopes.phase_of``). Forward, backward, optimizer,
``other`` and the unnamed time sum to the busy time a step: the
``device_scopes`` line has all five."""

LAYER = "train step (jit/api.py TrainStep)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
DRIVER = "train"


def compute(run):
    from chipbench import device_scopes
    return device_scopes.ms_where(run, lambda fact: fact.phase == "backward")
