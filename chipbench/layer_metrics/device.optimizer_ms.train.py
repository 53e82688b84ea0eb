"""Device time of the optimizer, ms a traced step: the events whose
instruction lies under ``TrainStep``'s scopes ``optimizer.update`` (the
per-parameter update, casts and pins included) or ``optimizer.grad_clip``
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
    return device_scopes.ms_where(run, lambda fact: fact.phase == "optimizer")
