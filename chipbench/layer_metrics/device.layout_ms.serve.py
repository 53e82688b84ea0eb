"""Device time that moves data and computes nothing, ms a traced step: the
events whose instruction is a ``copy``, a ``transpose`` or the start or end
of one of the compiler's asynchronous copies and slices (kind ``copy``,
``chipbench/device_scopes.kind_of``), and every event under the scope
``serving.cache_write`` (the ragged view's write of a step's K and V into
the pools)."""

LAYER = "model step (models/llama.py through ops/dispatcher.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"


def compute(run):
    from chipbench import device_scopes
    return device_scopes.ms_where(
        run, lambda fact: fact.kind == "copy"
        or device_scopes.CACHE_WRITE in fact.scope.split("/"))
