"""Programs the dispatcher launched in a step: the ``launches`` attribute of
the program's ``serving.step`` span, the difference of its ``dispatch.count``
counter across the step's model call; median over the traced steps."""

LAYER = "model step (models/llama.py through ops/dispatcher.py)"
UNIT = "launches"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"
DRIVER = "serve"


def compute(run):
    import statistics
    from chipbench import program_spans
    launches = program_spans.analyse(run).launches
    return float(statistics.median(launches)) if launches else None
