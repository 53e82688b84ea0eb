"""The host's time launching a step's programs: the program's span
``serving.step.dispatch`` (the uploads, the model's eager ops, gather and
sampling, until the last asynchronous call returns); median over the traced
steps. With the profiler on: PERF.md has the untraced figure beside it."""

LAYER = "model step (models/llama.py through ops/dispatcher.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"
DRIVER = "serve"


def compute(run):
    from chipbench import program_spans
    return program_spans.host_ms(run, ("dispatch",))
