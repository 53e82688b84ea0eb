"""The host's time in the scheduler's phases of ``engine.step()``: the
program's spans ``serving.step.admit`` + ``.schedule`` + ``.pack`` +
``.commit``, summed per step; median over the traced steps."""

LAYER = "engine scheduler (models/serving.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"
DRIVER = "serve"


def compute(run):
    from chipbench import program_spans
    return program_spans.host_ms(run, program_spans.SCHED_PHASES)
