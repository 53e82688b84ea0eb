"""Device idle time while the host was scheduling: the gaps between device
operations that overlap the program's spans ``serving.step.admit``,
``.schedule``, ``.pack`` and ``.commit``, or lie between two
``engine.step()`` calls; mean per traced step. With the idle time under
``serving.step.dispatch`` and ``.sync`` it adds up to the step's idle time."""

LAYER = "device"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
DRIVER = "serve"


def compute(run):
    from chipbench import program_spans
    return program_spans.idle_ms(
        run, program_spans.SCHED_PHASES + (program_spans.BETWEEN,))
