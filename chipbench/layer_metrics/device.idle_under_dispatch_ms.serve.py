"""Device idle time while the host was launching the step's programs: the
gaps between device operations that overlap the program's span
``serving.step.dispatch``, put on the trace's clock; mean per traced step."""

LAYER = "device"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
DRIVER = "serve"


def compute(run):
    from chipbench import program_spans
    return program_spans.idle_ms(run, ("dispatch",))
