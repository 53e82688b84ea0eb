"""Share of the step program's token slots that carried a token: the sum of
the ``tokens`` attribute of the program's ``serving.step`` spans over the sum
of their ``slots`` (the geometry each step ran), over the traced steps. A
program whose span has no ``slots`` gives nothing."""

LAYER = "model step (models/llama.py through ops/dispatcher.py)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "itl_p95_ms"
DRIVER = "serve"


def share(per_step):
    """``per_step``: for each traced step, the program's spans inside it."""
    counted = [s.attrs for got in per_step for s in got
               if s.name == "serving.step" and "slots" in s.attrs]
    slots = sum(a["slots"] for a in counted)
    return 100.0 * sum(a["tokens"] for a in counted) / slots if slots else None


def compute(run):
    from chipbench import program_spans
    steps = run.traced_steps
    if not steps:
        return None
    spans = program_spans.read("serving.step", steps[0]["t_begin"],
                               steps[-1]["t_end"])
    return share(program_spans.by_step(spans, steps))
