"""The share of the traced steps that were launched behind a step still in
flight: the program's ``serving.step`` spans whose attribute ``overlapped``
is 1. Near 100 in a loop that always has work; a change that makes every
step drain reads 0 here before it reads as lost tokens a second."""

LAYER = "engine scheduler (models/serving.py)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"


def compute(run):
    from chipbench import program_spans
    steps = run.traced_steps
    if not steps:
        return None
    flags = [s.attrs["overlapped"] for s in program_spans.read(
        "serving.step", steps[0]["t_begin"], steps[-1]["t_end"])
        if s.name == "serving.step" and "overlapped" in s.attrs]
    return 100.0 * sum(flags) / len(flags) if flags else None
