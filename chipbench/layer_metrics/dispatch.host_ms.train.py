"""The host's time in ``TrainStep.__call__`` before the device has the
step: the program's spans ``train.step.args`` (build, re-sync, the argument
tuple) and ``train.step.launch`` (the jitted call until it returns); median
over the traced steps. With the profiler on."""

LAYER = "train step (jit/api.py TrainStep)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_tokens_per_s"
DRIVER = "train"

PARTS = ("train.step.args", "train.step.launch")


def compute(run):
    import statistics
    from chipbench import program_spans
    steps = run.traced_steps
    if not steps:
        return None
    spans = program_spans.read("train.step.", steps[0]["t_begin"],
                               steps[-1]["t_end"])
    per_step = [sum((s.t1 - s.t0) * 1e3 for s in got)
                for got in program_spans.by_step(spans, steps)
                if sorted(s.name for s in got) == sorted(PARTS)]
    return statistics.median(per_step) if per_step else None
