"""The host's time in ``TrainStep.__call__`` before the device has the
step: the program's spans ``train.step.args`` (build, re-sync, the argument
tuple) and ``train.step.launch`` (the jitted call until it returns); median
over the traced steps launched while fewer than ``QUEUED_UNDER`` steps were
in flight. With some tens of steps queued the runtime makes a launch wait
for room, and the call then lasts about a step: that is the device's time,
not the host's. The traced slice begins with nothing in flight, so its first
steps are the ones read. With the profiler on."""

LAYER = "train step (jit/api.py TrainStep)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_tokens_per_s"
DRIVER = "train"

PARTS = ("train.step.args", "train.step.launch")
QUEUED_UNDER = 16


def compute(run):
    import statistics
    from chipbench import program_spans
    steps = run.traced_steps
    if not steps:
        return None
    spans = program_spans.read("train.step.", steps[0]["t_begin"],
                               steps[-1]["t_end"])
    per_step = [sum((s.t1 - s.t0) * 1e3 for s in got)
                for step, got in zip(steps, program_spans.by_step(spans, steps))
                if step.get("queued", 0) < QUEUED_UNDER
                and sorted(s.name for s in got) == sorted(PARTS)]
    return statistics.median(per_step) if per_step else None
