"""Engine steps between a request's due time and its first token: the
median over the requests due in the window that got one. The driver counts
the steps that returned in that interval, the last of which carried the
token. Read only for cells that report ``ttft_p50_ms``: none does yet
(PERF.md, Open questions)."""

LAYER = "engine scheduler (models/serving.py)"
UNIT = "steps"
SOURCE = "program_counter"
MOVES = "ttft_p50_ms"
DRIVER = "serve"


def compute(run):
    import statistics
    return float(statistics.median(run.first_steps)) if run.first_steps \
        else None
