"""The ragged selective scan's share of the HBM roofline in the traced
steps: the bytes it must move (for each step and each layer that keeps row
state: the SSM state of every row the step updated, read and written once,
and the packed tokens' ``x``, ``dt``, ``z``, ``B``, ``C`` in and ``y`` out;
the family's ``scan_bytes``) over the chip's bandwidth, divided by the
device time of ``ragged_selective_scan``. The rows and tokens are the
program's own: the attributes ``state_rows`` and ``scan_tokens`` of its
``serving.step`` spans. Nothing for a family without row state, and nothing
from a program whose spans lack the attributes."""

LAYER = "kernels (ops/kernels/pallas)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"

KERNEL = "ragged_selective_scan"


def least_bytes(fam, config, per_step):
    """``per_step``: for each traced step, the program's spans inside it."""
    counted = [s.attrs for got in per_step for s in got
               if s.name == "serving.step" and "state_rows" in s.attrs]
    return fam.state_layers(config) * sum(
        fam.scan_bytes(config, a["state_rows"], a["scan_tokens"])
        for a in counted)


def compute(run):
    from chipbench import model_math, program_spans
    from chipbench.drivers.common import family
    fam = family(run.config)
    steps = run.traced_steps
    if not hasattr(fam, "scan_bytes") or run.reduced is None or not steps:
        return None
    kernel_s = sum(s for name, s in run.reduced.ops if name == KERNEL)
    spans = program_spans.read("serving.step", steps[0]["t_begin"],
                               steps[-1]["t_end"])
    need = least_bytes(fam, run.config, program_spans.by_step(spans, steps))
    if kernel_s <= 0 or need <= 0:
        return None
    return 100.0 * need / model_math.peaks(
        run.device_kind)["hbm_bytes_per_s"] / kernel_s
