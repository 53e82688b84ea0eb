"""Share of the ragged attention kernel's visits that ran on a tile of one
token (a decode row, a chunk's last tile of one): the sum of the
``kv_token_blocks`` attribute of the program's ``serving.step`` spans over
the sum of their ``kv_tile_blocks``, over the traced steps. Those visits
take the kernel's one-token body where it has one. A program whose span has
no ``kv_token_blocks`` gives nothing."""

LAYER = "kernels (ops/kernels/pallas)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"
DRIVER = "serve"


def share(per_step):
    """``per_step``: for each traced step, the program's spans inside it."""
    counted = [s.attrs for got in per_step for s in got
               if s.name == "serving.step" and "kv_token_blocks" in s.attrs]
    visits = sum(a["kv_tile_blocks"] for a in counted)
    if not visits:
        return None
    return 100.0 * sum(a["kv_token_blocks"] for a in counted) / visits


def compute(run):
    from chipbench import program_spans
    steps = run.traced_steps
    if not steps:
        return None
    spans = program_spans.read("serving.step", steps[0]["t_begin"],
                               steps[-1]["t_end"])
    return share(program_spans.by_step(spans, steps))
