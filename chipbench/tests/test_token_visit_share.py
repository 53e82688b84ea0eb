"""``kernel.token_visit_share.serve``: one-token tiles' visits over all the
ragged kernel's visits of the traced steps' ``serving.step`` spans, on
rehearsed spans; nothing where no span carries ``kv_token_blocks`` (a program
without the one-token body) or no step was traced."""

import importlib.util
import os
import types

from chipbench import program_spans
from chipbench.program_spans import Span

HERE = os.path.dirname(os.path.abspath(__file__))


def metric():
    path = os.path.join(HERE, os.pardir, "layer_metrics",
                        "kernel.token_visit_share.serve.py")
    spec = importlib.util.spec_from_file_location("token_visit_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def step_span(t0, t1, **attrs):
    return Span("serving.step", t0, t1, attrs)


def test_token_visits_over_visits_of_the_traced_steps(monkeypatch):
    steps = [{"t_begin": 10.0, "t_end": 11.0}, {"t_begin": 11.5, "t_end": 12.5},
             {"t_begin": 13.0, "t_end": 14.0}]
    spans = [step_span(9.0, 9.5, kv_token_blocks=90, kv_tile_blocks=90),
             Span("serving.step.pack", 10.0, 10.1, {}),
             step_span(10.1, 10.9, kv_token_blocks=3000, kv_tile_blocks=3300),
             step_span(11.6, 12.4, kv_token_blocks=0, kv_tile_blocks=400),
             step_span(13.1, 13.9, kv_token_blocks=3200, kv_tile_blocks=3200)]
    asked = []

    def read(prefix, t_lo, t_hi):
        asked.append((prefix, t_lo, t_hi))
        return spans

    m = metric()
    monkeypatch.setattr(program_spans, "read", read)
    run = types.SimpleNamespace(traced_steps=steps)
    got = m.compute(run)
    assert asked == [("serving.step", 10.0, 14.0)]
    assert abs(got - 100.0 * (3000 + 0 + 3200) / (3300 + 400 + 3200)) < 1e-12
    # the parent's span counts the visits and not the one-token tiles'
    spans[:] = [step_span(10.1, 10.9, kv_tile_blocks=3300, kv_live_tiles=256)]
    assert m.compute(run) is None
    # steps with no visit at all (every row empty)
    spans[:] = [step_span(10.1, 10.9, kv_token_blocks=0, kv_tile_blocks=0)]
    assert m.compute(run) is None
    assert m.share([[]]) is None
    assert m.compute(types.SimpleNamespace(traced_steps=[])) is None
