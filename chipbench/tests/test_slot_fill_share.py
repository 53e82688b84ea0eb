"""``step.slot_fill_share.serve``: tokens over slots of the traced steps'
``serving.step`` spans, on rehearsed spans; nothing where no span carries
``slots`` (a program from before the second geometry)."""

import importlib.util
import os
import types

from chipbench import program_spans
from chipbench.program_spans import Span

HERE = os.path.dirname(os.path.abspath(__file__))


def metric():
    path = os.path.join(HERE, os.pardir, "layer_metrics",
                        "step.slot_fill_share.serve.py")
    spec = importlib.util.spec_from_file_location("slot_fill_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def step_span(t0, t1, **attrs):
    return Span("serving.step", t0, t1, attrs)


def test_tokens_over_slots_of_the_traced_steps_and_nothing_without_slots(
        monkeypatch):
    steps = [{"t_begin": 10.0, "t_end": 11.0}, {"t_begin": 11.5, "t_end": 12.5},
             {"t_begin": 13.0, "t_end": 14.0}]
    spans = [step_span(9.0, 9.5, tokens=500, slots=512),      # before: out
             Span("serving.step.pack", 10.0, 10.1, {}),       # a phase: out
             step_span(10.1, 10.9, tokens=7, slots=256),
             step_span(11.6, 12.4, tokens=300, slots=512),
             step_span(13.1, 13.9, tokens=64, slots=256)]
    asked = []

    def read(prefix, t_lo, t_hi):
        asked.append((prefix, t_lo, t_hi))
        return spans

    m = metric()
    monkeypatch.setattr(program_spans, "read", read)
    run = types.SimpleNamespace(traced_steps=steps)
    got = m.compute(run)
    assert asked == [("serving.step", 10.0, 14.0)]
    assert abs(got - 100.0 * (7 + 300 + 64) / (256 + 512 + 256)) < 1e-12
    # the parent's span has tokens and launches and no slots
    spans[:] = [step_span(10.1, 10.9, tokens=7, launches=4)]
    assert m.compute(run) is None
    assert m.share([[]]) is None
    assert m.compute(types.SimpleNamespace(traced_steps=[])) is None
