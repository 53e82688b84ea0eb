"""The program's spans in a traced run: containment in the traced steps, the
pairing of the two clocks, and the split of the device's idle time over the
phases, on hand-written tuples."""

from chipbench.program_spans import (BETWEEN, Span, by_step, clock_offset,
                                     idle_by_phase)
from chipbench.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def step(t_begin, t_end):
    return {"t_begin": t_begin, "t_end": t_end}


def host(name, start, dur):
    return Event(HOST, "main", name, float(start), float(dur))


def op(start, dur, plane=DEV):
    return Event(plane, "XLA Ops", "%fusion.1 = f32[1]{0} fusion()",
                 float(start), float(dur))


# -- containment --------------------------------------------------------------

def test_a_span_belongs_to_the_traced_step_it_lies_inside():
    steps = [step(10.0, 11.0), step(11.5, 12.5)]
    spans = [Span("serving.step.admit", 9.0, 9.5, {}),        # before
             Span("serving.step.admit", 10.0, 10.1, {}),
             Span("serving.step.commit", 10.9, 11.0, {}),
             Span("serving.step.commit", 10.9, 11.2, {}),     # ends outside
             Span("serving.step.admit", 11.2, 11.3, {}),      # between steps
             Span("serving.step.pack", 11.6, 11.7, {}),
             Span("serving.step.pack", 12.6, 12.7, {})]       # after
    got = by_step(spans, steps)
    assert [[s.t0 for s in g] for g in got] == [[10.0, 10.9], [11.6]]
    assert by_step(spans, []) == []


# -- the two clocks -----------------------------------------------------------

def test_a_constant_offset_is_recovered():
    steps = [step(100.0 + k, 100.5 + k) for k in range(5)]
    offset = -99.75e9                  # the profiler's clock starts later
    jitter = [0.0, 3e3, -2e3, 1e3, 0.0]
    spans = [host("chipbench.step", s["t_begin"] * 1e9 + offset + j, 0.5e9)
             for s, j in zip(steps, jitter)]
    spans.insert(2, host("chipbench.admit", 1.0, 2.0))     # not a step
    got, residual = clock_offset(steps, spans)
    assert abs(got - offset) < 1.0 and abs(residual - 5e3) < 1.0


def test_another_count_of_steps_gives_nothing():
    steps = [step(100.0 + k, 100.5 + k) for k in range(3)]
    spans = [host("chipbench.step", k * 1e9, 0.5e9) for k in range(4)]
    assert clock_offset(steps, spans) is None
    assert clock_offset([], []) is None


def test_a_spread_above_the_tolerance_gives_nothing():
    steps = [step(100.0 + k, 100.5 + k) for k in range(3)]
    spans = [host("chipbench.step", k * 1e9 + j, 0.5e9)
             for k, j in enumerate([0.0, 0.0, 101e3])]
    assert clock_offset(steps, spans) is None
    spans[2] = host("chipbench.step", 2e9 + 99e3, 0.5e9)
    assert clock_offset(steps, spans) is not None


# -- the idle split -----------------------------------------------------------

def phases(t):
    """One step's phases from t: schedule 10, dispatch 60, sync 20, commit
    10; the step's span is [t, t + 100)."""
    return [("schedule", t, t + 10), ("dispatch", t + 10, t + 70),
            ("sync", t + 70, t + 90), ("commit", t + 90, t + 100)]


def test_a_gap_across_two_phases_is_shared_by_overlap():
    # busy [5, 60) and [75, 95): idle [0,5) schedule; [60,75) is 10 of
    # dispatch and 5 of sync; [95,100) commit
    got = idle_by_phase([(0, 100)], [phases(0)], [op(5, 55), op(75, 20)])
    assert got == [{"schedule": 5.0, "dispatch": 10.0, "sync": 5.0,
                    "commit": 5.0, BETWEEN: 0.0}]


def test_the_idle_metrics_add_up_to_the_steps_idle_time():
    # two steps of 100 with 20 between them; the stretch of the first runs
    # to the start of the second, the last ends with its own span
    steps = [(0, 100), (120, 220)]
    events = [op(5, 55), op(75, 20),          # step 1 as above
              op(110, 20),                    # [110,130): over the boundary
              op(150, 60)]                    # [150, 210)
    got = idle_by_phase(steps, [phases(0), phases(120)], events)
    # step 1: the 25 above and [100,110) between the two calls
    assert got[0][BETWEEN] == 10.0
    assert sum(got[0].values()) == 35.0
    # step 2: [130,150) is dispatch; [210,220) is commit
    assert got[1] == {"dispatch": 20.0, "commit": 10.0, BETWEEN: 0.0}
    busy = (55 + 20) + 20 + 60
    assert sum(sum(g.values()) for g in got) == 220 - busy
    dispatch = sum(g.get("dispatch", 0.0) for g in got)
    sync = sum(g.get("sync", 0.0) for g in got)
    sched = sum(g.get(n, 0.0) for g in got
                for n in ("admit", "schedule", "pack", "commit", BETWEEN))
    assert dispatch + sync + sched == 220 - busy


def test_two_device_planes_are_averaged():
    got = idle_by_phase([(0, 100)], [phases(0)],
                        [op(0, 100), op(10, 90, "/device:TPU:1")])
    assert got == [{"schedule": 5.0, BETWEEN: 0.0}]


def test_no_phase_spans_puts_all_idle_between():
    got = idle_by_phase([(0, 100)], [[]], [op(40, 60)])
    assert got == [{BETWEEN: 40.0}]
