"""The program's own spans in a traced run: the host's phases of each
``engine.step()``, and the device's idle time split over them.

The program (``paddle_tpu.observability.tracing``) keeps finished spans in a
ring, stamped with ``time.perf_counter_ns``; the drivers stamp their steps
with ``time.perf_counter``, the same clock, so a span belongs to the traced
step it lies inside. The device's operations are on the profiler's clock.
The k-th traced step's ``t_begin`` and the k-th ``chipbench.step`` span of
the trace mark the same instant, so the median of their differences puts the
program's spans on the profiler's clock (``clock_offset``).

``by_step``, ``clock_offset`` and ``idle_by_phase`` are pure functions over
tuples, checked in ``chipbench/tests`` on hand-written lists. A program that
has no reader of its ring, or records no such span, gives empty lists here
and ``None`` from every metric that reads them.
"""

from __future__ import annotations

import bisect
import json
import statistics
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from . import trace

STEP_SPAN = "chipbench.step"
PHASE_PREFIX = "serving.step."
PHASES = ("admit", "schedule", "pack", "dispatch", "sync", "commit")
SCHED_PHASES = ("admit", "schedule", "pack", "commit")
BETWEEN = "between"             # inside no phase: between two engine.step()s
PAIRING_TOLERANCE_NS = 100e3


class Span(NamedTuple):
    """A program span; seconds on ``time.perf_counter``'s clock."""
    name: str
    t0: float
    t1: float
    attrs: Dict


def read(prefix: str, t_lo: float, t_hi: float, whole: bool = True
         ) -> List[Span]:
    """The program's finished spans named ``prefix``... that lie inside
    [t_lo, t_hi] (``whole``) or at least end there, by start time; nothing
    from a program without a reader."""
    from paddle_tpu.observability import tracing
    reader = getattr(tracing, "finished_spans", None)
    if reader is None:
        return []
    since_ns = int(t_lo * 1e9) if whole else None
    return [Span(s.name, s.t0_ns / 1e9, s.t1_ns / 1e9, s.attrs)
            for s in reader(prefix, since_ns=since_ns)
            if t_lo <= s.t1_ns / 1e9 <= t_hi]


def by_step(spans: Sequence[Span], steps: Sequence[Dict]) -> List[List[Span]]:
    """For each step (``t_begin``, ``t_end``; in order, not overlapping) the
    spans that lie inside it. A span inside no step is dropped."""
    begins = [s["t_begin"] for s in steps]
    out: List[List[Span]] = [[] for _ in steps]
    for sp in spans:
        k = bisect.bisect_right(begins, sp.t0) - 1
        if k >= 0 and sp.t1 <= steps[k]["t_end"]:
            out[k].append(sp)
    return out


def clock_offset(steps: Sequence[Dict], host_spans: Sequence[trace.Event],
                 tolerance_ns: float = PAIRING_TOLERANCE_NS
                 ) -> Optional[Tuple[float, float]]:
    """(offset, residual) in ns: ``t_begin * 1e9 + offset`` is the profiler's
    time of a step's begin, the median over the steps; the residual is the
    spread (largest minus smallest) of the differences. ``None`` if the
    trace has another number of step spans than there are steps, or the
    differences spread by more than the tolerance."""
    marks = sorted(s.start_ns for s in host_spans if s.name == STEP_SPAN)
    if not steps or len(marks) != len(steps):
        return None
    diffs = [m - s["t_begin"] * 1e9 for m, s in zip(marks, steps)]
    residual = max(diffs) - min(diffs)
    if residual > tolerance_ns:
        return None
    return statistics.median(diffs), residual


def _busy(events: Sequence[trace.Event]) -> Dict[str, List[Tuple[float,
                                                                 float]]]:
    """plane -> the union of its device operations, as sorted intervals."""
    planes: Dict[str, List[Tuple[float, float]]] = {}
    for e in events:
        planes.setdefault(e.plane, []).append(
            (e.start_ns, e.start_ns + e.duration_ns))
    return {p: trace._union(iv) for p, iv in planes.items()}


def _idle(busy: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """[lo, hi) without the sorted, disjoint ``busy`` intervals."""
    out, at = [], lo
    for a, b in busy[max(0, bisect.bisect_left(busy, (lo, lo)) - 1):]:
        if a >= hi:
            break
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


def idle_by_phase(step_spans: Sequence[Tuple[float, float]],
                  phases: Sequence[Sequence[Tuple[str, float, float]]],
                  events: Sequence[trace.Event]) -> List[Dict[str, float]]:
    """For each step, the device's idle nanoseconds under each phase.

    ``step_spans`` are the steps' (start, end) on the profiler's clock,
    ``phases`` each step's (name, start, end) on the same clock. A step's
    stretch runs from its start to the next step's start (the last: to its
    own end), so the stretches tile the traced slice. Idle time is the
    stretch without the union of the device operations (the mean over the
    device planes); each idle interval goes to the phases it overlaps, by
    overlap, and what no phase covers to ``BETWEEN``."""
    busy = _busy(events)
    out = []
    for k, (start, end) in enumerate(step_spans):
        hi = step_spans[k + 1][0] if k + 1 < len(step_spans) else end
        got: Dict[str, float] = {}
        for merged in busy.values():
            for lo_i, hi_i in _idle(merged, start, hi):
                left = hi_i - lo_i
                for name, p0, p1 in phases[k]:
                    over = min(hi_i, p1) - max(lo_i, p0)
                    if over > 0:
                        got[name] = got.get(name, 0.0) + over
                        left -= over
                got[BETWEEN] = got.get(BETWEEN, 0.0) + max(left, 0.0)
        out.append({n: v / max(len(busy), 1) for n, v in got.items()})
    return out


# -- one traced serving run ---------------------------------------------------

class Analysis(NamedTuple):
    phases: List[Dict[str, float]]       # per traced step: phase -> ms
    launches: List[int]                  # per traced step
    idle: Optional[List[Dict[str, float]]]   # per traced step: phase -> ms
    clock_residual_us: Optional[float]


def analyse(run) -> Analysis:
    """The traced steps of a serving run through the program's spans; once
    a run (kept on it), and printed once as an earlier line of its output."""
    done = getattr(run, "_program_spans", None)
    if done is not None:
        return done
    steps = run.traced_steps
    spans = (read("serving.step", steps[0]["t_begin"], steps[-1]["t_end"])
             if steps else [])
    per_step = by_step(spans, steps)
    # per step: (phase, t0, t1) in seconds, and the serving.step span's count
    timed = [[(s.name[len(PHASE_PREFIX):], s.t0, s.t1) for s in got
              if s.name.startswith(PHASE_PREFIX)] for got in per_step]
    phases = [{n: (t1 - t0) * 1e3 for n, t0, t1 in got} for got in timed]
    launches = [s.attrs["launches"] for got in per_step for s in got
                if s.name == "serving.step" and "launches" in s.attrs]
    idle = residual = None
    reduced = run.reduced
    paired = (clock_offset(steps, reduced.spans)
              if reduced is not None and reduced.events else None)
    if paired is not None and any(phases):
        offset, residual = paired
        marks = sorted((s.start_ns, s.start_ns + s.duration_ns)
                       for s in reduced.spans if s.name == STEP_SPAN)
        on_trace = [[(n, t0 * 1e9 + offset, t1 * 1e9 + offset)
                     for n, t0, t1 in got] for got in timed]
        idle = [{n: v / 1e6 for n, v in step.items()}
                for step in idle_by_phase(marks, on_trace, reduced.events)]
    done = run._program_spans = Analysis(
        phases, launches, idle, None if residual is None else residual / 1e3)
    if any(phases):
        print(json.dumps({"event": "program_spans", **summary(run, done)}),
              flush=True)
    return done


def _mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def summary(run, a: Analysis) -> Dict:
    """The serving step's budget in the traced steps: each phase's share of
    the host's step, and the device's idle time under each."""
    whole = [p for p in a.phases if set(p) >= set(PHASES)]
    step_ms = [(s["t_end"] - s["t_begin"]) * 1e3 for s in run.traced_steps]
    out = {"traced_steps": len(run.traced_steps), "steps_read": len(whole),
           "step_ms_mean": _mean(step_ms),
           "phase_ms_median": {n: statistics.median(p[n] for p in whole)
                               for n in PHASES if whole},
           "phase_ms_mean": {n: _mean([p[n] for p in whole])
                             for n in PHASES if whole},
           "launches_median": (statistics.median(a.launches)
                               if a.launches else None),
           "clock_residual_us": a.clock_residual_us}
    if whole and step_ms:
        out["phases_over_step"] = (sum(sum(p[n] for n in PHASES)
                                       for p in whole) / sum(step_ms))
    if a.idle is not None:
        names = PHASES + (BETWEEN,)
        out["idle_ms_mean"] = {n: _mean([i.get(n, 0.0) for i in a.idle])
                               for n in names}
        out["idle_ms_mean_total"] = _mean([sum(i.values()) for i in a.idle])
        r = run.reduced
        out["idle_ms_by_share"] = ((r.window_s - r.busy_s) * 1e3
                                   / len(a.idle))
    return out


def host_ms(run, phases: Sequence[str]) -> Optional[float]:
    """Median over the traced steps of the host's time in ``phases``."""
    per_step = [sum(p[n] for n in phases) for p in analyse(run).phases
                if all(n in p for n in phases)]
    return statistics.median(per_step) if per_step else None


def idle_ms(run, phases: Sequence[str]) -> Optional[float]:
    """Mean over the traced steps of the device's idle time under
    ``phases``."""
    idle = analyse(run).idle
    if not idle:
        return None
    return _mean([sum(i.get(n, 0.0) for n in phases) for i in idle])
