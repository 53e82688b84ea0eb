"""From a profiler trace to busy time, idle gaps by host span, and time by
device operation.

Two halves. ``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler``
writes into plain ``Event`` tuples with nothing but JAX. ``reduce`` is a pure
function over such tuples, so ``chipbench/tests`` checks it on a hand-written
list. Device events are the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane; host spans are the ``chipbench.*`` annotations the drivers put around
their calls into the program, which the profiler records on the same clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "chipbench."
UNATTRIBUTED = "(no chipbench span)"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    duration_ns: float


class Reduced(NamedTuple):
    window_s: float                         # first to last host span
    busy_s: float                           # mean over device planes
    ops: List[Tuple[str, float]]            # short name, seconds; all of them
    idle_gaps: List[Tuple[str, float]]      # host span name, idle seconds
    events: List[Event]                     # device events inside the window
    spans: List[Event]                      # host spans


def start(log_dir: str) -> None:
    """Start tracing device operations and annotations, without the Python
    call tracer (it records half a million events in four seconds of an
    eager serving loop and slows the host it measures)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str) -> List[Event]:
    """Device operations and chipbench host spans of one trace file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != DEVICE_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(HOST_SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


_SHAPE = re.compile(r"\{[^{}]*\}")       # layouts: {1,0:T(8,128)(2,1)S(1)}


def short_name(hlo: str, limit: int = 120) -> str:
    """A stable short name for a device operation's HLO line: the kernel's
    name for a custom call, else the instruction's name without its counter
    and the shape it writes, at most ``limit`` characters."""
    m = re.match(r"^%?([^\s=]+)\s*=\s*(.*)$", hlo)
    if not m:
        return hlo[:limit]
    name = re.sub(r"\.\d+$", "", m.group(1))
    rest = m.group(2)
    if "custom-call(" in rest and "tpu_custom_call" in rest:
        return name[:limit]
    # the result type ends where the opcode begins: "<type> opcode(..."
    head = re.split(r"\s[a-z][a-z0-9\-]*\(", rest, maxsplit=1)[0]
    head = _SHAPE.sub("", head)
    return f"{name} {head}"[:limit]


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(a, b) for a, b in merged]


def _clip(lo: float, hi: float, w0: float, w1: float
          ) -> Optional[Tuple[float, float]]:
    lo, hi = max(lo, w0), min(hi, w1)
    return (lo, hi) if hi > lo else None


def reduce(events: List[Event], top_span: Optional[str] = None) -> Reduced:
    """The window is from the start of the first host span to the end of the
    last (``top_span`` names which spans bound it; default: all). Busy time
    is the union of the device-operation intervals inside it, averaged over
    the device planes. A gap between them goes to the host spans it lies
    under."""
    spans = sorted((e for e in events if not DEVICE_PLANE.match(e.plane)),
                   key=lambda e: e.start_ns)
    ends = [s.start_ns + s.duration_ns for s in spans]
    device = [e for e in events if DEVICE_PLANE.match(e.plane)]
    bounding = [s for s in spans if top_span is None or s.name == top_span]
    if not bounding or not device:
        return Reduced(0.0, 0.0, [], [], [], spans)
    w0 = min(s.start_ns for s in bounding)
    w1 = max(s.start_ns + s.duration_ns for s in bounding)
    inside = [e for e in device
              if _clip(e.start_ns, e.start_ns + e.duration_ns, w0, w1)]
    planes = sorted({e.plane for e in inside})
    busy_ns = 0.0
    gaps: Dict[str, float] = {}
    for plane in planes:
        merged = _union(_clip(e.start_ns, e.start_ns + e.duration_ns, w0, w1)
                        for e in inside if e.plane == plane)
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi > lo:
                _split_by_span(spans, ends, lo, hi, gaps)
    n = max(len(planes), 1)
    ops: Dict[str, float] = {}
    for e in inside:
        key = short_name(e.name)
        ops[key] = ops.get(key, 0.0) + e.duration_ns
    by_time = lambda d: sorted(((k, v / n / 1e9) for k, v in d.items()),
                               key=lambda kv: -kv[1])
    return Reduced((w1 - w0) / 1e9, busy_ns / n / 1e9, by_time(ops),
                   by_time(gaps), inside, spans)


def _split_by_span(spans: List[Event], ends: List[float], lo: float,
                   hi: float, into: Dict[str, float]) -> None:
    """Adds the gap [lo, hi] to the host spans that overlap it, each by its
    overlap, and what no span covers to ``UNATTRIBUTED``. The drivers' spans
    follow one another and do not nest, so ``spans`` sorted by start has its
    ``ends`` sorted too, and the first that can overlap is found by bisection."""
    left = hi - lo
    for s in spans[bisect.bisect_right(ends, lo):]:
        if s.start_ns >= hi:
            break
        c = _clip(s.start_ns, s.start_ns + s.duration_ns, lo, hi)
        if c is not None:
            into[s.name] = into.get(s.name, 0.0) + (c[1] - c[0])
            left -= c[1] - c[0]
    if left > 1e-6:
        into[UNATTRIBUTED] = into.get(UNATTRIBUTED, 0.0) + left


def idle_share_pct(r: Optional[Reduced]) -> Optional[float]:
    """Share of the traced slice in which no operation ran on the device."""
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)


def breakdown(r: Reduced, top: int = 10) -> Dict:
    """The contract's ``breakdown``: at most ``top`` entries a list."""
    return {"device_ops": [[k, v] for k, v in r.ops[:top]],
            "idle_gaps": [[k, v] for k, v in r.idle_gaps[:top]]}
