"""What both drivers need: the run's context, the configuration's family,
the compile counter, the heartbeat, the tracer's slice."""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .. import trace

# a new program was lowered, or the backend compiled one: neither may
# happen inside a measured window
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass
class Context:
    """One run of one cell, as ``run.py`` hands it to a driver."""
    cell_name: str
    cell: Dict
    config: Dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_process: float            # time.perf_counter() when the process began
    scratch: str                # a directory inside the checkout
    device_kind: str
    heartbeat: bool = True      # False: no heartbeat thread in the window
    control: bool = False       # True: the check also reads its control

    def emit(self, event: str, **fields: Any) -> None:
        """An earlier line of standard output; never the last."""
        print(json.dumps({"event": event, **fields}), flush=True)


@dataclasses.dataclass
class Result:
    """What a driver hands back. ``end_to_end`` maps a metric's name to
    (value, unit); the remaining fields are what per-layer readers read."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, tuple]
    steps: List[Dict] = dataclasses.field(default_factory=list)
    traced_steps: List[Dict] = dataclasses.field(default_factory=list)
    requests: List[Dict] = dataclasses.field(default_factory=list)
    first_steps: List[int] = dataclasses.field(default_factory=list)
    reduced: Optional[trace.Reduced] = None
    config: Dict = dataclasses.field(default_factory=dict)
    cell: Dict = dataclasses.field(default_factory=dict)
    device_kind: str = ""
    # what ``correct`` compared: name -> [number, limit]
    compared: Dict[str, list] = dataclasses.field(default_factory=dict)
    # read by the driver once its window has closed, before any reference
    # of the check runs on the device and raises it
    memory_peak_bytes: int = 0


class CompileCounter:
    """Counts lowerings and backend compilations while ``armed``."""

    def __init__(self):
        import jax
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in _COMPILE_EVENTS:
            self.count += 1


class Heartbeat:
    """Says when the machine stood still. A daemon thread sleeps ``period_s``
    at a time from ``start()`` to ``stop()`` and keeps every wake-up that
    came more than ``late_s`` after it was due: a stop. It touches no metric
    and no ``correct``; the notes line carries what it found, so that a
    reader of a set of runs can tell which of them the machine spoiled. The
    clock and the sleep are arguments so that a test can make a stop."""

    def __init__(self, period_s: float = 0.005, late_s: float = 0.05,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self.period_s, self.late_s = period_s, late_s
        self._clock, self._sleep = clock, sleep
        self.stops_ms: List[float] = []
        self.beats = 0
        self.done = False
        self._thread: Optional[threading.Thread] = None

    def beat(self) -> None:
        """Sleeps and wakes until ``done``; the thread's whole work."""
        while not self.done:
            asleep = self._clock()
            self._sleep(self.period_s)
            late = self._clock() - asleep - self.period_s
            self.beats += 1
            if late > self.late_s:
                self.stops_ms.append(late * 1e3)

    def start(self, on: bool = True) -> "Heartbeat":
        """Starts the thread; with ``on`` false it starts nothing, and
        ``stop()`` then reports no beat."""
        if not on:
            return self
        self._thread = threading.Thread(target=self.beat, daemon=True,
                                        name="chipbench-heartbeat")
        self._thread.start()
        return self

    def stop(self) -> Dict[str, float]:
        """Ends the thread and returns the notes: how many stops over
        ``late_s``, the longest and their sum (0 where there was none)."""
        self.done = True
        if self._thread is not None:
            self._thread.join()
        return {"stops_over_50ms": len(self.stops_ms),
                "stop_longest_ms": max(self.stops_ms, default=0.0),
                "stop_sum_ms": sum(self.stops_ms, 0.0),
                "heartbeats": self.beats}


class Slice:
    """The traced slice of a window: its last ``length_s`` seconds. The
    profiler starts on a step boundary inside the window and is stopped
    after it, so that writing the trace costs the window nothing."""

    def __init__(self, ctx: Context, window_start: float, length_s: float):
        self.on = ctx.trace
        self.dir = os.path.join(ctx.scratch, f"trace-{ctx.cell_name}")
        self.begin_at = window_start + max(0.0, ctx.seconds - length_s)
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None

    def due(self, now: float) -> bool:
        """The next ``tick(now)`` starts the profiler."""
        return self.on and self.t0 is None and now >= self.begin_at

    def tick(self, now: float) -> None:
        if self.due(now):
            shutil.rmtree(self.dir, ignore_errors=True)
            trace.start(self.dir)
            self.t0 = time.perf_counter()

    def finish(self) -> None:
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            trace.stop()

    def covers(self, t_begin: float, t_end: float) -> bool:
        return (self.t0 is not None and self.t1 is not None
                and t_begin >= self.t0 and t_end <= self.t1)

    def reduce(self, top_span: str) -> Optional[trace.Reduced]:
        """Reads the slice back and deletes the trace files."""
        if self.t1 is None:
            return None
        path = trace.find_xplane(self.dir)
        if path is None:
            return None
        reduced = trace.reduce(trace.read_xplane(path), top_span)
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


def memory_peak_bytes(chips: int) -> int:
    """The peak on the fullest of the cell's chips, as JAX reports it."""
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:chips])


def sized(section: Dict, rehearse: bool) -> Dict:
    """A cell's or a configuration's values, with the ``rehearse`` group laid
    over them for a CPU rehearsal (one level deep)."""
    out = {k: v for k, v in section.items() if k != "rehearse"}
    if rehearse:
        for key, val in section.get("rehearse", {}).items():
            out[key] = ({**out.get(key, {}), **val}
                        if isinstance(val, dict) else val)
    return out


def model_sizes(config: Dict) -> Dict:
    """The published keys of a configuration file, with the rehearsal's
    ``model`` group (tiny widths, CPU only) laid over them if it is there."""
    return {**{k: v for k, v in config.items()
               if not isinstance(v, dict)}, **config.get("model", {})}


def family(config: Dict):
    """The module that builds a configuration's model and points at its
    plain reference: ``families/<family>.py``, ``llama`` where the
    configuration names none."""
    return importlib.import_module(
        "chipbench.families." + config.get("family", "llama"))


def quantile(values: List[float], q: float) -> float:
    """Linear-interpolated quantile, q in [0, 1]."""
    if not values:
        raise ValueError("quantile of nothing")
    s = sorted(values)
    x = q * (len(s) - 1)
    lo = int(x)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (x - lo)
