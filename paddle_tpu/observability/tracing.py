"""End-to-end tracing: one trace id from the fleet router to the compiled step.

Metrics (:mod:`.metrics`) aggregate and the profiler
(:mod:`paddle_tpu.profiler`) only records inside an opt-in window on one
process — neither can answer "where did THIS request's latency go?"
across the router → replica → engine → kernel path. This module is the
always-on, near-zero-cost third leg:

* a **span** is ``(trace_id, span_id, parent_id, name, t0..t1, events,
  attrs)``; completed spans land in a bounded per-process ring (the
  flight-recorder discipline: one slot assignment, lock-free under the
  GIL), gated by ``FLAGS_tracing`` resolved to ONE flag read;
* the ambient trace context propagates through **contextvars** — a span
  opened inside another becomes its child with zero plumbing, across
  threads only when explicitly carried (:func:`activate`);
* **cross-process** propagation is explicit and tiny: :func:`inject`
  serializes the ambient context into two hex words the fleet's
  JSON-lines submit frame carries; :func:`extract` + :func:`activate`
  re-establish it in the worker, so one ``trace_id`` spans the router
  process and every replica that ever served the request (failover
  re-submissions re-activate the ORIGINAL context — the replayed
  request keeps its trace);
* export is **Chrome-trace JSON** (:func:`dump_trace` — load in
  ``chrome://tracing`` / Perfetto), merged into the profiler's chrome
  trace when a window is open (:func:`set_span_sink`) and dumped next
  to the flight recorder on uncaught exception (:func:`_crash_dump`,
  chained by ``flight_recorder.install_excepthook``);
* a **live** span (:func:`span`, :func:`start_span`) also holds a
  ``jax.profiler.TraceAnnotation`` of its name open for its duration, so
  whenever a ``jax.profiler`` trace is running the program's spans are
  in the xplane on the host thread's line, on the device trace's clock
  (a no-op in C++ otherwise). Retroactive spans and instants stay
  ring-only; :func:`finished_spans` reads the ring back;
* the **device's** work is named by the compiled programs themselves:
  ``nn.Layer.__call__`` and ``TrainStep`` trace under ``jax.named_scope``,
  so every HLO instruction's ``op_name`` carries its module path, and
  whoever makes an executable hands it to :func:`note_program` once.
  :func:`device_ops` reads the noted programs' text back as one record an
  instruction, which a reader of a device profile joins to the profile's
  events by instruction name (``chipbench/device_scopes.py`` does).

The span-name taxonomy is FROZEN (:data:`SPAN_NAMES`) exactly like
``metrics.METRIC_NAMES``: a typo'd name would silently fork the
timeline grouping dashboards and tests key on. Runtime validation
rejects unregistered names; the graftcheck ``spans`` rule is the static
half. Adding a span = adding its name here first.

Span phases for one served request (TTFT = queue + compile + kernel)::

    fleet.submit ─ serving.admit ─ serving.journal_fsync   (ack point)
                   serving.queue      arrival -> row-slot admission
                   serving.prefill    admission -> first token
                   serving.decode     first token -> finish
    serving.step                      one ragged engine step (kernel time)
    serving.step.admit .schedule .pack .dispatch .sync .commit
                                      the host's phases of one step()
    jit.compile                       XLA compiles, parented if ambient
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import json
import os
import re
import sys
import threading
import time
from typing import Any, Dict, IO, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .. import flags as _flags
from . import metrics as _metrics

__all__ = [
    "SPAN_NAMES", "Span", "span", "start_span", "record_span", "instant",
    "event", "activate", "deactivate", "current", "current_trace_id",
    "inject", "extract", "enabled", "now_ns", "dump_trace", "to_chrome",
    "set_span_sink", "clear", "active_spans", "finished_spans",
    "FinishedSpan", "UNTRACED", "note_program", "device_ops", "DeviceOp",
]

# one-attribute-read disabled path, same discipline as _F_METRICS
_F_TRACING = _flags._REGISTRY["tracing"]

_M_SPANS = _metrics.registry().counter(
    "tracing.spans", help="completed spans recorded into the tracing ring")
_M_EVENTS = _metrics.registry().counter(
    "tracing.events", help="span events + instants recorded")


# The framework's frozen span taxonomy: every span and span-event name
# paddle_tpu itself records. The graftcheck `spans` rule statically
# checks each literal name at span()/start_span()/record_span()/
# instant()/event() call sites against this set; runtime validation
# below is the dynamic half. USER code may trace any name it likes —
# this set governs framework sources only.
SPAN_NAMES = frozenset({
    # serving/fleet/router.py — one request through the fleet
    "fleet.submit",            # span: submit -> durable ack on a replica
    "fleet.queue_full",        # event: a candidate refused admission
    "fleet.retry",             # event: all candidates full -> backoff round
    "fleet.shed",              # event: FleetShed raised (SLO / deadline)
    "fleet.replica_dead",      # event: READY->DEAD transition observed
    "fleet.failover",          # event: victim request settled from the log
    "fleet.handoff",           # event: parked request re-placed on survivor
    "fleet.drain",             # event: rolling-drain step
    "fleet.restart",           # event: replica restart initiated
    # serving/resilience/ — durability edges
    "serving.admit",           # span: admission incl. the durable journal ack
    "serving.journal_fsync",   # span: journal flush (tmp+fsync+rename)
    "serving.recover",         # span: journal load + replay re-admission
    "serving.drain",           # span: finish-or-journal-and-preempt drain
    "serving.step_hang",       # event: watchdog fired on a wedged step
    # models/serving.py — the ragged engine's per-request phases
    "serving.step",            # span: ONE ragged mixed prefill+decode step
    #                            (retro: dispatch + sync, attrs launches,
    #                            overlapped: launched behind one in flight)
    "serving.step.admit",      # span: _admit, preemption check, gauges
    "serving.step.schedule",   # span: decode/prefill rows, grants, drafts
    "serving.step.pack",       # span: the step's numpy arrays and cu
    "serving.step.dispatch",   # span: uploads, model, gather, sample:
    #                            until the last async launch returns
    "serving.step.sync",       # span: the sampled tokens' transfer
    "serving.step.commit",     # span: the post loop, on_finish, notify
    "serving.queue",           # span (retro): arrival -> row-slot admission
    "serving.prefill",         # span (retro): slot admission -> first token
    "serving.decode",          # span (retro): first token -> finish
    "serving.first_token",     # event: the TTFT edge
    "serving.finish",          # event: request finished
    "serving.preempt",         # event: LIFO preemption victim
    # jit/api.py — TrainStep's host side of one call
    "train.step",              # span (retro): args + launch (attrs tokens,
    #                            compiled: the call traced or compiled)
    "train.step.args",         # span: build, re-sync, lr, the argument tuple
    "train.step.launch",       # span: the jitted call until it returns
    # jit/step_capture.py — the training step
    "step_capture.capture",    # span: trace+lower+compile of a whole step
    "step_capture.replay",     # span: one captured-executable replay
    "step_capture.multi",      # span: one K-step block (capture or replay)
    # optimizer/optimizer.py
    "optimizer.update",        # span: one eager/traced optimizer.step()
    "optimizer.fused_update",  # span: the fused megakernel route's
    #                            bucketed apply inside optimizer.step()
    # distributed/resilience/
    "anomaly.verdict",         # event: non-OK AnomalyDetector verdict
    "checkpoint.snapshot",     # span: foreground device->host snapshot
    "checkpoint.commit",       # span: background serialize+fsync+commit
    # observability/incident.py — forensic bundle assembly
    "observability.incident",  # span: one incident bundle commit
    # this module's jax.monitoring listener
    "jit.compile",             # span (retro): one XLA backend compile
    # jit/exec_store.py — the persistent executable cache
    "jit.cache.load",          # span: deserialize one cached executable
})

_EVENTS_MAX = 256             # per-span event cap (rings bound everything else)

# the explicit carrier of a span that belongs to no request's trace
UNTRACED = (0, 0)

now_ns = time.perf_counter_ns

# ambient (trace_id, span_id) — None outside any activated span
_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_trace", default=None)

# span ids: per-process random base + GIL-atomic counter — unique within
# a trace even when the parent process and a worker share it
_SID_BASE = int.from_bytes(os.urandom(8), "big") & ((1 << 63) - 1)
_SID_SEQ = itertools.count(1)

# live (unfinished) spans for the crash dump; plain dict ops are atomic
# under the GIL, so no lock on the span hot path
_ACTIVE: Dict[int, "Span"] = {}

# optional sink for completed spans (the profiler merges them into its
# chrome trace while a record window is open)
_SINK = None


def enabled() -> bool:
    return bool(_F_TRACING.value)


def _new_trace_id() -> int:
    tid = int.from_bytes(os.urandom(8), "big") & ((1 << 63) - 1)
    return tid or 1            # 0 means "untraced" everywhere


def _new_span_id() -> int:
    return (_SID_BASE + next(_SID_SEQ)) & ((1 << 63) - 1)


def _check_name(name: str) -> None:
    if name not in SPAN_NAMES:
        raise ValueError(
            f"unregistered span name {name!r} — add it to "
            f"observability.tracing.SPAN_NAMES (frozen so timelines and "
            f"dashboards cannot fork)")


class Span:
    """One traced interval. Context-manager or explicit :meth:`end` —
    the explicit form serves cross-step phases a caller holds open (a
    request's life is not one stack frame). ``kind`` is ``"span"`` or
    ``"instant"`` (zero-duration point records share the ring)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t0_ns",
                 "t1_ns", "tid", "attrs", "events", "kind", "_token",
                 "_ended", "_ann")

    def __init__(self, name: str, trace_id: int, parent_id: int,
                 attrs: Optional[Dict[str, Any]] = None,
                 t0_ns: Optional[int] = None, kind: str = "span"):
        _check_name(name)
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.t0_ns = now_ns() if t0_ns is None else t0_ns
        self.t1_ns: Optional[int] = None
        self.tid = threading.get_ident()
        self.attrs = attrs
        self.events: Optional[List[tuple]] = None
        self.kind = kind
        self._token = None
        self._ended = False
        self._ann = None           # the profiler annotation of a live span

    # -- context --------------------------------------------------------------
    @property
    def context(self) -> Tuple[int, int]:
        """(trace_id, span_id) — what a child would inherit."""
        return (self.trace_id, self.span_id)

    def set(self, **attrs: Any) -> "Span":
        """Attach/overwrite attributes (rendered as chrome ``args``)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        return self

    def event(self, name: str, **attrs: Any) -> None:
        """Timestamped point annotation on THIS span (chrome ``"i"``)."""
        _check_name(name)
        evs = self.events
        if evs is None:
            evs = self.events = []
        if len(evs) < _EVENTS_MAX:
            evs.append((now_ns(), name, attrs or None))
            _M_EVENTS.inc()

    # -- lifecycle ------------------------------------------------------------
    def end(self) -> None:
        if self._ended:
            return
        self._ended = True
        if self._ann is not None:      # left first: it lies inside [t0, t1]
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.t1_ns = now_ns()
        _ACTIVE.pop(self.span_id, None)
        if self._token is not None:
            _CTX.reset(self._token)
            self._token = None
        _ring().append(self)
        _M_SPANS.inc()
        sink = _SINK
        if sink is not None:
            try:
                sink(self)
            except Exception:
                pass       # a profiler-side bug must not break the traced path

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


class _NoopSpan:
    """The disabled path: every API returns this singleton; every method
    is a no-op, so a gated-off span costs one flag read + one call."""

    __slots__ = ()
    name = ""
    trace_id = 0
    span_id = 0
    parent_id = 0
    context = (0, 0)
    t0_ns = t1_ns = None       # the disabled path reads no clock

    def set(self, **attrs):
        return self

    def event(self, name, **attrs):
        pass

    def end(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


# -- the bounded ring ---------------------------------------------------------

class _Ring:
    """Fixed-capacity ring of finished spans/instants — the flight
    recorder's lock-free discipline (one slot assignment per append)."""

    __slots__ = ("_ring", "_i")

    def __init__(self, capacity: int):
        self._ring: List[Optional[Span]] = [None] * max(1, int(capacity))
        self._i = 0

    def append(self, sp: Span) -> None:
        i = self._i
        self._i = i + 1
        ring = self._ring
        ring[i % len(ring)] = sp

    def entries(self) -> List[Span]:
        return sorted((e for e in self._ring if e is not None),
                      key=lambda s: s.t0_ns)

    def clear(self) -> None:
        self._ring = [None] * len(self._ring)
        self._i = 0

    @property
    def total(self) -> int:
        return self._i


_RING: Optional[_Ring] = None
_RING_LOCK = threading.Lock()


def _ring() -> _Ring:
    global _RING
    r = _RING
    if r is None:
        with _RING_LOCK:
            r = _RING
            if r is None:
                r = _RING = _Ring(int(_flags.get_flag("tracing_ring_size")))
    return r


def _on_ring_size(value) -> None:
    # swap wholesale: unlike the flight recorder nobody holds a direct
    # reference to the ring object, so replacement (keeping the newest
    # entries) is simpler than in-place surgery
    global _RING
    old = _RING
    if old is None:
        return
    fresh = _Ring(int(value))
    for sp in old.entries()[-max(1, int(value)):]:
        fresh.append(sp)
    _RING = fresh


_flags.on_set("tracing_ring_size", _on_ring_size)


def clear() -> None:
    """Drop every recorded span and instant and every noted program
    (test/bench hygiene)."""
    if _RING is not None:
        _RING.clear()
    _ACTIVE.clear()
    _PROGRAMS.clear()


def active_spans() -> List[Span]:
    """Live (started, not ended) spans — what a crash dump adds."""
    return sorted(_ACTIVE.values(), key=lambda s: s.t0_ns)


class FinishedSpan(NamedTuple):
    """What a reader of the ring gets: stamps are ``perf_counter_ns``."""
    name: str
    t0_ns: int
    t1_ns: int
    attrs: Dict[str, Any]


def finished_spans(prefix: str = "", since_ns: Optional[int] = None
                   ) -> List[FinishedSpan]:
    """The ring's finished spans (no instants) by start time, optionally
    only names starting with ``prefix`` and spans that start at or after
    ``since_ns``. The ring is bounded (``FLAGS_tracing_ring_size``): the
    oldest are gone first."""
    if _RING is None:
        return []
    return [FinishedSpan(sp.name, sp.t0_ns, sp.t1_ns, sp.attrs or {})
            for sp in _RING.entries()
            if sp.kind == "span" and sp.name.startswith(prefix)
            and (since_ns is None or sp.t0_ns >= since_ns)]


# -- the compiled programs' own names for the device's work -------------------

_PROGRAMS_MAX = 16            # the newest programs; the oldest are dropped


class DeviceOp(NamedTuple):
    """One instruction of a noted program's optimized HLO, as its text has
    it. ``kernel`` is the Pallas call's ``name`` for a ``tpu_custom_call``
    (else ""); ``has_matmul`` says that the instruction is, or its fused
    computation contains, a ``dot`` or a ``convolution``;
    ``fused_op_names`` are the distinct ``op_name`` s inside the
    computations it calls (a fusion has one ``op_name`` of its own, its
    root's, whatever else XLA fused into it)."""
    program: str
    instruction: str
    result_type: str
    opcode: str
    op_name: str
    kernel: str
    has_matmul: bool
    fused_op_names: Tuple[str, ...] = ()


class _Program:
    __slots__ = ("name", "source", "ops")

    def __init__(self, name: str, source):
        self.name = name
        self.source = source       # dropped once its text has been read
        self.ops: Optional[List[DeviceOp]] = None


_PROGRAMS: "collections.deque[_Program]" = collections.deque(
    maxlen=_PROGRAMS_MAX)


def note_program(name: str, compiled_or_thunk) -> None:
    """Remember an executable for :func:`device_ops`: a ``Compiled``
    (anything with ``as_text()``) or a thunk that returns one. Called once
    where an executable is made, never on a step's path; nothing is read or
    parsed here. The newest ``_PROGRAMS_MAX`` are kept, each with whatever
    it holds (an executable; a thunk's closure) until :func:`device_ops`
    has read it or :func:`clear` is called."""
    if not _F_TRACING.value:
        return
    _PROGRAMS.append(_Program(name, compiled_or_thunk))


_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(]+)\s+=\s+(.*)$")
_HLO_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_HLO_CALLS = re.compile(r"calls=%?([^\s,}]+)")
_MATMULS = ("dot", "convolution")


def _parse_hlo(program: str, text: str) -> List[DeviceOp]:
    """Every instruction of every computation of an HLO module's text."""
    rows = []                  # name, type, opcode, op_name, kernel, calls
    matmul_in: Dict[str, bool] = {}      # computation -> has a dot of its own
    names_in: Dict[str, set] = {}        # computation -> its own op_names
    calls_of: Dict[str, List[str]] = {}  # computation -> computations called
    computation = ""
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            head = line.strip()
            if head.endswith("{") and not head.startswith("HloModule"):
                computation = head.split()[1 if head.startswith("ENTRY")
                                           else 0].lstrip("%")
            continue
        name, rest = m.groups()
        op = _HLO_OPCODE.search(" " + rest)
        if op is None:
            continue
        result_type = (" " + rest)[:op.start()].strip()
        opcode = op.group(1)
        found = _HLO_OP_NAME.search(rest)
        op_name = found.group(1) if found else ""
        called = _HLO_CALLS.findall(rest)
        kernel = (re.sub(r"\.\d+$", "", name)
                  if opcode == "custom-call" and "tpu_custom_call" in rest
                  else "")
        rows.append((name, result_type, opcode, op_name, kernel, called))
        if opcode in _MATMULS:
            matmul_in[computation] = True
        if op_name:
            names_in.setdefault(computation, set()).add(op_name)
        calls_of.setdefault(computation, []).extend(called)

    inside: Dict[str, Tuple[bool, frozenset]] = {}

    def within(comp: str) -> Tuple[bool, frozenset]:
        """(holds a matmul, the op_names) of a computation and all it calls."""
        if comp not in inside:
            parts = [within(c) for c in calls_of.get(comp, ())]
            inside[comp] = (
                matmul_in.get(comp, False) or any(m for m, _ in parts),
                frozenset(names_in.get(comp, ())).union(
                    *(n for _, n in parts)))
        return inside[comp]

    out = []
    for name, result_type, opcode, op_name, kernel, called in rows:
        parts = [within(c) for c in called]
        out.append(DeviceOp(
            program, name, result_type, opcode, op_name, kernel,
            opcode in _MATMULS or any(m for m, _ in parts),
            tuple(sorted(frozenset().union(*(n for _, n in parts))))))
    return out


def device_ops() -> List[DeviceOp]:
    """One record an instruction of every noted program, oldest program
    first. A program's text is taken and parsed on the first read and kept;
    one whose text cannot be had (its thunk or ``as_text`` raised) gives no
    record. Raw facts: grouping by scope, phase or kind is the reader's."""
    out: List[DeviceOp] = []
    for prog in list(_PROGRAMS):
        if prog.ops is None:
            source, prog.source = prog.source, None
            try:
                if not hasattr(source, "as_text"):
                    source = source()
                prog.ops = _parse_hlo(prog.name, source.as_text())
            except Exception:
                prog.ops = []  # a reader's fault must not break the run
        out.extend(prog.ops)
    return out


# -- span creation ------------------------------------------------------------

def _parent(trace) -> Tuple[int, int]:
    """(trace_id, parent_span_id) from an explicit carrier or ambient."""
    if trace is not None:
        return int(trace[0]), int(trace[1])
    ctx = _CTX.get()
    if ctx is not None:
        return ctx
    return (_new_trace_id(), 0)


def _open(name: str, trace, attrs) -> Span:
    """A live span: registered for the crash dump and, while a
    ``jax.profiler`` trace runs, an annotation of the same name on the
    host thread's line of the xplane, entered last so that it lies inside
    the ring's [t0, t1]."""
    tid, parent = _parent(trace)
    sp = Span(name, tid, parent, attrs)
    _ACTIVE[sp.span_id] = sp
    sp._ann = _TraceAnnotation(name)
    sp._ann.__enter__()
    return sp


def span(name: str, *, trace=None, attrs=None):
    """Open an ACTIVATED span: it becomes the ambient context (children
    opened inside — same thread, or via an awaited contextvars copy —
    parent onto it) until :meth:`Span.end` restores the previous one.
    Use as a context manager. ``trace`` overrides the ambient parent
    with an explicit ``(trace_id, span_id)`` carrier."""
    if not _F_TRACING.value:
        return _NOOP
    sp = _open(name, trace, attrs)
    sp._token = _CTX.set(sp.context)
    return sp


def start_span(name: str, *, trace=None, attrs=None):
    """Open a NON-activating span (no contextvar mutation): for phases a
    caller holds across steps/threads and ends explicitly, and, as a
    context manager with ``trace=UNTRACED``, for the phases of a step
    that serves many requests."""
    if not _F_TRACING.value:
        return _NOOP
    return _open(name, trace, attrs)


def record_span(name: str, t0_ns: int, t1_ns: int, *, trace=None,
                attrs=None) -> None:
    """Record a RETROACTIVE span from explicit perf_counter_ns stamps —
    for phases whose edges were observed before their duration was known
    (queue wait, prefill->first-token, a jax.monitoring compile
    duration). ``trace=None`` means untraced (trace_id 0), NOT the
    ambient — phase segments always name their request explicitly."""
    if not _F_TRACING.value:
        return
    tid, parent = (int(trace[0]), int(trace[1])) if trace is not None \
        else (0, 0)
    sp = Span(name, tid, parent, attrs, t0_ns=t0_ns)
    sp.t1_ns = t1_ns
    sp._ended = True
    _ring().append(sp)
    _M_SPANS.inc()
    sink = _SINK
    if sink is not None:
        try:
            sink(sp)
        except Exception:
            pass  # a profiler-side bug must not break the traced path


def instant(name: str, *, trace=None, attrs=None) -> None:
    """Record a point event straight into the ring (chrome ``"i"``) —
    for decisions with no natural open span (a failover settling a
    request whose submit span closed long ago). ``trace=None`` attaches
    to the ambient context if any, else records untraced."""
    if not _F_TRACING.value:
        return
    if trace is not None:
        tid, parent = int(trace[0]), int(trace[1])
    else:
        ctx = _CTX.get()
        tid, parent = ctx if ctx is not None else (0, 0)
    sp = Span(name, tid, parent, attrs, kind="instant")
    sp.t1_ns = sp.t0_ns
    sp._ended = True
    _ring().append(sp)
    _M_EVENTS.inc()


def event(name: str, **attrs: Any) -> None:
    """Annotate the ambient ACTIVE span (falls back to an untraced
    instant when no span is active)."""
    if not _F_TRACING.value:
        return
    ctx = _CTX.get()
    if ctx is not None:
        sp = _ACTIVE.get(ctx[1])
        if sp is not None:
            sp.event(name, **attrs)
            return
    instant(name, attrs=attrs or None)


# -- propagation --------------------------------------------------------------

def current() -> Optional[Tuple[int, int]]:
    """The ambient (trace_id, span_id), or None."""
    return _CTX.get()


def current_trace_id() -> int:
    ctx = _CTX.get()
    return ctx[0] if ctx is not None else 0


def activate(trace) -> Optional[contextvars.Token]:
    """Make an explicit (trace_id, span_id) carrier the ambient context;
    returns a token for :func:`deactivate`. The worker side of
    cross-process/cross-thread propagation."""
    if not _F_TRACING.value or trace is None:
        return None
    return _CTX.set((int(trace[0]), int(trace[1])))


def deactivate(token: Optional[contextvars.Token]) -> None:
    if token is not None:
        _CTX.reset(token)


def inject() -> Optional[List[str]]:
    """The ambient context as two hex words for a wire frame (the fleet
    submit op's ``"tc"`` field); None when untraced/disabled."""
    if not _F_TRACING.value:
        return None
    ctx = _CTX.get()
    if ctx is None:
        return None
    return [f"{ctx[0]:016x}", f"{ctx[1]:016x}"]


def extract(carrier) -> Optional[Tuple[int, int]]:
    """Parse :func:`inject`'s wire form back into a carrier tuple."""
    if not carrier:
        return None
    try:
        return (int(carrier[0], 16), int(carrier[1], 16))
    except (ValueError, TypeError, IndexError):
        return None            # a torn/foreign frame must not kill serving


# -- profiler merge -----------------------------------------------------------

def set_span_sink(fn) -> None:
    """Install/remove (None) a callable receiving every completed Span.
    The profiler sets one while a record window is open, so spans land
    in its chrome trace alongside op/host events."""
    global _SINK
    _SINK = fn


# -- jax compile visibility ---------------------------------------------------

def _on_jax_event(event_name: str, duration_secs: float, **kwargs) -> None:
    if event_name.endswith("backend_compile_duration") and _F_TRACING.value:
        t1 = now_ns()
        ctx = _CTX.get()
        record_span("jit.compile", t1 - int(duration_secs * 1e9), t1,
                    trace=ctx)


def _install_jax_compile_listener() -> None:
    try:   # same guard as metrics: a missing API must never break import
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_jax_event)
    except Exception:
        pass


_install_jax_compile_listener()


# -- export -------------------------------------------------------------------

def _chrome_args(sp: Span) -> Dict[str, Any]:
    args: Dict[str, Any] = {}
    if sp.trace_id:
        args["trace_id"] = f"{sp.trace_id:016x}"
    if sp.kind == "span":
        args["span_id"] = f"{sp.span_id:016x}"
    if sp.parent_id:
        args["parent_id"] = f"{sp.parent_id:016x}"
    if sp.attrs:
        args.update(sp.attrs)
    return args


def to_chrome(extra_spans=()) -> Dict[str, Any]:
    """Ring + active spans as a Chrome-trace dict (``traceEvents`` with
    ``"X"`` duration and ``"i"`` instant phases, µs timestamps — the
    same schema as ``profiler.ProfilerResult.to_chrome_json``)."""
    pid = os.getpid()
    trace: List[Dict[str, Any]] = []
    now = now_ns()
    spans = list(_ring().entries()) if _RING is not None or enabled() else []
    live = active_spans()
    for sp in itertools.chain(spans, live, extra_spans):
        args = _chrome_args(sp)
        if sp.kind == "instant":
            trace.append({"name": sp.name, "ph": "i", "s": "t", "pid": pid,
                          "tid": sp.tid, "ts": sp.t0_ns / 1e3,
                          "cat": "Trace", "args": args})
            continue
        t1 = sp.t1_ns
        if t1 is None:         # still open: clip to now, mark active
            t1 = now
            args["active"] = True
        trace.append({"name": sp.name, "ph": "X", "pid": pid,
                      "tid": sp.tid, "ts": sp.t0_ns / 1e3,
                      "dur": (t1 - sp.t0_ns) / 1e3,
                      "cat": "Trace", "args": args})
        for ts, ev_name, ev_attrs in (sp.events or ()):
            trace.append({"name": ev_name, "ph": "i", "s": "t", "pid": pid,
                          "tid": sp.tid, "ts": ts / 1e3, "cat": "Trace",
                          "args": dict(ev_attrs or {},
                                       trace_id=f"{sp.trace_id:016x}",
                                       parent_id=f"{sp.span_id:016x}")})
    return {"traceEvents": trace, "displayTimeUnit": "ms"}


def dump_trace(file: Optional[Any] = None, indent: Optional[int] = None
               ) -> str:
    """Chrome-trace JSON of everything recorded (plus live spans).
    ``file`` may be a path or a writable; the JSON string is returned
    either way — ``json.loads``-able, loadable in chrome://tracing."""
    s = json.dumps(to_chrome(), indent=indent)
    if isinstance(file, str):
        with open(file, "w") as f:
            f.write(s)
    elif file is not None:
        file.write(s)
    return s


# -- crash dump (chained from flight_recorder._crash_dump) --------------------

def _crash_dump() -> None:
    """On uncaught exception: land the trace next to the flight
    recorder. ``FLAGS_tracing_path`` set → full Chrome-trace JSON there;
    otherwise a short human-readable span listing (active spans + newest
    completed) to stderr — a JSON blob over a traceback helps nobody."""
    if not _F_TRACING.value:
        return
    live = active_spans()
    total = _RING.total if _RING is not None else 0
    if not live and total == 0:
        return
    path = str(_flags.get_flag("tracing_path") or "")
    if path:
        dump_trace(path)
        sys.stderr.write(
            f"[paddle_tpu tracing] dumped {total} spans "
            f"(+{len(live)} active) to {path}\n")
        return
    ents = _ring().entries()[-16:]
    sys.stderr.write(
        f"[paddle_tpu tracing] {len(live)} active spans, "
        f"last {len(ents)} of {total} completed (newest last):\n")
    for sp in ents:
        dur = (sp.t1_ns - sp.t0_ns) / 1e6 if sp.t1_ns is not None else 0.0
        sys.stderr.write(
            f"  trace={sp.trace_id:016x} {sp.kind} {sp.name} "
            f"dur={dur:.3f}ms\n")
    for sp in live:
        sys.stderr.write(
            f"  trace={sp.trace_id:016x} ACTIVE {sp.name} "
            f"started {(now_ns() - sp.t0_ns) / 1e6:.3f}ms ago\n")
    sys.stderr.flush()
