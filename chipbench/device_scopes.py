"""The device's busy time by the program's own names: module scope, phase of
the training step, kind of operation.

The program traces its modules under ``jax.named_scope``, so every
instruction of a compiled program carries its module path in ``op_name``, and
hands each executable to ``paddle_tpu.observability.tracing.note_program``;
``tracing.device_ops()`` gives one record an instruction (program,
instruction, result type, opcode, ``op_name``, kernel, ``has_matmul``). An
``XLA Ops`` event of the trace carries its instruction's HLO line and no
metadata. ``join`` brings the two together: on (instruction, result type),
and where the pair is not on record on the instruction alone; an event whose
records disagree on what it is, or that has none, is *unnamed* (the per-op
programs that no one notes: the reshape, gather and sampling after a serving
step). An instruction XLA added on its own has a record without an
``op_name``: its kind is known and its scope reads ``(no op_name)``.

``scope_of``, ``phase_of``, ``kind_of``, ``join`` and ``partition`` are pure
functions over tuples, checked in ``chipbench/tests`` on hand-written lists.
``partition`` gives every event the part of its interval that lies in the
window and that no earlier event of its plane covers, so the groups of any
grouping sum to ``Reduced.busy_s``. A program without ``device_ops`` (one
from before it) gives ``None`` here and from every metric that reads this.
"""

from __future__ import annotations

import json
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

from . import trace

UNNAMED = "(unnamed)"
NO_OP_NAME = "(no op_name)"
PHASES = ("forward", "backward", "optimizer", "other")
# opcodes that move data and compute nothing; the asynchronous ones are the
# compiler's prefetches, whose end the core waits for. A loaded executable's
# text prints those as ``async-start`` / ``async-done`` and keeps the name
# (``%slice-done.19 = ... async-done(...)``), so the name decides there
COPIES = ("copy", "transpose", "copy-start", "copy-done", "slice-start",
          "slice-done")
CACHE_WRITE = "serving.cache_write"
TOP_PAIRS = 20
TOP_UNNAMED = 5
TOP_OPS = 10


class Fact(NamedTuple):
    """What a record says its instruction is. ``inside`` are the phases of
    what XLA fused into it beside its own (a weight gradient's product with
    the optimizer's update as its epilogue is ``backward`` with
    ``("optimizer",)`` inside)."""
    scope: str
    phase: str
    kind: str
    has_matmul: bool
    inside: Tuple[str, ...] = ()


# -- op_name -> scope, phase; record -> kind ----------------------------------

_JIT = re.compile(r"jit\([^()]*\)")
_WRAPPER = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\(")


def scope_of(op_name: str) -> str:
    """The module path of an ``op_name``: without ``jit(...)`` components
    (the step's and the per-op ones), JAX's transform wrappers
    (``transpose(jvp(layers))/3`` is ``layers/3``) and the trailing primitive,
    a component of digits collapsed to ``*``."""
    path = _WRAPPER.sub("", _JIT.sub("", op_name)).replace(")", "")
    parts = [p for p in path.split("/") if p][:-1]
    return "/".join("*" if p.isdigit() else p for p in parts)


def phase_of(op_name: str) -> str:
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    if scope_of(op_name).startswith("optimizer."):
        return "optimizer"
    return "other"


def kind_of(record) -> str:
    if record.kernel:
        return "kernel:" + record.kernel
    if record.has_matmul:
        return "matmul"
    if record.opcode in COPIES or (
            record.opcode.startswith("async-")
            and re.sub(r"\.\d+$", "", record.instruction) in COPIES):
        return "copy"
    return "other"


def fact_of(record) -> Fact:
    phase = phase_of(record.op_name)
    inside = {phase_of(n) for n in getattr(record, "fused_op_names", ())}
    return Fact(scope_of(record.op_name) if record.op_name else NO_OP_NAME,
                phase, kind_of(record), bool(record.has_matmul),
                tuple(sorted(inside - {phase, "other"})))


# -- events to records ---------------------------------------------------------

_EVENT = re.compile(r"^%?([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s[a-z][a-z0-9\-]*\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def normal_type(result_type: str) -> str:
    """A result type without layouts and spaces: the trace and the
    program's text print the same shapes and may differ in those."""
    return re.sub(r"\s+", "", _LAYOUT.sub("", result_type))


def parse_event(name: str) -> Tuple[str, Optional[str]]:
    """(instruction, normal result type) of an ``XLA Ops`` event's name, its
    HLO line; (name, None) for a bare instruction name."""
    m = _EVENT.match(name)
    if m is None:
        return name.lstrip("%").strip(), None
    head = _OPCODE.split(" " + m.group(2), maxsplit=1)[0]
    return m.group(1), normal_type(head)


class Index(NamedTuple):
    by_pair: Dict[Tuple[str, str], frozenset]     # (instruction, type) -> facts
    by_name: Dict[str, frozenset]                 # instruction -> facts


def index(records: Iterable) -> Index:
    pairs: Dict[Tuple[str, str], set] = {}
    names: Dict[str, set] = {}
    for r in records:
        fact = fact_of(r)
        pairs.setdefault((r.instruction, normal_type(r.result_type)),
                         set()).add(fact)
        names.setdefault(r.instruction, set()).add(fact)
    return Index({k: frozenset(v) for k, v in pairs.items()},
                 {k: frozenset(v) for k, v in names.items()})


def join(event_name: str, idx: Index) -> Optional[Fact]:
    """The one thing the noted programs say this event is, or None: no
    record, or records (of two programs, as a rule) that disagree."""
    instruction, result_type = parse_event(event_name)
    facts = idx.by_pair.get((instruction, result_type))
    if facts is None:
        facts = idx.by_name.get(instruction)
    if facts is None or len(facts) != 1:
        return None
    return next(iter(facts))


# -- the busy time, event by event ----------------------------------------------

def window_of(r: trace.Reduced) -> Optional[Tuple[float, float]]:
    """The window ``trace.reduce`` cut: from the first to the last of the
    host spans of the one name whose extent is ``window_s``."""
    extents: Dict[str, List[float]] = {}
    for s in r.spans:
        lo_hi = extents.setdefault(s.name, [s.start_ns, s.start_ns])
        lo_hi[0] = min(lo_hi[0], s.start_ns)
        lo_hi[1] = max(lo_hi[1], s.start_ns + s.duration_ns)
    for lo, hi in extents.values():
        if abs((hi - lo) / 1e9 - r.window_s) < 1e-9:
            return lo, hi
    return None


def partition(events: Sequence[trace.Event],
              window: Optional[Tuple[float, float]] = None
              ) -> List[Tuple[trace.Event, float]]:
    """Each event with its seconds of busy time: the part of its interval
    inside ``window`` that no earlier event of its plane covers, over the
    number of planes. The seconds sum to ``trace.reduce``'s ``busy_s``."""
    planes = sorted({e.plane for e in events})
    out = []
    for plane in planes:
        at = float("-inf") if window is None else window[0]
        end = float("inf") if window is None else window[1]
        for e in sorted((e for e in events if e.plane == plane),
                        key=lambda e: e.start_ns):
            lo = max(e.start_ns, at)
            hi = min(e.start_ns + e.duration_ns, end)
            out.append((e, max(hi - lo, 0.0) / 1e9 / len(planes)))
            at = max(at, hi)
    return out


# -- one traced run -------------------------------------------------------------

class Analysis(NamedTuple):
    busy_s: float
    steps: int
    named: List[Tuple[Fact, float]]          # a fact and its seconds
    unnamed: List[Tuple[str, float]]         # short event name, seconds
    ops: Dict[str, Dict[Tuple[str, str], float]]  # short name -> (scope, kind)

    def seconds(self, keep) -> float:
        return sum(s for fact, s in self.named if keep(fact))

    def ms_a_step(self, keep) -> Optional[float]:
        return self.seconds(keep) * 1e3 / self.steps if self.steps else None

    @property
    def unnamed_s(self) -> float:
        return sum(s for _, s in self.unnamed)


def reduce(r: trace.Reduced, records: Iterable, steps: int) -> Analysis:
    idx = index(records)
    seen: Dict[str, Tuple[Optional[Fact], str]] = {}    # by event name
    named: Dict[Fact, float] = {}
    unnamed: Dict[str, float] = {}
    ops: Dict[str, Dict[Tuple[str, str], float]] = {}
    for e, s in partition(r.events, window_of(r)):
        if e.name not in seen:
            seen[e.name] = (join(e.name, idx), trace.short_name(e.name))
        fact, short = seen[e.name]
        if fact is None:
            unnamed[short] = unnamed.get(short, 0.0) + s
        else:
            named[fact] = named.get(fact, 0.0) + s
        pair = (UNNAMED, "") if fact is None else (fact.scope, fact.kind)
        by = ops.setdefault(short, {})
        by[pair] = by.get(pair, 0.0) + s
    return Analysis(r.busy_s, steps, list(named.items()),
                    sorted(unnamed.items(), key=lambda kv: -kv[1]), ops)


def analyse(run) -> Optional[Analysis]:
    """The traced slice of a run through the program's records; once a run
    (kept on it), and printed once as an earlier line of its output."""
    if hasattr(run, "_device_scopes"):
        return run._device_scopes
    from paddle_tpu.observability import tracing
    reader = getattr(tracing, "device_ops", None)
    r = run.reduced
    done = None
    if reader is not None and r is not None and r.busy_s > 0 and r.events:
        records = reader()
        if records:
            done = reduce(r, records, len(run.traced_steps))
            print(json.dumps({"event": "device_scopes", **summary(done)}),
                  flush=True)
    run._device_scopes = done
    return done


def summary(a: Analysis) -> Dict:
    """The ``device_scopes`` line: the largest (scope, kind) pairs, the
    matmuls by scope, the phases (and what of another phase XLA fused into
    each), the largest operations by their shape name with the scopes they
    belong to, and what stayed unnamed."""
    ms = lambda s: s * 1e3 / a.steps if a.steps else None
    pairs: Dict[Tuple[str, str], float] = {}
    phases = {**dict.fromkeys(PHASES, 0.0), UNNAMED: a.unnamed_s}
    fused: Dict[str, float] = {}
    for fact, s in a.named:
        pairs[(fact.scope, fact.kind)] = pairs.get((fact.scope, fact.kind),
                                                   0.0) + s
        phases[fact.phase] += s
        if fact.inside:
            key = fact.phase + " with " + "+".join(fact.inside) + " inside"
            fused[key] = fused.get(key, 0.0) + s
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])
    ops = by_time({k: sum(v.values()) for k, v in a.ops.items()})[:TOP_OPS]
    return {
        "steps": a.steps, "busy_s": a.busy_s, "busy_ms_a_step": ms(a.busy_s),
        "groups_over_busy": (sum(phases.values()) / a.busy_s
                             if a.busy_s else None),
        "pairs": [[scope, kind, s, ms(s)]
                  for (scope, kind), s in by_time(pairs)[:TOP_PAIRS]],
        "matmul_ms_by_scope": [
            [scope, ms(s)] for (scope, kind), s in by_time(pairs)
            if kind == "matmul"][:TOP_PAIRS],
        "phase_ms_a_step": {p: ms(s) for p, s in phases.items()},
        "fused_ms_a_step": {k: ms(s) for k, s in by_time(fused)},
        "no_op_name_s": a.seconds(lambda f: f.scope == NO_OP_NAME),
        "ops": [[name, s, [[scope, kind, part] for (scope, kind), part
                           in by_time(a.ops[name])[:3]]]
                for name, s in ops],
        "unnamed_s": a.unnamed_s,
        "unnamed_largest": [[n, s] for n, s in a.unnamed[:TOP_UNNAMED]]}


def ms_where(run, keep) -> Optional[float]:
    """ms a traced step of the events whose fact ``keep`` takes."""
    a = analyse(run)
    return None if a is None else a.ms_a_step(keep)


def unnamed_share_pct(run) -> Optional[float]:
    a = analyse(run)
    return None if a is None else 100.0 * a.unnamed_s / a.busy_s
