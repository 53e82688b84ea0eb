"""Whole-step capture: fuse forward + backward + optimizer into ONE
donated XLA executable.

PR 1 compiled the backward walk and the optimizer already updates its
whole pytree in one donated jit, but an eager training step still pays
one PJRT launch per forward op — dispatch-bound workloads (small BERT /
ResNet-CIFAR steps) are launch-bound, not FLOP-bound. The reference
closes this with a whole-graph compiler (CINN) plus fused multi-tensor
optimizer kernels; the TPU-native analog is to trace the ENTIRE step the
user already wrote — eager forward through the dispatcher, tape
backward, grad clip, LR read, ``opt.step()``/``clear_grad()`` — into a
single ``jax.jit`` with parameters and optimizer state donated, then
replay that executable on every subsequent step.

Lifecycle per (flags fingerprint x input avals x state structure) key:

1. **probe** — the step runs eagerly, instrumented: the dispatcher
   reports every leaf input tensor, ``Tensor._set_data`` reports
   mutations, ``Optimizer.step``/``LRScheduler.step`` report themselves.
   This discovers the step's persistent state: params, optimizer
   moments/masters, BN running stats, frozen weights.
2. **capture** — the step re-runs under ``jax.jit`` tracing with every
   state tensor swapped to a traced input (``_swap_state``), optimizer
   state/LR/step-count as traced inputs (``optimizer._CAPTURE``), RNG
   chained on device, and trace-through dispatch active
   (``dispatcher._STEP_TRACE``: per-op exec-cache jit bypassed, kernels
   called inline so the outer trace sees the whole step). The tape walk
   runs inline through the fused-backward planner (``engine._CAPTURE``).
3. **replay** — the donated executable runs; params/optimizer state are
   rebound via ``Tensor._rebind_donated`` and recorded host effects
   (optimizer step counts, no-arg scheduler advances) are re-applied.

Unfusable steps — tensor hooks, ``create_graph``, data-dependent Python
control flow (a concretization error at trace time), schedulers stepped
with explicit epochs/metrics, ZeRO-sharded optimizer state, input
arguments that require grad — fall back to the exact eager path with the
reason (a frozen ``FALLBACK_REASONS`` member plus detail) recorded in
the flight recorder and the ``step_capture.{captures,replays,fallbacks}``
counters. Steps whose SOURCE already proves them uncapturable are caught
even earlier: the graftcheck capture-safety screen
(``analysis.screen_step_fn``, gated by ``FLAGS_step_capture_screen``)
runs once before the probe and short-circuits with a ``file:line``
diagnosis (``step_capture.static_screened``), so a doomed step never
pays probe + trace + compile + abort. Shape changes miss the structure
cache and re-probe; a never-repeating stream of structures trips a
miss-streak breaker like the fused backward's.

Host-side Python in the step function (logging, metric math) runs during
probe and capture but NOT during replay — the same contract as
``to_static``/``TrainStep``. Data must enter through the CALL ARGUMENTS:
closure tensors the probe sees become live traced inputs (in-place
mutations flow through; small never-mutated leaves are baked as
constants with a per-replay version check), but REBINDING a closed-over
Python variable to a new Tensor between steps is invisible to the
capture — a loop that reads its batch from the enclosing scope instead
of an argument replays the probe iteration's data.
"""

from __future__ import annotations

import functools
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags
from ..autograd import engine
from ..core import generator
from ..core import tensor as tensor_mod
from ..core.tensor import Tensor
from ..observability import flight_recorder as _flight_mod
from ..observability import metrics as _metrics_mod
from ..observability import perf as _perf_mod
from ..observability import tracing as _tracing
from ..ops import dispatcher
from ..optimizer import lr as lr_mod
from ..optimizer import optimizer as optimizer_mod
from .api import _swap_state, _traced_rng

__all__ = ["jit_step", "CapturedStep", "capture_counters"]

_F_STEP = flags._REGISTRY["step_capture"]
_F_SCREEN = flags._REGISTRY["step_capture_screen"]

# structure-cache bounds: each entry is a WHOLE-STEP executable, far
# heavier than a per-op cache slot, so the FIFO is small; the breaker
# mirrors the fused backward's so dynamic-shape streams stop paying the
# probe instrumentation tax
_ENTRIES_MAX = 8
_MISS_STREAK_MAX = 8
_PROBE_EVERY = 16

_PRIMED = object()

# observability: authoritative dict (tests snapshot it), published as
# callback gauges — zero extra hot-path writes
capture_counters = {"probes": 0, "captures": 0, "replays": 0,
                    "fallbacks": 0, "bypass": 0, "invalidations": 0,
                    "static_screened": 0}
for _k in ("probes", "captures", "replays", "fallbacks", "bypass",
           "invalidations", "static_screened"):
    _metrics_mod.registry().gauge(
        "step_capture." + _k,
        fn=lambda _k=_k: float(capture_counters[_k]),
        help=f"whole-step capture '{_k}' events (jit/step_capture.py)")
del _k

# Frozen fallback-reason taxonomy. Every reason that can reach
# _fallback() — from this module, engine._CAPTURE.abort sites, and
# optimizer.py — lives here, so the flight recorder and the fallbacks
# counter can never fork on a typo'd or ad-hoc string. Parameterized
# reasons ("trace failed", "replay failed", "statically screened")
# carry the varying part in the separate `detail` argument. The
# graftcheck `taxonomy` rule checks literal call sites statically;
# _fallback() enforces membership at runtime for computed ones.
FALLBACK_REASONS = frozenset({
    "FLAGS_step_capture disabled",
    "unhashable static argument",
    "input argument requires grad (grads must land on the caller's "
    "tensor)",
    "LR scheduler stepped with an explicit epoch/metric argument",
    "step mutates an input argument in place",
    "ZeRO state sharding active on the optimizer",
    "optimizer.step() on an optimizer not seen during the discovery run",
    "learning rate changed mid-step (scheduler stepped before "
    "optimizer.step)",
    "step mutates a tensor outside the captured state set (stale "
    "discovery)",
    "tape has tensor hooks or structurally-unkeyed nodes "
    "(sot/to_static segments)",
    "backward(create_graph=True) inside a captured step",
    "functional grad() capture inside a captured step",
    "trace failed",
    "replay failed",
    "statically screened",
})


class CaptureAbort(Exception):
    """Raised mid-trace when the step cannot be captured faithfully;
    the caller rolls host state back and replays the eager path.

    `reason` must be a FALLBACK_REASONS member; `detail` carries the
    parameterization (exception text, source location)."""

    def __init__(self, reason: str, detail: Optional[str] = None):
        super().__init__(reason if detail is None
                         else f"{reason}: {detail}")
        self.reason = reason
        self.detail = detail


# -- ambient-state installation ----------------------------------------------

def _set_trace(ctx) -> None:
    dispatcher._STEP_TRACE = ctx
    engine._CAPTURE = ctx
    optimizer_mod._CAPTURE = ctx
    tensor_mod._MUTATION_HOOK = ctx.on_mutation if ctx is not None else None


def _set_probe(probe) -> None:
    dispatcher._STEP_PROBE = probe
    optimizer_mod._PROBE = probe
    lr_mod._PROBE = probe
    tensor_mod._MUTATION_HOOK = probe.on_mutation if probe is not None \
        else None


def _span_hook():
    return dispatcher._OP_SPAN_HOOK


# -- discovery (probe run) ----------------------------------------------------

# leaf tensors at or below this many elements that the step never
# mutates are baked into the executable as constants instead of becoming
# traced I/O (their versions are checked on replay, so a mutation
# invalidates the capture rather than replaying stale values)
_BAKE_MAX_SIZE = 16


class _Probe:
    """Discovery-run instrumentation sink."""

    def __init__(self, arg_ids):
        self._arg_ids = arg_ids
        self.seen: Dict[int, Any] = {}      # id -> weakref(leaf input Tensor)
        self.mutated: Dict[int, Any] = {}
        self.opts: List = []
        self._opt_ids: set = set()
        self.opt_step0: Dict[int, int] = {}
        self.sched_epoch0: Dict[int, int] = {}
        self.sched_arg = False
        self.arg_mutated = False

    # dispatcher hook: every op's input tensors, once per distinct leaf
    def on_op(self, in_tensors) -> None:
        for t in in_tensors:
            if t is not None and t._node is None:
                i = id(t)
                if i not in self._arg_ids and i not in self.seen:
                    self.seen[i] = weakref.ref(t)

    # core.tensor hook: every _set_data (called before the rebind)
    def on_mutation(self, t, new_arr) -> None:
        i = id(t)
        if i in self._arg_ids:
            self.arg_mutated = True
            return
        if i not in self.mutated:
            self.mutated[i] = weakref.ref(t)

    # optimizer hook: top of Optimizer.step()
    def saw_optimizer(self, opt) -> None:
        i = id(opt)
        if i not in self._opt_ids:
            self._opt_ids.add(i)
            self.opts.append(opt)
            # entry _step_count at first sight: the replayed host-side
            # advance is the probe run's measured DELTA, not the call
            # count — a step() whose optimizer had no grads early-outs
            # without advancing, and replays must not advance it either
            self.opt_step0[i] = opt._step_count
            sched = opt._lr
            if isinstance(sched, lr_mod.LRScheduler):
                self.sched_epoch0.setdefault(id(sched), sched.last_epoch)

    # lr hook: LRScheduler.step(arg)
    def saw_scheduler_step(self, sched, arg) -> None:
        self.sched_epoch0.setdefault(id(sched), sched.last_epoch)
        if arg is not None:
            self.sched_arg = True


class _Discovery:
    """What a probe run learned about the step's persistent state."""

    __slots__ = ("state", "state_ids", "baked", "opts", "opt_steps",
                 "sched_deltas", "reason")

    def __init__(self, probe: _Probe):
        self.reason: Optional[str] = None
        if probe.sched_arg:
            self.reason = ("LR scheduler stepped with an explicit "
                           "epoch/metric argument")
        elif probe.arg_mutated:
            self.reason = "step mutates an input argument in place"
        elif any(o._state_shardings for o in probe.opts):
            self.reason = "ZeRO state sharding active on the optimizer"

        state: List[Tensor] = []
        ids: set = set()

        def add(t: Tensor) -> None:
            if id(t) not in ids:
                ids.add(id(t))
                state.append(t)

        for opt in probe.opts:
            for p in opt._parameter_list:
                add(p)
        for ref in probe.mutated.values():
            t = ref()
            if t is not None:
                add(t)
        self.baked: List[Tuple[Any, int]] = []   # (weakref, version)
        for ref in probe.seen.values():
            t = ref()
            if t is None or id(t) in ids:
                continue
            if t._data.size <= _BAKE_MAX_SIZE:
                self.baked.append((ref, t._version))
            else:
                add(t)
        self.state = state
        self.state_ids = ids
        self.opts = list(probe.opts)
        # measured per-probe-run advance of each optimizer's host count
        self.opt_steps = {id(o): o._step_count - probe.opt_step0[id(o)]
                          for o in probe.opts}
        # host-side scheduler advance per step, replayed on replay calls
        self.sched_deltas: List[Tuple[Any, int]] = []
        for opt in self.opts:
            sched = opt._lr
            if isinstance(sched, lr_mod.LRScheduler):
                e0 = probe.sched_epoch0.get(id(sched), sched.last_epoch)
                delta = sched.last_epoch - e0
                if delta:
                    self.sched_deltas.append((weakref.ref(sched), delta))

    def refresh_baked_versions(self) -> None:
        self.baked = [(r, t._version) for r, t in
                      ((r, r()) for r, _ in self.baked) if t is not None]

    def baked_stale(self) -> bool:
        for ref, ver in self.baked:
            t = ref()
            if t is not None and t._version != ver:
                return True
        return False


# -- capture trace context ----------------------------------------------------

class _TraceCtx:
    """Ambient object the dispatcher/engine/optimizer consult while the
    whole-step trace runs."""

    __slots__ = ("state_ids", "opt_in")

    def __init__(self, state_ids, opt_in):
        self.state_ids = state_ids
        self.opt_in = opt_in    # id(opt) -> {"step","lr","lr_host","calls"}

    def abort(self, reason: str, detail: Optional[str] = None):
        raise CaptureAbort(reason, detail)

    def traced_lr(self, opt):
        rec = self.opt_in.get(id(opt))
        if rec is None:
            self.abort("optimizer.step() on an optimizer not seen during "
                       "the discovery run")
        if float(opt.get_lr()) != rec["lr_host"]:
            self.abort("learning rate changed mid-step (scheduler stepped "
                       "before optimizer.step)")
        return rec["lr"]

    def traced_step(self, opt, applied=None):
        """The traced step scalar for THIS optimizer.step() call.

        ``applied`` (default 1) is this call's advance of the persistent
        device step counter; a sentinel-guarded update passes the traced
        ``where(found, 0, 1)`` so a skipped update does not consume a
        step — the counter the NEXT replay's bias corrections read stays
        at applied-updates semantics, exactly like the eager GradScaler
        skipping the whole ``optimizer.step()`` call."""
        rec = self.opt_in.get(id(opt))
        if rec is None:
            self.abort("optimizer.step() on an optimizer not seen during "
                       "the discovery run")
        prev = rec.get("adv", rec["calls"])
        rec["calls"] += 1
        rec["adv"] = prev + (1 if applied is None else applied)
        return rec["step"] + prev + 1

    # core.tensor hook during the trace: a traced value written into a
    # persistent tensor OUTSIDE the captured state set would be silently
    # lost on replay — abort so the eager path (and a fresh probe) runs
    def on_mutation(self, t, new_arr) -> None:
        if id(t) in self.state_ids:
            return
        if isinstance(new_arr, jax.core.Tracer) \
                and not isinstance(t._data, jax.core.Tracer):
            self.abort("step mutates a tensor outside the captured state "
                       "set (stale discovery)")


class _HostSnapshot:
    """Host bookkeeping the traced fn mutates as it runs — rolled back
    when the capture aborts mid-trace so the eager re-run starts clean."""

    def __init__(self, disc: _Discovery):
        self._opt = [(o, o._step_count) for o in disc.opts]
        self._sched = []
        for o in disc.opts:
            s = o._lr
            if isinstance(s, lr_mod.LRScheduler):
                self._sched.append((s, dict(s.__dict__)))

    def restore(self) -> None:
        for o, c in self._opt:
            o._step_count = c
        for s, d in self._sched:
            s.__dict__.clear()
            s.__dict__.update(d)


# -- argument handling --------------------------------------------------------

def _flatten_args(args, kwargs):
    """Split (args, kwargs) into dynamic array leaves and hashable
    statics. Returns None when a static leaf is unhashable."""
    leaves, treedef = jax.tree.flatten(
        (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
    dyn_pos: List[int] = []
    dyn_arrays: List[jax.Array] = []
    dyn_kind: List[str] = []     # 'T' Tensor | 'a' raw array
    avals: List[tuple] = []
    statics: List[tuple] = []
    grad_arg = False
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, Tensor):
            a, kind = leaf._data, "T"
            if not leaf._stop_gradient:
                grad_arg = True
        elif isinstance(leaf, (jax.Array, np.ndarray)):
            # np arrays stay host-side here: jax converts them at the jit
            # boundary itself, and converting eagerly would pay an H2D
            # copy even on calls that end up on the eager fallback
            a, kind = leaf, "a"
        else:
            statics.append((i, leaf))
            continue
        dyn_pos.append(i)
        dyn_arrays.append(a)
        dyn_kind.append(kind)
        # weak_type is part of jax's tracing cache key: leaving it out
        # would alias two structures onto one entry and force a silent
        # retrace at replay time
        avals.append((a.shape, a.dtype, bool(getattr(a, "weak_type",
                                                     False))))
    statics_t = tuple(statics)
    try:
        hash(statics_t)
    except TypeError:
        return None
    sig = (treedef, tuple(avals), statics_t)
    return (sig, tuple(dyn_arrays), grad_arg,
            (treedef, leaves, tuple(dyn_pos), tuple(dyn_kind)))


def _make_step_body(fn, disc: "_Discovery", rebuild, lr_hosts,
                    tracebox: Dict[str, Any], outbox: Dict[str, Any]):
    """Build the pure traced step body the capture jit-compiles.

    Returns ``step_fn(state_arrs, grads_in, packs, key, lrs, dyn) ->
    (out_arrs, new_state, new_grads, new_packs, key)`` — one full user
    step (forward through trace-through dispatch, tape backward, grad
    clip, optimizer update) expressed over explicit array I/O. The body
    is a valid ``lax.scan`` body as well: its carry-shaped quadruple
    (state, grads, packs, key) round-trips with matching avals, which is
    what jit/multi_step.py scans K times inside ONE executable."""
    state = disc.state
    state_ids = disc.state_ids
    opts = disc.opts
    treedef, leaves, dyn_pos, dyn_kind = rebuild
    static_leaves = list(leaves)
    for pos in dyn_pos:
        static_leaves[pos] = None   # don't pin this call's batch

    def step_fn(state_arrs, grads_in, packs, key, lrs, dyn):
        tracebox["ran"] = True
        key, rng = jax.random.split(key)
        opt_in = {id(o): {"step": pack[2], "lr": lr_t,
                          "lr_host": lr_v, "calls": 0}
                  for o, pack, lr_t, lr_v in zip(opts, packs, lrs,
                                                 lr_hosts)}
        ctx = _TraceCtx(state_ids, opt_in)
        saved_opt = [(list(o._states), list(o._masters)) for o in opts]
        saved_grads = [t._grad for t in state]
        try:
            with _swap_state(list(state), list(state_arrs)):
                for o, pack in zip(opts, packs):
                    o._states = list(pack[0])
                    o._masters = list(pack[1])
                for t, g in zip(state, grads_in):
                    t._grad = Tensor(g) if g is not None else None
                _set_trace(ctx)
                try:
                    lv = list(static_leaves)
                    for pos, arr, kind in zip(dyn_pos, dyn, dyn_kind):
                        lv[pos] = Tensor(arr) if kind == "T" else arr
                    cargs, ckwargs = jax.tree.unflatten(treedef, lv)
                    with _traced_rng(rng):
                        out = fn(*cargs, **ckwargs)
                finally:
                    _set_trace(None)
                # collect while state still holds the traced values
                out_flat, out_tree = jax.tree.flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor))
                outbox["tree"] = out_tree
                outbox["is_tensor"] = tuple(
                    isinstance(x, Tensor) for x in out_flat)
                out_arrs = tuple(x._data if isinstance(x, Tensor) else x
                                 for x in out_flat)
                new_state = tuple(t._data for t in state)
                new_grads = tuple(
                    t._grad._data if t._grad is not None else None
                    for t in state)
                new_packs = tuple(
                    (tuple(o._states), tuple(o._masters),
                     opt_in[id(o)]["step"]
                     + opt_in[id(o)].get("adv",
                                         opt_in[id(o)]["calls"]))
                    for o in opts)
        finally:
            for o, (s, m) in zip(opts, saved_opt):
                o._states, o._masters = s, m
            for t, g0 in zip(state, saved_grads):
                t._grad = g0
        return out_arrs, new_state, new_grads, new_packs, key

    return step_fn


class _Captured:
    """A compiled whole-step executable plus its replay binding plan.

    Carries the _Discovery it was traced under: replays must bind state
    and re-apply host effects (scheduler deltas, step counts) from the
    CAPTURE-TIME discovery, not whatever later probe happens to sit on
    the CapturedStep — two static variants of one step can differ in
    exactly those host effects."""

    __slots__ = ("jfn", "disc", "out_is_tensor", "tracebox", "perf")

    def __init__(self, jfn, disc, tracebox):
        self.jfn = jfn
        self.disc = disc
        self.out_is_tensor = None
        self.perf = None       # ExecutableLedger row, when the plane is on
        self.tracebox = tracebox


# -- the public wrapper -------------------------------------------------------

class CapturedStep:
    """Result of :func:`jit_step`: a training-step function that, once
    its structure is stable, replays as one donated XLA executable."""

    _perf_kind = "step"        # ledger kind; multi_step overrides

    def __init__(self, fn: Callable):
        self._fn = fn
        self._disc: Optional[_Discovery] = None
        self._entries: Dict[Any, Any] = {}
        self._dev_key = None
        self._opt_sync: Dict[int, list] = {}   # id(opt) -> [host_step, dev]
        self._lr_cache: Dict[int, tuple] = {}  # id(opt) -> (float, jnp)
        self._streak = 0
        self._probe_tick = 0
        self._last_reason: Optional[str] = None
        self._screen: Optional[str] = None     # None=unscreened, ""=clean
        functools.update_wrapper(self, fn, updated=())

    # -- fallbacks -----------------------------------------------------------
    def _fallback(self, reason: str, detail: Optional[str] = None) -> None:
        if reason not in FALLBACK_REASONS:
            raise ValueError(
                f"unregistered step_capture fallback reason {reason!r} — "
                f"add it to FALLBACK_REASONS (frozen so the flight "
                f"recorder and counters cannot fork)")
        capture_counters["fallbacks"] += 1
        msg = reason if detail is None else f"{reason}: {detail}"
        if msg != self._last_reason:
            # one ring entry per distinct reason, not per eager step —
            # a long eager run must not bury the dispatch history
            self._last_reason = msg
            if _flight_mod.enabled():
                _flight_mod.recorder().record(
                    "step_capture.fallback", (msg,), reason)

    # -- static screen -------------------------------------------------------
    def _compute_screen(self) -> str:
        """Run the graftcheck capture-safety screen over the step's
        source ONCE; returns "" when clean/unscreenable, else the
        source-located diagnosis. A doomed step then never pays the
        probe + trace + compile + abort cycle — the precise reason is
        known before the first instrumented run."""
        try:
            from ..analysis import screen_step_fn
            findings = screen_step_fn(self._fn)
        except Exception:
            return ""   # the screen must never break training; the
            #             dynamic probe/abort path stays authoritative
        if not findings:
            return ""
        capture_counters["static_screened"] += 1
        first = findings[0]
        detail = f"{first.path}:{first.line}: {first.message}"
        if len(findings) > 1:
            detail += f" (+{len(findings) - 1} more)"
        if _flight_mod.enabled():
            _flight_mod.recorder().record(
                "step_capture.static_screened",
                tuple(f"{f.path}:{f.line}: {f.message}" for f in findings),
                None)
        return detail

    # -- key -----------------------------------------------------------------
    def _state_sig(self):
        d = self._disc
        st = tuple((t._data.shape, t._data.dtype, t._grad is not None,
                    t._stop_gradient) for t in d.state)
        osig = []
        for o in d.opts:
            clip = o._grad_clip
            clip_sig = None if clip is None else (
                type(clip).__name__, getattr(clip, "clip_norm", None),
                getattr(clip, "min", None), getattr(clip, "max", None))
            masks = tuple((s is None, m is None)
                          for s, m in zip(o._states, o._masters))
            osig.append((id(o), type(o).__name__, o._update_static_key(),
                         clip_sig, isinstance(o._lr, lr_mod.LRScheduler),
                         o._multi_precision,
                         tuple(id(p) for p in o._parameter_list), masks))
        return (st, tuple(osig))

    # -- probe ---------------------------------------------------------------
    def _probe_and_prime(self, args, kwargs, arg_sig):
        capture_counters["probes"] += 1
        arg_ids = {id(a) for a in jax.tree.leaves(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
            if isinstance(a, Tensor)}
        probe = _Probe(arg_ids)
        _set_probe(probe)
        try:
            out = self._fn(*args, **kwargs)
        finally:
            _set_probe(None)
        self._disc = _Discovery(probe)
        key = (flags.version, arg_sig, self._state_sig())
        if self._disc.reason is not None:
            self._put_entry(key, ("unfusable", self._disc.reason, None))
            self._fallback(self._disc.reason)
        elif key not in self._entries:
            self._put_entry(key, _PRIMED)
        return out

    def _put_entry(self, key, value) -> None:
        if key not in self._entries and len(self._entries) >= _ENTRIES_MAX:
            self._entries.pop(next(iter(self._entries)))
        self._entries[key] = value

    # -- capture -------------------------------------------------------------
    def _attempt_capture(self, key, dyn_arrays, rebuild):
        d = self._disc

        if self._dev_key is None:
            self._dev_key = generator.next_key()
        lr_hosts = [float(o.get_lr()) for o in d.opts]
        lrs = self._lr_args(d)
        packs = tuple(self._opt_pack(o) for o in d.opts)
        state_arrs = tuple(t._data for t in d.state)
        grads_in = tuple(t._grad._data if t._grad is not None else None
                         for t in d.state)

        tracebox: Dict[str, Any] = {}
        outbox: Dict[str, Any] = {}
        step_fn = _make_step_body(self._fn, d, rebuild, lr_hosts,
                                  tracebox, outbox)

        snap = _HostSnapshot(d)
        jfn = jax.jit(self._wrap_body(step_fn), donate_argnums=(0, 1, 2, 3))
        # persistent exec store: lower() still traces the body exactly
        # once (tracebox/outbox fill during the trace), so a disk hit
        # skips only the XLA compile; CaptureAbort propagates unchanged
        from . import exec_store as _exec_store
        jfn = _exec_store.persistent(
            jfn, self._perf_kind, label="step_capture",
            perf_key=("step_capture", key))
        perf_lower = None
        if _perf_mod.enabled():
            try:
                # aval snapshot BEFORE the donating launch, so the
                # ledger can lower+compile for cost analysis at report
                # time without the live buffers
                avals = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    (state_arrs, grads_in, packs, self._dev_key,
                     lrs, dyn_arrays))
                perf_lower = (lambda f=jfn, av=avals:
                              f.lower(*av).compile())
            except Exception:
                pass   # cost model is fail-open; capture must not care
        t_cap = _perf_mod.clock()
        hook = _span_hook()
        try:
            if hook is not None:
                with hook("step_capture::capture"):
                    outs = jfn(state_arrs, grads_in, packs, self._dev_key,
                               lrs, dyn_arrays)
            else:
                outs = jfn(state_arrs, grads_in, packs, self._dev_key,
                           lrs, dyn_arrays)
        except CaptureAbort:
            snap.restore()
            raise
        except Exception as e:  # trace failure: data-dependent control
            snap.restore()      # flow, host sync, unpicklable output, ...
            raise CaptureAbort(
                "trace failed", f"{type(e).__name__}: {e}") from e
        d.refresh_baked_versions()
        entry = _Captured(jfn, d, tracebox)
        entry.out_is_tensor = (outbox["tree"], outbox["is_tensor"])
        if _perf_mod.enabled():
            # the entry key already folds flags.version, so an off->on
            # toggle re-captures with a ledger row and on->off drops it
            led = _perf_mod.ledger()
            cap_s = _perf_mod.clock() - t_cap
            entry.perf = led.register(
                ("step_capture", key), self._perf_kind,
                name=self._perf_kind, lower=perf_lower, compile_s=cap_s)
            led.tick(entry.perf)
            led.commit(entry.perf, cap_s)
        self._put_entry(key, entry)
        tracebox.pop("ran", None)
        # the trace itself executed the step's host side (step counts,
        # scheduler advances), so only outputs need applying here
        return self._apply_outputs(entry, outs, host_effects=False)

    def _opt_pack(self, o):
        sync = self._opt_sync.get(id(o))
        if sync is None or sync[0] != o._step_count:
            # state loaded/reset externally: re-sync the device-resident
            # step scalar from the host count (one transfer)
            sync = [o._step_count, jnp.asarray(o._step_count, jnp.int32)]
            self._opt_sync[id(o)] = sync
        return (tuple(o._states), tuple(o._masters), sync[1])

    def _wrap_body(self, step_fn):
        """Hook for subclasses to reshape the traced body before jit —
        multi_step wraps it in a K-iteration ``lax.scan``."""
        return step_fn

    def _lr_args(self, d) -> tuple:
        """Per-optimizer traced lr arguments for one executable launch
        (scalars here; a [K] schedule stack in multi_step). Cached so a
        steady lr pays zero transfers — one H2D per lr CHANGE."""
        lrs = []
        for o in d.opts:
            v = float(o.get_lr())
            c = self._lr_cache.get(id(o))
            if c is None or c[0] != v:
                c = (v, jnp.asarray(v, jnp.float32))
                self._lr_cache[id(o)] = c
            lrs.append(c[1])
        return tuple(lrs)

    def _host_reps(self, host_effects: bool) -> int:
        """How many per-step host-effect applications (optimizer step
        counts, scheduler advances) one executable launch owes. The
        trace itself runs the step's host side once, so a launch that
        traced pays one fewer than a pure replay — 0 vs 1 here, K-1 vs
        K for a K-step block."""
        return 1 if host_effects else 0

    # -- replay --------------------------------------------------------------
    def _replay(self, entry: _Captured, dyn_arrays):
        d = entry.disc     # bind state/host effects as captured, not as
        if d.baked_stale():  # the latest probe happened to discover them
            capture_counters["invalidations"] += 1
            self._disc = None
            self._entries.clear()
            return None     # caller re-dispatches (re-probes)
        lrs = self._lr_args(d)
        packs = tuple(self._opt_pack(o) for o in d.opts)
        state_arrs = tuple(t._data for t in d.state)
        grads_in = tuple(t._grad._data if t._grad is not None else None
                         for t in d.state)
        if self._dev_key is None:
            self._dev_key = generator.next_key()
        hook = _span_hook()
        snap = _HostSnapshot(d)   # a surprise retrace runs host effects
        pe = entry.perf
        p_sample = _perf_mod.ledger().tick(pe) if pe is not None else False
        t_rep = _perf_mod.clock()
        try:
            if hook is not None:
                with hook("step_capture"):
                    outs = entry.jfn(state_arrs, grads_in, packs,
                                     self._dev_key, tuple(lrs), dyn_arrays)
            else:
                outs = entry.jfn(state_arrs, grads_in, packs,
                                 self._dev_key, tuple(lrs), dyn_arrays)
        except Exception as e:
            # an unexpected retrace (or a consistency guard inside it)
            # failed BEFORE execution: roll host state back, drop the
            # capture, and let the caller re-dispatch onto the eager
            # path. A failure AFTER dispatch is different: donation has
            # consumed params/grads/optimizer state, so nothing can run
            # — surface that explicitly instead of letting the eager
            # retry crash later on deleted arrays.
            snap.restore()
            capture_counters["invalidations"] += 1
            self._entries.clear()
            self._disc = None
            self._opt_sync.clear()
            self._lr_cache.clear()
            if any(getattr(t._data, "is_deleted", lambda: False)()
                   for t in d.state):
                if _flight_mod.enabled():
                    # the post-mortem must distinguish "replay failed,
                    # eager retry ran" from "donation consumed the
                    # state" — only the latter needs a restore
                    _flight_mod.recorder().record(
                        "step_capture.donation_lost",
                        (f"{type(e).__name__}: {e}",), None)
                raise RuntimeError(
                    "step_capture replay failed after its donated inputs "
                    "were consumed — params/optimizer state no longer "
                    "exist; restore from a committed checkpoint "
                    "(distributed.resilience.ResilientTrainer.restore / "
                    "checkpoint.latest_checkpoint) or disable "
                    "FLAGS_step_capture and reload."
                ) from e
            if isinstance(e, CaptureAbort):
                self._fallback(e.reason, e.detail)
            else:
                self._fallback("replay failed",
                               f"{type(e).__name__}: {e}")
            return None
        if pe is not None:
            wall = _perf_mod.clock() - t_rep
            ready = None
            if p_sample:
                try:     # sampled replay: device-time via a timed sync
                    jax.block_until_ready(outs)
                    ready = _perf_mod.clock() - t_rep
                except Exception:
                    pass
            _perf_mod.ledger().commit(pe, wall, ready)
        # if jax silently re-traced, the step's host side already ran
        host_effects = not entry.tracebox.pop("ran", False)
        capture_counters["replays"] += 1
        return self._apply_outputs(entry, outs, host_effects=host_effects)

    def _apply_outputs(self, entry: _Captured, outs, host_effects: bool):
        d = entry.disc
        reps = self._host_reps(host_effects)
        out_arrs, new_state, new_grads, new_packs, new_key = outs
        for t, arr in zip(d.state, new_state):
            t._rebind_donated(arr)
        for t, g in zip(d.state, new_grads):
            t._grad = Tensor(g) if g is not None else None
        for o, pack in zip(d.opts, new_packs):
            o._states = list(pack[0])
            o._masters = list(pack[1])
            if reps:
                # sentinel note: whether a guarded update (and its step
                # advance) applied is on DEVICE only — the optimizer's
                # cumulative-skip ledger in _anomaly_t lets its next
                # consume_anomaly() reconcile this host count exactly,
                # however many replays happened in between
                o._step_count += reps * d.opt_steps.get(id(o), 0)
            self._opt_sync[id(o)] = [o._step_count, pack[2]]
        if reps:
            for sref, delta in d.sched_deltas:
                s = sref()
                if s is not None:
                    for _ in range(reps * delta):
                        s.step()
        self._dev_key = new_key
        out_tree, is_tensor = entry.out_is_tensor
        out_leaves = [Tensor(a) if is_t else a
                      for a, is_t in zip(out_arrs, is_tensor)]
        return jax.tree.unflatten(out_tree, out_leaves)

    # -- dispatch ------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if not _F_STEP.value:
            self._fallback("FLAGS_step_capture disabled")
            return self._fn(*args, **kwargs)
        if dispatcher._STEP_TRACE is not None \
                or dispatcher._STEP_PROBE is not None \
                or not jax.core.trace_ctx.is_top_level():
            # nested inside another capture/trace: run inline, the outer
            # program absorbs this step
            return self._fn(*args, **kwargs)

        if _F_SCREEN.value:
            # pre-probe static screen: a step whose source proves it can
            # never capture (host branch on a tensor, .numpy()/.item(),
            # hooks, create_graph=True) short-circuits to eager with a
            # file:line diagnosis instead of paying probe+trace+abort
            if self._screen is None:
                self._screen = self._compute_screen()
            if self._screen:
                self._fallback("statically screened", self._screen)
                return self._fn(*args, **kwargs)

        if self._streak >= _MISS_STREAK_MAX:
            # breaker first: a never-repeating structure stream must not
            # even pay the per-call flatten/signature cost
            self._probe_tick += 1
            if self._probe_tick % _PROBE_EVERY:
                capture_counters["bypass"] += 1
                return self._fn(*args, **kwargs)

        flat = _flatten_args(args, kwargs)
        if flat is None:
            self._fallback("unhashable static argument")
            return self._fn(*args, **kwargs)
        arg_sig, dyn_arrays, grad_arg, rebuild = flat
        if grad_arg:
            self._fallback("input argument requires grad (grads must "
                           "land on the caller's tensor)")
            return self._fn(*args, **kwargs)

        if self._disc is None:
            return self._probe_and_prime(args, kwargs, arg_sig)

        key = (flags.version, arg_sig, self._state_sig())
        ent = self._entries.get(key)
        if ent is None:
            self._streak += 1
            return self._probe_and_prime(args, kwargs, arg_sig)
        if ent is _PRIMED:
            try:
                # the span survives CaptureAbort (the with-block ends
                # it) so an aborted capture's cost is still attributed
                with _tracing.span("step_capture.capture"):
                    out = self._attempt_capture(key, dyn_arrays, rebuild)
            except CaptureAbort as e:
                self._put_entry(key, ("unfusable", e.reason, e.detail))
                self._disc = None   # a stale discovery gets one re-probe
                self._fallback(e.reason, e.detail)
                return self._fn(*args, **kwargs)
            capture_counters["captures"] += 1
            self._streak = 0
            return out
        if isinstance(ent, tuple):      # ("unfusable", reason, detail)
            self._fallback(ent[1], ent[2])
            return self._fn(*args, **kwargs)
        # compiled: refresh FIFO age, replay
        self._entries.pop(key)
        self._entries[key] = ent
        with _tracing.span("step_capture.replay"):
            out = self._replay(ent, dyn_arrays)
        if out is None:                 # baked-constant invalidation
            return self._probe_and_prime(args, kwargs, arg_sig)
        self._streak = 0
        return out


def jit_step(function: Optional[Callable] = None, *, k_steps: int = 1):
    """Wrap a training-step function for whole-step capture.

    ``step = paddle_tpu.jit_step(train_step)`` — ``train_step`` runs the
    usual eager code (forward, ``loss.backward()``, ``opt.step()``,
    ``opt.clear_grad()``); after one eager probe the entire step is
    compiled into a single donated XLA executable and replayed. Usable
    as a decorator. Gated by ``FLAGS_step_capture``; anything the
    capture cannot express falls back to the eager path with the reason
    in the flight recorder.

    ``k_steps=K`` (K > 1) returns a :class:`~paddle_tpu.jit.multi_step.
    MultiStepCapture` instead: every call takes a ``[K, ...]``-stacked
    batch block (leading axis = step index; ``io.DataLoader.fill_ring``
    builds them) and runs K whole steps inside ONE ``lax.scan``
    executable, returning ``[K]``-stacked outputs — the host touches
    the job once per block.
    """
    if function is None:
        return functools.partial(jit_step, k_steps=k_steps)
    if int(k_steps) > 1:
        from .multi_step import MultiStepCapture
        return MultiStepCapture(function, int(k_steps))
    return CapturedStep(function)
