"""Multi-step capture: K whole training steps in ONE device-side loop.

Whole-step capture (jit/step_capture.py) made one step one executable,
but the host still pays dispatch, input transfer, and replay bookkeeping
per step. This module captures a ``lax.scan`` whose body is the SAME
traced step body single-step capture compiles (``_make_step_body``) and
runs it K times inside one donated executable: the carry holds the
params/optimizer state, gradients, per-optimizer (states, masters,
device step scalar) packs, and the RNG key — so the traced lr/step
scalars advance *inside* the loop exactly as K sequential single-step
replays would advance them — and the xs are a ``[K, ...]``-stacked
batch block (``io.DataLoader.fill_ring`` builds those from its prefetch
thread) plus a ``[K]`` lr schedule stack computed by advancing a shadow
copy of the host scheduler. Loss/metric outputs come back ``[K]``-
stacked and are read once per block.

Host effects recorded at capture time (optimizer step-count deltas,
no-arg scheduler advances) are re-applied K times per block replay
(K-1 after the capture launch itself, whose trace ran the host side
once). The anomaly sentinel's cumulative-skip channel rides the carry
like any other state tensor, so K-step bodies keep per-lane skip
semantics for free and ``Optimizer.consume_anomaly()`` reconciles once
per block.

Blocks that cannot run multi-step — a stacked leading axis that does
not match K, or any single-step unfusable edge — fall back to K eager
steps with the reason frozen in ``MULTI_STEP_FALLBACK_REASONS`` (the
graftcheck taxonomy rule unions every ``*_REASONS`` set); epoch tails
shorter than K are the caller's job (``hapi.Model.fit`` routes them
through the existing single-step capture and counts them in
``multi_step.tail_steps``).
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags
from ..core.tensor import Tensor
from ..observability import flight_recorder as _flight_mod
from ..observability import metrics as _metrics_mod
from ..observability import tracing as _tracing
from ..ops import dispatcher
from .step_capture import (CaptureAbort, CapturedStep, _F_SCREEN, _F_STEP,
                           _HostSnapshot, _MISS_STREAK_MAX, _PRIMED,
                           _PROBE_EVERY, _flatten_args, capture_counters)

__all__ = ["MultiStepCapture", "MULTI_STEP_FALLBACK_REASONS",
           "multi_counters"]

# Frozen multi-step fallback taxonomy. Single-step reasons (trace
# failures, unfusable edges) keep their step_capture.FALLBACK_REASONS
# spelling; only the block-shaped edges live here. The graftcheck
# taxonomy rule collects every module-level *_REASONS frozenset, so
# these join the same checked union.
MULTI_STEP_FALLBACK_REASONS = frozenset({
    "FLAGS_multi_step disabled",
    "ring block shorter than k_steps (epoch tail)",
    "per-step host callbacks need single-step dispatch",
    "multi-step block skipped inside a rewind poison window",
})

multi_counters = {"blocks": 0, "replays": 0, "fallbacks": 0,
                  "tail_steps": 0}
for _k in ("blocks", "replays", "fallbacks", "tail_steps"):
    _metrics_mod.registry().gauge(
        "multi_step." + _k,
        fn=lambda _k=_k: float(multi_counters[_k]),
        help=f"multi-step capture '{_k}' events (jit/multi_step.py)")
del _k


def _split_block(args, kwargs, k: int):
    """Slice a [K, ...]-stacked (args, kwargs) block into K per-step
    call trees. Raises on a dynamic leaf whose leading axis is not K —
    a malformed block is a caller bug, not a fallback edge."""
    leaves, treedef = jax.tree.flatten(
        (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
    for leaf in leaves:
        if isinstance(leaf, Tensor):
            shape = leaf._data.shape
        elif isinstance(leaf, (jax.Array, np.ndarray)):
            shape = leaf.shape
        else:
            continue
        if tuple(shape[:1]) != (k,):
            raise ValueError(
                f"multi-step block: every dynamic leaf needs a leading "
                f"[K={k}] step axis, got shape {tuple(shape)} — stack "
                f"K batches (io.DataLoader.fill_ring) before the call")
    steps = []
    for i in range(k):
        lv = []
        for leaf in leaves:
            if isinstance(leaf, Tensor):
                lv.append(Tensor(leaf._data[i]))
            elif isinstance(leaf, (jax.Array, np.ndarray)):
                lv.append(leaf[i])
            else:
                lv.append(leaf)
        steps.append(jax.tree.unflatten(treedef, lv))
    return steps


def _stack_block_outputs(outs: List[Any]):
    """Stack K per-step output trees into one [K]-stacked tree, the
    same shape the scanned executable returns."""
    flats = [jax.tree.flatten(o, is_leaf=lambda x: isinstance(x, Tensor))
             for o in outs]
    leaves0, tree0 = flats[0]
    stacked: List[Any] = []
    for j in range(len(leaves0)):
        col = [f[0][j] for f in flats]
        if isinstance(col[0], Tensor):
            stacked.append(Tensor(jnp.stack([t._data for t in col])))
        elif isinstance(col[0], (jax.Array, np.ndarray)):
            stacked.append(jnp.stack(col))
        elif isinstance(col[0], (bool, int, float)):
            stacked.append(jnp.asarray(col))
        else:
            stacked.append(col)   # opaque host values: per-step list
    return jax.tree.unflatten(tree0, stacked)


def record_block_fallback(reason: str, detail=None) -> None:
    """Record a block-level fallback decided OUTSIDE a capture object
    (e.g. hapi.fit declining the multi-step path before building one).
    The reason must be a frozen member of MULTI_STEP_FALLBACK_REASONS."""
    if reason not in MULTI_STEP_FALLBACK_REASONS:
        raise ValueError(f"unregistered multi_step fallback reason "
                         f"{reason!r} — add it to "
                         f"MULTI_STEP_FALLBACK_REASONS")
    multi_counters["fallbacks"] += 1
    msg = reason if detail is None else f"{reason}: {detail}"
    if _flight_mod.enabled():
        _flight_mod.recorder().record("multi_step.fallback", (msg,), reason)


class MultiStepCapture(CapturedStep):
    """K-step block capture: each call takes a [K, ...]-stacked batch
    block and runs K whole steps inside one scanned executable.

    Lifecycle mirrors :class:`CapturedStep` — the first block probes
    (step 0 instrumented, the rest eager), the second block captures
    the scan, every later block replays. The per-step traced body is
    byte-for-byte the single-step body, so a block is equivalent to K
    sequential single-step replays: same carry chaining of the device
    step scalars, same RNG split-per-step chain, same donated state."""

    _perf_kind = "multi"       # K-step blocks get their own ledger kind

    def __init__(self, fn, k_steps: int):
        if int(k_steps) < 2:
            raise ValueError(f"k_steps must be >= 2, got {k_steps} "
                             f"(use jit_step(fn) for single-step capture)")
        super().__init__(fn)
        self.k_steps = int(k_steps)
        self._block_lr_cache: Dict[int, tuple] = {}  # id(opt)->(ks, [K])

    # -- fallbacks -----------------------------------------------------------
    def _fallback(self, reason, detail=None):
        multi_counters["fallbacks"] += 1
        if reason in MULTI_STEP_FALLBACK_REASONS:
            msg = reason if detail is None else f"{reason}: {detail}"
            if msg != self._last_reason:
                self._last_reason = msg
                if _flight_mod.enabled():
                    _flight_mod.recorder().record(
                        "multi_step.fallback", (msg,), reason)
        else:
            super()._fallback(reason, detail)

    # -- capture hooks -------------------------------------------------------
    def _wrap_body(self, step_fn):
        k = self.k_steps

        def multi_fn(state_arrs, grads_in, packs, key, lrs, dyn):
            def body(carry, xs):
                st, gr, pk, ky = carry
                lrs_i, dyn_i = xs
                out, st, gr, pk, ky = step_fn(st, gr, pk, ky, lrs_i, dyn_i)
                return (st, gr, pk, ky), out

            carry, outs = jax.lax.scan(
                body, (state_arrs, grads_in, packs, key), (lrs, dyn),
                length=k)
            st, gr, pk, ky = carry
            return outs, st, gr, pk, ky

        return multi_fn

    def _lr_args(self, d) -> tuple:
        """[K] lr stacks per optimizer: advance a shadow copy of the
        host scheduler K times and stack the schedule, cached so a
        steady schedule pays one transfer per distinct K-window."""
        k = self.k_steps
        if d.sched_deltas:
            snap = _HostSnapshot(d)
            try:
                cols = [[] for _ in d.opts]
                for _ in range(k):
                    for i, o in enumerate(d.opts):
                        cols[i].append(float(o.get_lr()))
                    for sref, delta in d.sched_deltas:
                        s = sref()
                        if s is not None:
                            for _ in range(delta):
                                s.step()
            finally:
                snap.restore()
        else:
            cols = [[float(o.get_lr())] * k for o in d.opts]
        out = []
        for o, col in zip(d.opts, cols):
            sig = tuple(col)
            c = self._block_lr_cache.get(id(o))
            if c is None or c[0] != sig:
                c = (sig, jnp.asarray(col, jnp.float32))
                self._block_lr_cache[id(o)] = c
            out.append(c[1])
        return tuple(out)

    def _host_reps(self, host_effects: bool) -> int:
        # the capture launch's trace ran the step's host side once
        return self.k_steps if host_effects else self.k_steps - 1

    # -- probe ---------------------------------------------------------------
    def _probe_and_prime(self, args, kwargs, arg_sig):
        # probe on step 0's slice (instrumented eager run, discovers the
        # persistent state); the block's remaining K-1 warmup steps run
        # plain eager so the caller still gets K trained steps back
        steps = _split_block(args, kwargs, self.k_steps)
        a0, k0 = steps[0]
        outs = [super()._probe_and_prime(a0, k0, arg_sig)]
        for a_i, k_i in steps[1:]:
            outs.append(self._fn(*a_i, **k_i))
        return _stack_block_outputs(outs)

    def _run_block_eager(self, args, kwargs):
        outs = [self._fn(*a, **kw)
                for a, kw in _split_block(args, kwargs, self.k_steps)]
        return _stack_block_outputs(outs)

    # -- dispatch ------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if not _F_STEP.value:
            self._fallback("FLAGS_step_capture disabled")
            return self._run_block_eager(args, kwargs)
        if dispatcher._STEP_TRACE is not None \
                or dispatcher._STEP_PROBE is not None \
                or not jax.core.trace_ctx.is_top_level():
            # nested inside another capture/trace: the outer program
            # absorbs the steps one by one
            return self._run_block_eager(args, kwargs)

        if _F_SCREEN.value:
            if self._screen is None:
                self._screen = self._compute_screen()
            if self._screen:
                self._fallback("statically screened", self._screen)
                return self._run_block_eager(args, kwargs)

        if self._streak >= _MISS_STREAK_MAX:
            self._probe_tick += 1
            if self._probe_tick % _PROBE_EVERY:
                capture_counters["bypass"] += 1
                return self._run_block_eager(args, kwargs)

        flat = _flatten_args(args, kwargs)
        if flat is None:
            self._fallback("unhashable static argument")
            return self._run_block_eager(args, kwargs)
        arg_sig, dyn_arrays, grad_arg, rebuild = flat
        if grad_arg:
            self._fallback("input argument requires grad (grads must "
                           "land on the caller's tensor)")
            return self._run_block_eager(args, kwargs)

        if self._disc is None:
            return self._probe_and_prime(args, kwargs, arg_sig)

        key = (flags.version, arg_sig, self._state_sig())
        ent = self._entries.get(key)
        if ent is None:
            self._streak += 1
            return self._probe_and_prime(args, kwargs, arg_sig)
        if ent is _PRIMED:
            try:
                with _tracing.span("step_capture.multi"):
                    out = self._attempt_capture(key, dyn_arrays, rebuild)
            except CaptureAbort as e:
                self._put_entry(key, ("unfusable", e.reason, e.detail))
                self._disc = None   # a stale discovery gets one re-probe
                self._fallback(e.reason, e.detail)
                return self._run_block_eager(args, kwargs)
            capture_counters["captures"] += 1
            multi_counters["blocks"] += 1
            self._streak = 0
            return out
        if isinstance(ent, tuple):      # ("unfusable", reason, detail)
            self._fallback(ent[1], ent[2])
            return self._run_block_eager(args, kwargs)
        self._entries.pop(key)
        self._entries[key] = ent
        with _tracing.span("step_capture.multi"):
            out = self._replay(ent, dyn_arrays)
        if out is None:                 # baked-constant invalidation
            return self._probe_and_prime(args, kwargs, arg_sig)
        multi_counters["blocks"] += 1
        multi_counters["replays"] += 1
        self._streak = 0
        return out
