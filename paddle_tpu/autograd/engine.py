"""Reverse-mode eager autograd engine.

Design (TPU-native counterpart of paddle/fluid/eager/backward.cc:105
`RunBackward` + grad_node_info.h:197 `GradNodeBase`):

* Every differentiable eager op records ONE `GradNode` holding the raw input
  arrays (primals) and the op identity. No hand-written per-op VJP code: the
  node's backward is `jax.vjp` of the op's pure kernel, jit-compiled and
  cached per (op, static-attrs, input avals) — so repeated backward steps hit
  the XLA executable cache exactly like forward ops do.
* Residual policy is rematerialization: the VJP recomputes the forward inside
  the cached executable instead of saving activations host-side (the analog
  of TensorWrapper, paddle/fluid/eager/tensor_wrapper.h:39, but chosen to
  trade FLOPs for HBM, which is the right default on TPU). Random ops take
  their PRNG key as an explicit primal, so recompute is bit-deterministic.
* `backward()` walks nodes in reverse creation order (a monotonic id gives a
  valid topological order for a tape), accumulating cotangents into node
  slots and leaf `.grad`.
"""

from __future__ import annotations

import contextlib
import functools
import heapq
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..observability import metrics as _obs_metrics
from ..observability import perf as _perf_mod

# -- grad mode ----------------------------------------------------------------
#
# Thread-local, not process-global: serving replicas run their step loops
# under no_grad() on background threads, and a shared flag would let the
# save/restore pairs of concurrent contexts interleave and strand the whole
# process with grads off. Each thread starts with grads enabled.

_grad_mode = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_grad_mode, "enabled", True)


def _set_grad_enabled(mode: bool) -> None:
    _grad_mode.enabled = bool(mode)


@contextlib.contextmanager
def no_grad():
    prev = is_grad_enabled()
    _grad_mode.enabled = False
    try:
        yield
    finally:
        _grad_mode.enabled = prev


@contextlib.contextmanager
def enable_grad():
    prev = is_grad_enabled()
    _grad_mode.enabled = True
    try:
        yield
    finally:
        _grad_mode.enabled = prev


# -- graph nodes --------------------------------------------------------------

_node_counter = 0


class GradNode:
    """One recorded op application on the tape."""

    __slots__ = ("id", "op_name", "vjp_callable", "primals", "in_tensors",
                 "out_avals", "out_grads", "hooks", "vjp_key", "dmask")

    def __init__(self, op_name: str, vjp_callable: Callable, primals, in_tensors,
                 out_avals, vjp_key=None, dmask=None):
        global _node_counter
        _node_counter += 1
        self.id = _node_counter
        self.op_name = op_name
        self.vjp_callable = vjp_callable   # (primals, cotangents) -> input grads
        self.primals = primals             # tuple of jax arrays
        # parent tensors aligned with primals (None for non-tensor primals
        # like PRNG keys); kept as strong refs — the tape owns the graph.
        self.in_tensors: List[Optional[Tensor]] = in_tensors
        self.out_avals = out_avals         # [(shape, dtype), ...]
        self.out_grads: List[Optional[jax.Array]] = [None] * len(out_avals)
        self.hooks: List[Callable] = []
        # structural identity of vjp_callable (dispatcher exec-cache key):
        # two nodes with equal vjp_key + primal avals compute the same
        # backward function. None (closure-held residuals, second-order
        # nodes, sot segments) keeps the node off the fused path.
        self.vjp_key = vjp_key
        self.dmask = dmask                 # per-primal "grad flows" mask

    def accumulate_out_grad(self, idx: int, g: jax.Array):
        cur = self.out_grads[idx]
        self.out_grads[idx] = g if cur is None else cur + g

    def __repr__(self):
        return f"GradNode({self.op_name}, id={self.id})"


def record_node(op_name, vjp_callable, primals, in_tensors, out_tensors,
                vjp_key=None, dmask=None) -> None:
    # tuple, not list: the fused-backward signature embeds it as-is
    # (jax shapes are tuples and dtypes hash by value)
    out_avals = tuple([(t._data.shape, t._data.dtype) for t in out_tensors])
    node = GradNode(op_name, vjp_callable, primals, in_tensors, out_avals,
                    vjp_key=vjp_key, dmask=dmask)
    for i, t in enumerate(out_tensors):
        t._node = node
        t._out_idx = i
        t._stop_gradient = False


# -- tensor hooks -------------------------------------------------------------
# Leaf hooks live ON the tensor object (not a WeakKeyDictionary keyed by
# Tensor: dict bucket probing would call the elementwise __eq__ and blow up
# on multi-element tensors whenever id-hashes collide).


class RemovableHandle:
    def __init__(self, store: list, fn):
        self._store, self._fn = store, fn

    def remove(self):
        try:
            self._store.remove(self._fn)
        except ValueError:
            pass


def register_tensor_hook(t: Tensor, hook: Callable):
    """Hook fires ONCE on the tensor's fully-accumulated gradient
    (paddle/pytorch semantics), not per contribution. Non-leaf tensors
    register on their producing node's output slot; leaves on the object."""
    if t._node is not None:
        entry = (t._out_idx, hook)
        t._node.hooks.append(entry)

        class _NodeHandle:
            def __init__(self, node, e):
                self._node, self._e = node, e

            def remove(self):
                try:
                    self._node.hooks.remove(self._e)
                except ValueError:
                    pass

        return _NodeHandle(t._node, entry)
    hooks = getattr(t, "_leaf_hooks", None)
    if hooks is None:
        hooks = []
        t._leaf_hooks = hooks
    hooks.append(hook)
    return RemovableHandle(hooks, hook)


def _run_hooks(hooks, g: jax.Array) -> jax.Array:
    for hook in hooks:  # hook: Tensor -> Tensor | None
        res = hook(g if isinstance(g, Tensor) else Tensor(g))
        if res is not None:
            if isinstance(g, Tensor):
                g = res if isinstance(res, Tensor) else Tensor(res)
            else:
                g = res._data if isinstance(res, Tensor) else res
    return g


# -- backward -----------------------------------------------------------------

def _is_float0(arr) -> bool:
    return getattr(arr, "dtype", None) == jax.dtypes.float0


def _second_order_vjp(fn, n_p: int, diff_slots):
    """VJP of a node's first-order vjp_callable.

    `fn(primals, cts) -> grads-aligned-with-primals` is jax-traceable (it
    closes over jitted executables / jax.vjp pullbacks, both of which trace),
    so differentiating THROUGH it gives the double-grad the reference eager
    engine computes by re-walking higher-order GradNodes
    (paddle/fluid/eager/general_grad.h; backward.cc:429 RunBackward with
    create_graph). Returns grads aligned with (primals + cts)."""

    def vjp2(primals2, cts2):
        prim, cts_in = primals2[:n_p], primals2[n_p:]

        def g_fn(*args):
            outs = fn(tuple(args[:n_p]), tuple(args[n_p:]))
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            return tuple(outs[i] for i in diff_slots)

        _, pull = jax.vjp(g_fn, *prim, *cts_in)
        return list(pull(tuple(cts2)))

    return vjp2


def _run_vjp_create_graph(node: "GradNode", ct_tensors):
    """Run one node's vjp with the call itself recorded on the tape.

    The produced input-grads become tape tensors whose GradNode is the VJP
    application — so a second backward() differentiates through them
    (create_graph=True semantics)."""
    fn = node.vjp_callable
    primals = node.primals
    cts = tuple(t._data for t in ct_tensors)
    raw = fn(primals, cts)
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    results: List[Optional[Tensor]] = []
    out_tensors: List[Tensor] = []
    diff_slots: List[int] = []
    for i, g in enumerate(raw):
        t_in = node.in_tensors[i] if i < len(node.in_tensors) else None
        if g is None or _is_float0(g) or t_in is None or t_in._stop_gradient:
            results.append(None)
        else:
            gt = Tensor(g)
            results.append(gt)
            out_tensors.append(gt)
            diff_slots.append(i)
    if out_tensors and is_grad_enabled():
        vjp2 = _second_order_vjp(fn, len(primals), tuple(diff_slots))
        record_node("grad::" + node.op_name, vjp2,
                    tuple(primals) + cts,
                    list(node.in_tensors) + list(ct_tensors),
                    out_tensors)
    return results


# -- structure-cached fused backward ------------------------------------------
#
# The per-node walk pays one PJRT launch per GradNode plus an eager
# `cur + g` add per cotangent accumulation (on the CPU, BENCH_r05:
# ~18.9us/op eager with tape vs ~0.3us/op compiled). A training iteration's
# tape has a STABLE structure, so the whole reverse walk is compiled once
# per structure into ONE XLA executable taking every node primal plus the
# seed cotangents and returning every leaf grad. First sight of a
# signature primes via the per-node walk; walks with tensor hooks,
# create_graph, capture, or nodes recorded without a vjp_key always take
# the per-node walk, so semantics are unchanged. Gated by
# FLAGS_fused_backward; the signature cache is bounded like the
# dispatcher's _CONST_CACHE.

_FUSED_CACHE: Dict[tuple, Any] = {}   # signature -> None (primed) | jitted fn
_FUSED_CACHE_MAX = 128
_MISSING = object()
_F_FUSED = None   # cached _Flag object (set lazily; registry import order)

# thrash breaker: a workload whose tape structure never repeats (e.g.
# variable-length batches) would otherwise pay O(tape) planning + signature
# hashing on EVERY backward with zero fused executions. After
# _MISS_STREAK_MAX consecutive never-seen structures the planner is
# bypassed, probing again every _PROBE_EVERY walks so a workload that
# settles into a stable structure regains the fused path.
_MISS_STREAK_MAX = 256
_PROBE_EVERY = 64
_miss_streak = 0
_probe_tick = 0

# observability: primed = first-sight structures, hit = fused executions,
# fallback = walks the fused path refused (hooks / unkeyed nodes),
# compile = jit builds, bypass = walks skipped by the thrash breaker.
# Read by tests and the profiler story.
fused_counters = {"primed": 0, "hit": 0, "fallback": 0, "compile": 0,
                  "bypass": 0}

# observability (observability/): the fused counters above stay the
# authoritative store (tests snapshot the dict) and are PUBLISHED as
# callback gauges — zero extra hot-path writes; plan/exec wall time go
# to always-on histograms read by Prometheus dumps and the profiler's
# Metrics section.
_M_BACKWARD = _obs_metrics.registry().counter(
    "autograd.backward.count", "backward() reverse walks")
_H_FUSED_PLAN = _obs_metrics.registry().histogram(
    "autograd.fused.plan_seconds",
    "fused-backward structural planning wall time")
_H_FUSED_EXEC = _obs_metrics.registry().histogram(
    "autograd.fused.exec_seconds",
    "fused-backward executable host dispatch time (async backends "
    "return before the device finishes; device time needs the profiler)")
for _k in ("primed", "hit", "fallback", "compile", "bypass"):
    _obs_metrics.registry().gauge(
        "autograd.fused." + _k,
        fn=lambda _k=_k: float(fused_counters[_k]),
        help=f"fused-backward '{_k}' events (engine.fused_counters)")
del _k


def _fused_enabled() -> bool:
    global _F_FUSED
    if _F_FUSED is None:
        from .. import flags
        f = flags._REGISTRY.get("fused_backward")
        if f is None:
            return False
        _F_FUSED = f
    return bool(_F_FUSED.value)


def _op_span_hook_ref():
    """The profiler's span factory, when one is recording (lazy read off
    the dispatcher module — no import cycle, no hot-path cost)."""
    d = sys.modules.get("paddle_tpu.ops.dispatcher")
    return getattr(d, "_OP_SPAN_HOOK", None) if d is not None else None


class _FusedPlan:
    __slots__ = ("signature", "nodes", "edges", "seed_plan", "leaf_tensors",
                 "ext_seeds")

    def __init__(self, signature, nodes, edges, seed_plan, leaf_tensors,
                 ext_seeds):
        self.signature = signature
        self.nodes = nodes            # reachable nodes, id-descending
        self.edges = edges            # per node: [(primal_idx, target), ...]
        self.seed_plan = seed_plan    # [(kind, pos, idx, implicit, shape, dt)]
        self.leaf_tensors = leaf_tensors
        self.ext_seeds = ext_seeds    # caller-provided seed arrays, in order


def _plan_fused(tensors, grad_tensors) -> Optional[_FusedPlan]:
    """Structural plan of the reachable tape, or None when the walk has
    features only the per-node path supports (hooks, unkeyed nodes).

    Reachability mirrors the eager walk exactly: an edge is live iff the
    input tensor exists, doesn't stop gradient, and its dmask slot says
    the vjp produces a grad there — so the reachable set (and therefore
    which leaves receive grads) is identical to the per-node walk's."""
    roots: List[Tuple[Tensor, Optional[jax.Array]]] = []
    for t, g in zip(tensors, grad_tensors):
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    f"grad can be implicitly created only for scalar outputs, "
                    f"got shape {t.shape}")
            g_arr = None
        else:
            g_arr = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        roots.append((t, g_arr))

    # One traversal does reachability AND edge discovery (edges in terms
    # of producer node ids, remapped to positions after the sort) — the
    # plan runs on EVERY backward, so a second pass re-reading the same
    # tensor attributes measurably dominates the fused path (~2.2ms for
    # a 200-node tape before the merge, ~95% of fused backward cost).
    leaf_slot: Dict[int, int] = {}       # id(tensor) -> slot
    leaf_tensors: List[Tensor] = []

    def slot_of(t: Tensor) -> Optional[int]:
        s = leaf_slot.get(id(t))
        if s is None:
            if getattr(t, "_leaf_hooks", None):
                return None              # leaf hook: per-node walk only
            s = len(leaf_tensors)
            leaf_slot[id(t)] = s
            leaf_tensors.append(t)
        return s

    node_by_id: Dict[int, GradNode] = {}
    work: List[GradNode] = []
    for t, _g in roots:
        n = t._node
        if n is not None and n.id not in node_by_id:
            node_by_id[n.id] = n
            work.append(n)
    raw: List[Tuple[int, GradNode, list]] = []  # (id, node, [(i, src|-1, k)])
    while work:
        n = work.pop()
        dm = n.dmask
        if n.hooks or n.vjp_key is None or dm is None \
                or len(n.in_tensors) > len(dm):
            return None
        es = []
        for i, t in enumerate(n.in_tensors):
            if t is None or t._stop_gradient or not dm[i]:
                continue
            p = t._node
            if p is None:
                s = slot_of(t)
                if s is None:
                    return None
                es.append((i, -1, s))    # src_id -1 marks a leaf target
            else:
                es.append((i, p.id, t._out_idx))
                if p.id not in node_by_id:
                    node_by_id[p.id] = p
                    work.append(p)
        raw.append((n.id, n, es))

    # eager pop order: producers always have lower ids than consumers, so
    # the heap visits reachable nodes in strictly decreasing id order
    # (ids are unique, so the sort never compares the nodes themselves)
    raw.sort(reverse=True)
    nodes = [r[1] for r in raw]
    pos_of = {r[0]: k for k, r in enumerate(raw)}

    # seed_plan doubles as the seed part of the signature: shapes are
    # tuples and dtype objects hash/compare by value, so they go in
    # as-is (str()-ing them costs ~10us per primal — measured dominating
    # the whole plan)
    seed_plan, ext_seeds = [], []
    for t, g_arr in roots:
        implicit = g_arr is None
        shape, dt = t._data.shape, t._data.dtype
        if t._node is not None:
            tgt = ("n", pos_of[t._node.id], t._out_idx)
        elif not t._stop_gradient:
            s = slot_of(t)
            if s is None:
                return None
            tgt = ("l", s, 0)
        else:
            continue                     # stop-gradient leaf root: no-op
        if not implicit:
            shape, dt = g_arr.shape, g_arr.dtype
            ext_seeds.append(g_arr)
        seed_plan.append((tgt[0], tgt[1], tgt[2], implicit, shape, dt))

    edges: List[List[Tuple[int, tuple]]] = []
    sig_nodes = []
    for _nid, n, es in raw:
        fes = [(i, ("l", k, 0) if src < 0 else ("n", pos_of[src], k))
               for i, src, k in es]
        edges.append(fes)
        try:
            ps = n.primals
            if len(ps) == 2:             # dominant arity: skip the comp frame
                p0, p1 = ps
                prim_sig = ((p0.shape, p0.dtype), (p1.shape, p1.dtype))
            else:
                prim_sig = tuple([(p.shape, p.dtype) for p in ps])
        except AttributeError:
            return None                  # non-array primal: walk it
        # out_avals is already a hashable tuple of (shape, dtype)
        # (record_node builds it that way) — it goes in as-is
        sig_nodes.append((n.vjp_key, prim_sig, n.out_avals, tuple(fes)))

    signature = (tuple(sig_nodes), tuple(seed_plan), len(leaf_tensors))
    return _FusedPlan(signature, nodes, edges, seed_plan, leaf_tensors,
                      ext_seeds)


def _make_runner(plan: _FusedPlan):
    """Pure reverse-walk runner: (node primals, seeds) -> leaf grads.
    Closes over the vjp callables of the CURRENT tape — for keyed
    nodes those are pure functions of (primals, cts) built from the
    shared exec cache, so replaying the traced program on a later tape
    with the same signature is exact (no arrays are baked in). Jitted
    by the fused-backward cache; called INLINE (unjitted) when a
    step-capture trace is ambient, so the outer whole-step executable
    absorbs the walk."""
    vjps = [n.vjp_callable for n in plan.nodes]
    out_avals = [n.out_avals for n in plan.nodes]
    edges = plan.edges
    seed_plan = plan.seed_plan
    n_leaves = len(plan.leaf_tensors)

    def run(prims, ext_seeds):
        slots = [[None] * len(av) for av in out_avals]
        leaf_g: List[Optional[jax.Array]] = [None] * n_leaves
        si = 0
        for kind, pos, idx, implicit, shape, dt in seed_plan:
            if implicit:
                g = jnp.ones(shape, dt)
            else:
                g = ext_seeds[si]
                si += 1
            if kind == "n":
                cur = slots[pos][idx]
                slots[pos][idx] = g if cur is None else cur + g
            else:
                cur = leaf_g[pos]
                leaf_g[pos] = g if cur is None else cur + g
        for pos, vjp in enumerate(vjps):
            cts = tuple(
                (g.astype(dt) if g.dtype != dt else g)
                if g is not None else jnp.zeros(shape, dt)
                for g, (shape, dt) in zip(slots[pos], out_avals[pos]))
            in_grads = vjp(prims[pos], cts)
            if not isinstance(in_grads, (tuple, list)):
                in_grads = (in_grads,)
            for i, (kind, j, k) in edges[pos]:
                if i >= len(in_grads):
                    continue
                g = in_grads[i]
                if g is None or _is_float0(g):
                    continue
                if kind == "n":
                    cur = slots[j][k]
                    slots[j][k] = g if cur is None else cur + g
                else:
                    cur = leaf_g[j]
                    leaf_g[j] = g if cur is None else cur + g
            slots[pos] = None            # free traced intermediates early
        return leaf_g

    return run


def _build_fused_runner(plan: _FusedPlan):
    runner = jax.jit(_make_runner(plan))
    # persistent exec store: identity on the lowered HLO digest, so the
    # process-local pieces of plan.signature never reach disk
    from ..jit import exec_store as _exec_store
    runner = _exec_store.persistent(
        runner, "fused_bwd", label="fused_bwd",
        perf_key=("fused_bwd", plan.signature))
    if _perf_mod.enabled():
        # one ledger row per stable tape structure, under the same
        # signature that keys the fused cache (wrap() is a passthrough
        # when the plane is off at compile time)
        runner = _perf_mod.ledger().wrap(
            ("fused_bwd", plan.signature), "backward", runner,
            name="fused_bwd")
    return runner


# Step-capture integration (jit/step_capture.py): non-None while a
# whole-step capture trace is active. backward() then runs the planner's
# reverse walk INLINE inside the ambient trace (the outer executable
# fuses it), and walks the planner can't express — tensor hooks,
# structurally-unkeyed nodes — or higher-order requests abort the
# capture so the step replays on the exact eager path instead.
_CAPTURE = None


def _capture_backward(cap, tensors, grad_tensors, retain_graph,
                      accumulate_ids) -> None:
    """Run the whole reverse walk inline under the ambient capture trace."""
    plan = _plan_fused(tensors, grad_tensors)
    if plan is None:
        cap.abort("tape has tensor hooks or structurally-unkeyed nodes "
                  "(sot/to_static segments)")
    if plan.leaf_tensors:
        prims = tuple([n.primals for n in plan.nodes])
        results = _make_runner(plan)(prims, plan.ext_seeds)
        for t, g in zip(plan.leaf_tensors, results):
            if accumulate_ids is not None and id(t) not in accumulate_ids:
                continue
            if t._grad is None:
                t._grad = Tensor(g)
            else:
                t._grad._set_data(t._grad._data + g)
    if not retain_graph:
        for t in tensors:
            _free_graph(t)


def _fused_backward(tensors, grad_tensors, retain_graph,
                    accumulate_ids) -> bool:
    """Try the single-executable walk; False -> caller runs the per-node
    walk (first sight of a structure, or a walk it can't express)."""
    global _miss_streak, _probe_tick
    if _miss_streak >= _MISS_STREAK_MAX:
        _probe_tick += 1
        if _probe_tick % _PROBE_EVERY:
            fused_counters["bypass"] += 1
            return False
    t_plan = time.perf_counter()
    plan = _plan_fused(tensors, grad_tensors)
    _H_FUSED_PLAN.observe(time.perf_counter() - t_plan)
    if plan is None:
        # permanently-unfusable tapes (leaf hooks, sot/to_static nodes
        # recorded without a vjp_key) must feed the breaker too, or a
        # hooked training loop pays the O(tape) planning tax on every
        # backward forever with zero fused executions
        fused_counters["fallback"] += 1
        _miss_streak += 1
        return False
    if not plan.leaf_tensors:
        # no grad ever becomes observable (everything dies at
        # stop_gradient): skip the launches entirely
        if not retain_graph:
            for t in tensors:
                _free_graph(t)
        return True
    entry = _FUSED_CACHE.pop(plan.signature, _MISSING)
    if entry is not _MISSING:
        # re-insert: eviction is oldest-first, so a hit refreshes the
        # entry's age and a hot structure survives churn from one-shot
        # structures priming around it
        _FUSED_CACHE[plan.signature] = entry
    if entry is _MISSING:
        if len(_FUSED_CACHE) >= _FUSED_CACHE_MAX:
            # FIFO-evict one entry: a wholesale clear() would recompile
            # every live structure after each overflow
            _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
        _FUSED_CACHE[plan.signature] = None
        fused_counters["primed"] += 1
        _miss_streak += 1
        return False                     # prime via the per-node walk
    if entry is None:
        entry = _build_fused_runner(plan)
        _FUSED_CACHE[plan.signature] = entry
        fused_counters["compile"] += 1
    fused_counters["hit"] += 1
    _miss_streak = 0
    # keyed nodes are recorded by the dispatcher, which always passes
    # primals as a tuple — no per-node re-tupling needed
    prims = tuple([n.primals for n in plan.nodes])
    hook = _op_span_hook_ref()
    t_exec = time.perf_counter()
    if hook is not None:
        with hook("fused_backward"):
            results = entry(prims, plan.ext_seeds)
    else:
        results = entry(prims, plan.ext_seeds)
    _H_FUSED_EXEC.observe(time.perf_counter() - t_exec)
    for t, g in zip(plan.leaf_tensors, results):
        if accumulate_ids is not None and id(t) not in accumulate_ids:
            continue
        if t._grad is None:
            t._grad = Tensor(g)
        else:
            t._grad._set_data(t._grad._data + g)
    if not retain_graph:
        for t in tensors:
            _free_graph(t)
    return True


def backward(tensors: Sequence[Tensor], grad_tensors: Sequence[Optional[Tensor]],
             retain_graph: bool = False, create_graph: bool = False,
             accumulate_ids=None, capture: Sequence[Tensor] = ()) -> None:
    """Run reverse accumulation from `tensors` into leaf `.grad` slots.

    `accumulate_ids`: optional set of id(tensor) — when given, only those
    leaves receive .grad (the functional-grad path: torch/paddle
    autograd.grad semantics, which never touch other leaves' .grad).
    `capture`: non-leaf tensors whose fully-accumulated cotangent should be
    deposited into their .grad too (functional grad() with intermediate
    inputs — the walk normally flows THROUGH non-leaves without storing)."""
    _M_BACKWARD.inc()
    if _CAPTURE is not None:
        if create_graph:
            _CAPTURE.abort("backward(create_graph=True) inside a "
                           "captured step")
        if capture:
            _CAPTURE.abort("functional grad() capture inside a "
                           "captured step")
        _capture_backward(_CAPTURE, tensors, grad_tensors, retain_graph,
                          accumulate_ids)
        return
    if not create_graph and not capture and _fused_enabled():
        if _fused_backward(tensors, grad_tensors, retain_graph,
                           accumulate_ids):
            return
    # Seed cotangents.
    heap = []          # max-heap over node id → reverse topological order
    in_heap: Dict[int, GradNode] = {}

    def push(node: GradNode):
        if node.id not in in_heap:
            in_heap[node.id] = node
            heapq.heappush(heap, -node.id)

    leaf_acc: Dict[int, list] = {}  # id(tensor) -> [tensor, accumulated grad]

    def accumulate_leaf(t: Tensor, g: jax.Array):
        slot = leaf_acc.get(id(t))
        if slot is None:
            leaf_acc[id(t)] = [t, g]
        else:
            slot[1] = slot[1] + g

    for t, g in zip(tensors, grad_tensors):
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    f"grad can be implicitly created only for scalar outputs, "
                    f"got shape {t.shape}")
            g_arr = jnp.ones_like(t._data)
        else:
            g_arr = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        if create_graph:
            # seed cotangents join the tape; keep the caller's Tensor
            # identity (leaf or not) so grads W.R.T. grad_outputs work —
            # the double-vjp pattern differentiates through the seed
            g_arr = g if isinstance(g, Tensor) else Tensor(g_arr)
        if t._node is None:
            if not t._stop_gradient:
                accumulate_leaf(t, g_arr)
            continue
        t._node.accumulate_out_grad(t._out_idx, g_arr)
        push(t._node)

    # (node-id, out_idx) -> non-leaf input tensor whose cotangent we capture
    cap_slots = {(t._node.id, t._out_idx): t for t in capture
                 if t._node is not None}

    span_hook = _op_span_hook_ref()
    while heap:
        node = in_heap.pop(-heapq.heappop(heap))
        # reverse-creation-order pop ⇒ every consumer already ran, so
        # out_grads are fully accumulated here: slot hooks fire exactly once.
        for idx, hook in node.hooks:
            if node.out_grads[idx] is not None:
                node.out_grads[idx] = _run_hooks([hook], node.out_grads[idx])
        if cap_slots:  # after hooks: captured grad == the propagated one
            for idx, g in enumerate(node.out_grads):
                t_cap = cap_slots.get((node.id, idx))
                if t_cap is not None and g is not None:
                    accumulate_leaf(t_cap, g)
        # cotangent dtype follows the primal output's dtype: accumulation
        # across mixed-precision consumers can promote (bf16+f32 -> f32
        # under AMP), and jax.vjp requires an exact dtype match
        if create_graph:
            ct_tensors = [
                (g.astype(dtype) if g._data.dtype != dtype else g)
                if g is not None else Tensor(jnp.zeros(shape, dtype))
                for g, (shape, dtype) in zip(node.out_grads, node.out_avals)
            ]
            in_grads = _run_vjp_create_graph(node, ct_tensors)
        else:
            cts = tuple(
                (g.astype(dtype) if g.dtype != dtype else g)
                if g is not None else jnp.zeros(shape, dtype)
                for g, (shape, dtype) in zip(node.out_grads, node.out_avals)
            )
            if span_hook is not None:
                with span_hook("grad::" + node.op_name):
                    in_grads = node.vjp_callable(node.primals, cts)
            else:
                in_grads = node.vjp_callable(node.primals, cts)
        if not isinstance(in_grads, (tuple, list)):
            in_grads = (in_grads,)
        for t, g in zip(node.in_tensors, in_grads):
            if t is None or g is None or _is_float0(g):
                continue
            if t._stop_gradient:  # stop_gradient cuts the graph (paddle semantics)
                continue
            if t._node is None:
                accumulate_leaf(t, g)
            else:
                t._node.accumulate_out_grad(t._out_idx, g)
                push(t._node)
        node.out_grads = [None] * len(node.out_avals)  # per-pass accumulator

    for _, (t, g) in leaf_acc.items():
        if accumulate_ids is not None and id(t) not in accumulate_ids:
            continue
        g = _run_hooks(getattr(t, "_leaf_hooks", None) or (), g)
        if create_graph:
            gt = g if isinstance(g, Tensor) else Tensor(g)
            # keep the tape connection: .grad is a non-leaf tensor whose
            # GradNode is the recorded VJP application
            t._grad = gt if t._grad is None else t._grad + gt
        elif t._grad is None:
            t._grad = Tensor(g._data if isinstance(g, Tensor) else g)
        else:
            t._grad._set_data(
                t._grad._data + (g._data if isinstance(g, Tensor) else g))

    if not (retain_graph or create_graph):
        for t in tensors:
            _free_graph(t)


def _free_graph(t: Tensor):
    # Release primal references so buffers can be freed; the tape is
    # per-iteration, so dropping the root's node chain is enough (GC handles
    # the rest since nodes only point backwards).
    t._node = None


def grad(outputs, inputs, grad_outputs=None, retain_graph=False, create_graph=False,
         allow_unused=False):
    """Functional paddle.grad: returns grads of `outputs` w.r.t. `inputs`.

    Implemented over the same tape. With create_graph=True every VJP
    application during the walk is itself recorded as a tape op (the
    returned grads carry a GradNode), so differentiating them again — via
    another grad()/backward() — computes true double grads, matching the
    reference eager engine's higher-order path
    (paddle/fluid/eager/general_grad.h, backward.cc:429).
    """
    outputs = [outputs] if isinstance(outputs, Tensor) else list(outputs)
    inputs = [inputs] if isinstance(inputs, Tensor) else list(inputs)
    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]
    saved = [(t, t._grad) for t in inputs]
    for t in inputs:
        t._grad = None
    backward(outputs, grad_outputs,
             retain_graph=retain_graph or create_graph,
             create_graph=create_graph,
             accumulate_ids={id(t) for t in inputs},
             capture=[t for t in inputs if t._node is not None])
    result, unused = [], None
    for i, (t, old) in enumerate(saved):
        g = t._grad
        if g is None and unused is None:
            unused = i
        result.append(g)
        t._grad = old  # restore ALL before any raise: no side effects
    if unused is not None and not allow_unused:
        raise ValueError(
            f"The {unused}th input tensor is not used in the graph of "
            f"the given outputs (set allow_unused=True to return None "
            f"for it)")
    return result
