"""to_static + TrainStep implementation. See package docstring."""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..autograd import engine
from ..core import generator
from ..core.tensor import Tensor
from ..nn.layer_base import Layer
from ..observability import tracing as _tracing


# Held while tensors are swapped: a model shared by threads (replicas of one
# fleet) is traced by one of them at a time, and a thread that gathers the
# same tensors' buffers as arguments takes it too, so it never reads tracers
_SWAP_LOCK = threading.RLock()


@contextlib.contextmanager
def _swap_state(tensors: List[Tensor], arrays: List[jax.Array]):
    """Temporarily rebind tensor buffers (to tracers during tracing)."""
    with _SWAP_LOCK:
        saved = [t._data for t in tensors]
        for t, a in zip(tensors, arrays):
            t._data = a
        try:
            yield
        finally:
            for t, s in zip(tensors, saved):
                t._data = s


@contextlib.contextmanager
def _traced_rng(key: jax.Array):
    """Route generator.next_key() through a traced key during tracing so
    random ops stay random across compiled steps."""
    gen = generator.default_generator()
    box = {"n": 0}
    orig = gen.next_key

    def traced_next_key():
        # counter fold_in, NOT a sequential split chain: every subkey
        # derives independently from the step's base key, so XLA can
        # compute all mask keys in parallel instead of serializing ~40
        # tiny threefry key-derivations through a data dependency (a
        # measured ~4ms/step on BERT-base dropout)
        box["n"] += 1
        return jax.random.fold_in(key, box["n"])

    gen.next_key = traced_next_key
    try:
        yield
    finally:
        gen.next_key = orig


def _collect_state(layer: Layer) -> Tuple[List[Tensor], List[Tensor]]:
    from ..nn import layer_base
    # LazyGuard-deferred params must materialize before a compiled path
    # snapshots their buffers (zeros placeholders would be baked into the
    # jit args and the real init silently lost)
    layer_base._materialize_params(layer)
    params = list(layer.parameters())
    buffers = [b for _, b in layer.named_buffers()]
    return params, buffers


class StaticFunction:
    """Result of to_static: a compiled forward with buffer-state threading.

    Trainable: the whole compiled forward is recorded as ONE GradNode whose
    VJP is jax.vjp of the pure function — the analog of the reference's
    run_program_op grad (paddle/fluid/operators/run_program_op) that makes
    a to_static sub-program differentiable inside the eager tape."""

    def __init__(self, fn: Callable, layer: Optional[Layer]):
        self._fn = fn
        self._layer = layer
        self._compiled = None
        self._vjp_cache = {}
        functools.update_wrapper(self, fn, updated=())

    def _pure(self, param_arrays, buffer_arrays, rng, in_arrays, kw_arrays,
              static_kwargs):
        params, buffers = (_collect_state(self._layer)
                           if self._layer is not None else ([], []))
        with _swap_state(params + buffers,
                         list(param_arrays) + list(buffer_arrays)):
            with _traced_rng(rng), engine.no_grad():
                args = jax.tree.map(Tensor, list(in_arrays))
                kwargs = {k: Tensor(v) for k, v in kw_arrays.items()}
                out = self._fn(*args, **dict(static_kwargs), **kwargs)
                out_arrays = jax.tree.map(
                    lambda t: t._data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda x: isinstance(x, Tensor))
                new_buf = [b._data for b in buffers]
        return out_arrays, new_buf

    def _build(self):
        self._compiled = jax.jit(self._pure, static_argnums=(5,))

    def _get_vjp(self, pmask, imask, static_kwargs):
        key = (pmask, imask, static_kwargs)
        fn = self._vjp_cache.get(key)
        if fn is None:
            def vjp_run(diff_primals, param_arrays, buffer_arrays, rng,
                        in_arrays, kw_arrays, cts_f):
                def f(*dp):
                    it = iter(dp)
                    pa = [next(it) if m else a
                          for a, m in zip(param_arrays, pmask)]
                    ia = [next(it) if m else a
                          for a, m in zip(in_arrays, imask)]
                    outs, _ = self._pure(pa, buffer_arrays, rng, ia, kw_arrays,
                                         static_kwargs)
                    flat = jax.tree.leaves(outs)
                    return tuple(o for o in flat
                                 if jnp.issubdtype(o.dtype, jnp.inexact))

                _, vjp = jax.vjp(f, *diff_primals)
                return vjp(tuple(cts_f))

            fn = jax.jit(vjp_run)
            self._vjp_cache[key] = fn
        return fn

    def __call__(self, *args, **kwargs):
        if self._compiled is None:
            self._build()
        params, buffers = (_collect_state(self._layer)
                           if self._layer is not None else ([], []))
        in_tensors = [a if isinstance(a, Tensor) else None for a in args]
        in_arrays = [a._data if isinstance(a, Tensor) else jnp.asarray(a)
                     for a in args]
        kw_arrays = {k: v._data for k, v in kwargs.items()
                     if isinstance(v, Tensor)}
        static_kwargs = tuple(sorted(
            (k, v) for k, v in kwargs.items() if not isinstance(v, Tensor)))
        rng = generator.next_key()
        param_arrays = tuple(p._data for p in params)
        buffer_arrays = tuple(b._data for b in buffers)
        out_arrays, new_buf = self._compiled(
            param_arrays, buffer_arrays, rng, in_arrays, kw_arrays,
            static_kwargs)
        for b, nb in zip(buffers, new_buf):
            b._set_data(nb)
        out = jax.tree.map(Tensor, out_arrays)

        # -- autograd wiring: one node for the whole compiled program --------
        if engine.is_grad_enabled():
            pmask = tuple(not p.stop_gradient for p in params)
            imask = tuple(t is not None and not t.stop_gradient
                          and jnp.issubdtype(t.dtype, jnp.inexact)
                          for t in in_tensors)
            if any(pmask) or any(imask):
                node_parents = [p for p, m in zip(params, pmask) if m] + \
                    [t for t, m in zip(in_tensors, imask) if m]
                diff_primals = tuple(a for a, m in zip(param_arrays, pmask) if m) \
                    + tuple(a for a, m in zip(in_arrays, imask) if m)
                out_leaves = [t for t in jax.tree.leaves(
                    out, is_leaf=lambda x: isinstance(x, Tensor))]
                out_dtypes = [t.dtype for t in out_leaves]
                vjp_fn = self._get_vjp(pmask, imask, static_kwargs)

                def vjp_callable(primals, cts, _saved=(param_arrays,
                                                       buffer_arrays, rng,
                                                       in_arrays, kw_arrays)):
                    cts_f = [c for c, dt in zip(cts, out_dtypes)
                             if jnp.issubdtype(dt, jnp.inexact)]
                    return vjp_fn(primals, _saved[0], _saved[1], _saved[2],
                                  _saved[3], _saved[4], cts_f)

                engine.record_node("to_static", vjp_callable, diff_primals,
                                   node_parents, out_leaves)
        return out


def to_static(function=None, input_spec=None, build_strategy=None,
              full_graph=True, backend=None):
    """paddle.jit.to_static (reference jit/api.py:171). Works as decorator or
    wrapper over a function or a Layer (compiles its forward).

    full_graph=True (default) uses the whole-graph tracer (StaticFunction —
    data-dependent Python control flow is not allowed, reference AST path).
    full_graph=False uses SOT-lite (jit/sot.py): eager trace + compiled
    segments with graph-break guards, surviving data-dependent control
    flow (reference sot/translate.py)."""

    def wrap(fn):
        if not full_graph:
            from .sot import SOTFunction
            if isinstance(fn, Layer):
                layer = fn
                sf = SOTFunction(lambda *a, **k: layer.forward(*a, **k))
                return _LayerStaticWrapper(layer, sf)
            return SOTFunction(fn)
        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(lambda *a, **k: layer.forward(*a, **k), layer)
            return _LayerStaticWrapper(layer, sf)
        return StaticFunction(fn, None)

    if function is not None:
        return wrap(function)
    return wrap


class _LayerStaticWrapper:
    """Callable wrapper: compiled forward + delegation to the Layer."""

    def __init__(self, layer: Layer, sf: StaticFunction):
        self._layer = layer
        self._sf = sf

    def __call__(self, *args, **kwargs):
        return self._sf(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._layer, name)


def not_to_static(fn=None):
    """Marker for functions excluded from tracing (reference jit.not_to_static);
    tracing is value-transparent here, so this is an identity."""
    return fn


def _to_array(t):
    return t._data if isinstance(t, Tensor) else jnp.asarray(t)


def _aval(a) -> jax.ShapeDtypeStruct:
    """An argument's shape for lowering; an array that was placed keeps its
    placement, as a call of the jitted function would have kept it."""
    return jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding if a.committed else None)


class TrainStep:
    """Whole-training-step compilation: loss fwd + grads + optimizer update
    in one donated XLA program.

    train = TrainStep(model, loss_fn, opt)   # loss_fn(model_out..., *labels)
    loss = train(inputs, labels)

    The optimizer's pure `_update` rule and state are reused, so eager
    optimizer.step() and compiled TrainStep produce identical updates."""

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 grad_accum: int = 1, amp_level: Optional[str] = None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.grad_accum = int(grad_accum)
        self.amp_level = amp_level  # trace fwd under amp.auto_cast(level)
        self._compiled = None
        self._accum_fn = None
        self._accum = None      # grad accumulation buffers
        self._micro = 0         # micro-batch counter within the accum window
        self._step = 0

    def _build(self):
        from ..nn import clip as clip_mod
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        all_params, buffers = _collect_state(model)
        params = [p for p in all_params if not p.stop_gradient]   # trainable
        frozen = [p for p in all_params if p.stop_gradient]
        # align optimizer state with trainable params, PRESERVING any
        # previously loaded/accumulated state (checkpoint resume)
        old = {id(p): (opt._states[i], opt._masters[i])
               for i, p in enumerate(opt._parameter_list)
               if i < len(opt._states)}
        opt._parameter_list = params
        abstract = getattr(self, "_abstract_state", False)
        states, masters = [], []
        for p in params:
            s, m = old.get(id(p), (None, None))
            if s is None:
                m = None
                if opt._multi_precision and p._data.dtype in (jnp.bfloat16,
                                                              jnp.float16):
                    m = (jax.ShapeDtypeStruct(p._data.shape, jnp.float32)
                         if abstract
                         else opt._place_state(p, p._data.astype(jnp.float32)))
                if abstract:
                    # AOT planning (distributed/auto_parallel/aot.py): the
                    # step is only LOWERED, never executed here — optimizer
                    # state stays as avals so an 8B-param plan costs no RAM
                    s = jax.eval_shape(
                        opt._init_state,
                        m if m is not None
                        else jax.ShapeDtypeStruct(p._data.shape,
                                                  p._data.dtype))
                else:
                    s = jax.tree.map(lambda a: opt._place_state(p, a),
                                     opt._init_state(m if m is not None
                                                     else p._data))
            states.append(s)
            masters.append(m)
        opt._states, opt._masters = states, masters
        self._step = opt._step_count
        wd = tuple(jnp.asarray(opt._param_weight_decay(i), jnp.float32)
                   for i in range(len(params)))
        grad_clip = opt._grad_clip

        amp_level = self.amp_level

        def _amp_ctx():
            if amp_level:
                from .. import amp as amp_mod
                return amp_mod.auto_cast(level=amp_level)
            return contextlib.nullcontext()

        def loss_of(param_arrays, frozen_arrays, buffer_arrays, rng, inputs, labels):
            with _swap_state(params + frozen + buffers,
                             list(param_arrays) + list(frozen_arrays)
                             + list(buffer_arrays)):
                with _traced_rng(rng), engine.no_grad(), _amp_ctx():
                    t_in = jax.tree.map(Tensor, inputs)
                    t_lb = jax.tree.map(Tensor, labels)
                    out = model(*t_in) if isinstance(t_in, (list, tuple)) \
                        else model(t_in)
                    outs = out if isinstance(out, (list, tuple)) else (out,)
                    lbls = t_lb if isinstance(t_lb, (list, tuple)) else (t_lb,)
                    loss = loss_fn(*outs, *lbls)
                    new_buf = tuple(b._data for b in buffers)
            return loss._data.astype(jnp.float32), new_buf

        grad_fn = jax.value_and_grad(loss_of, argnums=0, has_aux=True)
        n_accum = self.grad_accum

        if n_accum > 1:
            def accum_step(accum, param_arrays, frozen_arrays, buffer_arrays,
                           rng, inputs, labels):
                (loss, new_buf), grads = grad_fn(param_arrays, frozen_arrays,
                                                 buffer_arrays, rng, inputs,
                                                 labels)
                return tuple(a + g for a, g in zip(accum, grads)), new_buf, loss

            self._accum_fn = jax.jit(accum_step, donate_argnums=(0,))

        # Pin update outputs to the call-time input shardings so ZeRO-sharded
        # state stays sharded and params stay replicated across steps (XLA
        # computes the update shard-locally and all-gathers new params —
        # under this whole-step jit it may also reduce-scatter grads, the
        # stage-2 semantics).
        from ..distributed.sharding import pin as _pin_sh, sharding_of as _sh

        param_sh = tuple(_sh(p._data) for p in params)
        master_sh = tuple(_sh(m) for m in masters)
        state_sh = tuple({k: _sh(v) for k, v in s.items()} for s in states)
        pin_active = any(param_sh) or any(master_sh) \
            or any(any(d.values()) for d in state_sh)
        self._built_sharding_version = getattr(opt, "_sharding_version", 0)

        def _pin(x, sh):
            return _pin_sh(x, sh if pin_active else None)

        def step(accum, param_arrays, master_arrays, opt_states, buffer_arrays,
                 frozen_arrays, key, inputs, labels, lr, stepno):
            # rng/step live ON DEVICE and chain through the donated state:
            # shipping a fresh host scalar per call costs a full host->device
            # round trip and serialises the step stream
            key, rng = jax.random.split(key)
            stepno = stepno + 1
            (loss, new_buf), grads = grad_fn(param_arrays, frozen_arrays,
                                             buffer_arrays, rng, inputs, labels)
            if n_accum > 1:
                grads = tuple((a + g) / n_accum for a, g in zip(accum, grads))
            # the scopes name the device's work in a profile (every
            # instruction's op_name; tracing.device_ops): forward and
            # backward are JAX's own jvp(...) and transpose(jvp(...))
            if grad_clip is not None:
                with jax.named_scope("optimizer.grad_clip"):
                    grads = clip_mod.pure_clip(grad_clip, grads)
            new_params, new_masters, new_states = [], [], []
            with jax.named_scope("optimizer.update"):
                for p, m, s, g, w, psh, msh, ssh in zip(
                        param_arrays, master_arrays, opt_states, grads, wd,
                        param_sh, master_sh, state_sh):
                    target = m if m is not None else p
                    g = g.astype(target.dtype)
                    np_, ns_ = opt._update(target, g, s, lr, stepno, w)
                    ns_ = {k: _pin(v, ssh.get(k)) for k, v in ns_.items()}
                    if m is not None:
                        np_ = _pin(np_, msh)
                        new_masters.append(np_)
                        new_params.append(_pin(np_.astype(p.dtype), psh))
                    else:
                        new_masters.append(None)
                        new_params.append(_pin(np_, psh))
                    new_states.append(ns_)
            return (tuple(new_params), tuple(new_masters), tuple(new_states),
                    new_buf, loss, key, stepno)

        self._compiled = jax.jit(step, donate_argnums=(0, 1, 2, 3, 4, 6, 10))
        self._noted = False     # until a call hands the program to tracing
        self._params, self._buffers, self._frozen = params, buffers, frozen
        # device-resident step chain state (re-seeded on rebuild/resume)
        self._dev_key = generator.next_key()
        self._dev_step = jnp.asarray(self._step, jnp.int32)
        self._lr_cache = (None, None)

    def __call__(self, inputs, labels):
        loss = self._call_impl(inputs, labels)
        # multi-host: watch the async step for DCN stalls (reference
        # comm_task_manager.h:37 watches NCCL tasks). A daemon thread
        # blocks on the loss and retires the CommTask; if the step wedges
        # on a dead peer, the watchdog fires instead of hanging silently.
        if jax.process_count() > 1:
            from .. import flags as _flags
            from ..distributed.watchdog import comm_watchdog
            import threading

            task = comm_watchdog().start_task(
                "train_step", timeout_s=float(_flags.get_flag("comm_timeout_s")))

            def _retire(arr=loss._data, t=task):
                try:
                    jax.block_until_ready(arr)
                finally:
                    t._mgr.finish_task(t)

            threading.Thread(target=_retire, daemon=True).start()
        return loss

    def _step_args(self, inputs, labels):
        """The compiled step's arguments for the current state."""
        opt, params = self.optimizer, self._params
        lr_val = float(opt.get_lr())
        if self._lr_cache[0] != lr_val:  # one transfer per lr CHANGE
            self._lr_cache = (lr_val, jnp.asarray(lr_val, jnp.float32))
        return (self._accum if self.grad_accum > 1 else (),
                tuple(p._data for p in params),
                tuple(opt._masters[i] for i in range(len(params))),
                tuple(opt._states[i] for i in range(len(params))),
                tuple(b._data for b in self._buffers),
                tuple(f._data for f in self._frozen),
                self._dev_key, inputs, labels,
                self._lr_cache[1], self._dev_step)

    def lower(self, inputs, labels):
        """The whole-step program lowered for the current parameters,
        optimizer state and this batch; nothing runs and nothing is
        donated. ``lower(...).compile().as_text()`` shows which kernels
        (``tpu_custom_call``) and collectives the step was built with."""
        if self._compiled is None:
            self._build()
        inputs, labels = (
            jax.tree.map(_to_array, x,
                         is_leaf=lambda t: isinstance(t, Tensor))
            for x in (inputs, labels))
        if self.grad_accum > 1 and self._accum is None:
            self._accum = tuple(jnp.zeros(p._data.shape, p._data.dtype)
                                for p in self._params)
        return self._compiled.lower(*self._step_args(inputs, labels))

    def _call_impl(self, inputs, labels):
        # the host's side of a call: `train.step.args` (build, re-sync, the
        # argument tuple), then `train.step.launch`, and `train.step` over
        # both, recorded once the launch has returned (`_launch`)
        sp_args = _tracing.start_span("train.step.args",
                                      trace=_tracing.UNTRACED)
        opt = self.optimizer
        if self._compiled is not None and \
                getattr(opt, "_sharding_version", 0) \
                != getattr(self, "_built_sharding_version", 0):
            self._compiled = None   # sharding reconfigured: stale pins
        if self._compiled is None:
            self._build()
        if opt._step_count != self._step:
            # optimizer state was loaded/reset externally: re-sync the
            # device-resident step counter (one transfer)
            self._step = opt._step_count
            self._dev_step = jnp.asarray(self._step, jnp.int32)
        params, buffers = self._params, self._buffers
        inputs = jax.tree.map(_to_array, inputs,
                              is_leaf=lambda x: isinstance(x, Tensor))
        labels = jax.tree.map(_to_array, labels,
                              is_leaf=lambda x: isinstance(x, Tensor))

        if self.grad_accum > 1 and self._accum is None:
            self._accum = tuple(jnp.zeros(p._data.shape, p._data.dtype)
                                for p in params)

        if self.grad_accum > 1 and self._micro < self.grad_accum - 1:
            # accumulation-only micro-step: no optimizer update
            self._accum, new_buf, loss = self._launch(
                self._accum_fn,
                (self._accum, tuple(p._data for p in params),
                 tuple(f._data for f in self._frozen),
                 tuple(b._data for b in buffers),
                 generator.next_key(), inputs, labels), inputs, sp_args)
            for b, nb in zip(buffers, new_buf):
                b._set_data(nb)
            self._micro += 1
            return Tensor(loss)

        self._step += 1
        opt._step_count = self._step
        args = self._step_args(inputs, labels)
        if not self._noted:
            # once a build. The arguments are donated, so their shapes and
            # placements are kept: a reader of tracing.device_ops() lowers
            # and compiles from them, and the compile cache has the program.
            # Until then (or tracing.clear(), or 16 newer programs) the
            # registry holds the jit and with it the model
            self._noted = True
            jit, avals = self._compiled, jax.tree.map(_aval, args)
            _tracing.note_program(
                "train_step", lambda: jit.lower(*avals).compile())
        new_p, new_m, new_s, new_buf, loss, self._dev_key, self._dev_step = \
            self._launch(self._compiled, args, inputs, sp_args)
        for i, p in enumerate(params):
            p._set_data(new_p[i])
            opt._masters[i] = new_m[i]
            opt._states[i] = new_s[i]
        for b, nb in zip(buffers, new_buf):
            b._set_data(nb)
        self._accum = None
        self._micro = 0
        return Tensor(loss)

    @staticmethod
    def _launch(jit, args, inputs, sp_args):
        """``jit(*args)`` under the `train.step.launch` span, which follows
        ``sp_args``; then the call's `train.step` over both. ``tokens`` are
        the first input's elements (an LM's ids), ``compiled`` says that
        this call traced or compiled: the jit's cache grew."""
        sp_args.end()
        known = jit._cache_size()
        with _tracing.start_span("train.step.launch",
                                 trace=_tracing.UNTRACED) as sp:
            out = jit(*args)
        if sp.t1_ns is not None:
            first = jax.tree.leaves(inputs)[:1]
            _tracing.record_span(
                "train.step", sp_args.t0_ns, sp.t1_ns,
                trace=_tracing.UNTRACED,
                attrs={"tokens": sum(int(a.size) for a in first),
                       "compiled": int(jit._cache_size() > known)})
        return out


# -- jit.save / jit.load ------------------------------------------------------

def save(layer, path: str, input_spec=None, **configs):
    """paddle.jit.save (reference jit/api.py save + translated_layer.py):
    trace the layer/function over `input_spec` placeholders, recording the
    op graph with parameters baked in as constants, and serialize it as the
    .pdmodel/.pdiparams inference artifact pair.

    input_spec: list of static.InputSpec (or Tensors, whose shape/dtype are
    used).
    """
    from .. import static as static_mod
    from ..core.tensor import Tensor as _Tensor

    if input_spec is None:
        raise ValueError("jit.save requires input_spec (shapes/dtypes of "
                         "the exported entry's inputs)")
    fn = layer.forward if isinstance(layer, Layer) else layer
    was_training = isinstance(layer, Layer) and layer.training
    if was_training:
        layer.eval()

    try:
        prog = static_mod.Program()
        with static_mod.program_guard(prog):
            feeds = []
            for i, spec in enumerate(input_spec):
                shape, dtype = tuple(spec.shape), spec.dtype
                if any(d is None or (isinstance(d, int) and d < 0)
                       for d in shape):
                    raise ValueError(
                        f"jit.save: input_spec[{i}] has a dynamic dim "
                        f"{shape} — XLA traces static shapes; export one "
                        f"program per bucketed shape instead")
                name = getattr(spec, "name", None) or f"x{i}"
                feeds.append(static_mod.data(name, shape, dtype))
            out = fn(*feeds)
        fetches = list(out) if isinstance(out, (list, tuple)) else [out]

        exe = static_mod.Executor()
        static_mod.save_inference_model(path, feeds, fetches, exe,
                                        program=prog)
    finally:
        if was_training:
            layer.train()


class TranslatedLayer(Layer):
    """Runtime for a jit.save artifact (reference
    jit/translated_layer.py:TranslatedLayer): callable like the original
    layer, executing the recorded program through the jitted Executor."""

    def __init__(self, path: str):
        super().__init__()
        from .. import static as static_mod
        self._exe = static_mod.Executor()
        self._program, self._feed_names, self._fetch_names = \
            static_mod.load_inference_model(path, self._exe)

    def forward(self, *args):
        from ..core.tensor import Tensor as _Tensor
        if len(args) != len(self._feed_names):
            raise TypeError(
                f"TranslatedLayer expects {len(self._feed_names)} inputs "
                f"({self._feed_names}), got {len(args)}")
        feed = {}
        for name, a in zip(self._feed_names, args):
            feed[name] = a._data if isinstance(a, _Tensor) else a
        outs = self._exe.run(self._program, feed=feed,
                             fetch_list=self._fetch_names,
                             return_numpy=False)
        outs = [_Tensor(o) for o in outs]
        return outs[0] if len(outs) == 1 else outs


def load(path: str) -> TranslatedLayer:
    """paddle.jit.load — returns a TranslatedLayer over the saved program."""
    return TranslatedLayer(path)
