"""Layer: the module base class.

Analog of the reference `paddle.nn.Layer`
(python/paddle/nn/layer/layers.py:334): parameter/buffer/sublayer
registries, hooks, state_dict, train/eval, apply, to(). Parameters are
eager Tensors (stop_gradient=False) whose underlying buffers the optimizer
rebinds — the pytree of parameters is what jit/to_static captures.
"""

from __future__ import annotations

import collections
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from ..core import dtype as dtype_mod
from ..core.tensor import Tensor
from . import initializer as I


# -- lazy parameter initialization (reference paddle.LazyGuard,
# python/paddle/nn/initializer/lazy_init.py) ---------------------------------
_lazy_depth = 0


class LazyGuard:
    """Defer parameter materialization (reference paddle.LazyGuard).

    Inside the guard, Layer.create_parameter allocates only a host-RAM
    zero buffer (on the CPU backend — no accelerator HBM is touched) and
    records the initializer. The real initializer runs on the default
    device at the first forward pass of the owning layer — after the
    model has (optionally) been sharded, which is the TPU-native reason
    to defer: init computes directly into the sharded layout. Pending
    state is tracked per-Layer (`_has_lazy`), so lazily-built models
    that are never run cost unrelated models nothing."""

    def __enter__(self):
        global _lazy_depth
        _lazy_depth += 1
        return self

    def __exit__(self, *exc):
        global _lazy_depth
        _lazy_depth -= 1
        return False


def _materialize_one(p: "Parameter") -> None:
    init, shape, dtype = p._lazy_spec
    data = init(shape, dtype)
    p._set_data(data._data if isinstance(data, Tensor) else data)
    del p._lazy_spec


def _materialize_params(layer: "Layer") -> None:
    """Run deferred initializers for every lazy Parameter under `layer`
    (compiled paths call this before snapshotting buffers)."""
    for name, sub, _ in layer._walk(""):
        if sub.__dict__.pop("_has_lazy", None):
            for p in sub._parameters.values():
                if p is not None and hasattr(p, "_lazy_spec"):
                    _materialize_one(p)


class Parameter(Tensor):
    """Trainable tensor (stop_gradient=False, persistable)."""

    def __init__(self, data, trainable: bool = True, name: Optional[str] = None):
        super().__init__(data, stop_gradient=not trainable, name=name)
        self.persistable = True

    @property
    def trainable(self) -> bool:
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v: bool):
        self.stop_gradient = not v


class Layer:
    def __init__(self, name_scope: Optional[str] = None, dtype=None):
        # use object.__setattr__ to dodge our own __setattr__ interception
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_sub_layers", collections.OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        self.training = True
        self._dtype = dtype or dtype_mod.get_default_dtype()
        self._forward_pre_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._forward_post_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()
        # the name `__call__` runs `forward` under (`jax.named_scope`): the
        # key a parent registered this layer under (`add_sublayer`), the
        # class's until then
        self._scope_key = self._name_scope

    # -- registry ------------------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Parameter):
            self._parameters[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            self.add_sublayer(name, value)
            self.__dict__.pop(name, None)
        elif name in self._buffers and isinstance(value, Tensor):
            self._buffers[name] = value  # rebinding a registered buffer
        else:
            # plain assignment (including rebinding a registered name)
            for reg in (self._parameters, self._buffers, self._sub_layers):
                reg.pop(name, None)
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for reg_name in ("_parameters", "_buffers", "_sub_layers"):
            reg = self.__dict__.get(reg_name)
            if reg is not None and name in reg:
                return reg[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for reg in (self._parameters, self._buffers, self._sub_layers):
            if name in reg:
                del reg[name]
                return
        object.__delattr__(self, name)

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is not None:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        if sublayer is not None:
            sublayer._set_scope_key(f"{self._scope_key}/{name}"
                                    if self._only_holds() else str(name))
        return sublayer

    def _only_holds(self) -> bool:
        """No forward of its own (a LayerList): never called, so it opens
        no scope, and what it holds carries its key (``layers/3``)."""
        return type(self).forward is Layer.forward

    def _set_scope_key(self, key: str) -> None:
        object.__setattr__(self, "_scope_key", key)
        if self._only_holds():
            for name, sub in self._sub_layers.items():
                if sub is not None:
                    sub._set_scope_key(f"{key}/{name}")

    def register_buffer(self, name: str, tensor: Optional[Tensor],
                        persistable: bool = True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, dtype=None, default_initializer=None,
                         is_bias: bool = False, attr=None) -> Parameter:
        """Reference Layer.create_parameter (layers.py): shape+initializer →
        Parameter. `attr` may be a ParamAttr-like object or False (no param)."""
        if attr is False:
            return None
        dtype = dtype_mod.convert_dtype(dtype) or self._dtype
        init = default_initializer
        if attr is not None and getattr(attr, "initializer", None) is not None:
            init = attr.initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        if _lazy_depth > 0:
            # LazyGuard active: host-RAM zeros placeholder, init deferred
            import jax.numpy as jnp
            cpu = jax.local_devices(backend="cpu")[0]
            with jax.default_device(cpu):
                placeholder = jnp.zeros(tuple(int(s) for s in shape),
                                        dtype)
            p = Parameter(placeholder)
            p._lazy_spec = (init, tuple(int(s) for s in shape), dtype)
            object.__setattr__(self, "_has_lazy", True)
            if attr is not None and getattr(attr, "trainable", True) is False:
                p.trainable = False
            return p
        data = init(tuple(shape), dtype)
        p = Parameter(data)
        if attr is not None and getattr(attr, "trainable", True) is False:
            p.trainable = False
        return p

    # -- iteration -----------------------------------------------------------
    def named_parameters(self, prefix: str = "", include_sublayers: bool = True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer, pfx in self._walk(prefix):
            for pname, p in layer._parameters.items():
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    if p.name is None:
                        p.name = pfx + pname  # stable dotted name (used by
                        # apply_decay_param_fun and checkpoints)
                    yield (pfx + pname, p)
            if not include_sublayers:
                break

    def parameters(self, include_sublayers: bool = True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "") -> Iterator[Tuple[str, Tensor]]:
        for name, layer, pfx in self._walk(prefix):
            for bname, b in layer._buffers.items():
                if b is not None:
                    yield (pfx + bname, b)

    def buffers(self) -> List[Tensor]:
        return [b for _, b in self.named_buffers()]

    def named_sublayers(self, prefix: str = "", include_self: bool = False
                        ) -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix.rstrip("."), self
        for name, sub in self._sub_layers.items():
            p = f"{prefix}{name}"
            yield p, sub
            yield from sub.named_sublayers(prefix=p + ".")

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self) -> Iterator["Layer"]:
        return iter(self._sub_layers.values())

    def _walk(self, prefix: str = ""):
        """Yield (name, layer, dotted_prefix) depth-first including self."""
        yield ("", self, prefix)
        for name, sub in self._sub_layers.items():
            yield from sub._walk(prefix=f"{prefix}{name}.")

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # -- modes / dtype / device ----------------------------------------------
    def train(self) -> "Layer":
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self) -> "Layer":
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    def to(self, device=None, dtype=None) -> "Layer":
        if dtype is not None:
            dtype = dtype_mod.convert_dtype(dtype)
            for p in self.parameters():
                if dtype_mod.is_floating_point_dtype(p.dtype):
                    p._set_data(p._data.astype(dtype))
            for _, b in self.named_buffers():
                if dtype_mod.is_floating_point_dtype(b.dtype):
                    b._set_data(b._data.astype(dtype))
            for layer in self.sublayers(include_self=True):
                layer._dtype = dtype
        if device is not None:
            from ..core.device import Place, _parse_place
            place = device if isinstance(device, Place) else _parse_place(str(device))
            for t in list(self.parameters()) + [b for _, b in self.named_buffers()]:
                t._set_data(jax.device_put(t._data, place.device))
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers: bool = True,
                   structured_name_prefix: str = "") -> Dict[str, Tensor]:
        out = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix):
            out[name] = p
        for name, layer, pfx in self._walk(structured_name_prefix):
            for bname, b in layer._buffers.items():
                if b is not None and bname not in layer._non_persistable_buffer_names:
                    out[pfx + bname] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k in own:
                arr = v._data if isinstance(v, Tensor) else np.asarray(v)
                target = own[k]
                if tuple(target._data.shape) != tuple(arr.shape):
                    raise ValueError(
                        f"shape mismatch for '{k}': {tuple(arr.shape)} vs "
                        f"expected {tuple(target._data.shape)}")
                import jax.numpy as jnp
                # COPY the value in (paddle copy-on-load semantics): an
                # alias would be invalidated when the source model's next
                # compiled TrainStep donates its param buffers
                target._set_data(jnp.array(arr, dtype=target._data.dtype,
                                           copy=True))
            else:
                unexpected.append(k)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    # -- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        hid = id(hook)
        self._forward_pre_hooks[hid] = hook
        return _HookRemover(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        hid = id(hook)
        self._forward_post_hooks[hid] = hook
        return _HookRemover(self._forward_post_hooks, hid)

    # -- call ----------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        if "_has_lazy" in self.__dict__:
            _materialize_params(self)
        for hook in self._forward_pre_hooks.values():
            res = hook(self, inputs)
            if res is not None:
                inputs = res if isinstance(res, tuple) else (res,)
        # under a trace every instruction's op_name gets the path of keys
        # (layers/3/self_attn/q_proj: the prefix of a parameter's structured
        # name), which observability.tracing.device_ops() hands to a reader
        # of a device profile; eager it names nothing
        with jax.named_scope(self._scope_key):
            out = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, inputs, out)
            if res is not None:
                out = res
        return out

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{type(self).__name__}({extra}"]
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub_repr}")
        if len(lines) == 1:
            return f"{type(self).__name__}({extra})"
        lines.append(")")
        return "\n".join(lines)


class _HookRemover:
    def __init__(self, store, hid):
        self._store, self._hid = store, hid

    def remove(self):
        self._store.pop(self._hid, None)
