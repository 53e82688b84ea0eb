"""paddle_tpu.quantization — QAT fake-quant + PTQ observers (SURVEY §2.6).

Reference: python/paddle/quantization (QuantConfig config.py, QAT qat.py,
PTQ ptq.py, observers in observer/, fake-quant layers quanters/) over the
phi fake_quantize kernels.

TPU shape: fake-quant is a pure function (scale → round → clamp →
dequantize) with a straight-through-estimator gradient — XLA fuses it into
the surrounding matmul. int8 MXU execution of converted models rides XLA's
native int8 dot support.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Type

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn.layer_base import Layer
from ..nn.layers_common import Conv2D, Linear

__all__ = ["QuantConfig", "QAT", "PTQ", "AbsmaxObserver", "EMAObserver",
           "FakeQuant", "quant_linear", "QuantedLinear", "QuantedConv2D",
           "fake_quant"]


# -- fake quant (STE) ---------------------------------------------------------

def fake_quant(x: Tensor, scale, bit_length: int = 8) -> Tensor:
    """Routed through the `fake_quantize` op (ops/kernels/quant.py) so the
    tape records it and the STE custom_vjp drives the gradient. `scale` is a
    tensor input — observer updates never recompile or sync the host."""
    from ..ops.dispatcher import call_op
    if not isinstance(scale, Tensor):
        scale = Tensor(jnp.asarray(scale, jnp.float32))
    return call_op("fake_quantize", x, scale, bit_length=bit_length)


# -- observers ----------------------------------------------------------------

def _check_not_traced(data):
    """QAT observers mutate Python-held device state; under to_static /
    TrainStep tracing that would capture a tracer and silently lose
    calibration (then crash on later eager use). Fail loudly instead —
    calibrate eagerly, convert(), THEN compile (reference QAT flow)."""
    import jax as _jax
    if isinstance(data, _jax.core.Tracer):
        raise RuntimeError(
            "quantization observers must run eagerly: observe() was called "
            "under jit/to_static tracing. Calibrate the model eagerly "
            "first, call convert(), and only then compile the quantized "
            "model.")


class AbsmaxObserver:
    """Per-tensor abs-max range observer (reference observer/abs_max.py).

    State stays a DEVICE scalar — observing adds one fused reduction to the
    async stream, never a host round-trip."""

    def __init__(self, quant_bits: int = 8):
        self.quant_bits = quant_bits
        self._max = jnp.zeros((), jnp.float32)

    def observe(self, x):
        data = x._data if isinstance(x, Tensor) else jnp.asarray(x)
        _check_not_traced(data)
        self._max = jnp.maximum(self._max,
                                jnp.abs(data).max().astype(jnp.float32))

    def scale(self):
        return jnp.maximum(self._max, 1e-9)


class EMAObserver:
    """Moving-average abs-max (reference observer/ema.py semantics);
    device-side state like AbsmaxObserver."""

    def __init__(self, quant_bits: int = 8, moving_rate: float = 0.9):
        self.quant_bits = quant_bits
        self.moving_rate = moving_rate
        self._ema = None

    def observe(self, x):
        data = x._data if isinstance(x, Tensor) else jnp.asarray(x)
        _check_not_traced(data)
        cur = jnp.abs(data).max().astype(jnp.float32)
        self._ema = cur if self._ema is None else (
            self.moving_rate * self._ema + (1 - self.moving_rate) * cur)

    def scale(self):
        if self._ema is None:
            return jnp.asarray(1e-9, jnp.float32)
        return jnp.maximum(self._ema, 1e-9)


# -- config -------------------------------------------------------------------

class FakeQuant:
    """Quanter spec: observer class + bits."""

    def __init__(self, observer_cls=AbsmaxObserver, quant_bits: int = 8):
        self.observer_cls = observer_cls
        self.quant_bits = quant_bits

    def make(self):
        return self.observer_cls(self.quant_bits)


class QuantConfig:
    """reference quantization/config.py: which layers get which quanters."""

    def __init__(self, activation: Optional[FakeQuant] = None,
                 weight: Optional[FakeQuant] = None):
        self.activation = activation or FakeQuant(EMAObserver, 8)
        self.weight = weight or FakeQuant(AbsmaxObserver, 8)
        self._type_configs: Dict[Type[Layer], Dict] = {}

    def add_type_config(self, layer_type, activation=None, weight=None):
        self._type_configs[layer_type] = {
            "activation": activation or self.activation,
            "weight": weight or self.weight}

    def config_for(self, layer: Layer) -> Optional[Dict]:
        for t, cfg in self._type_configs.items():
            if isinstance(layer, t):
                return cfg
        if isinstance(layer, (Linear, Conv2D)):
            return {"activation": self.activation, "weight": self.weight}
        return None


# -- quantized layer wrappers -------------------------------------------------

class QuantedLinear(Layer):
    """Linear with fake-quantized weight+activation (QAT) or recorded scales
    (PTQ convert)."""

    def __init__(self, inner: Linear, cfg: Dict):
        super().__init__()
        self.inner = inner
        self.weight_quanter = cfg["weight"].make()
        self.act_quanter = cfg["activation"].make()
        self.weight_bits = cfg["weight"].quant_bits
        self.act_bits = cfg["activation"].quant_bits
        self.calibrating = False

    def forward(self, x):
        if self.calibrating:
            self.act_quanter.observe(x)
            return self.inner(x)
        self.weight_quanter.observe(self.inner.weight)
        self.act_quanter.observe(x)
        w = fake_quant(self.inner.weight, self.weight_quanter.scale(),
                       self.weight_bits)
        xq = fake_quant(x, self.act_quanter.scale(), self.act_bits)
        from ..ops.dispatcher import call_op
        return call_op("linear", xq, w, self.inner.bias)


class QuantedConv2D(Layer):
    """Conv2D with fake-quantized weight+activation (QAT)."""

    def __init__(self, inner: Conv2D, cfg: Dict):
        super().__init__()
        self.inner = inner
        self.weight_quanter = cfg["weight"].make()
        self.act_quanter = cfg["activation"].make()
        self.weight_bits = cfg["weight"].quant_bits
        self.act_bits = cfg["activation"].quant_bits
        self.calibrating = False

    def forward(self, x):
        if self.calibrating:
            self.act_quanter.observe(x)
            return self.inner(x)
        self.weight_quanter.observe(self.inner.weight)
        self.act_quanter.observe(x)
        w = fake_quant(self.inner.weight, self.weight_quanter.scale(),
                       self.weight_bits)
        xq = fake_quant(x, self.act_quanter.scale(), self.act_bits)
        from ..ops.dispatcher import call_op
        i = self.inner
        return call_op("conv2d", xq, w, i.bias, stride=i.stride,
                       padding=i.padding, dilation=i.dilation,
                       groups=i.groups, data_format=i.data_format)


class QAT:
    """Quantization-aware training wrapper (reference qat.py QAT.quantize):
    replaces quantizable sublayers with fake-quant twins."""

    def __init__(self, config: Optional[QuantConfig] = None):
        self.config = config or QuantConfig()

    def quantize(self, model: Layer, inplace: bool = True) -> Layer:
        if not inplace:
            import copy
            model = copy.deepcopy(model)
        self._quantize_inplace(model)
        return model

    def _quantize_inplace(self, model: Layer) -> None:
        for name, sub in list(model._sub_layers.items()):
            cfg = self.config.config_for(sub)
            if cfg is not None and isinstance(sub, Linear):
                model.add_sublayer(name, QuantedLinear(sub, cfg))
            elif cfg is not None and isinstance(sub, Conv2D):
                model.add_sublayer(name, QuantedConv2D(sub, cfg))
            else:
                self._quantize_inplace(sub)


class PTQ:
    """Post-training quantization (reference ptq.py): calibrate with sample
    batches, then convert weights to int8 + dequant scales."""

    def __init__(self, config: Optional[QuantConfig] = None):
        self.config = config or QuantConfig(
            activation=FakeQuant(AbsmaxObserver, 8))

    def quantize(self, model: Layer) -> Layer:
        qat = QAT(self.config)
        model = qat.quantize(model)
        for layer in _walk(model):
            if isinstance(layer, (QuantedLinear, QuantedConv2D)):
                layer.calibrating = True
        return model

    def convert(self, model: Layer) -> Layer:
        """Freeze observed scales: store int8 weights + dequant scale."""
        for layer in _walk(model):
            if isinstance(layer, (QuantedLinear, QuantedConv2D)):
                layer.calibrating = False
                w = layer.inner.weight._data
                layer.weight_quanter.observe(layer.inner.weight)
                qmax = float(2 ** (layer.weight_bits - 1) - 1)
                scale = float(layer.weight_quanter.scale()) / qmax
                layer.int8_weight = jnp.clip(
                    jnp.round(w / scale), -qmax - 1, qmax).astype(jnp.int8)
                layer.dequant_scale = scale
                # forward now dequantizes the stored int8 weight
                layer.forward = _converted_forward(layer)
        return model


def _converted_forward(layer):
    from ..ops.dispatcher import call_op

    def linear_forward(x):
        w = Tensor(layer.int8_weight.astype(jnp.float32) *
                   layer.dequant_scale)
        return call_op("linear", x, w, layer.inner.bias)

    def conv_forward(x):
        w = Tensor(layer.int8_weight.astype(jnp.float32) *
                   layer.dequant_scale)
        i = layer.inner
        return call_op("conv2d", x, w, i.bias, stride=i.stride,
                       padding=i.padding, dilation=i.dilation,
                       groups=i.groups, data_format=i.data_format)

    return conv_forward if isinstance(layer, QuantedConv2D) else \
        linear_forward


def _walk(layer: Layer):
    yield layer
    for sub in layer._sub_layers.values():
        yield from _walk(sub)


def quant_linear(x, weight, bias, scale_in, scale_w, bits: int = 8):
    """Functional int8 linear with explicit scales (serving path)."""
    from ..ops.dispatcher import call_op
    xq = fake_quant(x, scale_in, bits)
    wq = fake_quant(weight, scale_w, bits)
    return call_op("linear", xq, wq, bias)
