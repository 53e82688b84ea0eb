"""paddle.nn.quant — weight-only quantized serving surface.

Reference: `python/paddle/nn/quant/quantized_linear.py` (weight_quantize /
weight_dequantize / weight_only_linear / llm_int8_linear wrappers over the
cutlass kernels) — here over the XLA int8-operand matmul formulation
(ops/kernels/pallas/weight_only_gemm.py docstring).
"""

from __future__ import annotations

from typing import Optional

from ..ops.dispatcher import call_op
from .layer_base import Layer
from .layers_common import Linear


def weight_quantize(x, algo: str = "weight_only_int8", arch=None,
                    group_size: int = -1):
    return call_op("weight_quantize", x, algo=algo, group_size=group_size)


def weight_dequantize(x, scale, algo: str = "weight_only_int8",
                      out_dtype="float32", group_size: int = -1):
    return call_op("weight_dequantize", x, scale, algo=algo,
                   out_dtype=out_dtype, group_size=group_size)


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype: str = "int8", arch=None,
                       group_size: int = -1):
    return call_op("weight_only_linear", x, weight, bias, weight_scale,
                   weight_dtype=weight_dtype, group_size=group_size)


def llm_int8_linear(x, weight, bias=None, weight_scale=None,
                    threshold: float = 6.0):
    return call_op("llm_int8_linear", x, weight, bias, weight_scale,
                   threshold=threshold)


class WeightOnlyLinear(Layer):
    """Serving Linear with int8/int4 weights (dequant-in-kernel matmul).

    Build from a trained Linear via `WeightOnlyLinear.from_linear(lin)` or
    construct empty and `set_quantized(q, scales)`.
    """

    def __init__(self, in_features: int, out_features: int,
                 weight_dtype: str = "int8", group_size: int = -1,
                 bias=None):
        super().__init__()
        import jax.numpy as jnp
        self.in_features = in_features
        self.out_features = out_features
        self.weight_dtype = weight_dtype
        self.group_size = group_size
        # bias rides state_dict as a BUFFER (inference-only layer: it must
        # not appear in parameters() nor alias the source Linear's trainable
        # Parameter). `bias=True` pre-registers zeros so a skeleton can load
        # a checkpoint saved from a from_linear-built layer.
        if bias is True:
            self.register_buffer("bias", jnp.zeros((out_features,),
                                                   jnp.float32))
        elif bias is None or bias is False:
            self.bias = None
        else:
            # copy into a fresh buffer so it never aliases a trainable
            # Parameter of the source layer (which a donating TrainStep
            # could delete out from under us)
            self.register_buffer(
                "bias", jnp.array(getattr(bias, "_data", bias), copy=True))
        # zero-initialised buffers with the derived shapes so a freshly
        # constructed skeleton can LOAD a saved quantized checkpoint
        # (set_state_dict copies into registered buffers only)
        k = in_features // 2 if weight_dtype == "int4" else in_features
        srows = (in_features // group_size) if group_size > 0 else None
        self.register_buffer(
            "qweight", jnp.zeros((k, out_features), jnp.int8))
        self.register_buffer(
            "weight_scale",
            jnp.zeros((srows, out_features) if srows else (out_features,),
                      jnp.float32))

    @staticmethod
    def from_linear(lin: Linear, weight_dtype: str = "int8",
                    group_size: int = -1) -> "WeightOnlyLinear":
        algo = ("weight_only_int4" if weight_dtype == "int4"
                else "weight_only_int8")
        q, s = weight_quantize(lin.weight, algo=algo, group_size=group_size)
        layer = WeightOnlyLinear(lin.weight.shape[0], lin.weight.shape[1],
                                 weight_dtype, group_size,
                                 bias=getattr(lin, "bias", None))
        layer.set_quantized(q, s)
        return layer

    def set_quantized(self, qweight, weight_scale):
        # registered as buffers: they ride state_dict but take no grads
        self.register_buffer("qweight", qweight)
        self.register_buffer("weight_scale", weight_scale)

    def forward(self, x):
        return weight_only_linear(x, self.qweight, self.bias,
                                  self.weight_scale,
                                  weight_dtype=self.weight_dtype,
                                  group_size=self.group_size)


def quantize_for_inference(model: Layer, algo: str = "weight_only_int8",
                           group_size: int = -1,
                           skip: Optional[tuple] = ("lm_head",)) -> Layer:
    """Swap every nn.Linear in `model` for a WeightOnlyLinear IN PLACE
    (the reference's serving flow quantizes checkpoints offline; here the
    same transform runs on a loaded model). `skip` filters by attribute
    name (lm_head stays high precision by default)."""
    wdt = "int4" if algo == "weight_only_int4" else "int8"

    def visit(layer: Layer):
        for name, sub in list(layer._sub_layers.items()):
            if isinstance(sub, Linear) and (not skip or name not in skip):
                layer.add_sublayer(name, WeightOnlyLinear.from_linear(
                    sub, weight_dtype=wdt, group_size=group_size))
            else:
                visit(sub)

    visit(model)
    return model
