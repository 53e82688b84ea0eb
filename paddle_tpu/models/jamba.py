"""Jamba: a hybrid of Mamba-1 (selective state-space) layers and attention
layers, every layer followed by the same SiLU-gated feed-forward.

Reference counterpart: `transformers`' `modeling_jamba.py`
(`JambaMambaMixer.slow_forward`, `JambaAttentionDecoderLayer`,
`JambaMambaDecoderLayer`) at ``num_experts = 1``. Layer ``i`` holds
attention where ``i % attn_layer_period == attn_layer_offset`` and a Mamba
mixer otherwise; attention has no rotary embedding (the recurrence carries
the order); the head is tied to the embedding.

What the model keeps between a row's tokens differs by layer, and it says
so (`layer_states`): an attention layer keeps paged keys and values, a
Mamba layer two fixed-size arrays a row, the convolution's tail and the
float32 SSM state. `ContinuousBatchingEngine` allocates by that
declaration, and the mixer reads and writes its row's arrays through the
step's cache view (`models/serving.py: _RaggedView`) with the two ragged
ops of `ops/kernels/pallas/ragged_selective_scan.py`.

Two forwards: the whole-sequence one without a cache, every sequence
starting from a zero state (the tests' shape), which runs the same two ops
over rows of its own; and the engine's ragged step. There is no dense
decode loop: `generate()` raises for a model with row state.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..ops.dispatcher import call_op
from .. import nn
from ..nn import initializer as I
from ..nn.layer_base import Layer
from .generation import (GenerationMixin, PagedKV, RowState,
                         enters_step_program)
from .llama import (LlamaAttention, LlamaMLP, LlamaRMSNorm, _dtype_scope,
                    _linear)

_LANES = 128        # the row-state arrays lay channels out as [D / 128, 128]


@dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    use_flash_attention: bool = True
    dtype: str = "float32"
    # read by LlamaAttention: Jamba's attention has no positions at all
    rope_theta: Optional[float] = None

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset


class _FreshRows:
    """What a Mamba mixer reads its rows from when there is no cache: every
    sequence of the batch is one row that starts from a zero state, and
    what the ops write back is dropped."""

    def __init__(self, config: JambaConfig, batch: int, seq: int):
        self._arrays = dict(
            (name, (shape, dt)) for name, shape, dt
            in _mamba_state(config).arrays)
        self._batch = batch
        self._segments = (
            Tensor(jnp.arange(batch + 1, dtype=jnp.int32) * seq),
            Tensor(jnp.arange(batch, dtype=jnp.int32)),
            Tensor(jnp.zeros((batch,), jnp.int32)))

    def segments(self) -> Tuple[Tensor, Tensor, Tensor]:
        return self._segments

    def row_state(self, layer: int, name: str) -> Tensor:
        shape, dt = self._arrays[name]
        return Tensor(jnp.zeros((self._batch + 1,) + shape, dt))

    def set_row_state(self, layer: int, name: str, value: Tensor) -> None:
        pass


def _mamba_state(config: JambaConfig) -> RowState:
    groups = config.d_inner // _LANES
    return RowState((
        ("conv", (config.mamba_d_conv - 1, groups, _LANES), config.dtype),
        ("ssm", (config.mamba_d_state, groups, _LANES), "float32")))


class JambaMambaMixer(Layer):
    """Mamba-1 with Jamba's three inner norms. With u the normed hidden
    state: ``[x, z] = W_in u``; ``x = silu(conv(x))`` (depthwise, causal, K
    taps); ``[dt, B, C] = W_x x``, each RMS-normed; ``delta =
    softplus(W_dt dt + b_dt)``; ``s_t = exp(delta A) s_{t-1} + delta x_t B``
    with ``A = -exp(A_log)`` and s in float32; ``y = s_t C + D x_t``; out =
    ``W_out (y silu(z))``. ``A_log`` is held as ``[N, D]`` and the
    convolution's weight as ``[K, D]``, channels last, as the kernels read
    them."""

    def __init__(self, config: JambaConfig):
        super().__init__()
        h, d = config.hidden_size, config.d_inner
        n, k, r = (config.mamba_d_state, config.mamba_d_conv,
                   config.mamba_dt_rank)
        if d % _LANES:
            raise ValueError(f"mamba_expand * hidden_size = {d} must be a "
                             f"multiple of {_LANES}")
        self.sections = [r, n, n]
        self.in_proj = _linear(h, 2 * d, col=True)
        self.conv_weight = self.create_parameter(
            (k, d), default_initializer=I.Normal(0.0, 1.0 / math.sqrt(k)))
        self.conv_bias = self.create_parameter((d,), is_bias=True)
        self.x_proj = _linear(d, r + 2 * n, col=False)
        self.dt_layernorm = LlamaRMSNorm(r, config.rms_norm_eps)
        self.b_layernorm = LlamaRMSNorm(n, config.rms_norm_eps)
        self.c_layernorm = LlamaRMSNorm(n, config.rms_norm_eps)
        self.dt_proj = _linear(r, d, has_bias=True, col=True)
        self.A_log = self.create_parameter(
            (n, d), default_initializer=I.Assign(np.broadcast_to(
                np.log(np.arange(1, n + 1, dtype=np.float32))[:, None],
                (n, d))))
        self.D = self.create_parameter(
            (d,), default_initializer=I.Constant(1.0))
        self.out_proj = _linear(d, h, col=False)

    def forward(self, u, rows, layer_idx: int):
        b, s, h = u.shape
        x, z = call_op("split", self.in_proj(u).reshape([b * s, -1]), 2,
                       axis=-1)
        cu, slots, start = rows.segments()
        x, tail = call_op("ragged_causal_conv", x, self.conv_weight,
                          self.conv_bias, cu, slots, start,
                          rows.row_state(layer_idx, "conv"))
        rows.set_row_state(layer_idx, "conv", tail)
        dt, B, C = call_op("split", self.x_proj(x), self.sections, axis=-1)
        dt = self.dt_proj(self.dt_layernorm(dt))
        y, ssm = call_op("ragged_selective_scan", x, dt,
                         self.b_layernorm(B), self.c_layernorm(C), z,
                         self.A_log, self.D, cu, slots, start,
                         rows.row_state(layer_idx, "ssm"))
        rows.set_row_state(layer_idx, "ssm", ssm)
        return self.out_proj(y).reshape([b, s, h])


class JambaDecoderLayer(Layer):
    def __init__(self, config: JambaConfig, layer_idx: int):
        super().__init__()
        self.layer_idx = layer_idx
        if config.is_attention(layer_idx):
            self.self_attn = LlamaAttention(config)
        else:
            self.mamba = JambaMambaMixer(config)
        self.feed_forward = LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config.hidden_size,
                                            config.rms_norm_eps)
        self.pre_ff_layernorm = LlamaRMSNorm(config.hidden_size,
                                             config.rms_norm_eps)

    def forward(self, x, cache, rows, start_pos):
        h = self.input_layernorm(x)
        if hasattr(self, "mamba"):
            x = x + self.mamba(h, rows, self.layer_idx)
        else:
            x = x + self.self_attn(h, cache=cache, start_pos=start_pos,
                                   layer_idx=self.layer_idx)
        return x + self.feed_forward(self.pre_ff_layernorm(x))


class JambaModel(Layer):
    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        with _dtype_scope(config.dtype):
            self.embed_tokens = nn.Embedding(config.vocab_size,
                                             config.hidden_size)
            self.layers = nn.LayerList(
                [JambaDecoderLayer(config, i)
                 for i in range(config.num_hidden_layers)])
            self.final_layernorm = LlamaRMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    def forward(self, input_ids, cache=None, start_pos=None):
        x = self.embed_tokens(input_ids)
        rows = cache if cache is not None else _FreshRows(
            self.config, x.shape[0], x.shape[1])
        for layer in self.layers:
            x = layer(x, cache, rows, start_pos)
        return self.final_layernorm(x)


class JambaForCausalLM(Layer, GenerationMixin):
    """Jamba with its head tied to the embedding."""

    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        self.jamba = JambaModel(config)

    def layer_states(self) -> Tuple:
        """What each layer keeps between a row's tokens: paged keys and
        values in the attention layers, the convolution's tail and the SSM
        state a row in the Mamba layers."""
        cfg = self.config
        paged = PagedKV(cfg.num_key_value_heads,
                        cfg.hidden_size // cfg.num_attention_heads)
        return tuple(paged if cfg.is_attention(i) else _mamba_state(cfg)
                     for i in range(cfg.num_hidden_layers))

    @enters_step_program
    def forward(self, input_ids, cache=None, start_pos=None):
        hidden = self.jamba(input_ids, cache=cache, start_pos=start_pos)
        return call_op("matmul", hidden, self.jamba.embed_tokens.weight,
                       transpose_y=True)
