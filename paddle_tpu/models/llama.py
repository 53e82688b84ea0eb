"""Llama model family — the flagship (BASELINE config 3).

Reference counterpart: PaddleNLP `paddlenlp/transformers/llama/modeling.py`
(out of the reference tree; architecture is the public Llama-3 one) built on
the reference's TP layer set `fleet/layers/mpu/mp_layers.py:46,335,542` and
fused kernels (`phi/kernels/fusion/gpu/fused_rope*`, flash attention
`phi/kernels/gpu/flash_attn_kernel.cu:91`).

TPU-first design:
- weights live sharded from construction (GSPMD NamedSharding via the fleet
  TP layers) — no megatron-style explicit collectives anywhere in the model;
  the mp psum / allgather fall out of XLA's partitioner.
- attention routes through the `flash_attention` op, which picks the Pallas
  splash kernel on TPU and the XLA composite elsewhere.
- rotary tables are precomputed buffers; position ids are static under jit.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..ops.dispatcher import call_op
from .. import nn
from ..nn import initializer as I
from ..nn.layer_base import Layer
from .generation import GenerationMixin, enters_step_program
from ..distributed.topology import get_hybrid_communicate_group as _get_hcg


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    recompute: bool = False
    use_scan_layers: bool = False   # stacked-params lax.scan over layers
    dtype: str = "float32"

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, hidden_size=4096,
                           intermediate_size=14336, num_hidden_layers=32,
                           num_attention_heads=32, num_key_value_heads=8,
                           max_position_embeddings=8192, rope_theta=500000.0,
                           dtype="bfloat16")

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab_size, hidden_size=64,
                           intermediate_size=128, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=128)


def _tp_enabled() -> bool:
    hcg = _get_hcg()
    return hcg is not None and hcg.get_model_parallel_world_size() > 1


import contextlib as _contextlib

from ..core import dtype as _dtype_mod


@_contextlib.contextmanager
def _dtype_scope(dtype: str):
    """Create params in config.dtype (bf16 params → bf16 compute; the
    optimizer's multi_precision master weights keep update precision)."""
    prev = _dtype_mod.get_default_dtype()
    _dtype_mod.set_default_dtype(dtype)
    try:
        yield
    finally:
        _dtype_mod.set_default_dtype(prev)


def _linear(in_f, out_f, has_bias=False, col=True, gather_output=False,
            input_is_parallel=True):
    """Column/Row-parallel linear under TP, plain Linear otherwise."""
    if _tp_enabled():
        from ..distributed.fleet.mp_layers import (ColumnParallelLinear,
                                                   RowParallelLinear)
        if col:
            return ColumnParallelLinear(in_f, out_f, has_bias=has_bias,
                                        gather_output=gather_output)
        return RowParallelLinear(in_f, out_f, has_bias=has_bias,
                                 input_is_parallel=input_is_parallel)
    return nn.Linear(in_f, out_f, bias_attr=has_bias)


class LlamaRMSNorm(Layer):
    def __init__(self, hidden_size: int, eps: float = 1e-6):
        super().__init__()
        self.weight = self.create_parameter(
            (hidden_size,), default_initializer=I.Constant(1.0))
        self.eps = eps

    def forward(self, x):
        return call_op("rms_norm", x, self.weight, epsilon=self.eps)


class LlamaRotaryEmbedding(Layer):
    """Precomputed cos/sin tables (reference fused_rope feeds from the same)."""

    def __init__(self, head_dim: int, max_pos: int, theta: float):
        super().__init__()
        # what the tables are made from: equal keys, equal tables
        self.key = (head_dim, max_pos, float(theta))
        inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim))
        t = jnp.arange(max_pos, dtype=jnp.float32)
        freqs = jnp.outer(t, inv)                      # [max_pos, dim/2]
        emb = jnp.concatenate([freqs, freqs], axis=-1)  # [max_pos, dim]
        self.register_buffer("cos_cached", Tensor(jnp.cos(emb)))
        self.register_buffer("sin_cached", Tensor(jnp.sin(emb)))

    def forward(self, seq_len: int):
        return (Tensor(self.cos_cached._data[:seq_len]),
                Tensor(self.sin_cached._data[:seq_len]))


class LlamaAttention(Layer):
    """GQA attention: q/k/v column-parallel, o row-parallel; rope fused op;
    flash_attention op (Pallas on TPU).

    Under tensor parallelism the op-level dispatcher resolves the fleet
    topology (mp_layers.tp_attention_context) and runs the Pallas kernel
    per head-shard inside a mesh-aware shard_map
    (ops/kernels/pallas/tp_attention.py) — heads ride 'mp', batch rides
    'dp', and the only mp collective in the block stays o_proj's psum.
    Non-divisible head counts (e.g. kv_heads < tp) fall back to the XLA
    composite with the reason in the flight recorder.

    A config whose ``rope_theta`` is None has no positions in its
    attention (models/jamba.py): no table is built and no rope op runs."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        self.q_proj = _linear(h, self.num_heads * self.head_dim, col=True)
        self.k_proj = _linear(h, self.num_kv_heads * self.head_dim, col=True)
        self.v_proj = _linear(h, self.num_kv_heads * self.head_dim, col=True)
        self.o_proj = _linear(self.num_heads * self.head_dim, h, col=False)
        self.rotary = None if config.rope_theta is None else \
            LlamaRotaryEmbedding(self.head_dim,
                                 config.max_position_embeddings,
                                 config.rope_theta)

    def forward(self, x, attn_mask=None, position_ids=None, cache=None,
                start_pos=None, layer_idx=0):
        b, s, _ = x.shape
        if getattr(cache, "packed_rows", False):
            return self._forward_rows(x, cache, start_pos, layer_idx)
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        if cache is not None:
            # decode path: rope at absolute positions, write into the cache,
            # attend against everything written so far (serving kernels).
            # start_pos may be a PER-ROW vector (every row of the batch
            # decodes at its own depth). A ragged step's per-token positions
            # never come here: `_forward_rows`
            if self.rotary is not None:
                if getattr(start_pos, "ndim", 0) == 1:
                    pos_ids = (start_pos.reshape([b, 1])
                               + call_op("arange", end=s, dtype="int32")
                               .reshape([1, s]))
                else:
                    pos_ids = (call_op("arange", end=s, dtype="int32")
                               + start_pos).reshape([1, s]) \
                        .broadcast_to([b, s])
                cos, sin = self.rotary(self.config.max_position_embeddings)
                q, k = call_op("rope", q, k, cos=cos, sin=sin,
                               position_ids=pos_ids)
            cache.update(layer_idx, k, v, start_pos)
            out = cache.attend(layer_idx, q, start_pos, attn_mask)
            out = out.reshape([b, s, self.num_heads * self.head_dim])
            return self.o_proj(out)
        if self.rotary is not None:
            cos, sin = self.rotary(s)
            q, k = call_op("rope", q, k, cos=cos, sin=sin,
                           position_ids=position_ids)
        hcg = _get_hcg()
        if hcg is not None and hcg.get_sep_parallel_world_size() > 1:
            # context parallelism: seq dim sharded over sep, ring attention
            out = call_op("ring_attention", q, k, v, is_causal=True)
        else:
            op = "flash_attention" if self.config.use_flash_attention \
                else "scaled_dot_product_attention"
            out = call_op(op, q, k, v, attn_mask=attn_mask, is_causal=True)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.o_proj(out)

    def _forward_rows(self, x, view, pos, layer_idx):
        """A ragged serving step (`models/serving.py: _RaggedView`): x is
        [1, T, hidden], the step's packed tokens, pos[1, T] their
        positions. From `q_proj` to `o_proj` the queries and the attention
        output stay [T, H*D] rows: rope turns the rows, the ragged kernel
        reads rows and writes rows. An axis split off on the way (a
        [T, H, D] view for rope's tables or for a tile pack) has XLA choose
        another order of the axes for each consumer, and pay for every
        change backwards into a transposition of the weight (ISSUE 40)."""
        t = x.shape[1]
        q = self.q_proj(x).reshape([t, self.num_heads * self.head_dim])
        k = self.k_proj(x).reshape([t, self.num_kv_heads * self.head_dim])
        v = self.v_proj(x)
        if self.rotary is not None:
            # the tokens' rows of the tables, cast as `rope` casts them:
            # the same in every layer whose tables are, so made once a step
            cos, sin = view.once(
                ("rope_rows", self.rotary.key, q.dtype),
                lambda: [Tensor(jnp.take(table._data, pos._data.reshape(-1),
                                         axis=0).astype(q.dtype))
                         for table in self.rotary(
                             self.config.max_position_embeddings)])
            q = call_op("rope_rows", q, cos=cos, sin=sin)
            k = call_op("rope_rows", k, cos=cos, sin=sin)
        kv_shape = [1, t, self.num_kv_heads, self.head_dim]
        view.update(layer_idx, k.reshape(kv_shape), v.reshape(kv_shape), pos)
        out = view.attend(layer_idx, q)
        return self.o_proj(out.reshape([1, t, -1]))


class LlamaMLP(Layer):
    """SwiGLU MLP: gate/up column-parallel, down row-parallel."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = _linear(h, m, col=True)
        self.up_proj = _linear(h, m, col=True)
        self.down_proj = _linear(m, h, col=False)

    def forward(self, x):
        return self.down_proj(
            call_op("swiglu", self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config.hidden_size,
                                            config.rms_norm_eps)
        self.post_attention_layernorm = LlamaRMSNorm(config.hidden_size,
                                                     config.rms_norm_eps)

    def forward(self, x, attn_mask=None, position_ids=None, cache=None,
                start_pos=None, layer_idx=0):
        x = x + self.self_attn(self.input_layernorm(x), attn_mask,
                               position_ids, cache=cache,
                               start_pos=start_pos, layer_idx=layer_idx)
        return x + self.mlp(self.post_attention_layernorm(x))


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        with _dtype_scope(config.dtype):
            self._build(config)

    def _build(self, config: LlamaConfig):
        if _tp_enabled():
            from ..distributed.fleet.mp_layers import VocabParallelEmbedding
            self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                       config.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(config.vocab_size,
                                             config.hidden_size)
        if self._pp_degree() > 1 or config.use_scan_layers:
            from ..nn.stack import LayerStack
            self.layer_stack = LayerStack(
                lambda: LlamaDecoderLayer(config), config.num_hidden_layers,
                remat=config.recompute)
        else:
            self.layers = nn.LayerList(
                [LlamaDecoderLayer(config)
                 for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)

    @staticmethod
    def _pp_degree() -> int:
        hcg = _get_hcg()
        return hcg.get_pipe_parallel_world_size() if hcg is not None else 1

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                cache=None, start_pos=None):
        if cache is not None:
            if not hasattr(self, "layers"):
                raise NotImplementedError(
                    "KV-cache decode requires the unrolled layer list "
                    "(use_scan_layers/pp stacks are train-time paths)")
            x = self.embed_tokens(input_ids)
            for i, layer in enumerate(self.layers):
                x = layer(x, attn_mask=attn_mask, cache=cache,
                          start_pos=start_pos, layer_idx=i)
            return self.norm(x)
        x = self.embed_tokens(input_ids)
        pp = self._pp_degree()
        if pp > 1 and hasattr(self, "layer_stack"):
            # decoder stack over the pp mesh axis: microbatch + ppermute
            # rotation; embedding/norm/head stay outside, replicated over pp
            from ..distributed.pipeline import pipelined_stack_forward
            x = pipelined_stack_forward(
                self.layer_stack, x, (attn_mask, position_ids), pp,
                remat=self.config.recompute)
        elif hasattr(self, "layer_stack"):
            x = self.layer_stack(x, attn_mask, position_ids)
        else:
            for layer in self.layers:
                if self.config.recompute and self.training:
                    from ..distributed.recompute import recompute
                    pol = None
                    if self.config.recompute == "selective":
                        # keep matmul outputs, recompute elementwise only
                        pol = jax.checkpoint_policies.dots_saveable
                    x = recompute(layer, x, attn_mask, position_ids,
                                  policy=pol)
                else:
                    x = layer(x, attn_mask, position_ids)
        return self.norm(x)


class LlamaForCausalLM(Layer, GenerationMixin):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        self.lm_head = None
        if not config.tie_word_embeddings:
            with _dtype_scope(config.dtype):
                self.lm_head = _linear(config.hidden_size, config.vocab_size,
                                       col=True, gather_output=True)

    @enters_step_program
    def forward(self, input_ids, attn_mask=None, position_ids=None,
                cache=None, start_pos=None):
        hidden = self.llama(input_ids, attn_mask, position_ids,
                            cache=cache, start_pos=start_pos)
        if self.lm_head is None:  # tied: logits = h @ E^T
            return call_op("matmul", hidden, self.llama.embed_tokens.weight,
                           transpose_y=True)
        return self.lm_head(hidden)


class LlamaPretrainingCriterion(Layer):
    """Shifted next-token cross entropy; under TP this is the
    ParallelCrossEntropy path (reference mp_layers.py:743)."""

    def __init__(self, config: Optional[LlamaConfig] = None):
        super().__init__()

    def forward(self, logits, labels):
        # fused CE keeps the [b, s, V] logits bf16-resident (no f32 copy,
        # no saved probs) — the difference between fitting batch 8 and
        # OOM on a 16G chip (kernels/nn.py fused_softmax_ce)
        loss = call_op("fused_softmax_ce", logits[:, :-1, :], labels[:, 1:])
        return loss.mean()
