"""The plain reference: a Llama-family causal language model (which Mistral
is, without a sliding window) in straightforward float32 ``jax.numpy``.

No kernels, no cache, no batching, nothing imported from the program. It
follows the published architecture: token embedding, then per layer RMSNorm,
grouped-query attention with rotate-half rotary embedding, residual,
RMSNorm, SwiGLU, residual; a final RMSNorm and an untied head. Weights come
in the layout the program holds them in, [in, out] matrices named as in
``WEIGHT_SHAPES``, and are raised to float32 one at a time where they are
used, so that the reference fits beside a model that fills the chip.

Departure from a textbook forward, for memory only: attention and the head
run over blocks of ``query_block`` query positions against the whole
sequence's keys, which changes no value.

``precision`` is for the control of the benchmark's check and for nothing
else: the same forward with both operands of every matrix product, and the
cached keys and values, rounded to a lower precision first (``bf16``,
``int8`` or ``fp8``), as a later PR's faster path would. ``None`` is the
reference.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def weight_shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    """Name and shape of every weight, as ``LlamaForCausalLM`` names them."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    shapes = {"llama.embed_tokens.weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"llama.layers.{i}."
        shapes.update({
            p + "self_attn.q_proj.weight": (h, h),
            p + "self_attn.k_proj.weight": (h, kv),
            p + "self_attn.v_proj.weight": (h, kv),
            p + "self_attn.o_proj.weight": (h, h),
            p + "mlp.gate_proj.weight": (h, f),
            p + "mlp.up_proj.weight": (h, f),
            p + "mlp.down_proj.weight": (f, h),
            p + "input_layernorm.weight": (h,),
            p + "post_attention_layernorm.weight": (h,),
        })
    shapes["llama.norm.weight"] = (h,)
    shapes["lm_head.weight"] = (h, v)
    return shapes


def make_weights(cfg: Dict, seed: int, dtype) -> Dict[str, jax.Array]:
    """Every weight in one jitted call on the device: matrices normal(0,
    ``initializer_range``), norm weights 1, in the type they are run in."""
    shapes = weight_shapes(cfg)
    std = cfg.get("initializer_range", 0.02)

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if len(shape) == 1:
                out[name] = jnp.ones(shape, dtype)
            else:
                out[name] = (jax.random.normal(jax.random.fold_in(key, i),
                                               shape, F32) * std).astype(dtype)
        return out

    return jax.jit(build)(jax.random.key(seed % (2 ** 31 - 1)))


def _lower(x, axis: int, precision):
    """``x`` rounded to ``precision`` and raised to float32 again. int8 and
    fp8 are scaled so that the largest magnitude along ``axis`` (a row of
    activations, a column of weights, one head's key) just fits: 127, or
    float8_e4m3's 448."""
    if precision is None:
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    top = {"int8": 127.0, "fp8": 448.0}[precision]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis, keepdims=True), 1e-30) / top
    if precision == "int8":
        return jnp.round(x / scale) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rotary(x, positions, theta):
    """x: [s, heads, d]. Rotate-half convention of the published model."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, query_block):
    """Causal grouped-query attention; q [s, H, d], k and v [s, KV, d]."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    cols = jnp.arange(s)
    outs = []
    for lo in range(0, s, query_block):
        qb = q[lo:lo + query_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(d))
        rows = lo + jnp.arange(qb.shape[0])
        scores = jnp.where(cols[None, None, :] <= rows[None, :, None],
                           scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs, 0)


def _layer(cfg, x, w, prefix, positions, query_block, precision=None):
    heads = cfg["num_attention_heads"]
    kv_heads = cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    s = x.shape[0]

    def times(a, name):
        return _lower(a, -1, precision) @ _lower(
            w[prefix + name].astype(F32), 0, precision)

    h = _rms_norm(x, w[prefix + "input_layernorm.weight"], cfg["rms_norm_eps"])
    q = times(h, "self_attn.q_proj.weight").reshape(s, heads, d)
    k = times(h, "self_attn.k_proj.weight").reshape(s, kv_heads, d)
    v = times(h, "self_attn.v_proj.weight").reshape(s, kv_heads, d)
    q = _rotary(q, positions, cfg["rope_theta"])
    k = _lower(_rotary(k, positions, cfg["rope_theta"]), -1, precision)
    v = _lower(v, -1, precision)
    a = _attention(q, k, v, query_block).reshape(s, heads * d)
    x = x + times(a, "self_attn.o_proj.weight")
    h = _rms_norm(x, w[prefix + "post_attention_layernorm.weight"],
                  cfg["rms_norm_eps"])
    gate = jax.nn.silu(times(h, "mlp.gate_proj.weight"))
    return x + times(gate * times(h, "mlp.up_proj.weight"),
                     "mlp.down_proj.weight")


def hidden_states(cfg: Dict, weights: Dict, ids, query_block: int = 1024,
                  precision=None):
    """Final-norm hidden states [s, hidden] of one sequence ``ids`` [s].
    One jitted call per layer, so only one layer's float32 copies live."""
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0])
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, i: e[i].astype(F32))(
            weights["llama.embed_tokens.weight"], ids)
        layer = jax.jit(_layer, static_argnums=(0, 3, 5, 6))
        frozen = _Frozen(cfg)
        for i in range(cfg["num_hidden_layers"]):
            p = f"llama.layers.{i}."
            x = layer(frozen, x, {k: v for k, v in weights.items()
                                  if k.startswith(p)}, p, positions,
                      query_block, precision)
        return jax.jit(_rms_norm, static_argnums=2)(
            x, weights["llama.norm.weight"], cfg["rms_norm_eps"])


class _Frozen(dict):
    """A config dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def _head(x, w, start, count, precision):
    rows = jax.lax.dynamic_slice_in_dim(x, start, count)
    return _lower(rows, -1, precision) @ _lower(w.astype(F32), 0, precision)


def logits(cfg: Dict, weights: Dict, ids, query_block: int = 1024,
           rows: Optional[Tuple[int, int]] = None, precision=None):
    """Float32 logits [s, vocab] of one sequence, or of ``rows[1]``
    positions from ``rows[0]`` on alone (one program whatever the start)."""
    x = hidden_states(cfg, weights, ids, query_block, precision)
    start, count = rows if rows is not None else (0, x.shape[0])
    with jax.default_matmul_precision("highest"):
        return jax.jit(_head, static_argnums=(3, 4))(
            x, weights["lm_head.weight"], start, count, precision)


def loss(cfg: Dict, weights: Dict, ids, query_block: int = 1024) -> float:
    """Mean next-token cross entropy of one sequence: position t predicts
    token t + 1, the last position predicts nothing."""
    ids = jnp.asarray(ids, jnp.int32)
    x = hidden_states(cfg, weights, ids, query_block)

    def block_nll(xb, w, labels):
        lg = xb @ w.astype(F32)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0])

    n = ids.shape[0] - 1
    total = 0.0
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(block_nll)
        for lo in range(0, n, query_block):
            hi = min(lo + query_block, n)
            total += float(fn(x[lo:hi], weights["lm_head.weight"],
                              ids[lo + 1:hi + 1]))
    return total / n
