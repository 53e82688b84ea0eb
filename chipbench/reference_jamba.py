"""The plain reference of the Jamba family: Mamba-1 layers and attention
layers in straightforward float32 ``jax.numpy``.

No kernels, no cache, no batching, nothing imported from the program. It
follows `transformers`' `modeling_jamba.py` (`JambaMambaMixer.slow_forward`,
`JambaAttentionDecoderLayer`, `JambaMambaDecoderLayer`) at ``num_experts =
1``, where the expert keys select nothing:

    h = embed[ids]                     (no scaling, no positions of any kind)
    for layer i:  h = h + mixer_i(rms(h; w_in_i))
                  h = h + ffn_i(rms(h; w_ff_i))
    logits = rms(h; w_final) @ embed.T          (the head is tied)
    rms(x; w) = w * x / sqrt(mean(x^2) + eps);  ffn(x) = W_down (silu(W_gate x) * (W_up x))

Layer i is attention where ``i % attn_layer_period == attn_layer_offset``:
``q = W_q x`` as H heads, ``k = W_k x``, ``v = W_v x`` as KV heads, NO rotary
embedding, causal softmax of ``q k^T / sqrt(d)``, ``W_o``; no bias. Every
other layer is a Mamba-1 mixer with Jamba's three inner norms:

    [x, z] = split(W_in u)                      (x first, the gate z second)
    x_t = silu(b_conv + sum_j w_conv[j] * x_{t-K+1+j})    (depthwise, causal, zeros before the sequence)
    [dt, B, C] = split(W_x x_t; R, N, N), each RMS-normed with its own weight
    delta = softplus(W_dt dt + b_dt);  A = -exp(A_log)
    s_t = exp(delta A) * s_{t-1} + (delta x_t) B^T,  s in float32, s_{-1} = 0
    y_t = s_t C + D x_t;   out = W_out (y_t * silu(z_t))

The recurrence is a ``lax.scan`` over positions. Departures from that file:
none in the mathematics. Two in layout, which change no value: weights come
as the program holds them, matrices ``[in, out]``, ``A_log`` as ``[N, D]``
and the convolution's weight as ``[K, D]`` (tap K - 1 multiplies the
current token); and attention and the head run over blocks of
``query_block`` positions, for memory. Weights are raised to float32 one
layer at a time, so a float32 copy of the whole model never exists.

``precision`` is for the control of the benchmark's check and for nothing
else: ``bf16``, ``int8`` or ``fp8`` round both operands of every matrix
product and the keys and values first, as `reference._lower` does;
``state_bf16`` keeps the recurrent state in bfloat16 between positions
where the configuration says float32. ``None`` is the reference.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .reference import F32, _Frozen, _attention, _lower, _rms_norm


def is_attention(cfg: Dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def weight_shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    """Name and shape of every weight, as ``JambaForCausalLM`` names them."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    d = cfg["mamba_expand"] * h
    n, k, r = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    shapes = {"jamba.embed_tokens.weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"jamba.layers.{i}."
        if is_attention(cfg, i):
            shapes.update({
                p + "self_attn.q_proj.weight": (h, h),
                p + "self_attn.k_proj.weight": (h, kv),
                p + "self_attn.v_proj.weight": (h, kv),
                p + "self_attn.o_proj.weight": (h, h)})
        else:
            shapes.update({
                p + "mamba.in_proj.weight": (h, 2 * d),
                p + "mamba.conv_weight": (k, d),
                p + "mamba.conv_bias": (d,),
                p + "mamba.x_proj.weight": (d, r + 2 * n),
                p + "mamba.dt_layernorm.weight": (r,),
                p + "mamba.b_layernorm.weight": (n,),
                p + "mamba.c_layernorm.weight": (n,),
                p + "mamba.dt_proj.weight": (r, d),
                p + "mamba.dt_proj.bias": (d,),
                p + "mamba.A_log": (n, d),
                p + "mamba.D": (d,),
                p + "mamba.out_proj.weight": (d, h)})
        shapes.update({
            p + "feed_forward.gate_proj.weight": (h, f),
            p + "feed_forward.up_proj.weight": (h, f),
            p + "feed_forward.down_proj.weight": (f, h),
            p + "input_layernorm.weight": (h,),
            p + "pre_ff_layernorm.weight": (h,)})
    shapes["jamba.final_layernorm.weight"] = (h,)
    return shapes


def _make_group(key, first, kinds, std, dtype):
    """The weights ``kinds`` (local name, shape), the j-th drawing from
    ``key`` folded with ``first + j``."""
    out = {}
    for j, (name, shape) in enumerate(kinds):
        if name.endswith("A_log"):
            out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=F32))[:, None], shape).astype(dtype)
        elif name.endswith("bias"):
            out[name] = jnp.zeros(shape, dtype)
        elif len(shape) == 1:
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, first + j),
                                           shape, F32) * std).astype(dtype)
    return out


def make_weights(cfg: Dict, seed: int, dtype) -> Dict[str, jax.Array]:
    """Every weight, made on the device: matrices and the convolution
    normal(0, ``initializer_range``), norm weights and ``D`` 1, the two
    biases 0, ``A_log[n, c] = log(n + 1)``; the i-th weight of
    ``weight_shapes`` draws from the seed's key folded with i. One jitted
    call a layer: the layers of a kind share one program, where one call
    for all 462 arrays took the chip's compiler 90 s."""
    std = cfg.get("initializer_range", 0.02)
    groups = []             # [prefix, index of its first weight, kinds]
    for i, (name, shape) in enumerate(weight_shapes(cfg).items()):
        m = re.match(r"(jamba\.layers\.\d+\.)(.*)", name)
        prefix, local = m.groups() if m else (name, "")
        if not groups or groups[-1][0] != prefix:
            groups.append([prefix, i, []])
        groups[-1][2].append((local, shape))
    make = jax.jit(_make_group, static_argnums=(2, 3, 4))
    key = jax.random.key(seed % (2 ** 31 - 1))
    out = {}
    for prefix, first, kinds in groups:
        made = make(key, first, tuple(kinds), std, jnp.dtype(dtype))
        out.update({prefix + local: w for local, w in made.items()})
    return out


def _selective_scan(x, delta, a, b, c, state_bf16):
    """``s_t = exp(delta_t A) s_{t-1} + (delta_t x_t) B_t^T``, ``y_t = s_t
    C_t``, from ``s = 0``. x, delta [s, D]; a [N, D]; b, c [s, N]."""

    def position(s, t):
        x_t, delta_t, b_t, c_t = t
        s = jnp.exp(delta_t[None, :] * a) * s \
            + (delta_t * x_t)[None, :] * b_t[:, None]
        if state_bf16:
            # not a pair of casts: XLA drops those on the chip (it read 0)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.sum(s * c_t[:, None], 0)

    _, y = jax.lax.scan(position, jnp.zeros(a.shape, F32), (x, delta, b, c))
    return y


def _mamba(cfg, u, w, p, times, precision):
    n, k, r = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    eps = cfg["rms_norm_eps"]
    x, z = jnp.split(times(u, "mamba.in_proj.weight"), 2, -1)
    taps = w[p + "mamba.conv_weight"].astype(F32)               # [K, D]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), F32), x])
    x = jax.nn.silu(w[p + "mamba.conv_bias"].astype(F32) + sum(
        taps[j] * padded[j:j + x.shape[0]] for j in range(k)))
    dt, b, c = jnp.split(times(x, "mamba.x_proj.weight"), [r, r + n], -1)
    dt = _rms_norm(dt, w[p + "mamba.dt_layernorm.weight"], eps)
    b = _rms_norm(b, w[p + "mamba.b_layernorm.weight"], eps)
    c = _rms_norm(c, w[p + "mamba.c_layernorm.weight"], eps)
    delta = jax.nn.softplus(times(dt, "mamba.dt_proj.weight")
                            + w[p + "mamba.dt_proj.bias"].astype(F32))
    a = -jnp.exp(w[p + "mamba.A_log"].astype(F32))              # [N, D]
    y = _selective_scan(x, delta, a, b, c, precision == "state_bf16") \
        + w[p + "mamba.D"].astype(F32) * x
    return times(y * jax.nn.silu(z), "mamba.out_proj.weight")


def _layer(cfg, x, w, p, attention, query_block, precision=None):
    # ``state_bf16`` lowers the recurrent state alone, no matrix product
    low = None if precision == "state_bf16" else precision
    eps = cfg["rms_norm_eps"]

    def times(a, name):
        return _lower(a, -1, low) @ _lower(w[p + name].astype(F32), 0, low)

    u = _rms_norm(x, w[p + "input_layernorm.weight"], eps)
    if attention:
        heads, kv_heads = (cfg["num_attention_heads"],
                           cfg["num_key_value_heads"])
        s, d = x.shape[0], cfg["hidden_size"] // heads
        q = times(u, "self_attn.q_proj.weight").reshape(s, heads, d)
        k = _lower(times(u, "self_attn.k_proj.weight")
                   .reshape(s, kv_heads, d), -1, low)
        v = _lower(times(u, "self_attn.v_proj.weight")
                   .reshape(s, kv_heads, d), -1, low)
        x = x + times(_attention(q, k, v, query_block).reshape(s, heads * d),
                      "self_attn.o_proj.weight")
    else:
        x = x + _mamba(cfg, u, w, p, times, precision)
    u = _rms_norm(x, w[p + "pre_ff_layernorm.weight"], eps)
    gate = jax.nn.silu(times(u, "feed_forward.gate_proj.weight"))
    return x + times(gate * times(u, "feed_forward.up_proj.weight"),
                     "feed_forward.down_proj.weight")


def hidden_states(cfg: Dict, weights: Dict, ids, query_block: int = 1024,
                  precision=None):
    """Final-norm hidden states [s, hidden] of one sequence ``ids`` [s].
    One jitted call per layer, so only one layer's float32 copies live."""
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, i: e[i].astype(F32))(
            weights["jamba.embed_tokens.weight"], ids)
        layer = jax.jit(_layer, static_argnums=(0, 3, 4, 5, 6))
        frozen = _Frozen(cfg)
        for i in range(cfg["num_hidden_layers"]):
            # every layer of a kind under one prefix: two programs in all
            p = f"jamba.layers.{i}."
            x = layer(frozen, x, {"L." + k[len(p):]: v
                                  for k, v in weights.items()
                                  if k.startswith(p)}, "L.",
                      is_attention(cfg, i), query_block, precision)
        return jax.jit(_rms_norm, static_argnums=2)(
            x, weights["jamba.final_layernorm.weight"], cfg["rms_norm_eps"])


def _head(x, embed, start, count, precision):
    rows = jax.lax.dynamic_slice_in_dim(x, start, count)
    return _lower(rows, -1, precision) @ _lower(embed.astype(F32), -1,
                                                precision).T


def logits(cfg: Dict, weights: Dict, ids, query_block: int = 1024,
           rows: Optional[Tuple[int, int]] = None, precision=None):
    """Float32 logits [s, vocab] of one sequence, or of ``rows[1]``
    positions from ``rows[0]`` on alone (one program whatever the start)."""
    x = hidden_states(cfg, weights, ids, query_block, precision)
    start, count = rows if rows is not None else (0, x.shape[0])
    low = None if precision == "state_bf16" else precision
    with jax.default_matmul_precision("highest"):
        return jax.jit(_head, static_argnums=(3, 4))(
            x, weights["jamba.embed_tokens.weight"], start, count, low)
