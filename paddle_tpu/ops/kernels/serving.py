"""Serving-path kernels: KV-cache write + cache/ragged paged attention.

Reference: phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu (paged
KV decode attention) and the write-cache/masked-attention pieces of the
fused_multi_transformer serving path.

TPU-native: fixed-capacity cache buffers with dynamic-slice writes (position
is a TENSOR input, so every decode step reuses one compiled executable), and
one attention op over the paged pool, `ragged_paged_attention`: the Pallas
tile kernel, or a block-table gather + masked SDPA composite under the same
op name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..dispatcher import register_kernel
from .nn import scaled_dot_product_attention
from .pallas.quant_common import (INT8_BOUND, absmax_scale,
                                  quantize_symmetric)
from ...observability import flight_recorder as _flight_mod
from ...observability import metrics as _metrics_mod

# Frozen fallback-reason taxonomy of the quantized-KV pool (same discipline
# as tp_attention.TP_FALLBACK_REASONS: graftcheck's taxonomy rule checks
# literal call sites statically, the runtime membership check below covers
# computed keys).
KV_QUANT_FALLBACK_REASONS = frozenset({
    "kv_int8_dense_cache",   # dense KVCache has no quantized layout;
                             # cache stays at the compute dtype
})

_M_KV_FALLBACK = _metrics_mod.registry().counter(
    "serving.kv.fallback",
    "quantized-KV dispatches that left the dequant fast path "
    "(frozen KV_QUANT_FALLBACK_REASONS)")


def record_fallback(kind: str, key: str, reason: str) -> None:
    """Count + flight-record a quantized-KV fallback. `key` is the
    frozen taxonomy member; `reason` carries the parameterized detail."""
    if key not in KV_QUANT_FALLBACK_REASONS:
        raise ValueError(
            f"unregistered serving fallback reason {key!r} — add it to "
            f"KV_QUANT_FALLBACK_REASONS (frozen so counters cannot fork)")
    _M_KV_FALLBACK.inc()
    if _flight_mod.enabled():
        _flight_mod.recorder().record(
            f"serving.fallback[{kind}]", (reason,), key)


@register_kernel("cache_write")
def cache_write_kernel(cache, new, pos):
    """cache[B,T,H,D]; new[B,S,H,D]; pos scalar → cache with new written at
    [:, pos:pos+S]. Donation-friendly pure update."""
    return jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype),
        (jnp.zeros((), jnp.int32), pos.astype(jnp.int32),
         jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)))


@register_kernel("cache_attention")
def cache_attention_kernel(q, k_cache, v_cache, pos, attn_mask=None,
                           scale=None):
    """Attend q[B,S,H,D] (query positions pos..pos+S-1) against the full
    cache [B,T,KV,D], masking cache slots beyond each query's position.
    attn_mask (bool, broadcastable to [B,H,S,T]) ANDs in padding masks."""
    T = k_cache.shape[1]
    S = q.shape[1]
    qpos = pos.astype(jnp.int32) + jnp.arange(S, dtype=jnp.int32)
    mask = (jnp.arange(T, dtype=jnp.int32)[None, None, None, :]
            <= qpos[None, None, :, None])
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            mask = mask & attn_mask
        else:
            # additive float mask (0 keep / -inf drop), same convention as
            # the non-cache sdpa path: fold the causal mask into the bias
            bias = jnp.where(mask, 0.0, -jnp.inf) + attn_mask.astype(
                jnp.float32)
            return scaled_dot_product_attention(q, k_cache, v_cache,
                                                attn_mask=bias, scale=scale)
    return scaled_dot_product_attention(q, k_cache, v_cache, attn_mask=mask,
                                        scale=scale)


@register_kernel("paged_cache_write")
def paged_cache_write_kernel(pool, new, slot_ids):
    """pool[NB,BS,KV,D]; new[B,S,KV,D]; slot_ids[B*S] (flat
    block*BS+offset per token, row-major over (B,S)) → pool with every
    token written into its slot. S=1 is the per-token decode write; S>1
    is the bulk prefill write."""
    nb, bs = pool.shape[0], pool.shape[1]
    flat = pool.reshape(nb * bs, *pool.shape[2:])
    flat_new = new.reshape(-1, *new.shape[2:])
    flat = flat.at[slot_ids.reshape(-1).astype(jnp.int32)].set(
        flat_new.astype(pool.dtype))
    return flat.reshape(pool.shape)


@register_kernel("paged_cache_write_q")
def paged_cache_write_q_kernel(pool, scale_pool, new, slot_ids):
    """Quantize-on-append paged write: pool[NB,BS,KV,D] int8;
    scale_pool[NB,BS,KV] f32; new[B,S,KV,D] (compute dtype);
    slot_ids[B*S] flat token slots → (pool, scale_pool) updated.

    Each token's scale is the absmax of ITS OWN [D] vector per kv head
    (per-token-slot granularity, K and V pools scaled separately by the
    caller). A coarser one-scale-per-block scheme would requantize
    already-written tokens whenever a later append grew the block's
    absmax — making pool contents depend on the chunking schedule and
    breaking the engine's byte-identical-replay contract. Per-token
    scales keep quantization a pure function of the token's values, so
    every schedule writes bit-identical pool bytes."""
    nb, bs = pool.shape[0], pool.shape[1]
    ids = slot_ids.reshape(-1).astype(jnp.int32)
    flat_new = new.reshape(-1, *new.shape[2:]).astype(jnp.float32)
    scales = absmax_scale(flat_new, axis=-1)           # [B*S, KV]
    q = quantize_symmetric(flat_new, scales[..., None], INT8_BOUND)
    flat = pool.reshape(nb * bs, *pool.shape[2:]).at[ids].set(q)
    sflat = scale_pool.reshape(nb * bs, *scale_pool.shape[2:]) \
        .at[ids].set(scales)
    return flat.reshape(pool.shape), sflat.reshape(scale_pool.shape)


def _ragged_composite(q, k_pool, v_pool, block_tables, context_lens,
                      cu_q_lens, scale=None, k_scale=None, v_scale=None):
    """XLA composite for ragged mixed prefill+decode attention: per-token
    expansion of the dense paged gather. Every packed token gathers its
    row's blocks and attends as a batch-1 decode row whose visible
    context is its own absolute position + 1 — causality inside a
    prefill chunk falls out of the per-token bound. Memory scales with
    T * MB * BS; the Pallas kernel streams blocks instead."""
    T = q.shape[0]
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    R, mb = block_tables.shape
    cu = cu_q_lens.astype(jnp.int32)
    tok = jnp.arange(T, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(cu, tok, side="right")
                   .astype(jnp.int32) - 1, 0, R - 1)
    qlen = cu[row + 1] - cu[row]
    qpos = (context_lens.astype(jnp.int32)[row] - qlen + (tok - cu[row]))
    # step-padding tokens carry garbage positions; clamp so their (then
    # discarded) rows still see one finite score instead of all -inf
    qpos = jnp.clip(qpos, 0, None)
    tbl = jnp.clip(block_tables.astype(jnp.int32), 0, nb - 1)[row]
    k = k_pool[tbl]                    # [T, MB, BS, KV, D]
    v = v_pool[tbl]
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[tbl][..., None]
        v = v.astype(jnp.float32) * v_scale[tbl][..., None]
    k = k.reshape(T, mb * bs, *k.shape[3:])
    v = v.reshape(T, mb * bs, *v.shape[3:])
    mask = (jnp.arange(mb * bs, dtype=jnp.int32)[None, None, None, :]
            <= qpos[:, None, None, None])
    out = scaled_dot_product_attention(q[:, None], k, v, attn_mask=mask,
                                       scale=scale)
    return out[:, 0]


@register_kernel("ragged_paged_attention")
def ragged_paged_attention_kernel(q, k_pool, v_pool, block_tables,
                                  context_lens, cu_q_lens, k_scale=None,
                                  v_scale=None, scale=None):
    """ONE kernel for a ragged mix of prefill chunks and decode rows
    over the paged KV pool (Ragged Paged Attention, arXiv:2604.15464).

    q[T,H*D] packed query tokens (or the view [T,H,D]; the result has q's
    shape) segmented by cu_q_lens[R+1]; pools
    [NB,BS,KV,D]; block_tables[R,MB]; context_lens[R] counts the tokens
    visible per row AFTER this step's chunk was written (write-then-
    attend order). Decode rows contribute q_len 1, prefill chunks their
    chunk size. Routed to the Pallas tile kernel
    (pallas/ragged_paged_attention.py) when FLAGS_use_pallas_kernels;
    under an ambient TP mesh heads shard over mp via shard_map
    (pallas/tp_attention.py); XLA per-token gather composite otherwise,
    with TP fallbacks recording their frozen reason."""
    from ... import flags
    from .pallas import ragged_paged_attention as rpa
    rows = q                # [T, H*D] stays 2-D all the way to the kernel
    q = q.reshape(q.shape[0], -1, k_pool.shape[3])
    if rpa.supported(q.shape, k_pool.shape):
        from .pallas import tp_attention as tpa
        ctx = tpa.current_tp_context()
        if ctx is not None:
            if not flags.get_flag("use_pallas_kernels"):
                tpa.record_fallback("ragged", "flags_off",
                                    "FLAGS_use_pallas_kernels off")
            else:
                mesh, head_axis, batch_axis = ctx
                out = tpa.sharded_ragged_paged_attention(
                    q, k_pool, v_pool, block_tables, context_lens,
                    cu_q_lens, mesh, head_axis, batch_axis, scale,
                    k_scale=k_scale, v_scale=v_scale)
                if out is not None:
                    return out.reshape(rows.shape)
        elif flags.get_flag("use_pallas_kernels"):
            return rpa.ragged_paged_attention(
                rows, k_pool, v_pool, block_tables, context_lens, cu_q_lens,
                scale, k_scale=k_scale, v_scale=v_scale)
    return _ragged_composite(q, k_pool, v_pool, block_tables, context_lens,
                             cu_q_lens, scale, k_scale=k_scale,
                             v_scale=v_scale).reshape(rows.shape)


def _token_rows(tokens, cu_q_lens, slots, start_pos, padding_slot):
    """For each packed token of a ragged step: its row's index into the
    row-state cache (``padding_slot`` for step padding, whose tokens lie
    past ``cu[R]``), whether its row's state starts from zeros at it (the
    first token of a segment at position 0), and whether it belongs to a
    row at all."""
    cu = cu_q_lens.astype(jnp.int32)
    tok = jnp.arange(tokens, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(cu, tok, side="right")
                   .astype(jnp.int32) - 1, 0, cu.shape[0] - 2)
    real = tok < cu[-1]
    slot = jnp.where(real, slots.astype(jnp.int32)[row], padding_slot)
    fresh = real & (tok == cu[row]) & (start_pos[row] == 0)
    return slot, fresh, real


def _scan_composite(x, dt, B, C, z, A_log, D, cu_q_lens, slots, start_pos,
                    state):
    """XLA composite of the ragged selective scan: one `lax.scan` over the
    packed tokens that reads and writes the owning row's state at every
    token. Step padding reads and writes the cache's last row."""
    shape = state.shape
    flat = state.reshape(shape[0], shape[1], -1)           # [S, N, D]
    slot, fresh, real = _token_rows(x.shape[0], cu_q_lens, slots, start_pos,
                                    shape[0] - 1)
    a = -jnp.exp(A_log.astype(jnp.float32))                # [N, D]
    f32 = lambda v: v.astype(jnp.float32)

    def token(flat, t):
        slot_t, fresh_t, x_t, dt_t, b_t, c_t = t
        delta = jax.nn.softplus(dt_t)
        s = jnp.where(fresh_t, 0.0, flat[slot_t])
        s = jnp.exp(delta[None, :] * a) * s \
            + (delta * x_t)[None, :] * b_t[:, None]
        return flat.at[slot_t].set(s), jnp.sum(s * c_t[:, None], 0)

    flat, y = jax.lax.scan(
        token, flat, (slot, fresh, f32(x), f32(dt), f32(B), f32(C)))
    y = (y + f32(D) * f32(x)) * jax.nn.silu(f32(z))
    y = jnp.where(real[:, None], y, 0.0).astype(x.dtype)
    return y, flat.reshape(shape)


def _conv_composite(x, weight, bias, cu_q_lens, slots, start_pos, tail):
    """XLA composite of the ragged causal convolution: a `lax.scan` over
    the packed tokens that shifts the owning row's tail at every token."""
    shape = tail.shape
    flat = tail.reshape(shape[0], shape[1], -1)            # [S, K-1, D]
    slot, fresh, real = _token_rows(x.shape[0], cu_q_lens, slots, start_pos,
                                    shape[0] - 1)
    w = weight.astype(jnp.float32)

    def token(flat, t):
        slot_t, fresh_t, x_t = t
        before = jnp.where(fresh_t, 0.0, flat[slot_t].astype(jnp.float32))
        window = jnp.concatenate([before, x_t[None]])      # [K, D]
        return (flat.at[slot_t].set(window[1:].astype(flat.dtype)),
                jnp.sum(window * w, 0))

    flat, y = jax.lax.scan(token, flat,
                           (slot, fresh, x.astype(jnp.float32)))
    y = jax.nn.silu(y + bias.astype(jnp.float32))
    y = jnp.where(real[:, None], y, 0.0).astype(x.dtype)
    return y, flat.reshape(shape)


def _row_state_pallas(d_inner):
    """The Pallas module of the two row-state ops where it takes the width
    and FLAGS_use_pallas_kernels is on; None for the composites."""
    from ... import flags
    from .pallas import ragged_selective_scan as rss
    if rss.supported(d_inner) and flags.get_flag("use_pallas_kernels"):
        return rss
    return None


@register_kernel("ragged_selective_scan")
def ragged_selective_scan_kernel(x, dt, B, C, z, A_log, D, cu_q_lens, slots,
                                 start_pos, state):
    """The selective scan of a Mamba-1 layer over a ragged step, each
    row's segment continuing from the row's own state.

    x, dt, z[T, D] packed over rows by cu_q_lens[R+1] (x after the
    convolution and SiLU, dt before its softplus); B, C[T, N];
    A_log[N, D]; D[D]; state[S, N, D/128, 128] float32, row r's at
    slots[r]; start_pos[R] the position of each row's first token, a
    segment at position 0 starting from zeros. Returns y * silu(z) and
    the state, the rows that had tokens updated: s = exp(delta A) s +
    delta x B, y = s C + D x, delta = softplus(dt), A = -exp(A_log).
    The Pallas kernel (pallas/ragged_selective_scan.py) or the XLA
    composite, chosen as ragged_paged_attention chooses."""
    rss = _row_state_pallas(x.shape[1])
    fn = rss.ragged_selective_scan if rss else _scan_composite
    return fn(x, dt, B, C, z, A_log, D, cu_q_lens, slots, start_pos, state)


@register_kernel("ragged_causal_conv")
def ragged_causal_conv_kernel(x, weight, bias, cu_q_lens, slots, start_pos,
                              tail):
    """The depthwise causal convolution (and SiLU) of a Mamba-1 layer over
    a ragged step: x[T, D], weight[K, D], bias[D], tail[S, K-1, D/128,
    128] each row's last K-1 inputs at slots[r], zeros before a segment
    at position 0. Returns silu(conv(x)) and the tail, the rows that had
    tokens updated."""
    rss = _row_state_pallas(x.shape[1])
    fn = rss.ragged_causal_conv if rss else _conv_composite
    return fn(x, weight, bias, cu_q_lens, slots, start_pos, tail)


def _filter_logits(logits, temperature, top_k, top_p):
    """Temperature/top-k/top-p filtering shared by both sampling heads."""
    logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
    V = logits.shape[-1]
    if top_k and top_k < V:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p (keep at least 1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


@register_kernel("sample_logits")
def sample_logits_kernel(logits, key, temperature=1.0, top_k=0, top_p=1.0):
    """Token sampling head: greedy when temperature==0, else
    temperature/top-k/top-p filtered categorical draw. logits[B,V] → [B].
    The key is injected from the GLOBAL generator (ops.yaml `key: true`),
    so draws depend on every other consumer of the global stream — fine
    for generate(), wrong for a serving engine (see sample_logits_keyed)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _filter_logits(logits, temperature, top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@register_kernel("sample_logits_keyed")
def sample_logits_keyed_kernel(logits, key_data, stream_pos,
                               temperature=1.0, top_k=0, top_p=1.0):
    """Per-row keyed sampling for the serving engine: logits[B,V],
    key_data[B,W] (raw uint32 key data of each row's PRIVATE stream,
    jax.random.key_data of a per-request key), stream_pos[B] int32 (the
    row's token index, folded in per draw) → [B] int32.

    Row r's draw is a pure function of (its key, its token index), so
    a request's stochastic output is SCHEDULE-INDEPENDENT: batching,
    chunked prefill, and preemption re-ordering never change which key
    samples which token — the property the continuous-batching engine
    needs for deterministic replay and preemption-transparent output."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # threefry, NOT FLAGS_rng_impl: the rbg generator's bits depend on a
    # key's position inside a vmapped batch, so a request's draw would
    # change with the slot it happens to occupy — exactly the
    # schedule-dependence this op exists to eliminate. threefry draws are
    # a pure function of (key, shape).
    keys = jax.random.wrap_key_data(key_data, impl="threefry2x32")  # [B]
    keys = jax.vmap(jax.random.fold_in)(keys,
                                        stream_pos.astype(jnp.uint32))
    filt = _filter_logits(logits, temperature, top_k, top_p)
    return jax.vmap(jax.random.categorical)(keys, filt).astype(jnp.int32)
