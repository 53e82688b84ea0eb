"""Ring attention — the SEP/context-parallel execution engine.

Reference counterpart: the reference has NO in-tree ring attention — its
sequence parallelism is a mesh axis + model-side gathers (`SegmentParallel`
`fleet/meta_parallel/segment_parallel.py:26`, 4-direction p2p
`pp_utils/four_directions_p2p_communication.py`, flash-attn SPMD rule
`phi/infermeta/spmd_rules/flash_attention.cc`); SURVEY.md §5 flags true
ring attention as a must-exceed item for the TPU build.

Design: sequence dim sharded over the `sep` mesh axis. Each device keeps
its q shard resident and rotates the K/V shards around the ring with
`lax.ppermute` (ICI neighbor exchange), merging per-block attention
results with the online-softmax rule

    lse = logaddexp(lse_a, lse_b)
    out = out_a * exp(lse_a - lse) + out_b * exp(lse_b - lse)

so peak score memory is (s/P)^2 instead of s^2 and K/V never materialise
globally. Causality is positional: block (me, src) masks with global
indices, so blocks entirely above the diagonal contribute exp(-inf)=0 and
the merge is a no-op (wasted flops, not wrong results; zigzag load
balancing is a later optimisation).

Backward is jax AD through the rotation scan: ppermute transposes to the
reverse rotation, which IS the ring-attention backward pass.

Per-block math has TWO implementations, selected by shard shape
(`_pallas_block_supported`):
  - `_ring_local_pallas` (s/P >= 128, block-aligned): each block runs the
    Pallas flash kernel via `flash_block` — a custom_vjp whose lse output
    is differentiable (the merge weights consume it; its cotangent folds
    into the backward delta term, flash_attention.py:242-249) — so BOTH
    forward and backward are flash-style: no (s/P)^2 score matrix ever
    round-trips HBM. Ring position picks the mask branch statically
    (full / diagonal-causal / masked) via lax.switch.
  - `_ring_local` (small/unaligned shards, CPU tests): plain XLA einsum +
    logsumexp blocks, differentiated by AD.
Parity for values and all three grads: tests/test_pallas_and_pp.py
(TestRingAttention); block-level speed against the XLA composite: not
measured (no benchmark cell runs this kernel; PERF.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ....jax_compat import shard_map

_NEG = -1e30


def _block_attn(q, k, v, row0, col0, s_loc, causal, scale):
    """One q-shard x kv-shard attention block.

    q: [b, sl, hq, d]; k/v: [b, sl, hk, d]; row0/col0: global offsets of the
    q rows / kv cols (traced scalars). Returns (out [b, sl, hq, d] f32,
    lse [b, hq, sl] f32)."""
    b, sl, hq, d = q.shape
    hk = k.shape[2]
    if hk != hq:  # GQA
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (sl, sl), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (sl, sl), 1)
        logits = jnp.where((cols <= rows)[None, None], logits, _NEG)
    m = jnp.max(logits, axis=-1)                        # [b, h, sl]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)                             # [b, h, sl]
    # fully-masked rows keep a FINITE huge-negative lse (~_NEG): the merge
    # weight exp(lse_j - lse) underflows to 0 without -inf - -inf = nan
    lse = m + jnp.log(l)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o = o / jnp.transpose(l, (0, 2, 1))[..., None]      # normalised block
    return o, lse


def _merge(o_a, lse_a, o_b, lse_b):
    lse = jnp.logaddexp(lse_a, lse_b)                   # [b, h, sl]
    wa = jnp.exp(lse_a - lse)
    wb = jnp.exp(lse_b - lse)
    to = lambda w: jnp.transpose(w, (0, 2, 1))[..., None]  # -> [b, sl, h, 1]
    return o_a * to(wa) + o_b * to(wb), lse


def _ring_local(q, k, v, axis_name, num_shards, causal, scale):
    """Per-device body (under shard_map): q/k/v are local seq shards."""
    me = jax.lax.axis_index(axis_name)
    Pn = num_shards
    b, sl, hq, d = q.shape
    perm = [(i, (i + 1) % Pn) for i in range(Pn)]

    o0 = jnp.zeros((b, sl, hq, d), jnp.float32)
    lse0 = jnp.full((b, hq, sl), _NEG, jnp.float32)

    def step(carry, j):
        o_acc, lse_acc, kk, vv = carry
        src = (me - j) % Pn                 # owner of the kv we hold now
        o_j, lse_j = _block_attn(q, kk, vv, me * sl, src * sl, sl,
                                 causal, scale)
        o_acc, lse_acc = _merge(o_acc, lse_acc, o_j, lse_j)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (o_acc, lse_acc, kk, vv), None

    (o, lse, _, _), _ = jax.lax.scan(
        step, (o0, lse0, k, v), jnp.arange(Pn))
    return o.astype(q.dtype)


def _ring_local_pallas(q, k, v, axis_name, num_shards, causal, scale):
    """Per-device body using the Pallas flash kernel per block (the
    "planned optimisation" of the module docstring, now real). Ring
    position decides the mask statically-per-branch: a kv shard is either
    fully visible (src < me), diagonal (src == me → causal flash), or
    fully masked (src > me) — `lax.switch` picks the compiled branch, so
    global offsets never enter the kernels."""
    from .flash_attention import flash_block

    me = jax.lax.axis_index(axis_name)
    Pn = num_shards
    b, sl, hq, d = q.shape
    hk = k.shape[2]
    perm = [(i, (i + 1) % Pn) for i in range(Pn)]

    def fold(x, h):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, sl, d)

    qf = fold(q, hq)
    o0 = jnp.zeros((b * hq, sl, d), jnp.float32)
    lse0 = jnp.full((b * hq, sl), _NEG, jnp.float32)

    def step(carry, j):
        o_acc, lse_acc, kk, vv = carry
        src = (me - j) % Pn

        def full():
            o, lse = flash_block(qf, kk, vv, False, scale)
            return o.astype(jnp.float32), lse

        def diag():
            o, lse = flash_block(qf, kk, vv, True, scale)
            return o.astype(jnp.float32), lse

        def masked():
            return jnp.zeros_like(o0), jnp.full_like(lse0, _NEG)

        if causal:
            case = jnp.where(src < me, 0, jnp.where(src == me, 1, 2))
            o_j, lse_j = jax.lax.switch(case, [full, diag, masked])
        else:
            o_j, lse_j = full()
        lse_new = jnp.logaddexp(lse_acc, lse_j)
        wa = jnp.exp(lse_acc - lse_new)[..., None]
        wb = jnp.exp(lse_j - lse_new)[..., None]
        o_acc = o_acc * wa + o_j * wb
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (o_acc, lse_new, kk, vv), None

    (o, _, _, _), _ = jax.lax.scan(
        step, (o0, lse0, fold(k, hk), fold(v, hk)), jnp.arange(Pn))
    return jnp.swapaxes(o.reshape(b, hq, sl, d), 1, 2).astype(q.dtype)


_RING_CACHE: dict = {}


def _pallas_block_supported(q_shape, k_shape) -> bool:
    from .flash_attention import _block
    b, sl, hq, d = q_shape
    hk = k_shape[2]
    return (hq % hk == 0 and sl >= 128
            and _block(sl, 512) is not None)


def ring_attention(query, key, value, mesh, axis_name: str = "sep",
                   causal: bool = False, scale=None, head_axis=None):
    """[b, s, h, d] attention with the seq dim sharded over `axis_name`.

    Same contract as flash_attention/scaled_dot_product_attention; the
    caller's arrays should already be sharded (or shardable) on dim 1.
    Per-block math runs through the Pallas flash kernel when the local
    shard shape supports it (s/P >= 128, block-aligned), else the XLA
    composite blocks.

    `head_axis` additionally shards the HEAD dim over a tensor-parallel
    mesh axis inside the same region (GSPMD TP x SEP composition,
    tp_attention.py stance): the ring body is head-independent, so each
    (sep, mp) shard rotates only its local kv-head slice — ppermute
    payloads shrink by the tp degree. Falls back to head-replicated
    when the head counts don't divide the tp degree (recorded)."""
    d = query.shape[-1]
    if scale is None:
        scale = d ** -0.5
    num = mesh.shape[axis_name]
    sl = query.shape[1] // num
    ha = None
    if head_axis is not None and mesh.shape.get(head_axis, 1) > 1:
        tp = mesh.shape[head_axis]
        if query.shape[2] % tp == 0 and key.shape[2] % tp == 0:
            ha = head_axis
        else:
            from .tp_attention import record_fallback
            record_fallback(
                "ring", "ring_head_replicated",
                f"heads {query.shape[2]}/{key.shape[2]} not "
                f"divisible by tp degree {tp} (head-replicated "
                f"ring instead)")
    hdiv = mesh.shape[ha] if ha else 1
    use_pallas = _pallas_block_supported(
        (query.shape[0], sl, query.shape[2] // hdiv, d),
        (key.shape[0], sl, key.shape[2] // hdiv, d))
    ck = (mesh, axis_name, ha, num, causal, float(scale), use_pallas)
    fn = _RING_CACHE.get(ck)
    if fn is None:
        body = _ring_local_pallas if use_pallas else _ring_local
        local = lambda q, k, v: body(q, k, v, axis_name, num,
                                     causal, float(scale))
        spec = P(None, axis_name) if ha is None else P(None, axis_name, ha)
        fn = jax.jit(shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names=frozenset(a for a in (axis_name, ha) if a),
            check_vma=False))
        _RING_CACHE[ck] = fn
    return fn(query, key, value)
