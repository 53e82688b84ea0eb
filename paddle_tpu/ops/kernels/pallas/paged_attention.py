"""Paged-KV decode attention as a Pallas TPU kernel.

Reference counterpart: `paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu` — the paged (block-table) KV decode
attention of the serving path. The XLA composite in kernels/serving.py
gathers every sequence's blocks into a dense [B, MB*BS, KV, D] buffer in
HBM before attending; this kernel instead streams KV blocks pool→VMEM
directly, addressed by a scalar-prefetched block table, so:

- no dense gather materializes in HBM (the composite's extra
  B*MB*BS*KV*D read+write round trip disappears),
- blocks at or past `context_len` are predicated off with `pl.when` —
  compute scales with the actual context, not the padded table width,
- online-softmax state (m, l, acc) lives in VMEM scratch across the
  block-indexed grid dimension (flash-attention decode form).

Layout: grid (B, MB); each step loads one pool block [BS, KV, D] ONCE and
attends every query head against it (GQA groups batched as a leading dim),
so pool bandwidth is optimal and the block's trailing dims stay
tile-aligned for Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret  # shared interpret override

_NEG = -1e30


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, bs, mb, kv, g8, scale):
    b, j = pl.program_id(0), pl.program_id(1)
    ctx = len_ref[b]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * bs < ctx)
    def _():
        q = q_ref[0].astype(jnp.float32).reshape(kv, g8, -1)   # [KV, G8, D]
        k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)   # [KV, BS, D]
        v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale        # [KV, G8, BS]
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < ctx, s, _NEG)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)                                 # [KV, G8, BS]
        alpha = jnp.exp(m_prev - m_new)                        # [KV, G8, 1]
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                # [KV, G8, D]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == mb - 1)
    def _():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.reshape(kv * g8, -1).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    scale=None):
    """q [B, 1, H, D]; pools [NB, BS, KV, D]; block_tables [B, MB] int32;
    context_lens [B]. Returns [B, 1, H, D]."""
    B, _, H, D = q.shape
    NB, BS, KV, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = H // KV
    if scale is None:
        scale = D ** -0.5
    G8 = max(8, -(-G // 8) * 8)
    Hp = KV * G8
    # [B, 1, H, D] -> [B, KV*G8, D] (zero-padded query groups)
    qr = q[:, 0].reshape(B, KV, G, D)
    if G8 != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, G8 - G), (0, 0)))
    qr = qr.reshape(B, Hp, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, Hp, D), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, BS, KV, D),
                         lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, BS, KV, D),
                         lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hp, D), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((KV, G8, 1), jnp.float32),
                        pltpu.VMEM((KV, G8, 1), jnp.float32),
                        pltpu.VMEM((KV, G8, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=BS, mb=MB, kv=KV, g8=G8,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, D), q.dtype),
        name="paged_attention",
        interpret=_interpret(),
    )(jnp.clip(block_tables.astype(jnp.int32), 0, NB - 1),
      context_lens.astype(jnp.int32), qr, k_pool, v_pool)
    return out.reshape(B, KV, G8, D)[:, :, :G].reshape(B, 1, H, D)
