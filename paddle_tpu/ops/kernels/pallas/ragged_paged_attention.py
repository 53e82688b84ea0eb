"""Ragged paged attention: ONE Pallas kernel for mixed prefill + decode.

Reference counterpart: the "Ragged Paged Attention" TPU serving kernel
(arXiv:2604.15464) that vLLM-lineage TPU backends use to serve a ragged
mix of prefill chunks and decode rows in a single invocation over the
paged KV pool. The per-regime split the old serving path had — batch-1
SDPA prefill + `paged_attention.py` gang decode — forced the scheduler
to stall every decode step around each admitted prompt; this kernel
removes the regime split entirely: every row of a step contributes
``q_len`` query tokens (1 for decode rows, the chunk size for prefill
chunks) and attends causally against its own block-table slice of the
shared pool.

Layout: packed queries ``q[T, H, D]`` segmented by ``cu_q_lens[R+1]``
(row r owns tokens ``cu[r]:cu[r+1]`` at absolute positions
``context_lens[r] - q_len_r + i`` — the chunk is already written to the
pool, write-then-attend order). The kernel tiles the ragged token axis
into fixed ``TQ=8``-token q tiles (a decode row is one mostly-padded
tile; a chunk of C tokens is ``ceil(C/8)`` tiles), so the grid is
``(NT, MB)`` with tile metadata (owning row, absolute position of the
tile's first token, valid count) scalar-prefetched — the same
block-table streaming discipline as ``paged_attention.py``: each step
DMAs ONE pool block ``[BS, KV, D]`` into VMEM and attends the whole
tile against it, online-softmax state ``(m, l, acc)`` living in VMEM
scratch across the kv-block grid dimension. Blocks past a tile's causal
horizon are predicated off with ``pl.when`` — compute scales with
``sum(q_len_r * context_len_r)``, not the padded rectangle.

``NT = R + ceil(T/TQ)`` is a static upper bound on the tile count
(each row wastes at most one partial tile), so an engine with a fixed
token budget and row count reuses ONE compiled executable for every
step, whatever the prefill/decode mix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret  # shared interpret override

_NEG = -1e30

TQ = 8  # query tokens per tile (f32 sublane)


def supported(q_shape, pool_shape) -> bool:
    """Whether the Pallas path handles this case (else XLA composite)."""
    t, h, d = q_shape
    kv, pd = pool_shape[2], pool_shape[3]
    return h % kv == 0 and d == pd


def _kernel(row_ref, qp0_ref, qc_ref, tbl_ref, q_ref, k_ref, v_ref, *rest,
            bs, mb, kv, g, scale, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    t, j = pl.program_id(0), pl.program_id(1)
    qc = qc_ref[t]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal horizon: the tile's LAST token position bounds every kv
    # position any of its tokens may see; empty (padding) tiles skip all
    @pl.when((qc > 0) & (j * bs <= qp0_ref[t] + qc - 1))
    def _():
        q = q_ref[0].astype(jnp.float32)                       # [KV, TG, D]
        kf = k_ref[0].astype(jnp.float32)                      # [BS, KV, D]
        vf = v_ref[0].astype(jnp.float32)
        if quantized:
            # int8 pool: dequant at the VMEM tile — the block arrived
            # from HBM at int8 bytes; one [BS, KV] scale tile rode the
            # same block-table index (weight_only_gemm playbook)
            kf = kf * ks_ref[0][..., None]
            vf = vf * vs_ref[0][..., None]
        k = jnp.swapaxes(kf, 0, 1)                             # [KV, BS, D]
        v = jnp.swapaxes(vf, 0, 1)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale        # [KV, TG, BS]
        kvpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        qlocal = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // g
        live = (kvpos <= qp0_ref[t] + qlocal) & (qlocal < qc)
        s = jnp.where(live, s, _NEG)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(live, p, 0.0)   # exp(-1e30 - -1e30) = 1 guard
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                # [KV, TG, D]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == mb - 1)
    def _():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)   # fully-masked padding lanes
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                           cu_q_lens, scale=None, k_scale=None,
                           v_scale=None):
    """q [T, H, D] packed over rows; pools [NB, BS, KV, D];
    block_tables [R, MB] int32; context_lens [R] visible tokens per row
    AFTER this step's write; cu_q_lens [R+1] ragged row segmentation of
    the packed token axis. Returns [T, H, D].

    k_scale/v_scale [NB, BS, KV] f32 (int8 pool): per-token-slot
    per-kv-head dequant scales riding the block table — each kv block's
    scale tile is DMA'd by the same index map as the block itself and
    the dequant happens inside the VMEM tile load, so HBM reads stay at
    int8 bytes."""
    T, H, D = q.shape
    NB, BS, KV, _ = k_pool.shape
    R, MB = block_tables.shape
    G = H // KV
    TG = TQ * G
    if scale is None:
        scale = D ** -0.5
    NT = R + -(-T // TQ)   # static tile-count upper bound

    cu = cu_q_lens.astype(jnp.int32)
    ctx = context_lens.astype(jnp.int32)
    qlen = cu[1:] - cu[:-1]                                    # [R]
    tile_cu = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum((qlen + TQ - 1) // TQ, dtype=jnp.int32)])  # [R+1]
    tiles = jnp.arange(NT, dtype=jnp.int32)
    row_of = jnp.clip(
        jnp.searchsorted(tile_cu, tiles, side="right").astype(jnp.int32) - 1,
        0, R - 1)
    local = tiles - tile_cu[row_of]                  # tile index within row
    tok0 = cu[row_of] + local * TQ
    qcount = jnp.clip(qlen[row_of] - local * TQ, 0, TQ)
    qpos0 = ctx[row_of] - qlen[row_of] + local * TQ

    # pack q into tiles: [T, H, D] -> [NT, KV, TQ*G, D] (zero-padded)
    slot = jnp.arange(TQ, dtype=jnp.int32)
    tok_idx = jnp.where(slot[None, :] < qcount[:, None],
                        tok0[:, None] + slot[None, :], T)
    q_pad = jnp.concatenate([q, jnp.zeros((1, H, D), q.dtype)])
    q_tiles = (q_pad[tok_idx.reshape(-1)]
               .reshape(NT, TQ, KV, G, D)
               .transpose(0, 2, 1, 3, 4)
               .reshape(NT, KV, TG, D))

    quantized = k_scale is not None
    block_spec = pl.BlockSpec((1, BS, KV, D),
                              lambda t, j, row, qp0, qc, tbl:
                              (tbl[row[t], j], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, BS, KV),
                              lambda t, j, row, qp0, qc, tbl:
                              (tbl[row[t], j], 0, 0))
    in_specs = [
        pl.BlockSpec((1, KV, TG, D), lambda t, j, *_: (t, 0, 0, 0)),
        block_spec, block_spec,
    ]
    operands = [q_tiles, k_pool, v_pool]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(NT, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, TG, D), lambda t, j, *_: (t, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((KV, TG, 1), jnp.float32),
                        pltpu.VMEM((KV, TG, 1), jnp.float32),
                        pltpu.VMEM((KV, TG, D), jnp.float32)],
    )
    out_dtype = q.dtype
    out = pl.pallas_call(
        functools.partial(_kernel, bs=BS, mb=MB, kv=KV, g=G,
                          scale=float(scale), quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NT, KV, TG, D), out_dtype),
        name="ragged_paged_attention",
        interpret=_interpret(),
    )(row_of, qpos0, qcount,
      jnp.clip(block_tables.astype(jnp.int32), 0, NB - 1),
      *operands)

    # unpack tiles back to the packed token axis; tokens past cu[R]
    # (step padding) read the appended zero row
    tok = jnp.arange(T, dtype=jnp.int32)
    trow = jnp.clip(
        jnp.searchsorted(cu, tok, side="right").astype(jnp.int32) - 1,
        0, R - 1)
    tlocal = tok - cu[trow]
    src = (tile_cu[trow] + tlocal // TQ) * TQ + tlocal % TQ
    src = jnp.where(tok < cu[R], src, NT * TQ)
    out_flat = (out.reshape(NT, KV, TQ, G, D)
                .transpose(0, 2, 1, 3, 4)
                .reshape(NT * TQ, H, D))
    out_flat = jnp.concatenate([out_flat, jnp.zeros((1, H, D), out.dtype)])
    return out_flat[src]
