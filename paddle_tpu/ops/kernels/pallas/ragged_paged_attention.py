"""Ragged paged attention: ONE Pallas kernel for mixed prefill + decode.

Reference counterpart: the "Ragged Paged Attention" TPU serving kernel
(arXiv:2604.15464) that vLLM-lineage TPU backends use to serve a ragged
mix of prefill chunks and decode rows in a single invocation over the
paged KV pool. There is no per-regime split (a prefill kernel beside a
decode kernel, with the scheduler stalling decode around each admitted
prompt): every row of a step contributes ``q_len`` query tokens (1 for
decode rows, the chunk size for prefill chunks) and attends causally
against its own block-table slice of the shared pool. It is the
repo's only paged attention kernel; a decode-only step is this kernel
with ``q_len = 1`` rows.

Layout: packed queries ``q[T, H, D]`` segmented by ``cu_q_lens[R+1]``
(row r owns tokens ``cu[r]:cu[r+1]`` at absolute positions
``context_lens[r] - q_len_r + i`` — the chunk is already written to the
pool, write-then-attend order). The kernel tiles the ragged token axis
into fixed ``TQ=8``-token q tiles (a decode row is one mostly-padded
tile; a chunk of C tokens is ``ceil(C/8)`` tiles). The grid is ``(NT,)``,
one step a tile, with the tile metadata (owning row, absolute position
of the tile's first token, valid count, live block count) and the block
table scalar-prefetched. The pools stay in HBM; the kv axis is a loop
INSIDE the kernel whose trip count is the tile's live block count
``nblk = ceil((qpos0 + qcount) / BS)``: the blocks up to the causal
horizon of the tile's last token, read from ``context_lens``, whatever
the table's width. The loop is double-buffered: two VMEM slots per pool
operand and a DMA semaphore per slot; iteration ``j`` starts the copy of
block ``j + 1`` (``block_tables[row, j + 1]``, one ``[BS, KV, D]``
block) into the other slot, waits for its own, and attends the whole
tile against it, online-softmax state ``(m, l, acc)`` living in VMEM
scratch across the loop; the output tile is written once after it. So
both the arithmetic and the grid scale with
``sum_tiles(nblk) ~ sum(q_len_r * context_len_r) / (TQ * BS)``, not with
the padded ``NT x MB`` rectangle of tiles and table columns. A padding
tile (``nblk = 0``) costs its grid step: the q tile's pipelined copy in
and a tile of zeros out, no pool traffic.

``NT = R + ceil(T/TQ)`` (``num_tiles``) is a static upper bound on the
tile count (each row wastes at most one partial tile), so an engine with a fixed
token budget and row count reuses ONE compiled executable for every
step, whatever the prefill/decode mix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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


def num_tiles(rows: int, tokens: int) -> int:
    """``NT``: the static upper bound on the q tiles of ``tokens`` packed
    tokens over ``rows`` rows (each row wastes at most one partial tile)."""
    return rows + -(-tokens // TQ)


def _kernel(row_ref, qp0_ref, qc_ref, nblk_ref, tbl_ref, q_ref, *rest,
            bs, g, scale, quantized):
    n_pool = 4 if quantized else 2           # k, v (+ their scale tiles)
    pools, o_ref = rest[:n_pool], rest[n_pool]
    bufs = rest[n_pool + 1:2 * n_pool + 1]
    sem, m_scr, l_scr, acc_scr = rest[2 * n_pool + 1:]
    t = pl.program_id(0)
    row, qp0, qc, nblk = row_ref[t], qp0_ref[t], qc_ref[t], nblk_ref[t]

    def copies(j, slot):
        # block j of this tile's row: one DMA per pool operand, HBM -> the
        # slot's VMEM buffer, all riding the same block-table entry
        b = tbl_ref[row, j]
        return [pltpu.make_async_copy(pool.at[b], buf.at[slot],
                                      sem.at[i, slot])
                for i, (pool, buf) in enumerate(zip(pools, bufs))]

    m_scr[...] = jnp.full_like(m_scr, _NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(nblk > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def body(j, carry):
        slot = j % 2

        # the other slot was consumed by iteration j - 1: refill it while
        # this iteration waits for and works on its own
        @pl.when(j + 1 < nblk)
        def _():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        q = q_ref[0].astype(jnp.float32)                       # [KV, TG, D]
        kf = bufs[0][slot].astype(jnp.float32)                 # [BS, KV, D]
        vf = bufs[1][slot].astype(jnp.float32)
        k = jnp.swapaxes(kf, 0, 1)                             # [KV, BS, D]
        v = jnp.swapaxes(vf, 0, 1)
        if quantized:
            # int8 pool: dequant at the VMEM tile — the block arrived
            # from HBM at int8 bytes; one [KV, BS] scale tile rode the
            # same block-table index (weight_only_gemm playbook)
            k = k * bufs[2][slot, :, :bs][..., None]
            v = v * bufs[3][slot, :, :bs][..., None]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale        # [KV, TG, BS]
        kvpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        qlocal = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // g
        live = (kvpos <= qp0 + qlocal) & (qlocal < qc)
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
        return carry

    # the tile's LIVE blocks only: the causal horizon of its last token
    # bounds every kv position any of its tokens may see (nblk), and a
    # padding tile (nblk = 0) falls through to the zero write below
    jax.lax.fori_loop(0, nblk, body, 0)
    l = l_scr[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)   # fully-masked padding lanes
    o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def _tile_metadata(cu, ctx, nt, bs, mb):
    """Per tile of the ``nt``-tile grid: the row's first tile
    (``tile_cu[R+1]``), owning row, first packed token, valid token
    count, absolute position of the first token, and the live kv block
    count — blocks up to the causal horizon of the tile's last token,
    0 for a padding tile."""
    R = ctx.shape[0]
    qlen = cu[1:] - cu[:-1]                                    # [R]
    tile_cu = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum((qlen + TQ - 1) // TQ, dtype=jnp.int32)])  # [R+1]
    tiles = jnp.arange(nt, dtype=jnp.int32)
    row_of = jnp.clip(
        jnp.searchsorted(tile_cu, tiles, side="right").astype(jnp.int32) - 1,
        0, R - 1)
    local = tiles - tile_cu[row_of]                  # tile index within row
    tok0 = cu[row_of] + local * TQ
    qcount = jnp.clip(qlen[row_of] - local * TQ, 0, TQ)
    qpos0 = ctx[row_of] - qlen[row_of] + local * TQ
    nblk = jnp.where(qcount > 0,
                     jnp.minimum((qpos0 + qcount + bs - 1) // bs, mb), 0)
    return tile_cu, row_of, tok0, qcount, qpos0, nblk


def live_tile_blocks(q_lens, context_lens, block_size) -> int:
    """The (tile, kv block) pairs one call walks, counted on the host
    (numpy) from the step's own ``q_lens`` and ``context_lens``: the sum
    of ``_tile_metadata``'s ``nblk``. The engine puts it on its step span
    beside the ``NT x MB`` pairs of the whole table."""
    qlen = np.asarray(q_lens, np.int64)
    ctx = np.asarray(context_lens, np.int64)
    ntiles = (qlen + TQ - 1) // TQ
    row = np.repeat(np.arange(len(qlen)), ntiles)
    local = np.arange(len(row)) - np.repeat(np.cumsum(ntiles) - ntiles,
                                            ntiles)
    # one past the position of the tile's last token
    end = ctx[row] - qlen[row] + np.minimum(qlen[row], (local + 1) * TQ)
    return int(((end + block_size - 1) // block_size).sum())


def ragged_paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                           cu_q_lens, scale=None, k_scale=None,
                           v_scale=None):
    """q [T, H, D] packed over rows; pools [NB, BS, KV, D];
    block_tables [R, MB] int32; context_lens [R] visible tokens per row
    AFTER this step's write; cu_q_lens [R+1] ragged row segmentation of
    the packed token axis. Returns [T, H, D].

    k_scale/v_scale [NB, BS, KV] f32 (int8 pool): per-token-slot
    per-kv-head dequant scales riding the block table — each kv block's
    scale tile is DMA'd by the same table entry as the block itself and
    the dequant happens on the VMEM tile, so HBM reads stay at int8
    bytes."""
    T, H, D = q.shape
    NB, BS, KV, _ = k_pool.shape
    R, MB = block_tables.shape
    G = H // KV
    TG = TQ * G
    if scale is None:
        scale = D ** -0.5
    NT = num_tiles(R, T)

    cu = cu_q_lens.astype(jnp.int32)
    tile_cu, row_of, tok0, qcount, qpos0, nblk = _tile_metadata(
        cu, context_lens.astype(jnp.int32), NT, BS, MB)

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
    operands = [k_pool, v_pool]
    bufs = [pltpu.VMEM((2, BS, KV, D), k_pool.dtype),
            pltpu.VMEM((2, BS, KV, D), v_pool.dtype)]
    if quantized:
        # a DMA out of HBM cannot slice a minor axis narrower than the
        # 128 lanes, so the scales go in as [NB, KV, BS padded to 128]
        bsp = BS + -BS % 128
        operands += [
            jnp.pad(jnp.swapaxes(s.astype(jnp.float32), 1, 2),
                    ((0, 0), (0, 0), (0, bsp - BS)))
            for s in (k_scale, v_scale)]
        bufs += [pltpu.VMEM((2, KV, bsp), jnp.float32)] * 2
    tile_spec = pl.BlockSpec((1, KV, TG, D), lambda t, *_: (t, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(NT,),
        # the pools stay in HBM: the kernel DMAs the blocks it walks
        in_specs=[tile_spec] + [pl.BlockSpec(memory_space=pl.ANY)
                                for _ in operands],
        out_specs=tile_spec,
        scratch_shapes=bufs + [
            pltpu.SemaphoreType.DMA((len(operands), 2)),
            pltpu.VMEM((KV, TG, 1), jnp.float32),
            pltpu.VMEM((KV, TG, 1), jnp.float32),
            pltpu.VMEM((KV, TG, D), jnp.float32)],
    )
    out_dtype = q.dtype
    out = pl.pallas_call(
        functools.partial(_kernel, bs=BS, g=G, scale=float(scale),
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NT, KV, TG, D), out_dtype),
        name="ragged_paged_attention",
        interpret=_interpret(),
    )(row_of, qpos0, qcount, nblk,
      jnp.clip(block_tables.astype(jnp.int32), 0, NB - 1),
      q_tiles, *operands)

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
