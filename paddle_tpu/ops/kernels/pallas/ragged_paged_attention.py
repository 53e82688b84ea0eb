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

Layout: packed queries ``q[T, H*D]``, one row a token with its heads side
by side as ``q_proj`` leaves them, segmented by ``cu_q_lens[R+1]`` (row r
owns tokens ``cu[r]:cu[r+1]`` at absolute positions
``context_lens[r] - q_len_r + i`` — the chunk is already written to the
pool, write-then-attend order). The kernel tiles the ragged token axis
into fixed ``TQ=8``-token q tiles (a decode row is one mostly-padded
tile; a chunk of C tokens is ``ceil(C/8)`` tiles). The grid is ``(NT,)``,
one step a tile, with the tile metadata (owning row, first packed token,
absolute position of the tile's first token, valid count, live block
count) and the block table scalar-prefetched. The pools stay in HBM; the
kv axis is a loop INSIDE the kernel whose trip count is the tile's live
block count ``nblk = ceil((qpos0 + qcount) / BS)``: the blocks up to the
causal horizon of the tile's last token, read from ``context_lens``,
whatever the table's width.

The kernel does its own tiling (ISSUE 40): the packed rows and the packed
output are whole arrays in VMEM for the call (float32: 4 MB each at 256
tokens x 32 heads), and nothing around the call reorders an axis. Tile
``t`` copies its ``qcount`` valid rows ``tok0[t] + i`` into the
``[KV, TQ*G, D]`` operand of the body, head ``h``'s 128 lanes of token ``i``
to row ``(h % G) * TQ + i`` of kv head ``h // G`` (the ``G`` heads of a
group stacked along sublanes, group-major); after the tile's visits it
stores the same rows of the result back, the valid ones only. A row that
no tile owns (step padding, past ``cu[R]``) reads zeros: grid step 0
clears the output. A pack outside the kernel (a gather to ``NT * TQ`` rows
and a transposition to ``[NT, KV, TQ*G, D]``, and their inverse behind it)
cost six relayout copies and two gathers a call, and had XLA write
``q_proj``'s product tokens-minor and transpose the weight every step to
get there. The resident rows are XLA's allocation in VMEM beside the
kernel's own limit, so the step's tokens are bounded (``supported()``:
1536 at 32 heads); past that the composite serves.

The walk over a call's live (tile, block) pairs is ONE stream (ISSUE 38).
Number the pairs ``g = 0 .. P-1`` in the order the grid visits them, tile
by tile and block by block. Each pool operand has a ring of ``SLOTS`` VMEM
slots with a DMA semaphore a slot; visit ``g`` works in slot ``g % SLOTS``
and, before it waits for its own block, starts the copy of visit
``g + AHEAD`` (``block_tables[row, j]`` of that pair, one ``[BS, KV, D]``
block an operand), which may belong to the next tile or the one after: the
ring, its semaphores and the fetch cursor ``(tile, j)`` are scratch that
outlives a grid step, and the grid is sequential. Grid step 0 primes the
first ``AHEAD`` copies; every copy started is waited for exactly once, by
the visit that consumes it. So a visit costs its block's bytes, not a
copy's issue-to-landing time, and a decode row's first block is on its way
while the tiles before it are computed. ``SLOTS`` and ``AHEAD`` follow
from the bytes of one visit's slots (``_ring_depth``). A visit attends the
whole tile against its block, online-softmax state ``(m, l, acc)`` living
in VMEM scratch across the tile's visits; the output tile is written once
after them. So both the arithmetic and the copies scale with
``sum_tiles(nblk) ~ sum(q_len_r * context_len_r) / (TQ * BS)``, not with
the padded ``NT x MB`` rectangle of tiles and table columns.

A tile of ONE valid token (a decode row; a chunk's last tile when one token
is left for it) runs a body of its own: its packed row, reshaped,
is the ``[KV, Gp, D]`` operand, head ``h`` in row ``h % G`` of kv head
``h // G``, ``Gp = G`` rounded up to 8 sublanes, with ``(m, l, acc)`` in
scratch of that height; its mask is the token's causal horizon and rows
``< G``. At Jamba's 20 heads on one KV head that is 24 rows where the full
body computes 160, of which a decode row fills 20. At so few rows a visit is
its chain's latency (product, max, exp, product), so the one-token body
walks its blocks two a step: both waited for, both computed in one stretch,
the second's products overlapping the first's softmax; the second's fetch
ahead goes to the first's slot once it is computed. The values are the full
body's for the same row, bit for bit. Where ``Gp`` is no less than
``TQ * G`` (G = 1) only the full body is compiled (``_token_rows``); the
scalar-prefetched valid count picks the body a tile runs.

The live tiles are the first ``tile_cu[R]`` of the grid and the stream
ends with the last of them: a padding tile's grid step starts no copy,
loads no row, computes nothing and stores nothing.

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

from ....jax_compat import tpu_compiler_params
from .flash_attention import _interpret  # shared interpret override

_NEG = -1e30

TQ = 8  # query tokens per tile (f32 sublane)
_SUBLANES = 8  # float32 rows a vreg holds

# what the rings of one call may hold in VMEM; the kernel's VMEM limit, for
# the rings, the tile's operand, (m, l, acc) and the float32 copies of one
# visit's K and V (4 MiB at 32 KV heads); and what the packed rows and the
# packed output may hold there between them, XLA's allocation beside the
# kernel's limit: 8 MiB each at 512 tokens x 32 heads, growing with the
# step's tokens. In the step program XLA:TPU holds the output, the kernel's
# limit and 2.5 MiB more to 64 MiB (compile-only, Mistral-7B widths: 1536
# tokens compile, 1920 do not)
_RING_BYTES = 8 * 1024 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024
_ROWS_BYTES = 48 * 1024 * 1024


def supported(q_shape, pool_shape) -> bool:
    """Whether the Pallas path handles this case (else XLA composite): the
    heads in whole groups over the pool's, and a step whose packed rows and
    output fit in VMEM (1536 tokens at 32 heads)."""
    t, h, d = q_shape
    kv, pd = pool_shape[2], pool_shape[3]
    return (h % kv == 0 and d == pd
            and 2 * _vmem_bytes((t, h * d), jnp.float32) <= _ROWS_BYTES)


def num_tiles(rows: int, tokens: int) -> int:
    """``NT``: the static upper bound on the q tiles of ``tokens`` packed
    tokens over ``rows`` rows (each row wastes at most one partial tile)."""
    return rows + -(-tokens // TQ)


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of ``shape`` as VMEM lays it out: the last axis in 128 lanes,
    the one before in as many sublanes as a 32-bit row packs values."""
    item = jnp.dtype(dtype).itemsize
    *lead, sub, lane = shape
    pack = 8 * (4 // item)
    return (int(np.prod(lead)) * (sub + -sub % pack) * (lane + -lane % 128)
            * item)


def _ring_depth(visit_bytes: int):
    """``(SLOTS, AHEAD)`` of the ring from the VMEM bytes of one visit's
    slots (a K and a V block, and the int8 pool's two scale tiles): as many
    slots as ``_RING_BYTES`` hold, 3 to 8, and every slot but the one being
    computed on in flight. The copies are read-only, so a slot is free the
    moment its visit is over."""
    slots = max(3, min(8, _RING_BYTES // visit_bytes))
    return slots, slots - 1


def _token_rows(g) -> int:
    """``Gp``: the query rows of the one-token body, a tile's ``G`` heads a
    KV head rounded up to whole float32 sublanes; 0 where that is no fewer
    than the full body's ``TQ * G`` (G = 1), which then serves every tile."""
    gp = g + -g % _SUBLANES
    return gp if gp < TQ * g else 0


def _kernel(row_ref, tok0_ref, qp0_ref, qc_ref, nblk_ref, pair0_ref, live_ref,
            tbl_ref, q_ref, *rest, bs, g, scale, quantized, slots, ahead):
    n_pool = 4 if quantized else 2           # k, v (+ their scale tiles)
    pools, o_ref = rest[:n_pool], rest[n_pool]
    bufs = rest[n_pool + 1:2 * n_pool + 1]
    sem, cur, *scratch = rest[2 * n_pool + 1:]
    # (q, m, l, acc) of the full body, then of the one-token body if any
    full, token = scratch[:4], scratch[4:]
    t = pl.program_id(0)
    row, tok0, qp0 = row_ref[t], tok0_ref[t], qp0_ref[t]
    qc, nblk, pair0 = qc_ref[t], nblk_ref[t], pair0_ref[t]
    pairs = pair0_ref[pl.num_programs(0)]    # P: the call's live pairs
    kvh, d = full[0].shape[0], full[0].shape[2]
    heads = kvh * g

    def valid_tokens(visit):
        # visit(packed row, [head h's (kv head, row of the tile's operand)])
        # for each valid token of the tile: token i of head h is row
        # (h % G) * TQ + i of kv head h // G, the G heads of a group stacked
        # along sublanes, and columns h*D : (h+1)*D of packed row tok0 + i
        for i in range(TQ):
            @pl.when(i < qc)
            def _():
                visit(pl.ds(tok0 + i, 1),
                      [(h // g, pl.ds(h % g * TQ + i, 1))
                       for h in range(heads)])

    def copies(r, j, slot):
        # block j of row r: one DMA per pool operand, HBM -> the slot's
        # VMEM buffer, all riding the same block-table entry
        b = tbl_ref[r, j]
        return [pltpu.make_async_copy(pool.at[b], buf.at[slot],
                                      sem.at[i, slot])
                for i, (pool, buf) in enumerate(zip(pools, bufs))]

    def fetch(f):
        # start the copies of pair f, the one the cursor stands on, and
        # move the cursor to pair f + 1 (a live tile has a block or more)
        ft, fj = cur[0], cur[1]
        for c in copies(row_ref[ft], fj, f % slots):
            c.start()
        last = fj + 1 >= nblk_ref[ft]
        cur[0] = jnp.where(last, ft + 1, ft)
        cur[1] = jnp.where(last, 0, fj + 1)

    @pl.when(t == 0)
    def _():
        # a row no tile owns (step padding, past cu[R]) reads zeros
        o_ref[...] = jnp.zeros_like(o_ref)
        cur[0] = 0
        cur[1] = 0
        for f in range(ahead):
            @pl.when(f < pairs)
            def _():
                fetch(f)

    def fetch_ahead(f):
        # the slot of pair f + AHEAD held a pair before f, consumed by now:
        # fill it while visit f waits for and works on its own
        @pl.when(f + ahead < pairs)
        def _():
            fetch(f + ahead)

    def wait(j, slot):
        for c in copies(row, j, slot):
            c.wait()

    def attend(j, slot, scr, live_of, guard):
        # the tile's queries against block j, in its slot: online-softmax
        # state (m, l, acc) in VMEM across the tile's visits
        q_scr, m_scr, l_scr, acc_scr = scr
        q = q_scr[...]                                         # [KV, TG, D]
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
        live = live_of(kvpos, jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        s = jnp.where(live, s, _NEG)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        if guard:
            p = jnp.where(live, p, 0.0)   # exp(-1e30 - -1e30) = 1 guard
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                # [KV, TG, D]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    def full_live(kvpos, sub):
        qlocal = sub % TQ
        return (kvpos <= qp0 + qlocal) & (qlocal < qc)

    def full_visits():
        def visit(j, carry):
            f = pair0 + j
            slot = f % slots
            fetch_ahead(f)
            wait(j, slot)
            attend(j, slot, full, full_live, guard=True)
            return carry

        jax.lax.fori_loop(0, nblk, visit, 0)

    def token_live(kvpos, sub):
        # the one token at qp0 sees every position up to its own, and
        # position 0 is in the first block: a valid row's running max is
        # a score from its first visit on, so a masked exp is 0 without
        # the guard. Rows G.. of the operand are padding
        return (kvpos <= qp0) & (sub < g)

    def token_visits():
        # two visits a step: both blocks waited for, then both computed
        # in one stretch, so the second's products overlap the first's
        # softmax (at Gp rows a visit is its chain's latency). The second
        # visit's fetch ahead goes to the first's slot, free once it is
        # computed
        def two(i, carry):
            j = 2 * i
            f = pair0 + j
            slot, slot1 = f % slots, (f + 1) % slots
            fetch_ahead(f)
            wait(j, slot)
            wait(j + 1, slot1)
            attend(j, slot, token, token_live, guard=False)
            attend(j + 1, slot1, token, token_live, guard=False)
            fetch_ahead(f + 1)
            return carry

        jax.lax.fori_loop(0, nblk // 2, two, 0)

        @pl.when(nblk % 2 == 1)
        def _():
            f = pair0 + nblk - 1
            slot = f % slots
            fetch_ahead(f)
            wait(nblk - 1, slot)
            attend(nblk - 1, slot, token, token_live, guard=False)

    def tile(scr, load, visits, store):
        # a live tile: its LIVE blocks only, the causal horizon of its last
        # token bounds every kv position any of its tokens may see (nblk)
        q_scr, m_scr, l_scr, acc_scr = scr
        load(q_scr)
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        visits()
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)   # fully-masked padding lanes
        acc_scr[...] = acc_scr[...] / l_safe
        store(acc_scr)

    def full_load(q_scr):
        def load(tok, rows):
            q_tok = q_ref[tok, :]                              # [1, H*D]
            for h, (kv, r) in enumerate(rows):
                q_scr[kv, r, :] = q_tok[:, h * d:(h + 1) * d]

        # the tile's rows of the packed queries; a row past its valid count
        # keeps what an earlier tile left there, masked like any padding
        valid_tokens(load)

    def full_store(acc_scr):
        def store(tok, rows):
            o_ref[tok, :] = jnp.concatenate(
                [acc_scr[kv, r, :] for kv, r in rows], axis=1)

        # only the valid rows: the next tile's tokens are this one's
        # neighbours in the packed output
        valid_tokens(store)

    def token_load(q_scr):
        # head h to row h % G of kv head h // G: the packed row is the
        # [KV, G, D] operand, its padding rows untouched
        q_scr[:, :g, :] = q_ref[pl.ds(tok0, 1), :].reshape(kvh, g, d)

    def token_store(acc_scr):
        o_ref[pl.ds(tok0, 1), :] = acc_scr[:, :g, :].reshape(1, heads * d)

    # a padding tile (the tail of the grid) is past the stream's end
    live = t < live_ref[0]
    if token:
        # a tile of one valid token (a decode row, a chunk's last tile of
        # one) runs the body at Gp rows, not at TQ * G
        @pl.when(live & (qc == 1))
        def _():
            tile(token, token_load, token_visits, token_store)

        live = live & (qc != 1)

    @pl.when(live)
    def _():
        tile(full, full_load, full_visits, full_store)


def _tile_metadata(cu, ctx, nt, bs, mb):
    """Per tile of the ``nt``-tile grid: the row's first tile
    (``tile_cu[R+1]``; ``tile_cu[R]`` is the count of live tiles, which are
    the grid's first), owning row, first packed token, valid token count,
    absolute position of the first token, the live kv block count — blocks
    up to the causal horizon of the tile's last token, at least the one of
    its own first token, 0 for a padding tile — and the index of the tile's
    first (tile, block) pair in the order the grid walks them (``[nt + 1]``:
    the last is the call's pair count)."""
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
                     jnp.clip((qpos0 + qcount + bs - 1) // bs, 1, mb), 0)
    pair0 = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(nblk, dtype=jnp.int32)])
    return tile_cu, row_of, tok0, qcount, qpos0, nblk, pair0


def _tiles_of(q_lens):
    return (np.asarray(q_lens, np.int64) + TQ - 1) // TQ


def live_tiles(q_lens) -> int:
    """The q tiles one call computes, counted on the host as
    ``live_tile_blocks`` counts their blocks: ``_tile_metadata``'s
    ``tile_cu[R]``."""
    return int(_tiles_of(q_lens).sum())


def live_tile_blocks(q_lens, context_lens, block_size) -> int:
    """The (tile, kv block) pairs one call walks, counted on the host
    (numpy) from the step's own ``q_lens`` and ``context_lens``: the sum
    of ``_tile_metadata``'s ``nblk``. The engine puts it on its step span
    beside the ``NT x MB`` pairs of the whole table."""
    qlen = np.asarray(q_lens, np.int64)
    ctx = np.asarray(context_lens, np.int64)
    ntiles = _tiles_of(qlen)
    row = np.repeat(np.arange(len(qlen)), ntiles)
    local = np.arange(len(row)) - np.repeat(np.cumsum(ntiles) - ntiles,
                                            ntiles)
    # one past the position of the tile's last token
    end = ctx[row] - qlen[row] + np.minimum(qlen[row], (local + 1) * TQ)
    return int(((end + block_size - 1) // block_size).sum())


def live_token_blocks(q_lens, context_lens, block_size) -> int:
    """The pairs of ``live_tile_blocks`` whose tile holds one valid token (a
    decode row; a chunk's last tile when one token is left for it): the
    visits of the one-token body, where the kernel has one
    (``_token_rows``). Such a tile is its row's last, so its horizon is the
    row's context."""
    qlen = np.asarray(q_lens, np.int64)
    ctx = np.asarray(context_lens, np.int64)
    return int(((ctx[qlen % TQ == 1] + block_size - 1) // block_size).sum())


def ragged_paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                           cu_q_lens, scale=None, k_scale=None,
                           v_scale=None):
    """q [T, H*D] packed over rows, a token's heads side by side as
    ``q_proj`` leaves them (or its view [T, H, D]); pools [NB, BS, KV, D];
    block_tables [R, MB] int32; context_lens [R] visible tokens per row
    AFTER this step's write; cu_q_lens [R+1] ragged row segmentation of
    the packed token axis. Returns q's shape, zeros for the tokens past
    ``cu[R]``.

    k_scale/v_scale [NB, BS, KV] f32 (int8 pool): per-token-slot
    per-kv-head dequant scales riding the block table — each kv block's
    scale tile is DMA'd by the same table entry as the block itself and
    the dequant happens on the VMEM tile, so HBM reads stay at int8
    bytes."""
    NB, BS, KV, D = k_pool.shape
    R, MB = block_tables.shape
    T = q.shape[0]
    H = q.size // (T * D)
    G = H // KV
    TG = TQ * G
    if scale is None:
        scale = D ** -0.5
    NT = num_tiles(R, T)

    cu = cu_q_lens.astype(jnp.int32)
    tile_cu, row_of, tok0, qcount, qpos0, nblk, pair0 = _tile_metadata(
        cu, context_lens.astype(jnp.int32), NT, BS, MB)

    # float32: a row at a dynamic offset is one sublane of a 32-bit tile,
    # and Mosaic addresses no narrower one; the body computed in float32
    # all along
    rows = q.reshape(T, H * D).astype(jnp.float32)
    quantized = k_scale is not None
    operands = [k_pool, v_pool]
    blocks = [((BS, KV, D), k_pool.dtype), ((BS, KV, D), v_pool.dtype)]
    if quantized:
        # a DMA out of HBM cannot slice a minor axis narrower than the
        # 128 lanes, so the scales go in as [NB, KV, BS padded to 128]
        bsp = BS + -BS % 128
        operands += [
            jnp.pad(jnp.swapaxes(s.astype(jnp.float32), 1, 2),
                    ((0, 0), (0, 0), (0, bsp - BS)))
            for s in (k_scale, v_scale)]
        blocks += [((KV, bsp), jnp.float32)] * 2
    slots, ahead = _ring_depth(sum(_vmem_bytes(*b) for b in blocks))
    # the tile's queries and online-softmax state (m, l, acc): the full
    # body's [KV, TQ*G, .], and the one-token body's [KV, Gp, .] where G
    # heads a KV head leave it shorter
    gp = _token_rows(G)
    state = [pltpu.VMEM(shape, jnp.float32)
             for n in ((TG, gp) if gp else (TG,))
             for shape in ((KV, n, D), (KV, n, 1), (KV, n, 1), (KV, n, D))]
    # the packed rows, whole, in VMEM for the call: XLA's allocation, which
    # the fusion before the call writes and `o_proj`'s reads where they are
    rows_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(NT,),
        # the pools stay in HBM: the kernel DMAs the blocks it walks
        in_specs=[rows_spec] + [
            pl.BlockSpec(memory_space=pl.ANY) for _ in operands],
        out_specs=rows_spec,
        scratch_shapes=[pltpu.VMEM((slots,) + shape, dtype)
                        for shape, dtype in blocks] + [
            pltpu.SemaphoreType.DMA((len(operands), slots)),
            pltpu.SMEM((2,), jnp.int32),      # the fetch cursor (tile, j)
            *state],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=BS, g=G, scale=float(scale),
                          quantized=quantized, slots=slots, ahead=ahead),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H * D), jnp.float32),
        # the ring and its cursor carry from one grid step to the next
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ragged_paged_attention",
        interpret=_interpret(),
    )(row_of, tok0, qpos0, qcount, nblk, pair0, tile_cu[R:],
      jnp.clip(block_tables.astype(jnp.int32), 0, NB - 1),
      rows, *operands)
    return out.astype(q.dtype).reshape(q.shape)
