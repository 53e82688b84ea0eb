"""Ragged paged attention (ISSUE 8): ONE kernel invocation serving a
mixed bag of prefill chunks and decode rows over the paged KV pool.

Acceptance evidence: the Pallas tile kernel == the XLA per-token
composite == a sequential per-row reference built from batch-1 SDPA
(allclose + EXACT dtype) across decode-only, prefill-only, and mixed
ragged layouts incl. GQA and step padding; the TP-sharded run through
the shard_map wrapper (forced 8-device CPU mesh) matches the unsharded
reference; every fallback edge records its frozen
TP_FALLBACK_REASONS member and never errors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability import flight_recorder as fr
from paddle_tpu.ops.dispatcher import call_op
from paddle_tpu.ops.kernels.pallas import quant_common
from paddle_tpu.ops.kernels.pallas import ragged_paged_attention as rpa
from paddle_tpu.ops.kernels.pallas import tp_attention as tpa
from paddle_tpu.ops.kernels.serving import _ragged_composite

pytestmark = pytest.mark.smoke


@pytest.fixture(autouse=True)
def _fresh_topology():
    from paddle_tpu.distributed import topology
    prev = topology.get_hybrid_communicate_group()
    topology.set_hybrid_communicate_group(None)
    yield
    topology.set_hybrid_communicate_group(prev)


def _fallback_reasons(kind=None):
    """Frozen taxonomy keys of recorded fallbacks (the human-readable
    detail rides e[4][0]; the key is the ring entry's cache-key slot)."""
    ents = [e for e in fr.recorder().entries()
            if str(e[3]).startswith("tp_attention.fallback")]
    if kind is not None:
        ents = [e for e in ents if f"[{kind}]" in e[3]]
    return [e[5] for e in ents]


def _layout(rng, qlens, ctxs, T, bs=16, nb=32, mb=6, kv=2, h=4, d=32,
            dtype=jnp.float32):
    """Random pool + block tables realizing (qlens, ctxs); rows own
    disjoint blocks. Returns (q, k_pool, v_pool, tbl, ctx, cu)."""
    R = len(qlens)
    assert sum(qlens) <= T
    cu = np.concatenate([[0], np.cumsum(qlens)]).astype(np.int32)
    tbl = np.zeros((R, mb), np.int32)
    nxt = 1
    for r in range(R):
        for b in range(-(-ctxs[r] // bs)):
            tbl[r, b] = nxt
            nxt += 1
    assert nxt <= nb
    q = jnp.asarray(rng.randn(T, h, d), dtype)
    kp = jnp.asarray(rng.randn(nb, bs, kv, d), dtype)
    vp = jnp.asarray(rng.randn(nb, bs, kv, d), dtype)
    return (q, kp, vp, jnp.asarray(tbl),
            jnp.asarray(ctxs, jnp.int32), jnp.asarray(cu))


def _quantize_pools(kp, vp):
    """Per-token-slot per-kv-head symmetric int8, as paged_cache_write_q
    produces: scales [NB, BS, KV] f32 riding the block table."""
    from paddle_tpu.ops.kernels.pallas import quant_common
    ks = quant_common.absmax_scale(kp, axis=-1)
    vs = quant_common.absmax_scale(vp, axis=-1)
    kq = quant_common.quantize_symmetric(kp, ks[..., None])
    vq = quant_common.quantize_symmetric(vp, vs[..., None])
    return kq, vq, ks, vs


def _reference(q, kp, vp, tbl, ctx, cu, bs):
    """Sequential per-row reference: gather each row's blocks densely and
    run one masked SDPA per TOKEN (a decode row's math, token by token)."""
    q, kp, vp = (np.asarray(q, np.float32), np.asarray(kp, np.float32),
                 np.asarray(vp, np.float32))
    tbl, ctx, cu = np.asarray(tbl), np.asarray(ctx), np.asarray(cu)
    T, H, D = q.shape
    KV = kp.shape[2]
    G = H // KV
    out = np.zeros((T, H, D), np.float32)
    for r in range(len(ctx)):
        L = int(ctx[r])
        qlen = int(cu[r + 1] - cu[r])
        if qlen == 0:
            continue
        nblk = -(-L // bs)
        ks = np.concatenate([kp[tbl[r, b]] for b in range(nblk)])[:L]
        vs = np.concatenate([vp[tbl[r, b]] for b in range(nblk)])[:L]
        for i in range(qlen):
            p = L - qlen + i
            for hh in range(H):
                s = ks[:p + 1, hh // G] @ q[cu[r] + i, hh] * (D ** -0.5)
                w = np.exp(s - s.max())
                w /= w.sum()
                out[cu[r] + i, hh] = w @ vs[:p + 1, hh // G]
    return out


class TestRaggedKernel:
    def test_mixed_prefill_decode_matches_reference(self):
        rng = np.random.RandomState(0)
        qlens, ctxs, T = [1, 12, 10, 1], [20, 12, 37, 49], 32
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, T)
        ref = _reference(q, kp, vp, tbl, ctx, cu, bs=16)
        got = rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, cu)
        assert got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got)[:cu[-1]], ref[:cu[-1]],
                                   atol=2e-5, rtol=2e-5)

    def test_composite_matches_reference(self):
        rng = np.random.RandomState(1)
        qlens, ctxs, T = [8, 1, 1, 16], [8, 30, 1, 16], 32
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, T)
        ref = _reference(q, kp, vp, tbl, ctx, cu, bs=16)
        got = _ragged_composite(q, kp, vp, tbl, ctx, cu)
        assert got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got)[:cu[-1]], ref[:cu[-1]],
                                   atol=2e-5, rtol=2e-5)

    def test_decode_only_and_prefill_only(self):
        rng = np.random.RandomState(2)
        for qlens, ctxs in ([[1, 1, 1, 1], [5, 17, 33, 1]],
                            [[24, 8, 0, 0], [24, 8, 0, 0]]):
            q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 32)
            ref = _reference(q, kp, vp, tbl, ctx, cu, bs=16)
            got = rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, cu)
            np.testing.assert_allclose(np.asarray(got)[:cu[-1]],
                                       ref[:cu[-1]], atol=2e-5, rtol=2e-5)

    def test_gqa_group_mapping(self):
        rng = np.random.RandomState(3)
        qlens, ctxs = [1, 9], [40, 9]
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 16, kv=2, h=8)
        ref = _reference(q, kp, vp, tbl, ctx, cu, bs=16)
        got = rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, cu)
        np.testing.assert_allclose(np.asarray(got)[:cu[-1]], ref[:cu[-1]],
                                   atol=2e-5, rtol=2e-5)

    def test_bf16_exact_dtype(self):
        rng = np.random.RandomState(4)
        qlens, ctxs = [1, 10], [33, 10]
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 16,
                                          dtype=jnp.bfloat16)
        got = rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, cu)
        assert got.dtype == jnp.bfloat16
        ref = _reference(q, kp, vp, tbl, ctx, cu, bs=16)
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[:cu[-1]], ref[:cu[-1]],
            atol=5e-2, rtol=5e-2)

    def test_step_padding_tokens_zero(self):
        # tokens past cu[-1] are the engine's fixed-budget padding: they
        # must come back as zeros, never NaN (the engine discards them)
        rng = np.random.RandomState(5)
        qlens, ctxs, T = [1, 3, 0, 0], [9, 3, 0, 0], 24
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, T)
        got = np.asarray(rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, cu))
        assert np.isfinite(got).all()
        assert np.abs(got[cu[-1]:]).max() == 0.0
        comp = np.asarray(_ragged_composite(q, kp, vp, tbl, ctx, cu))
        assert np.isfinite(comp).all()

    def test_int8_pallas_equals_dequantized_pools_exactly(self):
        # dequant inside the VMEM tile load must be numerically
        # IDENTICAL to pre-dequantizing the pools and running the float
        # kernel — same values enter the same flash-attention math
        rng = np.random.RandomState(7)
        qlens, ctxs, T = [1, 12, 10, 1], [20, 12, 37, 49], 32
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, T)
        kq, vq, ks, vs = _quantize_pools(kp, vp)
        got = rpa.ragged_paged_attention(q, kq, vq, tbl, ctx, cu,
                                         k_scale=ks, v_scale=vs)
        kd = quant_common.dequantize_symmetric(kq, np.asarray(ks)[..., None])
        vd = quant_common.dequantize_symmetric(vq, np.asarray(vs)[..., None])
        want = rpa.ragged_paged_attention(q, kd, vd, tbl, ctx, cu)
        assert got.dtype == q.dtype
        np.testing.assert_array_equal(np.asarray(got)[:cu[-1]],
                                      np.asarray(want)[:cu[-1]])

    def test_int8_pallas_matches_composite_and_reference(self):
        rng = np.random.RandomState(8)
        qlens, ctxs, T = [8, 1, 1, 16], [8, 30, 1, 16], 32
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, T)
        kq, vq, ks, vs = _quantize_pools(kp, vp)
        got = np.asarray(rpa.ragged_paged_attention(
            q, kq, vq, tbl, ctx, cu, k_scale=ks, v_scale=vs))
        comp = np.asarray(_ragged_composite(
            q, kq, vq, tbl, ctx, cu, k_scale=ks, v_scale=vs))
        np.testing.assert_allclose(got[:cu[-1]], comp[:cu[-1]],
                                   atol=2e-5, rtol=2e-5)
        # and both sit inside the int8 quantization band of the float ref
        ref = _reference(q, kp, vp, tbl, ctx, cu, bs=16)
        np.testing.assert_allclose(got[:cu[-1]], ref[:cu[-1]],
                                   atol=5e-2, rtol=5e-2)

    def test_op_dispatch_routes_pallas_and_composite(self):
        rng = np.random.RandomState(6)
        qlens, ctxs = [1, 12], [17, 12]
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 16)
        args = [Tensor(x) for x in (q, kp, vp, tbl, ctx, cu)]
        prev = paddle.get_flags(["FLAGS_use_pallas_kernels"])[
            "FLAGS_use_pallas_kernels"]
        try:
            paddle.set_flags({"FLAGS_use_pallas_kernels": True})
            a = np.asarray(call_op("ragged_paged_attention", *args)._data)
            paddle.set_flags({"FLAGS_use_pallas_kernels": False})
            b = np.asarray(call_op("ragged_paged_attention", *args)._data)
        finally:
            paddle.set_flags({"FLAGS_use_pallas_kernels": prev})
        np.testing.assert_allclose(a[:cu[-1]], b[:cu[-1]],
                                   atol=2e-5, rtol=2e-5)


def _garbage_in_dead_columns(rng, tbl, ctx, bs, widen):
    """``tbl`` made ``widen`` times wider, every column past a row's live
    blocks out-of-range garbage."""
    R, mb = tbl.shape
    live = np.arange(mb)[None, :] < -(-np.asarray(ctx)[:, None] // bs)
    junk = rng.randint(-2 ** 30, 2 ** 30, (R, widen * mb)).astype(np.int32)
    wide = junk.copy()
    wide[:, :mb] = np.where(live, np.asarray(tbl), junk[:, :mb])
    return jnp.asarray(wide)


# (q_lens, context_lens) at block_size 16: what the in-kernel loop over a
# tile's live blocks must get right at its edges
_LOOP_CASES = {
    # contexts of exactly k x BS and k x BS + 1, decode rows and chunks
    "block_multiples": ([1, 1, 16, 17, 1], [32, 33, 16, 17, 16]),
    # one token of context: the row's own first token, one block, one lane
    "one_token_of_context": ([1, 1, 1], [1, 40, 1]),
    # empty rows between live ones, and a row with context but no query
    # (a prefill row the budget gave nothing this step)
    "empty_rows_between": ([1, 0, 0, 9, 0, 1], [20, 0, 7, 9, 0, 33]),
    # speculative verify rows: q_len = K + 1 = 3 inside one tile
    "verify_row": ([3, 1, 3, 3], [35, 16, 3, 48]),
    # chunks of 9 and 17 tokens: a last tile of one token, the one-token
    # body's like a decode row's, beside the full body's tiles
    "chunk_last_tile_one_token": ([9, 1, 17], [9, 40, 33]),
}


class TestLiveBlockLoop:
    """ISSUE 27: the kv axis is a loop over each tile's live blocks; the
    table's width and its dead columns are never read."""

    @pytest.mark.parametrize("pool", ["f32", "bf16", "int8"])
    @pytest.mark.parametrize("case", sorted(_LOOP_CASES))
    def test_matches_reference_whatever_the_tables_width(self, case, pool):
        qlens, ctxs = _LOOP_CASES[case]
        rng = np.random.RandomState(sorted(_LOOP_CASES).index(case))
        dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 48, dtype=dtype)
        ref = _reference(q, kp, vp, tbl, ctx, cu, bs=16)
        scales = {}
        if pool == "int8":
            kp, vp, ks, vs = _quantize_pools(kp, vp)
            scales = dict(k_scale=ks, v_scale=vs)
        got = rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, cu, **scales)
        assert got.dtype == q.dtype
        tol = 2e-5 if pool == "f32" else 5e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[:cu[-1]], ref[:cu[-1]],
            atol=tol, rtol=tol)
        # a table 32 times wider, its dead columns out-of-range garbage
        wide = _garbage_in_dead_columns(rng, np.asarray(tbl), ctx, 16,
                                        widen=32)
        got_wide = rpa.ragged_paged_attention(
            q, kp, vp, wide, ctx, cu, **scales)
        np.testing.assert_array_equal(np.asarray(got_wide, np.float32),
                                      np.asarray(got, np.float32))

    @pytest.mark.parametrize("case", sorted(_LOOP_CASES))
    def test_host_count_is_the_kernels_trip_count(self, case):
        # the engine's span attribute (numpy) against the scalar-prefetched
        # per-tile trip counts the kernel loops over
        qlens, ctxs = _LOOP_CASES[case]
        cu = np.concatenate([[0], np.cumsum(qlens)]).astype(np.int32)
        nt = rpa.num_tiles(len(qlens), 48)
        for bs in (4, 16, 64):
            *_, qcount, _, nblk, pair0 = rpa._tile_metadata(
                jnp.asarray(cu), jnp.asarray(ctxs, jnp.int32), nt, bs, 512)
            assert int((np.asarray(qcount) > 0).sum()) == sum(
                -(-n // rpa.TQ) for n in qlens)
            assert rpa.live_tile_blocks(qlens, ctxs, bs) == int(
                np.asarray(nblk).sum())

    @pytest.mark.parametrize("case", sorted(_LOOP_CASES))
    def test_host_token_count_is_the_one_token_tiles_trip_count(self, case):
        # the engine's kv_token_blocks (numpy) against the trip
        # counts of the tiles whose valid count is 1, the one-token body's
        qlens, ctxs = _LOOP_CASES[case]
        cu = np.concatenate([[0], np.cumsum(qlens)]).astype(np.int32)
        nt = rpa.num_tiles(len(qlens), 48)
        for bs in (4, 16, 64):
            *_, qcount, _, nblk, _ = rpa._tile_metadata(
                jnp.asarray(cu), jnp.asarray(ctxs, jnp.int32), nt, bs, 512)
            one = np.asarray(qcount) == 1
            assert rpa.live_token_blocks(qlens, ctxs, bs) == int(
                np.asarray(nblk)[one].sum())
            assert int(one.sum()) == sum(n % rpa.TQ == 1 for n in qlens)


# name -> (q_lens, context_lens, packed tokens, `_layout` keywords): what
# the stream over a call's live (tile, block) pairs must get right where
# it crosses from tile to tile, starts and ends
_STREAM_CASES = {
    # every row a single block: the ring runs wholly across tiles
    "single_block_rows": ([1] * 9, [3, 16, 9, 1, 12, 5, 16, 2, 7], 16, {}),
    # rows of 2 and 3 blocks where 7 copies are in flight: a visit's fetch
    # lands two and three tiles on
    "fewer_blocks_than_ahead": ([1, 1, 1, 1], [20, 40, 33, 17], 8, {}),
    # 11 pairs through 8 slots
    "pairs_not_a_multiple_of_slots": ([1, 1, 1], [80, 70, 16], 8, {}),
    "one_live_tile": ([0, 1, 0], [0, 50, 0], 8, {}),
    # every row empty: zeros come back and no copy is started
    "no_live_tile": ([0, 0, 0, 0], [0, 0, 0, 0], 16, {}),
    "empty_rows_between": ([1, 0, 0, 9, 0, 1], [60, 0, 7, 25, 0, 33], 24,
                           {"mb": 4}),
    # a chunk of 256 tokens at context 3,072 beside decode rows, as
    # serve-chat-steady packs them: 32 tiles of 45 to 48 blocks of 64
    "chunk_beside_decode_rows": (
        [1, 256, 1, 1], [700, 3072, 64, 130], 264,
        {"bs": 64, "mb": 48, "nb": 80}),
    # decode rows whose context the table cannot hold: the walk stops at
    # its last column, as if the context ended there
    "nblk_capped_at_the_tables_width": ([1, 1, 1, 9], [40, 20, 60, 30], 16,
                                        {"mb": 2, "capped": True}),
    # Jamba's attention layers: 20 query heads on 1 KV head
    "mqa_20_on_1": ([1, 9, 1, 0, 1], [40, 9, 70, 0, 16], 16,
                    {"h": 20, "kv": 1, "mb": 5}),
    # decode rows of 1 to 6 blocks, odd and even, and chunks whose last
    # tile holds one token: the one-token body walks its blocks two a step
    "chunk_last_tile_one_token": ([1, 9, 1, 17, 1], [40, 9, 96, 33, 17], 32,
                                  {}),
    # one query head a KV head: the full body is already one token's rows
    "one_head_a_kv_head": ([1, 9, 1], [40, 9, 70], 16, {"h": 2, "kv": 2}),
}


def _stream_layout(case, dtype=jnp.float32):
    qlens, ctxs, tokens, kw = _STREAM_CASES[case]
    kw = dict(kw)
    capped = kw.pop("capped", False)
    bs, mb = kw.get("bs", 16), kw.get("mb", 6)
    rng = np.random.RandomState(sorted(_STREAM_CASES).index(case))
    # a capped row is laid out, and judged, at the context its table holds
    held = [min(c, mb * bs) for c in ctxs]
    q, kp, vp, tbl, _, cu = _layout(rng, qlens, held, tokens, dtype=dtype,
                                    **kw)
    return rng, bs, capped, (q, kp, vp, tbl, jnp.asarray(ctxs, jnp.int32),
                             cu), jnp.asarray(held, jnp.int32)


class TestStream:
    """ISSUE 38: a call's live (tile, block) pairs are one stream through a
    ring of VMEM slots, fetched ahead across blocks and across tiles."""

    @pytest.mark.parametrize("pool", ["f32", "bf16", "int8"])
    @pytest.mark.parametrize("case", sorted(_STREAM_CASES))
    def test_matches_reference_and_ignores_dead_columns(self, case, pool):
        dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
        rng, bs, capped, args, held = _stream_layout(case, dtype)
        q, kp, vp, tbl, ctx, cu = args
        ref = _reference(q, kp, vp, tbl, held, cu, bs=bs)
        scales = {}
        if pool == "int8":
            kp, vp, ks, vs = _quantize_pools(kp, vp)
            scales = dict(k_scale=ks, v_scale=vs)
        got = rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, cu, **scales)
        assert got.dtype == q.dtype
        got = np.asarray(got, np.float32)
        tol = 2e-5 if pool == "f32" else 5e-2
        np.testing.assert_allclose(got[:cu[-1]], ref[:cu[-1]],
                                   atol=tol, rtol=tol)
        assert np.abs(got[cu[-1]:]).max(initial=0.0) == 0.0
        wide = _garbage_in_dead_columns(rng, np.asarray(tbl), held, bs,
                                        widen=1 if capped else 8)
        got_wide = rpa.ragged_paged_attention(q, kp, vp, wide, ctx, cu,
                                              **scales)
        np.testing.assert_array_equal(np.asarray(got_wide, np.float32), got)

    @pytest.mark.parametrize("depth", [(3, 2), (8, 4), (8, 7)],
                             ids=["3-2", "8-4", "8-7"])
    @pytest.mark.parametrize("case", sorted(_STREAM_CASES))
    def test_walks_schedule(self, case, depth):
        # a model of the kernel's walk on the host, step for step (grid
        # step, prime, fetch ahead, wait, compute; the cursor's move), with
        # what interpret mode cannot assert: its copies complete at once
        slots, ahead = depth
        qlens, ctxs, tokens, kw = _STREAM_CASES[case]
        bs, mb = kw.get("bs", 16), kw.get("mb", 6)
        token_body = rpa._token_rows(kw.get("h", 4) // kw.get("kv", 2)) > 0
        cu = np.concatenate([[0], np.cumsum(qlens)]).astype(np.int32)
        nt = rpa.num_tiles(len(qlens), tokens)
        tile_cu, row_of, _, qcount, _, nblk, pair0 = (
            np.asarray(a) for a in rpa._tile_metadata(
                jnp.asarray(cu), jnp.asarray(ctxs, jnp.int32), nt, bs, mb))
        n_live, pairs = int(tile_cu[-1]), int(pair0[nt])
        assert n_live == rpa.live_tiles(qlens)
        assert (nblk[:n_live] >= 1).all() and (nblk[n_live:] == 0).all()
        if not kw.get("capped"):
            assert pairs == rpa.live_tile_blocks(qlens, ctxs, bs)

        cursor = [0, 0]
        holds = {}              # slot -> (pair, landed and consumed?)
        started, waited, in_flight = [], [], 0

        def fetch(f):
            nonlocal in_flight
            slot, pair = f % slots, tuple(cursor)
            # the slot's last pair has been consumed before it is refilled
            assert holds.get(slot, (None, True))[1], (f, slot)
            holds[slot] = (pair, False)
            started.append((f, pair))
            in_flight += 1
            # the visit's own copy and AHEAD beyond it, a slot each
            assert in_flight <= ahead + 1 <= slots
            last = cursor[1] + 1 >= nblk[cursor[0]]
            cursor[:] = [cursor[0] + 1, 0] if last else [cursor[0],
                                                         cursor[1] + 1]

        for t in range(nt):
            if t == 0:
                for f in range(ahead):
                    if f < pairs:
                        fetch(f)
                assert len(started) == min(ahead, pairs)
            if t >= n_live:
                continue        # a padding tile: no copy, no wait
            # a step of the tile's loop: one visit, or two for a tile of
            # one token (both waited for, then both computed, then the
            # second's fetch ahead, into the first's slot)
            step = 2 if token_body and qcount[t] == 1 else 1
            for j0 in range(0, nblk[t], step):
                js = range(j0, min(j0 + step, nblk[t]))
                f0 = int(pair0[t]) + j0
                if f0 + ahead < pairs:
                    fetch(f0 + ahead)
                for j in js:
                    f = int(pair0[t]) + j
                    # the visit waits for the copy the cursor started for it
                    assert holds[f % slots] == ((t, j), False)
                    waited.append((f, (t, j)))
                    in_flight -= 1
                    assert in_flight <= ahead
                for j in js:
                    holds[(int(pair0[t]) + j) % slots] = ((t, j), True)
                for j in js[1:]:
                    f = int(pair0[t]) + j
                    if f + ahead < pairs:
                        fetch(f + ahead)
        # every copy started is waited for exactly once, by its own visit,
        # and each live pair was fetched, in the grid's order
        assert started == waited and in_flight == 0
        assert [f for f, _ in started] == list(range(pairs))
        assert [p for _, p in started] == [
            (t, j) for t in range(n_live) for j in range(nblk[t])]

    def test_depth_follows_the_visits_bytes(self):
        # one rule for every pool: 8 KV heads in bf16, Jamba's one float32
        # head, 32 heads (a visit of 1 MiB), the int8 pool with its scales
        def depth(kv, dtype, scales=False):
            blocks = [((64, kv, 128), dtype)] * 2
            blocks += [((kv, 128), jnp.float32)] * (2 if scales else 0)
            return rpa._ring_depth(sum(rpa._vmem_bytes(*b) for b in blocks))

        assert rpa._vmem_bytes((64, 8, 128), jnp.bfloat16) == 64 * 16 * 256
        assert rpa._vmem_bytes((64, 1, 128), jnp.float32) == 64 * 8 * 512
        for kv, dtype, scales in ((8, jnp.bfloat16, False),
                                  (1, jnp.float32, False),
                                  (32, jnp.bfloat16, False),
                                  (8, jnp.int8, True),
                                  (32, jnp.int8, True)):
            slots, ahead = depth(kv, dtype, scales)
            assert 3 <= slots <= 8 and 1 <= ahead < slots
        assert depth(8, jnp.bfloat16) == (8, 7)
        # slots too large for the budget still leave a ring of three
        assert rpa._ring_depth(rpa._RING_BYTES) == (3, 2)
        assert rpa._ring_depth(rpa._RING_BYTES // 5) == (5, 4)


# name -> (q_lens, context_lens): what the kernel's own tiling of the packed
# rows must get right. Six rows over 21 packed tokens, not a multiple of TQ,
# in every case: one compiled kernel a pool serves them all
_ROWS_TOKENS = 21
_ROWS_CASES = {
    "decode_only": ([1] * 6, [20, 1, 33, 16, 17, 40]),
    # 13 tokens: a full tile and one of 5 valid rows, whose other 3 rows
    # are the next rows' tokens in the packed array
    "chunk_ends_mid_tile": ([1, 13, 1, 1, 0, 0], [30, 13, 9, 21, 0, 0]),
    "row_with_no_token": ([1, 0, 5, 0, 1, 0], [12, 0, 25, 7, 3, 0]),
    # the last tile starts at token 18 of 21: its rows run past the array
    "last_tile_overhangs_the_array": ([1, 11, 1, 5, 3, 0],
                                      [18, 27, 5, 5, 44, 0]),
    "step_padding": ([1, 3, 1, 0, 0, 0], [9, 19, 35, 0, 0, 0]),
    # a 17-token chunk at context 40: tiles of 8, 8 and 1 token, the last
    # one's row read and written by the one-token body
    "chunk_last_tile_one_token": ([1, 17, 1, 0, 1, 1],
                                  [12, 40, 9, 0, 33, 16]),
}
# the cells' heads: Mistral's 32 on 8 over a bf16 and an int8 pool, Jamba's
# 20 on 1 over a float32 pool; bf16 queries, head_dim 128. And G = 1 (no
# one-token body), G = 4 on one KV head, G = 20 over an int8 pool
_ROWS_POOLS = {"32on8-bf16": (32, 8, jnp.bfloat16),
               "32on8-int8": (32, 8, jnp.int8),
               "20on1-f32": (20, 1, jnp.float32),
               "8on8-bf16": (8, 8, jnp.bfloat16),
               "4on1-f32": (4, 1, jnp.float32),
               "20on1-int8": (20, 1, jnp.int8)}
_rows_kernel = jax.jit(rpa.ragged_paged_attention)
_rows_composite = jax.jit(_ragged_composite)


def _rows_layout(case, pool):
    qlens, ctxs = _ROWS_CASES[case]
    heads, kv, pool_dtype = _ROWS_POOLS[pool]
    rng = np.random.RandomState(sorted(_ROWS_CASES).index(case))
    q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, _ROWS_TOKENS, kv=kv,
                                      h=heads, d=128, mb=3)
    q = q.astype(jnp.bfloat16)
    scales = {}
    if pool_dtype == jnp.int8:
        kp, vp, ks, vs = _quantize_pools(kp, vp)
        scales = dict(k_scale=ks, v_scale=vs)
        ref_pools = (kp.astype(jnp.float32) * ks[..., None],
                     vp.astype(jnp.float32) * vs[..., None])
    else:
        kp, vp = kp.astype(pool_dtype), vp.astype(pool_dtype)
        ref_pools = (kp, vp)
    return (q, kp, vp, tbl, ctx, cu), scales, ref_pools


class TestRows:
    """ISSUE 40: the kernel reads the packed [T, H*D] rows and writes its
    valid rows itself; no pack, no unpack, no transposition around it."""

    @pytest.mark.parametrize("pool", sorted(_ROWS_POOLS))
    @pytest.mark.parametrize("case", sorted(_ROWS_CASES))
    def test_matches_reference_and_composite(self, case, pool):
        (q, kp, vp, tbl, ctx, cu), scales, ref_pools = _rows_layout(case,
                                                                    pool)
        ref = _reference(q, *ref_pools, tbl, ctx, cu, bs=16)
        got = _rows_kernel(q, kp, vp, tbl, ctx, cu, **scales)
        assert got.dtype == q.dtype and got.shape == q.shape
        composite = _rows_composite(q, kp, vp, tbl, ctx, cu, **scales)
        valid = int(cu[-1])
        for other in (ref, np.asarray(composite, np.float32)):
            np.testing.assert_allclose(np.asarray(got, np.float32)[:valid],
                                       other[:valid], atol=5e-2, rtol=5e-2)
        # the rows as `q_proj` leaves them are the same call
        rows = _rows_kernel(q.reshape(q.shape[0], -1), kp, vp, tbl, ctx, cu,
                            **scales)
        assert rows.shape == (q.shape[0], q.shape[1] * q.shape[2])
        np.testing.assert_array_equal(
            np.asarray(rows, np.float32).reshape(q.shape),
            np.asarray(got, np.float32))

    @pytest.mark.parametrize("case", sorted(_ROWS_CASES))
    def test_float32_matches_reference_closely(self, case):
        qlens, ctxs = _ROWS_CASES[case]
        rng = np.random.RandomState(7)
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, _ROWS_TOKENS,
                                          mb=3)
        ref = _reference(q, kp, vp, tbl, ctx, cu, bs=16)
        got = np.asarray(_rows_kernel(q, kp, vp, tbl, ctx, cu))
        np.testing.assert_allclose(got[:cu[-1]], ref[:cu[-1]], atol=2e-5,
                                   rtol=2e-5)

    @pytest.mark.parametrize("case", sorted(_ROWS_CASES))
    def test_a_tile_stores_its_valid_rows_only(self, case):
        # the tokens past cu[R] belong to no tile and read zeros, whatever
        # q holds there and however the last live tile overhangs them; and
        # a row's tokens come out the same to the bit whether or not the
        # rows around it are in the step, partly valid tiles and all
        qlens, ctxs = _ROWS_CASES[case]
        rng = np.random.RandomState(11)
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, _ROWS_TOKENS,
                                          mb=3, dtype=jnp.bfloat16)
        got = np.asarray(_rows_kernel(q, kp, vp, tbl, ctx, cu), np.float32)
        assert np.abs(got[int(cu[-1]):]).max(initial=0.0) == 0.0
        qlens = np.diff(np.asarray(cu))
        for r in np.flatnonzero(qlens):
            alone = np.where(np.arange(len(qlens)) == r, qlens, 0)
            cu_r = np.concatenate([[0], np.cumsum(alone)]).astype(np.int32)
            lo, n = int(cu[r]), int(qlens[r])
            # row r's tokens moved to the front of an otherwise empty step
            q_r = jnp.concatenate([q[lo:lo + n], q[:q.shape[0] - n]])
            out = np.asarray(_rows_kernel(
                q_r, kp, vp, tbl, ctx, jnp.asarray(cu_r)), np.float32)
            np.testing.assert_array_equal(out[:n], got[lo:lo + n])
            assert np.abs(out[n:]).max(initial=0.0) == 0.0

    # the packed rows and the packed output are whole in VMEM, XLA's
    # allocation beside the kernel's own limit, so the step's tokens are
    # bounded: 48 MiB of rows (tests/test_chip_compile.py compiles the
    # bound)
    @pytest.mark.parametrize("tokens, heads, kv, fits", [
        (512, 32, 8, True),        # the Mistral cells' budget
        (512, 32, 32, True),       # chip_smoke
        (512, 20, 1, True),        # the Jamba cell's budget
        (1024, 32, 8, True),
        (1536, 32, 8, True),
        (1537, 32, 8, False),
        (2048, 32, 8, False),
        (2048, 20, 1, True),
        (2464, 20, 1, False),
    ])
    def test_supported_holds_the_rows_to_vmem(self, tokens, heads, kv, fits):
        assert rpa.supported((tokens, heads, 128),
                             (4096, 64, kv, 128)) == fits

    def test_a_step_over_the_vmem_takes_the_composite(self, monkeypatch):
        # a token budget whose rows do not fit is served like any other
        # unsupported shape, and does not fail in Mosaic at warm-up
        from paddle_tpu import flags
        rng = np.random.RandomState(13)
        qlens, ctxs = _ROWS_CASES["chunk_ends_mid_tile"]
        args = _layout(rng, qlens, ctxs, 19, mb=3)
        ref = _reference(*args, bs=16)
        monkeypatch.setattr(rpa, "_ROWS_BYTES", 16 * 1024)
        assert not rpa.supported(args[0].shape, args[1].shape)
        monkeypatch.setattr(
            rpa, "ragged_paged_attention",
            lambda *a, **k: pytest.fail("the kernel was asked"))
        rows = args[0].reshape(19, -1)
        prev = flags.get_flag("use_pallas_kernels")
        flags.set_flags({"use_pallas_kernels": True})
        try:
            got = call_op("ragged_paged_attention", Tensor(rows),
                          *map(Tensor, args[1:])).numpy()
        finally:
            flags.set_flags({"use_pallas_kernels": prev})
        assert got.shape == rows.shape
        valid = int(args[5][-1])
        np.testing.assert_allclose(got.reshape(args[0].shape)[:valid],
                                   ref[:valid], atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_rope_rows_is_rope_on_the_rows(self, dtype):
        from paddle_tpu.ops.kernels.nn import rope, rope_rows
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(11, 4, 32), dtype)
        angle = rng.uniform(0, 6.28, (64, 16)).astype(np.float32)
        angle = np.concatenate([angle, angle], axis=1)
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        pos = jnp.asarray(rng.randint(0, 64, 11), jnp.int32)
        want = rope(x[None], cos=cos, sin=sin, position_ids=pos[None])[0]
        got = rope_rows(x.reshape(11, -1), cos[pos], sin[pos]).reshape(x.shape)
        assert got.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs the forced 8-device CPU mesh")
class TestShardedRagged:
    def test_matches_unsharded_reference(self):
        rng = np.random.RandomState(7)
        mesh = jax.make_mesh((4,), ("mp",))
        # decode rows and chunks at 2 heads on 1 KV head a shard; chunks
        # whose last tile holds one token; Jamba's 20 on 1 in each shard
        for qlens, ctxs, h in (([1, 12, 10, 1], [20, 12, 37, 49], 8),
                               ([1, 9, 17, 1], [20, 9, 40, 49], 8),
                               ([1, 9, 1, 1], [20, 9, 37, 49], 80)):
            q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 32, kv=4,
                                              h=h)
            out = tpa.sharded_ragged_paged_attention(q, kp, vp, tbl, ctx,
                                                     cu, mesh, "mp")
            assert out is not None
            ref = rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, cu)
            assert out.dtype == ref.dtype
            np.testing.assert_allclose(np.asarray(out)[:cu[-1]],
                                       np.asarray(ref)[:cu[-1]],
                                       atol=2e-5, rtol=2e-5)
            np.testing.assert_allclose(
                np.asarray(out)[:cu[-1]],
                _reference(q, kp, vp, tbl, ctx, cu, bs=16)[:cu[-1]],
                atol=2e-5, rtol=2e-5)
            # heads really ride the mp axis
            assert out.sharding.spec[1] == "mp"

    def test_int8_sharded_matches_unsharded_quantized(self):
        # scale tiles shard with the pool's kv-head axis: the sharded
        # quantized build must agree with the unsharded quantized kernel
        rng = np.random.RandomState(12)
        qlens, ctxs = [1, 12, 10, 1], [20, 12, 37, 49]
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 32, kv=4, h=8)
        kq, vq, ks, vs = _quantize_pools(kp, vp)
        mesh = jax.make_mesh((4,), ("mp",))
        out = tpa.sharded_ragged_paged_attention(
            q, kq, vq, tbl, ctx, cu, mesh, "mp", k_scale=ks, v_scale=vs)
        assert out is not None
        ref = rpa.ragged_paged_attention(q, kq, vq, tbl, ctx, cu,
                                         k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(out)[:cu[-1]],
                                   np.asarray(ref)[:cu[-1]],
                                   atol=2e-5, rtol=2e-5)
        assert out.sharding.spec[1] == "mp"

    def test_op_dispatch_under_tp_context(self):
        rng = np.random.RandomState(8)
        qlens, ctxs = [1, 12], [17, 12]
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 16, kv=4, h=8)
        args = [Tensor(x) for x in (q, kp, vp, tbl, ctx, cu)]
        ref = np.asarray(call_op("ragged_paged_attention", *args)._data)
        mesh = jax.make_mesh((4,), ("mp",))
        with tpa.tp_shard_context(mesh, "mp"):
            out = np.asarray(call_op("ragged_paged_attention",
                                     *args)._data)
        np.testing.assert_allclose(out[:cu[-1]], ref[:cu[-1]],
                                   atol=2e-5, rtol=2e-5)

    def test_heads_indivisible_falls_back_with_reason(self):
        rng = np.random.RandomState(9)
        qlens, ctxs = [1, 4], [9, 4]
        # h=6 not divisible by tp=4
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 8, kv=2, h=6)
        mesh = jax.make_mesh((4,), ("mp",))
        out = tpa.sharded_ragged_paged_attention(q, kp, vp, tbl, ctx, cu,
                                                 mesh, "mp")
        assert out is None
        assert _fallback_reasons("ragged")[-1] == "heads_indivisible"

    def test_kv_heads_indivisible_falls_back_with_reason(self):
        rng = np.random.RandomState(10)
        qlens, ctxs = [1, 4], [9, 4]
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 8, kv=2, h=8)
        mesh = jax.make_mesh((4,), ("mp",))
        out = tpa.sharded_ragged_paged_attention(q, kp, vp, tbl, ctx, cu,
                                                 mesh, "mp")
        assert out is None
        assert _fallback_reasons("ragged")[-1] == "kv_heads_indivisible"

    def test_flags_off_records_reason_under_context(self):
        rng = np.random.RandomState(11)
        qlens, ctxs = [1, 4], [9, 4]
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 8, kv=4, h=8)
        args = [Tensor(x) for x in (q, kp, vp, tbl, ctx, cu)]
        mesh = jax.make_mesh((4,), ("mp",))
        prev = paddle.get_flags(["FLAGS_use_pallas_kernels"])[
            "FLAGS_use_pallas_kernels"]
        try:
            paddle.set_flags({"FLAGS_use_pallas_kernels": False})
            with tpa.tp_shard_context(mesh, "mp"):
                out = call_op("ragged_paged_attention", *args)
        finally:
            paddle.set_flags({"FLAGS_use_pallas_kernels": prev})
        assert tuple(out.shape) == (8, 8, 32)
        assert _fallback_reasons("ragged")[-1] == "flags_off"

    def test_rows_over_dp_records_partial_reason(self):
        # the packed token axis is ragged: asking for rows over dp keeps
        # the head-sharded fast path but records the frozen reason
        rng = np.random.RandomState(12)
        qlens, ctxs = [1, 12], [17, 12]
        q, kp, vp, tbl, ctx, cu = _layout(rng, qlens, ctxs, 16, kv=4, h=8)
        mesh = jax.make_mesh((2, 4), ("dp", "mp"))
        out = tpa.sharded_ragged_paged_attention(
            q, kp, vp, tbl, ctx, cu, mesh, "mp", batch_axis="dp")
        assert out is not None
        assert _fallback_reasons("ragged")[-1] == "ragged_rows_replicated"
        ref = rpa.ragged_paged_attention(q, kp, vp, tbl, ctx, cu)
        np.testing.assert_allclose(np.asarray(out)[:cu[-1]],
                                   np.asarray(ref)[:cu[-1]],
                                   atol=2e-5, rtol=2e-5)

    def test_all_reasons_are_frozen_taxonomy_members(self):
        for r in _fallback_reasons("ragged"):
            assert r in tpa.TP_FALLBACK_REASONS
