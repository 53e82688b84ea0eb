"""Varlen (unpadded/packed) flash attention — `flash_attn_unpadded`.

Reference counterpart: `paddle/phi/kernels/gpu/flash_attn_kernel.cu:199`
(FlashAttnUnpaddedKernel over cu_seqlens). TPU-first design: XLA needs
static shapes, so the packed [total, heads, dim] layout IS the natural
fit — sequences stay concatenated, per-token segment ids + in-sequence
positions (derived once from cu_seqlens) drive the mask, and a scalar-
prefetched per-block segment-range table gives per-block SKIP: a
(q-block, k-block) pair runs only when their segment ranges overlap
(and, under causal, only when the k block isn't entirely in the future),
so compute scales with sum(len_i^2), not total^2 — the flash property,
kept across ragged batches.

Forward AND backward are Pallas (the backward reuses the transposed
[bk, bq] score orientation of flash_attention.py's kernels with the
segment masks folded in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _block, _interpret  # shared interpret override

_NEG_INF = -1e30


def _mask(segq, posq, segk, posk, causal):
    """[bq, 1] vs [1, bk] broadcasting -> bool [bq, bk]."""
    m = segq[:, None] == segk[None, :]
    if causal:
        m &= posk[None, :] <= posq[:, None]
    return m


# -- forward ----------------------------------------------------------------

def _fwd_kernel(ranges_ref, q_ref, k_ref, v_ref, sq_ref, pq_ref, sk_ref,
                pk_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, bq, bk, nk, nq, token_causal_skip):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # block skip from the prefetched segment-range table
    # ranges: [2, nq + nk] int32 — rows (lo, hi); cols [0,nq) = q blocks
    qlo, qhi = ranges_ref[0, iq], ranges_ref[1, iq]
    klo, khi = ranges_ref[0, nq + ik], ranges_ref[1, nq + ik]
    run = (klo <= qhi) & (khi >= qlo)
    if token_causal_skip:
        # self-attention packing (cu_q is cu_k): within a segment,
        # pos_c <= pos_r <=> token_c <= token_r, so whole future k blocks
        # skip in TOKEN space — causal compute stays ~sum(len^2)/2
        run &= ik * bk <= iq * bq + bq - 1

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        live = _mask(sq_ref[0], pq_ref[0], sk_ref[0], pk_ref[0], causal)
        s = jnp.where(live, s, _NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(live, p, 0.0)     # exp(-1e30 - -1e30) = 1 guard
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)   # fully-masked padding rows
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # transpose, not reshape: see flash_attention._fwd_kernel (Mosaic
        # AOT rejects the (bq,1)->(1,bq) implicit-dim reshape)
        lse_ref[0] = jax.lax.transpose(m_scr[:, :1] + jnp.log(l_safe),
                                       (1, 0))


# -- backward (transposed orientation, see flash_attention._dq_kernel) ------

def _dq_kernel(ranges_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               sq_ref, pq_ref, sk_ref, pk_ref, dq_ref, acc_scr,
               *, scale, causal, bq, bk, nk, nq, token_causal_skip):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qlo, qhi = ranges_ref[0, iq], ranges_ref[1, iq]
    klo, khi = ranges_ref[0, nq + ik], ranges_ref[1, nq + ik]
    run = (klo <= qhi) & (khi >= qlo)
    if token_causal_skip:
        run &= ik * bk <= iq * bq + bq - 1

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        live = _mask(sk_ref[0], pk_ref[0], sq_ref[0], pq_ref[0], False)
        if causal:
            live &= pq_ref[0][None, :] >= pk_ref[0][:, None]
        pt = jnp.where(live, jnp.exp(st - lse_ref[0]), 0.0)   # [bk, bq]
        v = v_ref[0].astype(jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - dl_ref[0])
        acc_scr[:] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(ranges_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                sq_ref, pq_ref, sk_ref, pk_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, causal, bq, bk, nq_total, nq, nk,
                token_causal_skip):
    ik, iqg = pl.program_id(1), pl.program_id(2)
    iq = iqg % nq

    @pl.when(iqg == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    qlo, qhi = ranges_ref[0, iq], ranges_ref[1, iq]
    klo, khi = ranges_ref[0, nq + ik], ranges_ref[1, nq + ik]
    run = (klo <= qhi) & (khi >= qlo)
    if token_causal_skip:
        run &= iq * bq + bq - 1 >= ik * bk

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        live = _mask(sk_ref[0], pk_ref[0], sq_ref[0], pq_ref[0], False)
        if causal:
            live &= pq_ref[0][None, :] >= pk_ref[0][:, None]
        pt = jnp.where(live, jnp.exp(st - lse_ref[0]), 0.0)
        v = v_ref[0].astype(jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - dl_ref[0])
        dk_scr[:] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        dv_scr[:] += jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iqg == nq_total - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# -- host-side layout -------------------------------------------------------

def _segments(cu, total, pad_total, pad_id):
    """cu_seqlens [n+1] -> (seg_id [pad_total], pos [pad_total]); padding
    tail gets `pad_id` so q and k padding never match each other."""
    t = jnp.arange(pad_total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu.astype(jnp.int32), t, side="right") \
        .astype(jnp.int32) - 1
    start = cu.astype(jnp.int32)[jnp.clip(seg, 0, cu.shape[0] - 2)]
    pos = t - start
    pad = t >= total
    return jnp.where(pad, pad_id, seg), jnp.where(pad, 0, pos)


def _block_ranges(seg, nb, bsz):
    """Per-block (min, max) segment ids -> [2, nb] int32 (prefetch table)."""
    s = seg.reshape(nb, bsz)
    return jnp.stack([s.min(axis=1), s.max(axis=1)], axis=0)


def _pad_to(x, t, axis=0):
    pad = t - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _ceil_to(x, m):
    return -(-x // m) * m


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _varlen(q, k, v, cu_q, cu_k, causal, scale, tok_skip):
    out, _ = _varlen_fwd_impl(q, k, v, cu_q, cu_k, causal, scale, tok_skip)
    return out


# outer scope: keeps the kernels' name= plain under jax.grad (see
# flash_attention.py)
@jax.named_scope("flash_varlen")
def _varlen_fwd_impl(q, k, v, cu_q, cu_k, causal, scale, tok_skip):
    Tq, h, d = q.shape
    Tk, hk, _ = k.shape
    g = h // hk
    bq = _block(_ceil_to(Tq, 128), 512)
    bk = _block(_ceil_to(Tk, 128), 512)
    Tqp, Tkp = _ceil_to(Tq, bq), _ceil_to(Tk, bk)
    nq, nk = Tqp // bq, Tkp // bk

    segq, posq = _segments(cu_q, Tq, Tqp, -1)
    segk, posk = _segments(cu_k, Tk, Tkp, -2)
    ranges = jnp.concatenate([_block_ranges(segq, nq, bq),
                              _block_ranges(segk, nk, bk)], axis=1)

    qf = _pad_to(jnp.swapaxes(q, 0, 1), Tqp, 1)          # [h, Tqp, d]
    kf = _pad_to(jnp.swapaxes(k, 0, 1), Tkp, 1)
    vf = _pad_to(jnp.swapaxes(v, 0, 1), Tkp, 1)
    sq2, pq2 = segq.reshape(1, Tqp), posq.reshape(1, Tqp)
    sk2, pk2 = segk.reshape(1, Tkp), posk.reshape(1, Tkp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, *_, g=g: (b // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, *_, g=g: (b // g, j, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j, *_: (0, i)),
            pl.BlockSpec((1, bq), lambda b, i, j, *_: (0, i)),
            pl.BlockSpec((1, bk), lambda b, i, j, *_: (0, j)),
            pl.BlockSpec((1, bk), lambda b, i, j, *_: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j, *_: (b, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, nq=nq,
                          token_causal_skip=tok_skip),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((h, Tqp, d), q.dtype),
            jax.ShapeDtypeStruct((h, 1, Tqp), jnp.float32),
        ],
        name="flash_varlen_fwd",
        interpret=_interpret(),
    )(ranges, qf, kf, vf, sq2, pq2, sk2, pk2)
    return jnp.swapaxes(out[:, :Tq], 0, 1), (qf, kf, vf, out, lse, ranges,
                                             sq2, pq2, sk2, pk2)


def _varlen_fwd(q, k, v, cu_q, cu_k, causal, scale, tok_skip):
    out, res = _varlen_fwd_impl(q, k, v, cu_q, cu_k, causal, scale,
                                tok_skip)
    return out, (res, q.shape, k.shape)


@jax.named_scope("flash_varlen")
def _varlen_bwd(causal, scale, tok_skip, carry, dout):
    res, q_shape, k_shape = carry
    qf, kf, vf, outf, lse, ranges, sq2, pq2, sk2, pk2 = res
    Tq, h, d = q_shape
    Tk, hk, _ = k_shape
    g = h // hk
    Tqp, Tkp = qf.shape[1], kf.shape[1]
    bq = _block(Tqp, 512)
    bk = _block(Tkp, 512)
    nq, nk = Tqp // bq, Tkp // bk

    dof = _pad_to(jnp.swapaxes(dout, 0, 1), Tqp, 1)
    delta = jnp.sum(dof.astype(jnp.float32) * outf.astype(jnp.float32),
                    axis=-1)[:, None, :]

    common = dict(scale=scale, causal=causal, bq=bq, bk=bk, nk=nk, nq=nq,
                  token_causal_skip=tok_skip)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, bk, d),
                             lambda b, i, j, *_, g=g: (b // g, j, 0)),
                pl.BlockSpec((1, bk, d),
                             lambda b, i, j, *_, g=g: (b // g, j, 0)),
                pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, 1, bq), lambda b, i, j, *_: (b, 0, i)),
                pl.BlockSpec((1, 1, bq), lambda b, i, j, *_: (b, 0, i)),
                pl.BlockSpec((1, bq), lambda b, i, j, *_: (0, i)),
                pl.BlockSpec((1, bq), lambda b, i, j, *_: (0, i)),
                pl.BlockSpec((1, bk), lambda b, i, j, *_: (0, j)),
                pl.BlockSpec((1, bk), lambda b, i, j, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((h, Tqp, d), qf.dtype),
        name="flash_varlen_bwd_dq",
        interpret=_interpret(),
    )(ranges, qf, kf, vf, dof, lse, delta, sq2, pq2, sk2, pk2)

    nqg = nq * g
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, nq_total=nqg, nq=nq, nk=nk,
                          token_causal_skip=tok_skip),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(hk, nk, nqg),
            in_specs=[
                pl.BlockSpec((1, bq, d),
                             lambda b, j, t, *_, g=g, nq=nq:
                             (b * g + t // nq, t % nq, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, t, *_: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, t, *_: (b, j, 0)),
                pl.BlockSpec((1, bq, d),
                             lambda b, j, t, *_, g=g, nq=nq:
                             (b * g + t // nq, t % nq, 0)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, j, t, *_, g=g, nq=nq:
                             (b * g + t // nq, 0, t % nq)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, j, t, *_, g=g, nq=nq:
                             (b * g + t // nq, 0, t % nq)),
                pl.BlockSpec((1, bq), lambda b, j, t, *_, nq=nq: (0, t % nq)),
                pl.BlockSpec((1, bq), lambda b, j, t, *_, nq=nq: (0, t % nq)),
                pl.BlockSpec((1, bk), lambda b, j, t, *_: (0, j)),
                pl.BlockSpec((1, bk), lambda b, j, t, *_: (0, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda b, j, t, *_: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, t, *_: (b, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((hk, Tkp, d), kf.dtype),
            jax.ShapeDtypeStruct((hk, Tkp, d), vf.dtype),
        ],
        name="flash_varlen_bwd_dkv",
        interpret=_interpret(),
    )(ranges, qf, kf, vf, dof, lse, delta, sq2, pq2, sk2, pk2)

    return (jnp.swapaxes(dq[:, :Tq], 0, 1),
            jnp.swapaxes(dk[:, :Tk], 0, 1),
            jnp.swapaxes(dv[:, :Tk], 0, 1),
            None, None)


_varlen.defvjp(_varlen_fwd, _varlen_bwd)


def varlen_composite(q, k, v, cu_seqlens_q, cu_seqlens_k, scale=None,
                     causal: bool = False):
    """XLA composite over the packed layout (dense [Tq, Tk] scores with
    segment-id masking) — the GSPMD-partitionable fallback the TP
    dispatcher takes when the shard_map'd kernel can't (head counts not
    divisible by the tp degree, FLAGS_use_pallas_kernels off)."""
    Tq, h, d = q.shape
    Tk, hk = k.shape[0], k.shape[1]
    if scale is None:
        scale = d ** -0.5
    segq, posq = _segments(cu_seqlens_q.astype(jnp.int32), Tq, Tq, -1)
    segk, posk = _segments(cu_seqlens_k.astype(jnp.int32), Tk, Tk, -2)
    if hk != h:
        k = jnp.repeat(k, h // hk, axis=1)
        v = jnp.repeat(v, h // hk, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    live = segq[:, None] == segk[None, :]
    if causal:
        live &= posk[None, :] <= posq[:, None]
    logits = jnp.where(live[None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(live[None], probs, 0.0)  # fully-masked rows -> 0
    return jnp.einsum("hqk,khd->qhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def same_cu_layout(cu_seqlens_q, cu_seqlens_k) -> bool:
    """Whether q and k share one packing — the precondition for the
    token-space causal block skip. Valid only for self-attention
    packing (identical cu layouts): same batch + same total token count
    does NOT imply identical packing (q lens [1,199] vs k lens [199,1]),
    so only array identity — which survives tracing — or an equal
    concrete host-side comparison may enable it; otherwise the mask
    alone enforces causality (correct, fewer skipped blocks)."""
    if cu_seqlens_q is cu_seqlens_k:
        return True
    if isinstance(cu_seqlens_q, jax.core.Tracer) \
            or isinstance(cu_seqlens_k, jax.core.Tracer):
        return False
    return (cu_seqlens_q.shape == cu_seqlens_k.shape
            and bool((np.asarray(cu_seqlens_q)
                      == np.asarray(cu_seqlens_k)).all()))


def flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q=None, max_seqlen_k=None, scale=None,
                        causal: bool = False):
    """Packed varlen attention (reference flash_attn_unpadded contract):
    q [total_q, num_heads, head_dim]; k/v [total_k, kv_heads, head_dim];
    cu_seqlens_* [batch+1] int32 prefix sums. max_seqlen_* accepted for
    API parity (shapes are static here). Returns [total_q, heads, dim]."""
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    tok_skip = bool(causal) and same_cu_layout(cu_seqlens_q, cu_seqlens_k)
    return _varlen(q, k, v, cu_seqlens_q.astype(jnp.int32),
                   cu_seqlens_k.astype(jnp.int32), bool(causal),
                   float(scale), tok_skip)
