"""Flash attention (fwd + bwd) as Pallas TPU kernels.

Reference counterpart: `paddle/phi/kernels/gpu/flash_attn_kernel.cu:91,199`
links an external FlashAttention-2 CUDA library via dynload
(`paddle/phi/backends/dynload/flashattn.cc`). The TPU build writes the kernel
itself: an online-softmax tiled attention whose working set stays in VMEM,
so the [sq, sk] score matrix never round-trips HBM.

Design notes (TPU-first):
- layouts are folded to [batch*heads, seq, head_dim]; the kernel grid is
  (batch*heads, q_blocks, kv_blocks) with the kv dimension innermost so the
  online-softmax state (m, l, acc) lives in VMEM scratch across kv steps.
- GQA is handled in the BlockSpec index maps (q head -> kv head = q // group),
  never by materialising repeated K/V in HBM.
- causal masking skips fully-masked kv blocks via `pl.when` predication; the
  partially-masked diagonal blocks mask with a large negative instead of -inf
  (every q row always has >= 1 valid column in its first kv block, so the
  running max is finite and exp() stays clean).
- backward runs as two kernels with opposite loop nests: dq accumulates over
  kv blocks; dk/dv accumulate over (group-head, q-block) pairs. Residuals are
  (q, k, v, out, lse); delta = rowsum(dout * out) is a cheap XLA elementwise.
- everything accumulates in f32 (MXU `preferred_element_type`), casts on the
  final write.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Trace-time override for interpreter mode (None = decide from the host
# backend). tp_attention.py sets it from the TARGET mesh platform while
# tracing a shard_map region: a deviceless AOT lowering for a TPU
# topology must embed the real Mosaic kernel even though the host
# default_backend() is cpu (and vice versa for forced CPU meshes).
_FORCE_INTERPRET = None


def _interpret() -> bool:
    # CPU (tests / dev boxes) runs the kernels in interpreter mode so the
    # same code path is exercised without a TPU.
    if _FORCE_INTERPRET is not None:
        return _FORCE_INTERPRET
    return jax.default_backend() != "tpu"


def _block(seq: int, want: int) -> Optional[int]:
    for b in (want, 512, 256, 128):
        if b <= want and seq % b == 0:
            return b
    return None


def supported(q_shape, k_shape, causal: bool) -> bool:
    """Whether the Pallas path handles this case (else XLA composite)."""
    b, sq, hq, d = q_shape
    sk, hk = k_shape[1], k_shape[2]
    if hq % hk != 0:
        return False
    if causal and sq > sk:
        return False  # more queries than keys has no right-aligned offset
    return (_block(sq, 512) is not None and _block(sk, 512) is not None
            and sq >= 128 and sk >= 128)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, nk,
                coff=0):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: kv block is live iff its first column <= last q row
    # (+ the right-alignment offset coff = sk - sq when sq != sk)
    run = (ik * bk <= iq * bq + bq - 1 + coff) if causal else True

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
            s = jnp.where(cols <= rows + coff, s, _NEG_INF)

        m_prev = m_scr[:, :1]                      # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                     # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # lse is stored [bh, 1, sq] (sublane-padded 8x only; a [bh, sq, 1]
        # layout lane-pads 128x in HBM). (bq,1)->(1,bq) once per q block —
        # spelled as a transpose, NOT a reshape: Mosaic's AOT layout
        # inference rejects the implicit-dim reshape ("Unsupported
        # implicit dim change") while the 2-d transpose compiles.
        lse_ref[0] = jax.lax.transpose(m_scr[:, :1] + jnp.log(l), (1, 0))


# The scope around each pallas_call's own name= takes the decoration of a
# transform (jvp(...), transpose(jvp(...))) in the kernel's place: the
# custom call is then flash_attention_fwd / _bwd_dq / _bwd_dkv in the
# compiled program and in a device trace under jax.grad too, not
# jvp_flash_attention_fwd_ (tests/test_chip_compile.py).
@jax.named_scope("flash_attention")
def _fwd(q, k, v, causal, scale):
    """q: [bh, sq, d]; k/v: [bh_kv, sk, d] -> (out [bh, sq, d], lse [bh, sq])."""
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    g = bh // bh_kv
    bq, bk = _block(sq, 512), _block(sk, 512)
    nq, nk = sq // bq, sk // bk

    grid = (bh, nq, nk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, coff=sk - sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               acc_scr, *, scale, causal, bq, bk, nk, coff=0):
    """Transposed orientation: scores live as s^T [bk, bq] so the per-q-row
    lse/delta [1, bq] broadcast along lanes with no relayouts."""
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = (ik * bk <= iq * bq + bq - 1 + coff) if causal else True

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0) + ik * bk
            qpos = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1) + iq * bq
            st = jnp.where(kpos <= qpos + coff, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])                 # [bk, bq]
        v = v_ref[0].astype(jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - dl_ref[0])                  # [bk, bq]
        acc_scr[:] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, bq, bk, nq, nqg, coff=0):
    """Transposed orientation (see _dq_kernel): dk = ds^T q, dv = p^T do fall
    out directly from the [bk, bq] score layout."""
    ik, iqg = pl.program_id(1), pl.program_id(2)
    iq = iqg % nq

    @pl.when(iqg == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = (iq * bq + bq - 1 + coff >= ik * bk) if causal else True

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0) + ik * bk
            qpos = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1) + iq * bq
            st = jnp.where(kpos <= qpos + coff, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])                 # [bk, bq]
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

    @pl.when(iqg == nqg - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@jax.named_scope("flash_attention")
def _bwd(causal, scale, res, dout, dlse=None):
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    g = bh // bh_kv
    bq, bk = _block(sq, 512), _block(sk, 512)
    nq, nk = sq // bq, sk // bk

    # delta = rowsum(dout * out), stored [bh, 1, sq] like lse. When lse is
    # itself an output being differentiated (ring attention's merge weights
    # use it), its cotangent folds in here: ds = p*(dp - delta + dlse),
    # i.e. delta' = delta - dlse — the kernels stay unchanged.
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)[:, None, :]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, coff=sk - sq),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=_interpret(),
    )(q, k, v, dout, lse, delta)

    nqg = nq * g
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, nqg=nqg, coff=sk - sq),
        grid=(bh_kv, nk, nqg),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda b, j, t: (b * g + t // nq, t % nq, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bq, d),
                         lambda b, j, t: (b * g + t // nq, t % nq, 0)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, j, t: (b * g + t // nq, 0, t % nq)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, j, t: (b * g + t // nq, 0, t % nq)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh_kv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh_kv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        name="flash_attention_bwd_dkv",
        interpret=_interpret(),
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom_vjp over folded [bh, s, d] layout)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, scale):
    out, _ = _fwd(q, k, v, causal, scale)
    return out


def _flash_fwd(q, k, v, causal, scale):
    out, lse = _fwd(q, k, v, causal, scale)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, res, dout):
    return _bwd(causal, scale, res, dout)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_block(q, k, v, causal, scale):
    """One attention block returning (out, lse), folded layout
    ([bh, sq, d], [bh, sq]) — the ring-attention building block. lse is a
    REAL differentiable output: the online-softmax merge weights downstream
    consume it, and its cotangent folds into the backward's delta term."""
    out, lse = _fwd(q, k, v, causal, scale)
    return out, lse[:, 0, :]


def _flash_block_fwd(q, k, v, causal, scale):
    out, lse = _fwd(q, k, v, causal, scale)
    return (out, lse[:, 0, :]), (q, k, v, out, lse)


def _flash_block_bwd(causal, scale, res, cts):
    dout, dlse = cts
    return _bwd(causal, scale, res, dout, dlse=dlse)


flash_block.defvjp(_flash_block_fwd, _flash_block_bwd)


def flash_attention(query, key, value, causal=False, scale=None):
    """[batch, seq, heads, head_dim] attention, GQA-aware.

    Same contract as the composite `scaled_dot_product_attention` kernel in
    ops/kernels/nn.py (reference API: paddle.nn.functional.flash_attention,
    `python/paddle/nn/functional/flash_attention.py:147`).
    """
    b, sq, hq, d = query.shape
    sk, hk = key.shape[1], key.shape[2]
    if scale is None:
        scale = d ** -0.5
    q = jnp.swapaxes(query, 1, 2).reshape(b * hq, sq, d)
    k = jnp.swapaxes(key, 1, 2).reshape(b * hk, sk, d)
    v = jnp.swapaxes(value, 1, 2).reshape(b * hk, sk, d)
    out = _flash(q, k, v, causal, float(scale))
    return jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2)
