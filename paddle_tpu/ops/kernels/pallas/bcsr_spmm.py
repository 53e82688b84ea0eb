"""Block-CSR sparse @ dense matmul (SpMM) as a Pallas TPU kernel.

Reference counterpart: `paddle/phi/kernels/sparse/` SpMM kernels (cuSPARSE
on GPU); SURVEY §2.2 sparse-kernel stance: "composite lowering; BCSR
Pallas where hot". The composite in `paddle_tpu/sparse` (gather +
segment_sum) moves one row of the dense operand per NONZERO; this kernel
moves one (bk x bn) tile per nonzero BLOCK and hits the MXU with
[bm x bk] @ [bk x bn] products — the right asymptotics for structured
sparsity (block-pruned weights, ASP-style patterns).

Layout (BCSR): the [M, K] sparse matrix is tiled into (bm x bk) blocks;
`crows [Mb+1]` CSR-indexes the nonzero blocks per block-row,
`cols [NB]` holds each block's column-block id, `values [NB, bm, bk]`
the block contents. Grid = (N tiles, nonzero blocks in CSR order): the
accumulator scratch is revisited across each block-row's run, written out
on its last block. Rows with no blocks are zeroed in the wrapper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel(row_ref, first_ref, last_ref, cols_ref, vals_ref, x_ref, o_ref,
            acc_scr):
    b = pl.program_id(1)

    @pl.when(first_ref[b] == 1)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot(
        vals_ref[0].astype(jnp.float32), x_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(last_ref[b] == 1)
    def _():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def bcsr_spmm(crows, cols, values, x, bn: int = 512):
    """(crows [Mb+1], cols [NB], values [NB, bm, bk]) @ x [K, N] -> [M, N].

    crows/cols must be host-available (block structure is static per
    compiled call — the usual case: pruned weights); x and values are
    traced device arrays.
    """
    crows_np = np.asarray(crows)
    cols_np = np.asarray(cols).astype(np.int32)
    NB, bm, bk = values.shape
    Mb = len(crows_np) - 1
    K, N = x.shape
    assert K % bk == 0, f"K={K} not divisible by block k={bk}"
    if NB == 0:
        return jnp.zeros((Mb * bm, N), x.dtype)

    # per-block row id + first/last-in-row flags (CSR order)
    row_of = np.repeat(np.arange(Mb), np.diff(crows_np)).astype(np.int32)
    first = np.zeros(NB, np.int32)
    last = np.zeros(NB, np.int32)
    first[crows_np[:-1][np.diff(crows_np) > 0]] = 1
    last[crows_np[1:][np.diff(crows_np) > 0] - 1] = 1

    # N tiles stay lane-aligned even for ragged N (pad up to 128s): a
    # single full-width block would blow VMEM for wide vocab-sized N
    bn = max(128, -(-min(bn, N) // 128) * 128)
    Np = -(-N // bn) * bn
    xp = jnp.pad(x, ((0, 0), (0, Np - N))) if Np != N else x
    nn = Np // bn

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nn, NB),
        in_specs=[
            pl.BlockSpec((1, bm, bk),
                         lambda ni, b, row, fi, la, co: (b, 0, 0)),
            pl.BlockSpec((bk, bn),
                         lambda ni, b, row, fi, la, co: (co[b], ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda ni, b, row, fi, la, co: (row[b], ni)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mb * bm, Np), x.dtype),
        name="bcsr_spmm",
        interpret=_interpret(),
    )(jnp.asarray(row_of), jnp.asarray(first), jnp.asarray(last),
      jnp.asarray(cols_np), values, xp)
    # rows whose block-row is empty were never written: zero them
    empty = np.diff(crows_np) == 0
    if empty.any():
        mask = jnp.asarray(np.repeat(~empty, bm))[:, None]
        out = jnp.where(mask, out, 0)
    return out[:, :N]


def bcsr_from_dense(dense, bm: int, bk: int, tol: float = 0.0):
    """Tile a dense [M, K] matrix into BCSR, dropping all-(near)zero
    blocks. Returns (crows [Mb+1] np, cols [NB] np, values [NB, bm, bk])."""
    d = np.asarray(dense)
    M, K = d.shape
    assert M % bm == 0 and K % bk == 0
    Mb, Kb = M // bm, K // bk
    blocks = d.reshape(Mb, bm, Kb, bk).transpose(0, 2, 1, 3)
    keep = np.abs(blocks).max(axis=(2, 3)) > tol       # [Mb, Kb]
    crows = np.zeros(Mb + 1, np.int64)
    cols, vals = [], []
    for i in range(Mb):
        js = np.nonzero(keep[i])[0]
        crows[i + 1] = crows[i] + len(js)
        cols.extend(js.tolist())
        for j in js:
            vals.append(blocks[i, j])
    values = (np.stack(vals) if vals
              else np.zeros((0, bm, bk), d.dtype))
    return crows, np.asarray(cols, np.int64), jnp.asarray(values)


def bcsr_spmm_reference(crows, cols, values, x):
    """Dense reconstruction golden."""
    crows_np = np.asarray(crows)
    cols_np = np.asarray(cols)
    NB, bm, bk = values.shape
    Mb = len(crows_np) - 1
    K = x.shape[0]
    dense = jnp.zeros((Mb * bm, K), values.dtype)
    for i in range(Mb):
        for p in range(int(crows_np[i]), int(crows_np[i + 1])):
            j = int(cols_np[p])
            dense = dense.at[i * bm:(i + 1) * bm,
                             j * bk:(j + 1) * bk].set(values[p])
    return dense @ x
