"""Ragged selective scan and ragged causal convolution: the two Pallas
kernels of a Mamba-1 mixer inside the serving engine's ragged step.

A ragged step packs every row's tokens on one token axis: decode rows of
one token and prefill chunks of up to a chunk's tokens, segmented by
``cu_q_lens[R+1]``. A recurrent layer keeps one state a row, not one a
token: the float32 SSM state ``[N, D]`` and the convolution's tail, the
last ``K - 1`` inputs. Both live in a row-state cache ``[S, ...]`` whose
index is the row's slot (`PagedKVCache.row`), and both kernels have the
same shape:

- the channel axis ``D`` is laid out as ``[D // 128, 128]`` (sublanes x
  lanes), so a state ``[N, D // 128, 128]`` is ``N`` stacks of whole
  vector registers and a token's ``B[n]`` and ``C[n]`` are scalars (read
  from scalar memory) that multiply whole registers: no transposes, no
  lane broadcasts;
- the grid runs over tiles of the channel axis, as wide as keeps the
  packed tokens' tile of ``x``, ``dt``, ``z`` and the output (BlockSpecs)
  within ``_BLOCK_BYTES`` each: the whole axis at the widths served so
  far, since a row's visit costs about a microsecond whatever it moves;
- the state stays in HBM, aliased to the output. Inside a grid step the
  kernel walks the LIVE rows (those with a token in the step, compacted
  by the wrapper into ``order``) through a ring of ``SLOTS`` VMEM slots:
  the state tiles of the next ``AHEAD`` live rows are on their way in
  while the segment's tokens run one after the other on the resident
  tile, and a tile goes back to HBM with ``SLOTS - AHEAD`` visits of time
  before its slot is filled again, so a decode step's walk is bound by
  the state's bytes and not by a copy's latency. A row without a token
  is neither read nor written. A segment whose first token is at
  position 0 starts from zeros whatever the cache holds, so admission,
  preemption and the reuse of a row slot cost no launch of their own.

The XLA composites of the same two ops are in ``ops/kernels/serving.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....jax_compat import tpu_compiler_params
from .flash_attention import _interpret  # shared interpret override

LANES = 128
# the ring of VMEM slots the live rows' state tiles pass through, and how
# many rows ahead of the one being computed their copies are started
SLOTS, AHEAD = 8, 4
# the most one of the packed tokens' blocks may hold in VMEM
_BLOCK_BYTES = 6 * 1024 * 1024
_VMEM_LIMIT = 96 * 1024 * 1024


def supported(d_inner: int) -> bool:
    """Whether the Pallas path takes this width (else the XLA composite)."""
    return d_inner % LANES == 0


def _tile(groups: int, tokens: int, itemsize: int) -> int:
    """Sublanes of the channel axis a grid step holds: the whole axis, or
    its largest divisor of whole 8-sublane tiles whose block of the packed
    tokens stays within ``_BLOCK_BYTES``."""
    fits = [d for d in range(8, groups, 8) if groups % d == 0
            and tokens * d * LANES * itemsize <= _BLOCK_BYTES]
    if tokens * groups * LANES * itemsize <= _BLOCK_BYTES or not fits:
        return groups
    return max(fits)


def _live_rows(cu):
    """The rows that have a token in the step, first and in row order, as
    one array ``[R + 1]`` of scalars: ``order[:R]``, then how many are
    live."""
    live = cu[1:] > cu[:-1]
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return jnp.concatenate([order, jnp.sum(live, dtype=jnp.int32)[None]])


def _walk_rows(n_live, fetch, write_back, compute):
    """For k = 0 .. n_live - 1: the k-th live row's state tile copied into
    VMEM slot k % SLOTS, ``compute(k, slot)`` on it, the tile copied back.
    The copies of the next ``AHEAD`` rows are in flight meanwhile, and a
    slot is filled again ``SLOTS - AHEAD`` visits after its write-back
    began. ``fetch(k, slot)`` and ``write_back(k, slot)`` make the copies'
    descriptors."""
    for k in range(AHEAD):
        @pl.when(k < n_live)
        def _():
            fetch(k, k % SLOTS).start()

    def body(k, carry):
        slot = k % SLOTS
        ahead = k + AHEAD

        @pl.when(ahead < n_live)
        def _():
            # the slot row k + AHEAD goes into last held row k + AHEAD -
            # SLOTS: that row's tile must have landed in HBM first
            @pl.when(ahead >= SLOTS)
            def _():
                write_back(ahead - SLOTS, ahead % SLOTS).wait()

            fetch(ahead, ahead % SLOTS).start()

        fetch(k, slot).wait()
        compute(k, slot)
        write_back(k, slot).start()
        return carry

    jax.lax.fori_loop(0, n_live, body, 0)

    # the loop waited for a row's write-back when it filled the row's slot
    # again, SLOTS rows on: the last SLOTS rows are waited for here
    for i in range(1, SLOTS + 1):
        @pl.when(n_live >= i)
        def _():
            write_back(n_live - i, (n_live - i) % SLOTS).wait()


def _copies(rows_ref, slot_ref, hbm_in, hbm_out, buf, sem):
    """The descriptors of a state tile's way in and out for `_walk_rows`:
    live row k's tile, as many sublanes as the VMEM slot holds, from this
    grid step's offset."""
    j = pl.program_id(0)
    ds = buf.shape[2]

    def tile(hbm, k):
        return hbm.at[slot_ref[rows_ref[k]], :, pl.ds(j * ds, ds), :]

    def fetch(k, slot):
        return pltpu.make_async_copy(tile(hbm_in, k), buf.at[slot],
                                     sem.at[0, slot])

    def write_back(k, slot):
        return pltpu.make_async_copy(buf.at[slot], tile(hbm_out, k),
                                     sem.at[1, slot])

    return fetch, write_back


def _scan_kernel(rows_ref, cu_ref, slot_ref, pos0_ref, b_ref, c_ref,
                 x_ref, dt_ref, z_ref, alog_ref, d_ref, state_in,
                 y_ref, state_out, buf, a_scr, sem, *, n_state):
    rows = rows_ref.shape[0] - 1
    fetch, write_back = _copies(rows_ref, slot_ref, state_in, state_out,
                                buf, sem)
    # step-padding tokens belong to no row: they read zeros
    y_ref[...] = jnp.zeros_like(y_ref)
    a_scr[...] = -jnp.exp(alog_ref[...].astype(jnp.float32))
    skip = d_ref[...].astype(jnp.float32)

    def compute(k, slot):
        row = rows_ref[k]
        off = cu_ref[row]

        @pl.when(pos0_ref[row] == 0)
        def _():
            buf[slot] = jnp.zeros(buf.shape[1:], buf.dtype)

        def token(i, carry):
            t = off + i
            x = x_ref[t].astype(jnp.float32)              # [ds, 128]
            delta = jax.nn.softplus(dt_ref[t].astype(jnp.float32))
            dx = delta * x
            y = skip * x
            for n in range(n_state):
                s = (jnp.exp(delta * a_scr[n]) * buf[slot, n]
                     + dx * b_ref[t * n_state + n])
                buf[slot, n] = s
                y = y + s * c_ref[t * n_state + n]
            z = z_ref[t].astype(jnp.float32)
            y_ref[t] = (y * z * jax.nn.sigmoid(z)).astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, cu_ref[row + 1] - off, token, 0)

    _walk_rows(rows_ref[rows], fetch, write_back, compute)


def _conv_kernel(rows_ref, cu_ref, slot_ref, pos0_ref,
                 x_ref, w_ref, b_ref, tail_in,
                 y_ref, tail_out, buf, sem, *, taps):
    rows = rows_ref.shape[0] - 1
    fetch, write_back = _copies(rows_ref, slot_ref, tail_in, tail_out,
                                buf, sem)
    y_ref[...] = jnp.zeros_like(y_ref)
    w = [w_ref[i].astype(jnp.float32) for i in range(taps)]
    bias = b_ref[...].astype(jnp.float32)

    def compute(k, slot):
        row = rows_ref[k]
        off = cu_ref[row]
        fresh = pos0_ref[row] == 0
        window = tuple(
            jnp.where(fresh, 0.0, buf[slot, i].astype(jnp.float32))
            for i in range(taps - 1))

        def token(i, window):
            t = off + i
            x = x_ref[t].astype(jnp.float32)
            acc = bias + w[taps - 1] * x
            for j in range(taps - 1):
                acc = acc + w[j] * window[j]
            y_ref[t] = (acc * jax.nn.sigmoid(acc)).astype(y_ref.dtype)
            return window[1:] + (x,)

        window = jax.lax.fori_loop(0, cu_ref[row + 1] - off, token, window)
        for i in range(taps - 1):
            buf[slot, i] = window[i].astype(buf.dtype)

    _walk_rows(rows_ref[rows], fetch, write_back, compute)


def _tiled(a):
    """``[..., D]`` as ``[..., D // 128, 128]``."""
    return a.reshape(*a.shape[:-1], a.shape[-1] // LANES, LANES)


def _lead_spec(lead, tile):
    """A block of ``tile`` sublanes of ``[lead, D // 128, 128]``: the packed
    tokens' (lead = T) or a weight's."""
    return pl.BlockSpec((lead, tile, LANES), lambda j, *_: (0, j, 0))


def ragged_selective_scan(x, dt, B, C, z, A_log, D, cu_q_lens, slots,
                          start_pos, state):
    """The selective scan of one Mamba-1 layer over a ragged step.

    x, dt, z ``[T, D]`` packed over rows by ``cu_q_lens[R + 1]`` (x after
    the convolution and SiLU, dt before its softplus); B, C ``[T, N]``;
    A_log ``[N, D]``; D ``[D]``; slots ``[R]`` each row's index into
    ``state [S, N, D // 128, 128]`` float32; start_pos ``[R]`` the
    position of each row's first token (0: the segment starts from a zero
    state). Returns ``y * silu(z)`` ``[T, D]`` (zeros for step padding)
    and the state, updated in place for the rows that had tokens."""
    tokens, d_inner = x.shape
    n_state = B.shape[1]
    groups = d_inner // LANES
    tile = _tile(groups, tokens, x.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(groups // tile,),
        in_specs=[_lead_spec(tokens, tile)] * 3 + [
            _lead_spec(n_state, tile),
            pl.BlockSpec((tile, LANES), lambda j, *_: (j, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[_lead_spec(tokens, tile),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((SLOTS, n_state, tile, LANES), state.dtype),
            pltpu.VMEM((n_state, tile, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2, SLOTS))],
    )
    cu = cu_q_lens.astype(jnp.int32)
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=n_state),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((tokens, groups, LANES), x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 11 (after the six scalar ones): the state, written in place
        input_output_aliases={11: 1},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ragged_selective_scan",
        interpret=_interpret(),
    )(_live_rows(cu), cu, slots.astype(jnp.int32),
      start_pos.astype(jnp.int32),
      B.astype(jnp.float32).reshape(-1), C.astype(jnp.float32).reshape(-1),
      _tiled(x), _tiled(dt), _tiled(z), _tiled(A_log), _tiled(D), state)
    return y.reshape(tokens, d_inner), state


def ragged_causal_conv(x, weight, bias, cu_q_lens, slots, start_pos, tail):
    """The depthwise causal convolution of one Mamba-1 layer over a ragged
    step, with its SiLU.

    x ``[T, D]`` packed over rows by ``cu_q_lens[R + 1]``; weight
    ``[K, D]`` (tap K - 1 multiplies the token itself); bias ``[D]``;
    ``tail [S, K - 1, D // 128, 128]`` each row's last ``K - 1`` inputs,
    oldest first, indexed by ``slots[R]``; a row whose ``start_pos`` is 0
    has zeros before its first token. Returns ``silu(conv(x))`` ``[T, D]``
    and the tail, updated in place for the rows that had tokens."""
    tokens, d_inner = x.shape
    taps = weight.shape[0]
    groups = d_inner // LANES
    tile = _tile(groups, tokens, x.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(groups // tile,),
        in_specs=[_lead_spec(tokens, tile), _lead_spec(taps, tile),
                  pl.BlockSpec((tile, LANES), lambda j, *_: (j, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[_lead_spec(tokens, tile),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((SLOTS, taps - 1, tile, LANES), tail.dtype),
            pltpu.SemaphoreType.DMA((2, SLOTS))],
    )
    cu = cu_q_lens.astype(jnp.int32)
    y, tail = pl.pallas_call(
        functools.partial(_conv_kernel, taps=taps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((tokens, groups, LANES), x.dtype),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype)],
        input_output_aliases={7: 1},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ragged_causal_conv",
        interpret=_interpret(),
    )(_live_rows(cu), cu, slots.astype(jnp.int32),
      start_pos.astype(jnp.int32),
      _tiled(x), _tiled(weight), _tiled(bias), tail)
    return y.reshape(tokens, d_inner), tail
