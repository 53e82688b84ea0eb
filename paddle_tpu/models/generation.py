"""Autoregressive generation: the dense KV cache and its decode loop, and
the paged block pool the serving engine writes into.

Reference: the serving path around
phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu (paged KV) and
PaddleNLP's GenerationMixin API (generate with greedy/top-k/top-p).

TPU shape: fixed-capacity cache buffers so every decode step hits ONE cached
executable (position/length are tensor inputs, never static attrs); the
paged cache is a host-side block allocator over a device block pool —
sequences share the pool, blocks are recycled on release. Its one consumer
is `models/serving.py: ContinuousBatchingEngine`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..ops.dispatcher import call_op


class KVCache:
    """Contiguous per-layer cache [B, max_len, KV_heads, head_dim]."""

    def __init__(self, num_layers: int, batch: int, max_len: int,
                 num_kv_heads: int, head_dim: int, dtype="float32"):
        self.max_len = max_len
        self.k = [Tensor(jnp.zeros((batch, max_len, num_kv_heads, head_dim),
                                   dtype=dtype)) for _ in range(num_layers)]
        self.v = [Tensor(jnp.zeros((batch, max_len, num_kv_heads, head_dim),
                                   dtype=dtype)) for _ in range(num_layers)]

    def update(self, layer: int, k_new: Tensor, v_new: Tensor,
               pos: Tensor) -> Tuple[Tensor, Tensor]:
        """Write k/v at [:, pos:pos+S]; returns the full cache views."""
        self.k[layer] = call_op("cache_write", self.k[layer], k_new, pos)
        self.v[layer] = call_op("cache_write", self.v[layer], v_new, pos)
        return self.k[layer], self.v[layer]

    def attend(self, layer: int, q: Tensor, pos: Tensor,
               attn_mask: Optional[Tensor] = None) -> Tensor:
        return call_op("cache_attention", q, self.k[layer], self.v[layer],
                       pos, attn_mask)


def kv_pool_blocks(kv_pool_bytes: int, block_size: int, num_kv_heads: int,
                   head_dim: int, num_layers: int, dtype="float32",
                   kv_dtype: str = "auto") -> int:
    """Blocks a fixed HBM byte budget buys at a storage regime — the
    admission-capacity side of FLAGS_kv_cache_dtype: sizing a pool in
    bytes instead of blocks lets int8 nearly double block count (and
    with it continuous-batching occupancy and prefix-cache headroom)
    for the same memory, scale rows included in the denominator."""
    if kv_dtype in (None, "", "auto"):
        kv_dtype = "auto"
    store = {"auto": dtype, "bf16": "bfloat16",
             "bfloat16": "bfloat16", "int8": "int8"}.get(kv_dtype)
    if store is None:
        raise ValueError(
            f"unsupported kv_dtype {kv_dtype!r}: expected 'auto', "
            f"'bf16' or 'int8' (FLAGS_kv_cache_dtype)")
    per_tok = 2 * num_kv_heads * head_dim * jnp.dtype(store).itemsize
    if kv_dtype == "int8":
        per_tok += 2 * num_kv_heads * 4       # f32 scale per token slot
    return max(1, int(kv_pool_bytes) // (per_tok * num_layers * block_size))


@dataclass(frozen=True)
class PagedKV:
    """A layer whose state is every token's keys and values: it gets a
    pool ``[blocks, block_size, num_kv_heads, head_dim]`` for each."""
    num_kv_heads: int
    head_dim: int


@dataclass(frozen=True)
class RowState:
    """A layer whose state is a fixed size a row, whatever the row has
    seen (a recurrence): named arrays, each ``(name, shape, dtype)`` of
    one row. It gets a row-state cache ``[rows + 1, *shape]`` for each."""
    arrays: Tuple[Tuple[str, Tuple[int, ...], str], ...]


def layer_states(model) -> Tuple:
    """What each layer of ``model`` keeps between a row's tokens, as the
    model declares it (``model.layer_states()``: a `PagedKV` or a
    `RowState` a layer). A model that declares nothing keeps paged keys
    and values in every layer, sized from its config."""
    declare = getattr(model, "layer_states", None)
    if declare is not None:
        return tuple(declare())
    cfg = model.config
    return (PagedKV(cfg.num_key_value_heads,
                    cfg.hidden_size // cfg.num_attention_heads),
            ) * cfg.num_hidden_layers


def has_row_state(model) -> bool:
    return any(isinstance(l, RowState) for l in layer_states(model))


def enters_step_program(forward):
    """Wraps a causal LM's ``forward``: handed the serving engine's view of
    a ragged step (a cache that names a ``program``), the call runs as
    that one XLA program (models/serving.py), which traces the wrapped
    forward once over a view of tracers. The one place through which
    every model class reaches the step program."""

    @functools.wraps(forward)
    def entered(self, input_ids, *args, cache=None, start_pos=None, **kw):
        program = getattr(cache, "program", None)
        if program is not None:
            return program(self, input_ids, start_pos, cache)
        return forward(self, input_ids, *args, cache=cache,
                       start_pos=start_pos, **kw)

    return entered


class PagedKVCache:
    """Block-pool cache with per-sequence block tables (paged attention),
    and beside it the row-state cache of the layers that keep a fixed-size
    state a row.

    Pool: [num_blocks, block_size, KV_heads, head_dim] per layer. The host
    allocator hands free blocks to sequences as they grow; `release` returns
    them — the serving memory model of the reference's block_multi_head
    path.

    The pool arrays belong to the cache: ``k[l]._data`` and ``v[l]._data``
    (and the int8 scales) are whatever the last write returned. A writer
    that donates them (the ragged engine's step program) hands the old
    arrays to XLA and rebinds the new ones through `set_pools`, so nobody
    keeps a pool array across a write: read it from the cache each time.

    ``layers`` says what each of the model's layers keeps (`layer_states`);
    without it every one of ``num_layers`` layers keeps paged K and V of
    ``num_kv_heads`` x ``head_dim``. A `PagedKV` layer gets its K and V
    pools; a `RowState` layer gets, for each array it names, ``[batch + 1,
    *shape]``: row r's state at index r, the last index being where the
    padding of a step writes. ``k``, ``v`` and each ``row_state[name]``
    list the layers of their kind in model order; ``write``, ``scale_kwargs``
    and ``row`` take the model's layer index. ``num_layers`` counts the
    paged layers."""

    def __init__(self, num_layers: int, batch: int, num_blocks: int,
                 block_size: int, num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 max_blocks_per_seq: Optional[int] = None, dtype="float32",
                 kv_dtype: str = "auto", layers: Optional[Tuple] = None):
        if layers is None:
            layers = (PagedKV(num_kv_heads, head_dim),) * num_layers
        self.layers = tuple(layers)
        paged = [l for l in self.layers if isinstance(l, PagedKV)]
        self.block_size = block_size
        self.num_layers = len(paged)
        # kv_dtype: "auto" stores at the compute dtype; "bf16" halves
        # bf16-vs-f32 bytes; "int8" quantizes on append with per-token-
        # slot per-kv-head f32 scales [NB, BS, KV] riding the block
        # table (FLAGS_kv_cache_dtype; dequant happens inside the
        # attention kernels' tile loads)
        if kv_dtype in (None, "", "auto"):
            kv_dtype = "auto"
        store = {"auto": dtype, "bf16": "bfloat16",
                 "bfloat16": "bfloat16", "int8": "int8"}.get(kv_dtype)
        if store is None:
            raise ValueError(
                f"unsupported kv_dtype {kv_dtype!r}: expected 'auto', "
                f"'bf16' or 'int8' (FLAGS_kv_cache_dtype)")
        self.kv_dtype = "int8" if kv_dtype == "int8" else str(store)
        self.quantized = kv_dtype == "int8"

        def stored(l: PagedKV):
            # the chip tiles a pool over its last two axes, [KV, head_dim],
            # and packs two 16-bit rows into one word: a single KV head in
            # bfloat16 would be padded to a pair (and no block of it can be
            # copied whole), so it is kept in float32, the same bytes
            if (l.num_kv_heads == 1 and not self.quantized
                    and jnp.dtype(store).itemsize < 4):
                return jnp.float32
            return store

        self.k = [Tensor(jnp.zeros((num_blocks, block_size, l.num_kv_heads,
                                    l.head_dim), dtype=stored(l)))
                  for l in paged]
        self.v = [Tensor(jnp.zeros((num_blocks, block_size, l.num_kv_heads,
                                    l.head_dim), dtype=stored(l)))
                  for l in paged]
        if self.quantized:
            self.k_scale = [Tensor(jnp.zeros(
                (num_blocks, block_size, l.num_kv_heads), jnp.float32))
                for l in paged]
            self.v_scale = [Tensor(jnp.zeros(
                (num_blocks, block_size, l.num_kv_heads), jnp.float32))
                for l in paged]
        else:
            self.k_scale = self.v_scale = None
        self.row_state: Dict[str, List[Tensor]] = {}
        for l in self.layers:
            for name, shape, dt in getattr(l, "arrays", ()):
                self.row_state.setdefault(name, []).append(Tensor(
                    jnp.zeros((batch + 1,) + tuple(shape), dtype=dt)))
        self._set_names()
        self._free = list(range(num_blocks - 1, -1, -1))
        self.block_tables = np.zeros((batch, max_blocks_per_seq), np.int32)
        # blocks handed to each sequence so far — allocation is per TOKEN,
        # not per layer-write (all layers share one block table)
        self._allocated = np.zeros((batch,), np.int32)

    def _set_names(self) -> None:
        """``pool_names`` (the paged pools' lists, then the row-state
        arrays' names), ``spec`` (what `over` needs to lay the same
        arrays out again) and each layer's index within its kind."""
        self.paged_names = (("k", "v", "k_scale", "v_scale")
                            if self.quantized else ("k", "v"))
        self.pool_names = self.paged_names + tuple(self.row_state)
        self.spec = (self.layers, self.quantized)
        counts = {}
        self._index = []
        for l in self.layers:
            self._index.append(counts.get(type(l), 0))
            counts[type(l)] = self._index[-1] + 1

    # -- the pools as one flat group of arrays --------------------------------
    def paged_lists(self) -> List[List[Tensor]]:
        """The per-layer lists of the block pools: K, V and, for an int8
        pool, their scales."""
        return [getattr(self, name) for name in self.paged_names]

    def pool_lists(self) -> List[List[Tensor]]:
        """Every per-layer list of arrays a step program owns: the block
        pools, then the row-state arrays name by name."""
        return self.paged_lists() + list(self.row_state.values())

    def pools(self) -> Tuple:
        """Every pool array, list by list and layer by layer: what a
        program that owns the pools takes and gives back."""
        return tuple(t._data for ts in self.pool_lists() for t in ts)

    def set_pools(self, arrays) -> None:
        """Rebinds every pool to ``arrays`` (the order of `pools`)."""
        for t, a in zip((t for ts in self.pool_lists() for t in ts), arrays):
            t._data = a

    @classmethod
    def over(cls, spec: Tuple, arrays) -> "PagedKVCache":
        """A cache that is nothing but pools: ``arrays`` (tracers, while a
        program that owns the pools is traced) in the order of `pools`
        for a cache whose `spec` this is. It can `write`, hand out
        `scale_kwargs` and `row` states and give its `pools` back; it has
        no allocator and belongs to no engine."""
        c = object.__new__(cls)
        c.layers, c.quantized = spec
        c.num_layers = n = sum(isinstance(l, PagedKV) for l in c.layers)
        rows = [l for l in c.layers if isinstance(l, RowState)]
        c.row_state = {name: [] for l in rows for name, _, _ in l.arrays}
        c._set_names()
        arrays = [Tensor(a) for a in arrays]
        for name in c.paged_names:
            setattr(c, name, arrays[:n])
            arrays = arrays[n:]
        for name in c.row_state:
            c.row_state[name] = arrays[:len(rows)]
            arrays = arrays[len(rows):]
        return c

    def write(self, layer: int, k_new: Tensor, v_new: Tensor,
              slots: Tensor):
        """THE pool write: the int8 quantize-on-append and the plain
        write are one implementation.
        Pure: the pools it returns replace the ones it read. Called per
        op it copies a whole pool to write a few slots; the ragged step
        calls it inside the one program that owns the pools, where the
        same scatter is in place."""
        layer = self._index[layer]
        if self.quantized:
            self.k[layer], self.k_scale[layer] = call_op(
                "paged_cache_write_q", self.k[layer], self.k_scale[layer],
                k_new, slots)
            self.v[layer], self.v_scale[layer] = call_op(
                "paged_cache_write_q", self.v[layer], self.v_scale[layer],
                v_new, slots)
        else:
            self.k[layer] = call_op("paged_cache_write", self.k[layer],
                                    k_new, slots)
            self.v[layer] = call_op("paged_cache_write", self.v[layer],
                                    v_new, slots)
        return self.k[layer], self.v[layer]

    def scale_kwargs(self, layer: int) -> dict:
        """Dequant-scale kwargs for the ragged attention op (empty for
        an unquantized pool)."""
        if not self.quantized:
            return {}
        layer = self._index[layer]
        return dict(k_scale=self.k_scale[layer],
                    v_scale=self.v_scale[layer])

    def kv(self, layer: int) -> Tuple[Tensor, Tensor]:
        """The K and V pools of the model's layer ``layer``."""
        return self.k[self._index[layer]], self.v[self._index[layer]]

    def row(self, layer: int, name: str) -> Tensor:
        """The row-state array ``name`` of the model's layer ``layer``."""
        return self.row_state[name][self._index[layer]]

    def set_row(self, layer: int, name: str, value: Tensor) -> None:
        self.row_state[name][self._index[layer]] = value

    def row_state_bytes(self) -> int:
        """Resident bytes of every row-state array (0 without any)."""
        return sum(t._data.nbytes for ts in self.row_state.values()
                   for t in ts)

    def kv_bytes_per_token(self) -> int:
        """HBM bytes one token's K+V occupies across all layers —
        including the f32 scale bytes for the int8 pool (the honest
        bandwidth denominator the serving.kv.bytes_per_token gauge
        reports)."""
        per = 0
        for k in self.k:                          # the paged layers only
            kv, d = k.shape[2], k.shape[3]
            per += 2 * kv * d * jnp.dtype(k._data.dtype).itemsize
            if self.quantized:
                per += 2 * kv * 4                 # [NB, BS, KV] f32 x2
        return per

    # -- host-side allocator -------------------------------------------------
    def alloc_slots(self, seq: int, pos0: int, n: int,
                    alloc_block=None) -> np.ndarray:
        """Vectorized write slots for ``n`` tokens at ``pos0..pos0+n-1``:
        block allocation runs once per NEW BLOCK, not per token, and the
        flat slot ids come out of one vectorized expression.
        ``alloc_block`` overrides the free-list pop — the serving engine
        routes allocation through its prefix-cache-aware allocator
        (evictable cached blocks count as free there)."""
        if n <= 0:
            return np.empty((0,), np.int64)
        blk_hi = (pos0 + n - 1) // self.block_size
        if blk_hi >= self.block_tables.shape[1]:
            raise RuntimeError(
                f"PagedKVCache: position {pos0 + n - 1} needs block "
                f"{blk_hi} but max_blocks_per_seq="
                f"{self.block_tables.shape[1]}")
        while self._allocated[seq] <= blk_hi:
            if alloc_block is not None:
                blk = alloc_block()
            elif self._free:
                blk = self._free.pop()
            else:
                raise RuntimeError("PagedKVCache: block pool exhausted")
            self.block_tables[seq, self._allocated[seq]] = blk
            self._allocated[seq] += 1
        pos = pos0 + np.arange(n)
        return (self.block_tables[seq, pos // self.block_size]
                .astype(np.int64) * self.block_size
                + pos % self.block_size)

    def release(self, seq: int):
        used = int(self._allocated[seq])
        self._free.extend(int(b) for b in self.block_tables[seq, :used])
        self.block_tables[seq, :] = 0
        self._allocated[seq] = 0


class GenerationMixin:
    """Decode loop (PaddleNLP GenerationMixin analog). Host model must
    accept forward(input_ids, cache=..., start_pos=...) returning logits."""

    def generate(self, input_ids: Tensor, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token_id: Optional[int] = None,
                 max_cache_len: Optional[int] = None):
        """Static-batch decode over the dense [B, T] cache. Paged serving
        is `ContinuousBatchingEngine`."""
        from ..autograd.engine import no_grad
        if has_row_state(self):
            raise NotImplementedError(
                f"{type(self).__name__} keeps recurrent state a row, which "
                f"the dense generate() loop's KVCache cannot hold: serve it "
                f"through models.serving.ContinuousBatchingEngine")
        cfg = self.config
        b, s = input_ids.shape[0], input_ids.shape[1]
        total = s + max_new_tokens
        if max_cache_len is not None and max_cache_len < total:
            raise ValueError(
                f"max_cache_len={max_cache_len} < prompt+max_new_tokens="
                f"{total}: the cache would wrap and corrupt decoding")
        if total > cfg.max_position_embeddings:
            raise ValueError(
                f"prompt+max_new_tokens={total} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings} "
                f"(rope table would clamp positions)")
        from .. import flags as _flags
        kv_dtype = _flags.get_flag("kv_cache_dtype")
        if kv_dtype == "int8":
            from ..ops.kernels.serving import record_fallback
            record_fallback(
                "kv", "kv_int8_dense_cache",
                "contiguous KVCache has no quantized layout; "
                "cache stays at the compute dtype")
        cache = KVCache(cfg.num_hidden_layers, b,
                        max_cache_len or total,
                        cfg.num_key_value_heads,
                        cfg.hidden_size // cfg.num_attention_heads,
                        dtype=getattr(cfg, "dtype", "float32"))
        tokens = [input_ids]
        finished = np.zeros((b,), bool)
        with no_grad():
            # prefill: whole prompt in one pass
            logits = self(input_ids, cache=cache,
                          start_pos=Tensor(jnp.asarray(0, jnp.int32)))
            next_tok = call_op("sample_logits", logits[:, -1, :],
                               temperature=temperature, top_k=top_k,
                               top_p=top_p)
            for step in range(max_new_tokens):
                if eos_token_id is not None:
                    # finished rows emit eos forever (padding), never live
                    # samples
                    tok_np = np.where(finished, eos_token_id,
                                      np.asarray(next_tok._data))
                    finished |= tok_np == eos_token_id
                    next_tok = Tensor(jnp.asarray(tok_np, jnp.int32))
                tokens.append(next_tok.reshape([b, 1]))
                if eos_token_id is not None and finished.all():
                    break
                if step == max_new_tokens - 1:
                    break
                pos = Tensor(jnp.asarray(s + step, jnp.int32))
                logits = self(tokens[-1], cache=cache, start_pos=pos)
                next_tok = call_op("sample_logits", logits[:, -1, :],
                                   temperature=temperature, top_k=top_k,
                                   top_p=top_p)
        return call_op("concat", tokens, axis=1)
