"""Continuous batching over the paged-KV cache — the ragged serving loop.

Reference counterpart: the block_multi_head_attention serving flow
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)
driven by an insert/evict scheduler, modernised to the "Ragged Paged
Attention" TPU serving discipline (arXiv:2604.15464) with vLLM-lineage
chunked prefill and prefix caching:

- **One ragged step.** Every scheduler step packs up to ``token_budget``
  tokens — one per decoding row plus fixed-size prefill chunks of the
  admitted prompts — into ONE model invocation over the shared pool
  (`ragged_paged_attention`). The step's arrays have one of two static
  slot counts (`_geometries`: half the budget, and the budget), the
  smaller that holds what was packed, so XLA compiles the step twice,
  both on the engine's first step, and every mix of prefill/decode
  replays one of the two. Long prompts prefill in chunks interleaved
  with everyone else's decode tokens.
- **One step in flight.** A call of `step()` launches step N+1 from the
  host's picture of the rows and only then waits for step N's sampled
  tokens, so scheduling, packing and the uploads run while the device is
  busy. A decoding row's next id is gathered inside the step program from
  the samples of the launch before, the host's commit runs one step
  behind, and whatever reads or rewinds a row (preemption, copy-on-write)
  or has nothing left to launch commits the step in flight first.
- **Token-budget admission.** Requests queue until a row slot AND enough
  pool blocks for their worst case (prompt + max_new_tokens, minus the
  prefix-cached head) are free — the vLLM reservation rule, so decode
  never exhausts the pool mid-flight. Head-of-line starvation preempts
  the LIFO victim (recompute-on-resume).
- **Prefix cache.** Full prompt blocks are content-hashed (chained, so a
  block's identity covers its whole prefix) and published after being
  written; a later request whose prompt shares the head acquires the
  blocks by refcount instead of recomputing them — admission cost drops
  to the unshared suffix. Blocks with no active holder stay warm in an
  evictable FIFO until the allocator needs them; a write into a tracked
  block copy-on-writes to a fresh block first (defensive: chunked
  prefill only ever appends past the shared, block-aligned head).
- **Operability.** Scheduler state (queue depth, active rows, prefill
  backlog, free blocks, prefix-cache hit/share/eviction, preemptions)
  exports through the metrics registry — the Prometheus dumper makes
  the server observable under load — and per-request TTFT/TPOT land in
  histograms so the bench reports latency percentiles.
- **Schedule-independent sampling.** Each request samples through its
  own PRNG stream (`sample_logits_keyed`: engine seed folded with the
  request id, then the token index), so stochastic output is identical
  whatever the batching, chunking, or preemption schedule.

This is the only serving engine and the only consumer of `PagedKVCache`;
the tests' independent oracle is the dense `generate()` loop.
"""

from __future__ import annotations

import functools
import hashlib
import threading
import time
import weakref
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..jit import exec_store as _exec_store
from ..jit.api import _SWAP_LOCK, _aval, _collect_state, _swap_state
from ..observability import metrics as _metrics_mod
from ..observability import perf as _perf_mod
from ..observability import tracing as _tracing
from ..ops.dispatcher import call_op
from ..ops.kernels.pallas import ragged_paged_attention as _rpa
from .generation import (PagedKV, PagedKVCache, kv_pool_blocks,
                         layer_states)

__all__ = ["Request", "ContinuousBatchingEngine", "PrefixCache",
           "QueueFull"]


class QueueFull(RuntimeError):
    """Admission queue is at ``max_queue``: the server must shed load
    explicitly (HTTP 429 / retry-after) instead of buffering without
    bound — an unbounded `pending` deque turns overload into OOM.

    ``retry_after_hint`` (seconds, None when the engine has served no
    traffic yet) is the median observed queue wait — the engine's own
    estimate of when a slot opens, for the caller's backoff/Retry-After
    header instead of a guessed constant."""

    def __init__(self, msg: str,
                 retry_after_hint: Optional[float] = None):
        super().__init__(msg)
        self.retry_after_hint = retry_after_hint

_M = _metrics_mod.registry()
_M_STEPS = _M.counter(
    "serving.steps", "ragged scheduler steps executed")
# ops/dispatcher.py's count of dispatches, in which the step program counts
# each of its launches (`_StepProgram.launches` reads it for the step)
_M_LAUNCHES = _M.counter("dispatch.count")
_M_TRACES = _M.counter(
    "serving.step.traces",
    "times a ragged step program was traced (1 per model and geometry "
    "when healthy, all on the first step)")
_M_STEP_TOKENS = _M.counter(
    "serving.step_tokens", "packed tokens processed (prefill + decode)")
_M_STEP_SLOTS = _M.counter(
    "serving.step_slots",
    "token slots of the geometries the steps ran (step_tokens over this "
    "is the share of the model's rows that carried a token)")
_M_TOKEN_BLOCKS = _M.counter(
    "serving.attention.token_blocks",
    "(tile, kv block) pairs of one layer's ragged attention call whose tile "
    "holds one token, the one-token body's visits (a step's "
    "kv_token_blocks)")
_M_OVERLAPPED = _M.counter(
    "serving.pipeline.overlapped",
    "steps launched while the step before them was still in flight (over "
    "serving.steps: the share of steps whose host work hid behind the device)")
_M_DRAINS = _M.counter(
    "serving.pipeline.drains",
    "times the step in flight was committed with none launched behind it: "
    "speculation (the drafts need the committed tokens), before a "
    "preemption or a copy-on-write, and when no row had work left")
_M_DISCARDED = _M.counter(
    "serving.pipeline.discarded_tokens",
    "tokens sampled for a row whose request had ended (an EOS) while the "
    "row's next step was already launched; never emitted")
_M_GEN_TOKENS = _M.counter(
    "serving.generated_tokens", "tokens sampled and emitted to requests")
_M_PREFILL_TOKENS = _M.counter(
    "serving.prefill_tokens", "prompt tokens prefilled (chunked)")
_M_ADMITTED = _M.counter(
    "serving.admitted", "requests admitted to a row slot")
_M_FINISHED = _M.counter(
    "serving.finished", "requests completed (eos / max_new_tokens)")
_M_PREEMPTIONS = _M.counter(
    "serving.preemptions", "LIFO preemptions (head-of-line starvation)")
_M_QUEUE = _M.gauge(
    "serving.queue_depth", "requests waiting for admission")
_M_ACTIVE = _M.gauge(
    "serving.active_rows", "row slots occupied by live requests")
_M_BACKLOG = _M.gauge(
    "serving.prefill_backlog_tokens",
    "prompt tokens admitted but not yet prefilled")
_M_FREE = _M.gauge(
    "serving.free_blocks", "allocatable pool blocks (free + evictable)")
_M_PC_HIT = _M.counter(
    "serving.prefix_cache.hit_blocks", "prompt blocks served from cache")
_M_PC_MISS = _M.counter(
    "serving.prefix_cache.miss_blocks", "full prompt blocks recomputed")
_M_PC_SHARED = _M.counter(
    "serving.prefix_cache.shared_tokens",
    "prompt tokens whose KV was shared instead of recomputed")
_M_PC_EVICT = _M.counter(
    "serving.prefix_cache.evictions",
    "cached blocks reclaimed by the allocator")
_M_COW = _M.counter(
    "serving.cow_copies", "copy-on-write block copies before a shared write")
_M_TTFT = _M.histogram(
    "serving.ttft_seconds", "request arrival -> first emitted token")
_M_TPOT = _M.histogram(
    "serving.tpot_seconds", "mean inter-token time after the first token")
_M_QWAIT = _M.histogram(
    "serving.queue_wait_seconds", "request arrival -> row-slot admission")
_M_REJECTED = _M.counter(
    "serving.rejected", "requests rejected at intake (queue full)")
_M_KV_BPT = _M.gauge(
    "serving.kv.bytes_per_token",
    "HBM bytes one token's K+V occupies across all layers (int8 pool "
    "includes its f32 scale bytes) — the decode bandwidth denominator")
_M_KV_DEQ = _M.counter(
    "serving.kv.dequant_blocks",
    "pool blocks dequantized inside attention tile loads (int8 pool)")
_M_SPEC_PROP = _M.counter(
    "serving.spec.proposed", "draft tokens packed into verify rows")
_M_SPEC_ACC = _M.counter(
    "serving.spec.accepted", "draft tokens accepted by exact-match verify")
_M_SPEC_REJ = _M.counter(
    "serving.spec.rejected", "draft tokens rejected at verify")
_M_SPEC_ROWS = _M.counter(
    "serving.spec.verify_rows", "decode rows that carried draft tokens")
_M_STATE_BYTES = _M.gauge(
    "serving.state.bytes",
    "resident bytes of the row-state cache (layers that keep a fixed-size "
    "state a row; 0 for a model without any)")
_M_STATE_RESETS = _M.counter(
    "serving.state.resets",
    "segments that started at position 0, so from a zero row state "
    "(admission, resume after preemption, a reused row slot)")
_M_PC_SKIPPED = _M.counter(
    "serving.prefix.skipped_recurrent",
    "prefix-cache lookups not taken because the model keeps row state: a "
    "recurrent layer must see every token, so no block can stand in")

# per-tenant children of the admission counters, cached so the hot path
# pays one dict hit instead of the registry lock. Tenant cardinality is
# the caller's contract — these are billing/SLO attribution labels, not
# a per-request id.
_TENANT_COUNTERS: Dict[Tuple[str, str], Any] = {}


def _inc_tenant(name: str, tenant: Optional[str]) -> None:
    if tenant is None:
        return
    key = (name, tenant)
    c = _TENANT_COUNTERS.get(key)
    if c is None:
        c = _M.counter(name, labels={"tenant": tenant})
        _TENANT_COUNTERS[key] = c
    c.inc()


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [L] int32
    max_new_tokens: int
    out_tokens: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    done: bool = False
    admit_order: int = -1              # LIFO preemption victim choice
    preemptions: int = 0
    # -- ragged-engine occupancy state (reset on preemption) ---------------
    ctx: int = 0                       # tokens whose pool writes were launched
    in_flight: int = 0                 # tokens sampled by a launched step, not
                                       # yet committed to out_tokens
    target: int = 0                    # prefill target length
    full_seq: Optional[np.ndarray] = None
    block_hashes: List[bytes] = field(default_factory=list)
    key_data: Optional[np.ndarray] = None   # private sampling stream
    t_arrive: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    n_replayed: int = 0                # tokens emitted by a previous process
    tenant: Optional[str] = None       # labels the admission counters
    _registered_upto: int = 0          # prompt blocks published to the cache
    # -- tracing (observability/tracing.py): the ambient trace context at
    # intake plus perf_counter_ns edge stamps, so the engine records the
    # request's queue/prefill/decode phases as RETROACTIVE spans instead
    # of holding a span object open across scheduler steps
    trace_id: int = 0
    span_parent: int = 0
    t_arrive_ns: int = 0
    t_admit_ns: int = 0
    t_first_ns: int = 0


def _fold_in_host(key: np.ndarray, data: int) -> np.ndarray:
    """``jax.random.key_data(jax.random.fold_in(key, data))`` for a
    threefry2x32 key given as its two words, computed on the host. The
    device's version is a program and a transfer: issued from
    `add_request` it would wait for whatever step is in flight, and the
    device then idles through the next launch."""
    with np.errstate(over="ignore"):
        k0, k1 = np.uint32(key[0]), np.uint32(key[1])
        ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
        x0, x1 = ks[0], np.uint32(data) + ks[1]   # the count is (0, data)
        rounds = ((13, 15, 26, 6), (17, 29, 16, 24))
        for i in range(5):
            for r in rounds[i % 2]:
                x0 = x0 + x1
                x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
                x1 = x1 ^ x0
            x0 = x0 + ks[(i + 1) % 3]
            x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return np.array([x0, x1], np.uint32)


def _req_trace(req: "Request"):
    return (req.trace_id, req.span_parent) if req.trace_id else None


class PrefixCache:
    """Content-addressed sharing of full prompt blocks (vLLM lineage).

    A block's key is the CHAINED hash of its tokens and every token
    before it, so equal keys imply equal KV content. Refcounts track the
    active holders; blocks whose count drops to zero stay warm in an
    evictable FIFO (hash retained) until `evict_one` hands them back to
    the allocator. Registration is first-writer-wins: a concurrent
    identical prefill keeps its private copy, which the release path
    simply frees."""

    def __init__(self):
        self._map: Dict[bytes, int] = {}     # chain digest -> block id
        self._hash_of: Dict[int, bytes] = {}  # block id -> chain digest
        self._ref: Dict[int, int] = {}       # block id -> active holders
        self._evictable: "OrderedDict[int, None]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._map)

    def tracked(self, block: int) -> bool:
        return block in self._ref

    def ref(self, block: int) -> int:
        return self._ref.get(block, 0)

    @property
    def evictable(self) -> int:
        return len(self._evictable)

    def lookup(self, hashes: List[bytes]) -> List[int]:
        """Longest cached prefix: block ids for the leading hashes."""
        out = []
        for h in hashes:
            b = self._map.get(h)
            if b is None:
                break
            out.append(b)
        return out

    def acquire(self, block: int) -> None:
        self._ref[block] += 1
        self._evictable.pop(block, None)

    def register(self, h: bytes, block: int) -> bool:
        if h in self._map:
            return False
        self._map[h] = block
        self._hash_of[block] = h
        self._ref[block] = 1
        return True

    def release_block(self, block: int) -> bool:
        """Drop one hold. True when the block is cache-tracked (the
        caller must then NOT return it to the free list)."""
        if block not in self._ref:
            return False
        self._ref[block] -= 1
        if self._ref[block] <= 0:
            self._ref[block] = 0
            self._evictable[block] = None
        return True

    def evict_one(self) -> Optional[int]:
        """Reclaim the oldest zero-ref cached block for reuse."""
        if not self._evictable:
            return None
        block, _ = self._evictable.popitem(last=False)
        del self._map[self._hash_of.pop(block)]
        del self._ref[block]
        return block


class _RaggedView:
    """Cache facade for ONE ragged step: per-token write slots were
    precomputed by the scheduler (bulk block allocation, COW-guarded),
    and attention is the single ragged_paged_attention invocation over
    the pool — decode rows and prefill chunks in the same call.

    The view the engine hands to the model names the engine's
    `_StepProgram`: the model's forward runs as that one XLA program,
    which owns the pools for the call. ``update``, ``attend`` and the
    row-state methods run only inside the program's trace, on a view over
    tracers (``program`` None); a ragged step has no per-op path.

    A layer that keeps row state (`generation.RowState`) reads the step's
    ``segments`` and its own arrays through ``row_state`` and hands the
    updated ones back through ``set_row_state``.

    ``prev`` and ``src`` ride along for the program alone: the tokens the
    launch before this one sampled, still on the device, and for each
    packed token the lane of ``prev`` its id comes from (-1: the id the
    host packed stands)."""

    # the model's attention hands q and takes the output as [T, H*D] rows
    packed_rows = True

    def __init__(self, cache: PagedKVCache, slots: Tensor, tables: Tensor,
                 lens: Tensor, cu: Tensor,
                 program: Optional["_StepProgram"] = None,
                 prev: Optional[Tensor] = None, src: Optional[Tensor] = None):
        self._c = cache
        self._slots = slots
        self._tables = tables
        self._lens = lens
        self._cu = cu
        self._prev = prev
        self._src = src
        self.program = program
        self._once = {}

    def update(self, layer: int, k_new: Tensor, v_new: Tensor, pos):
        # named under the module that called: .../self_attn/serving.cache_write
        with jax.named_scope("serving.cache_write"):
            return self._c.write(layer, k_new, v_new, self._slots)

    def attend(self, layer: int, q: Tensor):
        """q[T, H*D], the step's packed rows, to the attention output in
        the same rows."""
        return call_op("ragged_paged_attention", q, *self._c.kv(layer),
                       self._tables, self._lens, self._cu,
                       **self._c.scale_kwargs(layer))

    def once(self, key, make):
        """``make()``, made for the first layer of the step that asks under
        ``key`` and handed to every layer after it: a value of the step's
        tokens that layers with the same ``key`` would each compute alike."""
        if key not in self._once:
            self._once[key] = make()
        return self._once[key]

    def segments(self) -> Tuple[Tensor, Tensor, Tensor]:
        """The step's rows as a recurrent layer needs them: ``cu_q_lens``,
        each row's index into the row-state cache (its own; the cache's
        last for a row with no token in the step) and the position of each
        row's first token (0: the segment starts from a zero state). Made
        inside the program from what every step uploads already."""
        def make():
            cu, lens = self._cu._data, self._lens._data
            qlen = cu[1:] - cu[:-1]
            rows = qlen.shape[0]
            slots = jnp.where(qlen > 0, jnp.arange(rows, dtype=jnp.int32),
                              rows)
            return self._cu, Tensor(slots), Tensor(lens - qlen)

        return self.once("segments", make)

    def row_state(self, layer: int, name: str) -> Tensor:
        return self._c.row(layer, name)

    def set_row_state(self, layer: int, name: str, value: Tensor) -> None:
        self._c.set_row(layer, name, value)


# model -> {(flags.version, cache spec): its `_ModelProgram`}: engines over
# one model (the replicas of a fleet, a relaunch, a warm-up engine) share
# the traced program and its executables, one for each geometry they ask
# for. Keyed on flags.version like the dispatcher's cache, so a store
# attached later wraps anew
_STEP_PROGRAMS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _geometries(token_budget: int, max_batch: int,
                spec_k: int) -> Tuple[int, ...]:
    """The slot counts a step's token-sized arrays may have, ascending; a
    step runs the first that holds what it packed. Half the budget is one
    only where a full decode-or-verify step fits it, so the choice is
    between "rows alone, or a short chunk beside them" and "a prompt's
    chunks". (The ragged kernel takes any count: its last q tile may be
    partial.)"""
    small = token_budget // 2
    if small >= max_batch * (spec_k + 1):
        return (small, token_budget)
    return (token_budget,)


class _ModelProgram:
    """A model's step program (`_step_program`): the jitted function, the
    tensors whose buffers are its first argument, and the executables made
    from it ahead of any call, one for each set of argument shapes."""

    def __init__(self, jit, state: List[Tensor]):
        self.jit = jit
        self.state = state
        self._executables: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()

    def executable(self, avals):
        """The program compiled for ``avals`` (`_StepProgram._args`' order):
        traced, lowered and compiled, or loaded from an attached exec
        store, once; nothing runs and nothing is donated."""
        key = tuple(jax.tree.leaves(avals))
        with self._lock:        # replicas' threads ask for the same one
            exe = self._executables.get(key)
            if exe is None:
                # with an exec store attached the jit resolves through it
                # (a relaunching replica loads from disk); plain jax.jit
                # compiles ahead of time
                exe = (self.jit.executable(*avals)
                       if isinstance(self.jit, _exec_store.PersistentJit)
                       else self.jit.lower(*avals).compile())
                self._executables[key] = exe
                if hasattr(exe, "as_text"):
                    # once an executable; one an exec store loaded without
                    # its text names nothing (tracing.device_ops)
                    _tracing.note_program("serving_step", exe)
            return exe


def _step_program(model, spec: Tuple) -> _ModelProgram:
    """The ragged step's model call as ONE XLA program:

        (parameters and buffers, the pools, ids, pos, slots, tables, lens,
         cu, prev, src)  ->  (logits [1, slots, V], the same pools)

    Built by tracing the model's own forward (its ops run inline on
    tracers through the dispatcher) over a `_RaggedView` of tracers, with
    every pool and row-state array (``spec``: the cache's) donated and
    returned: the pool writes scatter in place, and one launch replaces
    the forward's per-op launches. A packed token whose ``src`` is not
    negative takes its id from that lane of ``prev``, the tokens the
    launch before sampled: a decoding row's next id never visits the host
    before it is fed."""
    from .. import flags
    from ..autograd.engine import no_grad
    programs = _STEP_PROGRAMS.setdefault(model, {})
    key = (flags.version, spec)
    if key in programs:
        return programs[key]
    params, buffers = _collect_state(model)
    state = params + buffers
    model_ref = weakref.ref(model)

    def serving_step(state_arrays, pools, ids, pos, slots, tables, lens, cu,
                     prev, src):
        _M_TRACES.inc()
        before = _M_LAUNCHES.value
        with jax.named_scope("serving.gather_ids"):
            ids = jnp.where(src >= 0, prev[jnp.maximum(src, 0)], ids)
        over = PagedKVCache.over(spec, pools)
        view = _RaggedView(over, Tensor(slots), Tensor(tables),
                           Tensor(lens), Tensor(cu))
        with _swap_state(state, list(state_arrays)), no_grad():
            logits = model_ref()(Tensor(ids), cache=view,
                                 start_pos=Tensor(pos))
        _StepProgram.traced_ops += _M_LAUNCHES.value - before
        return logits._data, over.pools()

    # with an exec store attached (a relaunching replica) the compiled
    # program is loaded from disk; the trace still runs once
    jit = _exec_store.persistent(
        jax.jit(serving_step, donate_argnums=(1,)), "serving",
        label="serving_step")
    programs[key] = _ModelProgram(jit, state)
    return programs[key]


class _StepProgram:
    """One engine's use of its model's step program (`_step_program`):
    the program owns the engine's pools across a call. The arrays it was
    given are gone when it returns (where the backend donates), and the
    cache is rebound to the ones it gave back before anyone else can read
    them. Every shape but the slot count is the engine's static one, so
    there is one trace and one executable for each of ``geometries``, all
    of them made on the first call, and every later call runs one of
    them."""

    # dispatches counted while a program was traced: they launched nothing
    traced_ops = 0

    def __init__(self, cache: PagedKVCache, geometries: Tuple[int, ...]):
        self._cache = cache
        self._geometries = geometries
        self._executables: Dict[int, Any] = {}     # slots -> executable

    @classmethod
    def launches(cls) -> int:
        """``dispatch.count`` less the dispatches that ran on tracers while
        a program was traced: a clock of launches to take differences of."""
        return _M_LAUNCHES.value - cls.traced_ops

    @property
    def cold(self) -> bool:
        """Before the first call: no geometry has its executable yet."""
        return not self._executables

    def _args(self, state: List[Tensor], ids: Tensor, pos: Tensor,
              view: _RaggedView):
        with _SWAP_LOCK:    # another replica's thread may be tracing the model
            state = tuple(t._data for t in state)
        return (state, self._cache.pools(),
                ids._data, pos._data, view._slots._data, view._tables._data,
                view._lens._data, view._cu._data, view._prev._data,
                view._src._data)

    def __call__(self, model, ids: Tensor, pos: Tensor,
                 view: _RaggedView) -> Tensor:
        program = _step_program(model, self._cache.spec)
        args = self._args(program.state, ids, pos, view)
        if self.cold:
            # every geometry before the first step returns: a server's
            # first 300-token prompt must not be the one that compiles
            state, pools, ids_a, pos_a, slots_a, *rows, src_a = jax.tree.map(
                _aval, args)
            for n in self._geometries:
                self._executables[n] = program.executable((
                    state, pools, ids_a.update(shape=(1, n)),
                    pos_a.update(shape=(1, n)), slots_a.update(shape=(n,)),
                    *rows, src_a.update(shape=(n,))))
        _M_LAUNCHES.inc()
        logits, pools = self._executables[ids.shape[1]](*args)
        self._cache.set_pools(pools)
        return Tensor(logits)

    def lower(self, model, args):
        """The program lowered for ``args`` (arrays or their shapes, as
        `_args` orders them); nothing runs and nothing is donated.
        ``lower(...).compile()`` has its text, cost and memory analysis."""
        return _step_program(model, self._cache.spec).jit.lower(*args)

    def compiled(self, slots: int):
        """The executable of the ``slots``-slot geometry, with its text,
        cost and memory analysis; None before the first call."""
        return self._executables.get(slots)


# kinds of entry in a launched step's commit plan: a prefill chunk, the chunk
# that ends a prompt and samples its first token, a decode or verify row
_CHUNK, _FIRST, _DECODE = range(3)


@dataclass(slots=True)
class _Launched:
    """A ragged step that was enqueued and not yet committed."""
    nxt: Tensor                 # sampled tokens: on the device until commit
    post: List[Tuple]           # the commit plan: `_CHUNK` / `_FIRST` /
    #                             `_DECODE` entries naming their requests
    drafts: Dict[int, np.ndarray]   # what its verify rows carried
    dequant_blocks: int         # attended blocks of an int8 pool, a layer
    t0_ns: Optional[int]        # dispatch began
    td_ns: Optional[int]        # its last launch returned
    perf_entry: Any             # the perf ledger's row, and whether this
    perf_sample: Optional[bool]     # call's device time is sampled


class ContinuousBatchingEngine:
    """Ragged continuous batching: chunked prefill + decode in one
    compiled step over the paged pool, with prefix-cache block sharing.

    ``token_budget`` bounds the packed token count per step; it must
    cover at least one token per row (``max_batch``). A step's arrays
    have the smallest slot count of ``geometries`` (`_geometries`: half
    the budget where every row's tokens fit it, and the budget) that
    holds what the scheduler packed: static shapes -> two executables,
    both made on the first step, so 7 or 64 decode rows do not pay for
    the matmuls of a prompt's chunks. ``prefill_chunk`` is the fixed
    chunk size long prompts are sliced into, so a long admission never
    stalls decode for more than one chunk's worth of compute.

    The pools are ``self.cache``'s. During a step's model call they
    belong to the engine's `_StepProgram`, which donates them and rebinds
    the cache to what it returns; between steps every reader
    (copy-on-write, preemption, the warm-cache snapshot) takes
    ``cache.k[l]._data`` anew and keeps no pool array across a step."""

    def __init__(self, model, max_batch: int,
                 num_blocks: Optional[int] = None,
                 block_size: int = 64,
                 max_blocks_per_seq: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, preempt_after: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 enable_prefix_cache: bool = True, seed: int = 0,
                 max_queue: Optional[int] = None,
                 on_finish=None, kv_dtype: Optional[str] = None,
                 speculative_k: Optional[int] = None,
                 draft_proposer=None,
                 kv_pool_bytes: Optional[int] = None):
        from .. import flags as _flags
        cfg = model.config
        self.model = model
        self.eos = eos_token_id
        self.sampling = dict(temperature=temperature, top_k=top_k,
                             top_p=top_p)
        if kv_dtype is None:
            kv_dtype = _flags.get_flag("kv_cache_dtype")
        # what each layer keeps between a row's tokens, as the model
        # declares it: paged K and V, or a fixed-size state a row
        layers = layer_states(model)
        paged = [l for l in layers if isinstance(l, PagedKV)]
        if num_blocks is None:
            # pool sized in BYTES: the admission math below is all in
            # blocks, so the storage regime's capacity win (int8 buys
            # ~2x blocks per byte) flows straight into occupancy
            if kv_pool_bytes is None:
                raise ValueError(
                    "pass num_blocks or kv_pool_bytes to size the pool")
            num_blocks = kv_pool_blocks(
                kv_pool_bytes, block_size, paged[0].num_kv_heads,
                paged[0].head_dim, len(paged),
                dtype=getattr(cfg, "dtype", "float32"), kv_dtype=kv_dtype)
        mb = max_blocks_per_seq or (
            -(-cfg.max_position_embeddings // block_size))
        self.cache = PagedKVCache(
            len(layers), max_batch, num_blocks=num_blocks,
            block_size=block_size, max_blocks_per_seq=mb,
            dtype=getattr(cfg, "dtype", "float32"), kv_dtype=kv_dtype,
            layers=layers)
        _M_KV_BPT.set(self.cache.kv_bytes_per_token())
        _M_STATE_BYTES.set(self.cache.row_state_bytes())
        # a model with row state changes three of the scheduler's rules,
        # each because a recurrent layer must see every token in order: no
        # prefix-cache hit is taken, a preempted row re-prefills from
        # position 0 (where the kernels start from a zero state), and a
        # draft cannot be taken back out of a recurrence
        self.recurrent = bool(self.cache.row_state)
        # speculative decoding: K draft tokens per decode row, verified
        # as one q_len=K+1 ragged row out of the leftover token budget.
        # Acceptance is EXACT-MATCH against the row's keyed sample at
        # each stream position, so spec-on output is byte-identical to
        # spec-off at any temperature — schedule independence and
        # replay determinism hold with speculation on for free
        if speculative_k is None:
            speculative_k = int(_flags.get_flag("speculative_k"))
        self.spec_k = max(0, int(speculative_k))
        if self.spec_k and self.recurrent:
            raise ValueError(
                f"speculative_k={self.spec_k} with a model that keeps row "
                f"state: a rejected draft token cannot be taken back out "
                f"of a recurrent layer's state")
        if self.spec_k and draft_proposer is None:
            from .speculative import NGramProposer
            draft_proposer = NGramProposer()
        self.proposer = draft_proposer
        self.block_size = block_size
        self.max_batch = max_batch
        self.prefill_chunk = prefill_chunk or block_size
        self.token_budget = token_budget or (max_batch + self.prefill_chunk)
        if self.token_budget < max_batch:
            raise ValueError(
                f"token_budget={self.token_budget} < max_batch={max_batch}:"
                f" decode rows alone would not fit one step")
        self.geometries = _geometries(self.token_budget, max_batch,
                                      self.spec_k)
        self._program = _StepProgram(self.cache, self.geometries)
        self.enable_prefix_cache = enable_prefix_cache
        # one reserved block absorbs the writes of step-padding tokens
        self._trash_slot = self.cache._free.pop() * block_size
        self._total_blocks = num_blocks - 1
        self._pc = PrefixCache()
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.pending: deque[Request] = deque()
        self.results: Dict[int, Request] = {}
        # one step in flight: the launch whose tokens the host has not
        # read yet (`_Launched`; None when the pipeline is empty), the
        # tokens the last launch sampled, which the next one's decoding
        # rows take their ids from on the device (zeros of the same shape
        # before any launch, so the first call makes the executables every
        # later call runs), and the requests finished by commits that
        # `step()` has not returned yet
        self._inflight: Optional[_Launched] = None
        self._prev = jnp.zeros((max_batch * (self.spec_k + 1),), jnp.int32)
        self._ready_ns = 0
        self._finished: List[Request] = []
        self._next_rid = 0
        self._admit_seq = 0
        self.steps = 0
        # head-of-line fairness: preempt the LIFO victim when the queue
        # head has starved this many steps (None = never preempt)
        self.preempt_after = preempt_after
        self._head_waited = 0
        self.preempt_count = 0
        # per-request private sampling streams: engine seed -> fold(rid)
        # -> fold(token index), so stochastic output never depends on the
        # batching/chunking/preemption schedule (or the global generator).
        # threefry keys: rbg draws depend on the vmap row position (see
        # sample_logits_keyed), which would leak the slot assignment back
        # into the output
        self._base_key_data = np.asarray(jax.random.key_data(
            jax.random.key(seed, impl="threefry2x32")))
        self._key_w = self._base_key_data.shape[-1]
        self.seed = seed
        # bounded intake (None = legacy unbounded) + finished hand-off:
        # with `on_finish` set, completed Requests are passed to the
        # callback and RETIRED from `results`, so a long-running server
        # does not grow host memory with every request it ever served
        self.max_queue = max_queue
        self.on_finish = on_finish
        # drain hook (serving/resilience): a paused engine keeps
        # stepping its in-flight rows but admits nothing new
        self.admission_paused = False
        # finish signal for cross-thread pollers: step() notifies after
        # the on_finish dispatch, so a blocking pop_result(timeout=)
        # wakes instead of busy-spinning on an idle engine
        self.finish_cv = threading.Condition()

    # -- request intake ------------------------------------------------------
    def add_request(self, prompt, max_new_tokens: int = 32, *,
                    rid: Optional[int] = None,
                    out_tokens: Optional[List[int]] = None,
                    tenant: Optional[str] = None) -> int:
        """Queue a request. ``rid``/``out_tokens`` are the journal-replay
        re-admission hooks (serving/resilience): a recovered request must
        keep its ORIGINAL rid (the sampling stream folds it — a fresh rid
        would draw a different continuation) and resumes from its already
        committed output tokens exactly like a preempted row
        (recompute-on-resume re-derives the lost KV by prefill).
        ``tenant`` additionally counts the admission/rejection on a
        tenant-labeled child of the serving counters."""
        if rid is None:
            # the queue bound governs NEW traffic only: a journal-replay
            # re-admission (rid given) was already durably acked by a
            # previous incarnation — bouncing it here would turn a
            # relaunch into a permanent QueueFull crash loop whenever
            # more than max_queue requests were in flight at the kill
            if (self.max_queue is not None
                    and len(self.pending) >= self.max_queue):
                _M_REJECTED.inc()
                _inc_tenant("serving.rejected", tenant)
                raise QueueFull(
                    f"admission queue is full ({len(self.pending)}/"
                    f"{self.max_queue} pending): shed load or retry later",
                    retry_after_hint=_M_QWAIT.quantile(0.5))
            rid = self._next_rid
        elif rid in self.results:
            raise ValueError(f"rid {rid} already journaled to this engine")
        self._next_rid = max(self._next_rid, rid + 1)
        req = Request(rid, np.asarray(prompt, np.int32).reshape(-1),
                      max_new_tokens, tenant=tenant)
        if out_tokens:
            if len(out_tokens) >= max_new_tokens:
                raise ValueError(
                    f"resumed request {rid} already has {len(out_tokens)} "
                    f"of max_new_tokens={max_new_tokens} tokens: nothing "
                    f"left to generate (load it from the journal instead)")
            req.out_tokens = [int(t) for t in out_tokens]
            # replayed tokens were emitted by a previous incarnation —
            # this process must not observe their TTFT or TPOT
            req.t_first = time.time()
            req.n_replayed = len(req.out_tokens)
        if len(req.prompt) == 0:
            raise ValueError("empty prompt: there is no token to prefill, "
                             "so no logits exist to sample from")
        mb = self.cache.block_tables.shape[1]
        if self._blocks_needed(req) > min(self._total_blocks, mb):
            raise ValueError(
                f"request needs {self._blocks_needed(req)} blocks but the "
                f"pool has {self._total_blocks} and a sequence may hold at "
                f"most max_blocks_per_seq={mb}: it could never be admitted")
        req.t_arrive = time.time()
        req.t_arrive_ns = _tracing.now_ns()
        tc = _tracing.current()
        if tc is not None:
            req.trace_id, req.span_parent = tc
        # sha256 chain digests, NOT builtin hash(): a 64-bit hash()
        # collision would silently serve another request's KV blocks
        # (and salted-hash keys are constructible when the seed leaks) —
        # the same hardening vLLM applied to this exact design
        h = b""
        for bi in range(len(req.prompt) // self.block_size):
            h = hashlib.sha256(
                h + req.prompt[bi * self.block_size:
                               (bi + 1) * self.block_size].tobytes()
            ).digest()
            req.block_hashes.append(h)
        req.key_data = _fold_in_host(self._base_key_data, rid)
        self.pending.append(req)
        self.results[rid] = req
        return rid

    def _blocks_needed(self, req: Request) -> int:
        return -(-(len(req.prompt) + req.max_new_tokens)
                 // self.block_size)

    # -- pool accounting -----------------------------------------------------
    def _free_effective(self) -> int:
        """Allocatable blocks: the free list plus warm cached blocks with
        no active holder (the allocator may evict those)."""
        return len(self.cache._free) + self._pc.evictable

    def _outstanding_reservation(self) -> int:
        """Blocks the ACTIVE sequences may still claim: their worst case
        minus what they already hold. Admission must leave room for this,
        or decode could exhaust the pool mid-flight."""
        return sum(self._blocks_needed(r)
                   - int(self.cache._allocated[r.slot])
                   for r in self.slots if r is not None)

    def _alloc_block(self) -> int:
        if self.cache._free:
            return self.cache._free.pop()
        blk = self._pc.evict_one()
        if blk is None:
            raise RuntimeError("PagedKVCache: block pool exhausted")
        _M_PC_EVICT.inc()
        return blk

    def _ensure_writable(self, i: int, blk_idx: int) -> None:
        """Copy-on-write: a write into a cache-tracked block would mutate
        content other holders (or the cache's hash) still reference —
        copy it to a fresh private block first. Defensive: the scheduler
        only appends past the block-aligned shared head, so this fires
        only if sharing and write ranges ever overlap."""
        blk = int(self.cache.block_tables[i, blk_idx])
        if not self._pc.tracked(blk):
            return
        fresh = self._alloc_block()
        # one-block scatter through the cached paged_cache_write
        # executable (the engine's normal write path — compiled once,
        # reused for every COW), not an eager full-pool .at[].set
        bs = self.cache.block_size
        slots = Tensor(jnp.asarray(fresh * bs + np.arange(bs), jnp.int32))
        # int8 pool: the per-token-slot scale rows move with their block
        # (paged_cache_write is shape-generic over the trailing dims, so
        # the [NB,BS,KV] scale pools ride the same one-block scatter
        # executable). Each pool is read here, after the last step
        # rebound it, and replaced by the write's result
        for pool in self.cache.paged_lists():
            for layer in range(self.cache.num_layers):
                rows = Tensor(pool[layer]._data[blk][None])  # [1,BS,...]
                pool[layer] = call_op("paged_cache_write", pool[layer],
                                      rows, slots)
        self.cache.block_tables[i, blk_idx] = fresh
        self._pc.release_block(blk)
        _M_COW.inc()

    def _write_slots(self, i: int, pos0: int, n: int) -> np.ndarray:
        if n > 0 and pos0 % self.block_size:
            self._ensure_writable(i, pos0 // self.block_size)
        return self.cache.alloc_slots(i, pos0, n, self._alloc_block)

    # -- admission -----------------------------------------------------------
    def _admit(self):
        if self.admission_paused:
            return
        for i in range(self.max_batch):
            if not self.pending:
                return
            if self.slots[i] is not None:
                continue
            req = self.pending[0]
            full = (np.concatenate([req.prompt,
                                    np.asarray(req.out_tokens[:-1],
                                               np.int32)])
                    if req.out_tokens else req.prompt)
            target = len(full)
            hits = []
            if self.enable_prefix_cache and self.recurrent:
                _M_PC_SKIPPED.inc()
            elif self.enable_prefix_cache:
                hits = self._pc.lookup(req.block_hashes)
            # never share the whole target: the last token must be
            # recomputed so its logits exist to sample from (and a
            # resumed row needs a well-formed write position)
            n_use = min(len(hits), max(0, (target - 1) // self.block_size))
            # shared blocks with no active holder leave the evictable set,
            # so they consume allocatable headroom exactly like fresh ones
            evict_take = sum(1 for b in hits[:n_use]
                             if self._pc.ref(b) == 0)
            need = self._blocks_needed(req) - n_use + evict_take
            if need > self._free_effective() - self._outstanding_reservation():
                return                 # reservation: wait for reclaims
            self.pending.popleft()
            self._head_waited = 0
            if req.admit_order == -1:
                # first admission only: a preemption re-admission's
                # arrival-to-now span includes on-device decode
                # residency, which is not queue wait
                _M_QWAIT.observe(time.time() - req.t_arrive)
                req.t_admit_ns = _tracing.now_ns()
                _tracing.record_span(
                    "serving.queue", req.t_arrive_ns, req.t_admit_ns,
                    trace=_req_trace(req), attrs={"rid": req.rid})
            req.slot = i
            req.admit_order = self._admit_seq
            self._admit_seq += 1
            self.slots[i] = req
            req.full_seq = full
            req.target = target
            req._registered_upto = n_use   # shared head: already published
            for bi in range(n_use):
                self._pc.acquire(hits[bi])
                self.cache.block_tables[i, bi] = hits[bi]
            self.cache._allocated[i] = n_use
            req.ctx = n_use * self.block_size
            _M_ADMITTED.inc()
            _inc_tenant("serving.admitted", req.tenant)
            if n_use:
                _M_PC_HIT.inc(n_use)
                _M_PC_SHARED.inc(n_use * self.block_size)
            _M_PC_MISS.inc(max(0, len(req.prompt) // self.block_size
                               - n_use))
            # n_use is capped at (target-1)//block_size, so ctx < target
            # here always: every admission prefills at least one token
            # (a resumed request then decodes from its last emitted token)

    # -- lifecycle -----------------------------------------------------------
    @property
    def num_active(self) -> int:
        return sum(1 for r in self.slots if r is not None)

    def _release_slot(self, i: int):
        used = int(self.cache._allocated[i])
        for blk in self.cache.block_tables[i, :used]:
            blk = int(blk)
            if not self._pc.release_block(blk):
                self.cache._free.append(blk)
        self.cache.block_tables[i, :] = 0
        self.cache._allocated[i] = 0
        self.slots[i] = None

    def _preempt_lifo(self):
        """Evict the most-recently-admitted sequence (vLLM's default
        victim): reclaim its blocks now, requeue it right behind the
        starved head for recompute-on-resume (its private sampling
        stream makes the resumed output identical). The step in flight
        still moves the victim's row, and its tokens belong to the prefix
        the victim resumes from: it is committed first."""
        self._drain()
        victim = max((r for r in self.slots if r is not None),
                     key=lambda r: r.admit_order, default=None)
        if victim is None:
            return
        self._release_slot(victim.slot)
        victim.slot = None
        victim.ctx = 0
        victim.full_seq = None      # rebuilt at re-admission
        victim.preemptions += 1
        self.preempt_count += 1
        _M_PREEMPTIONS.inc()
        _tracing.instant("serving.preempt", trace=_req_trace(victim),
                         attrs={"rid": victim.rid,
                                "preemptions": victim.preemptions})
        self.pending.insert(1, victim)  # right behind the starved head

    def _register_blocks(self, req: Request, i: int, new_ctx: int):
        """Publish freshly-completed FULL prompt blocks to the prefix
        cache (never the recomputed tail of a resumed request)."""
        if not self.enable_prefix_cache or self.recurrent:
            return
        hi = min(new_ctx, len(req.prompt)) // self.block_size
        for bi in range(req._registered_upto, hi):
            self._pc.register(req.block_hashes[bi],
                              int(self.cache.block_tables[i, bi]))
        req._registered_upto = max(req._registered_upto, hi)

    def _append_token(self, req: Request, i: int, tok: int, now: float,
                      finished: List[Request]):
        req.out_tokens.append(tok)
        _M_GEN_TOKENS.inc()
        if req.t_first is None:
            req.t_first = now
            _M_TTFT.observe(now - req.t_arrive)
            req.t_first_ns = _tracing.now_ns()
            # slot admission -> first token: with serving.queue before it
            # and jit.compile/serving.step beside it, TTFT decomposes
            # into queue vs compile vs kernel time on one timeline
            _tracing.record_span(
                "serving.prefill",
                req.t_admit_ns or req.t_arrive_ns, req.t_first_ns,
                trace=_req_trace(req), attrs={"rid": req.rid})
            _tracing.instant("serving.first_token", trace=_req_trace(req),
                             attrs={"rid": req.rid})
        if (len(req.out_tokens) >= req.max_new_tokens
                or (self.eos is not None and tok == self.eos)):
            req.done = True
            req.t_done = now
            # resumed rows skip TPOT like they skip TTFT: t_first is the
            # re-admission time and part of the count was emitted by a
            # dead process, so the quotient measures neither incarnation
            if len(req.out_tokens) > 1 and req.n_replayed == 0:
                _M_TPOT.observe((now - req.t_first)
                                / (len(req.out_tokens) - 1))
            self._release_slot(i)
            req.slot = None
            # admission-scoped prefill buffer: a long-running server keeps
            # every finished Request in self.results (out_tokens are the
            # result), so drop the prompt+generated copy with it
            req.full_seq = None
            _M_FINISHED.inc()
            _tracing.record_span(
                "serving.decode",
                req.t_first_ns or req.t_admit_ns or req.t_arrive_ns,
                _tracing.now_ns(), trace=_req_trace(req),
                attrs={"rid": req.rid, "tokens": len(req.out_tokens)})
            _tracing.instant("serving.finish", trace=_req_trace(req),
                             attrs={"rid": req.rid})
            finished.append(req)

    # -- the ragged step -----------------------------------------------------
    def step(self) -> List[Request]:
        """One call of the serving loop: LAUNCH the next ragged step, then
        COMMIT the one the call before launched, so the host's scheduling,
        packing and uploads run while the device is still busy with the
        step before.

        *Launch*: admit, then pack ONE mixed prefill+decode batch (a token
        for every decoding row plus prefill chunks up to the token budget)
        and enqueue its single compiled model invocation and its sampling.
        The host's picture moves here: ``req.ctx`` by what was packed, and
        ``req.in_flight`` counts the sampled token the host has not seen; a
        decoding row's next id is taken on the device from the tokens the
        previous launch sampled. A row whose last token is in flight is not
        packed again.

        *Commit*: wait for a launched step's tokens and hand them to their
        requests (``out_tokens``, first-token and finish times, the row's
        release, the prefix cache's new blocks). A request that ended
        unforeseen (``eos_token_id``) may have been launched once more: that
        token is dropped and counted.

        The step in flight is committed in the call that launched it (the
        pipeline *drains*) when nothing could be launched behind it: with
        speculation on (the drafts and the accepted count need the committed
        tokens, so every call is launch-then-commit of the same step), and
        when no row has a chunk or a token left to schedule, so a loop that
        steps until its requests are done sees the last one finish in the
        call that launched its last step. It is committed ahead of the
        launch where the host is about to read or rewind a row: before a
        preemption and before a copy-on-write.

        Returns the requests that finished in the steps this call
        committed."""
        # the host's phases, each a live span on the thread's timeline
        # (untraced: one ragged step serves many requests) and, under a
        # jax.profiler trace, an annotation on the device's clock: admit,
        # schedule, pack and dispatch of the step this call launches, sync
        # and commit of what it commits. All six carry the call's number
        n_step = self.steps + 1
        with _tracing.start_span("serving.step.admit",
                                 trace=_tracing.UNTRACED,
                                 attrs={"step": n_step}):
            self._admit()
            if self.pending and self.preempt_after is not None \
                    and not self.admission_paused:
                self._head_waited += 1
                if self._head_waited > self.preempt_after:
                    self._preempt_lifo()
                    self._head_waited = 0
                    self._admit()
            _M_QUEUE.set(len(self.pending))
            _M_ACTIVE.set(self.num_active)
            _M_BACKLOG.set(sum(r.target - r.ctx for r in self.slots
                               if r is not None and r.ctx < r.target))
            _M_FREE.set(self._free_effective())
        if self._inflight is not None and self._copy_due():
            self._drain(n_step)
        if self._work_left():
            launched = self._launch(n_step)
            due = []
            if self._inflight is not None:
                _M_OVERLAPPED.inc()
                due.append(self._inflight)
            self._inflight = launched
            if self.spec_k or not self._work_left():
                _M_DRAINS.inc()
                due.append(launched)
                self._inflight = None
            self._settle(due, n_step)
        if self._inflight is not None and not self._work_left():
            # no launch will come to carry the step in flight out: an EOS
            # ended the last row that had work
            self._drain(n_step)
        finished, self._finished = self._finished, []
        return finished

    def _schedulable(self, req: Request) -> bool:
        """A chunk of the prompt or a token left to launch. The token count
        is the launched one: a row whose last token is in flight has none."""
        return (req.ctx < req.target or
                len(req.out_tokens) + req.in_flight < req.max_new_tokens)

    def _work_left(self) -> bool:
        return any(r is not None and self._schedulable(r)
                   for r in self.slots)

    def _copy_due(self) -> bool:
        """Whether the next pack would copy a block before it writes
        (`_ensure_writable`): a row about to append into a block the
        prefix cache tracks."""
        if not len(self._pc):
            return False
        bs, tables = self.block_size, self.cache.block_tables
        return any(r is not None and r.ctx % bs and self._schedulable(r)
                   and self._pc.tracked(int(tables[i, r.ctx // bs]))
                   for i, r in enumerate(self.slots))

    def _drain(self, n_step: Optional[int] = None) -> None:
        """Commit the step in flight, if any, with none launched behind it
        (``n_step``: the call it happens in; the next one's by default)."""
        if self._inflight is not None:
            launched, self._inflight = self._inflight, None
            _M_DRAINS.inc()
            self._settle([launched], n_step or self.steps + 1)

    def _launch(self, n_step: int) -> "_Launched":
        """Schedule, pack and enqueue one ragged step from the host's
        picture of the rows; returns what its commit needs."""
        from ..autograd.engine import no_grad

        with _tracing.start_span("serving.step.schedule",
                                 trace=_tracing.UNTRACED,
                                 attrs={"step": n_step}) as sp:
            B, R, bs = self.token_budget, self.max_batch, self.block_size
            # fixed-size prefill chunks, round-robin by admission order, into
            # the budget left after every decoding row's token
            decode_rows = [i for i, r in enumerate(self.slots)
                           if r is not None and r.ctx >= r.target
                           and self._schedulable(r)]
            prefill_rows = sorted(
                (i for i, r in enumerate(self.slots)
                 if r is not None and r.ctx < r.target),
                key=lambda i: self.slots[i].admit_order)
            grants = dict.fromkeys(prefill_rows, 0)
            left = B - len(decode_rows)
            while left > 0:
                gave = False
                for i in prefill_rows:
                    req = self.slots[i]
                    g = min(self.prefill_chunk,
                            req.target - req.ctx - grants[i], left)
                    if g > 0:
                        grants[i] += g
                        left -= g
                        gave = True
                    if left <= 0:
                        break
                if not gave:
                    break

            # speculative drafts out of the LEFTOVER budget: each decode row
            # may carry up to spec_k draft tokens, turning its q_len=1 row
            # into a q_len=1+K' verify row (a prefill-chunk shape the step
            # executable already compiles for). The emission cap keeps
            # write positions inside the admission-time worst case, so the
            # block reservation math is untouched by speculation.
            drafts: Dict[int, np.ndarray] = {}
            if self.spec_k and left > 0:
                for i in decode_rows:
                    req = self.slots[i]
                    cap = min(self.spec_k,
                              req.max_new_tokens - len(req.out_tokens) - 1,
                              left)
                    if cap <= 0:
                        continue
                    # proposal depends ONLY on this request's committed
                    # tokens — never batch composition — so speculative
                    # output stays schedule-independent
                    hist = np.concatenate(
                        [req.prompt, np.asarray(req.out_tokens, np.int32)])
                    d = self.proposer.propose(hist, cap)
                    if len(d):
                        drafts[i] = np.asarray(d, np.int32)
                        left -= len(d)
                    if left <= 0:
                        break
            sp.set(decode_rows=len(decode_rows),
                   prefill_rows=len(prefill_rows),
                   granted=sum(grants.values()))
            # what the step packs (decode rows, drafts, grants) is known
            # here: its arrays get the smallest geometry that holds it
            T = next(n for n in self.geometries if B - left <= n)

        with _tracing.start_span("serving.step.pack",
                                 trace=_tracing.UNTRACED,
                                 attrs={"step": n_step}):
            # L sample lanes per row: lane j of a verify row samples stream
            # position len(out)+j from the logits of packed token t+j. With
            # spec off L=1 and the arrays are exactly the legacy geometry.
            L = self.spec_k + 1
            ids = np.zeros((T,), np.int32)
            # the lane of the previous launch's samples a token's id comes
            # from, on the device; -1 where the host's id stands
            src = np.full((T,), -1, np.int32)
            pos = np.zeros((T,), np.int32)
            # int32 on the host: handed over as int64 the upload would
            # convert on the device, in a program of its own a geometry
            slot_vec = np.full((T,), self._trash_slot, np.int32)
            qlen = np.zeros((R,), np.int32)
            lens = np.zeros((R,), np.int32)
            sample_idx = np.zeros((R * L,), np.int32)
            stream_pos = np.zeros((R * L,), np.int32)
            keys = np.zeros((R * L, self._key_w), np.uint32)
            # the commit plan: (row, request, tokens packed, ctx after them,
            # kind). The request and not only its row: by the commit the
            # row may be another's
            post = []
            dequant_blocks = 0
            t = 0
            for i in range(R):
                req = self.slots[i]
                if req is None:
                    continue
                if req.ctx >= req.target:           # decode / verify row
                    emitted = len(req.out_tokens) + req.in_flight
                    if emitted >= req.max_new_tokens:
                        continue            # its last token is in flight
                    d = drafts.get(i)
                    n = 1 + (0 if d is None else len(d))
                    if req.in_flight:
                        src[t] = i * L      # the step in flight samples it
                    else:
                        ids[t] = req.out_tokens[-1]
                    if n > 1:
                        ids[t + 1:t + n] = d
                    pos[t:t + n] = np.arange(req.ctx, req.ctx + n)
                    slot_vec[t:t + n] = self._write_slots(i, req.ctx, n)
                    qlen[i] = n
                    lens[i] = req.ctx + n
                    sample_idx[i * L:(i + 1) * L] = t   # spare lanes: dup t
                    sample_idx[i * L:i * L + n] = np.arange(t, t + n)
                    stream_pos[i * L:i * L + n] = emitted + np.arange(n)
                    keys[i * L:(i + 1) * L] = req.key_data
                    # the token every such row emits; a verify row's commit
                    # adds the drafts it accepted
                    req.ctx += 1
                    req.in_flight += 1
                    post.append((i, req, n, req.ctx, _DECODE))
                else:                                           # prefill chunk
                    n = grants.get(i, 0)
                    lens[i] = req.ctx + n
                    if n == 0:
                        continue
                    ids[t:t + n] = req.full_seq[req.ctx:req.ctx + n]
                    pos[t:t + n] = np.arange(req.ctx, req.ctx + n)
                    slot_vec[t:t + n] = self._write_slots(i, req.ctx, n)
                    qlen[i] = n
                    kind = _CHUNK
                    if req.ctx + n == req.target and not req.out_tokens:
                        sample_idx[i * L] = t + n - 1  # first tok: last logits
                        stream_pos[i * L] = 0
                        keys[i * L] = req.key_data
                        req.in_flight += 1
                        kind = _FIRST
                    # (a resumed row's first decode id is its last emitted
                    # token, on the host already)
                    req.ctx += n
                    post.append((i, req, n, req.ctx, kind))
                dequant_blocks += (int(lens[i]) + bs - 1) // bs
                t += n
            cu = np.zeros((R + 1,), np.int32)
            np.cumsum(qlen, out=cu[1:])
            step_attrs = {}
            if self.recurrent:
                # every row with a token has its state updated, and one
                # whose first token is at position 0 starts from zeros
                live = qlen > 0
                _M_STATE_RESETS.inc(int((live & (lens == qlen)).sum()))
                step_attrs = {"state_rows": int(live.sum()),
                              "scan_tokens": t}
            # what one layer's attention call walks: its live tiles and
            # their live kv blocks (the pairs its ring streams), beside the
            # tile x table-column pairs of the whole grid
            kv_live_tiles = _rpa.live_tiles(qlen)
            kv_tile_blocks = _rpa.live_tile_blocks(qlen, lens, bs)
            # of those, the visits of tiles of one token (decode rows)
            kv_token_blocks = _rpa.live_token_blocks(qlen, lens, bs)
            kv_table_blocks = (_rpa.num_tiles(R, T)
                               * self.cache.block_tables.shape[1])

        with _tracing.start_span("serving.step.dispatch",
                                 trace=_tracing.UNTRACED,
                                 attrs={"step": n_step}) as sp_dispatch:
            launches = self._program.launches()
            # ledger row of the ragged step, one a geometry: the model call
            # is one executable (`_StepProgram`), whose cost analysis gives
            # the row its FLOPs and HBM bytes; gather and sampling are two
            # small ops beside it. The commit's host sync makes the
            # device-time sample free
            _pe = _p_sample = None
            if _perf_mod.enabled():
                _led = _perf_mod.ledger()
                _pe = _led.register(
                    ("serving", self.max_batch, T,
                     self.spec_k, self.cache.kv_dtype),
                    "serving", name="serving_step",
                    lower=functools.partial(self._program.compiled, T))
                _p_sample = _led.tick(_pe)
            cold = self._program.cold
            view = _RaggedView(
                self.cache,
                Tensor(jnp.asarray(slot_vec)),
                # a snapshot: a commit may clear a row of the table
                # (`_release_slot`) before this launch has read it, and an
                # upload on the CPU may alias the host's memory
                Tensor(jnp.asarray(
                    self.cache.block_tables.astype(np.int32))),
                Tensor(jnp.asarray(lens, jnp.int32)),
                Tensor(jnp.asarray(cu, jnp.int32)),
                program=self._program,
                prev=Tensor(self._prev), src=Tensor(jnp.asarray(src)))
            with no_grad():
                logits = self.model(
                    Tensor(jnp.asarray(ids[None])), cache=view,
                    start_pos=Tensor(jnp.asarray(pos[None], jnp.int32)))
                tail = (Tensor(jnp.asarray(sample_idx, jnp.int32)),
                        Tensor(jnp.asarray(keys)),
                        Tensor(jnp.asarray(stream_pos, jnp.int32)))
                nxt = self._sample(logits, *tail)
                launches = self._program.launches() - launches
                if cold:
                    # the step program made every geometry's executable on
                    # this first call; the per-op programs behind it follow
                    # the logits' shape, so each other geometry's run once
                    # here, on zeros
                    for n in self.geometries:
                        if n != T:
                            self._sample(Tensor(jnp.zeros(
                                (1, n) + tuple(logits.shape[2:]),
                                logits._data.dtype)), *tail)
            self._prev = nxt._data
            self.steps += 1
            _M_STEPS.inc()
            _M_STEP_TOKENS.inc(t)
            _M_STEP_SLOTS.inc(T)
            _M_TOKEN_BLOCKS.inc(kv_token_blocks)
        # the phase's own edges: dispatch began, its last async launch
        # returned (none with FLAGS_tracing off: the ledger row then counts
        # its calls only)
        t0_ns, td_ns = sp_dispatch.t0_ns, sp_dispatch.t1_ns
        if td_ns is not None:
            # retroactive, on the thread timeline, in the call that
            # launched the step: the model call and its sampling, enqueued
            _tracing.record_span(
                "serving.step", t0_ns, td_ns,
                attrs={"tokens": t, "slots": T,
                       # launched behind a step still in flight
                       "overlapped": int(self._inflight is not None),
                       "decode_rows": len(decode_rows),
                       "prefill_rows": len(prefill_rows),
                       "launches": launches,
                       "kv_live_tiles": kv_live_tiles,
                       "kv_tile_blocks": kv_tile_blocks,
                       "kv_token_blocks": kv_token_blocks,
                       "kv_table_blocks": kv_table_blocks,
                       **step_attrs})
        return _Launched(nxt, post, drafts, dequant_blocks, t0_ns, td_ns,
                         _pe, _p_sample)

    def _settle(self, due: List["_Launched"], n_step: int) -> None:
        """Sync and commit the launched steps ``due``, oldest first (none:
        the call that starts a pipeline has nothing to commit, and records
        both spans all the same)."""
        with _tracing.start_span("serving.step.sync",
                                 trace=_tracing.UNTRACED,
                                 attrs={"step": n_step}):
            # each step's tokens on the host, and when they were
            sampled = [(np.asarray(s.nxt._data).reshape(-1),
                        _tracing.now_ns()) for s in due]
        with _tracing.start_span("serving.step.commit",
                                 trace=_tracing.UNTRACED,
                                 attrs={"step": n_step}):
            finished: List[Request] = []
            for launched, (tokens, ready_ns) in zip(due, sampled):
                self._commit(launched, tokens, ready_ns, finished)
            if self.on_finish is not None:
                for req in finished:
                    self.results.pop(req.rid, None)
                    self.on_finish(req)
            if finished:
                with self.finish_cv:
                    self.finish_cv.notify_all()
            self._finished += finished

    def _commit(self, launched: "_Launched", sampled: np.ndarray,
                ready_ns: int, finished: List[Request]) -> None:
        """Hand a launched step's sampled tokens to their requests."""
        if launched.perf_entry is not None and launched.td_ns is not None:
            # launch to tokens-on-host, less the time the step waited
            # behind the one before it: the device-time estimate
            began = max(launched.t0_ns, self._ready_ns)
            _perf_mod.ledger().commit(
                launched.perf_entry,
                (launched.td_ns - launched.t0_ns) / 1e9,
                (ready_ns - began) / 1e9 if launched.perf_sample else None)
        self._ready_ns = ready_ns
        if self.cache.quantized:
            # every attended block is dequantized in-tile each step:
            # bandwidth accounting for the int8 pool (per layer, per row)
            _M_KV_DEQ.inc(launched.dequant_blocks * self.cache.num_layers)
        L = self.spec_k + 1
        now = time.time()
        for i, req, n, ctx, kind in launched.post:
            if req.done or self.slots[i] is not req:
                # the request ended (an EOS) while this step was launched:
                # its row was packed once more, and the token goes nowhere
                if kind != _CHUNK:
                    _M_DISCARDED.inc()
                continue
            if kind == _DECODE:
                # exact-match verify: draft j is accepted iff it equals
                # the keyed sample at its stream position — so spec-on
                # output is byte-identical to spec-off at ANY temperature
                # (the samples themselves are the ground truth). Accepted
                # drafts validate the NEXT lane's logits; the first
                # mismatch invalidates everything after it.
                d = launched.drafts.get(i)
                nd = n - 1
                base = i * L
                a = 0
                while a < nd and int(sampled[base + a]) == int(d[a]):
                    a += 1
                if nd:
                    _M_SPEC_PROP.inc(nd)
                    _M_SPEC_ACC.inc(a)
                    _M_SPEC_REJ.inc(nd - a)
                    _M_SPEC_ROWS.inc()
                # rejected-draft KV rows (positions ctx+a..ctx+n-2) are
                # garbage: the row's length hides them and the next step
                # overwrites those slots in place
                req.ctx += a
                req.in_flight -= 1
                for j in range(a + 1):
                    self._append_token(req, i, int(sampled[base + j]),
                                       now, finished)
                    if req.done:
                        break
            else:
                _M_PREFILL_TOKENS.inc(n)
                self._register_blocks(req, i, ctx)
                if kind == _FIRST:
                    req.in_flight -= 1
                    self._append_token(req, i, int(sampled[i * L]),
                                       now, finished)

    def _sample(self, logits: Tensor, sample_idx: Tensor, keys: Tensor,
                stream_pos: Tensor) -> Tensor:
        """The step's three per-op programs behind the model call: the
        packed logits ``[1, slots, V]`` reshaped, the sampling lanes' rows
        gathered, and each lane's token drawn from its own stream."""
        lrows = call_op("gather",
                        logits.reshape([logits.shape[1], -1]), sample_idx)
        return call_op("sample_logits_keyed", lrows, keys, stream_pos,
                       **self.sampling)

    def pop_result(self, rid: int,
                   timeout: Optional[float] = None) -> Optional[Request]:
        """Retire a finished request from ``results`` (long-running
        server memory: poll-style callers hand finished outputs off
        instead of retaining every Request forever). With ``timeout``,
        block on the finish condition until the request completes or the
        deadline lands — the stepping thread notifies after each step's
        finishes, so waiters never busy-spin."""
        if timeout is None:
            req = self.results.get(rid)
            if req is None or not req.done:
                return None
            return self.results.pop(rid)
        deadline = time.monotonic() + float(timeout)
        with self.finish_cv:
            while True:
                req = self.results.get(rid)
                if req is not None and req.done:
                    return self.results.pop(rid)
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                self.finish_cv.wait(timeout=left)

    def run(self) -> Dict[int, List[int]]:
        """Drive until every request (queued + active) completes (a
        paused engine only drains its in-flight rows). Requests retired
        through ``on_finish`` are still included in the return value."""
        out: Dict[int, List[int]] = {}
        while ((self.pending and not self.admission_paused)
               or self.num_active):
            for req in self.step():
                out[req.rid] = req.out_tokens
        for rid, req in self.results.items():
            out.setdefault(rid, req.out_tokens)
        return out
