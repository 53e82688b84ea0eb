"""GSPMD-composable tensor-parallel dispatch for the Pallas attention tier.

XLA's SPMD partitioner cannot split a `pallas_call` on its own: a
Pallas attention op reached with tp-sharded operands either aborts the
partitioner or silently gathers everything onto one device. Until this
module existed the framework therefore DISABLED its flagship flash
kernel whenever GSPMD tensor parallelism was active (the old
`auto_parallel/aot.py` `use_flash_attention=False` line) and fell back
to the XLA gather+SDPA composite — forfeiting the hand-kernel win
exactly where the ROADMAP north-star needs it (sharded production
runs; see Ragged Paged Attention, arXiv:2604.15464, and the Gemma
TPU comparison, arXiv:2605.25645, which attributes most of the TPU
advantage to this kernel tier).

The fix is the standard one: wrap the kernel in a mesh-aware
``shard_map`` (via the `jax_compat` shim) whose in/out specs shard the
HEAD dimension over the tensor-parallel mesh axis, so each device runs
the unmodified single-chip Pallas kernel on its local ``num_heads /
tp`` (and ``kv_heads / tp``) slice. Head-block contiguity makes this
exact for GQA: shard r's query heads ``[r*hq/tp, (r+1)*hq/tp)`` map
onto exactly its kv heads ``[r*hk/tp, (r+1)*hk/tp)`` whenever both
head counts divide the tp degree, with the group ratio g = hq/hk
preserved per shard — no cross-shard attention ever exists, so the
region needs no collectives and its AD transpose is collective-free
too.

Dispatch contract (threaded through ops/kernels/nn.py and serving.py
behind the FLAGS_use_pallas_kernels gate):

* an ambient TP context — the fleet hybrid topology with mp > 1, or an
  explicit :func:`tp_shard_context` (how the deviceless AOT planner
  lowers the v5p plan) — selects the shard_map'd entry points here;
* divisibility guards (``hq % tp``, ``hk % tp`` — the GQA-replication
  edge — and per-shard kernel support) fall back CLEANLY to the XLA
  composite, recording the reason in the flight recorder and a
  `tp_attention.fallback` metric, never erroring;
* kernels read the ambient context at TRACE time, so every context
  change bumps `flags.bump_mesh_epoch()` — the per-op exec cache keys
  on the fingerprint and can never replay an executable traced under a
  retired mesh.

Interpreter mode follows the TARGET mesh platform (not the host
backend): a deviceless v5p lowering embeds the real Mosaic kernels,
a forced-8-device CPU mesh runs them interpreted.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ....jax_compat import shard_map
from .... import flags as _flags
from ....observability import flight_recorder as _flight_mod
from ....observability import metrics as _metrics_mod

_M_SHARDED = _metrics_mod.registry().counter(
    "tp_attention.sharded",
    "attention dispatches compiled onto the shard_map'd Pallas path")
_M_FALLBACK = _metrics_mod.registry().counter(
    "tp_attention.fallback",
    "attention dispatches under a TP mesh that fell back to the XLA "
    "composite (divisibility / flags / shard-shape guards)")


# -- ambient TP context -------------------------------------------------------

_TP_CONTEXT: Optional[Tuple] = None   # (mesh, head_axis, batch_axis|None)


@contextlib.contextmanager
def tp_shard_context(mesh, head_axis: str = "mp",
                     batch_axis: Optional[str] = None):
    """Pin the TP mesh the attention kernels shard over while tracing.

    Used by the topology-AOT planner (no hybrid topology is installed
    there — TP exists only as shardings) and by tests. Entering/leaving
    bumps the flags mesh epoch so per-op executables traced under the
    context never replay outside it.

    The Pallas interpret mode is ALSO pinned from the target mesh's
    platform for the whole context — not per kernel call — because
    custom_vjp backward rules and remat re-traces run at transpose time,
    well after any per-call window: a deviceless v5p lowering on a CPU
    host must embed Mosaic custom calls in BOTH the forward and the
    re-traced backward."""
    from . import flash_attention as fa

    global _TP_CONTEXT
    prev = _TP_CONTEXT
    prev_interp = fa._FORCE_INTERPRET
    platform = getattr(next(iter(mesh.devices.flat)), "platform", "cpu")
    _TP_CONTEXT = (mesh, head_axis, batch_axis)
    fa._FORCE_INTERPRET = platform != "tpu"
    _flags.bump_mesh_epoch()
    try:
        yield
    finally:
        _TP_CONTEXT = prev
        fa._FORCE_INTERPRET = prev_interp
        _flags.bump_mesh_epoch()


def current_tp_context() -> Optional[Tuple]:
    """(mesh, head_axis, batch_axis|None) when tensor parallelism is
    ambient: an explicit tp_shard_context, else the fleet hybrid
    topology with model-parallel degree > 1 (the mp_layers stance:
    heads ride the mp axis, batch rides dp).

    An EXPLICIT context stays active even at tp degree 1: under GSPMD
    lowering the shard_map WRAP is what keeps a bare pallas_call away
    from the SPMD partitioner — a dp-only plan (tp=1) still needs it,
    with the batch manual over dp and the head 'sharding' trivial."""
    if _TP_CONTEXT is not None:
        mesh, ha, ba = _TP_CONTEXT
        return (mesh, ha, ba) if ha in mesh.shape else None
    from ....distributed.fleet.mp_layers import tp_attention_context
    return tp_attention_context()


# -- fallback recording -------------------------------------------------------

# Frozen fallback-reason taxonomy: the `key` passed to record_fallback
# must be a member, so the tp_attention.fallback counter and the flight
# recorder can never fork on a typo'd reason. The graftcheck `taxonomy`
# rule checks every literal call site statically; this runtime check
# covers computed keys. The human-readable `reason` string carries the
# parameterization (shapes, degrees) and rides the ring entry.
TP_FALLBACK_REASONS = frozenset({
    "flags_off",             # FLAGS_use_pallas_kernels disabled
    "heads_indivisible",     # num_heads % tp != 0
    "kv_heads_indivisible",  # kv_heads % tp != 0 (GQA replication edge)
    "shard_unsupported",     # per-shard shape outside the kernel's support
    "head_dim_mismatch",     # ragged: q head_dim != pool head_dim
    "ring_head_replicated",  # ring attention running head-replicated
    "ragged_rows_replicated",  # ragged serving: rows asked onto dp, but
                               # the packed token axis is ragged — heads
                               # still shard, rows stay replicated
})


def record_fallback(kind: str, key: str, reason: str) -> None:
    """Count + flight-record a composite fallback under a TP mesh.

    `key` is the frozen taxonomy member (TP_FALLBACK_REASONS); `reason`
    the parameterized human-readable detail. Recorded at TRACE time
    (once per compiled specialization, not per step) — one ring entry
    per distinct fallback site, which is exactly the post-mortem
    question 'why is this TP run not on the fast path?'."""
    if key not in TP_FALLBACK_REASONS:
        raise ValueError(
            f"unregistered tp_attention fallback reason {key!r} — add it "
            f"to TP_FALLBACK_REASONS (frozen so counters cannot fork)")
    _M_FALLBACK.inc()
    if _flight_mod.enabled():
        _flight_mod.recorder().record(
            f"tp_attention.fallback[{kind}]", (reason,), key)


def _tp_reason(tp: int, hq: int, hk: int) -> Optional[Tuple[str, str]]:
    """(taxonomy key, detail) for a divisibility fallback, or None."""
    if hq % tp:
        return ("heads_indivisible",
                f"num_heads {hq} not divisible by tp degree {tp}")
    if hk % tp:
        return ("kv_heads_indivisible",
                f"kv_heads {hk} not divisible by tp degree {tp} "
                f"(GQA replication)")
    return None


def _batch_axis(mesh, batch_axis: Optional[str], b: int) -> Optional[str]:
    """Shard the batch dim over the data axis only when it divides."""
    if batch_axis and mesh.shape.get(batch_axis, 1) > 1 \
            and b % mesh.shape[batch_axis] == 0:
        return batch_axis
    return None


# -- compiled shard_map cache -------------------------------------------------

_TP_CACHE: dict = {}
_TP_CACHE_MAX = 128


def _cached(key, build):
    fn = _TP_CACHE.get(key)
    if fn is None:
        if len(_TP_CACHE) >= _TP_CACHE_MAX:
            _TP_CACHE.clear()
        fn = _TP_CACHE[key] = build()
    return fn


# -- shard_map'd entry points -------------------------------------------------
# Every region below is manual over ALL axes of the mesh, not only the
# ones its specs name: Mosaic refuses a kernel in a partial-manual region
# ("cannot be automatically partitioned"), and the fleet's hybrid mesh
# always carries its five axes, most of degree 1. An axis the specs leave
# out means "replicated over it".

def _manual(local, mesh, in_specs, out_specs):
    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset(mesh.axis_names), check_vma=False))


def sharded_flash_attention(query, key, value, mesh, head_axis,
                            batch_axis=None, causal=False, scale=None):
    """[b, s, h, d] flash attention with heads sharded over `head_axis`
    (and batch over `batch_axis` when it divides). Returns None after
    recording the reason when the sharded fast path can't run — the
    caller then takes the composite."""
    from . import flash_attention as fa

    b, sq, hq, d = query.shape
    sk, hk = key.shape[1], key.shape[2]
    tp = mesh.shape[head_axis]
    fb = _tp_reason(tp, hq, hk)
    if fb is None and not fa.supported(
            (b, sq, hq // tp, d), (b, sk, hk // tp, d), causal):
        fb = ("shard_unsupported",
              f"local shard q[{b},{sq},{hq // tp},{d}] "
              f"unsupported by the pallas flash kernel")
    if fb is not None:
        record_fallback("flash", *fb)
        return None
    if scale is None:
        scale = d ** -0.5
    ba = _batch_axis(mesh, batch_axis, b)

    def build():
        spec = P(ba, None, head_axis, None)

        def local(q, k, v):
            return fa.flash_attention(q, k, v, causal=causal, scale=scale)

        return _manual(local, mesh, (spec, spec, spec), spec)

    fn = _cached(("flash", mesh, head_axis, ba, bool(causal), float(scale)),
                 build)
    _M_SHARDED.inc()
    return fn(query, key, value)


def sharded_flash_varlen(q, k, v, cu_q, cu_k, mesh, head_axis,
                         causal=False, scale=None, tok_skip=False):
    """Packed [total, heads, dim] varlen attention, heads sharded over
    `head_axis` (token dim stays whole — it is ragged). Returns None
    (recorded) when head counts don't divide the tp degree."""
    from . import flash_varlen as fv

    h, d = q.shape[1], q.shape[2]
    hk = k.shape[1]
    tp = mesh.shape[head_axis]
    fb = _tp_reason(tp, h, hk)
    if fb is not None:
        record_fallback("varlen", *fb)
        return None
    if scale is None:
        scale = d ** -0.5

    def build():
        hspec = P(None, head_axis, None)
        rep = P(None)

        def local(q_, k_, v_, cq, ck):
            return fv._varlen(q_, k_, v_, cq, ck, bool(causal),
                              float(scale), bool(tok_skip))

        return _manual(local, mesh, (hspec, hspec, hspec, rep, rep), hspec)

    fn = _cached(("varlen", mesh, head_axis, bool(causal), float(scale),
                  bool(tok_skip)), build)
    _M_SHARDED.inc()
    return fn(q, k, v, cu_q.astype(jnp.int32), cu_k.astype(jnp.int32))


def sharded_ragged_paged_attention(q, k_pool, v_pool, block_tables,
                                   context_lens, cu_q_lens, mesh,
                                   head_axis, batch_axis=None, scale=None,
                                   k_scale=None, v_scale=None):
    """Ragged mixed prefill+decode serving attention with q heads AND
    the pool's kv heads sharded over `head_axis`. The packed token axis
    is ragged (cu_q_lens segments it), so rows CANNOT co-shard over a
    data axis the way a dense batch dim does — when the caller asks
    for one anyway the request is recorded (frozen reason
    `ragged_rows_replicated`) and the kernel still runs head-sharded
    with rows replicated. Returns None (recorded) on the divisibility /
    head-dim edges; the caller then takes the composite."""
    from . import ragged_paged_attention as rpa

    T, H, D = q.shape
    KV = k_pool.shape[2]
    tp = mesh.shape[head_axis]
    fb = _tp_reason(tp, H, KV)
    if fb is None and D != k_pool.shape[3]:
        fb = ("head_dim_mismatch",
              f"q head_dim {D} != pool head_dim {k_pool.shape[3]}")
    if fb is not None:
        record_fallback("ragged", *fb)
        return None
    if batch_axis and mesh.shape.get(batch_axis, 1) > 1:
        record_fallback(
            "ragged", "ragged_rows_replicated",
            f"ragged rows cannot shard over {batch_axis!r} "
            f"(degree {mesh.shape[batch_axis]}): packed token axis is "
            f"ragged; running head-sharded with rows replicated")
    if scale is None:
        scale = D ** -0.5
    quantized = k_scale is not None

    def build():
        qspec = P(None, head_axis, None)
        pspec = P(None, None, head_axis, None)
        # int8 pool scales [NB, BS, KV]: kv heads shard with the pool
        sspec = P(None, None, head_axis)
        rep2, rep1 = P(None, None), P(None)

        if quantized:
            def local(q_, kp, vp, tbl, lens, cu, ks, vs):
                return rpa.ragged_paged_attention(
                    q_, kp, vp, tbl, lens, cu, scale=scale,
                    k_scale=ks, v_scale=vs)
            in_specs = (qspec, pspec, pspec, rep2, rep1, rep1,
                        sspec, sspec)
        else:
            def local(q_, kp, vp, tbl, lens, cu):
                return rpa.ragged_paged_attention(q_, kp, vp, tbl, lens,
                                                  cu, scale=scale)
            in_specs = (qspec, pspec, pspec, rep2, rep1, rep1)

        return _manual(local, mesh, in_specs, qspec)

    fn = _cached(("ragged", mesh, head_axis, float(scale), quantized),
                 build)
    _M_SHARDED.inc()
    args = (q, k_pool, v_pool, block_tables.astype(jnp.int32),
            context_lens.astype(jnp.int32), cu_q_lens.astype(jnp.int32))
    if quantized:
        args += (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
    return fn(*args)
