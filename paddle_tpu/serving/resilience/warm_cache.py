"""Prefix-cache warm-start: snapshot published prefix blocks, preload
them into a relaunched server's pool.

The prefix cache maps sha256 *chain digests* of full prompt blocks to
pool block ids — content-addressed, so a snapshot is just ``digest →
KV block bytes`` with no reference to the dead process's block
numbering. On drain (and periodically) the tracked blocks are gathered
to host and written as one committed generation
(``gen-<n>``: ``blocks.npz`` + ``meta.json`` + ``COMMITTED``, all via
:mod:`paddle_tpu.utils.durability`); on relaunch the newest committed
generation is preloaded into freshly-allocated pool blocks and
registered *evictable* — warm capacity the allocator may reclaim, so
preloading never steals admission headroom. Recovered requests and new
traffic sharing those prompt heads then prefill from warm blocks
instead of recomputing them (measured as warm-vs-cold TTFT by
``bench.py serving_recovery``).

A geometry/dtype mismatch (different block size, kv heads, head dim, or
model fingerprint) refuses the preload rather than serving another
model's KV.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
import uuid
from typing import Optional

import jax
import numpy as np

from ...core.tensor import Tensor
from ...jit.api import _SWAP_LOCK
from ...observability import flight_recorder as _flight
from ...observability import metrics as _metrics
from ...ops.dispatcher import call_op
from ...utils.durability import (fsync_write, latest_committed,
                                 read_committed_marker,
                                 write_committed_marker)

__all__ = ["snapshot_prefix_cache", "load_prefix_cache",
           "last_generation"]

_GEN_PREFIX = "gen-"
# incarnation fencing, same rationale as the journal's seg-<n>-<uid>: a
# wedged-then-unwedged previous process resuming from the same
# last_generation() must land its snapshot in its OWN directory, never
# interleave fsync_write renames inside one the relaunch is writing
_UID = uuid.uuid4().hex[:8]
# how long an UNCOMMITTED generation dir is presumed to be a live
# concurrent writer's in-flight snapshot rather than crash debris
_PRUNE_GRACE_S = 900.0

_M_SNAPSHOTS = _metrics.registry().counter(
    "serving.resilience.snapshots",
    help="prefix-cache snapshot generations committed")
_M_WARM = _metrics.registry().gauge(
    "serving.resilience.warm_blocks",
    help="prefix blocks preloaded warm at the last relaunch")


_record = _flight.record_event


def _model_fingerprint(model) -> str:
    """Cheap weights identity: config fields + strided probes of
    several parameters spread through the model (always including the
    first and last). A contiguous head-of-first-param slice would miss
    fine-tunes that freeze the embedding table or never touch row 0;
    strided sampling across layers catches any realistic weight update
    for a few KB of D2H — no full-model digest on the drain path."""
    h = hashlib.sha256()
    cfg = getattr(model, "config", None)
    if cfg is not None:
        h.update(repr(sorted(
            (k, v) for k, v in vars(cfg).items()
            if isinstance(v, (int, float, str, bool, type(None))))).encode())
    params = list(model.parameters())
    if params:
        picks = sorted({0, len(params) - 1,
                        *range(0, len(params),
                               max(1, len(params) // 8))})
        # replicas in threads share their model: while one of them traces
        # its step program the parameters hold tracers
        with _SWAP_LOCK:
            arrays = [params[idx]._data for idx in picks]
        for a in arrays:
            flat = a.reshape(-1)
            stride = max(1, int(flat.shape[0]) // 64)
            probe = np.asarray(jax.device_get(flat[::stride][:64]))
            h.update(probe.tobytes())
    return h.hexdigest()


def _meta(engine) -> dict:
    c = engine.cache
    pool = c.k[0]._data
    # serving weights are frozen: probe the model ONCE per engine, not
    # on every periodic snapshot (and not on the drain deadline path)
    fp = getattr(engine, "_warm_model_fp", None)
    if fp is None:
        fp = engine._warm_model_fp = _model_fingerprint(engine.model)
    return {
        "block_size": int(c.block_size),
        "num_layers": int(c.num_layers),
        "kv_heads": int(pool.shape[2]),
        "head_dim": int(pool.shape[3]),
        "dtype": str(pool.dtype),
        # storage regime, not just element type: an int8 snapshot is
        # meaningless without its scales and a float snapshot has none,
        # so EITHER direction of mismatch (old snapshot + quantized
        # engine, quantized snapshot + float engine) must refuse — the
        # any-differing-key check below covers both, including meta
        # written before this key existed (None != "int8")
        "kv_dtype": str(c.kv_dtype),
        "model_fingerprint": fp,
    }


def last_generation(root: str) -> int:
    """Highest generation number present under ``root`` (committed or
    not), 0 when none: a relaunched server must continue the sequence,
    never rewrite an already-COMMITTED generation in place."""
    last = 0
    try:
        names = os.listdir(root)
    except OSError:
        return 0
    for name in names:
        if name.startswith(_GEN_PREFIX):
            try:
                last = max(last,
                           int(name[len(_GEN_PREFIX):].split("-")[0]))
            except ValueError:
                continue
    return last


def snapshot_prefix_cache(engine, root: str, gen: int,
                          keep: int = 2) -> Optional[str]:
    """Serialize every published prefix block (chain digest → KV bytes,
    all layers) as one committed generation under ``root``. Returns the
    generation path, or None when the cache is empty."""
    pc = engine._pc
    # INSERTION order, not digest order: prefill publishes ascending
    # block indices, so a child's digest registers after its parent's —
    # a prefix of this list stays parent-closed and a truncated preload
    # doesn't waste pool blocks on children unreachable via lookup()
    # (eviction can still orphan a child whose parent re-registers
    # later; an orphan preload is wasted warmth, never wrong bytes)
    items = list(pc._map.items())          # (digest, block id)
    if not items:
        return None
    digests = [d.hex() for d, _ in items]
    block_ids = np.asarray([b for _, b in items], np.int64)
    payload = {}
    dtype_name = None
    for layer in range(engine.cache.num_layers):
        for tag, pool in (("k", engine.cache.k), ("v", engine.cache.v)):
            # gather the tracked blocks ON DEVICE before the transfer:
            # this runs on the SIGTERM drain deadline, and a real pool
            # is GB-sized while the warm set is a handful of blocks
            host = np.asarray(jax.device_get(pool[layer]._data[block_ids]))
            if host.dtype == jax.numpy.bfloat16:
                host = host.view(np.uint16)
                dtype_name = "bfloat16"
            else:
                dtype_name = host.dtype.name
            payload[f"{tag}_{layer}"] = host
    if engine.cache.quantized:
        # int8 blocks are unusable without their per-token-slot scales:
        # the scale rows ride the snapshot under ks_/vs_ keys and replay
        # through the same paged_cache_write path on preload
        for layer in range(engine.cache.num_layers):
            for tag, pool in (("ks", engine.cache.k_scale),
                              ("vs", engine.cache.v_scale)):
                payload[f"{tag}_{layer}"] = np.asarray(
                    jax.device_get(pool[layer]._data[block_ids]))
    meta = _meta(engine)
    meta["payload_dtype"] = dtype_name
    meta["digests"] = digests
    path = os.path.join(root, f"{_GEN_PREFIX}{int(gen):08d}-{_UID}")
    os.makedirs(path, exist_ok=True)
    fsync_write(os.path.join(path, "blocks.npz"),
                lambda f: np.savez(f, **payload))
    fsync_write(os.path.join(path, "meta.json"),
                lambda f: f.write(json.dumps(meta).encode()))
    write_committed_marker(path, step=int(gen), blocks=len(items))
    _prune(root, keep)
    _M_SNAPSHOTS.inc()
    _record("serving.resilience.snapshot", (path, len(items)))
    return path


def _prune(root: str, keep: int) -> None:
    """Keep the newest ``keep`` committed generations; drop older
    committed ones and stale uncommitted debris. An uncommitted dir
    younger than the grace window is left alone: it may be a CONCURRENT
    incarnation's snapshot mid-write (the uid-fenced zombie scenario) —
    deleting it under the writer would crash a healthy server's
    fsync_write, not clean up debris."""
    committed = []
    try:
        names = os.listdir(root)
    except OSError:
        return
    now = time.time()
    for name in names:
        if not name.startswith(_GEN_PREFIX):
            continue
        sub = os.path.join(root, name)
        if not os.path.isdir(sub):
            continue
        if read_committed_marker(sub) is not None:
            committed.append(sub)
        else:
            try:
                fresh = now - os.path.getmtime(sub) < _PRUNE_GRACE_S
            except OSError:
                fresh = False          # already gone: nothing to keep
            if not fresh:
                shutil.rmtree(sub, ignore_errors=True)
    committed.sort(reverse=True)
    for sub in committed[keep:]:
        shutil.rmtree(sub, ignore_errors=True)


def load_prefix_cache(engine, root: str) -> int:
    """Preload the newest committed snapshot generation into the
    engine's pool: each digest gets a fresh block, its KV bytes land
    through the engine's normal compiled ``paged_cache_write`` path, and
    the block registers in the prefix cache *evictable* (zero active
    holders) — warm, but reclaimable, so admission headroom is
    unchanged. Returns the number of blocks preloaded (0 when no
    snapshot exists, geometry mismatches, or the pool has no room)."""
    path = latest_committed(root)
    if path is None:
        return 0
    try:
        with open(os.path.join(path, "meta.json"), encoding="utf-8") as f:
            meta = json.load(f)
    except OSError:
        return 0
    want = _meta(engine)
    if any(meta.get(k) != v for k, v in want.items()):
        _record("serving.resilience.warm_mismatch",
                (path, {k: (meta.get(k), v) for k, v in want.items()
                        if meta.get(k) != v}))
        return 0
    digests = [bytes.fromhex(d) for d in meta["digests"]]
    try:
        z = np.load(os.path.join(path, "blocks.npz"))
    except OSError:
        return 0
    with z:    # release the zip handle: _prune may rotate this gen away
        if z["k_0"].shape[0] != len(digests):
            # meta and payload disagree — refuse, don't crash mid-init
            _record("serving.resilience.warm_mismatch",
                    (path, {"digests": len(digests),
                            "payload_blocks": int(z["k_0"].shape[0])}))
            return 0
        # never drain the free list completely: admissions come first
        n = min(len(digests),
                max(0, len(engine.cache._free) - engine.max_batch))
        if n <= 0:
            _M_WARM.set(0.0)
            return 0
        blocks = [engine.cache._free.pop() for _ in range(n)]
        bs = engine.cache.block_size
        slot_np = (np.asarray(blocks, np.int64)[:, None] * bs
                   + np.arange(bs)[None, :]).reshape(-1)
        slots = Tensor(jax.numpy.asarray(slot_np, jax.numpy.int32))
        for layer in range(engine.cache.num_layers):
            for tag, pool in (("k", engine.cache.k), ("v", engine.cache.v)):
                host = z[f"{tag}_{layer}"][:n]
                if meta.get("payload_dtype") == "bfloat16":
                    host = host.view(jax.numpy.bfloat16)
                rows = Tensor(jax.numpy.asarray(host.reshape(
                    1, n * bs, host.shape[2], host.shape[3])))
                pool[layer] = call_op("paged_cache_write", pool[layer],
                                      rows, slots)
        if engine.cache.quantized:
            # kv_dtype matched above, so the snapshot carries ks_/vs_
            # scale rows: same one-scatter write, [BS, KV] trailing dims
            for layer in range(engine.cache.num_layers):
                for tag, pool in (("ks", engine.cache.k_scale),
                                  ("vs", engine.cache.v_scale)):
                    host = z[f"{tag}_{layer}"][:n]
                    rows = Tensor(jax.numpy.asarray(host.reshape(
                        1, n * bs, host.shape[2])))
                    pool[layer] = call_op("paged_cache_write", pool[layer],
                                          rows, slots)
    preloaded = 0
    for digest, block in zip(digests[:n], blocks):
        if engine._pc.register(digest, block):
            engine._pc.release_block(block)  # zero holders: warm+evictable
            preloaded += 1
        else:
            # digest already tracked (second preload, or the engine
            # served traffic first): hand the block straight back or
            # it leaks out of the pool forever
            engine.cache._free.append(block)
    _M_WARM.set(float(preloaded))
    _record("serving.resilience.warm_start", (path, preloaded))
    return preloaded
