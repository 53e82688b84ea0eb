"""Async snapshot checkpointing with an atomic commit protocol.

A blocking ``save_state_dict`` stalls the step loop for the full
device→host transfer + serialization + fsync; on a v5p pod that is
seconds of lost step time per snapshot, which pushes snapshot cadence
down and loss-on-preemption up. :class:`AsyncCheckpointer` splits the
save at the only boundary that matters for correctness:

* **snapshot** (foreground, :func:`save_load.collect_shards`): every
  owned shard box is copied to host memory before ``save`` returns.
  From that moment the snapshot is immune to donation — the captured
  step may consume (donate) the source buffers on its very next replay,
  which is why the snapshot must be taken from replay *outputs* between
  steps, never from inside a trace (``save`` refuses under an active
  trace).
* **write** (background thread): serialization, ``np.savez``, fsync,
  rename and the commit marker overlap the next captured steps.

Commit protocol (shared with the bare ``save_state_dict``): every file
lands via ``tmp-<uid>`` + fsync + atomic rename, and a generation
becomes visible only when its ``COMMITTED`` marker (carrying the step
number) exists. ``latest_checkpoint``/``load_state_dict`` never observe
a torn generation; a writer killed at any point leaves an invisible
directory that retention later prunes. Multi-writer saves barrier on
the job's TCPStore before the coordinator writes the marker, so the
marker also certifies that EVERY rank's shards are on disk.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from ...core.tensor import Tensor
from ...observability import flight_recorder as _flight
from ...observability import metrics as _metrics
from ...observability import tracing as _tracing
from ..checkpoint.save_load import (collect_shards, latest_checkpoint,
                                    load_state_dict, read_committed_marker,
                                    write_committed_marker, write_shards,
                                    _fsync_write, _load_metadata)
from ..env import get_rank, get_world_size

__all__ = ["AsyncCheckpointer", "flatten_state", "restore_state",
           "training_state"]


def training_state(network, optimizer=None) -> Dict[str, Any]:
    """Reference-based state tree for :meth:`AsyncCheckpointer.save`.

    ``optimizer.state_dict()`` defensively ``jnp.copy``-s every state
    array (its contract must survive the next donated step); the async
    checkpointer needs no such copies — its foreground snapshot host-
    copies every shard before ``save`` returns, strictly before the next
    replay can donate the sources. Restore by feeding the rebuilt
    ``"opt"`` subtree to ``optimizer.set_state_dict``."""
    state: Dict[str, Any] = {"model": network.state_dict()}
    if optimizer is not None:
        opt: Dict[str, Any] = {"step": optimizer._step_count,
                               "states": list(optimizer._states),
                               "masters": list(optimizer._masters)}
        lr = getattr(optimizer, "_lr", None)
        if hasattr(lr, "state_dict"):
            opt["lr"] = lr.state_dict()
        state["opt"] = opt
    return state

_HOST_FILE = "host_state.json"
_GEN_PREFIX = "step-"

_M_SNAPSHOT = _metrics.registry().histogram(
    "checkpoint.snapshot_seconds",
    help="foreground device->host snapshot time per AsyncCheckpointer.save")
_M_WRITE = _metrics.registry().histogram(
    "checkpoint.write_seconds",
    help="background serialize+fsync+commit time per checkpoint generation")
_M_COMMITTED = _metrics.registry().counter(
    "checkpoint.committed", help="checkpoint generations committed")
_M_ABORTED = _metrics.registry().counter(
    "checkpoint.aborted",
    help="checkpoint saves that failed before their COMMITTED marker")


_record = _flight.record_event


def _is_array(v: Any) -> bool:
    return isinstance(v, (Tensor, jax.Array, np.ndarray))


def flatten_state(tree: Any, prefix: str = ""
                  ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Split an arbitrary nested state tree (dicts/lists/tuples) into a
    flat ``key -> array`` dict (saved as sharded ``.distcp`` payload)
    and a flat ``key -> host value`` dict (ints/floats/strings/None —
    optimizer step counts, scheduler state — saved as JSON). List and
    tuple positions flatten under their index, so an optimizer
    ``state_dict`` round-trips without the caller reshaping it."""
    arrays: Dict[str, Any] = {}
    host: Dict[str, Any] = {}

    def walk(node, key):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, f"{key}.{k}" if key else str(k))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, f"{key}.{i}" if key else str(i))
        elif _is_array(node):
            arrays[key] = node
        else:
            host[key] = node

    walk(tree, prefix)
    return arrays, host


def _rebuild(tree: Any, arrays: Dict[str, Any], host: Dict[str, Any],
             key: str = "") -> Any:
    """Mirror of :func:`flatten_state`: rebuild the tree with loaded
    leaves. Tensor leaves were filled in place by ``load_state_dict``
    (same objects); everything else is replaced by the loaded value."""
    if isinstance(tree, dict):
        return {k: _rebuild(v, arrays, host, f"{key}.{k}" if key else str(k))
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        out = [_rebuild(v, arrays, host, f"{key}.{i}" if key else str(i))
               for i, v in enumerate(tree)]
        return tuple(out) if isinstance(tree, tuple) else out
    if _is_array(tree):
        return arrays[key]
    return host[key] if key in host else tree


def _reconstruct_missing(arrays: Dict[str, Any], host: Dict[str, Any],
                         path: str) -> Dict[str, list]:
    """Target positions that are ``None`` but exist as array subtrees in
    the checkpoint (a FRESH process restores before its first step, so
    optimizer per-param state dicts are still ``None``) get zero-array
    templates synthesized from the checkpoint's own metadata, so
    ``load_state_dict`` fills them like any other target. Returns
    ``parent key -> its saved subtree keys`` for structure rebuild."""
    import jax.numpy as jnp
    saved = _load_metadata(path).state_dict_metadata
    recon: Dict[str, list] = {}
    for key, val in host.items():
        if val is not None:
            continue
        subkeys = sorted(k for k in saved
                         if k == key or k.startswith(key + "."))
        if not subkeys:
            continue
        for sk in subkeys:
            boxes = saved[sk]
            ndim = len(boxes[0].global_offset)
            shape = tuple(max(b.global_offset[d] + b.local_shape[d]
                              for b in boxes) for d in range(ndim))
            arrays[sk] = jnp.zeros(shape, boxes[0].dtype)
        recon[key] = subkeys
    return recon


def _subtree_from_keys(prefix: str, keys: list, arrays: Dict[str, Any]):
    """Rebuild a nested structure from dotted key paths. All-integer
    sibling segments become a list, anything else a dict — the shapes
    optimizer state trees actually use."""
    if keys == [prefix]:
        return arrays[prefix]
    children: Dict[str, list] = {}
    for k in keys:
        seg = k[len(prefix) + 1:].split(".", 1)[0]
        children.setdefault(seg, []).append(k)
    if all(s.isdigit() for s in children):
        return [_subtree_from_keys(f"{prefix}.{s}", children[s], arrays)
                for s in sorted(children, key=int)]
    return {s: _subtree_from_keys(f"{prefix}.{s}", children[s], arrays)
            for s in children}


def restore_state(state: Any, path: str) -> Tuple[Any, Optional[int]]:
    """Fill ``state`` from the committed checkpoint at ``path`` via the
    existing reshard-on-load path and return ``(rebuilt_tree, step)``.

    Tensor leaves are updated IN PLACE (model parameters restore without
    rebinding); non-Tensor array leaves and host scalars come back as
    new values in the rebuilt tree — push those into their owners (e.g.
    ``optimizer.set_state_dict``). ``None`` positions that the
    checkpoint holds array subtrees for (not-yet-materialized optimizer
    moments in a fresh process) are reconstructed from the checkpoint
    metadata. ``step`` is the committed step from the generation's
    marker, or None for markers without one."""
    arrays, host = flatten_state(state)
    recon = _reconstruct_missing(arrays, host, path)
    if arrays:
        load_state_dict(arrays, path)
    for key, subkeys in recon.items():
        host[key] = _subtree_from_keys(key, subkeys, arrays)
    loaded_host = dict(host)
    try:
        with open(os.path.join(path, _HOST_FILE)) as f:
            loaded_host.update(json.load(f))
    except OSError:
        pass  # checkpoint written without host scalars (arrays only)
    rebuilt = _rebuild(state, arrays, loaded_host)
    marker = read_committed_marker(path)
    step = marker.get("step") if marker else None
    return rebuilt, (int(step) if isinstance(step, (int, float)) else None)


class AsyncCheckpointer:
    """Overlapped checkpoint writer with commit/retention semantics.

    One generation is in flight at a time: ``save`` first drains the
    previous write (bounding host memory to one snapshot), takes the
    foreground snapshot, then returns while a background thread
    serializes and commits. A failed write records
    ``checkpoint.aborted`` + a flight event and surfaces via
    :attr:`last_error` — checkpointing must never kill the training
    loop it exists to protect.
    """

    def __init__(self, root: str, keep: int = 3,
                 store=None, rank: Optional[int] = None,
                 world_size: Optional[int] = None,
                 coordinator_rank: int = 0,
                 barrier_timeout_ms: int = 120_000):
        self.root = root
        self.keep = max(1, int(keep))
        self.store = store
        self.rank = get_rank() if rank is None else rank
        self.world_size = get_world_size() if world_size is None \
            else world_size
        self.coordinator_rank = coordinator_rank
        self.barrier_timeout_ms = barrier_timeout_ms
        self.last_error: Optional[BaseException] = None
        self._pending: Optional[threading.Thread] = None
        os.makedirs(root, exist_ok=True)

    # -- save ----------------------------------------------------------------
    def generation_path(self, step: int) -> str:
        return os.path.join(self.root, f"{_GEN_PREFIX}{int(step):08d}")

    def save(self, state: Any, step: int, block: bool = False) -> str:
        """Snapshot ``state`` and commit it as generation ``step``.

        The device→host snapshot completes before this returns (safe
        against donation by the next captured step); serialization +
        fsync + commit run on a background thread unless ``block``.
        Returns the generation path (committed only once the write
        finishes — use :meth:`wait` / ``block=True`` to confirm)."""
        if not jax.core.trace_ctx.is_top_level():
            raise RuntimeError(
                "AsyncCheckpointer.save called inside a jax trace — a "
                "captured step must snapshot from replay OUTPUTS between "
                "steps, never from traced values (the donated buffers "
                "this trace consumes no longer exist at replay time)")
        self.wait()
        self.last_error = None   # reflects THIS save from here on
        t0 = time.perf_counter()
        with _tracing.span("checkpoint.snapshot",
                           attrs={"step": int(step)}) as _sp:
            arrays, host = flatten_state(state)
            payload, md = collect_shards(arrays, rank=self.rank)
        _M_SNAPSHOT.observe(time.perf_counter() - t0)
        path = self.generation_path(step)
        # hand the snapshot span's context to the writer thread: the
        # background commit joins the step's trace, not a fresh root
        tc = _sp.context if _sp.trace_id else None
        worker = threading.Thread(
            target=self._write_generation,
            args=(payload, md, dict(host), path, int(step), tc),
            name=f"ckpt-writer-{step}", daemon=True)
        self._pending = worker
        worker.start()
        if block:
            self.wait()
        return path

    def _write_generation(self, payload, md, host, path, step,
                          tc=None) -> None:
        t0 = time.perf_counter()
        with _tracing.span("checkpoint.commit", trace=tc,
                           attrs={"step": step, "path": path}):
            self._write_generation_inner(payload, md, host, path, step, t0)

    def _write_generation_inner(self, payload, md, host, path, step,
                                t0) -> None:
        try:
            write_shards(payload, md, path, rank=self.rank,
                         coordinator_rank=self.coordinator_rank)
            if self.rank == self.coordinator_rank:
                _fsync_write(os.path.join(path, _HOST_FILE),
                             lambda f: f.write(json.dumps(host).encode()))
            if self.store is not None and self.world_size > 1:
                # every rank's shards must be durable before the marker
                # certifies the generation; a dead peer times the
                # barrier out and the generation stays uncommitted
                self.store.barrier(f"ckpt/{os.path.basename(path)}",
                                   self.world_size,
                                   timeout_ms=self.barrier_timeout_ms)
            if self.rank == self.coordinator_rank:
                write_committed_marker(path, step=step,
                                       world_size=self.world_size)
                self._prune(step)
            _M_WRITE.observe(time.perf_counter() - t0)
            _M_COMMITTED.inc()
            _record("checkpoint.committed", (path, step))
        except BaseException as e:
            self.last_error = e
            _M_ABORTED.inc()
            _record("checkpoint.aborted",
                    (path, step, f"{type(e).__name__}: {e}"))

    def wait(self) -> None:
        """Drain the in-flight write (no-op when idle)."""
        w = self._pending
        if w is not None:
            w.join()
            self._pending = None

    def close(self) -> None:
        self.wait()

    # -- restore -------------------------------------------------------------
    def latest(self) -> Optional[str]:
        return latest_checkpoint(self.root)

    def restore_latest(self, state: Any) -> Tuple[Any, Optional[int]]:
        """Restore from the newest committed generation; returns
        ``(state, None)`` untouched when no generation exists."""
        path = self.latest()
        if path is None:
            return state, None
        return restore_state(state, path)

    # -- retention -----------------------------------------------------------
    def _prune(self, newest_step: int) -> None:
        """Keep the newest ``keep`` committed generations; drop older
        committed ones AND stale uncommitted directories from writers
        that died mid-save (never the generation being written now)."""
        committed = []
        for name in os.listdir(self.root):
            if not name.startswith(_GEN_PREFIX):
                continue
            sub = os.path.join(self.root, name)
            if not os.path.isdir(sub):
                continue
            try:
                dir_step = int(name[len(_GEN_PREFIX):])
            except ValueError:
                continue
            if read_committed_marker(sub) is not None:
                committed.append((dir_step, sub))
            elif dir_step < newest_step:
                shutil.rmtree(sub, ignore_errors=True)
        committed.sort(reverse=True)
        for _, sub in committed[self.keep:]:
            shutil.rmtree(sub, ignore_errors=True)
