"""Persistent executable + AOT-plan cache: the on-disk cache spine.

Every process used to recompile every executable from scratch — fleet
relaunch MTTR was compile-bound, a rolling deploy paid N cold ragged
compiles, and the v5p AOT planner repeated minutes-long compiles per
process.  This module is the shared spine the five private in-process
caches (dispatcher exec-cache, fused-backward planner, step-capture /
multi-step structure cache, static executor, AOT planner) persist
through.

Keying
------
An entry's identity is the sha256 digest of the **lowered StableHLO
text** plus a stable environment fingerprint.  Lowering (tracing) is
cheap and always happens; only the XLA compile is skipped on a hit, so
a wrong hit is structurally impossible — the digest *is* the program.
The environment fingerprint folds in:

* jax / jaxlib / framework versions (toolchain bump = full invalidation)
* a stable flags fingerprint: sha256 over sorted ``(name, repr(value))``
  pairs plus the mesh epoch.  ``flags.version`` itself is a salted
  per-process ``hash()`` and must never reach disk.
* the store *scope* — the serving model-weights fingerprint
  (``serving/resilience``), so a store attached to the wrong weights
  refuses its entries.

Layout & durability
-------------------
``<root>/<kind>/<digest16>-<uid>/{payload.bin, COMMITTED}`` — every
write rides :mod:`paddle_tpu.utils.durability` (tmp+fsync+rename, then
a COMMITTED marker carrying the payload sha256).  Entry directories are
fenced by a per-process uid like journal segments, so concurrent
writers of the same program land in distinct directories and a reader
never sees a torn entry.  A corrupt or truncated entry is a miss plus a
flight-recorder event, never a crash.  Retention is keep-K committed
entries per kind.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil
import threading
import time
import uuid
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from .. import flags as _flags
from ..observability import flight_recorder as _flight
from ..observability import metrics as _metrics
from ..observability import tracing as _tracing
from ..utils.durability import (COMMIT_FILE, fsync_write,
                                read_committed_marker,
                                write_committed_marker)

from jax.experimental import serialize_executable as _se

_flags.define_flag(
    "exec_cache_dir", "",
    "root directory of the persistent executable cache (exec_store); "
    "empty disables persistence")
_flags.define_flag(
    "exec_cache_keep", 64,
    "committed entries retained per kind in the persistent executable "
    "cache (keep-K, oldest pruned)")

_F_DIR = _flags._REGISTRY["exec_cache_dir"]
_F_KEEP = _flags._REGISTRY["exec_cache_keep"]

_M_HITS = _metrics.registry().counter(
    "jit.cache.hits", "persistent executable cache: disk hits")
_M_MISSES = _metrics.registry().counter(
    "jit.cache.misses", "persistent executable cache: disk misses")
_M_BYTES = _metrics.registry().counter(
    "jit.cache.bytes", "persistent executable cache: payload bytes "
    "loaded from disk")
_H_LOAD = _metrics.registry().histogram(
    "jit.cache.load_seconds", "persistent executable cache: wall "
    "seconds spent deserializing one entry")

# schema version of the on-disk format itself: bump to orphan every
# existing entry when the payload encoding changes
_STORE_SCHEMA = 1

# per-process uid fencing entry directories (concurrent writers of the
# same digest commit into distinct dirs; readers take any committed one)
_UID = uuid.uuid4().hex[:8]

_PAYLOAD = "payload.bin"
_DEBRIS_GRACE_S = 900.0
_MEMO_CAP = 64


def flags_fingerprint() -> str:
    """Stable cross-process stand-in for ``flags.version``: sha256 over
    the sorted flag values plus the mesh epoch (``hash()`` is salted
    per process and must never key a disk entry)."""
    h = hashlib.sha256()
    h.update(b"mesh_epoch=%d\n" % _flags._mesh_epoch)
    for name in sorted(_flags._REGISTRY):
        if name in ("exec_cache_dir", "exec_cache_keep"):
            continue  # the cache's own knobs don't change programs
        h.update(("%s=%r\n" % (name, _flags._REGISTRY[name].value)).encode())
    return h.hexdigest()


def _canon(part: Any) -> str:
    """Canonical stable string for one key part."""
    if isinstance(part, bytes):
        return "b:" + hashlib.sha256(part).hexdigest()
    if isinstance(part, (tuple, list)):
        return "(" + ",".join(_canon(p) for p in part) + ")"
    return repr(part)


class ExecStore:
    """One on-disk cache root; see module docstring for layout."""

    def __init__(self, root: str, scope: str = "",
                 keep: Optional[int] = None):
        self.root = os.path.abspath(root)
        self.scope = scope
        self.keep = int(_F_KEEP.value) if keep is None else int(keep)
        self._lock = threading.Lock()
        # local mirrors for /statusz (global counters are cumulative
        # across attach/detach cycles)
        self.hits = 0
        self.misses = 0
        self.loaded_bytes = 0
        self.written = 0

    # -- keying ------------------------------------------------------

    def env_fingerprint(self) -> str:
        h = hashlib.sha256()
        h.update(("schema=%d\njax=%s\njaxlib=%s\nfw=%s\nflags=%s\n"
                  "scope=%s\n" % (
                      _STORE_SCHEMA, jax.__version__, _jaxlib_version(),
                      _framework_version(), flags_fingerprint(),
                      self.scope)).encode())
        return h.hexdigest()

    def key_digest(self, kind: str, parts: Tuple[Any, ...]) -> str:
        h = hashlib.sha256()
        h.update(self.env_fingerprint().encode())
        h.update(("\nkind=%s\n" % kind).encode())
        h.update(_canon(tuple(parts)).encode())
        return h.hexdigest()

    # -- layout ------------------------------------------------------

    def _kind_dir(self, kind: str) -> str:
        return os.path.join(self.root, kind)

    def _entry_dir(self, kind: str, digest: str) -> str:
        return os.path.join(self._kind_dir(kind),
                            "%s-%s" % (digest[:32], _UID))

    def _candidates(self, kind: str, digest: str):
        kd = self._kind_dir(kind)
        try:
            names = sorted(os.listdir(kd))
        except OSError:
            return
        for name in names:
            if name.startswith(digest[:32] + "-"):
                yield os.path.join(kd, name)

    # -- read side ---------------------------------------------------

    def get(self, kind: str, parts: Tuple[Any, ...]
            ) -> Optional[Tuple[bytes, Dict[str, Any]]]:
        """Return ``(payload, marker)`` for a committed, checksum-clean
        entry, else ``None``.  Corrupt entries are a miss plus a flight
        event — never a crash."""
        digest = self.key_digest(kind, parts)
        for path in self._candidates(kind, digest):
            marker = read_committed_marker(path)
            if marker is None:
                continue
            try:
                with open(os.path.join(path, _PAYLOAD), "rb") as f:
                    payload = f.read()
            except OSError:
                _flight.record_event(
                    "jit.cache.corrupt", (kind, digest[:16], "unreadable"))
                continue
            if hashlib.sha256(payload).hexdigest() != \
                    marker.get("payload_sha256"):
                _flight.record_event(
                    "jit.cache.corrupt", (kind, digest[:16], "checksum"))
                continue
            with self._lock:
                self.hits += 1
                self.loaded_bytes += len(payload)
            _M_HITS.inc()
            _M_BYTES.inc(len(payload))
            return payload, marker
        with self._lock:
            self.misses += 1
        _M_MISSES.inc()
        return None

    def get_json(self, kind: str, parts: Tuple[Any, ...]
                 ) -> Optional[Dict[str, Any]]:
        got = self.get(kind, parts)
        if got is None:
            return None
        payload, _ = got
        try:
            obj = json.loads(payload.decode("utf-8"))
        except Exception:
            _flight.record_event(
                "jit.cache.corrupt",
                (kind, self.key_digest(kind, parts)[:16], "json"))
            return None
        return obj if isinstance(obj, dict) else None

    # -- write side (commit protocol only) ---------------------------

    def put(self, kind: str, parts: Tuple[Any, ...], payload: bytes,
            **meta: Any) -> bool:
        """Commit one entry (tmp+fsync+rename, then COMMITTED marker
        with the payload checksum).  Best-effort: returns False and
        records a flight event on any I/O failure."""
        digest = self.key_digest(kind, parts)
        path = self._entry_dir(kind, digest)
        try:
            os.makedirs(path, exist_ok=True)
            fsync_write(os.path.join(path, _PAYLOAD),
                        lambda f: f.write(payload))
            write_committed_marker(
                path, payload_sha256=hashlib.sha256(payload).hexdigest(),
                nbytes=len(payload), kind=kind, digest=digest, **meta)
        except OSError:
            _flight.record_event(
                "jit.cache.write_failed", (kind, digest[:16]))
            return False
        with self._lock:
            self.written += 1
        self._prune(kind)
        return True

    def put_json(self, kind: str, parts: Tuple[Any, ...],
                 obj: Dict[str, Any], **meta: Any) -> bool:
        return self.put(kind, parts,
                        json.dumps(obj, sort_keys=True).encode("utf-8"),
                        **meta)

    def _prune(self, kind: str) -> None:
        """Keep-K committed entries per kind; foreign uncommitted
        debris is swept only after a grace window (a concurrent writer
        may be mid-commit)."""
        kd = self._kind_dir(kind)
        try:
            names = os.listdir(kd)
        except OSError:
            return
        committed = []
        now = time.time()
        for name in names:
            path = os.path.join(kd, name)
            marker = os.path.join(path, COMMIT_FILE)
            try:
                committed.append((os.path.getmtime(marker), path))
            except OSError:
                # uncommitted: ours never linger (commit follows put
                # immediately); a foreign writer gets a grace window
                if not name.endswith("-" + _UID):
                    try:
                        if now - os.path.getmtime(path) > _DEBRIS_GRACE_S:
                            shutil.rmtree(path, ignore_errors=True)
                    except OSError:
                        pass  # racing writer finished/removed it: fine
        committed.sort()
        for _, path in committed[:max(0, len(committed) - self.keep)]:
            shutil.rmtree(path, ignore_errors=True)

    # -- introspection ----------------------------------------------

    def state(self) -> Dict[str, Any]:
        entries = 0
        kinds: Dict[str, int] = {}
        try:
            for kind in sorted(os.listdir(self.root)):
                kd = os.path.join(self.root, kind)
                if not os.path.isdir(kd):
                    continue
                n = sum(
                    1 for name in os.listdir(kd)
                    if os.path.exists(os.path.join(kd, name, COMMIT_FILE)))
                kinds[kind] = n
                entries += n
        except OSError:
            pass  # store root vanished underneath us: report what we have
        return {"dir": self.root, "scope": self.scope[:16],
                "keep": self.keep, "entries": entries, "kinds": kinds,
                "hits": self.hits, "misses": self.misses,
                "loaded_bytes": self.loaded_bytes,
                "written": self.written}


def _jaxlib_version() -> str:
    try:
        import jaxlib
        return getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover  # fail-open: fold "?" into fp
        return "?"


def _framework_version() -> str:
    try:
        from .. import __version__
        return __version__
    except Exception:  # pragma: no cover  # circular-import fallback
        return "?"


# ---------------------------------------------------------------------
# module-level store resolution: an explicit attach() wins, else the
# FLAGS_exec_cache_dir flag drives a memoized instance
# ---------------------------------------------------------------------

_ATTACHED: Optional[ExecStore] = None
_FLAG_STORE: Optional[ExecStore] = None
_RESOLVE_LOCK = threading.Lock()


def attach(root: str, scope: str = "",
           keep: Optional[int] = None) -> ExecStore:
    """Attach a store explicitly (e.g. the serving engine scoping the
    cache to its model-weights fingerprint).  Overrides the flag."""
    global _ATTACHED
    st = ExecStore(root, scope=scope, keep=keep)
    with _RESOLVE_LOCK:
        _ATTACHED = st
    return st


def detach() -> None:
    global _ATTACHED
    with _RESOLVE_LOCK:
        _ATTACHED = None


def store() -> Optional[ExecStore]:
    """The active store, or ``None`` when persistence is off."""
    global _FLAG_STORE
    with _RESOLVE_LOCK:
        if _ATTACHED is not None:
            return _ATTACHED
        root = _F_DIR.value
        if not root:
            return None
        if _FLAG_STORE is None or _FLAG_STORE.root != os.path.abspath(root):
            _FLAG_STORE = ExecStore(root)
        return _FLAG_STORE


def state() -> Optional[Dict[str, Any]]:
    st = store()
    return None if st is None else st.state()


# ---------------------------------------------------------------------
# the persistent-executable wrapper the five cache sites ride
# ---------------------------------------------------------------------

def _aval_sig(args: Tuple[Any, ...]) -> Tuple[Any, ...]:
    leaves, treedef = jax.tree_util.tree_flatten(args)
    import jax.numpy as jnp
    return (treedef,
            tuple((jnp.shape(l), str(jnp.result_type(l))) for l in leaves))


class PersistentJit:
    """Wrap a ``jax.jit`` callable with the disk cache: lower always
    (tracing is cheap and trace errors must propagate unchanged),
    compile only on a disk miss.  When no store is active at call time
    the underlying jit function runs untouched."""

    __slots__ = ("_jfn", "_kind", "_label", "_perf_key", "_extra",
                 "_memo", "_lock")

    def __init__(self, jfn: Callable, kind: str, label: str = "",
                 perf_key: Any = None, extra: Tuple[Any, ...] = ()):
        self._jfn = jfn
        self._kind = kind
        self._label = label or kind
        self._perf_key = perf_key
        self._extra = tuple(extra)
        self._memo: Dict[Any, Callable] = {}
        self._lock = threading.Lock()

    def lower(self, *args, **kwargs):
        # the perf ledger's lazy cost analysis reaches through here
        return self._jfn.lower(*args, **kwargs)

    def __call__(self, *args):
        if any(isinstance(l, jax.core.Tracer)
               for l in jax.tree_util.tree_leaves(args)):
            # under an ambient trace (step capture, an outer jit) a
            # loaded Compiled cannot be called — inline the jit fn, the
            # OUTER program owns the compile and the cache entry
            return self._jfn(*args)
        return self.executable(*args)(*args)

    def executable(self, *args) -> Callable:
        """What a call with ``args`` (arrays or their shapes) runs: loaded
        from the store or compiled, once a signature. Nothing runs."""
        sig = _aval_sig(args)
        fn = self._memo.get(sig)
        if fn is None:
            fn = self._resolve(sig, args)
        return fn

    def _resolve(self, sig, args) -> Callable:
        with self._lock:
            fn = self._memo.get(sig)
            if fn is not None:
                return fn
            fn = self._load_or_compile(args)
            if len(self._memo) >= _MEMO_CAP:
                self._memo.clear()
            self._memo[sig] = fn
            return fn

    def _load_or_compile(self, args) -> Callable:
        st = store()
        if st is None:
            return self._jfn
        lowered = self._jfn.lower(*args)  # trace errors propagate
        try:
            hlo = lowered.as_text().encode("utf-8")
        except Exception:
            # backend refuses a textual dump -> no stable key, no
            # persistence for this program (fail-open by design)
            _flight.record_event(
                "jit.cache.skip", (self._kind, self._label, "as_text"))
            return self._jfn
        parts = self._extra + (hashlib.sha256(hlo).hexdigest(),)
        got = st.get(self._kind, parts)
        if got is not None:
            fn = self._deserialize(got[0])
            if fn is not None:
                return fn
        try:
            compiled = lowered.compile()
        except Exception:
            # compile failed through the AOT path: let the plain jit
            # call surface the real error with its own diagnostics
            return self._jfn
        self._serialize_put(st, parts, compiled)
        return compiled

    def _deserialize(self, payload: bytes) -> Optional[Callable]:
        t0 = time.perf_counter()
        try:
            with _tracing.span("jit.cache.load",
                               attrs={"kind": self._kind,
                                      "label": self._label}):
                blob, device_ids = pickle.loads(payload)
                # onto the executable's own devices: left to itself
                # deserialize_and_load takes every device of the
                # backend, and a one-device program loaded onto eight
                # then fails when it is called
                by_id = {d.id: d for d in jax.devices()}
                fn = _se.deserialize_and_load(
                    *blob,
                    execution_devices=[by_id[i] for i in device_ids])
        except Exception:
            _flight.record_event(
                "jit.cache.corrupt", (self._kind, self._label,
                                      "deserialize"))
            return None
        dt = time.perf_counter() - t0
        _H_LOAD.observe(dt)
        if self._perf_key is not None:
            from ..observability import perf as _perf
            _perf.ledger().mark_cached(self._perf_key, load_s=dt)
        return fn

    def _serialize_put(self, st: ExecStore, parts, compiled) -> None:
        try:
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            payload = pickle.dumps((_se.serialize(compiled), device_ids))
        except Exception:
            # backend refuses serialization (e.g. no PjRt executable
            # serialization support): fail open, keep the compiled fn
            _flight.record_event(
                "jit.cache.skip", (self._kind, self._label, "serialize"))
            return
        st.put(self._kind, parts, payload, label=self._label)


def persistent(jfn: Callable, kind: str, label: str = "",
               perf_key: Any = None,
               extra: Tuple[Any, ...] = ()) -> Callable:
    """Wrap ``jfn`` for disk persistence when a store is active at wrap
    time; otherwise return it unchanged (zero overhead off-path).  Cache
    sites keyed on ``flags.version`` re-wrap automatically after a flag
    mutation attaches the store."""
    if store() is None:
        return jfn
    return PersistentJit(jfn, kind, label=label, perf_key=perf_key,
                         extra=extra)
