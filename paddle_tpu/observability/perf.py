"""Performance attribution plane: the executable ledger.

Everything the process compiles — per-op executables (ops/dispatcher.py
exec cache), fused backward walks (autograd/engine.py), whole-step and
K-step captures (jit/step_capture.py, jit/multi_step.py), fused and
per-leaf optimizer updates (optimizer/optimizer.py), static-graph
programs (static/executor.py) and the serving ragged step
(models/serving.py) — registers here under its already-computed cache
key.  The ledger captures XLA ``cost_analysis()`` FLOPs/bytes and
``memory_analysis()`` arg/output/temp HBM at compile time (fail-open
when a backend lacks them) and accumulates per-executable call counts,
host dispatch wall time, and *device* time sampled by a timed
``block_until_ready`` every ``FLAGS_perf_sample_every``-th call.

From those three numbers per executable the plane derives what ops
actually needs: achieved FLOP/s, achieved bytes/s, MFU against the
roofline reference peaks, and a compute/bandwidth/host-bound
classification — published as labeled series
(``perf.executable.*{key=,kind=}``) through the metrics label/delta
machinery, so fleet workers piggyback them on heartbeats exactly like
``serving.*``.

Cost model when off/on:

* ``FLAGS_perf_attribution=False`` (default): trace-time caches whose
  keys fold ``flags.version`` (per-op exec cache, step capture, fused
  optimizer) rebuild WITHOUT any instrumentation, so their hot paths
  pay literally nothing; coarse sites (static executor, per-leaf
  optimizer, serving step) pay one flag attribute read per call.
* ``True``: every registered call pays a counter increment + two
  ``perf_counter`` reads; every Nth call additionally blocks until the
  result is ready and updates the derived gauges.  The bench gates the
  composed sampling tax at <3% of round CPU (bench_serving_fleet).

The module also owns step-time decomposition
(``perf.step.{data_wait,host_dispatch,device,other}_seconds``) wired
through hapi ``train_batch``/``fit``, the ResilientTrainer loops and
the K-block multi-step path, and the runtime perf-regression sentinel:
when a sampled executable's achieved throughput drops
``REGRESSION_DROP_PCT`` below its own session high-water mark, a
``perf.regression`` counter increments and a flight-recorder event
lands with the offender's key.
"""

from __future__ import annotations

import functools
import hashlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import flags as _flags
from . import flight_recorder as _flight
from . import metrics as _metrics

__all__ = [
    "ExecutableLedger", "ledger", "enabled", "clock", "note_data_wait",
    "timed_iter", "record_step", "step_beat", "step_seq",
    "last_step_age_s",
    "note_projection",
    "projections", "perfz_snapshot", "format_perfz", "format_table",
    "set_roofline", "reset",
]

_F_PERF = _flags._REGISTRY["perf_attribution"]
_F_EVERY = _flags._REGISTRY["perf_sample_every"]

# Host-side timestamp for ledger commit windows. Trace-confined files
# (the graftcheck trace-purity rule bans direct clock calls in
# jit/step_capture.py wholesale) time their HOST paths through this
# alias — anything inside an actual trace must not read a clock at all.
clock = time.perf_counter


def enabled() -> bool:
    """One-attribute-read gate for the whole plane."""
    return bool(_F_PERF.value)


# Per-chip peaks by jax ``device_kind``: (bf16 dense FLOP/s, HBM bytes/s).
# A kind that is not in the table gets no MFU and no roofline figure
# (None), never another part's numbers.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    # Google Cloud documentation, "TPU v5p": 459 TFLOP/s bf16, 2765 GB/s
    # — the part the AOT planner projects against
    # (distributed/auto_parallel/aot.py)
    "TPU v5": (459e12, 2765e9),
    "TPU v5p": (459e12, 2765e9),
}


@functools.lru_cache(maxsize=None)
def _peaks():
    """Peaks of the device this process runs on, resolved at first use;
    None for a device the table does not know (a CPU, say)."""
    import jax
    return DEVICE_PEAKS.get(jax.devices()[0].device_kind)

# Sentinel: fire when achieved throughput of a sampled executable drops
# more than this far below its own session high-water mark, confirmed
# by two consecutive breaching samples (one slow sample is noise; two
# in a row at -30% is a regression). Re-arms on recovery.
REGRESSION_DROP_PCT = 30.0
_SENTINEL_MIN_SAMPLES = 3
_SENTINEL_DEBOUNCE = 2

# Ledger capacity: bounds labeled-series cardinality (each entry owns
# 6 instruments). Registrations past the cap are counted and dropped.
_MAX_ENTRIES = 256

_REG = _metrics.registry()

_C_SAMPLES = _REG.counter(
    "perf.samples",
    help="timed block_until_ready device-time samples taken by the "
         "executable ledger (includes per-entry warmup samples)")
_C_REGRESSIONS = _REG.counter(
    "perf.regression",
    help="perf-regression sentinel firings: a sampled executable's "
         "achieved throughput dropped below its session high-water mark")
_C_DROPPED = _REG.counter(
    "perf.ledger.dropped",
    help="executable registrations dropped because the ledger was full")

# step-time decomposition histograms; components are defined to sum to
# the step wall exactly ("other" is the remainder), so decomposition
# never invents or loses time
_H_STEP_TOTAL = _REG.histogram(
    "perf.step.seconds", help="training step wall time (seconds)")
_H_DATA_WAIT = _REG.histogram(
    "perf.step.data_wait_seconds",
    help="per-step time blocked on the data pipeline (seconds)")
_H_HOST_DISPATCH = _REG.histogram(
    "perf.step.host_dispatch_seconds",
    help="per-step host-side dispatch time: step call until the async "
         "launch returns (seconds)")
_H_DEVICE = _REG.histogram(
    "perf.step.device_seconds",
    help="per-step device wait: launch return until results are "
         "host-visible (seconds)")
_H_OTHER = _REG.histogram(
    "perf.step.other_seconds",
    help="per-step remainder: step wall minus data_wait, host_dispatch "
         "and device (callbacks, metric reads, logging)")

_STEP_HISTS = {
    "data_wait": _H_DATA_WAIT, "host_dispatch": _H_HOST_DISPATCH,
    "device": _H_DEVICE, "other": _H_OTHER,
}


def _digest(key: Any) -> str:
    # deterministic short id from the site's cache key; repr is stable
    # enough within a process and across replicas for value-only keys
    # (keys folding id()s simply get per-process labels, which is fine —
    # fleet attribution is per-replica anyway)
    return hashlib.md5(repr(key).encode()).hexdigest()[:8]


class _Entry:
    """One compiled program's ledger row. Mutations go through the
    ledger's tick/commit under the per-entry lock."""

    __slots__ = (
        "key", "kind", "label", "compile_s", "cached",
        "flops", "bytes_accessed", "arg_bytes", "out_bytes", "temp_bytes",
        "cost_state", "_lower",
        "calls", "wall_s", "samples", "device_s", "_warmed",
        "hwm_thr", "_breach", "_fired",
        "c_calls", "g_wall", "g_dev", "g_fps", "g_bps", "g_mfu",
        "lock",
    )

    def __init__(self, key, kind, label):
        self.key = key
        self.kind = kind
        self.label = label
        self.compile_s = None
        self.cached = False      # loaded from the persistent exec store
        self.flops = None
        self.bytes_accessed = None
        self.arg_bytes = None
        self.out_bytes = None
        self.temp_bytes = None
        self.cost_state = None   # None=untried, "ok", "failed"
        self._lower = None       # zero-arg -> compiled, for lazy cost
        self.calls = 0
        self.wall_s = 0.0
        self.samples = 0
        self.device_s = 0.0
        self._warmed = False
        self.hwm_thr = 0.0
        self._breach = 0
        self._fired = False
        lab = {"key": label, "kind": kind}
        self.c_calls = _REG.counter(
            "perf.executable.calls",
            help="calls of this registered executable", labels=lab)
        self.g_wall = _REG.gauge(
            "perf.executable.wall_seconds",
            help="cumulative host dispatch wall seconds", labels=lab)
        self.g_dev = _REG.gauge(
            "perf.executable.device_seconds",
            help="cumulative sampled device seconds", labels=lab)
        self.g_fps = _REG.gauge(
            "perf.executable.flops_per_s",
            help="achieved FLOP/s over sampled calls", labels=lab)
        self.g_bps = _REG.gauge(
            "perf.executable.bytes_per_s",
            help="achieved HBM bytes/s over sampled calls", labels=lab)
        self.g_mfu = _REG.gauge(
            "perf.executable.mfu",
            help="achieved FLOP/s / roofline peak", labels=lab)
        self.lock = threading.Lock()

    # -- derived views (read-only, approximate under concurrency) ------------

    @property
    def avg_device_s(self) -> Optional[float]:
        return (self.device_s / self.samples) if self.samples else None

    def achieved(self) -> Tuple[Optional[float], Optional[float]]:
        """(flops_per_s, bytes_per_s) over sampled calls, or Nones."""
        avg = self.avg_device_s
        if not avg:
            return None, None
        fps = (self.flops / avg) if self.flops else None
        bps = (self.bytes_accessed / avg) if self.bytes_accessed else None
        return fps, bps

    def zero(self) -> None:
        """Zero the accounting window (calls/samples/time + sentinel
        state). Compile-time facts — cost model, compile_s, warmup —
        persist: they describe the executable, not the window."""
        with self.lock:
            self.calls = 0
            self.wall_s = 0.0
            self.samples = 0
            self.device_s = 0.0
            self.hwm_thr = 0.0
            self._breach = 0
            self._fired = False
        self.c_calls._reset()
        for g in (self.g_wall, self.g_dev, self.g_fps,
                  self.g_bps, self.g_mfu):
            g._reset()

    def bound(self) -> str:
        """compute / bandwidth / host / unknown classification."""
        peaks = _peaks()
        if peaks is None or (not self.flops and not self.bytes_accessed):
            return "unknown"
        t_c = (self.flops or 0.0) / peaks[0]
        t_m = (self.bytes_accessed or 0.0) / peaks[1]
        avg = self.avg_device_s
        if avg is not None and avg > 3.0 * max(t_c, t_m, 1e-12):
            return "host"
        return "compute" if t_c >= t_m else "bandwidth"


def _resolve_cost(e: _Entry) -> None:
    """Lazily pull cost/memory analysis for an entry, at most once.
    May compile (sites with donated buffers hand us avals, not the live
    executable) — only ever called from report paths, never hot ones."""
    with e.lock:
        if e.cost_state is not None:
            return
        e.cost_state = "failed"   # fail-open: one attempt, then stop
        lower = e._lower
    try:
        compiled = lower() if callable(lower) else lower
        if compiled is None:
            return
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0))
        traffic = float(cost.get("bytes accessed", 0.0))
        mem = compiled.memory_analysis()
        with e.lock:
            e.flops = flops or None
            e.bytes_accessed = traffic or None
            e.arg_bytes = int(getattr(mem, "argument_size_in_bytes", 0))
            e.out_bytes = int(getattr(mem, "output_size_in_bytes", 0))
            e.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0))
            e.cost_state = "ok"
    except Exception:
        pass   # fail-open by contract: no cost model, attribution still counts


class ExecutableLedger:
    """Registry of every compiled program the process runs.

    Sites call :meth:`register` once per compile (under their own cache
    key), then either wrap the executable with :meth:`wrap` or drive
    :meth:`tick`/:meth:`commit` around their existing call/timing
    structure. All paths are no-ops when ``FLAGS_perf_attribution`` is
    off.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[Any, _Entry] = {}

    # -- registration --------------------------------------------------------

    def register(self, key: Any, kind: str, name: str = "",
                 lower: Any = None, compile_s: Optional[float] = None
                 ) -> Optional[_Entry]:
        """Get-or-create the ledger row for ``key``.

        ``lower`` is either the compiled/jitted object itself or a
        zero-arg callable producing one (for donated-buffer sites that
        must snapshot avals before the first launch); cost analysis is
        resolved from it lazily at report time. Returns None when the
        plane is off or the ledger is full — callers treat that as
        "don't instrument".
        """
        if not _F_PERF.value:
            return None
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                if len(self._entries) >= _MAX_ENTRIES:
                    _C_DROPPED.inc()
                    return None
                label = (f"{name}:{_digest(key)}" if name
                         else f"{kind}:{_digest(key)}")
                e = _Entry(key, kind, label)
                self._entries[key] = e
        if lower is not None and e._lower is None:
            e._lower = lower
        if compile_s is not None and e.compile_s is None:
            e.compile_s = compile_s
        return e

    def entry(self, key: Any) -> Optional[_Entry]:
        with self._lock:
            return self._entries.get(key)

    def mark_cached(self, key: Any, load_s: Optional[float] = None) -> None:
        """Flag ``key``'s row as deserialized from the persistent exec
        store (jit/exec_store.py) rather than compiled; ``load_s``
        stands in for compile_seconds so /perfz totals stay meaningful.
        No-op when the plane is off or the key was never registered."""
        e = self.entry(key)
        if e is None:
            return
        e.cached = True
        if load_s is not None and e.compile_s is None:
            e.compile_s = load_s

    def entries(self) -> List[_Entry]:
        with self._lock:
            return list(self._entries.values())

    def __len__(self) -> int:
        return len(self._entries)

    # -- call accounting -----------------------------------------------------

    def tick(self, e: _Entry) -> bool:
        """Count a call; True when THIS call should be device-timed.
        Call 1 is always timed but treated as warmup (its ready time
        includes the XLA compile), call 2 is the first real sample,
        then every ``FLAGS_perf_sample_every``-th call."""
        if e is None or not _F_PERF.value:
            return False
        with e.lock:
            e.calls += 1
            n = e.calls
        e.c_calls.inc()
        every = _F_EVERY.value or 1
        return n <= 2 or n % every == 0

    def commit(self, e: _Entry, wall_s: float,
               ready_s: Optional[float] = None) -> None:
        """Fold one call's timings in. ``wall_s`` is the host dispatch
        wall (async launch); ``ready_s``, when the call was sampled, is
        launch-to-results-ready — the device-time estimate."""
        if e is None or not _F_PERF.value:
            return
        fire = None
        with e.lock:
            e.wall_s += wall_s
            if ready_s is None:
                return
            if not e._warmed:
                # warmup sample: first ready time of a fresh executable
                # includes its compile — record it as that, never as a
                # device sample (it would wreck achieved throughput)
                e._warmed = True
                if e.compile_s is None:
                    e.compile_s = ready_s
            else:
                e.samples += 1
                e.device_s += ready_s
                thr = (e.flops or 1.0) / max(ready_s, 1e-9)
                if thr > e.hwm_thr:
                    e.hwm_thr = thr
                    e._breach = 0
                elif (e.samples >= _SENTINEL_MIN_SAMPLES and
                      thr < e.hwm_thr * (1.0 - REGRESSION_DROP_PCT / 100.0)):
                    e._breach += 1
                    if e._breach >= _SENTINEL_DEBOUNCE and not e._fired:
                        e._fired = True
                        fire = (e.label, thr, e.hwm_thr)
                else:
                    e._breach = 0
                    e._fired = False   # recovered: re-arm
            wall, dev = e.wall_s, e.device_s
        _C_SAMPLES.inc()
        # derived gauges refresh only on sampled calls — bounded tax
        e.g_wall.set(wall)
        e.g_dev.set(dev)
        fps, bps = e.achieved()
        if fps is not None:
            e.g_fps.set(fps)
            if _peaks() is not None:
                e.g_mfu.set(fps / _peaks()[0])
        if bps is not None:
            e.g_bps.set(bps)
        if fire is not None:
            label, thr, hwm = fire
            _C_REGRESSIONS.inc()
            _flight.record_event(
                "perf.regression",
                (label, f"thr={thr:.3g}", f"hwm={hwm:.3g}",
                 f"drop>{REGRESSION_DROP_PCT:.0f}%"))
            # forensics: bundle the ledger + stacks while the slow
            # executable is still resident (lazy import — incident pulls
            # perfz_snapshot from here at assembly time)
            from . import incident as _incident
            _incident.record_incident(
                "perf.regression",
                attrs={"label": label, "throughput": thr,
                       "high_water_mark": hwm,
                       "drop_pct": REGRESSION_DROP_PCT})

    def wrap(self, key: Any, kind: str, fn: Callable, name: str = "",
             lower: Any = None) -> Callable:
        """Instrumented wrapper around a compiled callable. When the
        plane is off at wrap time the original is returned unchanged —
        the zero-cost path for caches keyed on ``flags.version``."""
        e = self.register(key, kind, name=name, lower=lower)
        if e is None:
            return fn

        def timed(*args, **kwargs):
            if not _F_PERF.value:
                return fn(*args, **kwargs)
            if e._lower is None and hasattr(fn, "lower"):
                # snapshot avals BEFORE the launch (donation may retire
                # the live buffers) so cost analysis can lower+compile
                # lazily at report time; fail-open on non-array args
                try:
                    import jax
                    avals = jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        (args, kwargs))
                    e._lower = (lambda f=fn, av=avals:
                                f.lower(*av[0], **av[1]).compile())
                except Exception:
                    e._lower = False   # tried and failed: don't retry
            sample = self.tick(e)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            wall = time.perf_counter() - t0
            ready = None
            if sample:
                try:
                    import jax
                    jax.block_until_ready(out)
                    ready = time.perf_counter() - t0
                except Exception:
                    pass   # sample lost, call still counted — fail-open
            self.commit(e, wall, ready)
            return out

        return timed

    # -- reporting -----------------------------------------------------------

    def stats(self, resolve_cost: bool = True) -> List[Dict[str, Any]]:
        """Plain-dict rows, sorted by cumulative device time desc."""
        rows = []
        peaks = _peaks()
        for e in self.entries():
            if not e.calls:
                continue   # registered but idle (or zeroed by reset())
            if resolve_cost:
                _resolve_cost(e)
            fps, bps = e.achieved()
            avg = e.avg_device_s
            row = {
                "key": e.label, "kind": e.kind, "calls": e.calls,
                "samples": e.samples,
                "compile_seconds": e.compile_s,
                "cached": e.cached,
                "flops": e.flops, "bytes_accessed": e.bytes_accessed,
                "hbm": {"arg_bytes": e.arg_bytes,
                        "out_bytes": e.out_bytes,
                        "temp_bytes": e.temp_bytes},
                "wall_seconds": round(e.wall_s, 6),
                "device_seconds": round(e.device_s, 6),
                "avg_device_seconds": round(avg, 9) if avg else None,
                "achieved_flops_per_s": fps,
                "achieved_bytes_per_s": bps,
                "mfu": (fps / peaks[0]) if (fps and peaks) else None,
                "bound": e.bound(),
            }
            if peaks and (e.flops or e.bytes_accessed):
                # the same roofline the AOT planner projects: what the
                # hardware allows vs what sampling measured
                t_c = (e.flops or 0.0) / peaks[0]
                t_m = (e.bytes_accessed or 0.0) / peaks[1]
                proj = max(t_c, t_m)
                row["roofline"] = {
                    "compute_seconds": t_c, "memory_seconds": t_m,
                    "projected_step_seconds": proj,
                    "attainment": (proj / avg) if (avg and proj) else None,
                }
            rows.append(row)
        rows.sort(key=lambda r: r["device_seconds"], reverse=True)
        return rows

    def reset(self) -> None:
        """Zero every entry IN PLACE. Entries are never dropped: the op
        exec-cache is shape-agnostic and long-lived, so live wrapped
        executables hold their entry reference across a reset and keep
        committing to it — dropping the row would orphan those commits
        forever. Zero-call rows are hidden from :meth:`stats` instead.
        Test/bench hygiene only."""
        with self._lock:
            entries = list(self._entries.values())
        for e in entries:
            e.zero()


_LEDGER = ExecutableLedger()


def ledger() -> ExecutableLedger:
    return _LEDGER


# -- step-time decomposition ---------------------------------------------------
#
# The training loop is effectively single-threaded per process, so a
# module slot + tiny lock carries the pending data-wait between the
# loader boundary (hapi fit / ResilientTrainer next_batch) and the step
# that consumes the batch.

_step_lock = threading.Lock()
_pending_data_wait = 0.0
_last_step_t: Optional[float] = None
_proc_t0 = time.monotonic()
_step_seq = 0   # bumps on every record_step: outer loops detect nesting


def step_beat() -> None:
    """Unconditional liveness beat: /statusz's last-step-progress age
    reads this, so stale-step detection works even with the perf plane
    off. One monotonic read per step."""
    global _last_step_t
    _last_step_t = time.monotonic()


def last_step_age_s() -> Optional[float]:
    """Seconds since the last training-step beat; None before any."""
    t = _last_step_t
    return (time.monotonic() - t) if t is not None else None


def process_uptime_s() -> float:
    return time.monotonic() - _proc_t0


def note_data_wait(seconds: float) -> None:
    """Attribute loader-blocked time to the NEXT recorded step."""
    global _pending_data_wait
    if not _F_PERF.value:
        return
    with _step_lock:
        _pending_data_wait += seconds


def step_seq() -> int:
    """Monotone count of record_step() calls. An outer driver (e.g.
    ResilientTrainer) compares it across its step callable to tell
    whether the inner step already self-reported — if not, the driver
    records the wall total itself instead of double-counting."""
    return _step_seq


def timed_iter(iterable):
    """Wrap a data loader (or block generator): time blocked inside
    ``next()`` is attributed to the NEXT recorded step's data_wait."""
    it = iter(iterable)
    while True:
        t0 = time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            return
        note_data_wait(time.perf_counter() - t0)
        yield item


def record_step(total_s: float, host_s: float = 0.0,
                device_s: float = 0.0) -> None:
    """Decompose one step (or one K-step block) of wall time.

    ``other = total - data_wait - host - device`` by construction, so
    the four components sum to the step wall exactly. Emits the
    ``perf.step.*`` histograms.
    """
    global _pending_data_wait, _step_seq
    step_beat()
    _step_seq += 1
    if not _F_PERF.value:
        return
    with _step_lock:
        data_wait = _pending_data_wait
        _pending_data_wait = 0.0
    data_wait = min(data_wait, total_s)
    # host dispatch and launch-to-ready are measured as overlapping
    # intervals; on tiny graphs their sum can exceed the step wall.
    # Clamp in priority order so the documented invariant (components
    # sum to the wall EXACTLY) survives the overlap artifact.
    host_s = min(host_s, total_s - data_wait)
    device_s = min(device_s, total_s - data_wait - host_s)
    other = max(0.0, total_s - data_wait - host_s - device_s)
    _H_STEP_TOTAL.observe(total_s)
    _H_DATA_WAIT.observe(data_wait)
    _H_HOST_DISPATCH.observe(host_s)
    _H_DEVICE.observe(device_s)
    _H_OTHER.observe(other)


def step_summary() -> Dict[str, Any]:
    """count/sum/avg/p50/p99 per decomposition component (+ total)."""
    out: Dict[str, Any] = {}
    for part, h in dict(_STEP_HISTS, total=_H_STEP_TOTAL).items():
        s = h.snapshot()
        out[part] = {
            "count": s["count"], "sum": round(s["sum"], 6),
            "avg": s["avg"], "p50": h.quantile(0.5),
            "p99": h.quantile(0.99),
        }
    return out


# -- AOT roofline join ---------------------------------------------------------

_projections: Dict[str, Dict[str, Any]] = {}


def note_projection(name: str, projected: Dict[str, Any]) -> None:
    """Record an AOT plan's projected roofline (aot.projected_throughput
    output) so /perfz can show achieved-vs-projected side by side."""
    with _step_lock:
        _projections[name] = dict(projected)


def projections() -> Dict[str, Dict[str, Any]]:
    with _step_lock:
        return dict(_projections)


# -- reports -------------------------------------------------------------------

def perfz_snapshot(top: int = 20, resolve_cost: bool = True
                   ) -> Dict[str, Any]:
    """The /perfz payload: top-K executables by cumulative device time
    with cost/memory stats and roofline attainment, the step-time
    decomposition, registered AOT projections and sentinel state."""
    rows = _LEDGER.stats(resolve_cost=resolve_cost and enabled())
    return {
        "enabled": enabled(),
        "sample_every": int(_F_EVERY.value or 1),
        "executables": rows[:top],
        "total_executables": len(rows),
        "step": step_summary(),
        "projections": projections(),
        "regressions": _C_REGRESSIONS.value,
        "samples": _C_SAMPLES.value,
        "dropped": _C_DROPPED.value,
    }


def _fmt(v, unit: str = "") -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        if abs(v) >= 1e9:
            return f"{v:.3g}{unit}"
        return f"{v:.4g}{unit}"
    return f"{v}{unit}"


def format_table(rows: Optional[List[Dict[str, Any]]] = None,
                 title: str = "Device executables") -> str:
    """Human table of ledger rows (profiler.summary / CLI view).
    Empty string when the ledger has nothing — callers print nothing."""
    if rows is None:
        rows = _LEDGER.stats(resolve_cost=enabled())
    if not rows:
        return ""
    cols = ("Key", "Kind", "Calls", "Device s", "Avg ms", "GFLOP/s",
            "MFU", "Bound")
    body = []
    for r in rows:
        avg = r["avg_device_seconds"]
        fps = r["achieved_flops_per_s"]
        body.append((
            r["key"], r["kind"], str(r["calls"]),
            _fmt(r["device_seconds"]),
            _fmt(avg * 1e3 if avg is not None else None),
            _fmt(fps / 1e9 if fps is not None else None),
            _fmt(r["mfu"]), r["bound"]))
    widths = [max(len(c), *(len(b[i]) for b in body)) + 2
              for i, c in enumerate(cols)]
    hdr = "".join(c.ljust(w) for c, w in zip(cols, widths)).rstrip()
    width = max(len(hdr), sum(widths))
    lines = ["-" * width, title, "-" * width, hdr, "-" * width]
    for b in body:
        lines.append("".join(v.ljust(w)
                             for v, w in zip(b, widths)).rstrip())
    lines.append("-" * width)
    return "\n".join(lines)


def format_perfz(snap: Optional[Dict[str, Any]] = None) -> str:
    """CLI rendering of the /perfz payload."""
    if snap is None:
        snap = perfz_snapshot()
    lines = [f"perf_attribution={'on' if snap['enabled'] else 'off'} "
             f"sample_every={snap['sample_every']} "
             f"samples={snap['samples']} regressions={snap['regressions']}"]
    tbl = format_table(snap["executables"])
    lines.append(tbl if tbl else "(no executables registered — set "
                 "FLAGS_perf_attribution=True and run a step)")
    step = snap["step"]
    if step["total"]["count"]:
        lines.append("Step decomposition (seconds):")
        for part in ("data_wait", "host_dispatch", "device", "other",
                     "total"):
            s = step[part]
            lines.append(
                f"  {part:<14} count={s['count']:<6} sum={s['sum']:<10} "
                f"avg={_fmt(s['avg'])} p99={_fmt(s['p99'])}")
    for name, proj in snap["projections"].items():
        lines.append(f"AOT projection [{name}]: "
                     f"step={proj.get('step_seconds')}s "
                     f"bound={proj.get('bound')} "
                     f"mfu_ub={proj.get('mfu_upper_bound')}")
    return "\n".join(lines)


def reset() -> None:
    """Full plane reset (ledger entries, pending decomposition state,
    projections). Test/bench hygiene only."""
    global _pending_data_wait, _last_step_t
    _LEDGER.reset()
    with _step_lock:
        _pending_data_wait = 0.0
        _projections.clear()
    for _h in list(_STEP_HISTS.values()) + [_H_STEP_TOTAL]:
        _h._reset()
    _last_step_t = None
