"""Process-wide runtime flag registry.

TPU-native analog of the reference's exported-flags system
(paddle/common/flags.cc:31 `PHI_DEFINE_EXPORTED_*`, ~135 flags with `FLAGS_*`
env override, surfaced to Python via `paddle.set_flags`/`get_flags`).

The registry is dual-homed: the Python dict is authoritative for the eager
layer, and every definition/mutation is mirrored into the native C++ registry
(csrc/flags.cc, bound via paddle_tpu.native) once that library loads, so C++
runtime components read the same flags. Flags may be seeded from the
environment (`FLAGS_<name>=...`) and mutated at runtime via :func:`set_flags`.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass
class _Flag:
    name: str
    default: Any
    help: str
    ctype: type
    value: Any = None


_REGISTRY: Dict[str, _Flag] = {}
_NATIVE = None  # ctypes lib once paddle_tpu.native loads
# per-flag mutation callbacks: fn(new_value) after set_flags commits —
# for components that materialize a flag's value at import time (e.g.
# the flight recorder ring sized by FLAGS_flight_recorder_size)
_ON_SET: Dict[str, list] = {}


def on_set(name: str, fn: Callable[[Any], None]) -> None:
    """Register a callback invoked with the new value whenever `name`
    is mutated via set_flags."""
    _ON_SET.setdefault(name.removeprefix("FLAGS_"), []).append(fn)


def _mirror_one(lib, f: "_Flag") -> None:
    ctype_name = {bool: "bool", int: "int", float: "double"}.get(
        f.ctype, "string")
    lib.PT_RegisterFlag(f.name.encode(), ctype_name.encode(),
                        str(f.default).encode(), f.help.encode())
    lib.PT_SetFlag(f.name.encode(), str(f.value).encode())


def _mirror_native(lib):
    global _NATIVE
    _NATIVE = lib
    for f in _REGISTRY.values():
        _mirror_one(lib, f)


def _parse_env(raw: str, ctype: type) -> Any:
    if ctype is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return ctype(raw)


def define_flag(name: str, default: Any, help: str = "") -> None:
    """Register a flag; environment variable ``FLAGS_<name>`` overrides default."""
    ctype = type(default)
    value = default
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        value = _parse_env(env, ctype)
    _REGISTRY[name] = _Flag(name, default, help, ctype, value)
    if _NATIVE is not None:
        _mirror_one(_NATIVE, _REGISTRY[name])


def get_flags(names) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        n = n.removeprefix("FLAGS_")
        if n not in _REGISTRY:
            raise ValueError(f"unknown flag: {n}")
        out["FLAGS_" + n] = _REGISTRY[n].value
    return out


def get_flag(name: str) -> Any:
    return _REGISTRY[name.removeprefix("FLAGS_")].value


# fingerprint of the current flag VALUES: kernels read flags at TRACE
# time, so cached per-op executables are keyed on the state they were
# traced under (ops/dispatcher.py _get_exec) — otherwise toggling e.g.
# FLAGS_use_pallas_kernels after an op has run once is silently ignored.
# A value fingerprint (not a counter) means toggling back to a previous
# state REUSES its executables and a same-value set_flags is a no-op.
version = 0

# Mesh/topology epoch folded into the fingerprint: kernels also read the
# AMBIENT device mesh at trace time (the hybrid topology's hcg, the AOT
# tp_shard_context) to decide shard_map wrapping — so executables traced
# under one mesh must not replay under another. Every topology mutation
# bumps this (distributed/topology.set_hybrid_communicate_group,
# pallas/tp_attention.tp_shard_context).
_mesh_epoch = 0


def bump_mesh_epoch() -> None:
    """Invalidate trace-time caches keyed on `version` after an ambient
    mesh/topology change."""
    global _mesh_epoch
    _mesh_epoch += 1
    _refingerprint()


def _refingerprint() -> None:
    global version
    version = hash((_mesh_epoch,
                    tuple(sorted((k, repr(f.value))
                                 for k, f in _REGISTRY.items()))))


def set_flags(flags: Dict[str, Any]) -> None:
    for k, v in flags.items():
        k = k.removeprefix("FLAGS_")
        if k not in _REGISTRY:
            raise ValueError(f"unknown flag: {k}")
        f = _REGISTRY[k]
        if isinstance(v, f.ctype):
            f.value = v
        elif isinstance(v, str):
            f.value = _parse_env(v, f.ctype)  # 'false'/'0' must not read True
        else:
            f.value = f.ctype(v)
        if _NATIVE is not None:
            _NATIVE.PT_SetFlag(k.encode(), str(f.value).encode())
        for cb in _ON_SET.get(k, ()):
            cb(f.value)
    _refingerprint()


# -- Core flags (subset mirroring paddle/common/flags.cc) ---------------------
define_flag("check_nan_inf", False, "check every op output for NaN/Inf (eager)")
define_flag("eager_op_jit", True, "jit-compile each eager op (per-op XLA cache)")
define_flag("fused_backward", True,
            "structure-cached fused backward: compile each stable tape "
            "structure's whole reverse walk into ONE XLA executable "
            "(autograd/engine.py). First sight of a structure, and walks "
            "with tensor hooks / create_graph / capture, use the per-node "
            "walk; the signature cache is bounded")
define_flag("step_capture", True,
            "whole-step capture (jit/step_capture.py): trace a repeated "
            "training step — eager forward, tape backward, grad clip and "
            "optimizer update — into ONE donated, structure-cached XLA "
            "executable and replay it. Gates both the explicit "
            "paddle_tpu.jit_step API and hapi.Model.train_batch "
            "auto-capture; unfusable steps (tensor hooks, create_graph, "
            "data-dependent control flow, dynamic shapes) fall back to "
            "the eager path with the reason in the flight recorder")
define_flag("step_capture_screen", True,
            "pre-probe static screen for whole-step capture "
            "(analysis.screen_step_fn): steps whose source proves them "
            "uncapturable (host branches/coercions on tensor values, "
            "tensor hooks, create_graph=True) fall back to eager with a "
            "source-located diagnosis BEFORE paying the probe + trace + "
            "abort cycle; False defers entirely to the dynamic path")
define_flag("multi_step", 0,
            "multi-step capture (jit/multi_step.py): K > 1 makes "
            "hapi.Model.fit drive training in K-step blocks — ONE "
            "lax.scan executable runs K whole captured steps (forward, "
            "fused backward, grad clip, optimizer update with lr/step "
            "scalars advanced inside the loop carry) over a [K, ...] "
            "input ring the DataLoader prefetch thread fills "
            "(DataLoader.fill_ring). The host touches the job once per "
            "block; epoch tails and unsupported edges (per-step host "
            "callbacks, arg-ful schedulers) run through single-step "
            "capture with the reason in the flight recorder. 0 (default) "
            "= off; explicit jit_step(fn, k_steps=K) ignores this flag")
define_flag("anomaly_sentinel", False,
            "numerical-fault sentinel (optimizer/optimizer.py): every "
            "optimizer update computes a fused device-side finiteness + "
            "global-norm reduction over the gradients and guards the "
            "parameter/state update with per-leaf selects — a "
            "non-finite step applies an exact bitwise no-op (critical "
            "under whole-step capture, "
            "where the update lands in DONATED buffers and a NaN step "
            "would corrupt params irrecoverably in-process). The sentinel "
            "scalar rides the step's outputs; read it host-side via "
            "Optimizer.consume_anomaly() or distributed.resilience."
            "AnomalyDetector. Eager steps pay one deferred host sync; "
            "captured steps pay none")
define_flag("use_pallas_kernels", True, "route hot ops to Pallas hand kernels")
define_flag("fused_optimizer", True,
            "dtype-bucketed fused optimizer update: ONE kernel per "
            "(dtype, weight-decay) bucket fusing grad unscale, global-"
            "norm clip, the anomaly-sentinel select, the update rule "
            "and the bf16 master write-back (Pallas on TPU, one flat "
            "XLA chain per bucket elsewhere); the per-param chain runs "
            "when off or ineligible (ops/kernels/pallas/"
            "fused_optimizer.py)")
define_flag("benchmark", False, "block on every op for accurate timing")
define_flag("comm_timeout_s", 600.0,
            "eager collective / train-step watchdog timeout (seconds); the "
            "FLAGS_nccl_blocking_wait analog for DCN stalls")
define_flag("low_precision_op_list", 0, "log ops run in low precision under AMP")
define_flag("eager_loop_warn_ops", 200000,
            "warn once after this many eagerly-dispatched ops (0 = off): "
            "a long-running eager loop is launch-bound and should "
            "compile its step via jit.TrainStep / to_static")
define_flag("metrics", True,
            "process-wide metrics registry (observability/): always-on "
            "counters/gauges/histograms on the dispatch, autograd, executor "
            "and collective hot paths; False short-circuits every "
            "increment to a flag read")
define_flag("flight_recorder", True,
            "always-on flight recorder: bounded ring buffer of the last N "
            "op dispatches (op, shapes/dtypes, exec-cache key, thread), "
            "dumped to stderr/file on uncaught exception or explicit "
            "observability.dump_flight_recorder()")
define_flag("flight_recorder_size", 256,
            "flight recorder ring capacity (op dispatches)")
define_flag("flight_recorder_path", "",
            "crash-dump destination for the flight recorder; empty = stderr")
define_flag("tracing", True,
            "always-on request/step tracing (observability/tracing.py): "
            "trace_id/span_id spans with contextvars propagation over a "
            "bounded per-process ring, exported as Chrome-trace JSON via "
            "observability.dump_trace(); False short-circuits every span "
            "to a single flag read")
define_flag("tracing_ring_size", 16384,
            "tracing ring capacity (completed spans + instant events); "
            "a serving step writes about ten, so this holds some minutes")
define_flag("tracing_path", "",
            "crash-dump destination for the span trace (Chrome-trace "
            "JSON, written next to the flight recorder dump on uncaught "
            "exception); empty = human-readable listing to stderr")
define_flag("telemetry_port", -1,
            "ops endpoint (observability/exporter.py): port for the "
            "stdlib-http /metrics /healthz /statusz /trace server; "
            "-1 (default) = off, 0 = pick a free port, >0 = bind that "
            "port. The server starts on the first fleet/engine attach "
            "(or explicit observability.serve_telemetry())")
define_flag("perf_attribution", False,
            "performance attribution plane (observability/perf.py): the "
            "ExecutableLedger registers every compiled program at its "
            "creation site (per-op exec cache, fused backward, step "
            "capture, fused optimizer, static executor, serving step), "
            "captures cost/memory analysis at compile time and samples "
            "device time via timed block_until_ready every "
            "FLAGS_perf_sample_every-th call — yielding live achieved "
            "FLOP/s, bytes/s, MFU and a compute/bandwidth/host-bound "
            "classification per executable on /perfz. Off (default) the "
            "hot path pays ~zero (trace-time caches rebuild without the "
            "instrumentation; coarse sites pay one flag read)")
define_flag("incident_recorder", True,
            "incident forensics plane (observability/incident.py): on a "
            "terminal transition — serving step hang, trainer comm "
            "timeout, anomaly rewind, fleet failover, perf-regression "
            "sentinel breach, uncaught exception — assemble ONE committed "
            "incident-<step>-<uid>/ bundle (classified host stacks, trace "
            "ring, flight-recorder tail, metrics + perf snapshots, flags "
            "fingerprint) under the attached root. False short-circuits "
            "every trigger to a single flag read")
define_flag("incident_dir", "",
            "explicit incident-bundle root; empty (default) = the root "
            "the serving engine / trainer / router attached (their own "
            "<root>/incidents)")
define_flag("incident_keep", 8,
            "keep-K retention: committed incident bundles beyond the "
            "newest K are pruned after each new commit")
define_flag("incident_rate_limit_s", 30.0,
            "minimum seconds between two bundles of the SAME incident "
            "kind (a flapping sentinel must not fill the disk); 0 = "
            "unlimited")
define_flag("perf_sample_every", 16,
            "device-time sampling period for the executable ledger: every "
            "Nth call of a registered executable is timed through "
            "block_until_ready when FLAGS_perf_attribution is on; 1 = "
            "time every call (bench mode), larger = lower sampling tax")
define_flag("kv_cache_dtype", "auto",
            "paged KV pool storage dtype for serving: 'auto' (model "
            "compute dtype), 'bf16', or 'int8' (per-token-slot absmax "
            "scales ride the block table; dequant happens inside the "
            "attention tile load so HBM reads stay at int8 bytes)")
define_flag("speculative_k", 0,
            "speculative decoding draft length K for the continuous "
            "batching engine: 0 disables; K>0 drafts K candidate tokens "
            "per decode row (greedy n-gram self-draft by default) and "
            "verifies them as one q_len=K+1 ragged row inside the "
            "existing token budget — still one executable per budget")
define_flag("default_dtype", "float32", "default floating-point dtype")
define_flag("seed", 0, "global random seed")
define_flag("rng_impl", "rbg",
            "PRNG key implementation for the global Generator: 'rbg' (XLA "
            "RngBitGenerator — the cuRAND-Philox analog, ~2x faster on TPU "
            "at dropout shapes) or 'threefry2x32' (jax default streams)")


# Mirror into the native C++ registry (csrc/flags.cc) once it loads; until
# then the Python dict is the sole home (no toolchain required to import).
from .native import on_load as _native_on_load  # noqa: E402

_native_on_load(_mirror_native)
_refingerprint()
