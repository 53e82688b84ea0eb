"""Device-side kernel timing via the XLA profiler (XPlane).

A kernel's own time is read from the device timeline: run the jitted
function N times under `jax.profiler.trace`, parse the `/device:TPU:0`
plane's "XLA Modules" line, and report per-execution device time. This
is device-busy time, host launch gaps excluded; end-to-end time is the
host clock around `block_until_ready`.

This is the same evidence the reference's kernel micro-benchmarks use
(CUDA events on-stream, `paddle/phi/kernels/autotune/gpu_timer.h`) —
a device clock, not a host clock.

Parsing uses the tsl xplane proto bundled with tensorflow (CPU build,
baked into the image). No tensorflow runtime is initialized here beyond
proto import; gated so CPU-only environments fall back to wall clock.
"""

from __future__ import annotations

import collections
import glob
import os
import shutil
import tempfile
import time

import jax


def _xplane_module_times(trace_dir):
    """-> {module_name: [durations_us,...]} from the newest xplane.pb."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # lazy, heavy

    pbs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
    if not pbs:
        raise RuntimeError(f"no xplane.pb under {trace_dir}")
    xs = xplane_pb2.XSpace()
    with open(max(pbs, key=os.path.getmtime), "rb") as f:
        xs.ParseFromString(f.read())
    out = collections.defaultdict(list)
    for plane in xs.planes:
        if not plane.name.startswith("/device:"):
            continue
        meta = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                name = meta.get(e.metadata_id, "")
                out[name.split("(")[0]].append(e.duration_ps / 1e6)
    return dict(out)


def device_time_us(fn, args, *, iters: int = 8, warmup: int = 2,
                   name: str | None = None, drop_slowest: bool = True):
    """Median device time (us) of one `fn(*args)` execution.

    fn must be a jitted callable; its XLA module name (jit_<fn name>)
    is matched against the device timeline. `name` overrides the match
    (substring). Falls back to host wall clock when no device plane
    exists (CPU backend).
    """
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)

    if jax.default_backend() != "tpu":
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e6

    tdir = tempfile.mkdtemp(prefix="xplane_bench_")
    try:
        with jax.profiler.trace(tdir):
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
        mods = _xplane_module_times(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)

    want = name or getattr(fn, "__name__", "")
    cands = {k: v for k, v in mods.items() if want and want in k}
    if not cands:
        # single-module trace: take the dominant module
        cands = mods
    if not cands:
        raise RuntimeError(f"no XLA module events (wanted {want!r})")
    key = max(cands, key=lambda k: sum(cands[k]))
    durs = sorted(cands[key])
    if drop_slowest and len(durs) > 2:
        durs = durs[:-1]              # first-touch / trace-start straggler
    return durs[len(durs) // 2]


def device_ratio(fn_a, args_a, fn_b, args_b, *, iters: int = 8, **kw):
    """(time_a_us, time_b_us / time_a_us) on the device clock."""
    ta = device_time_us(fn_a, args_a, iters=iters, **kw)
    tb = device_time_us(fn_b, args_b, iters=iters, **kw)
    return ta, tb / ta


def device_steps_seconds(fn, steps: int, *, warmup: int = 2):
    """Device seconds per call over `steps` sequential `fn()` calls.

    Sums ALL XLA-module executions on the device timeline inside the
    window (a train step that dispatches several modules per step is
    charged for all of them) and divides by `steps`. Host launch gaps
    are excluded: this is the device-resident step cost, not the step
    time a user waits for. Wall clock on CPU backends.
    """
    out = None
    for _ in range(warmup):
        out = fn()
    jax.block_until_ready(out)

    if jax.default_backend() != "tpu":
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps

    tdir = tempfile.mkdtemp(prefix="xplane_steps_")
    try:
        with jax.profiler.trace(tdir):
            for _ in range(steps):
                out = fn()
            jax.block_until_ready(out)
        mods = _xplane_module_times(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    total_us = sum(sum(v) for v in mods.values())
    return total_us / steps / 1e6
