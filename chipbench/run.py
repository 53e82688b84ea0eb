"""Runs one cell of the benchmark once.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, prints earlier lines as it
likes and then one JSON object as the last line: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``. Untraced the
metrics are the cell's end-to-end metrics, traced its per-layer metrics.

Everything that belongs to one thing is one file, found by name:
``workloads/<cell>.json`` names its configuration (``configs/<name>.json``)
and its driver (``drivers/<name>.py``); every ``layer_metrics/*.py`` whose
``DRIVER`` is the cell's driver and whose ``MOVES`` is an end-to-end metric
the cell reports is read for a traced run.

Exits non-zero, with no result line, unless JAX finds a TPU whose kind is in
``peaks.json`` and as many chips as the cell asks for. ``--rehearse``, which
the driver never passes, runs the cell's tiny rehearsal sizes on whatever
backend is there and prints that backend as its device.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse
import glob
import importlib
import importlib.util
import json
import os
import sys
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
NO_DEVICE = 3


def load_json(*parts: str) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def layer_metric_files(driver: str, reported: List[str]) -> Dict[str, object]:
    """name -> module, for the per-layer metrics of this kind of cell."""
    found = {}
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics", "*.py"))):
        name = os.path.basename(path)[:-3]
        if name.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            "chipbench.layer_metrics." + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if mod.DRIVER == driver and mod.MOVES in reported:
            found[name] = mod
    return found


def set_compile_cache() -> str:
    """JAX's persistent cache: where the environment says, else a fixed
    directory inside the checkout. Every program is kept, however quick it
    was to compile: an eager serving step is some tens of small ones."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(CHECKOUT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def open_cell(workload: str, seed: int, seconds: float, trace: bool,
              rehearse: bool, t_process: float, heartbeat: bool = True,
              control: bool = False):
    """The cell's files, the compile cache, the device check and the run's
    context. Returns (context, device) or, where the device will not do,
    (None, exit code)."""
    cell = load_json("workloads", workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    cache_dir = set_compile_cache()

    import jax
    from . import model_math
    from .drivers.common import Context
    devices = jax.devices()
    dev = devices[0]
    if not rehearse:
        try:
            if dev.platform != "tpu":
                raise model_math.UnknownDevice(
                    f"no TPU (JAX found {dev.platform})")
            if len(devices) < cell["chips"]:
                raise model_math.UnknownDevice(
                    f"{workload} needs {cell['chips']} chips, JAX found "
                    f"{len(devices)}")
            model_math.peaks(dev.device_kind)
        except model_math.UnknownDevice as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return None, NO_DEVICE
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    ctx = Context(cell_name=workload, cell=cell, config=config, seed=seed,
                  seconds=seconds, trace=trace, rehearse=rehearse,
                  t_process=t_process,
                  scratch=os.path.join(CHECKOUT, ".chipbench_tmp"),
                  device_kind=dev.device_kind, heartbeat=heartbeat,
                  control=control)
    ctx.emit("start", device=device, workload=workload, seed=seed,
             seconds=seconds, trace=int(trace), rehearse=rehearse,
             config=cell["config"], compile_cache=cache_dir,
             jax=jax.__version__)
    return ctx, device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--heartbeat", type=int, choices=(0, 1), default=1,
                    help="0 runs the window without the heartbeat thread, "
                         "to show what it costs; the driver never passes it")
    ap.add_argument("--control", action="store_true",
                    help="the check also reads its control, the reference "
                         "in a lower precision; the driver never passes it")
    args = ap.parse_args(argv)
    ctx, device = open_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), args.rehearse, _T_PROCESS,
                            bool(args.heartbeat), args.control)
    if ctx is None:
        return device
    cell = ctx.cell

    from . import trace
    driver = importlib.import_module(f"chipbench.drivers.{cell['driver']}")
    result = driver.run(ctx)

    device["memory_peak_bytes"] = result.memory_peak_bytes
    if args.trace:
        metrics = {}
        readers = layer_metric_files(cell["driver"], cell["end_to_end"])
        for name, mod in readers.items():
            value = mod.compute(result)
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
    else:
        metrics = {name: {"value": result.end_to_end[name][0],
                          "unit": result.end_to_end[name][1]}
                   for name in cell["end_to_end"]
                   if name in result.end_to_end}
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": device}
    if args.trace and result.reduced is not None \
            and result.reduced.window_s > 0:
        device["busy_s"] = result.reduced.busy_s
        device["window_s"] = result.reduced.window_s
        line["breakdown"] = trace.breakdown(result.reduced)
    # every number ``correct`` compared beside its limit: the line's last
    # key, and the last lines of standard error
    line["compared"] = result.compared
    print(json.dumps(line), flush=True)
    for name, (number, limit) in result.compared.items():
        print(f"chipbench compared {name} {number!r} limit {limit!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
