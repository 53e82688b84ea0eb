"""The command's last line, from a ``--rehearse`` run of each driver, and
the closed loop of the serving driver."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
STOPS = {"stops_over_50ms", "stop_longest_ms", "stop_sum_ms", "heartbeats"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell,trace", [
    ("serve-chat-steady", 0), ("serve-chat-steady", 1),
    ("train-pretrain-4k", 0), ("train-pretrain-4k", 1)])
def test_last_line_has_the_contracts_keys(cell, trace):
    done = run("--workload", cell, "--seed", str(2 ** 31 + 11), "--seconds",
               "2", "--trace", str(trace), "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == KEYS          # no device trace on the CPU
    # what ``correct`` compared, each number beside its limit: the line's
    # last key and the last lines of standard error
    assert list(line)[-1] == "compared" and line["compared"]
    said = done.stderr.strip().splitlines()[-len(line["compared"]):]
    for (name, (number, limit)), text in zip(line["compared"].items(), said):
        assert text.split()[:3] == ["chipbench", "compared", name]
        assert number <= limit
    notes = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith('{"event": "notes"')][0]
    assert STOPS <= set(notes) and notes["heartbeats"] > 100
    if cell.startswith("serve"):
        assert {"itl_p50_ms", "itl_p99_ms",
                "itl_over_half_budget_share"} <= set(notes)
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    with open(os.path.join(ROOT, "chipbench", "workloads",
                           cell + ".json")) as f:
        reported = json.load(f)["end_to_end"]
    if trace:
        assert line["metrics"] and not set(line["metrics"]) & set(reported)
    else:
        assert set(line["metrics"]) == set(reported)
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0


def test_without_a_tpu_it_prints_no_result():
    done = run("--workload", "serve-chat-steady", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "correct" not in done.stdout


def test_closed_loop_in_a_rehearsal():
    import time
    from chipbench import run as cli
    from chipbench.drivers import serve
    from chipbench.drivers.common import Context
    cell = cli.load_json("workloads", "serve-chat-steady.json")
    config = cli.load_json("configs", cell["config"] + ".json")
    cell["rehearse"]["traffic"].update(arrivals="closed", clients=3)
    ctx = Context(cell_name="closed", cell=cell, config=config, seed=5,
                  seconds=2.0, trace=False, rehearse=True,
                  t_process=time.perf_counter(),
                  scratch=os.path.join(ROOT, ".chipbench_tmp"),
                  device_kind="cpu")
    result = serve.run(ctx)
    assert result.correct and result.failed == 0 and result.attempted >= 3
    # three clients: never more than three rows in a step
    assert max(s["rows"] for s in result.steps) <= 3
    assert result.end_to_end["serve_tokens_per_s"][0] > 0


@pytest.mark.parametrize("trace", [False, True])
def test_training_launches_ahead_and_waits_for_every_step(trace):
    """Steps are launched ahead of the one waited for, and the window counts
    only steps that were waited for: each has a ``t_done``, in order, the
    last one before the rate's clock was read. A traced slice begins with
    nothing in flight, so its steps are the ones launched in it."""
    import time
    from chipbench import run as cli
    from chipbench.drivers import train
    from chipbench.drivers.common import Context
    cell = cli.load_json("workloads", "train-pretrain-4k.json")
    config = cli.load_json("configs", cell["config"] + ".json")
    cell["trace_slice_s"] = 1.0
    ctx = Context(cell_name="ahead", cell=cell, config=config, seed=7,
                  seconds=2.0, trace=trace, rehearse=True,
                  t_process=time.perf_counter(),
                  scratch=os.path.join(ROOT, ".chipbench_tmp"),
                  device_kind="cpu")
    result = train.run(ctx)
    assert result.correct and result.attempted == len(result.steps) > 2
    done = [s["t_done"] for s in result.steps]
    assert done == sorted(done)
    assert all(s["t_begin"] <= s["t_end"] and s["t_begin"] <= s["t_done"]
               for s in result.steps)
    tokens = result.end_to_end["train_tokens_per_s"][0]
    assert tokens > 0
    if trace:
        first = result.traced_steps[0]
        earlier = [s for s in result.steps if s["t_begin"] < first["t_begin"]]
        assert len(result.traced_steps) > 1 and all(
            s["t_done"] <= first["t_begin"] for s in earlier)
        # what ``dispatch.host_ms.train`` reads: the slice's first launches
        # find nothing queued before them
        assert [s["queued"] for s in result.traced_steps[:2]] == [0, 1]


def test_the_slice_is_due_once():
    from chipbench.drivers.common import Context, Slice
    ctx = Context(cell_name="due", cell={}, config={}, seed=0, seconds=10.0,
                  trace=True, rehearse=True, t_process=0.0,
                  scratch=os.path.join(ROOT, ".chipbench_tmp"),
                  device_kind="cpu")
    tracer = Slice(ctx, window_start=100.0, length_s=3.0)
    assert not tracer.due(106.9) and tracer.due(107.0)
    tracer.t0 = 107.0                   # as ``tick`` leaves it once started
    assert not tracer.due(108.0)
    ctx.trace = False
    assert not Slice(ctx, 100.0, 3.0).due(109.0)


@pytest.mark.parametrize("cell", [
    "serve-chat-steady", "serve-decode-batch", "serve-reason-batch"])
def test_the_ragged_kernels_roofline_is_read_in_every_serving_cell(cell):
    """``MOVES`` decides the cells a reader is read in: the ragged kernel's
    share of its roofline moves ``serve_tokens_per_s``, which every serving
    cell reports, and ``BENCHMARK.json`` lists it for each of them."""
    from chipbench import run as cli
    reported = cli.load_json("workloads", cell + ".json")["end_to_end"]
    assert "kernel.hbm_share.serve" in cli.layer_metric_files(
        "serve", reported)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = listed["kernel.hbm_share.serve"]
    assert cell in entry["workloads"] and entry["moves"] in reported
