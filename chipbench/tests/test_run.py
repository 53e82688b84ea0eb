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
