"""Performance attribution plane (ISSUE 17).

Covers the executable ledger (registration at the compile sites, the
warmup/sample accounting, the zero-cost off path, capacity overflow),
the perf-regression sentinel (fires on a planted slowdown, quiet on
noise), the step-time decomposition (components sum to the step wall;
wired through hapi train_batch and the ResilientTrainer fallback), the
labeled fleet merge under ``replica=``, the /perfz + /statusz + CLI
contract, the histogram/delta edge cases the plane leans on, and the
``bench.py --compare`` regression gate round trip.
"""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import perf

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _counter_value(name):
    m = obs_metrics.registry().get(name)
    return m.value if m is not None else 0


@pytest.fixture
def perf_on():
    entry = paddle.get_flags(["FLAGS_perf_attribution",
                              "FLAGS_perf_sample_every"])
    paddle.set_flags({"FLAGS_perf_attribution": True})
    perf.reset()
    try:
        yield
    finally:
        paddle.set_flags(entry)
        perf.reset()


@pytest.fixture
def sample_every_one(perf_on):
    entry = paddle.get_flags(["FLAGS_perf_sample_every"])
    try:
        paddle.set_flags({"FLAGS_perf_sample_every": 1})
        yield
    finally:
        paddle.set_flags(entry)


def _tiny_model():
    net = nn.Linear(8, 4)
    from paddle_tpu.hapi.model import Model
    m = Model(net)
    m.prepare(
        optimizer=paddle.optimizer.SGD(parameters=net.parameters(),
                                       learning_rate=0.1),
        loss=lambda out, y: ((out - y) ** 2).mean())
    x = np.random.RandomState(0).rand(4, 8).astype("float32")
    y = np.random.RandomState(1).rand(4, 4).astype("float32")
    return m, x, y


class TestLedgerRegistration:
    def test_off_means_no_entries_and_no_wrap(self):
        assert paddle.get_flags(["FLAGS_perf_attribution"])[
            "FLAGS_perf_attribution"] is False
        perf.reset()
        a = paddle.to_tensor(np.random.RandomState(2).rand(6, 6)
                             .astype("float32"))
        _ = paddle.matmul(a, a)
        # reset() zeroes rows in place but never drops them (live wrapped
        # executables keep their entry refs), so "off" means no ACTIVITY:
        # rows registered by an earlier perf-on test stay, with zero calls
        assert [e for e in perf.ledger().entries() if e.calls] == []
        assert perf.ledger().register(("k",), "op") is None
        fn = lambda v: v  # noqa: E731
        assert perf.ledger().wrap(("k2",), "op", fn) is fn

    def test_dispatcher_registers_per_compile(self, perf_on, monkeypatch):
        """Every exec-cache miss (a jit.compiles tick) of a jitted op
        lands one op-kind ledger row under the same cache identity."""
        c0 = _counter_value("jit.compiles")
        n0 = len([e for e in perf.ledger().entries() if e.kind == "op"])
        # a never-seen shape forces a fresh exec-cache entry + compile
        a = paddle.to_tensor(np.random.RandomState(3).rand(13, 17)
                             .astype("float32"))
        b = paddle.to_tensor(np.random.RandomState(4).rand(17, 11)
                             .astype("float32"))
        for _ in range(3):
            out = paddle.matmul(a, b)
        float(np.asarray(out._data).sum())
        new_ops = [e for e in perf.ledger().entries()
                   if e.kind == "op"][n0:]
        assert len(new_ops) >= 1
        assert _counter_value("jit.compiles") >= c0 + len(new_ops)
        (e,) = [x for x in new_ops if "matmul" in x.label]
        assert e.calls == 3
        def row():
            return [r for r in perf.ledger().stats()
                    if r["key"] == e.label][0]
        # cost analysis resolved from the live executable
        assert row()["flops"] and row()["flops"] > 0
        assert row()["hbm"]["arg_bytes"] > 0
        # the CPU is not in the peaks table: no MFU, no roofline figure
        assert row()["mfu"] is None and "roofline" not in row()
        monkeypatch.setattr(perf, "_peaks",
                            lambda: perf.DEVICE_PEAKS["TPU v5 lite"])
        assert row()["roofline"]["projected_step_seconds"] > 0

    def test_step_capture_and_optimizer_register(self, perf_on):
        sc = paddle.get_flags(["FLAGS_step_capture"])
        paddle.set_flags({"FLAGS_step_capture": True})
        try:
            m, x, y = _tiny_model()
            for _ in range(3):
                m.train_batch([x], [y])
        finally:
            paddle.set_flags(sc)
        kinds = {e.kind for e in perf.ledger().entries()}
        assert "step" in kinds
        (step,) = [e for e in perf.ledger().entries()
                   if e.kind == "step" and e.calls]
        assert step.calls >= 2          # capture + replays
        row = [r for r in perf.ledger().stats()
               if r["key"] == step.label][0]
        # donated-aval lazy lowering recovered the step's cost model
        assert row["flops"] and row["flops"] > 0
        assert row["compile_seconds"] is not None

    def test_eager_optimizer_registers(self, perf_on):
        m, x, y = _tiny_model()
        m.train_batch([x], [y])
        kinds = {e.kind for e in perf.ledger().entries()}
        assert kinds & {"opt", "opt_fused"}, kinds

    def test_static_executor_registers(self, perf_on):
        import paddle_tpu.static as static
        paddle.enable_static()
        try:
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("perf_x", [2, 2], "float32")
                y = x * 3.0
            exe = static.Executor()
            for _ in range(2):
                out, = exe.run(main,
                               feed={"perf_x": np.ones((2, 2), np.float32)},
                               fetch_list=[y])
        finally:
            paddle.disable_static()
        np.testing.assert_allclose(out, 3.0 * np.ones((2, 2)))
        execs = [e for e in perf.ledger().entries() if e.kind == "exec"]
        assert len(execs) == 1 and execs[0].calls == 2

    def test_multi_step_kind_wired(self):
        from paddle_tpu.jit.multi_step import MultiStepCapture
        from paddle_tpu.jit.step_capture import CapturedStep
        assert CapturedStep._perf_kind == "step"
        assert MultiStepCapture._perf_kind == "multi"

    def test_capacity_overflow_drops(self, perf_on):
        led = perf.ExecutableLedger()
        d0 = _counter_value("perf.ledger.dropped")
        for i in range(perf._MAX_ENTRIES):
            assert led.register(("cap", i), "op") is not None
        assert led.register(("cap", "overflow"), "op") is None
        assert _counter_value("perf.ledger.dropped") == d0 + 1


class TestSamplingAccounting:
    def test_warmup_then_samples(self, perf_on):
        entry = paddle.get_flags(["FLAGS_perf_sample_every"])
        try:
            paddle.set_flags({"FLAGS_perf_sample_every": 4})
            led = perf.ExecutableLedger()
            e = led.register(("s",), "op")
            # call 1: timed but warmup — ready lands in compile_s
            assert led.tick(e) is True
            led.commit(e, 0.001, 0.5)
            assert e.samples == 0 and e.compile_s == 0.5
            # call 2: first real device sample
            assert led.tick(e) is True
            led.commit(e, 0.001, 0.01)
            assert e.samples == 1 and e.device_s == pytest.approx(0.01)
            # calls 3..8: only multiples of the period sample
            ticks = [led.tick(e) for _ in range(6)]
            assert ticks == [False, True, False, False, False, True]
        finally:
            paddle.set_flags(entry)

    def test_unsampled_commits_fold_wall_only(self, perf_on):
        led = perf.ExecutableLedger()
        e = led.register(("w",), "op")
        led.tick(e)
        led.commit(e, 0.25)
        assert e.wall_s == pytest.approx(0.25)
        assert e.samples == 0 and e.compile_s is None

    def test_labeled_series_published(self, perf_on):
        led = perf.ExecutableLedger()
        e = led.register(("pub",), "op", name="pub_op")
        for ready in (0.1, 0.02, 0.02):
            led.tick(e)
            led.commit(e, 0.001, ready)
        calls = obs_metrics.registry().get(
            "perf.executable.calls", labels=dict(e.c_calls.labels))
        assert calls is not None and calls.value == 3
        dev = obs_metrics.registry().get(
            "perf.executable.device_seconds", labels=dict(e.g_dev.labels))
        assert dev.value == pytest.approx(0.04)


class TestRegressionSentinel:
    def _drive(self, readies):
        led = perf.ExecutableLedger()
        e = led.register(("sent", id(readies)), "op")
        for r in readies:
            led.tick(e)
            led.commit(e, 1e-4, r)
        return e

    def test_fires_on_planted_slowdown(self, sample_every_one):
        r0 = _counter_value("perf.regression")
        # warmup + 3 fast samples set the high-water mark, then a
        # sustained 10x slowdown breaches for 2 consecutive samples
        self._drive([0.001] * 4 + [0.01] * 2)
        assert _counter_value("perf.regression") == r0 + 1
        from paddle_tpu.observability import flight_recorder as fr
        events = [e for e in fr.recorder().entries()
                  if "perf.regression" in str(e)]
        assert events, "regression must land in the flight recorder"

    def test_quiet_on_noise(self, sample_every_one):
        r0 = _counter_value("perf.regression")
        rng = np.random.RandomState(5)
        # +-10% jitter never crosses the 30% drop band
        self._drive([0.001 * (1.0 + 0.1 * rng.uniform(-1, 1))
                     for _ in range(30)])
        assert _counter_value("perf.regression") == r0

    def test_single_blip_debounced(self, sample_every_one):
        r0 = _counter_value("perf.regression")
        # one slow sample between fast ones: debounce holds fire
        self._drive([0.001] * 4 + [0.01] + [0.001] * 4)
        assert _counter_value("perf.regression") == r0


class TestStepDecomposition:
    def test_components_sum_to_wall(self, perf_on):
        perf.note_data_wait(0.01)
        perf.record_step(0.1, host_s=0.04, device_s=0.03)
        s = perf.step_summary()
        assert s["data_wait"]["sum"] == pytest.approx(0.01)
        assert s["host_dispatch"]["sum"] == pytest.approx(0.04)
        assert s["device"]["sum"] == pytest.approx(0.03)
        assert s["other"]["sum"] == pytest.approx(0.02)
        parts = sum(s[p]["sum"] for p in
                    ("data_wait", "host_dispatch", "device", "other"))
        assert parts == pytest.approx(s["total"]["sum"], abs=3e-6)

    def test_data_wait_clamped_to_wall(self, perf_on):
        perf.note_data_wait(5.0)
        perf.record_step(0.1)
        s = perf.step_summary()
        assert s["data_wait"]["sum"] == pytest.approx(0.1)
        assert s["other"]["sum"] == pytest.approx(0.0)

    def test_hapi_train_batch_records(self, perf_on):
        m, x, y = _tiny_model()
        for _ in range(3):
            m.train_batch([x], [y])
        s = perf.step_summary()
        assert s["total"]["count"] == 3
        parts = sum(s[p]["sum"] for p in
                    ("data_wait", "host_dispatch", "device", "other"))
        assert parts == pytest.approx(s["total"]["sum"], abs=3e-6)

    def test_timed_iter_attributes_loader_wait(self, perf_on):
        import time as _time
        items = iter([1, 2])

        def slow():
            for v in items:
                _time.sleep(0.01)
                yield v

        out = []
        for v in perf.timed_iter(slow()):
            out.append(v)
            perf.record_step(0.05)   # wall must cover the wait (clamp)
        assert out == [1, 2]
        s = perf.step_summary()
        assert 0.02 <= s["data_wait"]["sum"] <= s["total"]["sum"]

    def test_step_beat_unconditional(self):
        assert paddle.get_flags(["FLAGS_perf_attribution"])[
            "FLAGS_perf_attribution"] is False
        perf.record_step(0.01)
        age = perf.last_step_age_s()
        assert age is not None and age < 5.0
        assert perf.process_uptime_s() > 0.0

    def test_trainer_fallback_records_raw_steps(self, perf_on):
        import tempfile

        from paddle_tpu.distributed.resilience.checkpointer import \
            AsyncCheckpointer
        from paddle_tpu.distributed.resilience.trainer import \
            ResilientTrainer
        c0 = perf.step_summary()["total"]["count"]
        with tempfile.TemporaryDirectory() as d:
            tr = ResilientTrainer(AsyncCheckpointer(d),
                                  state_fn=lambda: {"x": 1},
                                  snapshot_every=0, install_signal=False)
            rc = tr.run(lambda s: None, max_steps=3, final_snapshot=False)
        assert rc == "completed"
        assert perf.step_summary()["total"]["count"] == c0 + 3


class TestFleetMerge:
    def test_perf_series_merge_under_replica_label(self, perf_on):
        led = perf.ExecutableLedger()
        e = led.register(("merge",), "op", name="merge_op")
        for ready in (0.1, 0.02):
            led.tick(e)
            led.commit(e, 0.001, ready)
        # the worker side: delta over the heartbeat prefixes
        state = {}
        delta = obs_metrics.registry().delta_update(
            state, ("serving.", "jit.", "perf."))
        moved = [k for k in delta if k.startswith("perf.executable.")]
        assert moved, delta.keys()
        # the router side: fold under the replica's name
        obs_metrics.registry().merge_delta(delta,
                                           labels={"replica": "repT"})
        kids = obs_metrics.registry().children("perf.executable.calls")
        mine = [k for k in kids
                if dict(k.labels).get("replica") == "repT"
                and dict(k.labels).get("key") == e.label]
        assert mine and mine[0].value == 2

    def test_worker_heartbeat_covers_perf(self):
        import inspect

        from paddle_tpu.serving.fleet import worker
        src = inspect.getsource(worker)
        assert '"perf."' in src, \
            "fleet heartbeats must piggyback the perf.* families"


class TestPerfzSurfaces:
    def test_perfz_endpoint_and_statusz(self, perf_on):
        a = paddle.to_tensor(np.random.RandomState(6).rand(12, 12)
                             .astype("float32"))
        out = paddle.matmul(a, a)
        float(np.asarray(out._data).sum())
        perf.record_step(0.01, host_s=0.008, device_s=0.001)
        from paddle_tpu.observability.exporter import TelemetryServer
        srv = TelemetryServer()
        port = srv.serve(0)
        try:
            snap = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/perfz", timeout=10))
            assert snap["enabled"] is True
            assert snap["total_executables"] >= 1
            row = snap["executables"][0]
            for k in ("key", "kind", "calls", "device_seconds", "flops",
                      "hbm", "mfu", "bound"):
                assert k in row
            assert snap["step"]["total"]["count"] >= 1
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statusz", timeout=10
            ).read().decode()
            head = body.splitlines()[2]
            assert "uptime_s:" in head and "rss_mb:" in head \
                and "last_step_age_s:" in head
            # vitals carry real values on this platform
            assert "rss_mb: n/a" not in head
            assert "last_step_age_s: n/a" not in head
            # /healthz contract unchanged: process-alive 200
            hz = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10)
            assert hz.status == 200
            assert json.load(hz)["status"] == "ok"
        finally:
            srv.shutdown()

    def test_cli_perfz_view(self, perf_on, capsys):
        led = perf.ledger()
        e = led.register(("cli",), "op", name="cli_op")
        led.tick(e)
        led.commit(e, 0.001, 0.1)
        perf.note_projection("test_plan", {"step_seconds": 0.5,
                                           "bound": "compute",
                                           "mfu_upper_bound": 0.6})
        from paddle_tpu.observability.__main__ import main as obs_main
        assert obs_main(["perfz"]) == 0
        out = capsys.readouterr().out
        assert "Device executables" in out
        assert "cli_op" in out
        assert "AOT projection [test_plan]" in out

    def test_profiler_summary_appends_table(self, perf_on, capsys):
        import paddle_tpu.profiler as profiler
        led = perf.ledger()
        e = led.register(("prof",), "op", name="prof_op")
        led.tick(e)
        led.commit(e, 0.001, 0.01)
        p = profiler.Profiler()
        p.start()
        p.stop()
        p.summary()
        out = capsys.readouterr().out
        assert "Device executables" in out
        assert "prof_op" in out


class TestHistogramEdgeCases:
    def test_empty_quantile_is_none(self):
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram("edge.empty_seconds")
        for q in (0.0, 0.5, 0.99, 1.0):
            assert h.quantile(q) is None

    def test_never_observed_histogram_ships_nothing(self):
        reg = obs_metrics.MetricsRegistry()
        reg.histogram("edge.silent_seconds")
        state = {}
        assert reg.delta_update(state, ("edge.",)) == {}
        # and stays silent on repeat calls with the same state
        assert reg.delta_update(state, ("edge.",)) == {}

    def test_counter_reset_reseeds_without_negative_delta(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("edge.count")
        c.inc(5)
        state = {}
        d1 = reg.delta_update(state, ("edge.",))
        assert d1["edge.count"]["v"] == 5
        c._reset()
        c.inc(2)
        # backwards movement reseeds silently — no negative delta
        d2 = reg.delta_update(state, ("edge.",))
        assert "edge.count" not in d2
        c.inc(3)
        d3 = reg.delta_update(state, ("edge.",))
        assert d3["edge.count"]["v"] == 3

    def test_histogram_reset_reseeds_without_negative_delta(self):
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram("edge.h_seconds")
        h.observe(0.1)
        h.observe(0.2)
        state = {}
        d1 = reg.delta_update(state, ("edge.",))
        assert d1["edge.h_seconds"]["c"] == 2
        h._reset()
        h.observe(0.3)
        d2 = reg.delta_update(state, ("edge.",))
        assert "edge.h_seconds" not in d2
        h.observe(0.4)
        d3 = reg.delta_update(state, ("edge.",))
        assert d3["edge.h_seconds"]["c"] == 1


class TestBenchCompare:
    def _round(self, n, metrics):
        cfgs = [{"metric": k, "value": v, "unit": "x", "vs_baseline": 1.0}
                for k, v in metrics.items() if k != "headline"]
        return {"n": n, "cmd": "bench", "rc": 0, "tail": "",
                "parsed": {"metric": "headline",
                           "value": metrics.get("headline", 1.0),
                           "unit": "mfu_fraction",
                           "detail": {"configs": cfgs}}}

    def _write_rounds(self, tmp_path, rounds):
        paths = []
        for i, m in enumerate(rounds, start=1):
            p = tmp_path / f"BENCH_r{i:02d}.json"
            p.write_text(json.dumps(self._round(i, m)))
            paths.append(str(p))
        return paths

    def test_clean_tree_passes_against_itself(self, tmp_path, capsys):
        import bench
        m = {"headline": 0.6, "step_us": 100.0, "opt_speedup": 4.0}
        paths = self._write_rounds(tmp_path, [m, m])
        assert bench.bench_compare(paths[0]) == 0   # candidate = newest
        assert "no regression" in capsys.readouterr().out

    def test_planted_slowdown_fails_with_table(self, tmp_path, capsys):
        import bench
        base = {"headline": 0.6, "step_us": 100.0, "opt_speedup": 4.0}
        bad = {"headline": 0.6, "step_us": 200.0, "opt_speedup": 4.0}
        paths = self._write_rounds(tmp_path, [base, bad])
        assert bench.bench_compare(paths[0], paths[1]) == 1
        out = capsys.readouterr().out
        assert "REGRESSED" in out and "step_us" in out
        assert "opt_speedup" in out      # the per-micro table is printed

    def test_direction_awareness(self, tmp_path):
        import bench
        # _us shrinking and speedup growing are both improvements
        base = {"headline": 0.6, "step_us": 100.0, "opt_speedup": 4.0}
        better = {"headline": 0.9, "step_us": 50.0, "opt_speedup": 9.0}
        paths = self._write_rounds(tmp_path, [base, better])
        assert bench.bench_compare(paths[0], paths[1]) == 0
        # speedup COLLAPSING is a regression
        worse = {"headline": 0.6, "step_us": 100.0, "opt_speedup": 1.0}
        paths = self._write_rounds(tmp_path, [base, worse])
        assert bench.bench_compare(paths[0], paths[1]) == 1

    def test_noise_band_widens_with_history(self, tmp_path):
        import bench
        # step_us historically swings 40% round to round: a 25% move
        # sits inside 3 x median band and must NOT gate
        hist = [{"step_us": 100.0}, {"step_us": 140.0},
                {"step_us": 100.0}, {"step_us": 140.0},
                {"step_us": 125.0}]
        paths = self._write_rounds(tmp_path, hist)
        assert bench.bench_compare(paths[-2], paths[-1]) == 0

    def test_zero_valued_metrics_not_gated(self, tmp_path, capsys):
        import bench
        paths = self._write_rounds(
            tmp_path, [{"headline": 0.0, "step_us": 100.0},
                       {"headline": 0.0, "step_us": 100.0}])
        assert bench.bench_compare(paths[0], paths[1]) == 0
        assert "not gated" in capsys.readouterr().out

    def test_cli_entry(self, tmp_path, capsys, monkeypatch):
        import bench
        m = {"headline": 0.6, "step_us": 100.0}
        paths = self._write_rounds(tmp_path, [m, m])
        monkeypatch.setattr(sys, "argv",
                            ["bench.py", "--compare", paths[0]])
        with pytest.raises(SystemExit) as ei:
            bench.main()
        assert ei.value.code == 0
