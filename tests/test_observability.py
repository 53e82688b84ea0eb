"""Observability subsystem: metrics registry, flight recorder, wiring.

Covers the ISSUE 3 acceptance surface: registry instruments under
threads, the flag-gated no-op fast path, JSON/Prometheus dumpers,
flight-recorder ring bounds + crash dump (including after an injected op
failure), chrome-trace counter events, and the STABLE metric names the
dispatcher/engine/executor publish.
"""

import io
import json
import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.observability import flight_recorder as fr_mod
from paddle_tpu.observability.flight_recorder import FlightRecorder
from paddle_tpu.observability.metrics import (MetricsRegistry,
                                              format_metrics)


def _counter_value(name):
    return obs.registry().get(name).value


class TestRegistryInstruments:
    def test_counter_inc_and_threads(self):
        reg = MetricsRegistry()
        c = reg.counter("t.counter", "help text")
        c.inc()
        c.inc(4)
        assert c.value == 5

        n_threads, per_thread = 8, 1000
        threads = [threading.Thread(
            target=lambda: [c.inc() for _ in range(per_thread)])
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # the lock makes increments exact, not merely approximate
        assert c.value == 5 + n_threads * per_thread

    def test_histogram_under_threads(self):
        reg = MetricsRegistry()
        h = reg.histogram("t.hist")
        n_threads, per_thread = 4, 500

        def work():
            for i in range(per_thread):
                h.observe(1e-6 * (i + 1))

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        s = h.snapshot()
        assert s["count"] == n_threads * per_thread
        assert s["min"] == pytest.approx(1e-6)
        assert s["max"] == pytest.approx(per_thread * 1e-6)
        assert sum(n for _, n in s["buckets"]) == s["count"]

    def test_gauge_set_and_callback(self):
        reg = MetricsRegistry()
        g = reg.gauge("t.gauge")
        g.set(42.5)
        assert g.value == 42.5
        cb = reg.gauge("t.cb", fn=lambda: 7.0)
        assert cb.value == 7.0
        boom = reg.gauge("t.boom", fn=lambda: 1 / 0)
        assert boom.value is None  # callback failure never breaks a dump
        assert "t.boom" in reg.dump_json()

    def test_get_or_create_and_kind_conflict(self):
        reg = MetricsRegistry()
        a = reg.counter("same")
        assert reg.counter("same") is a
        with pytest.raises(TypeError):
            reg.gauge("same")

    def test_disabled_fast_path_is_noop(self):
        reg = MetricsRegistry()
        c = reg.counter("t.off")
        h = reg.histogram("t.off.h")
        g = reg.gauge("t.off.g")
        saved = paddle.get_flags(["FLAGS_metrics"])
        try:
            paddle.set_flags({"FLAGS_metrics": False})
            c.inc()
            h.observe(1.0)
            g.set(3.0)
            assert c.value == 0 and h.count == 0 and g.value == 0.0
        finally:
            paddle.set_flags(saved)
        c.inc()
        assert c.value == 1

    def test_reset_zeroes_values_not_definitions(self):
        reg = MetricsRegistry()
        c = reg.counter("t.reset")
        c.inc(3)
        reg.reset()
        assert reg.counter("t.reset") is c and c.value == 0


class TestDumpers:
    def _filled(self):
        reg = MetricsRegistry()
        reg.counter("a.count", "a counter").inc(3)
        reg.gauge("b.gauge").set(2.5)
        h = reg.histogram("c.seconds", "a histogram")
        h.observe(2e-6)
        h.observe(5e-3)
        return reg

    def test_json_dump_roundtrips(self):
        snap = json.loads(self._filled().dump_json())
        assert snap["a.count"] == {"type": "counter", "value": 3}
        assert snap["b.gauge"]["value"] == 2.5
        assert snap["c.seconds"]["count"] == 2
        assert snap["c.seconds"]["sum"] == pytest.approx(5.002e-3)

    def test_prometheus_text_format(self):
        text = self._filled().dump_prometheus()
        assert "# TYPE paddle_a_count counter" in text
        assert "paddle_a_count 3" in text
        assert "# HELP paddle_a_count a counter" in text
        assert "paddle_b_gauge 2.5" in text
        # histogram: cumulative buckets + _sum/_count
        assert 'paddle_c_seconds_bucket{le="+Inf"} 2' in text
        assert "paddle_c_seconds_count 2" in text
        assert "paddle_c_seconds_sum" in text

    def test_format_metrics_table(self):
        out = format_metrics(self._filled().snapshot())
        assert "Metrics" in out and "a.count" in out and "histogram" in out


class TestFlightRecorderRing:
    def test_ring_bounds_and_order(self):
        fr = FlightRecorder(capacity=8)
        for i in range(20):
            fr.record(f"op{i}", ((None, None),))
        ents = fr.entries()
        assert len(ents) == 8
        assert [e[0] for e in ents] == list(range(12, 20))  # oldest first
        assert ents[-1][3] == "op19"
        assert fr.total_recorded == 20

    def test_partial_fill(self):
        fr = FlightRecorder(capacity=16)
        fr.record("only", ())
        ents = fr.entries()
        assert len(ents) == 1 and ents[0][3] == "only"

    def test_bounds_under_threads(self):
        fr = FlightRecorder(capacity=32)

        def work():
            for i in range(500):
                fr.record("t", ())

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(fr.entries()) <= 32  # the ring NEVER grows past capacity

    def test_dump_format(self):
        fr = FlightRecorder(capacity=4)
        fr.record("matmul", (((2, 3), "float32"), ((3, 4), "float32")),
                  cache_key=("matmul", ()))
        buf = io.StringIO()
        ents = fr.dump(buf)
        out = buf.getvalue()
        assert "op=matmul" in out and "2x3:float32" in out
        assert "key=('matmul', ())" in out
        assert len(ents) == 1

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)

    def test_resize_keeps_newest_and_stays_bounded(self):
        fr = FlightRecorder(capacity=8)
        for i in range(12):
            fr.record(f"op{i}", ())
        fr.resize(4)
        assert fr.capacity == 4
        assert [e[3] for e in fr.entries()] == ["op8", "op9", "op10",
                                                "op11"]
        for i in range(3):
            fr.record(f"new{i}", ())
        ents = fr.entries()
        assert len(ents) == 4
        assert ents[-1][3] == "new2"            # newest survives
        seqs = [e[0] for e in ents]
        assert seqs == sorted(seqs) and len(set(seqs)) == 4

    def test_size_flag_resizes_live_ring(self):
        rec = fr_mod.recorder()
        old_cap = rec.capacity
        saved = paddle.get_flags(["FLAGS_flight_recorder_size"])
        try:
            paddle.set_flags({"FLAGS_flight_recorder_size": 16})
            assert rec.capacity == 16   # same object, resized in place
            assert fr_mod.recorder() is rec
        finally:
            paddle.set_flags(saved)
        assert rec.capacity == old_cap

    def test_clear_empties_the_ring_and_restarts_numbering(self):
        rec = FlightRecorder(4)
        for i in range(6):
            rec.record(f"op{i}", ())
        rec.clear()
        assert rec.entries() == [] and rec.total_recorded == 0
        rec.record("again", ())
        assert [(e[0], e[3]) for e in rec.entries()] == [(0, "again")]


class TestFlightRecorderCrashDump:
    def test_injected_op_failure_reproduces_last_dispatches(self):
        """The op that raised must be the NEWEST dump entry: records are
        written before the kernel runs."""
        from paddle_tpu.ops import dispatcher

        rec = fr_mod.recorder()
        x = paddle.to_tensor(np.ones((3, 3), np.float32))
        _ = x + 1.0
        _ = paddle.matmul(x, x)

        @dispatcher.register_kernel("___obs_fail")
        def fail_kernel(a):
            raise RuntimeError("injected kernel failure")

        schema = dispatcher.OpSchema(
            name="___obs_fail",
            params=[dispatcher.ParamSpec("x", "tensor")],
            kernel="___obs_fail", differentiable=False, jit=False)
        with pytest.raises(RuntimeError, match="injected kernel failure"):
            dispatcher._dispatch(schema, {"x": x})

        buf = io.StringIO()
        ents = rec.dump(buf)
        names = [e[3] for e in ents]
        assert names[-1] == "___obs_fail"
        assert "matmul" in names and "add" in names
        assert "op=___obs_fail" in buf.getvalue()

    def test_excepthook_dumps_to_stderr(self, capsys, monkeypatch):
        fr_mod.recorder().record("crash_op", ())
        monkeypatch.setattr(fr_mod, "_prev_excepthook",
                            lambda *a: None)
        fr_mod._excepthook(RuntimeError, RuntimeError("boom"), None)
        err = capsys.readouterr().err
        assert "flight recorder" in err and "op=crash_op" in err

    def test_excepthook_dumps_to_file(self, tmp_path, monkeypatch, capsys):
        fr_mod.recorder().record("crash_op2", ())
        path = str(tmp_path / "crash.txt")
        saved = paddle.get_flags(["FLAGS_flight_recorder_path"])
        try:
            paddle.set_flags({"FLAGS_flight_recorder_path": path})
            monkeypatch.setattr(fr_mod, "_prev_excepthook",
                                lambda *a: None)
            fr_mod._excepthook(RuntimeError, RuntimeError("boom"), None)
        finally:
            paddle.set_flags(saved)
        assert os.path.exists(path)
        assert "op=crash_op2" in open(path).read()

    def test_excepthook_outlives_its_own_dumps(self, monkeypatch):
        from paddle_tpu.observability import incident, tracing

        def broken(*a):
            raise RuntimeError("dump broke")

        chained = []
        monkeypatch.setattr(fr_mod, "_crash_dump", broken)
        monkeypatch.setattr(tracing, "_crash_dump", broken)
        monkeypatch.setattr(incident, "_crash_incident", broken)
        monkeypatch.setattr(fr_mod, "_prev_excepthook",
                            lambda *a: chained.append(a[0]))
        fr_mod._excepthook(ValueError, ValueError("x"), None)
        assert chained == [ValueError]    # the traceback still prints

    @pytest.mark.parametrize("exc", [KeyboardInterrupt, SystemExit])
    def test_deliberate_exits_skip_the_dumps(self, exc, monkeypatch):
        from paddle_tpu.observability import incident, tracing
        called = []
        monkeypatch.setattr(fr_mod, "_crash_dump",
                            lambda: called.append("flight"))
        monkeypatch.setattr(tracing, "_crash_dump",
                            lambda: called.append("spans"))
        monkeypatch.setattr(incident, "_crash_incident",
                            lambda *a: called.append("bundle"))
        monkeypatch.setattr(fr_mod, "_prev_excepthook",
                            lambda *a: called.append("traceback"))
        fr_mod._excepthook(exc, exc(), None)
        assert called == ["traceback"]

    def test_excepthook_installed_and_chains(self):
        import sys
        assert fr_mod._installed
        # install is idempotent and must not have broken sys.excepthook
        fr_mod.install_excepthook()
        assert callable(sys.excepthook)

    def test_disabled_flag_skips_recording_cost_path(self):
        saved = paddle.get_flags(["FLAGS_flight_recorder"])
        rec = fr_mod.recorder()
        try:
            paddle.set_flags({"FLAGS_flight_recorder": False})
            before = rec.total_recorded
            _ = paddle.to_tensor([1.0]) * 2.0
            assert rec.total_recorded == before
        finally:
            paddle.set_flags(saved)


class TestDispatcherWiring:
    def test_dispatch_and_binder_counters(self):
        d0 = _counter_value("dispatch.count")
        f0 = _counter_value("dispatch.bind_fast")
        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        _ = x * 2.0                      # dunder fast path
        _ = paddle.matmul(x, x)          # generic precompiled binder
        assert _counter_value("dispatch.count") >= d0 + 2
        assert _counter_value("dispatch.bind_fast") >= f0 + 1

    def test_exec_cache_gauges_registered(self):
        snap = obs.snapshot()
        for name in ("dispatch.exec_cache.hits", "dispatch.exec_cache.misses",
                     "dispatch.exec_cache.size"):
            assert snap[name]["type"] == "gauge"
            assert snap[name]["value"] is not None

    def test_flight_recorder_sees_dispatches(self):
        rec = fr_mod.recorder()
        x = paddle.to_tensor(np.ones((5, 7), np.float32))
        _ = paddle.matmul(x.t(), x)
        ents = rec.entries()
        last_matmul = [e for e in ents if e[3] == "matmul"][-1]
        shapes = [a[0] for a in last_matmul[4]]
        assert (7, 5) in shapes and (5, 7) in shapes

    def test_stable_metric_names(self):
        """The names the README documents and ops teams scrape."""
        names = set(obs.registry().names())
        assert names >= {
            "dispatch.count", "dispatch.bind_fast", "dispatch.bind_slow",
            "dispatch.exec_cache.hits", "dispatch.exec_cache.misses",
            "dispatch.exec_cache.size",
            "autograd.backward.count", "autograd.fused.primed",
            "autograd.fused.hit", "autograd.fused.fallback",
            "autograd.fused.compile", "autograd.fused.bypass",
            "autograd.fused.plan_seconds", "autograd.fused.exec_seconds",
            "executor.runs", "executor.compiles", "executor.scope_vars",
            "jit.compiles", "jit.compile_seconds",
            "device.live_array_bytes", "device.live_arrays", "device.count",
        }


class TestEngineWiring:
    def test_backward_count_and_fused_gauges(self):
        from paddle_tpu.autograd import engine
        b0 = _counter_value("autograd.backward.count")
        engine._FUSED_CACHE.clear()
        engine._miss_streak = 0
        plan_h = obs.registry().get("autograd.fused.plan_seconds")
        p0 = plan_h.count
        for _ in range(3):   # 1st primes, 3rd executes the fused walk
            x = paddle.to_tensor(np.ones(4, np.float32))
            x.stop_gradient = False
            (x * 2.0).sum().backward()
        assert _counter_value("autograd.backward.count") == b0 + 3
        assert plan_h.count > p0
        snap = obs.snapshot()
        # gauges mirror the authoritative dict exactly
        for k, v in engine.fused_counters.items():
            assert snap["autograd.fused." + k]["value"] == float(v)
        assert snap["autograd.fused.hit"]["value"] >= 1.0
        assert obs.registry().get("autograd.fused.exec_seconds").count >= 1

    def test_counters_visible_in_prometheus_dump(self):
        text = obs.dump_prometheus()
        assert "paddle_autograd_fused_hit" in text
        assert "paddle_dispatch_count" in text
        assert "paddle_jit_compile_seconds_count" in text


class TestExecutorWiring:
    def test_runs_compiles_scope_gauge(self):
        import paddle_tpu.static as static
        r0 = _counter_value("executor.runs")
        paddle.enable_static()
        try:
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("obs_x", [2, 2], "float32")
                y = x * 2.0
            exe = static.Executor()
            out, = exe.run(main, feed={"obs_x": np.ones((2, 2), np.float32)},
                           fetch_list=[y])
        finally:
            paddle.disable_static()
        np.testing.assert_allclose(out, 2.0 * np.ones((2, 2)))
        assert _counter_value("executor.runs") == r0 + 1
        assert obs.snapshot()["executor.scope_vars"]["value"] is not None


class TestJitCompileHook:
    def test_fresh_compile_counted(self):
        import jax
        import jax.numpy as jnp
        c0 = _counter_value("jit.compiles")
        h0 = obs.registry().get("jit.compile_seconds").count
        # a never-seen jaxpr forces a real backend compile
        val = float(np.random.RandomState(0).rand()) + 2.0
        out = jax.jit(lambda a: a * val + 0.12345)(jnp.ones(3))
        jax.block_until_ready(out)
        assert _counter_value("jit.compiles") > c0
        assert obs.registry().get("jit.compile_seconds").count > h0


class TestProfilerIntegration:
    def test_counter_events_in_chrome_json(self, tmp_path):
        from paddle_tpu.profiler import Profiler, ProfilerTarget
        got = {}
        p = Profiler(targets=[ProfilerTarget.CPU],
                     on_trace_ready=lambda prof: got.update(
                         result=prof.get_profiler_result()),
                     trace_dir=str(tmp_path))
        with p:
            x = paddle.to_tensor(np.ones((4, 4), np.float32))
            _ = paddle.matmul(x, x)
        path = str(tmp_path / "trace.json")
        got["result"].save(path)
        payload = json.load(open(path))
        counters = [e for e in payload["traceEvents"] if e["ph"] == "C"]
        names = {e["name"] for e in counters}
        assert "dispatch.count" in names
        assert "autograd.fused.hit" in names
        for e in counters:
            assert "args" in e and e["cat"] == "Metric"
        # machine-readable section rides along
        assert payload["metrics"]["dispatch.count"]["type"] == "counter"

    def test_load_skips_counter_events_restores_metrics(self, tmp_path):
        from paddle_tpu.profiler import (Profiler, ProfilerTarget,
                                         load_profiler_result)
        got = {}
        p = Profiler(targets=[ProfilerTarget.CPU],
                     on_trace_ready=lambda prof: got.update(
                         result=prof.get_profiler_result()),
                     trace_dir=str(tmp_path))
        with p:
            _ = paddle.to_tensor([1.0]) + 1.0
        path = str(tmp_path / "t.json")
        got["result"].save(path)
        loaded = load_profiler_result(path)
        assert all(not isinstance(e.name, dict) for e in loaded.events)
        span_names = [e.name for e in loaded.events]
        assert "dispatch.count" not in span_names   # C events filtered
        assert loaded.metrics and "dispatch.count" in loaded.metrics

    def test_summary_has_metrics_section(self, tmp_path, capsys):
        from paddle_tpu.profiler import Profiler, ProfilerTarget
        p = Profiler(targets=[ProfilerTarget.CPU],
                     on_trace_ready=lambda prof: None,
                     trace_dir=str(tmp_path))
        with p:
            x = paddle.to_tensor(np.ones((4, 4), np.float32))
            _ = paddle.matmul(x, x)
        p.summary()
        out = capsys.readouterr().out
        assert "matmul" in out
        assert "Metrics" in out and "dispatch.count" in out

    def test_summary_thread_sep(self, tmp_path, capsys):
        from paddle_tpu.profiler import Profiler, ProfilerTarget, RecordEvent
        p = Profiler(targets=[ProfilerTarget.CPU],
                     on_trace_ready=lambda prof: None,
                     trace_dir=str(tmp_path))
        with p:
            with RecordEvent("main_span"):
                pass

            def other():
                with RecordEvent("worker_span"):
                    pass

            t = threading.Thread(target=other)
            t.start()
            t.join()
        p.summary(thread_sep=True)
        out = capsys.readouterr().out
        assert out.count("Thread ") >= 2
        assert "worker_span" in out and "main_span" in out

    def test_gen_summary_thread_sep_tables(self):
        from paddle_tpu.profiler.profiler import _HostEvent
        from paddle_tpu.profiler import TracerEventType
        from paddle_tpu.profiler.profiler_statistic import gen_summary
        evs = [_HostEvent("a", 0, 100, 1, TracerEventType.Operator),
               _HostEvent("b", 0, 300, 2, TracerEventType.Operator)]
        out = gen_summary(evs, thread_sep=True)
        assert "Thread 1:" in out and "Thread 2:" in out
        flat = gen_summary(evs, thread_sep=False)
        assert "Thread" not in flat

    def test_export_filenames_collision_safe(self, tmp_path, monkeypatch):
        """Two exports in the same wall-clock millisecond must produce
        two files (per-process monotonic suffix)."""
        import time as _time
        from paddle_tpu.profiler import Profiler, ProfilerTarget
        from paddle_tpu.profiler import export_chrome_tracing
        monkeypatch.setattr(_time, "time", lambda: 1700000000.0)
        cb = export_chrome_tracing(str(tmp_path), worker_name="w0")
        for _ in range(2):
            with Profiler(targets=[ProfilerTarget.CPU], on_trace_ready=cb,
                          trace_dir=str(tmp_path)):
                _ = paddle.to_tensor([1.0]) * 2.0
        files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
        assert len(files) == 2, files


class TestShardMapShim:
    def test_shim_accepts_modern_kwargs(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.jax_compat import shard_map
        # jax.sharding.Mesh exists on every jax generation the shim
        # targets (jax.make_mesh does not)
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("x",))
        f = shard_map(lambda a: a * 2.0, mesh=mesh, in_specs=(P("x"),),
                      out_specs=P("x"), axis_names=frozenset({"x"}),
                      check_vma=False)
        x = jnp.arange(float(jax.device_count() * 2)).reshape(
            jax.device_count(), 2)
        np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                                   np.asarray(x) * 2.0)

    def test_is_distributed_initialized_returns_bool(self):
        from paddle_tpu.jax_compat import is_distributed_initialized
        assert is_distributed_initialized() is False
