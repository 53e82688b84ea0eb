"""End-to-end tracing (ISSUE 13): one trace id from the fleet router to
the compiled step.

Fast tier-1 covers the span core (nesting, the frozen-taxonomy runtime
check, the FLAGS_tracing disabled path, the bounded ring), contextvars
propagation and the inject/extract wire form, Chrome-trace export, the
crash artifacts (excepthook span dump, flight-recorder header trace
id), the profiler merge, the ``python -m paddle_tpu.observability``
CLI, and trace continuity across a thread-hosted fleet — one trace_id
from ``fleet.submit`` through admission, queue/prefill/decode phase
segments and the finish edge, surviving a kill-failover with the
original id.

The slow-marked tranche runs REAL subprocess replicas: the ``tc``
submit-frame field must re-establish the router's trace in the child,
a SIGKILL'd victim's requests must keep their original trace_id on the
survivor, and the survivor's clean-exit ``trace.json`` dump must carry
those ids.
"""

import io
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import flight_recorder, tracing
from paddle_tpu.observability.metrics import METRIC_NAMES, registry
from paddle_tpu.serving.fleet import (ReplicaRouter,
                                      SubprocessReplicaHandle,
                                      ThreadReplicaHandle)

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                      intermediate_size=160, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=256)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


ENG = dict(max_batch=4, num_blocks=64, block_size=16, temperature=0.9,
           seed=17)


def _prompts(n=4, rng_seed=3, bs=16):
    rng = np.random.RandomState(rng_seed)
    head = rng.randint(0, 128, bs).tolist()
    return [(head + rng.randint(0, 128, 3 + 2 * i).tolist())
            if i % 2 == 0 else rng.randint(0, 128, 4 + i).tolist()
            for i in range(n)]


def _mk_fleet(model, tmp_path, n=2, **router_kw):
    reps = [ThreadReplicaHandle(f"rep{i}", lambda: model,
                                str(tmp_path / f"rep{i}"),
                                journal_flush_every=1, **ENG)
            for i in range(n)]
    router = ReplicaRouter(reps, block_size=ENG["block_size"],
                           **router_kw)
    router.start()
    router.wait_ready(timeout_s=180.0)
    return router, reps


def _recorded(name=None):
    """Completed ring entries, optionally filtered by span name."""
    ents = tracing._ring().entries()
    return ents if name is None else [s for s in ents if s.name == name]


# ---------------------------------------------------------- span core (fast)

class TestSpanCore:
    def test_nested_spans_share_trace_and_parent(self):
        tracing.clear()
        with tracing.span("fleet.submit") as outer:
            assert outer.trace_id != 0
            assert outer.parent_id == 0          # fresh root
            assert tracing.current() == outer.context
            with tracing.span("serving.admit") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
            # inner ended: ambient context restored to the outer span
            assert tracing.current() == outer.context
        assert tracing.current() is None
        names = [s.name for s in _recorded()]
        assert names.count("fleet.submit") == 1
        assert names.count("serving.admit") == 1

    def test_sibling_roots_get_distinct_trace_ids(self):
        tracing.clear()
        with tracing.span("fleet.submit") as a:
            pass
        with tracing.span("fleet.submit") as b:
            pass
        assert a.trace_id != b.trace_id
        assert a.span_id != b.span_id

    def test_start_span_does_not_activate(self):
        tracing.clear()
        sp = tracing.start_span("serving.drain")
        try:
            assert tracing.current() is None
            assert sp in tracing.active_spans()
        finally:
            sp.end()
        assert sp not in tracing.active_spans()
        assert _recorded("serving.drain")

    def test_unregistered_name_rejected(self):
        with pytest.raises(ValueError, match="unregistered span name"):
            tracing.span("serving.not_a_registered_name")
        with tracing.span("fleet.submit") as sp:
            with pytest.raises(ValueError, match="unregistered"):
                sp.event("fleet.not_registered_either")

    def test_span_events_are_capped(self):
        tracing.clear()
        with tracing.span("serving.step") as sp:
            for _ in range(tracing._EVENTS_MAX + 40):
                sp.event("serving.first_token")
        (rec,) = _recorded("serving.step")
        assert len(rec.events) == tracing._EVENTS_MAX

    def test_counters_registered_and_incremented(self):
        assert "tracing.spans" in METRIC_NAMES
        assert "tracing.events" in METRIC_NAMES
        spans0 = registry().counter("tracing.spans").value
        events0 = registry().counter("tracing.events").value
        with tracing.span("serving.step"):
            tracing.event("serving.first_token")
        assert registry().counter("tracing.spans").value == spans0 + 1
        assert registry().counter("tracing.events").value == events0 + 1

    def test_disabled_gate_is_inert(self):
        tracing.clear()
        total0 = tracing._ring().total
        paddle.set_flags({"FLAGS_tracing": False})
        try:
            assert not tracing.enabled()
            sp = tracing.span("fleet.submit")
            assert sp.trace_id == 0
            sp.set(gid=1).event("fleet.retry")
            sp.end()
            tracing.record_span("serving.queue", 0, 1)
            tracing.instant("serving.finish")
            tracing.event("serving.first_token")
            assert tracing.inject() is None
            assert tracing.activate((1, 2)) is None
        finally:
            paddle.set_flags({"FLAGS_tracing": True})
        assert tracing._ring().total == total0       # nothing recorded


# -------------------------------------------------------- propagation (fast)

class TestPropagation:
    def test_inject_extract_roundtrip(self):
        assert tracing.inject() is None              # untraced: no frame
        with tracing.span("fleet.submit") as sp:
            wire = tracing.inject()
            assert wire == [f"{sp.trace_id:016x}", f"{sp.span_id:016x}"]
            assert tracing.extract(wire) == sp.context

    def test_extract_tolerates_torn_frames(self):
        for torn in (None, [], ["zz", "qq"], [1], ["0f"], "garbage",
                     [None, None]):
            assert tracing.extract(torn) is None

    def test_activate_deactivate_restores_ambient(self):
        token = tracing.activate((5, 7))
        try:
            assert tracing.current() == (5, 7)
            assert tracing.current_trace_id() == 5
        finally:
            tracing.deactivate(token)
        assert tracing.current() is None
        assert tracing.current_trace_id() == 0
        tracing.deactivate(None)                     # no-op, no raise

    def test_new_threads_start_untraced(self):
        seen = {}

        def probe():
            seen["ambient"] = tracing.current()
            tok = tracing.activate((9, 11))
            try:
                seen["activated"] = tracing.current()
            finally:
                tracing.deactivate(tok)

        with tracing.span("fleet.submit"):
            t = threading.Thread(target=probe)
            t.start()
            t.join(timeout=30.0)
        assert seen["ambient"] is None       # contextvars don't cross
        assert seen["activated"] == (9, 11)  # the carrier does


# --------------------------------------------------------------- ring (fast)

class TestRingBounds:
    def test_ring_bounds_and_flag_resize(self):
        tracing.clear()
        entry = paddle.get_flags(["FLAGS_tracing_ring_size"])
        try:
            paddle.set_flags({"FLAGS_tracing_ring_size": 8})
            for _ in range(20):
                tracing.instant("serving.finish")
            assert len(_recorded("serving.finish")) == 8
            assert tracing._ring().total == 20
            # growing keeps the survivors
            paddle.set_flags({"FLAGS_tracing_ring_size": 64})
            assert len(_recorded("serving.finish")) == 8
            tracing.instant("serving.finish")
            assert len(_recorded("serving.finish")) == 9
        finally:
            paddle.set_flags(entry)
        tracing.clear()
        assert _recorded() == []
        assert tracing._ring().total == 0


# ------------------------------------------ ring reader + xplane (fast)

def _xplane_host_events(log_dir, prefix):
    """(name, start_ns, duration_ns) of the host planes' events whose name
    starts with ``prefix``, read back from the one trace under log_dir."""
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


class TestProfilerClock:
    """A live span is one record in the ring and, while a jax.profiler
    trace runs, one host event of the same name in the xplane."""

    @pytest.mark.parametrize("opener", ["span", "start_span"])
    def test_live_span_is_in_the_ring_and_the_xplane(self, tmp_path, opener):
        import jax
        tracing.clear()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with getattr(tracing, opener)("serving.step.pack",
                                          trace=tracing.UNTRACED,
                                          attrs={"step": 7}):
                time.sleep(0.002)
            tracing.record_span("serving.queue", 1, 2)     # ring only
            tracing.instant("serving.finish")              # ring only
        finally:
            jax.profiler.stop_trace()
        (got,) = tracing.finished_spans("serving.step.")
        assert got.name == "serving.step.pack" and got.attrs == {"step": 7}
        assert got.t1_ns - got.t0_ns >= 2_000_000
        events = _xplane_host_events(str(tmp_path), "serving.")
        assert [e[0] for e in events] == ["serving.step.pack"]
        # the annotation lies inside the ring's stamps, to a few microseconds
        assert 0 <= (got.t1_ns - got.t0_ns) - events[0][2] < 1_000_000

    def test_tracing_off_reaches_neither(self, tmp_path):
        import jax
        tracing.clear()
        paddle.set_flags({"FLAGS_tracing": False})
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracing.start_span("serving.step.pack") as sp:
                assert sp.t0_ns is None and sp.t1_ns is None
            with tracing.span("serving.step.sync"):
                pass
        finally:
            jax.profiler.stop_trace()
            paddle.set_flags({"FLAGS_tracing": True})
        assert tracing.finished_spans() == []
        assert _xplane_host_events(str(tmp_path), "serving.") == []

    def test_finished_spans_filters_by_prefix_and_start(self):
        tracing.clear()
        tracing.record_span("serving.queue", 10, 20, attrs={"rid": 1})
        tracing.record_span("serving.step", 30, 40)
        tracing.record_span("serving.step.sync", 35, 40)
        tracing.instant("serving.finish")
        names = lambda spans: [s.name for s in spans]
        assert names(tracing.finished_spans()) == [
            "serving.queue", "serving.step", "serving.step.sync"]
        assert names(tracing.finished_spans("serving.step")) == [
            "serving.step", "serving.step.sync"]
        assert names(tracing.finished_spans("serving.", since_ns=30)) == [
            "serving.step", "serving.step.sync"]
        assert tracing.finished_spans("serving.queue")[0] == (
            "serving.queue", 10, 20, {"rid": 1})
        assert tracing.finished_spans("fleet.") == []

    def test_default_ring_holds_a_benchmark_window(self):
        # 50 s at 8.45 steps/s write some 4,000 entries (ISSUE 26)
        assert paddle.get_flags(["FLAGS_tracing_ring_size"])[
            "FLAGS_tracing_ring_size"] >= 16384


# ------------------------------------------------------- chrome export (fast)

class TestChromeExport:
    def test_dump_trace_is_valid_chrome_json(self):
        tracing.clear()
        with tracing.span("fleet.submit", attrs={"gid": 3}) as sp:
            sp.event("fleet.retry", attempt=1)
        tracing.instant("serving.finish", trace=sp.context)
        doc = json.loads(tracing.dump_trace())
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        x = [e for e in evs if e["name"] == "fleet.submit"]
        assert len(x) == 1 and x[0]["ph"] == "X" and x[0]["dur"] >= 0
        assert x[0]["args"]["gid"] == 3
        assert x[0]["args"]["trace_id"] == f"{sp.trace_id:016x}"
        # the span event and the instant render as "i", linked by args
        i_names = {e["name"]: e for e in evs if e["ph"] == "i"}
        assert i_names["fleet.retry"]["args"]["parent_id"] \
            == f"{sp.span_id:016x}"
        assert i_names["serving.finish"]["args"]["trace_id"] \
            == f"{sp.trace_id:016x}"

    def test_active_span_clipped_to_now(self):
        tracing.clear()
        sp = tracing.start_span("serving.drain")
        try:
            doc = tracing.to_chrome()
            (e,) = [x for x in doc["traceEvents"]
                    if x["name"] == "serving.drain"]
            assert e["args"]["active"] is True
            assert e["dur"] >= 0
        finally:
            sp.end()

    def test_dump_trace_to_path_and_io(self, tmp_path):
        tracing.clear()
        tracing.instant("serving.finish")
        p = str(tmp_path / "trace.json")
        s = tracing.dump_trace(p)
        assert json.load(open(p)) == json.loads(s)
        buf = io.StringIO()
        tracing.dump_trace(buf)
        assert json.loads(buf.getvalue())["traceEvents"]


# ------------------------------------------------------ crash artifacts (fast)

class TestCrashArtifacts:
    def test_crash_dump_writes_chrome_json_at_flag_path(self, tmp_path):
        tracing.clear()
        tracing.instant("serving.finish")
        path = str(tmp_path / "crash_trace.json")
        paddle.set_flags({"FLAGS_tracing_path": path})
        try:
            tracing._crash_dump()
        finally:
            paddle.set_flags({"FLAGS_tracing_path": ""})
        doc = json.load(open(path))
        assert any(e["name"] == "serving.finish"
                   for e in doc["traceEvents"])

    def test_excepthook_prints_span_listing(self, capsys):
        tracing.clear()
        with tracing.span("serving.recover"):
            pass
        sp = tracing.start_span("serving.drain")   # active at "crash"
        try:
            flight_recorder._excepthook(ValueError, ValueError("boom"),
                                        None)
        finally:
            sp.end()
        err = capsys.readouterr().err
        assert "[paddle_tpu tracing]" in err
        assert "serving.recover" in err
        assert "ACTIVE serving.drain" in err
        assert "ValueError" in err                 # traceback still printed

    def test_flight_recorder_dump_carries_trace_id(self):
        buf = io.StringIO()
        with tracing.span("serving.admit") as sp:
            flight_recorder.dump(buf)
        assert f"trace_id={sp.trace_id:016x}" in buf.getvalue()
        # untraced: no stray header field
        buf2 = io.StringIO()
        flight_recorder.dump(buf2)
        assert "trace_id=" not in buf2.getvalue()


# ------------------------------------------------------ profiler merge (fast)

class TestProfilerMerge:
    def test_spans_land_in_profiler_window(self, tmp_path):
        from paddle_tpu.profiler import (Profiler, ProfilerTarget,
                                         TracerEventType)
        got = {}
        p = Profiler(targets=[ProfilerTarget.CPU],
                     on_trace_ready=lambda prof: got.update(
                         res=prof.get_profiler_result()),
                     trace_dir=str(tmp_path))
        p.start()
        with tracing.span("serving.recover"):
            pass
        p.stop()
        assert tracing._SINK is None               # sink removed on stop
        evs = [e for e in got["res"].events if e.name == "serving.recover"]
        assert evs and evs[0].event_type is TracerEventType.Trace

    def test_spans_outside_window_not_sunk(self):
        assert tracing._SINK is None
        with tracing.span("serving.recover"):      # must not raise
            pass


# ----------------------------------------------------------------- CLI (fast)

class TestObservabilityCLI:
    def test_module_cli_emits_valid_dumps(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability", "trace"],
            capture_output=True, text=True, env=env, timeout=300)
        assert out.returncode == 0, out.stderr
        assert "traceEvents" in json.loads(out.stdout)


# ------------------------------------------- fleet trace continuity (thread)

class TestFleetTraceContinuity:
    def test_one_trace_from_submit_to_finish(self, model, tmp_path):
        tracing.clear()
        router, _ = _mk_fleet(model, tmp_path)
        try:
            gids = [router.submit(p, max_new_tokens=4)
                    for p in _prompts(3, rng_seed=5)]
            router.drain_all(timeout_s=120.0)
        finally:
            router.close()
        submits = {s.attrs["gid"]: s for s in _recorded("fleet.submit")}
        assert set(gids) <= set(submits)
        traces = {g: submits[g].trace_id for g in gids}
        assert all(traces.values())                 # every submit traced
        assert len(set(traces.values())) == len(gids)
        by_trace = {}
        for s in _recorded():
            by_trace.setdefault(s.trace_id, []).append(s)
        for g in gids:
            names = {s.name for s in by_trace[traces[g]]}
            # the whole request life shares ONE trace id: admission +
            # durable ack, then the TTFT decomposition segments
            assert {"fleet.submit", "serving.admit",
                    "serving.journal_fsync", "serving.queue",
                    "serving.prefill", "serving.decode",
                    "serving.first_token", "serving.finish"} <= names
            admit = next(s for s in by_trace[traces[g]]
                         if s.name == "serving.admit")
            assert admit.parent_id == submits[g].span_id
            # phase segments tile the request's life in order
            phases = {s.name: s for s in by_trace[traces[g]]
                      if s.name in ("serving.queue", "serving.prefill",
                                    "serving.decode")}
            assert (phases["serving.queue"].t0_ns
                    <= phases["serving.prefill"].t0_ns
                    <= phases["serving.decode"].t0_ns)
            assert phases["serving.decode"].t1_ns \
                >= phases["serving.prefill"].t1_ns

    def test_failover_keeps_the_original_trace(self, model, tmp_path):
        tracing.clear()
        router, reps = _mk_fleet(model, tmp_path)
        try:
            gids = [router.submit(p, max_new_tokens=5)
                    for p in _prompts(5, rng_seed=11)]
            victim_gid = gids[-1]
            victim = router._outstanding[victim_gid].replica
            victim_trace = router._outstanding[victim_gid].trace[0]
            next(r for r in reps if r.name == victim).kill()
            router.drain_all(timeout_s=120.0)
            assert router.rerouted_requests >= 1
            assert router.dropped_requests == 0
        finally:
            router.close()
        # the death and every victim settlement were recorded as
        # instants carrying the ORIGINAL trace ids
        assert any(s.attrs["replica"] == victim
                   for s in _recorded("fleet.replica_dead"))
        failovers = _recorded("fleet.failover")
        assert any(s.trace_id == victim_trace
                   and s.attrs["gid"] == victim_gid for s in failovers)
        # the replayed admission on the survivor kept the trace id: the
        # victim request has MORE THAN ONE serving.admit under its one
        # trace (original admission + the handoff re-admission) unless
        # it was settled straight from the journal
        handoffs = [s for s in _recorded("fleet.handoff")
                    if s.trace_id == victim_trace]
        admits = [s for s in _recorded("serving.admit")
                  if s.trace_id == victim_trace]
        (fo,) = [s for s in failovers if s.attrs["gid"] == victim_gid]
        if fo.attrs["disposition"] == "parked":
            assert handoffs and len(admits) >= 2
        else:
            assert fo.attrs["disposition"] == "delivered_from_journal"


# ------------------------------------------------- subprocess chaos (slow)

@pytest.mark.slow
@pytest.mark.heavy
class TestSubprocessTracePropagation:
    def test_trace_crosses_process_and_survives_sigkill(self, model,
                                                        tmp_path):
        """The acceptance path: REAL worker processes, the ``tc`` frame
        field re-establishing the router's trace in the child, a
        SIGKILL mid-stream, and the survivor's clean-exit trace.json
        carrying the victim's ORIGINAL trace ids (the killed worker,
        like its journal tail, leaves no dump)."""
        tracing.clear()
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [_TESTS_DIR, os.path.dirname(_TESTS_DIR)]))
        config = {"factory": "serving_chaos_worker:build_model",
                  "engine": {**ENG, "journal_flush_every": 1},
                  "max_queue": 8, "hb_interval_s": 0.1,
                  "step_sleep_s": 0.02}
        reps = [SubprocessReplicaHandle(
                    f"sub{i}", str(tmp_path / f"sub{i}"), dict(config),
                    spawn_env=env)
                for i in range(2)]
        router = ReplicaRouter(reps, block_size=ENG["block_size"],
                               heartbeat_timeout_s=5.0,
                               submit_deadline_s=30.0)
        try:
            router.start()
            router.wait_ready(timeout_s=300.0)
            gids = [router.submit(p, max_new_tokens=8)
                    for p in _prompts(6, rng_seed=13)]
            traces = {g: router._outstanding[g].trace[0] for g in gids}
            victim_gid = gids[-1]
            victim = router._outstanding[victim_gid].replica
            next(r for r in reps if r.name == victim).kill()  # SIGKILL
            router.drain_all(timeout_s=300.0)
            assert router.rerouted_requests >= 1
            assert router.dropped_requests == 0
        finally:
            router.close()        # clean stop: survivors dump trace.json

        assert all(traces.values())
        failovers = _recorded("fleet.failover")
        assert any(s.trace_id == traces[victim_gid] for s in failovers)

        survivor = next(r.name for r in reps if r.name != victim)
        child = json.load(open(tmp_path / survivor / "trace.json"))
        child_admits = {
            e["args"]["trace_id"]: e for e in child["traceEvents"]
            if e["name"] == "serving.admit" and e["ph"] == "X"}
        # every admission the survivor saw belongs to a router trace
        router_hex = {f"{t:016x}" for t in traces.values()}
        assert child_admits and set(child_admits) <= router_hex
        # the victim's replayed request kept its ORIGINAL trace id
        # unless the dead journal already held the finished stream
        (fo,) = [s for s in failovers
                 if s.attrs["gid"] == victim_gid]
        if fo.attrs["disposition"] == "parked":
            assert f"{traces[victim_gid]:016x}" in child_admits
        # SIGKILL leaves no dump — exactly like the journal tail
        assert not os.path.exists(tmp_path / victim / "trace.json")
