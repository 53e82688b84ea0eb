"""Incident forensics plane (PR18 tentpole): classified host stacks,
committed incident bundles, and their gating/retention discipline.

Unit tier: classify_frames precedence (subsystem beats mechanism — a
queue.get parked in Condition.wait is data_wait, not lock_wait),
capture_stacks over a genuinely blocked live thread, IncidentRecorder
bundle assembly against the durability commit protocol (every part file
present, COMMITTED marker last), the per-kind rate limit, keep-K
retention pruning, root-resolution precedence (explicit > flag >
first-wins attach), the disabled-flag short-circuit with its stderr
fallback for die-now paths, and the crash-excepthook trigger chain.
The end-to-end hang/failover attributions live with the chaos fixtures
in test_serving_resilience.py / test_serving_fleet.py.
"""

import importlib
import json
import os
import queue
import threading
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import debug, flight_recorder, incident
from paddle_tpu.observability.debug import (STACK_CLASSES, capture_stacks,
                                            classify_frames, format_stacks,
                                            stacks_snapshot)
from paddle_tpu.observability.incident import (INCIDENT_KINDS,
                                               IncidentRecorder)
from paddle_tpu.utils.durability import read_committed_marker


@pytest.fixture
def no_rate_limit():
    saved = paddle.get_flags(["FLAGS_incident_rate_limit_s"])
    paddle.set_flags({"FLAGS_incident_rate_limit_s": 0.0})
    yield
    paddle.set_flags(saved)


# ------------------------------------------------------ stack classification

class TestClassifyFrames:
    def test_vocabulary_is_frozen(self):
        assert STACK_CLASSES == frozenset({
            "data_wait", "jit_compile", "exec_cache_load", "device_call",
            "collective", "journal_fsync", "lock_wait", "idle", "other"})

    def test_queue_get_is_data_wait_not_lock_wait(self):
        # innermost frame of a queue.get IS threading.Condition.wait:
        # the subsystem (waiting on data) must win over the mechanism
        frames = [("/usr/lib/python3.10/threading.py", 320, "wait"),
                  ("/usr/lib/python3.10/queue.py", 171, "get"),
                  ("/app/worker.py", 10, "loop")]
        assert classify_frames(frames) == "data_wait"

    def test_dataloader_prefetch_is_data_wait(self):
        frames = [("/usr/lib/python3.10/threading.py", 320, "wait"),
                  ("paddle_tpu/io/dataloader.py", 88, "fill_ring")]
        assert classify_frames(frames) == "data_wait"

    def test_journal_fsync_wins_over_inner_lock(self):
        frames = [("/usr/lib/python3.10/threading.py", 300, "acquire"),
                  ("paddle_tpu/utils/durability.py", 40, "fsync_write"),
                  ("paddle_tpu/serving/resilience/journal.py", 200,
                   "flush")]
        assert classify_frames(frames) == "journal_fsync"

    def test_jax_compile_is_jit_compile(self):
        frames = [("site-packages/jax/_src/compiler.py", 500,
                   "backend_compile"),
                  ("paddle_tpu/jit/step_capture.py", 100, "_capture")]
        assert classify_frames(frames) == "jit_compile"

    def test_cache_deserialize_is_exec_cache_load_not_jit_compile(self):
        # a thread parked deserializing a cached executable would also
        # match jit_compile's jax-internals patterns further down the
        # stack — warm-MTTR attribution needs the cache-load label
        frames = [("site-packages/jax/_src/compiler.py", 500,
                   "backend_compile"),
                  ("paddle_tpu/jit/exec_store.py", 420, "_deserialize"),
                  ("paddle_tpu/jit/step_capture.py", 100, "_capture")]
        assert classify_frames(frames) == "exec_cache_load"

    def test_block_until_ready_is_device_call_any_file(self):
        frames = [("site-packages/jax/_src/array.py", 600,
                   "block_until_ready"),
                  ("/app/serve.py", 12, "step")]
        assert classify_frames(frames) == "device_call"

    def test_collective_file_matches_any_function(self):
        frames = [("paddle_tpu/distributed/collective.py", 77,
                   "all_reduce")]
        assert classify_frames(frames) == "collective"

    def test_bare_lock_is_lock_wait(self):
        frames = [("/usr/lib/python3.10/threading.py", 300, "acquire"),
                  ("/app/mine.py", 5, "work")]
        assert classify_frames(frames) == "lock_wait"

    def test_exporter_helper_demotes_to_idle(self):
        # outermost frame owned by the telemetry server: its poll loop
        # parking on a lock is not news in a hang report
        frames = [("/usr/lib/python3.10/threading.py", 300, "wait"),
                  ("/usr/lib/python3.10/selectors.py", 400, "select"),
                  ("paddle_tpu/observability/exporter.py", 170,
                   "_serve_loop")]
        assert classify_frames(frames) == "idle"

    def test_unowned_stack_is_other(self):
        assert classify_frames([("/app/x.py", 1, "f")]) == "other"
        assert classify_frames([]) == "other"

    def test_classes_all_registered(self):
        for frames, want in [
                ([("queue.py", 1, "get")], "data_wait"),
                ([("x.py", 1, "f")], "other")]:
            assert classify_frames(frames) in STACK_CLASSES
            assert want in STACK_CLASSES


class TestCaptureStacks:
    def test_live_blocked_thread_attributed(self):
        q = queue.Queue()
        started = threading.Event()

        def blocked():
            started.set()
            q.get(timeout=30.0)

        t = threading.Thread(target=blocked, name="wedge-probe",
                             daemon=True)
        t.start()
        started.wait(5.0)
        deadline = time.time() + 5.0
        cls = None
        while time.time() < deadline:
            stacks = capture_stacks()
            mine = [s for s in stacks if s["name"] == "wedge-probe"]
            if mine and mine[0]["class"] == "data_wait":
                cls = mine[0]["class"]
                break
            time.sleep(0.02)
        q.put(None)
        t.join(5.0)
        assert cls == "data_wait"

    def test_fallback_capture_sees_this_thread(self):
        (rec,) = debug._capture_fallback()
        assert rec["current"] is True
        assert rec["thread_id"] == threading.get_ident()
        assert rec["class"] in STACK_CLASSES
        assert ("test_fallback_capture_sees_this_thread"
                in [name for _, _, name in rec["frames"]])

    def test_current_thread_flagged_and_sorted_last(self):
        stacks = capture_stacks()
        assert stacks, "no threads captured"
        assert stacks[-1]["current"] is True
        assert sum(1 for s in stacks if s["current"]) == 1

    def test_snapshot_tally_matches(self):
        snap = stacks_snapshot()
        assert snap["threads"] == len(snap["stacks"])
        assert sum(snap["by_class"].values()) == snap["threads"]
        assert set(snap["by_class"]) <= STACK_CLASSES

    def test_format_and_json_round_trip(self):
        snap = stacks_snapshot()
        text = format_stacks(snap["stacks"])
        assert f"{snap['threads']} threads:" in text
        json.dumps(snap)          # bundles embed this verbatim

    def test_max_frames_honored(self):
        stacks = capture_stacks(max_frames=2)
        assert all(len(s["frames"]) <= 2 for s in stacks)


# ------------------------------------------------------ incident bundles

class TestIncidentRecorder:
    def test_bundle_is_committed_and_complete(self, tmp_path,
                                              no_rate_limit):
        rec = IncidentRecorder(str(tmp_path))
        path = rec.record("debug.manual", step=42,
                          attrs={"why": "test"}, trace_id=0xabc,
                          journal={"watermarks": {1: 3}})
        assert path and os.path.basename(path).startswith("incident-42-")
        md = read_committed_marker(path)
        assert md is not None
        assert md["kind"] == "debug.manual" and md["step"] == 42
        assert md["trace_id"] == f"{0xabc:016x}"
        for part in ("incident.json", "stacks.json", "stacks.txt",
                     "metrics.json", "trace.json", "flight.txt",
                     "journal.json"):
            assert os.path.exists(os.path.join(path, part)), part
        with open(os.path.join(path, "incident.json")) as f:
            hdr = json.load(f)
        assert hdr["kind"] == "debug.manual"
        assert hdr["attrs"] == {"why": "test"}
        assert hdr["pid"] == os.getpid()
        assert hdr["flags_version"]
        assert "incident_keep" in hdr["flags"]
        assert hdr["versions"]["python"]
        assert set(hdr["stack_classes"]) <= STACK_CLASSES

    def test_journal_part_is_optional(self, tmp_path, no_rate_limit):
        rec = IncidentRecorder(str(tmp_path))
        path = rec.record("debug.manual")
        assert not os.path.exists(os.path.join(path, "journal.json"))

    @pytest.mark.parametrize("part, module, fn", [
        ("trace.json", "tracing", "dump_trace"),
        ("perf.json", "perf", "perfz_snapshot"),
    ], ids=["trace", "perf"])
    def test_bundle_commits_without_a_broken_garnish(
            self, tmp_path, no_rate_limit, monkeypatch, part, module, fn):
        mod = importlib.import_module(f"paddle_tpu.observability.{module}")

        def broken(*a, **k):
            raise RuntimeError("garnish broke")

        monkeypatch.setattr(mod, fn, broken)
        path = IncidentRecorder(str(tmp_path)).record("debug.manual",
                                                      step=3)
        assert path is not None and read_committed_marker(path)
        assert not os.path.exists(os.path.join(path, part))
        for kept in ("incident.json", "stacks.json", "metrics.json"):
            assert os.path.exists(os.path.join(path, kept)), kept

    def test_unknown_kind_raises(self, tmp_path):
        with pytest.raises(ValueError, match="INCIDENT_KINDS"):
            IncidentRecorder(str(tmp_path)).record("serving.hagn")

    def test_rate_limit_per_kind(self, tmp_path):
        saved = paddle.get_flags(["FLAGS_incident_rate_limit_s"])
        paddle.set_flags({"FLAGS_incident_rate_limit_s": 3600.0})
        try:
            rec = IncidentRecorder(str(tmp_path))
            d0 = incident._C_DROPPED.value
            assert rec.record("debug.manual") is not None
            assert rec.record("debug.manual") is None     # suppressed
            assert incident._C_DROPPED.value == d0 + 1
            # a DIFFERENT kind is not held hostage
            assert rec.record("perf.regression") is not None
        finally:
            paddle.set_flags(saved)

    def test_keep_k_retention(self, tmp_path, no_rate_limit):
        saved = paddle.get_flags(["FLAGS_incident_keep"])
        paddle.set_flags({"FLAGS_incident_keep": 2})
        try:
            rec = IncidentRecorder(str(tmp_path))
            for i in range(4):
                assert rec.record("debug.manual", step=i) is not None
                time.sleep(0.01)          # distinct mtimes for pruning
            left = sorted(d for d in os.listdir(tmp_path)
                          if d.startswith("incident-"))
            assert len(left) == 2
            steps = {read_committed_marker(os.path.join(tmp_path, d))["step"]
                     for d in left}
            assert steps == {2, 3}        # newest K survive
        finally:
            paddle.set_flags(saved)

    def test_uncommitted_debris_is_invisible_and_unpruned(self, tmp_path,
                                                          no_rate_limit):
        # a writer killed mid-dump leaves a directory without COMMITTED:
        # retention must not count it and recent() never indexed it
        debris = tmp_path / "incident-9-deadbeef"
        debris.mkdir()
        (debris / "incident.json").write_text("{}")
        rec = IncidentRecorder(str(tmp_path))
        assert rec.record("debug.manual", step=1) is not None
        assert debris.exists()            # not pruned (never committed)
        assert all(r["step"] != 9 for r in rec.recent())

    def test_recent_index_newest_first(self, tmp_path, no_rate_limit):
        rec = IncidentRecorder(str(tmp_path))
        rec.record("debug.manual", step=1)
        rec.record("debug.manual", step=2)
        r = rec.recent()
        assert [x["step"] for x in r[:2]] == [2, 1]
        assert all(x["kind"] in INCIDENT_KINDS for x in r)

    def test_root_precedence_explicit_flag_attach(self, tmp_path,
                                                  no_rate_limit):
        a, b, c = (tmp_path / n for n in ("attach", "flag", "explicit"))
        for d in (a, b, c):
            d.mkdir()
        rec = IncidentRecorder()
        rec.attach_root(str(a))
        rec.attach_root(str(tmp_path / "late"))   # first attach wins
        assert rec.resolve_root() == str(a)
        saved = paddle.get_flags(["FLAGS_incident_dir"])
        paddle.set_flags({"FLAGS_incident_dir": str(b)})
        try:
            assert rec.resolve_root() == str(b)          # flag > attach
            assert rec.resolve_root(str(c)) == str(c)    # explicit > flag
            p = rec.record("debug.manual", root=str(c))
            assert p.startswith(str(c))
        finally:
            paddle.set_flags(saved)

    def test_no_root_is_counted_dropped(self):
        rec = IncidentRecorder()
        d0 = incident._C_DROPPED.value
        assert rec.record("debug.manual") is None
        assert incident._C_DROPPED.value == d0 + 1

    def test_disabled_flag_short_circuits(self, tmp_path, capsys):
        saved = paddle.get_flags(["FLAGS_incident_recorder"])
        paddle.set_flags({"FLAGS_incident_recorder": False})
        try:
            rec = IncidentRecorder(str(tmp_path))
            assert rec.record("debug.manual") is None
            assert list(tmp_path.iterdir()) == []
            # ... but a die-now caller still gets stacks on stderr
            assert rec.record("serving.hang", step=7,
                              fallback_stderr=True) is None
            err = capsys.readouterr().err
            assert "kind=serving.hang" in err and "step=7" in err
            assert "threads:" in err
        finally:
            paddle.set_flags(saved)

    def test_metrics_recorded(self, tmp_path, no_rate_limit):
        r0 = incident._C_RECORDED.value
        IncidentRecorder(str(tmp_path)).record("debug.manual")
        assert incident._C_RECORDED.value == r0 + 1

    def test_assembly_failure_drops_not_raises(self, tmp_path,
                                               no_rate_limit,
                                               monkeypatch):
        # forensics must never take down the path being diagnosed
        def boom():
            raise RuntimeError("capture failed")
        monkeypatch.setattr(debug, "stacks_snapshot", boom)
        d0 = incident._C_DROPPED.value
        assert IncidentRecorder(str(tmp_path)).record(
            "debug.manual") is None
        assert incident._C_DROPPED.value == d0 + 1


# ------------------------------------------------------ trigger chains

class TestTriggers:
    def test_crash_excepthook_chains_into_bundle(self, tmp_path,
                                                 no_rate_limit,
                                                 monkeypatch, capsys):
        saved = paddle.get_flags(["FLAGS_incident_dir"])
        paddle.set_flags({"FLAGS_incident_dir": str(tmp_path)})
        try:
            flight_recorder._excepthook(
                ValueError, ValueError("boom"), None)
            capsys.readouterr()           # the stderr crash dumps
            bundles = [d for d in os.listdir(tmp_path)
                       if d.startswith("incident-")]
            assert len(bundles) == 1
            with open(os.path.join(tmp_path, bundles[0],
                                   "incident.json")) as f:
                hdr = json.load(f)
            assert hdr["kind"] == "crash.exception"
            assert hdr["attrs"]["exc_type"] == "ValueError"
            assert "boom" in hdr["attrs"]["exc"]
        finally:
            paddle.set_flags(saved)

    def test_crash_trigger_respects_flag(self, tmp_path, monkeypatch,
                                         capsys):
        saved = paddle.get_flags(
            ["FLAGS_incident_recorder", "FLAGS_incident_dir"])
        paddle.set_flags({"FLAGS_incident_recorder": False,
                          "FLAGS_incident_dir": str(tmp_path)})
        try:
            flight_recorder._excepthook(
                ValueError, ValueError("boom"), None)
            capsys.readouterr()
            assert list(tmp_path.iterdir()) == []
        finally:
            paddle.set_flags(saved)

    def test_manual_kind_used_by_debugz_cli(self, tmp_path,
                                            no_rate_limit, capsys):
        saved = paddle.get_flags(["FLAGS_incident_dir"])
        paddle.set_flags({"FLAGS_incident_dir": str(tmp_path)})
        try:
            path = incident.record_incident("debug.manual")
            assert path is not None
            from paddle_tpu.observability.__main__ import main
            assert main(["debugz"]) == 0
            out = capsys.readouterr().out
            assert "threads:" in out
            assert "debug.manual" in out
        finally:
            paddle.set_flags(saved)
            with incident._RECORDER._lock:
                incident._RECORDER._recent.clear()
