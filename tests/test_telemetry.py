"""Fleet telemetry plane (ISSUE 14): labeled metrics, mergeable deltas,
Prometheus conformance, and the live ops endpoint.

Unit tier covers the metrics-registry extensions (labeled children with
frozen label sets, family kind discipline, the delta/merge wire format
the fleet heartbeats ride), a STRICT line-parser round trip of
``dump_prometheus`` (text exposition 0.0.4: HELP escaping, ``_total``
counter samples, TYPE-before-sample, cumulative ``le`` buckets,
deterministic ordering), and the exporter endpoints against an isolated
registry — including ``FLAGS_metrics=False``, the nothing-attached
/healthz, engine-phase-driven readiness, scrape-time SLI gauges, and a
subprocess proving a served-but-never-shut-down endpoint cannot hang
interpreter exit. The fleet-level acceptance (one scrape shows every
replica; a SIGKILLed replica's merged series survive) lives with the
fleet fixtures in test_serving_fleet.py.
"""

import json
import os
import re
import subprocess
import sys
import urllib.error
import urllib.request

import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import exporter as exporter_mod
from paddle_tpu.observability.exporter import TelemetryServer
from paddle_tpu.observability.metrics import (METRIC_NAMES, MetricsRegistry,
                                              _TIMING_BOUNDS, registry)

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _get(port, path, timeout=10.0):
    """(status, body_str, content_type) — 4xx/5xx returned, not raised."""
    url = f"http://127.0.0.1:{port}{path}"
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode(), r.headers.get("Content-Type")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), e.headers.get("Content-Type")


# ------------------------------------------------------ labeled instruments

class TestLabeledInstruments:
    def test_get_or_create_per_label_set(self):
        reg = MetricsRegistry()
        a = reg.counter("f.c", labels={"replica": "r0"})
        b = reg.counter("f.c", labels={"replica": "r1"})
        parent = reg.counter("f.c")
        assert a is not b and a is not parent
        # same label set (any insertion order) -> same child
        c = reg.counter("f.c", labels={"tenant": "t", "replica": "r0"})
        assert reg.counter("f.c", labels={"replica": "r0", "tenant": "t"}) \
            is c
        a.inc(2)
        b.inc(3)
        assert (a.value, b.value, parent.value) == (2, 3, 0)

    def test_family_kind_is_enforced_across_children(self):
        reg = MetricsRegistry()
        reg.counter("f.kind", labels={"replica": "r0"})
        with pytest.raises(TypeError):
            reg.gauge("f.kind", labels={"replica": "r1"})
        with pytest.raises(TypeError):
            reg.histogram("f.kind")

    def test_label_cap(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("f.cap", labels={f"k{i}": "v" for i in range(5)})

    def test_children_orders_unlabeled_first(self):
        reg = MetricsRegistry()
        reg.gauge("f.ch", labels={"replica": "r1"})
        reg.gauge("f.ch", labels={"replica": "r0"})
        parent = reg.gauge("f.ch")
        kids = reg.children("f.ch")
        assert kids[0] is parent
        assert [dict(k.labels).get("replica") for k in kids[1:]] \
            == ["r0", "r1"]

    def test_get_with_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("f.get", labels={"replica": "r0"})
        assert reg.get("f.get", labels={"replica": "r0"}) is c
        assert reg.get("f.get") is None


# ------------------------------------------------------ delta / merge wire

class TestDeltaMerge:
    def test_counter_roundtrip_and_quiescence(self):
        src, dst = MetricsRegistry(), MetricsRegistry()
        c = src.counter("serving.steps")
        state = {}
        c.inc(3)
        d1 = src.delta_update(state)
        dst.merge_delta(d1, labels={"replica": "r0"})
        assert dst.get("serving.steps", {"replica": "r0"}).value == 3
        # nothing moved -> empty delta
        assert src.delta_update(state) == {}
        c.inc(2)
        dst.merge_delta(src.delta_update(state), labels={"replica": "r0"})
        assert dst.get("serving.steps", {"replica": "r0"}).value == 5

    def test_gauge_last_write_wins(self):
        src, dst = MetricsRegistry(), MetricsRegistry()
        g = src.gauge("serving.queue_depth")
        state = {}
        g.set(4.0)
        dst.merge_delta(src.delta_update(state), labels={"replica": "r0"})
        g.set(1.0)
        dst.merge_delta(src.delta_update(state), labels={"replica": "r0"})
        assert dst.get("serving.queue_depth", {"replica": "r0"}).value == 1.0

    def test_fn_gauge_is_skipped(self):
        src = MetricsRegistry()
        src.gauge("device.count", fn=lambda: 8.0)
        assert src.delta_update({}) == {}

    def test_histogram_bucketwise_merge(self):
        src, dst = MetricsRegistry(), MetricsRegistry()
        h = src.histogram("serving.ttft_seconds")
        state = {}
        for v in (2e-6, 2e-6, 1e-3):
            h.observe(v)
        dst.merge_delta(src.delta_update(state), labels={"replica": "r0"})
        h.observe(0.5)
        dst.merge_delta(src.delta_update(state), labels={"replica": "r0"})
        m = dst.get("serving.ttft_seconds", {"replica": "r0"})
        assert m.count == 4
        assert m.sum == pytest.approx(2e-6 + 2e-6 + 1e-3 + 0.5)
        s = m.snapshot()
        assert s["min"] == pytest.approx(2e-6)
        assert s["max"] == pytest.approx(0.5)
        assert sum(n for _, n in s["buckets"]) == 4
        # the merged child and the source agree bucket for bucket
        assert s["buckets"] == h.snapshot()["buckets"]

    def test_histogram_bounds_travel_and_mismatch_raises(self):
        src, dst = MetricsRegistry(), MetricsRegistry()
        h = src.histogram("f.custom", bounds=(1.0, 2.0))
        h.observe(1.5)
        d = src.delta_update({})
        (rec,) = d.values()
        assert rec["bd"] == [1.0, 2.0]   # non-default bounds ship
        dst.merge_delta(d, labels={"replica": "r0"})
        assert dst.get("f.custom", {"replica": "r0"})._bounds == (1.0, 2.0)
        # pre-existing child with different bounds: refuse, don't corrupt
        dst2 = MetricsRegistry()
        dst2.histogram("f.custom", bounds=(9.0,), labels={"replica": "r0"})
        with pytest.raises(ValueError):
            dst2.merge_delta(d, labels={"replica": "r0"})
        # default bounds are elided from the record
        h2 = src.histogram("f.default")
        h2.observe(1e-5)
        (rec2,) = src.delta_update({}, prefixes=("f.default",)).values()
        assert "bd" not in rec2
        assert len(_TIMING_BOUNDS) == 27   # the contract "bd" elides to

    def test_prefix_filter(self):
        src = MetricsRegistry()
        src.counter("serving.steps").inc()
        src.counter("fleet.submitted").inc()
        d = src.delta_update({}, prefixes=("serving.", "jit."))
        assert [r["n"] for r in d.values()] == ["serving.steps"]

    def test_label_composition_worker_tenant_plus_router_replica(self):
        # a worker-side tenant child must land as a (replica, tenant)
        # child on the router: rec["l"] merges UNDER the merge labels
        src, dst = MetricsRegistry(), MetricsRegistry()
        src.counter("serving.admitted", labels={"tenant": "acme"}).inc(2)
        dst.merge_delta(src.delta_update({}), labels={"replica": "r0"})
        m = dst.get("serving.admitted",
                    {"replica": "r0", "tenant": "acme"})
        assert m is not None and m.value == 2

    def test_merge_lands_with_metrics_flag_off(self):
        # merging is control-plane: the router must keep aggregating
        # even when its local hot-path instrumentation is disabled
        src, dst = MetricsRegistry(), MetricsRegistry()
        src.counter("serving.steps").inc(7)
        d = src.delta_update({})
        saved = paddle.get_flags(["FLAGS_metrics"])
        try:
            paddle.set_flags({"FLAGS_metrics": False})
            dst.merge_delta(d, labels={"replica": "r0"})
        finally:
            paddle.set_flags(saved)
        assert dst.get("serving.steps", {"replica": "r0"}).value == 7


# ------------------------------------------------------ prometheus 0.0.4

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*)\})? '
    r'(-?(?:\d+\.?\d*(?:e[+-]?\d+)?|\+Inf|NaN))$', re.IGNORECASE)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_strict(text):
    """Strict 0.0.4 line parser. Returns (families, samples):
    families[name] = (kind, help or None); samples is a list of
    (sample_name, labels_dict, value_str). Raises on any malformed
    line, a sample before its TYPE, or duplicate TYPE lines."""
    families, samples, seen_type = {}, [], set()
    for line in text.splitlines():
        if line.startswith("# HELP "):
            name, _, help_ = line[len("# HELP "):].partition(" ")
            kind = families.get(name, (None, None))[0]
            families[name] = (kind, help_)
            continue
        if line.startswith("# TYPE "):
            name, _, kind = line[len("# TYPE "):].partition(" ")
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), f"bad TYPE: {line!r}"
            assert name not in seen_type, f"duplicate TYPE for {name}"
            seen_type.add(name)
            families[name] = (kind, families.get(name, (None, None))[1])
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, rawlab, val = m.groups()
        base = re.sub(r"_(total|bucket|sum|count)$", "", name)
        fam = name if name in seen_type else base
        assert fam in seen_type, f"sample {name!r} before its TYPE"
        labels = dict(_LABEL_RE.findall(rawlab)) if rawlab else {}
        samples.append((name, labels, val))
    return families, samples


def _unescape(v):
    return v.replace(r'\"', '"').replace(r"\n", "\n").replace(r"\\", "\\")


class TestPromConformance:
    def _filled(self):
        reg = MetricsRegistry()
        reg.counter("serving.steps", "steps").inc(3)
        reg.counter("serving.steps", labels={"replica": "r0"}).inc(2)
        reg.counter("serving.steps", labels={"replica": "r1"}).inc(5)
        reg.gauge("fleet.queue_depth", "depth").set(4.0)
        h = reg.histogram("serving.ttft_seconds", "ttft",
                          labels={"replica": "r0"})
        h.observe(2e-6)
        h.observe(3e-3)
        # hostile HELP text and label value: escaping must keep the
        # exposition line-parseable
        reg.counter("f.esc", 'line1\nline2 back\\slash',
                    labels={"tenant": 'we"ird\nten\\ant'}).inc()
        return reg

    def test_strict_parse_roundtrip(self):
        reg = self._filled()
        text = reg.dump_prometheus()
        families, samples = _parse_strict(text)
        by = {}
        for name, labels, val in samples:
            by.setdefault(name, []).append((labels, val))
        # counters: bare + _total samples, equal values, per child
        totals = dict((tuple(sorted(l.items())), v)
                      for l, v in by["paddle_serving_steps_total"])
        bares = dict((tuple(sorted(l.items())), v)
                     for l, v in by["paddle_serving_steps"])
        assert totals == bares
        assert totals[()] == "3"
        assert totals[(("replica", "r0"),)] == "2"
        assert totals[(("replica", "r1"),)] == "5"
        # histogram: buckets cumulative, +Inf == _count, labels compose
        buckets = [(l, v) for l, v in by["paddle_serving_ttft_seconds_bucket"]
                   if l.get("replica") == "r0"]
        cums = [int(v) for _, v in buckets]
        assert cums == sorted(cums)
        assert buckets[-1][0]["le"] == "+Inf"
        assert buckets[-1][1] == \
            by["paddle_serving_ttft_seconds_count"][0][1] == "2"
        # hostile label value survives the trip
        (lab, _v), = by["paddle_f_esc"]
        assert _unescape(lab["tenant"]) == 'we"ird\nten\\ant'
        # hostile HELP survives (escaped into one line)
        assert families["paddle_f_esc"][1] == r"line1\nline2 back\\slash"

    def test_deterministic_ordering(self):
        a = self._filled().dump_prometheus()
        b = self._filled().dump_prometheus()
        assert a == b
        # creation order must not leak into the exposition
        reg = MetricsRegistry()
        reg.counter("z.last").inc()
        reg.counter("a.first").inc()
        text = reg.dump_prometheus()
        assert text.index("paddle_a_first") < text.index("paddle_z_last")

    def test_zero_count_histogram_closes_inf(self):
        reg = MetricsRegistry()
        reg.histogram("f.empty", labels={"replica": "r0"})
        _, samples = _parse_strict(reg.dump_prometheus())
        by_name = {n: (l, v) for n, l, v in samples}
        lab, v = by_name["paddle_f_empty_bucket"]
        assert lab == {"le": "+Inf", "replica": "r0"} and v == "0"

    def test_process_registry_dump_is_strict_clean(self):
        # the REAL registry (every framework family, whatever state the
        # suite left it in) must parse strictly too
        _parse_strict(registry().dump_prometheus())


# ------------------------------------------------------ ops endpoint

class _FakeEngine:
    phase = "not_ready"


class _FakeHealth:
    def __init__(self, state):
        self.state = state


class _FakeRouter:
    def __init__(self, states):
        self._health = {n: _FakeHealth(s) for n, s in states.items()}
        self._replicas = {}


class TestExporter:
    def _server(self, reg=None):
        srv = TelemetryServer(registry=reg or MetricsRegistry())
        port = srv.serve(0)
        return srv, port

    def test_metrics_endpoint_and_self_instrumentation(self):
        reg = MetricsRegistry()
        reg.counter("f.c", "hi").inc(2)
        srv, port = self._server(reg)
        try:
            scrapes0 = registry().get("telemetry.scrapes").value
            code, body, ctype = _get(port, "/metrics")
            assert code == 200 and "version=0.0.4" in ctype
            _parse_strict(body)
            assert "paddle_f_c_total 2" in body.splitlines()
            assert registry().get("telemetry.scrapes").value == scrapes0 + 1
        finally:
            srv.shutdown()

    def test_serves_with_metrics_flag_off(self):
        # satellite: the ops endpoint is control-plane — a disabled
        # hot-path registry still scrapes (frozen values, not errors)
        reg = MetricsRegistry()
        reg.counter("f.frozen").inc(3)
        srv, port = self._server(reg)
        saved = paddle.get_flags(["FLAGS_metrics"])
        try:
            paddle.set_flags({"FLAGS_metrics": False})
            code, body, _ = _get(port, "/metrics")
            assert code == 200
            assert "paddle_f_frozen_total 3" in body.splitlines()
            code, _, _ = _get(port, "/healthz")
            assert code == 200
        finally:
            paddle.set_flags(saved)
            srv.shutdown()

    def test_healthz_nothing_attached_is_process_alive(self):
        srv, port = self._server()
        try:
            code, body, ctype = _get(port, "/healthz")
            assert code == 200 and "json" in ctype
            assert json.loads(body)["status"] == "ok"
        finally:
            srv.shutdown()

    def test_healthz_tracks_engine_phase(self):
        srv, port = self._server()
        eng = _FakeEngine()
        try:
            srv.attach_engine(eng)
            code, body, _ = _get(port, "/healthz")
            assert code == 503
            assert json.loads(body)["phase"] == "not_ready"
            eng.phase = "ready"
            code, _, _ = _get(port, "/healthz")
            assert code == 200
        finally:
            srv.shutdown()

    def test_healthz_fleet_any_ready(self):
        srv, port = self._server()
        router = _FakeRouter({"r0": "dead", "r1": "ready"})
        try:
            srv.attach_fleet(router)
            code, body, _ = _get(port, "/healthz")
            assert code == 200
            assert json.loads(body)["replicas"] == \
                {"r0": "dead", "r1": "ready"}
            router._health["r1"].state = "dead"
            code, _, _ = _get(port, "/healthz")
            assert code == 503
        finally:
            srv.shutdown()

    def test_sli_gauges(self):
        reg = MetricsRegistry()
        reg.counter("fleet.submitted").inc(3)
        reg.counter("fleet.sheds").inc(1)
        h = reg.histogram("serving.ttft_seconds", labels={"replica": "r0"})
        h.observe(1e-3)
        srv, port = self._server(reg)
        router = _FakeRouter({"r0": "ready", "r1": "dead"})
        try:
            srv.attach_fleet(router)
            code, body, _ = _get(port, "/metrics")
            assert code == 200
            _, samples = _parse_strict(body)
            vals = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
            assert float(vals[("paddle_fleet_sli_availability", ())]) == 0.5
            assert float(vals[("paddle_fleet_sli_shed_rate", ())]) \
                == pytest.approx(0.25)
            p99 = vals[("paddle_fleet_sli_ttft_p99_seconds",
                        (("replica", "r0"),))]
            assert float(p99) == h.quantile(0.99)
        finally:
            srv.shutdown()

    def test_statusz_and_trace(self):
        srv, port = self._server()
        try:
            code, body, _ = _get(port, "/statusz")
            assert code == 200
            assert "FLAGS_telemetry_port" in body
            assert "flight recorder tail" in body
            code, body, ctype = _get(port, "/trace")
            assert code == 200 and "json" in ctype
            json.loads(body)
        finally:
            srv.shutdown()

    def test_statusz_shows_engine_phase_and_exec_cache(self, tmp_path):
        from paddle_tpu.jit import exec_store
        srv, port = self._server()
        eng = _FakeEngine()
        try:
            srv.attach_engine(eng)
            exec_store.attach(str(tmp_path))
            assert exec_store.store().put_json("plan", ("k",), {"a": 1})
            code, body, _ = _get(port, "/statusz")
        finally:
            exec_store.detach()
            srv.shutdown()
        assert code == 200
        assert "engine: phase=not_ready" in body
        assert f"dir: {tmp_path}" in body
        assert "entries: 1  (plan=1)" in body
        assert "written: 1" in body

    def test_a_failing_page_is_a_500_and_the_server_lives(self,
                                                          monkeypatch):
        srv, port = self._server()

        def broken():
            raise RuntimeError("page broke")

        monkeypatch.setattr(srv, "_trace_body", broken)
        try:
            code, body, _ = _get(port, "/trace")
            assert code == 500 and "RuntimeError: page broke" in body
            code, _, _ = _get(port, "/healthz")
            assert code == 200
        finally:
            srv.shutdown()

    def test_endpoints_survive_dead_weakrefs(self):
        # satellite (PR18): the exporter observes the serving stack via
        # weakrefs only — after the router and engine are garbage
        # collected every endpoint must degrade to its process-level
        # view (healthz back to process-alive), never 500
        import gc
        srv, port = self._server()
        eng = _FakeEngine()
        router = _FakeRouter({"r0": "ready"})
        try:
            srv.attach_engine(eng)
            srv.attach_fleet(router)
            del eng, router
            gc.collect()
            for path in ("/metrics", "/healthz", "/statusz", "/perfz",
                         "/debugz"):
                code, _, _ = _get(port, path)
                assert code == 200, f"{path} -> {code} after refs died"
            code, body, _ = _get(port, "/healthz")
            assert json.loads(body)["status"] == "ok"
        finally:
            srv.shutdown()

    def test_debugz_live_stacks_and_on_demand_bundle(self, tmp_path):
        # tentpole surface: /debugz shows every thread classified, and
        # ?record=1 commits a debug.manual bundle on demand
        from paddle_tpu.observability import incident as incident_mod
        srv, port = self._server()
        saved = paddle.get_flags(
            ["FLAGS_incident_dir", "FLAGS_incident_rate_limit_s"])
        try:
            paddle.set_flags({
                "FLAGS_incident_dir": str(tmp_path),
                "FLAGS_incident_rate_limit_s": 0.0})
            code, body, ctype = _get(port, "/debugz")
            assert code == 200 and "text/plain" in ctype
            assert "thread" in body and "classes:" in body
            code, body, _ = _get(port, "/debugz?record=1")
            assert code == 200
            bundles = [d for d in os.listdir(tmp_path)
                       if d.startswith("incident-")]
            assert len(bundles) == 1
            assert os.path.exists(
                os.path.join(tmp_path, bundles[0], "COMMITTED"))
            assert bundles[0] in body
            # the bundle shows up in the incident index on a re-scrape
            code, body, _ = _get(port, "/debugz")
            assert "debug.manual" in body
        finally:
            paddle.set_flags(saved)
            srv.shutdown()
            with incident_mod._RECORDER._lock:
                incident_mod._RECORDER._recent.clear()

    def test_unknown_path_404(self):
        srv, port = self._server()
        try:
            code, _, _ = _get(port, "/nope")
            assert code == 404
        finally:
            srv.shutdown()

    def test_serve_idempotent_and_shutdown(self):
        srv, port = self._server()
        assert srv.serve(0) == port      # second serve: same server
        srv.shutdown()
        assert srv.port is None
        srv.shutdown()                   # idempotent
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2.0)

    def test_module_serve_honors_flag(self):
        saved = paddle.get_flags(["FLAGS_telemetry_port"])
        try:
            # flag off: attach alone must NOT start a listener
            paddle.set_flags({"FLAGS_telemetry_port": -1})
            exporter_mod.attach_engine(_FakeEngine())
            assert exporter_mod.port() is None
            # flag 0: explicit serve binds a free port
            port = exporter_mod.serve()
            assert exporter_mod.port() == port > 0
            code, _, _ = _get(port, "/healthz")
            assert code in (200, 503)
        finally:
            exporter_mod.shutdown()
            paddle.set_flags(saved)
        assert exporter_mod.port() is None

    def test_interpreter_exit_is_clean_with_server_running(self):
        # satellite: a served-but-never-shut-down endpoint must not hang
        # interpreter exit (daemon thread + atexit shutdown)
        code = (
            "import urllib.request\n"
            "import paddle_tpu.observability as obs\n"
            "port = obs.serve_telemetry(0)\n"
            "r = urllib.request.urlopen("
            "f'http://127.0.0.1:{port}/healthz', timeout=5)\n"
            "assert r.status == 200\n"
            "print('SERVED', port)\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr
        assert "SERVED" in out.stdout
