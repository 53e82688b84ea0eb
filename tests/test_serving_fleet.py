"""Fleet serving (ISSUE 12): multi-replica router with exactly-once
retry, health-driven failover, and SLO-aware load shedding.

Fast tier-1 covers the routing primitives (first-block affinity digest,
rendezvous stability under membership change), the per-replica health
state machine (STARTING exempt from heartbeat staleness, sticky DEAD,
died-once semantics), the engine-side satellites (NOT_READY readiness
phase replacing the watchdog compile-grace multiplier, blocking
``pop_output``/``pop_result`` with timeouts, ``QueueFull.
retry_after_hint``, ``Histogram.quantile``), and the router end to end
on thread-hosted replicas: byte-identity vs a single-engine reference,
failover of a replica killed right after the durable ack, shed-then-
retry, a rolling drain racing live submits, and zero dropped requests
throughout.

The slow-marked chaos tranche runs REAL subprocess replicas and lands a
genuine SIGKILL mid-stream: every victim request must complete
byte-identically on a survivor (journal watermark handoff under the
original gid — same-seed sampling streams make the token stream a pure
function of the global id).
"""

import io
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import ContinuousBatchingEngine, QueueFull
from paddle_tpu.observability import exporter as telemetry
from paddle_tpu.observability.metrics import (METRIC_NAMES, Histogram,
                                              registry)
from paddle_tpu.serving.fleet import (FleetShed, ReplicaRouter,
                                      ReplicaHealth, ReplicaState,
                                      ReplicaUnavailable,
                                      SubprocessReplicaHandle,
                                      ThreadReplicaHandle)
from paddle_tpu.serving.fleet.router import (_affinity_digest,
                                             _rendezvous_order)
from paddle_tpu.serving.resilience import (ResilientServingEngine,
                                           ServingAction)

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


def _prompts(n=6, rng_seed=3, bs=16):
    """Mixed stream: even indices share a one-block head (affinity +
    prefix-cache food), odd ones are short singletons."""
    rng = np.random.RandomState(rng_seed)
    head = rng.randint(0, 128, bs).tolist()
    out = []
    for i in range(n):
        body = rng.randint(0, 128, 3 + 2 * i).tolist()
        out.append((head + body) if i % 2 == 0 else body)
    return out


def _mk_fleet(model, tmp_path, n=2, max_queue=None, eng=None,
              **router_kw):
    e = {**ENG, **(eng or {})}
    reps = [ThreadReplicaHandle(f"rep{i}", lambda: model,
                                str(tmp_path / f"rep{i}"),
                                max_queue=max_queue,
                                journal_flush_every=1, **e)
            for i in range(n)]
    router = ReplicaRouter(reps, block_size=e["block_size"], **router_kw)
    router.start()
    router.wait_ready(timeout_s=180.0)
    return router, reps


def _reference(model, requests):
    """The byte-identity oracle: ONE plain engine serving every request
    under its fleet gid — token streams are a pure function of (seed,
    rid, index), so whatever the fleet routed/failed-over/drained must
    match this run byte for byte."""
    ref = ContinuousBatchingEngine(model, **ENG)
    for gid in sorted(requests):
        p, mx = requests[gid]
        ref.add_request(p, max_new_tokens=mx, rid=gid)
    ref.run()
    return {g: list(ref.results[g].out_tokens) for g in requests}


def _assert_byte_identical(router, model):
    ref = _reference(model, router.requests)
    got = {g: list(router.outputs[g]) for g in router.requests}
    assert got == ref


def _http_get(port, path, timeout=10.0):
    """(status, body) off the router's ops endpoint; 4xx/5xx returned."""
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ------------------------------------------------- routing primitives (fast)

class TestAffinityDigest:
    def test_first_block_keys_the_family(self):
        head = list(range(16))
        a = _affinity_digest(head + [1, 2, 3], 16)
        b = _affinity_digest(head + [9] * 40, 16)
        assert a == b                      # same head, different tails
        assert _affinity_digest([7] + head, 16) != a

    def test_short_prompt_keys_full_content(self):
        assert (_affinity_digest([1, 2, 3], 16)
                == _affinity_digest([1, 2, 3], 16))
        assert (_affinity_digest([1, 2, 3], 16)
                != _affinity_digest([1, 2, 4], 16))

    def test_rendezvous_stable_under_membership_change(self):
        """HRW's point: removing one replica must not reshuffle the
        relative order of the survivors (only the dead one's traffic
        moves)."""
        key = _affinity_digest(list(range(16)), 16)
        names = ["a", "b", "c", "d"]
        order = _rendezvous_order(key, names)
        for gone in names:
            survivors = [n for n in names if n != gone]
            assert (_rendezvous_order(key, survivors)
                    == [n for n in order if n != gone])

    def test_distinct_keys_spread_over_the_fleet(self):
        rng = np.random.RandomState(0)
        names = ["a", "b", "c"]
        firsts = {
            _rendezvous_order(
                _affinity_digest(rng.randint(0, 128, 20).tolist(), 16),
                names)[0]
            for _ in range(60)}
        assert firsts == set(names)        # no degenerate hot spot


# ------------------------------------------------- health machine (fast)

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestReplicaHealth:
    def _mk(self, **kw):
        clk = _Clock()
        return ReplicaHealth("r", clock=clk, **kw), clk

    def test_starting_to_ready_on_phase(self):
        h, _ = self._mk()
        st, died = h.observe(
            {"alive": True, "phase": "not_ready", "beat_age_s": 0.0})
        assert st == ReplicaState.STARTING and not died
        st, _ = h.observe(
            {"alive": True, "phase": "ready", "beat_age_s": 0.0})
        assert st == ReplicaState.READY

    def test_starting_exempt_from_heartbeat_staleness(self):
        # the whole STARTING window is one cold compile producing no
        # beats — staleness must not kill it
        h, clk = self._mk(heartbeat_timeout_s=1.0)
        clk.t = 500.0
        st, died = h.observe(
            {"alive": True, "phase": "not_ready", "beat_age_s": 400.0})
        assert st == ReplicaState.STARTING and not died

    def test_start_deadline_bounds_the_compile(self):
        h, clk = self._mk(start_deadline_s=10.0)
        clk.t = 9.0
        assert h.observe({"alive": True, "phase": "not_ready",
                          "beat_age_s": 9.0})[0] == ReplicaState.STARTING
        clk.t = 11.0
        st, died = h.observe(
            {"alive": True, "phase": "not_ready", "beat_age_s": 11.0})
        assert st == ReplicaState.DEAD and died

    def test_stale_heartbeat_kills_ready_exactly_once(self):
        h, _ = self._mk(heartbeat_timeout_s=1.0)
        h.observe({"alive": True, "phase": "ready", "beat_age_s": 0.0})
        st, died = h.observe(
            {"alive": True, "phase": "ready", "beat_age_s": 2.0})
        assert st == ReplicaState.DEAD and died
        st, died = h.observe(
            {"alive": True, "phase": "ready", "beat_age_s": 2.0})
        assert st == ReplicaState.DEAD and not died   # failover fires once

    def test_dead_is_sticky_until_reset(self):
        h, _ = self._mk()
        assert h.mark_dead()
        assert not h.mark_dead()           # second mark is a no-op
        st, died = h.observe(
            {"alive": True, "phase": "ready", "beat_age_s": 0.0})
        assert st == ReplicaState.DEAD and not died   # zombies stay dead
        h.reset()
        assert h.state == ReplicaState.STARTING

    def test_dead_cannot_drain(self):
        h, _ = self._mk()
        h.observe({"alive": True, "phase": "ready", "beat_age_s": 0.0})
        h.mark_draining()
        assert h.state == ReplicaState.DRAINING
        h.mark_dead()
        h.mark_draining()
        assert h.state == ReplicaState.DEAD

    def test_ready_back_to_starting_on_not_ready_phase(self):
        h, _ = self._mk()
        h.observe({"alive": True, "phase": "ready", "beat_age_s": 0.0})
        st, died = h.observe(
            {"alive": True, "phase": "not_ready", "beat_age_s": 0.0})
        assert st == ReplicaState.STARTING and not died


# --------------------------------------------- readiness gating (satellite)

class TestReadinessGating:
    def test_phase_tracks_lifecycle(self, model, tmp_path):
        eng = ResilientServingEngine(model, str(tmp_path / "p"), **ENG)
        assert eng.phase == "not_ready"
        eng.add_request([1, 2, 3], max_new_tokens=2)
        assert eng.phase == "not_ready"    # admitted, zero steps served
        eng.run()
        assert eng.phase == "ready"
        eng.drain()
        assert eng.phase == "drained"
        eng.close()

    def test_zero_step_window_is_not_hang_policed(self, model, tmp_path):
        """The old 10x-first_step compile grace is gone: without an
        explicit first_step_timeout_s a zero-step engine is NOT_READY
        (routers withhold traffic) — never a watchdog hang, no matter
        how long the compile takes."""
        eng = ResilientServingEngine(model, str(tmp_path / "w"),
                                     step_timeout_s=0.1, **ENG)
        eng.add_request([1, 2, 3], max_new_tokens=2)
        time.sleep(0.5)                    # way past step_timeout
        assert eng.poll() == ServingAction.CONTINUE
        assert eng.phase == "not_ready"
        eng.close()

    def test_explicit_first_step_deadline_still_caps(self, model,
                                                     tmp_path):
        eng = ResilientServingEngine(model, str(tmp_path / "w2"),
                                     step_timeout_s=5.0,
                                     first_step_timeout_s=0.1, **ENG)
        eng.add_request([1, 2, 3], max_new_tokens=2)
        deadline = time.time() + 5.0
        while (eng.poll() != ServingAction.RESTART
               and time.time() < deadline):
            time.sleep(0.05)
        assert eng.poll() == ServingAction.RESTART
        eng.close()


# ------------------------------------------ blocking pops (satellite)

class TestBlockingPops:
    def test_pop_result_blocks_until_finish(self, model):
        eng = ContinuousBatchingEngine(model, **ENG)
        rid = eng.add_request([5, 3, 1], max_new_tokens=3)
        t = threading.Thread(target=eng.run)
        t.start()
        req = eng.pop_result(rid, timeout=60.0)
        t.join()
        assert req is not None and len(req.out_tokens) == 3

    def test_pop_result_timeout_expires_to_none(self, model):
        eng = ContinuousBatchingEngine(model, **ENG)
        rid = eng.add_request([5, 3, 1], max_new_tokens=3)
        t0 = time.monotonic()
        assert eng.pop_result(rid, timeout=0.1) is None  # nobody steps
        assert time.monotonic() - t0 < 5.0

    def test_resilient_pop_output_blocks_and_times_out(self, model,
                                                       tmp_path):
        eng = ResilientServingEngine(model, str(tmp_path / "b"), **ENG)
        rid = eng.add_request([5, 3, 1], max_new_tokens=3)
        assert eng.pop_output(rid, timeout=0.05) is None
        t = threading.Thread(target=eng.run)
        t.start()
        toks = eng.pop_output(rid, timeout=60.0)
        t.join()
        assert toks is not None and len(toks) == 3
        eng.close()


# ------------------------------------- QueueFull hint + quantile (satellite)

class TestShedSignals:
    def test_queue_full_carries_retry_after_hint(self):
        err = QueueFull("admission queue is full (2/2 pending)",
                        retry_after_hint=0.25)
        assert err.retry_after_hint == 0.25
        assert QueueFull("full").retry_after_hint is None

    def test_engine_raise_site_sets_hint(self, model):
        eng = ContinuousBatchingEngine(model, max_queue=1, **ENG)
        eng.add_request([1, 2, 3], max_new_tokens=2)
        with pytest.raises(QueueFull) as ei:
            for _ in range(8):             # overfill without stepping
                eng.add_request([4, 5, 6], max_new_tokens=2)
        hint = ei.value.retry_after_hint
        assert hint is None or hint >= 0.0  # None only pre-histogram

    def test_histogram_quantile(self):
        h = Histogram("t.q")
        assert h.quantile(0.5) is None      # empty: no estimate
        for v in (0.001, 0.002, 0.003, 0.004, 0.1):
            h.observe(v)
        p50 = h.quantile(0.5)
        assert p50 is not None and 0.0 < p50 <= 0.1
        assert h.quantile(1.0) >= p50
        assert h.quantile(0.0) is not None
        with pytest.raises(ValueError):
            h.quantile(1.5)


class _FakeHandle:
    """status()-only stand-in so the SLO estimator can be unit-tested
    without a fleet."""

    def __init__(self, name, qd):
        self.name = name
        self.root = ""
        self.qd = qd

    def status(self):
        return {"alive": True, "phase": "ready",
                "queue_depth": self.qd, "beat_age_s": 0.0}


class TestSloGateEstimate:
    def test_estimate_is_windowed_and_decays(self):
        """The gate must read CURRENT load (fleet queue depth over the
        recent delivery rate), not a process-lifetime histogram: after
        an overload ends, the estimate has to fall back under the SLO
        instead of shedding forever."""
        r = ReplicaRouter([_FakeHandle("a", 8)])
        assert r._est_queue_wait_s() is None   # no deliveries yet
        now = time.monotonic()
        for i in range(16):                    # ~16 deliveries/s window
            r._completions.append(now - 1.0 + i * 0.01)
        est = r._est_queue_wait_s()
        assert est is not None and 0.0 < est < 5.0
        # the same deliveries aged out of the window: gate goes inert
        # (decay) rather than remembering the overload
        r._completions.clear()
        for _ in range(16):
            r._completions.append(now - 60.0)
        assert r._est_queue_wait_s() is None

    def test_estimate_scales_with_fleet_queue_depth(self):
        idle = ReplicaRouter([_FakeHandle("a", 0)])
        busy = ReplicaRouter([_FakeHandle("a", 64)])
        now = time.monotonic()
        for r in (idle, busy):
            for i in range(16):
                r._completions.append(now - 1.0 + i * 0.01)
        assert idle._est_queue_wait_s() == 0.0   # empty queues: no wait
        assert busy._est_queue_wait_s() > idle._est_queue_wait_s()


# ------------------------------------------------- fleet router (fast)

class TestFleetRouter:
    def test_two_replicas_byte_identical(self, model, tmp_path):
        router, _ = _mk_fleet(model, tmp_path)
        try:
            for p in _prompts(6):
                router.submit(p, max_new_tokens=6)
            router.drain_all(timeout_s=120.0)
            assert router.dropped_requests == 0
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_same_head_prompts_land_together(self, model, tmp_path):
        """The affinity-digest 'collision' is the DESIGN: prompts
        sharing a first block but differing after it must key to the
        same replica (warm KV), while staying distinct requests."""
        router, _ = _mk_fleet(model, tmp_path)
        try:
            head = list(range(16))
            gids = [router.submit(head + [50 + i, 60 + i],
                                  max_new_tokens=2) for i in range(4)]
            # submit() never polls on success, so placement is still
            # recorded even if the request already finished
            placed = {router._outstanding[g].replica for g in gids}
            assert len(placed) == 1
            assert len(set(gids)) == 4     # distinct requests, one key
            router.drain_all(timeout_s=120.0)
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_kill_after_ack_before_first_step(self, model, tmp_path):
        """Death in the gap between the durable ack and the victim's
        first step: the journal holds the admission (and possibly zero
        tokens) — the survivor regenerates the whole stream under the
        original gid, byte-identically."""
        router, reps = _mk_fleet(model, tmp_path)
        try:
            gids = [router.submit(p, max_new_tokens=5)
                    for p in _prompts(5, rng_seed=11)]
            # the LAST ack'd request cannot have finished yet: killing
            # its replica now guarantees a real mid-flight handoff
            victim = router._outstanding[gids[-1]].replica
            next(r for r in reps if r.name == victim).kill()
            router.drain_all(timeout_s=120.0)
            assert router.rerouted_requests >= 1
            assert router.dropped_requests == 0
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_failover_commits_incident_with_victim_trace(self, model,
                                                         tmp_path):
        """The death transition is a terminal event (PR18 tentpole):
        the router must commit a fleet.failover bundle whose
        victim_traces carry the ORIGINAL submit trace ids — the one
        key that correlates this bundle with the dead replica's own
        journal and trace ring."""
        saved = paddle.get_flags(["FLAGS_incident_rate_limit_s"])
        paddle.set_flags({"FLAGS_incident_rate_limit_s": 0.0})
        router, reps = _mk_fleet(model, tmp_path)
        try:
            gids = [router.submit(p, max_new_tokens=5)
                    for p in _prompts(5, rng_seed=11)]
            victim_name = router._outstanding[gids[-1]].replica
            victim_traces = {
                f"{o.trace[0]:016x}"
                for o in router._outstanding.values()
                if o.replica == victim_name and o.trace is not None}
            assert victim_traces, "submit spans must carry trace ids"
            next(r for r in reps if r.name == victim_name).kill()
            router.drain_all(timeout_s=120.0)
            inc_dir = tmp_path / "incidents"
            matched = []
            for d in os.listdir(inc_dir):
                if not d.startswith("incident-"):
                    continue
                with open(inc_dir / d / "incident.json") as f:
                    hdr = json.load(f)
                if (hdr["kind"] == "fleet.failover"
                        and hdr["attrs"]["replica"] == victim_name
                        and hdr["attrs"]["victims"] > 0):
                    matched.append(hdr)
            assert matched, "no failover incident for the victim"
            hdr = matched[0]
            assert hdr["trace_id"] in victim_traces
            assert set(hdr["attrs"]["victim_traces"]) <= victim_traces
            assert set(hdr["attrs"]["victim_gids"]) <= set(gids)
            assert router.dropped_requests == 0
            _assert_byte_identical(router, model)
        finally:
            router.close()
            paddle.set_flags(saved)

    def test_submit_routes_around_dead_transport(self, model, tmp_path):
        router, reps = _mk_fleet(model, tmp_path)
        try:
            reps[0].kill()
            gids = [router.submit(p, max_new_tokens=3)
                    for p in _prompts(4, rng_seed=2)]
            assert all(router._outstanding[g].replica == reps[1].name
                       for g in gids)
            router.drain_all(timeout_s=120.0)
            assert router._health[reps[0].name].state == ReplicaState.DEAD
            assert router.dropped_requests == 0
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_submit_discovered_death_settles_outstanding(self, model,
                                                         tmp_path):
        """A replica dying BETWEEN polls can be discovered by submit()
        tripping over the dead transport rather than by poll() — and
        observe() reports died_now only on the transition, so submit's
        mark_dead must run the same failover or the victim's acked
        requests stay outstanding forever (drain_all would time out)."""
        router, reps = _mk_fleet(model, tmp_path)
        try:
            gids = [router.submit(p, max_new_tokens=6)
                    for p in _prompts(6, rng_seed=31)]
            victim = router._outstanding[gids[-1]].replica
            next(r for r in reps if r.name == victim).kill()
            # no poll between the kill and these submits: the candidate
            # walk must be the one to find the corpse (rendezvous order
            # is per-key, so a few distinct prompts guarantee a hit)
            rng = np.random.RandomState(77)
            for i in range(64):
                if router._health[victim].state == ReplicaState.DEAD:
                    break
                router.submit(rng.randint(0, 128, 6 + i % 5).tolist(),
                              max_new_tokens=2)
            assert router._health[victim].state == ReplicaState.DEAD
            router.drain_all(timeout_s=120.0)
            assert router.rerouted_requests >= 1
            assert router.dropped_requests == 0
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_rolling_drain_survives_undrainable_replica(self, model,
                                                        tmp_path):
        """drain() raising ReplicaUnavailable (wedged worker, broken
        pipe) must fail the replica over — journaled work lands on a
        survivor — instead of hanging or aborting the deploy."""
        router, reps = _mk_fleet(model, tmp_path)
        try:
            gids = [router.submit(p, max_new_tokens=5)
                    for p in _prompts(5, rng_seed=41)]
            victim_name = router._outstanding[gids[-1]].replica
            victim = next(r for r in reps if r.name == victim_name)

            def wedged_drain():
                victim.kill()              # a wedged worker serves nothing
                raise ReplicaUnavailable("wedged mid-step")

            victim.drain = wedged_drain
            router.rolling_drain(ready_timeout_s=120.0)
            assert (router._health[victim_name].state
                    == ReplicaState.DEAD)
            router.drain_all(timeout_s=120.0)
            assert router.dropped_requests == 0
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_shed_then_retry(self, model, tmp_path):
        """Overload sheds with a retry-after; the SAME prompts admitted
        after backoff complete normally — shedding rejects work, it
        never loses any."""
        router, _ = _mk_fleet(model, tmp_path, max_queue=1,
                              eng=dict(max_batch=1, num_blocks=32))
        try:
            # 200 tokens a request: no row frees within a 20 ms deadline,
            # however fast a step of the tiny model is on this host
            prompts = _prompts(8, rng_seed=5)
            admitted, shed = [], []
            for p in prompts:
                try:
                    admitted.append(router.submit(
                        p, max_new_tokens=200, deadline_s=0.02))
                except FleetShed as e:
                    assert e.retry_after_s is not None
                    assert e.retry_after_s > 0.0
                    shed.append(p)
            assert shed                    # the burst really overloaded
            assert admitted                # but capacity was served
            router.drain_all(timeout_s=120.0)
            for p in shed:                 # the retry path
                admitted.append(router.submit(
                    p, max_new_tokens=200, deadline_s=30.0))
            router.drain_all(timeout_s=120.0)
            assert router.sheds == len(shed)
            assert router.dropped_requests == 0
            assert len(router.outputs) == len(admitted)
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_rolling_drain_zero_dropped(self, model, tmp_path):
        router, reps = _mk_fleet(model, tmp_path)
        try:
            for p in _prompts(8, rng_seed=21):
                router.submit(p, max_new_tokens=8)
            router.rolling_drain(ready_timeout_s=120.0)
            assert all(r._incarnation == 1 for r in reps)
            router.drain_all(timeout_s=120.0)
            assert router.dropped_requests == 0
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_rolling_drain_racing_live_submits(self, model, tmp_path):
        """A deploy drains the fleet while traffic keeps arriving:
        DRAINING replicas leave the routing set, racing submits either
        land on whoever is READY or shed-and-retry here — and nothing
        is dropped or altered."""
        router, _ = _mk_fleet(model, tmp_path)
        try:
            for p in _prompts(4, rng_seed=8):
                router.submit(p, max_new_tokens=8)
            errs = []

            def roll():
                try:
                    router.rolling_drain(ready_timeout_s=120.0)
                except Exception as e:     # surfaces in the assert below
                    errs.append(e)

            t = threading.Thread(target=roll)
            t.start()
            # deadline_s=0 sheds without polling internally: the drain
            # thread owns poll(), this thread only submits
            placed, i = 0, 0
            rng = np.random.RandomState(99)
            deadline = time.time() + 60.0
            while placed < 6 and time.time() < deadline:
                prompt = rng.randint(0, 128, 5 + i % 7).tolist()
                try:
                    router.submit(prompt, max_new_tokens=4,
                                  deadline_s=0.0)
                    placed += 1
                except FleetShed:
                    time.sleep(0.01)
                i += 1
            t.join(timeout=120.0)
            assert not t.is_alive() and not errs
            assert placed == 6
            router.drain_all(timeout_s=120.0)
            assert router.dropped_requests == 0
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_thread_drain_raises_on_wedged_worker(self, model,
                                                  tmp_path):
        """A worker wedged inside eng.step() still holds the engine
        lock: drain() must surface ReplicaUnavailable after the join
        times out instead of blocking forever on that lock."""

        class _WedgedThread:
            def join(self, timeout=None):
                pass                       # the join "times out"

            def is_alive(self):
                return True

        h = ThreadReplicaHandle("w", lambda: model,
                                str(tmp_path / "w"), **ENG)
        h.start()
        real = h._thread
        try:
            h._thread = _WedgedThread()
            with pytest.raises(ReplicaUnavailable):
                h.drain()
        finally:
            h._thread = real
            h.stop()

    def test_subprocess_restart_preserves_buffered_finishes(
            self, tmp_path, monkeypatch):
        """Finishes the reader buffered but the router never popped
        must survive restart() — on a fresh_root restart there is no
        journal replay to re-produce them, so clearing the buffer
        would lose a delivered request for good."""
        from paddle_tpu.serving.fleet import replica as replica_mod
        from paddle_tpu.serving.fleet.replica import FinishedInfo

        class _FakeProc:
            def __init__(self, *a, **k):
                self.stdin = io.StringIO()
                self.stdout = io.StringIO()
                self.pid = 0

            def poll(self):
                return None

            def wait(self, timeout=None):
                return 0

            def kill(self):
                pass

        monkeypatch.setattr(replica_mod.subprocess, "Popen",
                            lambda *a, **k: _FakeProc())
        h = SubprocessReplicaHandle("s", str(tmp_path / "s"),
                                    {"factory": "x:y"})
        h.start()
        h._finished.append(FinishedInfo(7, [1, 2, 3]))
        h.restart(fresh_root=True)
        assert [fi.gid for fi in h.pop_finished()] == [7]
        assert h.pop_finished() == []      # popped exactly once

    def test_fleet_metric_names_frozen(self):
        for name in ("fleet.replicas_ready", "fleet.replicas_dead",
                     "fleet.queue_depth", "fleet.submitted",
                     "fleet.completed", "fleet.retries", "fleet.sheds",
                     "fleet.rerouted_requests", "fleet.replica_deaths",
                     "fleet.drains", "fleet.restarts",
                     "fleet.affinity_hits", "fleet.handoff_seconds"):
            assert name in METRIC_NAMES, name
            assert registry().get(name) is not None, name


# ------------------------------------------------- telemetry plane (fast)

class TestFleetTelemetry:
    """ISSUE 14 acceptance, thread-transport half: ``router.start()``
    auto-serves the ops endpoint from ``FLAGS_telemetry_port`` and ONE
    scrape shows the whole fleet — a per-replica health-state series
    for every replica, the router-native failover/shed counters, and
    the scrape-time SLIs — while /healthz reports fleet readiness."""

    def test_one_scrape_shows_the_fleet(self, model, tmp_path):
        saved = paddle.get_flags(["FLAGS_telemetry_port"])
        paddle.set_flags({"FLAGS_telemetry_port": 0})  # 0 = free port
        try:
            router, _ = _mk_fleet(model, tmp_path)
            try:
                port = telemetry.port()
                assert port                # started by router.start()
                for p in _prompts(4):
                    router.submit(p, max_new_tokens=4)
                router.drain_all(timeout_s=120.0)
                code, body = _http_get(port, "/metrics")
                assert code == 200
                lines = body.splitlines()
                for rep in ("rep0", "rep1"):
                    assert (f'paddle_fleet_replica_state'
                            f'{{replica="{rep}"}} 1') in lines
                for fam in ("paddle_fleet_submitted_total ",
                            "paddle_fleet_sheds_total ",
                            "paddle_fleet_rerouted_requests_total ",
                            "paddle_fleet_sli_availability "):
                    assert any(l.startswith(fam) for l in lines), fam
                code, hz = _http_get(port, "/healthz")
                assert code == 200
                assert json.loads(hz)["replicas"] == \
                    {"rep0": "ready", "rep1": "ready"}
                code, st = _http_get(port, "/statusz")
                assert code == 200 and "rep0" in st and "rep1" in st
            finally:
                router.close()
        finally:
            telemetry.shutdown()
            paddle.set_flags(saved)


# ------------------------------------------------------- chaos (slow)

@pytest.mark.slow
@pytest.mark.heavy
class TestSubprocessFleetChaos:
    def test_sigkill_midstream_byte_identical(self, model, tmp_path):
        """The acceptance chaos: two REAL worker processes, a genuine
        SIGKILL mid-stream, and every victim request completing
        byte-identically on the survivor from the dead journal's
        committed watermark."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [_TESTS_DIR, os.path.dirname(_TESTS_DIR)]))
        config = {"factory": "serving_chaos_worker:build_model",
                  "engine": {**ENG, "journal_flush_every": 1},
                  "max_queue": 8, "hb_interval_s": 0.1,
                  # per-step sleep keeps streams long enough that the
                  # kill lands mid-generation, not post-finish
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
            victim = router._outstanding[gids[-1]].replica
            next(r for r in reps if r.name == victim).kill()  # SIGKILL
            router.drain_all(timeout_s=300.0)
            assert router.rerouted_requests >= 1
            assert router.dropped_requests == 0
            _assert_byte_identical(router, model)
        finally:
            router.close()

    def test_orphaned_worker_drains_and_exits_64(self, tmp_path):
        """Parent death = stdin EOF with stdout a broken pipe. The
        worker's orphan shutdown must survive its own (now-undeliverable)
        emits: drain, close the engine, and exit with the documented
        code 64 — not a BrokenPipeError traceback."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [_TESTS_DIR, os.path.dirname(_TESTS_DIR)]))
        cfg = {"root": str(tmp_path / "orph"),
               "factory": "serving_chaos_worker:build_model",
               "engine": {**ENG, "journal_flush_every": 1},
               "hb_interval_s": 0.1}
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving.fleet.worker"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, env=env, text=True)
        try:
            proc.stdin.write(json.dumps(cfg) + "\n")
            proc.stdin.flush()
            ready = False
            for line in proc.stdout:       # wait out warmup
                if json.loads(line).get("ev") == "ready":
                    ready = True
                    break
            assert ready
            proc.stdin.write(json.dumps(
                {"op": "submit", "gid": 0, "prompt": [1, 2, 3],
                 "n": 4}) + "\n")
            proc.stdin.flush()
            # the parent "dies": EOF on the worker's stdin, and nobody
            # holds the read end of its stdout anymore
            proc.stdin.close()
            proc.stdout.close()
            assert proc.wait(timeout=300) == 64
        finally:
            if proc.poll() is None:
                proc.kill()


@pytest.mark.slow
@pytest.mark.heavy
class TestSubprocessFleetTelemetry:
    """ISSUE 14 acceptance, subprocess half: real worker processes
    piggyback registry deltas on their heartbeats; the router merges
    them under ``replica="<name>"`` so one scrape shows every live
    replica's ENGINE series — and a SIGKILLed replica's counters
    survive as their last-merged values while its /healthz
    contribution flips to dead."""

    def test_killed_replica_series_survive(self, model, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [_TESTS_DIR, os.path.dirname(_TESTS_DIR)]))
        config = {"factory": "serving_chaos_worker:build_model",
                  "engine": {**ENG, "journal_flush_every": 1},
                  "max_queue": 8, "hb_interval_s": 0.1,
                  "step_sleep_s": 0.02}
        reps = [SubprocessReplicaHandle(
                    f"tsub{i}", str(tmp_path / f"tsub{i}"), dict(config),
                    spawn_env=env)
                for i in range(2)]
        names = [r.name for r in reps]
        router = ReplicaRouter(reps, block_size=ENG["block_size"],
                               heartbeat_timeout_s=5.0,
                               submit_deadline_s=30.0)
        saved = paddle.get_flags(["FLAGS_telemetry_port"])
        paddle.set_flags({"FLAGS_telemetry_port": 0})
        try:
            router.start()
            router.wait_ready(timeout_s=300.0)
            port = telemetry.port()
            assert port
            gids = [router.submit(p, max_new_tokens=8)
                    for p in _prompts(6, rng_seed=13)]
            # heartbeats are merging on the reader threads: wait until
            # every LIVE replica has contributed an engine series
            deadline = time.time() + 120.0
            while time.time() < deadline:
                if all(registry().get("serving.steps", {"replica": n})
                       is not None for n in names):
                    break
                time.sleep(0.05)
            merged = {n: registry().get("serving.steps", {"replica": n})
                      for n in names}
            assert all(m is not None for m in merged.values())
            victim = router._outstanding[gids[-1]].replica
            next(r for r in reps if r.name == victim).kill()  # SIGKILL
            router.drain_all(timeout_s=300.0)
            assert router.dropped_requests == 0
            # the victim's last-merged series survive its death, in the
            # same scrape as the survivors' still-advancing ones
            assert merged[victim].value > 0
            _, body = _http_get(port, "/metrics")
            step_lines = [l for l in body.splitlines()
                          if l.startswith("paddle_serving_steps_total{")]
            for n in names:
                assert any(f'replica="{n}"' in l for l in step_lines), n
            # ... while its /healthz contribution flips to dead
            code, hz = _http_get(port, "/healthz")
            payload = json.loads(hz)
            assert code == 200            # a survivor is still READY
            assert payload["replicas"][victim] == "dead"
            survivor = next(n for n in names if n != victim)
            assert payload["replicas"][survivor] == "ready"
            _assert_byte_identical(router, model)
        finally:
            router.close()
            telemetry.shutdown()
            paddle.set_flags(saved)


class TestGradModeThreadIsolation:
    """Replica step loops run under no_grad() on background threads; a
    process-global grad flag would let concurrent save/restore pairs
    interleave (A saves True, B saves False, A restores, B restores)
    and strand the whole process with grads off — silently breaking
    every later autograd test. Grad mode must be per-thread."""

    def test_concurrent_no_grad_threads_cannot_disable_main_thread(self):
        from paddle_tpu.autograd.engine import is_grad_enabled, no_grad

        stop = threading.Event()

        def churn():
            while not stop.is_set():
                with no_grad():
                    pass

        workers = [threading.Thread(target=churn, daemon=True)
                   for _ in range(4)]
        for w in workers:
            w.start()
        try:
            deadline = time.monotonic() + 1.0
            while time.monotonic() < deadline:
                assert is_grad_enabled()
        finally:
            stop.set()
            for w in workers:
                w.join(timeout=10.0)
        assert is_grad_enabled()
        x = paddle.to_tensor(np.ones((2, 2), np.float32),
                             stop_gradient=False)
        (x * x).sum().backward()
        assert x.grad is not None

    def test_fresh_thread_defaults_to_grads_enabled(self):
        from paddle_tpu.autograd.engine import is_grad_enabled, no_grad

        seen = {}

        def probe():
            seen["default"] = is_grad_enabled()
            with no_grad():
                seen["inside"] = is_grad_enabled()
            seen["after"] = is_grad_enabled()

        with no_grad():
            t = threading.Thread(target=probe)
            t.start()
            t.join(timeout=10.0)
        assert seen == {"default": True, "inside": False, "after": True}


pytestmark = pytest.mark.smoke
