"""Preemption-safe training (ISSUE 7): async distcp snapshots,
rank-death recovery, and the chaos harness.

Fast tier-1 tests cover the commit protocol (no torn checkpoint is ever
loadable), AsyncCheckpointer round-trips/retention, the single-process
preemption path (signal → snapshot-now → clean exit), watchdog-timeout
→ restart, and PreemptionHandler signal semantics.

The slow-marked chaos harness drives a REAL multi-process run over a
TCPStore: one rank SIGKILLed mid-step and one SIGTERMed at an arbitrary
step must both recover via re-rank + restore from a committed
generation, with loss-curve continuity against an uninterrupted
reference run from the same generation.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.checkpoint import (latest_checkpoint,
                                               load_state_dict,
                                               read_committed_marker,
                                               save_state_dict,
                                               write_committed_marker)
from paddle_tpu.distributed.fleet import ElasticManager
from paddle_tpu.distributed.fleet.elastic import PreemptionHandler
from paddle_tpu.distributed.resilience import (AsyncCheckpointer,
                                               ResilientTrainer,
                                               TrainerAction, restore_state)
from paddle_tpu.distributed.watchdog import CommTaskManager
from paddle_tpu.native.tcp_store import TCPStore
from paddle_tpu.observability import flight_recorder
from paddle_tpu.observability.metrics import METRIC_NAMES, registry

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chaos_worker.py")


def _flight_ops():
    return [e[3] for e in flight_recorder.recorder().entries()]


def _counter(name):
    return registry().get(name).value


# ---------------------------------------------------------------- fixtures

def _tiny_job(lr=1e-2):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
    opt = paddle.optimizer.Adam(learning_rate=lr,
                                parameters=net.parameters())

    def batch(step):
        r = np.random.RandomState(1000 + step)
        x = r.rand(4, 8).astype(np.float32)
        return x, x.sum(axis=1, keepdims=True).astype(np.float32)

    losses = []

    def step_fn(step):
        x, y = batch(step)
        loss = ((net(Tensor(x)) - Tensor(y)) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append((step, float(np.asarray(loss._data))))

    def state_fn():
        return {"model": net.state_dict(), "opt": opt.state_dict()}

    def apply_fn(rebuilt, resume):
        opt.set_state_dict(rebuilt["opt"])

    return net, opt, step_fn, state_fn, apply_fn, losses


# ---------------------------------------------------- commit protocol (fast)

class TestCommitProtocol:
    def test_uncommitted_checkpoint_not_loadable(self, tmp_path):
        """A save that died before its marker must fail with a CLEAR
        error, not a KeyError deep in assemble."""
        sd = {"w": paddle.to_tensor(np.ones((3,), np.float32))}
        save_state_dict(sd, str(tmp_path), commit=False)
        with pytest.raises(RuntimeError, match="uncommitted/partial"):
            load_state_dict(dict(sd), str(tmp_path))
        write_committed_marker(str(tmp_path), step=1)
        load_state_dict(dict(sd), str(tmp_path))   # now visible

    def test_latest_checkpoint_skips_uncommitted(self, tmp_path):
        for step, commit in ((1, True), (2, True), (3, False)):
            gen = tmp_path / f"step-{step:08d}"
            save_state_dict({"w": paddle.to_tensor([float(step)])},
                            str(gen), commit=commit, step=step)
        got = latest_checkpoint(str(tmp_path))
        assert got == str(tmp_path / "step-00000002")
        assert read_committed_marker(got)["step"] == 2

    def test_latest_checkpoint_empty_and_missing(self, tmp_path):
        assert latest_checkpoint(str(tmp_path)) is None
        assert latest_checkpoint(str(tmp_path / "nope")) is None

    def test_no_tmp_files_survive_a_save(self, tmp_path):
        save_state_dict({"w": paddle.to_tensor([1.0])}, str(tmp_path),
                        step=0)
        leftovers = [f for f in os.listdir(tmp_path) if ".tmp-" in f]
        assert leftovers == []

    def test_garbage_marker_reads_as_uncommitted(self, tmp_path):
        save_state_dict({"w": paddle.to_tensor([1.0])}, str(tmp_path))
        (tmp_path / "COMMITTED").write_bytes(b"\x00not json")
        assert read_committed_marker(str(tmp_path)) is None
        assert latest_checkpoint(str(tmp_path)) is None

    def test_marker_carries_step_number(self, tmp_path):
        save_state_dict({"w": paddle.to_tensor([1.0])}, str(tmp_path),
                        step=41)
        assert read_committed_marker(str(tmp_path))["step"] == 41


# -------------------------------------------------- async checkpointer (fast)

class TestAsyncCheckpointer:
    def test_roundtrip_model_and_optimizer(self, tmp_path):
        net, opt, step_fn, state_fn, apply_fn, _ = _tiny_job()
        step_fn(0)
        ck = AsyncCheckpointer(str(tmp_path))
        ck.save(state_fn(), 7, block=True)
        assert ck.last_error is None
        w0 = net[0].weight.numpy().copy()
        m0 = np.asarray(opt._states[0]["m"])
        net[0].weight._set_data(jnp.zeros_like(net[0].weight._data))
        rebuilt, step = restore_state(state_fn(), ck.latest())
        assert step == 7
        np.testing.assert_array_equal(net[0].weight.numpy(), w0)
        opt.set_state_dict(rebuilt["opt"])
        np.testing.assert_array_equal(np.asarray(opt._states[0]["m"]), m0)
        assert opt._step_count == 1

    def test_close_drains_the_inflight_write(self, tmp_path):
        net, opt, step_fn, state_fn, apply_fn, _ = _tiny_job()
        step_fn(0)
        ck = AsyncCheckpointer(str(tmp_path))
        path = ck.save(state_fn(), 3)        # the write runs behind us
        ck.close()
        assert ck._pending is None and ck.last_error is None
        assert ck.latest() == path

    def test_restore_latest_without_a_generation_is_a_noop(self,
                                                           tmp_path):
        net, opt, step_fn, state_fn, apply_fn, _ = _tiny_job()
        ck = AsyncCheckpointer(str(tmp_path))
        state = state_fn()
        assert ck.restore_latest(state) == (state, None)
        step_fn(0)
        ck.save(state_fn(), 5, block=True)
        assert ck.restore_latest(state_fn())[1] == 5

    def test_restore_into_fresh_process_state(self, tmp_path):
        """A relaunched rank restores BEFORE its first step: optimizer
        per-param states are still None and must be reconstructed from
        the checkpoint's own metadata (moments survive the restart)."""
        net, opt, step_fn, state_fn, _, _ = _tiny_job()
        step_fn(0)
        step_fn(1)
        ck = AsyncCheckpointer(str(tmp_path))
        ck.save(state_fn(), 1, block=True)
        m0 = np.asarray(opt._states[0]["m"])

        net2, opt2, _, state_fn2, _, _ = _tiny_job()
        assert opt2._states[0] is None    # fresh: nothing materialized
        rebuilt, step = restore_state(state_fn2(), ck.latest())
        opt2.set_state_dict(rebuilt["opt"])
        assert step == 1 and opt2._step_count == 2
        np.testing.assert_array_equal(np.asarray(opt2._states[0]["m"]), m0)

    def test_retention_prunes_old_and_stale(self, tmp_path):
        net, opt, step_fn, state_fn, _, _ = _tiny_job()
        step_fn(0)
        ck = AsyncCheckpointer(str(tmp_path), keep=2)
        # a stale uncommitted generation from a writer that died
        stale = tmp_path / "step-00000001"
        stale.mkdir()
        (stale / "0_0.distcp.npz").write_bytes(b"partial garbage")
        for s in (2, 3, 4):
            ck.save(state_fn(), s, block=True)
        assert ck.last_error is None
        assert sorted(os.listdir(tmp_path)) == ["step-00000003",
                                                "step-00000004"]

    def test_save_inside_trace_refused(self, tmp_path):
        ck = AsyncCheckpointer(str(tmp_path))

        def traced(x):
            ck.save({"w": x}, 0)
            return x

        with pytest.raises(RuntimeError, match="inside a jax trace"):
            jax.jit(traced)(jnp.ones((2,)))

    def test_write_failure_records_aborted(self, tmp_path, monkeypatch):
        from paddle_tpu.distributed.resilience import checkpointer as cm
        before = _counter("checkpoint.aborted")

        def boom(*a, **k):
            raise OSError("disk full")

        monkeypatch.setattr(cm, "write_shards", boom)
        ck = AsyncCheckpointer(str(tmp_path))
        ck.save({"w": paddle.to_tensor([1.0])}, 0, block=True)
        assert isinstance(ck.last_error, OSError)
        assert _counter("checkpoint.aborted") == before + 1
        assert latest_checkpoint(str(tmp_path)) is None
        assert "checkpoint.aborted" in _flight_ops()

    def test_metrics_registered_and_frozen(self, tmp_path):
        for name in ("checkpoint.snapshot_seconds",
                     "checkpoint.write_seconds", "checkpoint.committed",
                     "checkpoint.aborted", "resilience.preemptions",
                     "resilience.rank_deaths", "resilience.restores",
                     "resilience.resume_step"):
            assert name in METRIC_NAMES
            assert registry().get(name) is not None
        before = _counter("checkpoint.committed")
        snap_count = registry().get("checkpoint.snapshot_seconds").count
        AsyncCheckpointer(str(tmp_path)).save(
            {"w": paddle.to_tensor([1.0])}, 0, block=True)
        assert _counter("checkpoint.committed") == before + 1
        assert registry().get("checkpoint.snapshot_seconds").count \
            == snap_count + 1
        assert "checkpoint.committed" in _flight_ops()


# --------------------------------------- single-process resilience (tier-1)

class TestResilientTrainerFast:
    def test_signal_snapshot_now_and_clean_exit(self, tmp_path):
        """The tier-1 preemption test: a signal mid-run turns into a
        blocking snapshot + CHECKPOINT_EXIT within one step."""
        store = TCPStore("127.0.0.1", 0, is_master=True)
        elastic = ElasticManager(store, "n0", np_min=1, ttl=5.0,
                                 job_id="fastpre")
        elastic.register()
        net, opt, step_fn, state_fn, apply_fn, losses = _tiny_job()
        ck = AsyncCheckpointer(str(tmp_path))
        tr = ResilientTrainer(ck, state_fn, apply_fn, elastic=elastic,
                              snapshot_every=100, signum=signal.SIGUSR1)
        before = _counter("resilience.preemptions")

        def chaotic_step(step):
            step_fn(step)
            if step == 3:
                os.kill(os.getpid(), signal.SIGUSR1)

        try:
            action = tr.run(chaotic_step, 50)
        finally:
            tr.close()
            elastic.stop()
            store.close()
        assert action == TrainerAction.CHECKPOINT_EXIT
        assert len(losses) == 4                      # exited AT the notice
        gen = latest_checkpoint(str(tmp_path))
        assert gen is not None
        assert read_committed_marker(gen)["step"] == 3
        assert _counter("resilience.preemptions") == before + 1
        assert "resilience.preempted" in _flight_ops()

    def test_restore_continuity_vs_uninterrupted(self, tmp_path):
        """Loss-curve continuity, single-process: interrupt at step 5,
        restore into a FRESH job, run to 10 — losses 5..9 must match an
        uninterrupted 10-step run exactly."""
        net, opt, step_fn, state_fn, apply_fn, ref_losses = _tiny_job()
        for s in range(10):
            step_fn(s)

        net1, opt1, step1, state1, apply1, losses1 = _tiny_job()
        ck = AsyncCheckpointer(str(tmp_path))
        tr1 = ResilientTrainer(ck, state1, apply1, snapshot_every=0,
                               install_signal=False)
        assert tr1.run(step1, 5) == TrainerAction.COMPLETED

        net2, opt2, step2, state2, apply2, losses2 = _tiny_job()
        ck2 = AsyncCheckpointer(str(tmp_path))
        tr2 = ResilientTrainer(ck2, state2, apply2, snapshot_every=0,
                               install_signal=False)
        before = _counter("resilience.restores")
        assert tr2.run(step2, 10) == TrainerAction.COMPLETED
        assert _counter("resilience.restores") == before + 1
        assert registry().get("resilience.resume_step").value == 5.0
        assert [s for s, _ in losses2] == [5, 6, 7, 8, 9]
        got = dict(losses2)
        want = dict(ref_losses)
        for s in range(5, 10):
            np.testing.assert_allclose(got[s], want[s], rtol=1e-6)
        assert "resilience.restore" in _flight_ops()

    def test_watchdog_timeout_turns_into_restart(self, tmp_path):
        mgr = CommTaskManager(scan_interval=0.05)
        net, opt, step_fn, state_fn, apply_fn, _ = _tiny_job()
        ck = AsyncCheckpointer(str(tmp_path))
        tr = ResilientTrainer(ck, state_fn, apply_fn, watchdog=mgr,
                              snapshot_every=0, install_signal=False)
        before = _counter("resilience.rank_deaths")
        try:
            mgr.start_task("allreduce/dp", timeout_s=0.05)
            time.sleep(0.5)
            step_fn(0)
            assert tr.poll(0) == TrainerAction.RESTART
        finally:
            tr.close()
            mgr.shutdown()
        assert _counter("resilience.rank_deaths") == before + 1
        ops = _flight_ops()
        assert "resilience.comm_timeout" in ops
        assert "resilience.rank_death" in ops

    def test_peer_notice_checkpoints_this_rank_too(self, tmp_path):
        store = TCPStore("127.0.0.1", 0, is_master=True)
        m1 = ElasticManager(store, "n1", np_min=1, ttl=5.0, job_id="peer")
        m2 = ElasticManager(store, "n2", np_min=1, ttl=5.0, job_id="peer")
        m1.register()
        m2.register()
        net, opt, step_fn, state_fn, apply_fn, _ = _tiny_job()
        ck = AsyncCheckpointer(str(tmp_path))
        tr = ResilientTrainer(ck, state_fn, apply_fn, elastic=m1,
                              snapshot_every=0, install_signal=False)
        try:
            step_fn(0)
            assert tr.poll(0) == TrainerAction.CONTINUE
            m2.notify_preemption()          # the PEER got the SIGTERM
            step_fn(1)
            assert tr.poll(1) == TrainerAction.CHECKPOINT_EXIT
            assert latest_checkpoint(str(tmp_path)) is not None
        finally:
            tr.close()
            m1.stop()
            m2.stop()
            store.close()

    def test_donation_lost_recovers_in_process(self, tmp_path):
        """A captured-step replay failure AFTER donation consumed the
        state is unrecoverable in place — run() must restore from the
        latest committed generation and continue (bounded loss)."""
        net, opt, step_fn, state_fn, apply_fn, losses = _tiny_job()
        ck = AsyncCheckpointer(str(tmp_path))
        tr = ResilientTrainer(ck, state_fn, apply_fn, snapshot_every=2,
                              install_signal=False)
        blown = []

        def fragile_step(step):
            if step == 5 and not blown:
                blown.append(step)
                ck.wait()   # the step-4 generation is committed by now
                raise RuntimeError(
                    "step_capture replay failed after its donated inputs "
                    "were consumed — params/optimizer state no longer "
                    "exist")
            step_fn(step)

        assert tr.run(fragile_step, 8) == TrainerAction.COMPLETED
        steps = [s for s, _ in losses]
        assert steps[-1] == 7
        assert 5 in steps        # resumed at the last committed step + 1
        assert steps.count(5) >= 1 and blown == [5]


# ------------------------------------- PreemptionHandler semantics (tier-1)

class TestPreemptionHandlerSemantics:
    def test_chained_previous_handler_invoked(self):
        store = TCPStore("127.0.0.1", 0, is_master=True)
        m = ElasticManager(store, "n0", np_min=1, ttl=5.0, job_id="chain")
        m.register()
        prev_calls = []
        orig = signal.signal(signal.SIGUSR1,
                             lambda s, f: prev_calls.append(s))
        h = PreemptionHandler(m).install(signal.SIGUSR1)
        try:
            os.kill(os.getpid(), signal.SIGUSR1)
            time.sleep(0.2)
            assert h.pending()
            assert prev_calls == [signal.SIGUSR1]   # chained through
        finally:
            h.uninstall()
            signal.signal(signal.SIGUSR1, orig)
            m.stop()
            store.close()

    def test_process_idempotent_across_repeated_signals(self):
        store = TCPStore("127.0.0.1", 0, is_master=True)
        m = ElasticManager(store, "n0", np_min=1, ttl=5.0, job_id="rep")
        m.register()
        ran = []
        h = PreemptionHandler(m, on_notice=lambda: ran.append(1))
        h.install(signal.SIGUSR1)
        try:
            for _ in range(3):                      # SIGTERM storm
                os.kill(os.getpid(), signal.SIGUSR1)
                time.sleep(0.05)
            assert h.notices == 3
            assert h.process() is True
            assert h.process() is True              # idempotent
            os.kill(os.getpid(), signal.SIGUSR1)    # another after process
            time.sleep(0.05)
            assert h.process() is True
            assert ran == [1]                       # callback ran ONCE
        finally:
            h.uninstall()
            m.stop()
            store.close()

    def test_store_dead_still_runs_local_callback(self):
        store = TCPStore("127.0.0.1", 0, is_master=True)
        m = ElasticManager(store, "n0", np_min=1, ttl=5.0, job_id="dead")
        ran = []
        h = PreemptionHandler(m, on_notice=lambda: ran.append(1))
        h.install(signal.SIGUSR1)
        try:
            store.close()                           # store already gone
            os.kill(os.getpid(), signal.SIGUSR1)
            time.sleep(0.2)
            assert h.process() is True              # no raise
            assert ran == [1]                       # snapshot still taken
        finally:
            h.uninstall()
            m.stop()


# -------------------------------------------- watchdog handler guard (fast)

class TestWatchdogHandlerGuard:
    def test_raising_handler_does_not_kill_scan_thread(self):
        mgr = CommTaskManager(scan_interval=0.05)
        fired = []

        def bad(task):
            raise ValueError("handler bug")

        mgr.add_handler(bad)
        mgr.add_handler(lambda t: fired.append(t.name))
        try:
            mgr.start_task("a2a/ep", timeout_s=0.05)
            time.sleep(0.4)
            assert fired == ["a2a/ep"]      # later handler still ran
            assert "watchdog.handler_error" in _flight_ops()
            # the scan thread survived: a SECOND timeout is detected
            mgr.start_task("p2p/pp", timeout_s=0.05)
            time.sleep(0.4)
            assert fired == ["a2a/ep", "p2p/pp"]
        finally:
            mgr.shutdown()


# --------------------------------------------- elastic hardening (tier-1)

class TestElasticHardening:
    def test_corrupt_beat_payload_does_not_crash_watch(self):
        store = TCPStore("127.0.0.1", 0, is_master=True)
        m1 = ElasticManager(store, "n1", np_min=1, ttl=5.0, job_id="h")
        m2 = ElasticManager(store, "n2", np_min=1, ttl=5.0, job_id="h")
        m1.register()
        m2.register()
        try:
            store.set(f"{m2.prefix}/beat/n2", b"\xffgarbage")
            assert m1.alive_nodes() == ["n1"]       # corrupt == not alive
            alive, usable = m1.membership_snapshot()
            assert alive == ["n1"] and usable == ["n1"]
            assert m1.pod_status()                  # no crash
            store.set(f"{m1.prefix}/preempt/n1", b"not-a-float")
            assert not m1.is_preempted()            # corrupt == no notice
        finally:
            m1.stop()
            m2.stop()
            store.close()

    def test_pod_status_single_store_pass(self):
        """pod_status must ride the one-pass snapshot, not re-scan via
        alive_nodes() + preempted_nodes()."""
        store = TCPStore("127.0.0.1", 0, is_master=True)
        m = ElasticManager(store, "n0", np_min=1, ttl=5.0, job_id="one")
        m.register()
        try:
            m.wait_for_np(timeout=10)
            calls = []
            orig = m.store.get

            def spy(key, *a, **k):
                calls.append(key)
                return orig(key, *a, **k)

            m.store = type("S", (), {"get": staticmethod(spy),
                                     "set": store.set,
                                     "add": store.add,
                                     "delete": store.delete})()
            m.pod_status()
            beat_reads = [k for k in calls if "/beat/" in k]
            assert len(beat_reads) == 1     # one node, ONE lease read
        finally:
            m.store = store
            m.stop()
            store.close()

    def test_dead_notifier_does_not_crash_loop_relaunch(self):
        """A relaunched generation must resume training even while the
        DEPARTED node's preemption notice is still inside notice_ttl."""
        store = TCPStore("127.0.0.1", 0, is_master=True)
        m1 = ElasticManager(store, "n1", np_min=1, ttl=1.0, job_id="cl")
        m2 = ElasticManager(store, "n2", np_min=1, ttl=1.0, job_id="cl")
        m1.register()
        m2.register()
        try:
            m2.notify_preemption()
            assert m1.should_checkpoint()   # notifier still holds a lease
            m2.stop()
            time.sleep(1.5)                 # lease expires, notice fresh
            assert not m1.should_checkpoint()
        finally:
            m1.stop()
            m2.stop()
            store.close()


# ------------------------------------------------------- hapi hook (fast)

class TestHapiResilientCheckpoint:
    def test_fit_snapshots_and_resumes(self, tmp_path):
        X = np.random.RandomState(0).rand(8, 4).astype(np.float32)
        Y = X.sum(1, keepdims=True).astype(np.float32)

        def build():
            paddle.seed(0)
            m = paddle.Model(nn.Sequential(nn.Linear(4, 8), nn.Tanh(),
                                           nn.Linear(8, 1)))
            opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=m.parameters())
            return m.prepare(opt, nn.MSELoss())

        m1 = build()
        m1.fit(list(zip(X, Y)), batch_size=4, epochs=2, verbose=0,
               shuffle=False, resilience_dir=str(tmp_path),
               snapshot_steps=2)
        assert latest_checkpoint(str(tmp_path)) is not None
        trained_steps = m1._optimizer._step_count
        w1 = m1.network[0].weight.numpy().copy()

        m2 = build()                      # simulated relaunch
        m2.fit(list(zip(X, Y)), batch_size=4, epochs=1, verbose=0,
               shuffle=False, resilience_dir=str(tmp_path),
               snapshot_steps=100)
        # resumed FROM the trained state, not from scratch (the list
        # loader yields one sample per batch: 8 steps per epoch)
        assert m2._optimizer._step_count == trained_steps + 8
        assert not np.allclose(m2.network[0].weight.numpy(),
                               build().network[0].weight.numpy())
        assert w1.shape == m2.network[0].weight.numpy().shape


# ----------------------------------------------------- chaos harness (slow)

def _read_losses(path):
    out = {}
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            out[rec["step"]] = rec["loss"]
    return out


def _assert_no_torn_checkpoint(ckpt_dir):
    """Every directory latest_checkpoint COULD resolve must load; every
    uncommitted directory must be invisible to it."""
    net, opt, step_fn, state_fn, _, _ = _tiny_job()
    step_fn(0)
    for name in sorted(os.listdir(ckpt_dir)):
        gen = os.path.join(ckpt_dir, name)
        if not os.path.isdir(gen):
            continue
        if read_committed_marker(gen) is not None:
            rebuilt, step = restore_state(state_fn(), gen)   # must load
            assert step is not None
        else:
            assert latest_checkpoint(ckpt_dir) != gen


@pytest.mark.slow
@pytest.mark.heavy
class TestChaosHarness:
    TOTAL = 26
    SNAPSHOT_EVERY = 5   # must match chaos_worker.py

    def _spawn(self, tmp_path, port, rank, world, attempt, ckpt="ckpt",
               sleep="0.12"):
        env = dict(os.environ,
                   PADDLE_TRAINER_ID=str(rank),
                   PADDLE_TRAINERS_NUM=str(world),
                   CHAOS_STORE_PORT=str(port),
                   CHAOS_ATTEMPT=str(attempt),
                   CHAOS_STEP_SLEEP=sleep,
                   PYTHONPATH=os.path.dirname(os.path.dirname(_WORKER)))
        return subprocess.Popen(
            [sys.executable, _WORKER, str(tmp_path / "out"),
             str(tmp_path / ckpt), str(self.TOTAL)], env=env)

    def _wait_for_steps(self, tmp_path, rank, attempt, n, timeout=120):
        path = tmp_path / "out" / f"losses_r{rank}_a{attempt}.jsonl"
        deadline = time.time() + timeout
        while time.time() < deadline:
            if path.exists() and len(path.read_text().splitlines()) >= n:
                return
            time.sleep(0.2)
        raise AssertionError(f"rank {rank} never reached step {n}")

    def _reference_losses(self, tmp_path, src_ckpt):
        """Uninterrupted run FROM THE SAME GENERATION: copy the
        checkpoint root as it stood at relaunch time, run a clean
        single-rank worker over the copy to completion."""
        ref = tmp_path / "refckpt"
        shutil.copytree(tmp_path / src_ckpt, ref)
        p = self._spawn(tmp_path, port=self._port, rank=0, world=1,
                        attempt=99, ckpt="refckpt", sleep="0.0")
        assert p.wait(timeout=180) == 0
        res = json.load(open(tmp_path / "out" / "result_r0_a99.json"))
        return (_read_losses(tmp_path / "out" / "losses_r0_a99.jsonl"),
                res["resume"])

    def _run_recovery(self, tmp_path, kill_signal, expect_rc):
        """Shared chaos flow: two ranks train; rank 1 gets
        `kill_signal` mid-run; survivors exit per protocol; a re-ranked
        single-node relaunch must restore from a committed generation
        and finish with a loss curve matching the uninterrupted
        reference from that same generation."""
        (tmp_path / "out").mkdir()
        store = TCPStore("127.0.0.1", 0, is_master=True)
        self._port = store.port
        procs = []
        try:
            procs = [self._spawn(tmp_path, store.port, r, 2, attempt=0)
                     for r in (0, 1)]
            self._wait_for_steps(tmp_path, 1, 0, 9)
            self._wait_for_steps(tmp_path, 0, 0, 9)
            os.kill(procs[1].pid, kill_signal)     # chaos lands mid-step
            rc1 = procs[1].wait(timeout=60)
            rc0 = procs[0].wait(timeout=120)
            assert rc1 == (-kill_signal if kill_signal == signal.SIGKILL
                           else 64), rc1
            assert rc0 == expect_rc, rc0

            # relaunch: survivors re-ranked as a world of 1, restoring
            # from the latest committed generation (reshard-on-load
            # covers the world-size change)
            reference, ref_resume = self._reference_losses(tmp_path,
                                                           "ckpt")
            p = self._spawn(tmp_path, store.port, 0, 1, attempt=1)
            assert p.wait(timeout=180) == 0
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            store.close()

        res = json.load(open(tmp_path / "out" / "result_r0_a1.json"))
        assert res["action"] == "completed"
        resume = res["resume"]
        assert resume == ref_resume
        # recovery within N steps: bounded by the snapshot cadence
        kill_step = max(_read_losses(
            tmp_path / "out" / "losses_r1_a0.jsonl"))
        assert resume >= kill_step - 2 * self.SNAPSHOT_EVERY
        assert resume >= 1

        # loss-curve continuity vs the uninterrupted reference run from
        # the same generation
        got = _read_losses(tmp_path / "out" / "losses_r0_a1.jsonl")
        assert sorted(got) == list(range(resume, self.TOTAL))
        for s in range(resume, self.TOTAL):
            np.testing.assert_allclose(got[s], reference[s], rtol=1e-6,
                                       err_msg=f"loss diverged at {s}")

        _assert_no_torn_checkpoint(str(tmp_path / "ckpt"))
        return resume

    def test_sigkill_rank_death_recovers(self, tmp_path):
        """A rank SIGKILLed mid-step: the survivor's TTL watch turns it
        into RESTART (exit 75), the relaunch re-ranks and restores."""
        self._run_recovery(tmp_path, signal.SIGKILL, expect_rc=75)

    def test_sigterm_preemption_recovers(self, tmp_path):
        """A rank SIGTERMed at an arbitrary step: IT snapshots-now and
        exits cleanly; the peer observes the broadcast notice and
        checkpoints too (exit 64); relaunch resumes near the notice."""
        resume = self._run_recovery(tmp_path, signal.SIGTERM,
                                    expect_rc=64)
        # snapshot-NOW actually committed: resume lands at/after the
        # notice step, not back at the last periodic cadence... the
        # notice landed at step >= 9, periodic gens stop at multiples
        # of SNAPSHOT_EVERY
        assert resume >= 9


pytestmark = pytest.mark.smoke
