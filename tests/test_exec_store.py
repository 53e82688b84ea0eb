"""Persistent executable + AOT-plan cache (ISSUE 19): the on-disk
cache spine in jit/exec_store.py.

Covers the roundtrip (disk hit = zero XLA compiles, identical results),
every poisoning edge (corrupt/truncated entry -> miss + flight event,
never a crash; jaxlib bump -> full invalidation; mesh-epoch bump ->
miss; wrong weights-fingerprint -> refuse; concurrent uid-fenced
writers -> no torn entries), keep-K retention, the step-capture and
serving-engine integrations (bitwise-equal fp32 training blocks and
byte-identical serving streams cold vs cached), and the AOT planner's
read-bound plan short-circuit.
"""

import hashlib
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import flags
from paddle_tpu.jit import exec_store as es
from paddle_tpu.observability import flight_recorder as fr
from paddle_tpu.observability.metrics import METRIC_NAMES, registry
from paddle_tpu.observability.tracing import SPAN_NAMES
from paddle_tpu.utils.durability import COMMIT_FILE


@pytest.fixture(autouse=True)
def _detached_after():
    yield
    es.detach()


def _compiles():
    return registry().get("jit.compiles").value


def _fresh_process_sim():
    """Approximate a fresh process: drop every in-process executable so
    the next run either recompiles (cold) or loads from disk (warm)."""
    from paddle_tpu.ops import dispatcher as dsp
    dsp._get_exec.cache_clear()
    for schema in dsp.OPS.values():
        schema.__dict__.pop("_fast_ex", None)
    from paddle_tpu.models import serving
    serving._STEP_PROGRAMS.clear()
    jax.clear_caches()


def _corrupt_events():
    return [e for e in fr.recorder().entries() if e[3] == "jit.cache.corrupt"]


def _entry_dirs(root, kind):
    kd = os.path.join(root, kind)
    return sorted(os.path.join(kd, n) for n in os.listdir(kd)) \
        if os.path.isdir(kd) else []


def _mm():
    return jax.jit(lambda x, y: x @ y + 1.0)


X = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
Y = jnp.eye(8, dtype=jnp.float32)


class TestTaxonomy:
    def test_metrics_and_span_registered(self):
        for name in ("jit.cache.hits", "jit.cache.misses",
                     "jit.cache.load_seconds", "jit.cache.bytes"):
            assert name in METRIC_NAMES
        assert "jit.cache.load" in SPAN_NAMES


class TestRoundtrip:
    def test_disk_hit_skips_compile_and_matches(self, tmp_path):
        es.attach(str(tmp_path))
        w1 = es.persistent(_mm(), "op", label="t")
        r1 = np.asarray(w1(X, Y))
        st = es.store()
        assert st.state()["entries"] == 1 and st.written == 1
        # a second wrapper around the same program: loads, never compiles
        w2 = es.persistent(_mm(), "op", label="t2")
        c0 = _compiles()
        r2 = np.asarray(w2(X, Y))
        assert _compiles() - c0 == 0
        assert st.hits == 1
        assert np.array_equal(r1, r2)
        assert registry().get("jit.cache.hits").value >= 1
        assert registry().get("jit.cache.bytes").value > 0

    def test_unattached_wrapper_is_identity(self):
        f = _mm()
        assert es.persistent(f, "op") is f

    def test_fp32_training_block_bitwise_equal_cold_vs_cached(self,
                                                              tmp_path):
        """A donated fp32 train block (loss/grad/SGD x3) must produce
        bit-identical weights when replayed from the disk cache."""
        def block(w, xs, ys):
            for i in range(3):
                g = jax.grad(
                    lambda w: jnp.mean((xs[i] @ w - ys[i]) ** 2))(w)
                w = w - 0.05 * g
            return w

        w0 = np.linspace(-1.0, 1.0, 36, dtype=np.float32).reshape(6, 6)
        xs = jnp.asarray(np.random.RandomState(0)
                         .randn(3, 4, 6).astype(np.float32))
        ys = jnp.asarray(np.random.RandomState(1)
                         .randn(3, 4, 6).astype(np.float32))
        es.attach(str(tmp_path))
        cold = es.persistent(jax.jit(block, donate_argnums=(0,)),
                             "step", label="block")
        w_cold = np.asarray(cold(jnp.asarray(w0), xs, ys))
        warm = es.persistent(jax.jit(block, donate_argnums=(0,)),
                             "step", label="block")
        c0 = _compiles()
        w_warm = np.asarray(warm(jnp.asarray(w0), xs, ys))
        assert _compiles() - c0 == 0 and es.store().hits == 1
        assert w_cold.tobytes() == w_warm.tobytes()


class TestPoisoning:
    def _populate(self, tmp_path):
        es.attach(str(tmp_path))
        w = es.persistent(_mm(), "op")
        expect = np.asarray(w(X, Y))
        return expect

    def test_truncated_entry_is_miss_with_flight_event(self, tmp_path):
        expect = self._populate(tmp_path)
        (entry,) = _entry_dirs(tmp_path, "op")
        payload = os.path.join(entry, "payload.bin")
        raw = open(payload, "rb").read()
        with open(payload, "wb") as f:   # simulate torn write / bitrot
            f.write(raw[:len(raw) // 2])
        n0 = len(_corrupt_events())
        w2 = es.persistent(_mm(), "op")
        got = np.asarray(w2(X, Y))       # checksum miss -> recompile
        assert np.array_equal(got, expect)
        assert es.store().hits == 0
        assert len(_corrupt_events()) > n0

    def test_garbage_payload_with_valid_checksum_never_crashes(
            self, tmp_path):
        # a payload that passes the checksum but fails deserialization
        # (e.g. written by a future format) must also degrade to a miss
        es.attach(str(tmp_path))
        jfn = _mm()
        hlo = jfn.lower(X, Y).as_text().encode("utf-8")
        parts = (hashlib.sha256(hlo).hexdigest(),)
        es.store().put("op", parts, b"not-a-pickled-executable")
        n0 = len(_corrupt_events())
        w = es.persistent(_mm(), "op")
        got = np.asarray(w(X, Y))
        assert np.array_equal(got, np.asarray(jfn(X, Y)))
        assert len(_corrupt_events()) > n0

    def test_jaxlib_version_bump_invalidates_everything(
            self, tmp_path, monkeypatch):
        self._populate(tmp_path)
        monkeypatch.setattr(es, "_jaxlib_version", lambda: "99.99.99")
        es.attach(str(tmp_path))   # fresh mirror counters
        w = es.persistent(_mm(), "op")
        w(X, Y)
        assert es.store().hits == 0 and es.store().misses >= 1

    def test_mesh_epoch_bump_is_miss(self, tmp_path):
        self._populate(tmp_path)
        saved = flags._mesh_epoch
        try:
            flags._mesh_epoch = saved + 1
            es.attach(str(tmp_path))
            w = es.persistent(_mm(), "op")
            w(X, Y)
            assert es.store().hits == 0
        finally:
            flags._mesh_epoch = saved

    def test_wrong_weights_fingerprint_refuses(self, tmp_path):
        es.attach(str(tmp_path), scope="weights-A")
        np.asarray(es.persistent(_mm(), "op")(X, Y))
        es.attach(str(tmp_path), scope="weights-B")
        es.persistent(_mm(), "op")(X, Y)
        assert es.store().hits == 0
        # ... while the matching scope still resolves
        es.attach(str(tmp_path), scope="weights-A")
        es.persistent(_mm(), "op")(X, Y)
        assert es.store().hits == 1

    def test_concurrent_writers_are_uid_fenced(self, tmp_path,
                                               monkeypatch):
        es.attach(str(tmp_path))
        st = es.store()
        parts = ("prog",)
        monkeypatch.setattr(es, "_UID", "aaaaaaaa")
        assert st.put("op", parts, b"payload-from-writer-A")
        monkeypatch.setattr(es, "_UID", "bbbbbbbb")
        assert st.put("op", parts, b"payload-from-writer-B")
        dirs = _entry_dirs(tmp_path, "op")
        assert len(dirs) == 2      # distinct dirs, no overwrite race
        # a third writer died mid-commit: payload, no COMMITTED marker
        torn = dirs[0].rsplit("-", 1)[0] + "-cccccccc"
        os.makedirs(torn)
        with open(os.path.join(torn, "payload.bin"), "wb") as f:
            f.write(b"half-writ")
        got = st.get("op", parts)
        assert got is not None
        assert got[0] in (b"payload-from-writer-A",
                          b"payload-from-writer-B")

    def test_parallel_puts_same_key_no_torn_entries(self, tmp_path):
        es.attach(str(tmp_path))
        st = es.store()
        errs = []

        def work(i):
            try:
                for _ in range(5):
                    st.put("op", ("k",), b"x" * 2048)
            except Exception as e:  # pragma: no cover - the assertion
                errs.append(e)

        ts = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        got = st.get("op", ("k",))
        assert got is not None and got[0] == b"x" * 2048

    def test_keep_k_retention_prunes_oldest(self, tmp_path):
        es.attach(str(tmp_path), keep=2)
        st = es.store()
        for i in range(5):
            st.put("op", (f"prog-{i}",), b"p%d" % i)
        committed = [d for d in _entry_dirs(tmp_path, "op")
                     if os.path.exists(os.path.join(d, COMMIT_FILE))]
        assert len(committed) == 2
        # the newest entries survive
        assert st.get("op", ("prog-4",)) is not None


class TestStepCaptureSite:
    def test_captured_step_loads_from_disk_bitwise(self, tmp_path):
        def train(n_steps=3):
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(6, 8), nn.Tanh(),
                                nn.Linear(8, 3))
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=net.parameters())
            ce = nn.CrossEntropyLoss()

            def step(x, y):
                loss = ce(net(x), y)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss

            fn = paddle.jit_step(step)
            y = paddle.to_tensor(np.array([0, 1, 2, 0], np.int64))
            losses = []
            for i in range(n_steps):
                x = paddle.to_tensor(np.random.RandomState(i)
                                     .randn(4, 6).astype(np.float32))
                losses.append(float(fn(x, y)))
            return losses, [np.asarray(p._data)
                            for p in net.parameters()]

        saved = paddle.get_flags(["FLAGS_step_capture"])
        try:
            paddle.set_flags({"FLAGS_step_capture": True})
            es.attach(str(tmp_path))
            losses_cold, params_cold = train()
            assert es.store().state()["entries"] >= 1
            _fresh_process_sim()
            hits0 = es.store().hits
            losses_warm, params_warm = train()
            assert es.store().hits > hits0
            assert losses_cold == losses_warm
            for a, b in zip(params_cold, params_warm):
                assert a.tobytes() == b.tobytes()
        finally:
            paddle.set_flags(saved)


class TestAotPlanCache:
    def test_plan_short_circuits_read_bound(self, tmp_path):
        from paddle_tpu.distributed.auto_parallel import aot
        es.attach(str(tmp_path))
        plan_key = ("llama3_8b_v5p64", "v5p:4x4x4", 8, 8, 1, 2048, 2,
                    False)
        fake = {"params": 123, "mesh": {"dp": 8, "mp": 8},
                "compile_seconds": 120.0,
                "projected": {"step_seconds": 0.5, "flops_per_chip": 1.0,
                              "hbm_bytes_per_chip": 1.0,
                              "compute_seconds": 0.5,
                              "memory_seconds": 0.1, "bound": "compute",
                              "tokens_per_sec": 1.0,
                              "mfu_upper_bound": 0.5}}
        es.store().put_json("aot_plan", plan_key, fake)
        # the hit must short-circuit BEFORE the topology client and the
        # model build: a wrong topology name would otherwise raise
        out = aot.plan_llama3_8b_v5p64(tp=8, dp=8, batch_per_dp=1,
                                       seq=2048, layers=2)
        assert out["cached"] is True and out["params"] == 123

    def test_plan_key_is_argument_sensitive(self, tmp_path):
        from paddle_tpu.distributed.auto_parallel import aot  # noqa: F401
        es.attach(str(tmp_path))
        plan_key = ("llama3_8b_v5p64", "v5p:4x4x4", 8, 8, 1, 2048, 2,
                    False)
        es.store().put_json("aot_plan", plan_key, {"params": 1})
        other = ("llama3_8b_v5p64", "v5p:4x4x4", 8, 8, 1, 4096, 2,
                 False)
        assert es.store().get_json("aot_plan", other) is None


class TestServingWarmStart:
    @pytest.fixture(scope="class")
    def model(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=160, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    def test_relaunch_is_byte_identical_and_compile_free(self, model,
                                                         tmp_path):
        from paddle_tpu.serving.resilience import (ResilientServingEngine,
                                                   ServingAction)
        store_dir = str(tmp_path / "exec_cache")
        eng_kw = dict(max_batch=2, num_blocks=32, block_size=16,
                      temperature=0.9, seed=17,
                      exec_store_dir=store_dir)
        prompts = [[5, 9, 13, 2], [7, 3, 11, 4, 6]]

        def launch(root):
            _fresh_process_sim()
            eng = ResilientServingEngine(model, str(tmp_path / root),
                                         **eng_kw)
            eng.warmup()        # pre-admission load point (fleet READY)
            for p in prompts:
                eng.add_request(list(p), max_new_tokens=5)
            assert eng.run() == ServingAction.COMPLETED
            out = dict(eng.outputs)
            eng.close()
            return out

        hist = registry().get("jit.compile_seconds")
        c0, s0 = _compiles(), hist.sum
        out_cold = launch("r1")          # populates the store
        cold_compiles, cold_s = _compiles() - c0, hist.sum - s0
        c0, s0 = _compiles(), hist.sum
        out_warm = launch("r2")          # relaunch: loads from disk
        warm_compiles, warm_s = _compiles() - c0, hist.sum - s0
        # every dispatcher executable must come from disk; the residual
        # compiles are jax's implicit per-primitive eager jits (reshape,
        # gather, threefry...) that any fresh process pays in ~ms each
        assert es.store().hits > 0 and es.store().misses == 0, (
            es.store().state())
        # since ISSUE 30 a step is four executables: the step program
        # (the whole forward), the logits' reshape, gather and sampling
        assert cold_compiles - warm_compiles >= 4
        assert cold_s > warm_s * 2, (
            f"warm relaunch not compile-bound-free: cold {cold_s:.3f}s "
            f"vs warm {warm_s:.3f}s")
        assert out_cold == out_warm      # byte-identical streams

    def test_same_process_second_replica_compiles_nothing(self, model,
                                                          tmp_path):
        """Rolling deploy: the 2nd replica of a thread-based fleet
        shares the process (primitive jits warm) and the store (ragged
        executables warm) — jit.compiles delta must be ~zero."""
        from paddle_tpu.serving.resilience import (ResilientServingEngine,
                                                   ServingAction)
        store_dir = str(tmp_path / "exec_cache")
        eng_kw = dict(max_batch=2, num_blocks=32, block_size=16,
                      temperature=0.9, seed=17,
                      exec_store_dir=store_dir)

        def replica(root, clear):
            if clear:
                _fresh_process_sim()
            else:
                # same process: only the per-op executable cache drops,
                # as a restarted replica thread would see it
                from paddle_tpu.ops import dispatcher as dsp
                dsp._get_exec.cache_clear()
                for schema in dsp.OPS.values():
                    schema.__dict__.pop("_fast_ex", None)
            eng = ResilientServingEngine(model, str(tmp_path / root),
                                         **eng_kw)
            eng.warmup()
            eng.add_request([5, 9, 13, 2], max_new_tokens=4)
            assert eng.run() == ServingAction.COMPLETED
            out = dict(eng.outputs)
            eng.close()
            return out

        out1 = replica("ra", clear=True)
        c0 = _compiles()
        out2 = replica("rb", clear=False)
        assert _compiles() - c0 <= 2, "second replica recompiled"
        assert out1 == out2
