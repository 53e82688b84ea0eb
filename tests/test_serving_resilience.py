"""Serving resilience (ISSUE 9): request journal + replay,
drain-on-SIGTERM, prefix-cache warm-start.

Fast tier-1 covers the journal's commit-protocol durability (whole
segments or nothing — a torn journal is unrepresentable), single-process
replay byte-identity at temperature>0 (the per-request sampling streams
make KV re-derivation exact), drain semantics, warm-cache
snapshot/preload, the bounded admission queue + finished-request
retirement, and the step-hang watchdog.

The slow-marked chaos tranche drives REAL processes: SIGKILL mid-stream
→ relaunch → every unfinished journaled request completes
byte-identically vs an uninterrupted reference run; SIGTERM → drain →
committed journal + warm-cache snapshot → recovery; plus a
no-torn-journal kill sweep.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import ContinuousBatchingEngine, QueueFull
from paddle_tpu.observability.metrics import METRIC_NAMES, registry
from paddle_tpu.serving.resilience import (ResilientServingEngine,
                                           RequestJournal, ServingAction,
                                           load_prefix_cache,
                                           snapshot_prefix_cache)
from paddle_tpu.utils.durability import read_committed_marker

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "serving_chaos_worker.py")


def _counter(name):
    return registry().get(name).value


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


def _requests(n=4, head_blocks=0, rng_seed=0, bs=16):
    rng = np.random.RandomState(rng_seed)
    head = rng.randint(0, 128, head_blocks * bs).tolist()
    return [head + rng.randint(0, 128, 4 + 2 * i).tolist()
            for i in range(n)]


def _reference(model, tmp_path, prompts, max_new=6, name="ref", **kw):
    eng = ResilientServingEngine(model, str(tmp_path / name),
                                 **{**ENG, **kw})
    for p in prompts:
        eng.add_request(p, max_new_tokens=max_new)
    assert eng.run() == ServingAction.COMPLETED
    out = dict(eng.outputs)
    eng.close()
    return out


# ------------------------------------------------------------ journal (fast)

class TestRequestJournal:
    def test_roundtrip_and_segment_ordering(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append({"t": "config", "seed": 1, "sampling": {}, "eos": None})
        j.append({"t": "admit", "rid": 0, "prompt": [1, 2],
                  "max_new_tokens": 4})
        j.flush()
        j.append({"t": "tokens", "rid": 0, "from": 0, "toks": [5, 6]})
        j.flush()
        j.append({"t": "tokens", "rid": 0, "from": 2, "toks": [7]})
        j.append({"t": "finish", "rid": 0})
        j.flush()
        st = RequestJournal(str(tmp_path)).load()
        assert st.config["seed"] == 1
        assert st.requests[0].tokens == [5, 6, 7]
        assert st.requests[0].finished
        assert st.segments == 3

    def test_state_splits_finished_from_unfinished(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append({"t": "config", "seed": 1, "sampling": {}, "eos": None})
        for rid in (0, 1, 2):
            j.append({"t": "admit", "rid": rid, "prompt": [1, 2 + rid],
                      "max_new_tokens": 4})
        j.append({"t": "tokens", "rid": 1, "from": 0, "toks": [5]})
        j.append({"t": "finish", "rid": 1})
        j.flush()
        st = RequestJournal(str(tmp_path)).load()
        assert [r.rid for r in st.finished] == [1]
        assert sorted(r.rid for r in st.unfinished) == [0, 2]

    def test_empty_flush_writes_no_segment(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.flush()
        assert j.load().segments == 0

    def test_tmp_orphans_are_not_segments(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append({"t": "admit", "rid": 0, "prompt": [1],
                  "max_new_tokens": 2})
        j.flush()
        # a writer SIGKILLed mid-fsync leaves only a tmp sibling — it
        # must be invisible to the loader AND to segment numbering
        (tmp_path / "seg-00000001.jsonl.tmp-dead").write_bytes(
            b'{"t": "finish", "ri')
        j2 = RequestJournal(str(tmp_path))
        st = j2.load()
        assert len(st.requests) == 1 and not st.requests[0].finished
        j2.append({"t": "finish", "rid": 0})
        j2.flush()
        assert RequestJournal(str(tmp_path)).load().requests[0].finished

    def test_watermark_gap_is_an_integrity_error(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append({"t": "admit", "rid": 3, "prompt": [1],
                  "max_new_tokens": 9})
        j.append({"t": "tokens", "rid": 3, "from": 2, "toks": [8]})
        j.flush()
        with pytest.raises(ValueError, match="journal integrity"):
            j.load()

    def test_zombie_writer_cannot_clobber_segments(self, tmp_path):
        """Step-hang recovery relaunches OVER a possibly-still-alive
        wedged writer: when it unwedges and flushes, its segment must
        not atomically replace one the new incarnation already wrote.
        Overlapping watermark records (byte-identical by construction)
        merge on load."""
        j1 = RequestJournal(str(tmp_path))
        j1.append({"t": "admit", "rid": 0, "prompt": [1],
                   "max_new_tokens": 8})
        j1.append({"t": "tokens", "rid": 0, "from": 0, "toks": [5, 6]})
        j1.flush()
        j2 = RequestJournal(str(tmp_path))      # the relaunch
        j2.append({"t": "tokens", "rid": 0, "from": 2, "toks": [7, 8]})
        j2.flush()
        # the zombie unwedges: same segment NUMBER as j2's, regenerating
        # the same tokens (plus one more it got further on)
        j1.append({"t": "tokens", "rid": 0, "from": 2, "toks": [7, 8, 9]})
        j1.flush()
        st = RequestJournal(str(tmp_path)).load()
        assert st.segments == 3                 # nothing was replaced
        assert st.requests[0].tokens == [5, 6, 7, 8, 9]

    def test_diverging_overlap_is_an_integrity_error(self, tmp_path):
        j1 = RequestJournal(str(tmp_path))
        j1.append({"t": "admit", "rid": 0, "prompt": [1],
                   "max_new_tokens": 8})
        j1.append({"t": "tokens", "rid": 0, "from": 0, "toks": [5, 6]})
        j1.flush()
        j2 = RequestJournal(str(tmp_path))
        j2.append({"t": "tokens", "rid": 0, "from": 1, "toks": [99]})
        j2.flush()
        with pytest.raises(ValueError, match="diverge"):
            RequestJournal(str(tmp_path)).load()

    def test_orphaned_records_are_integrity_errors(self, tmp_path):
        """tokens/finish with no admit (hand-pruned segment files) must
        raise the diagnostic ValueError, not a bare KeyError."""
        j = RequestJournal(str(tmp_path))
        j.append({"t": "tokens", "rid": 7, "from": 0, "toks": [1]})
        j.flush()
        with pytest.raises(ValueError, match="no admit"):
            RequestJournal(str(tmp_path)).load()
        j2 = RequestJournal(str(tmp_path / "b"))
        j2.append({"t": "finish", "rid": 7})
        j2.flush()
        with pytest.raises(ValueError, match="no admit"):
            RequestJournal(str(tmp_path / "b")).load()

    def test_duplicate_admit_is_idempotent_but_must_agree(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append({"t": "admit", "rid": 0, "prompt": [1, 2],
                  "max_new_tokens": 4})
        j.append({"t": "tokens", "rid": 0, "from": 0, "toks": [5]})
        j.append({"t": "admit", "rid": 0, "prompt": [1, 2],
                  "max_new_tokens": 4})      # verbatim dup: keep tokens
        j.flush()
        st = RequestJournal(str(tmp_path)).load()
        assert st.requests[0].tokens == [5]
        j.append({"t": "admit", "rid": 0, "prompt": [9],
                  "max_new_tokens": 4})      # DIFFERENT request, same rid
        j.flush()
        with pytest.raises(ValueError, match="admitted twice"):
            RequestJournal(str(tmp_path)).load()

    def test_commit_marker_and_uncommit(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append({"t": "admit", "rid": 0, "prompt": [1],
                  "max_new_tokens": 2})
        j.commit(drained=True)
        md = j.committed_marker()
        assert md["drained"] is True and md["step"] == 1
        j.uncommit()
        assert j.committed_marker() is None

    def test_new_metric_names_frozen(self):
        for name in ("serving.queue_wait_seconds", "serving.rejected",
                     "serving.resilience.journal_records",
                     "serving.resilience.journal_flushes",
                     "serving.resilience.journal_compactions",
                     "serving.resilience.replayed_requests",
                     "serving.resilience.replayed_tokens",
                     "serving.resilience.recovered_finished",
                     "serving.resilience.drains",
                     "serving.resilience.drain_seconds",
                     "serving.resilience.snapshots",
                     "serving.resilience.warm_blocks",
                     "serving.resilience.step_hangs"):
            assert name in METRIC_NAMES, name
            assert registry().get(name) is not None, name


# ------------------------------------------------ journal compaction (fast)

class TestJournalCompaction:
    def _fill(self, root, n=6, finish_below=4):
        j = RequestJournal(root)
        j.append({"t": "config", "seed": 1, "sampling": {}, "eos": None})
        for rid in range(n):
            j.append({"t": "admit", "rid": rid, "prompt": [1, 2 + rid],
                      "max_new_tokens": 4})
            j.flush()
            j.append({"t": "tokens", "rid": rid, "from": 0, "toks": [5, 6]})
            if rid < finish_below:
                j.append({"t": "finish", "rid": rid})
            j.flush()
        return j

    def test_compact_before_any_segment_is_a_noop(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        assert j.compact(drop_rids={0}) == 0
        assert os.listdir(tmp_path) == []

    def test_compact_drops_only_retired_finished(self, tmp_path):
        j = self._fill(str(tmp_path))
        # rid 5 is unfinished and listed retired by mistake: never dropped
        dropped = j.compact(drop_rids={0, 1, 5})
        assert dropped == 2
        st = RequestJournal(str(tmp_path)).load()
        assert set(st.requests) == {2, 3, 4, 5}
        assert st.config["seed"] == 1                   # config survives
        assert st.requests[2].finished                  # unretired kept
        assert st.requests[2].tokens == [5, 6]
        assert not st.requests[5].finished
        names = os.listdir(str(tmp_path))
        assert sum(n.startswith("snap-") for n in names) == 1
        assert not any(n.startswith("seg-") for n in names)

    def test_appends_after_compaction_continue_the_stream(self, tmp_path):
        j = self._fill(str(tmp_path))
        j.compact(drop_rids={0})
        j.append({"t": "tokens", "rid": 4, "from": 2, "toks": [9]})
        j.flush()
        st = RequestJournal(str(tmp_path)).load()
        assert st.requests[4].tokens == [5, 6, 9]

    def test_recompaction_at_same_coverage_retires_old_snapshot(
            self, tmp_path):
        """Two compactions with no segment flushed in between share a
        coverage number; the second must REPLACE the first (equal
        coverage included in the unlink), or load()'s tie-break would
        pick between them by uid and could resurrect requests the later
        pass dropped."""
        j = self._fill(str(tmp_path))
        j.compact(drop_rids={0})
        j.compact(drop_rids={1})       # no new segments in between
        snaps = [n for n in os.listdir(str(tmp_path))
                 if n.startswith("snap-")]
        assert len(snaps) == 1, snaps
        st = RequestJournal(str(tmp_path)).load()
        assert 0 not in st.requests and 1 not in st.requests

    def test_leftover_old_segment_is_subsumed(self, tmp_path):
        """Crash mid-unlink: segments at or below the snapshot's
        coverage load as if deleted — the snapshot wins, and a retired
        request can never resurrect through a stale segment."""
        j = self._fill(str(tmp_path))
        seg0 = [n for n in os.listdir(str(tmp_path))
                if n.startswith("seg-")][0]
        body = open(tmp_path / seg0, encoding="utf-8").read()
        j.compact(drop_rids={0, 1, 2, 3})
        (tmp_path / seg0).write_text(body)   # "unlink never happened"
        st = RequestJournal(str(tmp_path)).load()
        assert set(st.requests) == {4, 5}

    def test_repeated_compaction_bounds_disk(self, tmp_path):
        """The satellite's disk-growth bound: a long retire-heavy stream
        compacted on the snapshot cadence keeps the journal directory at
        one snapshot + the tail segments, regardless of how many
        requests have retired."""
        j = RequestJournal(str(tmp_path))
        j.append({"t": "config", "seed": 1, "sampling": {}, "eos": None})
        sizes, counts = [], []
        rid = 0
        for round_ in range(6):
            for _ in range(20):
                j.append({"t": "admit", "rid": rid, "prompt": [1, 2],
                          "max_new_tokens": 4})
                j.flush()
                j.append({"t": "tokens", "rid": rid, "from": 0,
                          "toks": [3, 4, 5]})
                j.append({"t": "finish", "rid": rid})
                j.flush()
                rid += 1
            j.compact(drop_rids=set(range(rid)))   # everything delivered
            names = os.listdir(str(tmp_path))
            counts.append(len(names))
            sizes.append(sum(os.path.getsize(tmp_path / n) for n in names))
        assert all(c == 1 for c in counts), counts    # one snapshot file
        assert max(sizes) <= 2 * min(sizes), sizes    # no growth trend
        st = RequestJournal(str(tmp_path)).load()
        assert st.requests == {} and st.config["seed"] == 1

    def test_engine_snapshot_compacts_retired(self, model, tmp_path):
        """pop_output marks delivery; the next snapshot drops those
        requests from the WAL, and a relaunch neither recovers them nor
        replays them."""
        eng = ResilientServingEngine(model, str(tmp_path / "c"), **ENG)
        prompts = _requests(3)
        rids = [eng.add_request(p, max_new_tokens=3) for p in prompts]
        assert eng.run() == ServingAction.COMPLETED
        assert eng.pop_output(rids[0]) is not None
        assert eng.pop_output(rids[1]) is not None
        c0 = _counter("serving.resilience.journal_compactions")
        eng.snapshot()
        assert _counter("serving.resilience.journal_compactions") == c0 + 1
        eng.close()
        e2 = ResilientServingEngine(model, str(tmp_path / "c"), **ENG)
        assert set(e2.outputs) == {rids[2]}       # undelivered one only
        assert e2.replayed_requests == 0
        e2.close()

class TestBoundedQueue:
    def test_queue_full_rejects_explicitly(self, model):
        eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=32,
                                       block_size=16, temperature=0.0,
                                       max_queue=2)
        rej0 = _counter("serving.rejected")
        eng.add_request([1, 2, 3], max_new_tokens=2)
        eng.add_request([4, 5], max_new_tokens=2)
        with pytest.raises(QueueFull, match="admission queue is full"):
            eng.add_request([6], max_new_tokens=2)
        assert _counter("serving.rejected") == rej0 + 1
        # the rejection is about the QUEUE: draining it reopens intake
        eng.run()
        eng.add_request([6], max_new_tokens=2)
        eng.run()

    def test_queue_wait_observed_once_despite_preemption(self, model):
        """A preemption re-admission's arrival-to-now span includes
        on-device decode residency — observing it again would inflate
        the p99 exactly when preemption pressure makes it matter."""
        h = registry().get("serving.queue_wait_seconds")
        n0 = h.count
        eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=4,
                                       block_size=16, temperature=0.0,
                                       preempt_after=4)
        eng.add_request([3, 4, 5], max_new_tokens=24)
        eng.add_request([9, 8, 7], max_new_tokens=24)
        eng.run()
        assert eng.preempt_count >= 1, "pool pressure should preempt"
        assert h.count == n0 + 2              # one sample per REQUEST

    def test_queue_wait_histogram_observes_admissions(self, model):
        h = registry().get("serving.queue_wait_seconds")
        n0 = h.count
        eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=32,
                                       block_size=16, temperature=0.0)
        for _ in range(3):
            eng.add_request([1, 2, 3], max_new_tokens=2)
        eng.run()
        assert h.count >= n0 + 3

    def test_on_finish_retires_results(self, model):
        done = []
        eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=32,
                                       block_size=16, temperature=0.0,
                                       on_finish=done.append)
        rids = [eng.add_request([1, 2, 3, 4], max_new_tokens=3)
                for _ in range(3)]
        while eng.pending or eng.num_active:
            eng.step()
        # every finished Request was handed off and RETIRED — a
        # long-running server's results dict stays empty
        assert sorted(r.rid for r in done) == sorted(rids)
        assert eng.results == {}

    def test_replay_readmission_bypasses_queue_bound(self, model,
                                                     tmp_path):
        """A journal-replay re-admission was already durably acked by a
        previous incarnation: bouncing it off max_queue would turn a
        relaunch into a permanent QueueFull crash loop."""
        e1 = ResilientServingEngine(model, str(tmp_path / "q"), **ENG)
        prompts = _requests(3)
        for p in prompts:
            e1.add_request(p, max_new_tokens=3)
        del e1                    # killed with 3 journaled, none finished
        e2 = ResilientServingEngine(model, str(tmp_path / "q"),
                                    **dict(ENG, max_queue=1))
        assert e2.replayed_requests == 3      # all re-admitted, no bounce
        # NEW traffic still sees the bound while the queue is backed up
        with pytest.raises(QueueFull):
            e2.add_request([1, 2], max_new_tokens=2)
        e2.run()
        assert len(e2.outputs) == 3
        e2.close()

    def test_pop_result_retires_on_poll(self, model):
        eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=32,
                                       block_size=16, temperature=0.0)
        rid = eng.add_request([5, 6, 7], max_new_tokens=2)
        assert eng.pop_result(rid) is None     # not finished yet
        eng.run()
        req = eng.pop_result(rid)
        assert req is not None and req.done
        assert rid not in eng.results
        assert eng.pop_result(rid) is None


# ------------------------------------------------- journal replay (fast)

class TestJournalReplay:
    def test_interrupted_replay_is_byte_identical(self, model, tmp_path):
        """Abandon an engine mid-stream (the single-process image of
        SIGKILL: nothing flushed beyond the journal), relaunch over the
        same directory, and the stochastic outputs must equal an
        uninterrupted run's exactly."""
        prompts = _requests(4)
        ref = _reference(model, tmp_path, prompts)

        e1 = ResilientServingEngine(model, str(tmp_path / "j"),
                                    journal_flush_every=1, **ENG)
        for p in prompts:
            e1.add_request(p, max_new_tokens=6)
        for _ in range(3):
            e1.step()
        partial = {r.rid: len(r.out_tokens)
                   for r in e1.engine.results.values()}
        assert any(v > 0 for v in partial.values())   # killed MID-stream
        del e1                                        # no close, no drain

        e2 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        assert e2.replayed_requests + e2.recovered_finished == 4
        assert e2.run() == ServingAction.COMPLETED
        assert e2.outputs == ref
        e2.close()

    def test_finished_requests_load_from_the_log(self, model, tmp_path):
        prompts = _requests(2)
        e1 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        for p in prompts:
            e1.add_request(p, max_new_tokens=4)
        e1.run()
        ref = dict(e1.outputs)
        del e1
        e2 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        # nothing to regenerate: outputs came straight from the journal
        assert e2.recovered_finished == 2 and e2.replayed_requests == 0
        assert not e2.has_work
        assert e2.outputs == ref
        e2.close()

    def test_admission_is_durable_before_any_step(self, model, tmp_path):
        e1 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        rid = e1.add_request([9, 8, 7], max_new_tokens=3)
        del e1                        # killed before the first step
        e2 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        assert e2.replayed_requests == 1
        e2.run()
        assert len(e2.outputs[rid]) == 3
        e2.close()

    def test_new_traffic_after_recovery_gets_fresh_rids(self, model,
                                                        tmp_path):
        e1 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        r0 = e1.add_request([1, 2, 3], max_new_tokens=3)
        del e1
        e2 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        r1 = e2.add_request([4, 5, 6], max_new_tokens=3)
        assert r1 > r0
        e2.run()
        assert set(e2.outputs) == {r0, r1}
        e2.close()

    def test_replayed_rows_skip_ttft_and_tpot(self, model, tmp_path):
        """A resumed row's t_first is its re-admission time and part of
        its count was emitted by a dead process — observing either
        histogram would corrupt the serving latency record."""
        e1 = ResilientServingEngine(model, str(tmp_path / "j"),
                                    journal_flush_every=1, **ENG)
        e1.add_request(_requests(1)[0], max_new_tokens=6)
        for _ in range(3):
            e1.step()
        assert any(r.out_tokens for r in e1.engine.results.values())
        del e1
        ttft, tpot = (registry().get("serving.ttft_seconds"),
                      registry().get("serving.tpot_seconds"))
        n_ttft, n_tpot = ttft.count, tpot.count
        e2 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        assert e2.replayed_requests == 1
        e2.run()
        assert (ttft.count, tpot.count) == (n_ttft, n_tpot)
        e2.close()

    def test_simultaneous_finishes_flush_one_segment(self, model,
                                                     tmp_path):
        """N rows finishing in one ragged step cost ONE fsynced segment,
        not one commit dance per on_finish callback."""
        e1 = ResilientServingEngine(model, str(tmp_path / "j"),
                                    journal_flush_every=1000, **ENG)
        for p in ([1, 2, 3], [4, 5, 6]):      # lockstep: finish together
            e1.add_request(p, max_new_tokens=3)
        flushes = _counter("serving.resilience.journal_flushes")
        e1.run()
        # prefill + 2 decode steps; only the finish step flushed
        assert _counter("serving.resilience.journal_flushes") == flushes + 1
        assert all(len(t) == 3 for t in e1.outputs.values())
        e1.close()

    def test_model_fingerprint_probed_once_per_engine(self, model,
                                                      tmp_path,
                                                      monkeypatch):
        from paddle_tpu.serving.resilience import warm_cache
        calls = []
        real = warm_cache._model_fingerprint
        monkeypatch.setattr(warm_cache, "_model_fingerprint",
                            lambda m: calls.append(1) or real(m))
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        e1.snapshot()
        e1.snapshot()
        # __init__ probed via its own binding; _meta reuses the memo,
        # so the module-level hook never fires on the snapshot path
        assert calls == []
        assert getattr(e1.engine, "_warm_model_fp", None)
        e1.close()

    def test_journal_refuses_a_different_model(self, model, tmp_path):
        """Replaying against different weights would splice two models'
        tokens into one output — refuse at construction, like the warm
        cache refuses its preload."""
        e1 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        e1.add_request(_requests(1)[0], max_new_tokens=4)
        del e1
        paddle.seed(123)
        other = LlamaForCausalLM(model.config)
        other.eval()
        with pytest.raises(RuntimeError, match="fingerprint mismatch"):
            ResilientServingEngine(other, str(tmp_path / "j"), **ENG)
        # the original model still recovers fine
        e2 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        assert e2.replayed_requests == 1
        e2.close()

    def test_relaunch_flag_cannot_add_an_eos(self, model, tmp_path):
        """eos=None is part of the journaled identity too: a relaunch
        flag ADDING one would truncate replayed outputs below their
        committed watermarks."""
        e1 = ResilientServingEngine(model, str(tmp_path / "j"),
                                    journal_flush_every=1,
                                    **dict(ENG, eos_token_id=None))
        e1.add_request(_requests(1)[0], max_new_tokens=5)
        e1.step()
        del e1
        e2 = ResilientServingEngine(model, str(tmp_path / "j"),
                                    **dict(ENG, eos_token_id=2))
        assert e2.engine.eos is None
        e2.run()
        e2.close()

    def test_run_returns_outputs_despite_on_finish_retirement(self,
                                                              model):
        done = []
        eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=32,
                                       block_size=16, temperature=0.0,
                                       on_finish=done.append)
        rids = [eng.add_request([1, 2, 3, 4], max_new_tokens=3)
                for _ in range(3)]
        results = eng.run()
        assert eng.results == {}              # retired through the hook
        assert sorted(results) == sorted(rids)
        assert all(len(results[r]) == 3 for r in rids)

    def test_fresh_rids_after_finished_only_recovery(self, model,
                                                     tmp_path):
        """Finished rids never pass through add_request on recovery, but
        reusing one would journal a second admit record and clobber the
        durably-acked output on the NEXT relaunch."""
        e1 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        done = [e1.add_request(p, max_new_tokens=3)
                for p in _requests(2)]
        e1.run()
        ref = dict(e1.outputs)
        del e1
        e2 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        assert e2.recovered_finished == 2
        fresh = e2.add_request([4, 2], max_new_tokens=3)
        assert fresh not in done
        e2.run()
        del e2
        # the original outputs survive a THIRD launch untouched
        e3 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        assert all(e3.outputs[r] == ref[r] for r in done)
        assert set(e3.outputs) == set(done) | {fresh}
        e3.close()

    def test_journal_config_overrides_relaunch_kwargs(self, model,
                                                      tmp_path):
        """Byte-identity survives a WRONG relaunch command line: the
        journaled seed/sampling win over the constructor's."""
        prompts = _requests(2)
        ref = _reference(model, tmp_path, prompts, max_new=5)
        e1 = ResilientServingEngine(model, str(tmp_path / "j"),
                                    journal_flush_every=1, **ENG)
        for p in prompts:
            e1.add_request(p, max_new_tokens=5)
        e1.step()
        del e1
        wrong = dict(ENG, temperature=0.1, seed=999)
        e2 = ResilientServingEngine(model, str(tmp_path / "j"), **wrong)
        assert e2.engine.seed == ENG["seed"]
        assert e2.engine.sampling["temperature"] == ENG["temperature"]
        e2.run()
        assert e2.outputs == ref
        e2.close()


# --------------------------------------------------------- drain (fast)

class TestDrain:
    def test_zero_deadline_journals_and_preempts(self, model, tmp_path):
        prompts = _requests(3)
        ref = _reference(model, tmp_path, prompts, max_new=8)
        e1 = ResilientServingEngine(model, str(tmp_path / "d"), **ENG)
        for p in prompts:
            e1.add_request(p, max_new_tokens=8)
        for _ in range(2):
            e1.step()
        d0 = _counter("serving.resilience.drains")
        e1.drain(deadline_s=0.0)
        assert _counter("serving.resilience.drains") == d0 + 1
        md = e1.journal.committed_marker()
        assert md is not None and md["drained"] is True
        assert md["remaining"] > 0            # journal-and-preempt path
        with pytest.raises(RuntimeError, match="drained"):
            e1.add_request([1], max_new_tokens=1)
        e1.close()
        e2 = ResilientServingEngine(model, str(tmp_path / "d"), **ENG)
        assert e2.run() == ServingAction.COMPLETED
        assert e2.outputs == ref
        e2.close()

    def test_mid_decode_drain_replays_from_watermarks(self, model,
                                                      tmp_path):
        """A drain after decoding began: the relaunch resumes every
        request from its committed watermark, regenerates only the tail,
        preloads the drain's prefix snapshot, and matches an
        uninterrupted run."""
        prompts = _requests(3, head_blocks=1, rng_seed=5)
        ref = _reference(model, tmp_path, prompts, max_new=8)
        e1 = ResilientServingEngine(model, str(tmp_path / "d"),
                                    journal_flush_every=1, **ENG)
        for p in prompts:
            e1.add_request(p, max_new_tokens=8)
        for _ in range(20):
            e1.step()
            if sum(len(r.out_tokens)
                   for r in e1.engine.results.values()) >= 3:
                break
        e1.drain(deadline_s=0.0)
        e1.close()
        e2 = ResilientServingEngine(model, str(tmp_path / "d"), **ENG)
        committed = (sum(e2._watermark.values())
                     + sum(len(t) for t in e2.outputs.values()))
        assert e2.replayed_requests > 0 and committed > 0
        assert e2.warm_blocks > 0             # the drain's snapshot
        assert e2.run() == ServingAction.COMPLETED
        assert e2.outputs == ref
        assert sum(len(t) for t in e2.outputs.values()) > committed
        e2.close()

    def test_generous_deadline_finishes_in_flight(self, model, tmp_path):
        e1 = ResilientServingEngine(model, str(tmp_path / "d"), **ENG)
        for p in _requests(2):
            e1.add_request(p, max_new_tokens=3)
        e1.step()
        dt = e1.drain(deadline_s=60.0)
        md = e1.journal.committed_marker()
        assert md["remaining"] == 0           # everything finished
        assert dt < 60.0
        assert len(e1.outputs) == 2
        e1.close()
        # relaunch has nothing to replay: the log holds both outputs
        e2 = ResilientServingEngine(model, str(tmp_path / "d"), **ENG)
        assert not e2.has_work and e2.recovered_finished == 2
        e2.close()

    def test_drained_engine_never_busy_loops_or_steps(self, model,
                                                      tmp_path):
        """A zero-deadline drain can leave queued requests behind:
        run() must report DRAINED, not spin no-op steps forever under
        the committed marker."""
        e1 = ResilientServingEngine(model, str(tmp_path / "d"), **ENG)
        for p in _requests(6):
            e1.add_request(p, max_new_tokens=4)
        e1.step()                         # some admitted, some queued
        e1.drain(deadline_s=0.0)
        assert e1.run() == ServingAction.DRAINED
        with pytest.raises(RuntimeError, match="drained"):
            e1.step()
        e1.close()

    def test_drain_snapshots_even_after_failed_periodic(self, model,
                                                        tmp_path,
                                                        monkeypatch):
        """A failed periodic snapshot at the final step count must not
        talk drain() out of the snapshot it exists to produce."""
        from paddle_tpu.serving.resilience import engine as eng_mod
        e1 = ResilientServingEngine(model, str(tmp_path / "d"),
                                    snapshot_every=1,
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        real = eng_mod.snapshot_prefix_cache
        with monkeypatch.context() as mp:
            mp.setattr(eng_mod, "snapshot_prefix_cache",
                       lambda *a, **k: (_ for _ in ()).throw(
                           OSError("transient")))
            e1.run()                      # every periodic attempt fails
        e1.drain()
        from paddle_tpu.utils.durability import latest_committed
        assert latest_committed(e1.warm_root) is not None
        e1.close()
        assert real is eng_mod.snapshot_prefix_cache

    def test_drain_stops_the_watchdog(self, model, tmp_path):
        """Drain IS the clean exit: its commit+snapshot tail (and the
        journaled-and-preempted survivors left active afterwards) must
        not be misdiagnosed as a step hang."""
        e1 = ResilientServingEngine(model, str(tmp_path / "d"),
                                    step_timeout_s=0.2, **ENG)
        for p in _requests(3):
            e1.add_request(p, max_new_tokens=8)
        e1.step()
        e1.drain(deadline_s=0.0)          # survivors stay journaled+active
        assert e1.has_work                # so the hang scan WOULD trigger
        time.sleep(0.6)
        assert e1.poll() != ServingAction.RESTART
        e1.close()

    def test_sigterm_routes_into_drain(self, model, tmp_path):
        e1 = ResilientServingEngine(model, str(tmp_path / "d"),
                                    install_signal=True, **ENG)
        try:
            for p in _requests(2):
                e1.add_request(p, max_new_tokens=3)
            assert e1.poll() == ServingAction.CONTINUE
            os.kill(os.getpid(), signal.SIGTERM)
            assert e1.poll() == ServingAction.DRAINED
            assert e1.drained
            assert e1.journal.committed_marker() is not None
        finally:
            e1.close()


# ---------------------------------------------------- warm-start (fast)

class TestWarmStart:
    def test_snapshot_preload_hits_and_identical_output(self, model,
                                                        tmp_path):
        prompts = _requests(3, head_blocks=3, rng_seed=3)
        kw = dict(ENG, temperature=0.0)
        e1 = ResilientServingEngine(model, str(tmp_path / "w"), **kw)
        for p in prompts:
            e1.add_request(p, max_new_tokens=4)
        e1.run()
        assert e1.snapshot() is not None
        e1.close()

        hit0 = _counter("serving.prefix_cache.hit_blocks")
        e2 = ResilientServingEngine(model, str(tmp_path / "w"), **kw)
        assert e2.warm_blocks >= 3            # the shared head, at least
        probe = prompts[0][:48] + [1, 2, 3]
        rid = e2.add_request(probe, max_new_tokens=4)
        e2.run()
        assert _counter("serving.prefix_cache.hit_blocks") >= hit0 + 3
        cold = _reference(model, tmp_path, [probe], max_new=4,
                          name="wcold", temperature=0.0)
        assert e2.outputs[rid] == cold[0]     # warm changes work, not bits
        e2.close()

    def test_geometry_mismatch_refuses_preload(self, model, tmp_path):
        prompts = _requests(2, head_blocks=2, rng_seed=3)
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in prompts:
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        e1.snapshot()
        e1.close()
        # a relaunch with a DIFFERENT block size must refuse the bytes
        eng = ContinuousBatchingEngine(model, max_batch=4, num_blocks=64,
                                       block_size=32, temperature=0.0)
        assert load_prefix_cache(eng, str(tmp_path / "w" / "warmcache")) == 0

    def test_kv_dtype_mismatch_refuses_preload_both_ways(self, model,
                                                         tmp_path):
        """An int8 snapshot is meaningless without its scales and a
        float snapshot has none — BOTH directions of storage-regime
        mismatch must refuse the preload, not serve garbage KV."""
        prompts = _requests(2, head_blocks=2, rng_seed=3)
        e1 = ResilientServingEngine(model, str(tmp_path / "wf"),
                                    **dict(ENG, temperature=0.0))
        for p in prompts:
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        e1.snapshot()
        e1.close()
        q = ContinuousBatchingEngine(model, kv_dtype="int8",
                                     **dict(ENG, temperature=0.0))
        assert load_prefix_cache(q, e1.warm_root) == 0

        e2 = ResilientServingEngine(model, str(tmp_path / "wq"),
                                    kv_dtype="int8",
                                    **dict(ENG, temperature=0.0))
        for p in prompts:
            e2.add_request(p, max_new_tokens=3)
        e2.run()
        e2.snapshot()
        e2.close()
        f = ContinuousBatchingEngine(model, **dict(ENG, temperature=0.0))
        assert load_prefix_cache(f, e2.warm_root) == 0
        # matched regimes DO preload (scales ride the snapshot)
        q2 = ContinuousBatchingEngine(model, kv_dtype="int8",
                                      **dict(ENG, temperature=0.0))
        assert load_prefix_cache(q2, e2.warm_root) > 0

    def test_int8_warm_preload_identical_output(self, model, tmp_path):
        """Warm int8 blocks must replay their per-token-slot scales too:
        a warm-started quantized engine attends preloaded blocks through
        the dequant path and must emit the same tokens as a cold one."""
        prompts = _requests(3, head_blocks=3, rng_seed=3)
        kw = dict(ENG, temperature=0.0, kv_dtype="int8")
        e1 = ResilientServingEngine(model, str(tmp_path / "wq8"), **kw)
        for p in prompts:
            e1.add_request(p, max_new_tokens=4)
        e1.run()
        assert e1.snapshot() is not None
        e1.close()

        hit0 = _counter("serving.prefix_cache.hit_blocks")
        e2 = ResilientServingEngine(model, str(tmp_path / "wq8"), **kw)
        assert e2.warm_blocks >= 3
        probe = prompts[0][:48] + [1, 2, 3]
        rid = e2.add_request(probe, max_new_tokens=4)
        e2.run()
        assert _counter("serving.prefix_cache.hit_blocks") >= hit0 + 3
        cold = _reference(model, tmp_path, [probe], max_new=4,
                          name="wq8cold", temperature=0.0,
                          kv_dtype="int8")
        assert e2.outputs[rid] == cold[0]
        e2.close()

    def test_prune_spares_fresh_uncommitted_dirs(self, model, tmp_path):
        """An uncommitted gen dir younger than the grace window may be a
        concurrent incarnation's snapshot mid-write — pruning it under
        the writer would crash a healthy server, not clean up debris."""
        from paddle_tpu.serving.resilience.warm_cache import (_PRUNE_GRACE_S,
                                                              _prune)
        root = str(tmp_path / "warm")
        fresh = os.path.join(root, "gen-00000007-cccccccc")
        stale = os.path.join(root, "gen-00000003-dddddddd")
        os.makedirs(fresh)
        os.makedirs(stale)
        old = time.time() - _PRUNE_GRACE_S - 60
        os.utime(stale, (old, old))
        _prune(root, keep=2)
        assert os.path.isdir(fresh) and not os.path.isdir(stale)

    def test_failed_snapshot_never_kills_the_server(self, model, tmp_path,
                                                    monkeypatch):
        from paddle_tpu.serving.resilience import engine as eng_mod
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        rid = e1.add_request(_requests(1, head_blocks=2, rng_seed=3)[0],
                             max_new_tokens=3)
        monkeypatch.setattr(
            eng_mod, "snapshot_prefix_cache",
            lambda *a, **k: (_ for _ in ()).throw(OSError("disk raced")))
        assert e1.snapshot() is None          # recorded, not raised
        assert e1.run() == ServingAction.COMPLETED
        assert len(e1.outputs[rid]) == 3
        e1.close()

    def test_drain_skips_redundant_final_snapshot(self, model, tmp_path):
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    snapshot_every=1,
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()               # periodic snapshot fired at the last step
        snaps = _counter("serving.resilience.snapshots")
        e1.drain()             # zero drain-loop steps: state is identical
        assert _counter("serving.resilience.snapshots") == snaps
        e1.close()

    def test_weights_mismatch_refuses_preload(self, model, tmp_path):
        """Same architecture, different weights: the snapshot's KV was
        computed by the OLD model, so serving it would be silently
        wrong generations, not an error."""
        prompts = _requests(2, head_blocks=2, rng_seed=3)
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in prompts:
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        e1.snapshot()
        e1.close()
        paddle.seed(99)                   # geometry-identical re-init
        other = LlamaForCausalLM(model.config)
        other.eval()
        eng = ContinuousBatchingEngine(other,
                                       **dict(ENG, temperature=0.0))
        assert load_prefix_cache(eng, e1.warm_root) == 0

    def test_double_preload_leaks_no_blocks(self, model, tmp_path):
        """A digest already tracked hands its freshly-popped block back
        to the free list — register() returning False must not strand
        pool capacity."""
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        e1.snapshot()
        e1.close()
        eng = ContinuousBatchingEngine(model, **dict(ENG, temperature=0.0))
        assert load_prefix_cache(eng, e1.warm_root) > 0
        assert load_prefix_cache(eng, e1.warm_root) == 0   # all duplicates
        assert (len(eng.cache._free) + eng._pc.evictable
                == eng._total_blocks)

    def test_concurrent_incarnations_get_distinct_gen_dirs(
            self, model, tmp_path, monkeypatch):
        """Two incarnations resuming from the same last_generation()
        must not interleave writes inside ONE gen dir (the journal's
        fencing rationale applies to snapshots too)."""
        from paddle_tpu.serving.resilience import warm_cache
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        monkeypatch.setattr(warm_cache, "_UID", "aaaaaaaa")
        p1 = snapshot_prefix_cache(e1.engine, e1.warm_root, 1)
        monkeypatch.setattr(warm_cache, "_UID", "bbbbbbbb")
        p2 = snapshot_prefix_cache(e1.engine, e1.warm_root, 1)
        assert p1 != p2 and os.path.isdir(p1) and os.path.isdir(p2)
        from paddle_tpu.serving.resilience.warm_cache import last_generation
        assert last_generation(e1.warm_root) == 1
        e1.close()

    def test_pop_output_retires_delivered_results(self, model, tmp_path):
        e1 = ResilientServingEngine(model, str(tmp_path / "j"), **ENG)
        rid = e1.add_request([5, 3, 1], max_new_tokens=3)
        e1.run()
        toks = e1.pop_output(rid)
        assert toks is not None and len(toks) == 3
        assert rid not in e1.outputs
        assert e1.pop_output(rid) is None
        e1.close()

    def test_snapshot_generations_commit_and_prune(self, model, tmp_path):
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        for _ in range(4):
            assert e1.snapshot() is not None
        gens = sorted(os.listdir(e1.warm_root))
        assert len(gens) == 2                 # keep=2 retention
        for g in gens:
            assert read_committed_marker(
                os.path.join(e1.warm_root, g)) is not None
        e1.close()

    def test_idle_steps_do_not_refire_snapshots(self, model, tmp_path):
        """engine.steps freezes while idle: a parked multiple of
        snapshot_every must not re-run the full snapshot on every idle
        serve-loop tick."""
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    snapshot_every=1,
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        snaps = _counter("serving.resilience.snapshots")
        for _ in range(3):
            e1.step()                     # idle ticks
        assert _counter("serving.resilience.snapshots") == snaps
        e1.close()

    def test_relaunch_continues_generation_sequence(self, model,
                                                    tmp_path):
        """A relaunched server snapshots PAST the generations already on
        disk — rewriting a COMMITTED gen-N in place would tear it under
        its live marker."""
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        first = e1.snapshot()
        e1.close()
        e2 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        second = e2.snapshot()
        assert second is not None and second != first
        assert sorted(os.listdir(e2.warm_root)) == [
            os.path.basename(first), os.path.basename(second)]
        e2.close()

    def test_payload_meta_disagreement_refuses_preload(self, model,
                                                       tmp_path):
        """meta.json listing more digests than blocks.npz has rows is
        corruption the commit protocol can't rule out (two files) — the
        preload must refuse, not crash mid-init."""
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        gen = e1.snapshot()
        e1.close()
        mpath = os.path.join(gen, "meta.json")
        with open(mpath, encoding="utf-8") as f:
            meta = json.load(f)
        meta["digests"].append("ab" * 32)     # one digest with no bytes
        with open(mpath, "w", encoding="utf-8") as f:
            json.dump(meta, f)
        eng = ContinuousBatchingEngine(model, **dict(ENG, temperature=0.0))
        assert load_prefix_cache(eng, e1.warm_root) == 0

    def test_preload_never_steals_admission_headroom(self, model,
                                                     tmp_path):
        """Warm blocks are EVICTABLE: free + evictable headroom after a
        preload equals the free headroom before it."""
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        e1.snapshot()
        e1.close()
        e2 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        assert e2.warm_blocks > 0
        eng = e2.engine
        assert (len(eng.cache._free) + eng._pc.evictable
                == eng._total_blocks)
        e2.close()

    def test_preload_into_a_full_pool_loads_nothing(self, model,
                                                     tmp_path):
        """A pool with no more free blocks than rows gives a snapshot
        none of them: admissions come first."""
        e1 = ResilientServingEngine(model, str(tmp_path / "w"),
                                    **dict(ENG, temperature=0.0))
        for p in _requests(2, head_blocks=2, rng_seed=3):
            e1.add_request(p, max_new_tokens=3)
        e1.run()
        assert e1.snapshot() is not None
        e1.close()
        eng = ContinuousBatchingEngine(
            model, **dict(ENG, temperature=0.0,
                          num_blocks=ENG["max_batch"] + 1))
        free = list(eng.cache._free)
        assert len(free) <= eng.max_batch
        assert load_prefix_cache(eng, e1.warm_root) == 0
        assert eng.cache._free == free
        assert registry().get("serving.resilience.warm_blocks").value == 0


# ------------------------------------------------ step-hang watchdog (fast)

class TestStepHangWatchdog:
    def test_hang_flags_restart_and_journal_recovers(self, model,
                                                     tmp_path):
        h0 = _counter("serving.resilience.step_hangs")
        e1 = ResilientServingEngine(model, str(tmp_path / "h"),
                                    step_timeout_s=0.3, **ENG)
        rid = e1.add_request([3, 1, 4, 1, 5], max_new_tokens=4)
        e1.step()                             # some progress journals
        deadline = time.time() + 5.0
        while (e1.poll() != ServingAction.RESTART
               and time.time() < deadline):
            time.sleep(0.05)                  # the "wedged" step
        assert e1.poll() == ServingAction.RESTART
        assert _counter("serving.resilience.step_hangs") == h0 + 1
        e1.close()
        # the same journal→restart recovery as a kill
        e2 = ResilientServingEngine(model, str(tmp_path / "h"), **ENG)
        assert e2.replayed_requests == 1
        e2.run()
        assert len(e2.outputs[rid]) == 4
        e2.close()

    def test_first_step_gets_the_compile_grace(self, model, tmp_path):
        """An incarnation's first step pays the ragged XLA compile: the
        steady-state timeout must not flag it (with hang_exit that would
        be a permanent kill→relaunch→same-compile crash loop)."""
        e1 = ResilientServingEngine(model, str(tmp_path / "h"),
                                    step_timeout_s=0.2,
                                    first_step_timeout_s=60.0, **ENG)
        e1.add_request([1, 2, 3], max_new_tokens=3)
        time.sleep(0.6)                   # stalled BEFORE any step
        assert e1.poll() == ServingAction.CONTINUE
        e1.step()                         # first step done: steady state
        deadline = time.time() + 5.0
        while (e1.poll() != ServingAction.RESTART
               and time.time() < deadline):
            time.sleep(0.05)
        assert e1.poll() == ServingAction.RESTART
        e1.close()

    def test_hang_commits_incident_bundle(self, model, tmp_path,
                                          monkeypatch):
        """The watchdog's RESTART transition is a terminal event: it
        must leave ONE committed incident bundle under the engine's own
        <root>/incidents attributing the wedge (PR18 tentpole)."""
        saved = paddle.get_flags(["FLAGS_incident_rate_limit_s"])
        paddle.set_flags({"FLAGS_incident_rate_limit_s": 0.0})
        try:
            root = str(tmp_path / "h")
            e1 = ResilientServingEngine(model, root,
                                        step_timeout_s=0.3, **ENG)
            e1.add_request([3, 1, 4], max_new_tokens=4)
            e1.step()
            deadline = time.time() + 5.0
            while (e1.poll() != ServingAction.RESTART
                   and time.time() < deadline):
                time.sleep(0.05)
            assert e1.poll() == ServingAction.RESTART
            e1.close()
            inc_dir = os.path.join(root, "incidents")
            bundles = [d for d in os.listdir(inc_dir)
                       if d.startswith("incident-")]
            assert len(bundles) == 1
            bundle = os.path.join(inc_dir, bundles[0])
            md = read_committed_marker(bundle)
            assert md is not None and md["kind"] == "serving.hang"
            with open(os.path.join(bundle, "incident.json")) as f:
                hdr = json.load(f)
            assert hdr["kind"] == "serving.hang"
            assert hdr["attrs"]["stalled_s"] >= 0.3
            assert hdr["attrs"]["hang_exit"] is False
            assert set(hdr["stack_classes"]) <= set(
                paddle.observability.STACK_CLASSES)
            with open(os.path.join(bundle, "journal.json")) as f:
                jr = json.load(f)
            assert "watermarks" in jr and "pending_records" in jr
            for part in ("stacks.json", "stacks.txt", "metrics.json",
                         "flight.txt"):
                assert os.path.exists(os.path.join(bundle, part)), part
        finally:
            paddle.set_flags(saved)

    def test_no_hang_while_stepping_or_idle(self, model, tmp_path):
        e1 = ResilientServingEngine(model, str(tmp_path / "h"),
                                    step_timeout_s=0.5, **ENG)
        assert e1.poll() == ServingAction.CONTINUE
        time.sleep(0.8)                       # idle (no work) ≠ hung
        assert e1.poll() == ServingAction.CONTINUE
        e1.add_request([2, 7, 1], max_new_tokens=3)
        e1.run()
        assert e1.poll() == ServingAction.CONTINUE
        e1.close()


# ------------------------------------------------------- chaos (slow)

def _assert_journal_loadable(root):
    st = RequestJournal(os.path.join(root, "journal")).load()
    for rec in st.requests.values():
        assert len(rec.tokens) <= rec.max_new_tokens
    return st


@pytest.mark.slow
@pytest.mark.heavy
class TestServingChaos:
    def _spawn(self, tmp_path, attempt, root="serve", sleep="0.08",
               deadline="20", add=None, extra_env=None):
        env = dict(os.environ,
                   SERVE_STEP_SLEEP=sleep,
                   SERVE_DRAIN_DEADLINE=deadline,
                   PYTHONPATH=os.path.dirname(os.path.dirname(_WORKER)))
        if add is not None:
            env["SERVE_ADD"] = add
        if extra_env:
            env.update(extra_env)
        (tmp_path / "out").mkdir(exist_ok=True)
        return subprocess.Popen(
            [sys.executable, _WORKER, str(tmp_path / "out"),
             str(tmp_path / root), str(attempt)], env=env)

    def _wait_generated(self, tmp_path, attempt, n, timeout=120,
                        proc=None):
        """Until the worker has generated >= n tokens this attempt (or,
        with ``proc``, until it exits first — a relaunch may have
        nothing left to do)."""
        path = tmp_path / "out" / f"progress_a{attempt}.jsonl"
        deadline = time.time() + timeout
        while time.time() < deadline:
            if path.exists():
                lines = path.read_text().splitlines()
                if lines and json.loads(lines[-1])["generated"] >= n:
                    return True
            if proc is not None and proc.poll() is not None:
                return False
            time.sleep(0.1)
        raise AssertionError(f"attempt {attempt} never generated {n}")

    def _result(self, tmp_path, attempt):
        with open(tmp_path / "out" / f"result_a{attempt}.json") as f:
            return json.load(f)

    def _reference_outputs(self, tmp_path, extra_env=None):
        p = self._spawn(tmp_path, attempt=9, root="refserve", sleep="0.0",
                        add="1", extra_env=extra_env)
        assert p.wait(timeout=240) == 0
        return self._result(tmp_path, 9)["outputs"]

    def test_sigkill_midstream_replays_byte_identically(self, tmp_path):
        """SIGKILL mid-stream at temperature 0.85, relaunch: every
        unfinished journaled request's FULL output must equal the
        uninterrupted run's, token for token."""
        ref = self._reference_outputs(tmp_path)
        p = self._spawn(tmp_path, attempt=0)
        try:
            self._wait_generated(tmp_path, 0, 12)
            os.kill(p.pid, signal.SIGKILL)
            assert p.wait(timeout=60) == -signal.SIGKILL
        finally:
            if p.poll() is None:
                p.kill()
        st = _assert_journal_loadable(str(tmp_path / "serve"))
        assert st.unfinished, "kill landed after completion — tune sleep"
        p = self._spawn(tmp_path, attempt=1)
        assert p.wait(timeout=240) == 0
        res = self._result(tmp_path, 1)
        assert res["replayed"] + res["recovered_finished"] == len(ref)
        assert res["replayed"] >= 1
        assert res["outputs"] == ref

    def test_sigterm_drains_committed_then_recovers(self, tmp_path):
        """SIGTERM: the worker drains within its deadline and exits 64
        with a COMMITTED journal + committed warm-cache snapshot; the
        relaunch completes the preempted requests byte-identically."""
        ref = self._reference_outputs(tmp_path)
        p = self._spawn(tmp_path, attempt=0, deadline="3")
        try:
            self._wait_generated(tmp_path, 0, 8)
            t0 = time.time()
            os.kill(p.pid, signal.SIGTERM)
            assert p.wait(timeout=60) == 64
            assert time.time() - t0 < 30      # deadline + model-step slack
        finally:
            if p.poll() is None:
                p.kill()
        root = tmp_path / "serve"
        md = read_committed_marker(str(root / "journal"))
        assert md is not None and md["drained"] is True
        gens = [g for g in os.listdir(root / "warmcache")
                if read_committed_marker(str(root / "warmcache" / g))]
        assert gens, "drain must leave a committed warm-cache snapshot"
        p = self._spawn(tmp_path, attempt=1)
        assert p.wait(timeout=240) == 0
        res = self._result(tmp_path, 1)
        assert res["warm_blocks"] > 0         # relaunch started warm
        assert res["outputs"] == ref

    def test_sigkill_with_spec_and_int8_replays_identically(self,
                                                            tmp_path):
        """The ISSUE 20 regime ride: int8 quantized KV pool + K=4
        speculative verify, SIGKILL mid-stream, relaunch — byte-identical
        replay must survive accepted/rejected drafts and requantized KV
        (the reference runs the SAME flags: int8 shifts logits slightly,
        so only matched regimes compare token-for-token)."""
        fl = {"FLAGS_kv_cache_dtype": "int8", "FLAGS_speculative_k": "4"}
        ref = self._reference_outputs(tmp_path, extra_env=fl)
        p = self._spawn(tmp_path, attempt=0, extra_env=fl)
        try:
            self._wait_generated(tmp_path, 0, 12)
            os.kill(p.pid, signal.SIGKILL)
            assert p.wait(timeout=60) == -signal.SIGKILL
        finally:
            if p.poll() is None:
                p.kill()
        st = _assert_journal_loadable(str(tmp_path / "serve"))
        assert st.unfinished, "kill landed after completion — tune sleep"
        p = self._spawn(tmp_path, attempt=1, extra_env=fl)
        assert p.wait(timeout=240) == 0
        res = self._result(tmp_path, 1)
        assert res["replayed"] >= 1
        assert res["outputs"] == ref

    def test_no_torn_journal_kill_sweep(self, tmp_path):
        """SIGKILL at arbitrary points: after EVERY kill the journal
        must reduce cleanly (whole segments or nothing), and the final
        relaunch completes byte-identically."""
        ref = self._reference_outputs(tmp_path)
        rng = np.random.RandomState(11)
        for attempt in range(3):
            p = self._spawn(tmp_path, attempt=attempt, sleep="0.05")
            try:
                alive = self._wait_generated(tmp_path, attempt, 2,
                                             timeout=120, proc=p)
                if alive:
                    time.sleep(float(rng.uniform(0.0, 1.0)))
                    if p.poll() is None:
                        os.kill(p.pid, signal.SIGKILL)
                p.wait(timeout=60)
            finally:
                if p.poll() is None:
                    p.kill()
            _assert_journal_loadable(str(tmp_path / "serve"))
        p = self._spawn(tmp_path, attempt=5)
        assert p.wait(timeout=240) == 0
        assert self._result(tmp_path, 5)["outputs"] == ref


_HANG_EXIT_CHILD = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving.resilience import ResilientServingEngine
paddle.seed(0)
cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=1, num_attention_heads=2,
                  num_key_value_heads=2, max_position_embeddings=128)
m = LlamaForCausalLM(cfg)
m.eval()
eng = ResilientServingEngine(m, sys.argv[1], step_timeout_s=0.3,
                             hang_exit=True, max_batch=2, num_blocks=32,
                             block_size=8, temperature=0.0)
eng.add_request([1, 2, 3], max_new_tokens=8)
eng.step()                   # steady state: the watchdog now polices
print("STEPPED", flush=True)
time.sleep(120)              # the wedge — only os._exit(75) ends this
sys.exit(99)                 # unreachable if the watchdog fires
"""


@pytest.mark.slow
@pytest.mark.heavy
class TestHangExitChaos:
    """Satellite (PR18): ``hang_exit`` previously destroyed all
    evidence — ``os._exit(75)`` from the scan thread left NOTHING
    saying why the process died. The watchdog must now bundle-then-die:
    one committed incident under the engine's root survives the exit
    (recorder on), or the classified stacks land on stderr (recorder
    off). Either way the supervisor's exit code 75 has an attribution
    artifact next to it."""

    def _run_child(self, tmp_path, extra_env=None):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
        env.update(extra_env or {})
        return subprocess.run(
            [sys.executable, "-c", _HANG_EXIT_CHILD,
             str(tmp_path / "serve")],
            env=env, capture_output=True, text=True, timeout=240)

    def test_hang_exit_commits_bundle_then_dies_75(self, tmp_path):
        out = self._run_child(tmp_path)
        assert out.returncode == 75, (out.returncode, out.stderr[-2000:])
        assert "STEPPED" in out.stdout
        inc_dir = tmp_path / "serve" / "incidents"
        bundles = [d for d in os.listdir(inc_dir)
                   if d.startswith("incident-")]
        assert len(bundles) == 1, bundles   # exactly ONE, despite _exit
        bundle = inc_dir / bundles[0]
        md = read_committed_marker(str(bundle))
        assert md is not None and md["kind"] == "serving.hang"
        with open(bundle / "incident.json") as f:
            hdr = json.load(f)
        assert hdr["attrs"]["hang_exit"] is True
        assert hdr["attrs"]["stalled_s"] >= 0.3
        # the wedged main thread is attributed, not just listed: the
        # child parks in time.sleep, so its class is a known bucket
        with open(bundle / "stacks.json") as f:
            stacks = json.load(f)
        assert set(stacks["by_class"]) <= set(
            paddle.observability.STACK_CLASSES)
        main_th = [s for s in stacks["stacks"]
                   if s["name"] == "MainThread"]
        assert main_th and main_th[0]["frames"]
        with open(bundle / "journal.json") as f:
            jr = json.load(f)
        assert "watermarks" in jr
        for part in ("stacks.txt", "metrics.json", "flight.txt"):
            assert (bundle / part).exists(), part

    def test_hang_exit_recorder_off_stderr_fallback(self, tmp_path):
        out = self._run_child(
            tmp_path, {"FLAGS_incident_recorder": "False"})
        assert out.returncode == 75, (out.returncode, out.stderr[-2000:])
        assert not (tmp_path / "serve" / "incidents").exists() or not \
            os.listdir(tmp_path / "serve" / "incidents")
        assert "kind=serving.hang" in out.stderr
        assert "threads:" in out.stderr      # classified stacks dumped


pytestmark = pytest.mark.smoke
