"""Headline-bench robustness (VERDICT r4 Missing#1 / Next#1+#7).

The flagship MFU metric must never read 0.0 because one geometry OOMed:
bench_llama_headline walks a pre-registered fallback ladder on
RESOURCE_EXHAUSTED, and _run_isolated promotes the best companion
geometry if every headline rung fails. Reference stance: benchmark
robustness as CI infrastructure (tools/ci_op_benchmark.sh,
check_op_benchmark_result.py).
"""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench


class _FakeOOM(RuntimeError):
    pass


class TestHeadlineLadder:
    def test_pinned_geometry_is_preregistered(self):
        # rung 0 is the frozen r5 headline: stated in code before any
        # measurement, remat on (selective), NOT the r4 sweep argmax
        r0 = bench._HEADLINE_LADDER[0]
        assert r0["rung"] == 0
        assert r0["recompute"] == "selective"
        # ladder strictly loosens memory pressure going down
        assert [r["rung"] for r in bench._HEADLINE_LADDER] == [0, 1, 2, 3, 4]

    def test_explicit_env_geometry_bypasses_ladder(self, monkeypatch):
        monkeypatch.setenv("PTPU_BENCH_BATCH", "8")
        monkeypatch.setattr(bench, "bench_llama",
                            lambda on_tpu, dev: {"mfu": 0.2})
        r = bench.bench_llama_headline(True, None)
        assert "rung" not in r  # user sweep geometry ran verbatim

    def test_ladder_descends_on_oom(self, monkeypatch):
        for k in ("PTPU_BENCH_BATCH", "PTPU_BENCH_LAYERS",
                  "PTPU_RECOMPUTE"):
            monkeypatch.delenv(k, raising=False)
        calls = []

        def fake_llama(on_tpu, dev):
            calls.append((os.environ["PTPU_BENCH_BATCH"],
                          os.environ["PTPU_BENCH_LAYERS"],
                          os.environ["PTPU_RECOMPUTE"]))
            if len(calls) < 3:
                raise _FakeOOM("RESOURCE_EXHAUSTED: Out of memory "
                               "allocating 123 bytes")
            return {"mfu": 0.5, "batch": 2, "seq": 2048}

        monkeypatch.setattr(bench, "bench_llama", fake_llama)
        r = bench.bench_llama_headline(True, None)
        assert r["rung"] == 2
        assert r["headline_geometry"] == "pinned"
        assert calls == [("3", "6", "selective"), ("3", "6", "1"),
                         ("2", "6", "1")]

    def test_non_oom_error_propagates(self, monkeypatch):
        def fake_llama(on_tpu, dev):
            raise ValueError("a real bug, not memory")

        monkeypatch.setattr(bench, "bench_llama", fake_llama)
        with pytest.raises(ValueError):
            bench.bench_llama_headline(True, None)

    def test_env_pin_zero_bypasses_ladder(self, monkeypatch):
        monkeypatch.setenv("PTPU_BENCH_PINNED", "0")
        monkeypatch.setattr(bench, "bench_llama",
                            lambda on_tpu, dev: {"mfu": 0.1})
        r = bench.bench_llama_headline(True, None)
        assert "rung" not in r  # explicit env geometry ran verbatim


class TestHeadlineRescue:
    def test_zero_headline_promotes_companion(self):
        cfgs = [
            {"metric": "llama_pretrain_mfu_1chip_large", "value": 0.499,
             "detail": {"batch": 2}},
            {"metric": "llama_pretrain_mfu_1chip_seq8k", "value": 0.557,
             "detail": {"batch": 1}},
            {"metric": "bert_base_squad_step_ms", "value": 30.0},
        ]
        h = bench._rescue_headline({"value": 0.0, "detail": {}}, cfgs)
        assert h["value"] == 0.557
        assert h["detail"]["headline_fallback"] == (
            "llama_pretrain_mfu_1chip_seq8k")

    def test_missing_headline_promotes_companion(self):
        cfgs = [{"metric": "llama_pretrain_mfu_1chip_large", "value": 0.4}]
        h = bench._rescue_headline(None, cfgs)
        assert h["value"] == 0.4

    def test_good_headline_untouched(self):
        h0 = {"value": 0.62, "detail": {"rung": 0}}
        assert bench._rescue_headline(h0, []) is h0

    def test_all_failed_stays_zero(self):
        h = bench._rescue_headline(None, [])
        assert h["value"] == 0.0


class TestCompactTail:
    def test_compact_line_fits_tail_window(self, monkeypatch, capsys):
        # simulate the isolated merge with a representative config count
        # and assert the LAST printed line (the driver's record) is short
        fake = {"detail": {"configs": [
            {"metric": f"m{i}", "value": 1.234, "unit": "x",
             "vs_baseline": 1.0,
             "detail": {"blah": "y" * 120}} for i in range(16)]}}

        def fake_run(cmd, capture_output, text, env):
            class R:
                stdout = json.dumps({**fake, "value": 0.62,
                                     "metric": "llama_pretrain_mfu_1chip",
                                     "unit": "mfu_fraction",
                                     "vs_baseline": 1.55})
                stderr = ""
            return R()

        import subprocess
        monkeypatch.setattr(subprocess, "run", fake_run)
        monkeypatch.setattr(bench.time, "sleep", lambda s: None)
        bench._run_isolated(["llama", "bert"])
        lines = capsys.readouterr().out.strip().splitlines()
        assert len(lines) == 2
        last = json.loads(lines[-1])
        assert last["metric"] == "llama_pretrain_mfu_1chip"
        assert last["value"] == 0.62
        assert len(lines[-1]) < 2000  # whole record survives the tail
        # detail stripped to metric/value/ratio triples
        assert all(set(c) == {"metric", "value", "vs_baseline"}
                   for c in last["detail"]["configs"])


class TestTpAttentionMicro:
    def test_micro_runs_and_reports(self):
        """bench.py tp_attention smoke (ISSUE 4): the shard_map'd Pallas
        flash vs the GSPMD composite under a tp>=2 mesh must produce a
        well-formed entry on the forced multi-device CPU mesh."""
        import jax
        if jax.device_count() < 2:
            pytest.skip("needs the forced multi-device CPU mesh")
        r = bench.bench_tp_attention(False)
        assert r is not None
        assert r["metric"] == "tp_attention_us"
        assert r["unit"] == "us/call"
        assert r["value"] > 0.0
        assert r["vs_baseline"] > 0.0
        d = r["detail"]
        assert "tp" in d["shape"]
        assert d["xla_composite_us"] > 0.0


class TestServingRegimesMicro:
    def test_matrix_runs_and_meets_gates(self):
        """bench.py serving_regimes smoke (ISSUE 20 acceptance): the
        kv_dtype x spec matrix on a decode-heavy stream. The bench
        itself asserts byte-identical spec-on/spec-off outputs and the
        deterministic capacity facts (bytes/token ratio, blocks per
        byte budget); this smoke re-pins those from the artifact and
        drives the >=1.3x spec-on wall-clock gate with retries to
        absorb a busy host."""
        import gc
        for _attempt in range(5):
            gc.collect()                       # see TestServingFleetMicro
            r = bench.bench_serving_regimes(False, quick=True)
            d = r["detail"]
            if (d["spec_speedup_bf16"] >= 1.3
                    and d["spec_speedup_int8"] >= 1.3):
                break
        assert r["metric"] == "serving_spec_decode_speedup"
        assert r["unit"] == "ratio"
        # int8 pool halves the decode bandwidth denominator (gauge)
        assert d["kv_bytes_ratio"] <= 0.55, d
        assert (d["kv_bytes_per_token_int8"]
                < d["kv_bytes_per_token_bf16"])
        blocks = d["pool_blocks_per_64mb"]
        assert blocks["int8"] >= 1.8 * blocks["bf16"], blocks
        # spec-on finishes in fewer steps at both dtypes — a schedule
        # fact, independent of host load
        assert d["steps_bf16_spec6"] < d["steps_bf16_spec0"], d
        assert d["steps_int8_spec6"] < d["steps_int8_spec0"], d
        # the decode-heavy wall-clock gate, retried above
        assert d["spec_speedup_bf16"] >= 1.3, r
        assert d["spec_speedup_int8"] >= 1.3, r


class TestServingRecoveryMicro:
    def test_micro_runs_and_warm_beats_cold(self):
        """bench.py serving_recovery smoke (ISSUE 9 acceptance): the
        drain→relaunch round trip must produce a well-formed artifact —
        drain + recovery wall clock, replay throughput over a journal
        with real committed watermarks, and warm TTFT p50 STRICTLY
        below cold (the prefix-cache snapshot's whole purpose). One
        retry absorbs a busy host."""
        r = bench.bench_serving_recovery(False, quick=True)
        if r["value"] <= 1.0:      # timing gate: warm vs cold is wall
            r = bench.bench_serving_recovery(False, quick=True)  # clock
        assert r["metric"] == "serving_recovery_warm_ttft_speedup"
        d = r["detail"]
        assert d["drain_s"] > 0.0
        assert d["recover_s"] > 0.0
        assert d["replayed_requests"] > 0
        assert d["replay_committed_tokens"] > 0   # watermark replay ran
        assert d["replay_regenerated_tokens"] > 0
        assert d["replay_tok_per_sec"] > 0.0
        assert d["warm_blocks_preloaded"] > 0
        assert d["ttft_warm_p50_ms"] > 0.0
        # the acceptance gate: warm strictly lower than cold
        assert r["value"] > 1.0, r


class TestServingFleetMicro:
    def test_micro_runs_and_meets_gate(self):
        """bench.py serving_fleet smoke (ISSUE 12 acceptance): the
        two-replica fleet round trip must produce a well-formed
        artifact — base-rate goodput, overload sheds with a retry-after
        hint, a rolling drain, zero dropped requests, and every
        delivered stream byte-identical to the single-engine reference.
        Goodput and the tracing tax are wall-clock gates: retries
        absorb a busy host."""
        import gc
        for _attempt in range(5):                         # timing gates
            # deep into a serial full-suite run the heap holds millions of
            # live objects and a cyclic-GC pass landing inside one side of
            # a paired on/off round skews the overhead subtraction; start
            # each attempt collected (same hygiene as the dispatch gate)
            gc.collect()
            r = bench.bench_serving_fleet(False, quick=True)
            d = r["detail"]
            if not (r["value"] < 1.0 or d["overload_sheds"] == 0
                    or d["tracing_overhead_pct"] >= 3.0
                    or d["scrape_overhead_pct"] >= 3.0
                    or d["perf_overhead_pct"] >= 3.0
                    or d["incident_overhead_pct"] >= d["incident_gate_pct"]
                    or d["incident_disabled_probe_ns"] >= 1000.0
                    or d["cache_compile_ratio"] < 2.0
                    or d["cache_warm_ready_s"] >= d["cache_cold_ready_s"]):
                break
        assert r["metric"] == "serving_fleet_goodput"
        assert d["replicas"] == 2
        assert d["base_delivered"] == d["base_offered"]
        assert d["base_ttft_p50_ms"] > 0.0
        # shedding engaged under the 2x burst, with a usable hint,
        # and the admitted tail stayed bounded (not an SLO collapse)
        assert d["overload_sheds"] > 0
        assert (d["overload_admitted"] + d["overload_sheds"]
                == d["overload_offered"])
        assert d["overload_ttft_p99_ms"] is not None
        assert d["overload_ttft_p99_ms"] < d["slo_ttft_s"] * 1e3
        # the exactly-once invariants are hard gates, not timing
        assert d["dropped_requests"] == 0
        assert d["byte_identical"] is True
        # ISSUE 13 gate: always-on tracing must cost <3% of fleet
        # tokens/s (paired on/off rounds on the same warm fleet)
        assert d["tracing_on_tok_s"] > 0.0
        assert d["tracing_off_tok_s"] > 0.0
        assert d["tracing_overhead_pct"] < d["tracing_gate_pct"], d
        # ISSUE 14 gate: a 1 Hz ops scraper during a load round must
        # cost <3% of the round's CPU, and the scrapes themselves
        # must have been served (latency tail recorded)
        assert d["scrape_count"] >= 1
        assert d["scrape_latency_p99_ms"] > 0.0
        assert d["scrape_overhead_pct"] < d["scrape_gate_pct"], d
        # ISSUE 17 gate: the executable ledger's sampling tax during a
        # load round must compose to <3% of round CPU, and the recorded
        # /perfz rows must carry the serving step AND a captured train
        # step with cost-model fields
        assert d["perf_calls_per_round"] > 0
        assert d["perf_samples_per_round"] > 0
        assert d["perf_overhead_pct"] < d["perf_gate_pct"], d
        # PR18 gate: one worst-case incident bundle per load round must
        # compose to <1% of round CPU, and the disabled trigger probe
        # must stay in one-flag-read territory (sub-microsecond)
        assert d["incident_bundle_cost_ms"] > 0.0
        assert d["incident_disabled_probe_ns"] < 1000.0, d
        assert d["incident_overhead_pct"] < d["incident_gate_pct"], d
        # ISSUE 19 gates: the warm relaunch must load every dispatcher
        # executable from the persistent store (hard invariants), and
        # the compile-seconds ratio is a wall-clock gate (the measured
        # ratio is ~5x; >=2x here absorbs a busy host via the retry)
        assert d["cache_hits"] > 0 and d["cache_entries"] > 0
        assert d["cache_warm_compiles"] < d["cache_cold_compiles"]
        assert d["cache_second_replica_compiles"] <= 2, d
        assert d["cache_byte_identical"] is True
        assert d["cache_compile_ratio"] >= 2.0, d
        kinds = {row["kind"] for row in d["perfz_top"]}
        assert "serving" in kinds and "step" in kinds, d["perfz_top"]
        assert any(row["flops"] for row in d["perfz_top"])
        assert any(row["hbm_bytes"] for row in d["perfz_top"])
        # the endpoint the micro started must be gone afterwards
        from paddle_tpu.observability import exporter as telemetry
        assert telemetry.port() is None
        # the flags the micro toggles must be restored afterwards
        import paddle_tpu as paddle
        got = paddle.get_flags(["FLAGS_tracing", "FLAGS_perf_attribution"])
        assert got["FLAGS_tracing"] is True
        assert got["FLAGS_perf_attribution"] is False
        assert r["value"] == 1.0, r


class TestStepCaptureMicro:
    def test_micro_runs_and_reports(self):
        """bench.py step_capture smoke (ISSUE 5): captured vs eager
        fwd+bwd+opt on a dispatch-bound model must produce a well-formed
        entry on CPU, with the capture actually engaging."""
        r = bench.bench_step_capture(False)
        assert r["metric"] == "step_capture_step_us"
        assert r["unit"] == "us/step"
        assert r["value"] > 0.0
        d = r["detail"]
        assert d["mlp_eager_us_per_step"] > 0.0
        assert d["bert_tiny_captured_ms_per_step"] > 0.0
        assert d["counters"]["captures"] >= 2    # mlp + hapi bert both
        # the flag the micro toggles must be restored afterwards
        import paddle_tpu as paddle
        got = paddle.get_flags(["FLAGS_step_capture"])
        assert got["FLAGS_step_capture"] is True


class TestMultiStepMicro:
    def test_micro_runs_and_meets_gate(self):
        """bench.py multi_step smoke (ISSUE 15 acceptance): a K=16
        lax.scan block must beat single-step capture by >=1.3x per step
        on the dispatch-bound MLP micro, with ONE executable serving
        every timed K-block. The speedup is a wall-clock gate: one
        retry absorbs a busy host."""
        r = bench.bench_multi_step(False)
        if r["value"] < 1.3:        # timing gate: wall clock on a
            r = bench.bench_multi_step(False)   # shared CI host
        assert r["metric"] == "multi_step_speedup_k16"
        assert r["unit"] == "x_vs_single_step_capture"
        d = r["detail"]
        assert d["gate_model"] == "mlp"         # CPU run
        for k in ("k1", "k4", "k16"):
            assert d["mlp_us_per_step"][k] > 0.0
            assert d["bert_tiny_us_per_step"][k] > 0.0
        # ONE executable per K-block: at most one capture per
        # (model, K) pair — 2 models x K in {1,4,16} — while the timed
        # loops replayed blocks far more often than that
        assert 0 < d["executables_built"] <= 6
        assert d["block_replays"] > d["executables_built"]
        assert d["counters"]["fallbacks"] == 0
        # the acceptance gate itself (>=1.3x at K=16)
        assert r["value"] >= 1.3, r
        assert r["vs_baseline"] >= 1.0
        # the flag the micro toggles must be restored afterwards
        import paddle_tpu as paddle
        got = paddle.get_flags(["FLAGS_step_capture"])
        assert got["FLAGS_step_capture"] is True


class TestCheckpointOverlapMicro:
    def test_micro_runs_and_meets_gate(self):
        """bench.py checkpoint_overlap smoke (ISSUE 7 acceptance): async
        snapshot saves overlapped with captured steps must cost <20% of
        a blocking save_state_dict in ADDED step time, and the entry
        must be well-formed for the bench artifact."""
        r = bench.bench_checkpoint_overlap(False)
        if r["value"] >= 20.0:    # timing gate: one retry absorbs a
            r = bench.bench_checkpoint_overlap(False)   # busy-host blip
        assert r["metric"] == "checkpoint_overlap_added_pct"
        assert r["unit"] == "pct_of_blocking_added_step_time"
        d = r["detail"]
        assert d["base_step_us"] > 0.0
        assert d["blocking_step_us"] > d["base_step_us"]
        assert d["added_blocking_us_per_step"] > 0.0
        assert d["ckpt_every_k_steps"] >= 8
        # the acceptance gate itself
        assert r["value"] < 20.0, r
        assert r["vs_baseline"] > 1.0
        # the flag the micro toggles must be restored afterwards
        import paddle_tpu as paddle
        got = paddle.get_flags(["FLAGS_step_capture"])
        assert got["FLAGS_step_capture"] is True


class TestAnomalyOverheadMicro:
    def test_micro_runs_and_meets_gate(self):
        """bench.py anomaly_overhead smoke (ISSUE 10 acceptance): the
        in-capture anomaly sentinel (fused finiteness/global-norm sweep
        + select-guarded update inside the donated executable) must add
        <3% to the captured step, with a well-formed artifact entry.
        One retry absorbs a busy host."""
        r = bench.bench_anomaly_overhead(False)
        if r["value"] >= 3.0:       # timing gate: wall clock on a
            r = bench.bench_anomaly_overhead(False)   # shared CI host
        assert r["metric"] == "anomaly_sentinel_overhead_pct"
        assert r["unit"] == "pct_added_step_time"
        d = r["detail"]
        assert d["captured_step_us_sentinel_off"] > 0.0
        assert d["captured_step_us_sentinel_on"] > 0.0
        # both variants really ran captured (no eager fallback storm)
        assert d["counters"]["fallbacks"] == 0 or \
            d["counters"]["replays"] > d["counters"]["fallbacks"]
        # the acceptance gate itself
        assert r["value"] < 3.0, r
        # the flags the micro toggles must be restored afterwards
        import paddle_tpu as paddle
        got = paddle.get_flags(["FLAGS_step_capture",
                                "FLAGS_anomaly_sentinel"])
        assert got["FLAGS_step_capture"] is True
        assert got["FLAGS_anomaly_sentinel"] is False


class TestFusedOptimizerMicro:
    def test_micro_runs_and_meets_gate(self):
        """bench.py fused_optimizer smoke (ISSUE 16 acceptance): the
        bucketed megakernel route must beat the per-param launch chain
        by >=2x on the dispatch-bound adam/fp32/small_many cell, with
        the full {sgd,adam,adamw} x {f32,bf16} x {small_many,large_few}
        grid and the BERT-tiny multi-step twin-gap re-measure in the
        artifact entry. One retry absorbs a busy host."""
        r = bench.bench_fused_optimizer(False)
        if r["value"] < 2.0:        # timing gate: wall clock on a
            r = bench.bench_fused_optimizer(False)  # shared CI host
        assert r["metric"] == "fused_optimizer_speedup"
        assert r["unit"] == "x_vs_per_param_launch_chain"
        d = r["detail"]
        assert d["gate_config"] == "adam_f32_small_many"
        for name in ("sgd", "adam", "adamw"):
            for prec in ("f32", "bf16"):
                for size in ("small_many", "large_few"):
                    cell = d["grid"][f"{name}_{prec}_{size}"]
                    for k in ("per_param_chain_us", "pytree_us",
                              "fused_us"):
                        assert cell[k] > 0.0
                    assert cell["fused_vs_chain"] > 0.0
        # the fused route really ran (updates counted, bucket planned)
        assert d["counters"]["updates"] > 0
        assert d["counters"]["buckets"] >= 1
        bert = d["bert_tiny_multi_step_k8"]
        for k in ("unfused_us_per_step", "fused_us_per_step",
                  "native_twin_us_per_step"):
            assert bert[k] > 0.0
        # the captured tail must not regress beyond CPU host noise
        assert bert["fused_us_per_step"] < 1.25 * bert[
            "unfused_us_per_step"], bert
        # the acceptance gate itself (>=2x over the launch chain)
        assert r["value"] >= 2.0, r
        assert r["vs_baseline"] >= 1.0
        # the flags the micro toggles must be restored afterwards
        import paddle_tpu as paddle
        got = paddle.get_flags(["FLAGS_fused_optimizer",
                                "FLAGS_step_capture"])
        assert got["FLAGS_fused_optimizer"] is True
        assert got["FLAGS_step_capture"] is True


class TestObservabilityMicro:
    def test_micro_runs_and_reports(self):
        """bench.py observability_overhead smoke: the micro must run on
        CPU and report both the disabled-path and enabled-path costs
        (ISSUE 3: <=1us/op instrumentation budget with the flight
        recorder off)."""
        r = bench.bench_observability(False)
        assert r["metric"] == "observability_overhead_us_per_op"
        assert r["unit"] == "us/op"
        assert r["value"] >= 0.0
        d = r["detail"]
        assert "disabled_path_ns_per_op" in d
        assert "enabled_path_us_per_op" in d
        assert d["eager_us_per_op_no_instrumentation"] > 0
        # the flags the micro toggles must be restored afterwards
        import paddle_tpu as paddle
        got = paddle.get_flags(["FLAGS_metrics", "FLAGS_flight_recorder"])
        assert got["FLAGS_metrics"] is True
        assert got["FLAGS_flight_recorder"] is True


class TestCompareGate:
    """bench.py --compare rc contract (ISSUE PR18 satellite): the
    noise-aware regression gate must pass every recorded adjacent round
    pair (rc 0, zero REGRESSED verdicts — history is ground truth, any
    flag there is a false positive), fail a genuinely poisoned
    candidate with rc 1, and report usage errors with rc 2."""

    # r03..r07: the rounds whose records are kept in the repo
    ROUNDS = [os.path.join(REPO, f"BENCH_r0{i}.json") for i in range(3, 8)]
    R05, R06 = ROUNDS[2], ROUNDS[3]

    def test_recorded_rounds_exist(self):
        for p in self.ROUNDS:
            assert os.path.exists(p), f"missing recorded round {p}"

    @pytest.mark.parametrize("i", range(4))
    def test_adjacent_pairs_have_no_false_regressions(self, i, capsys):
        rc = bench.bench_compare(self.ROUNDS[i], self.ROUNDS[i + 1])
        out = capsys.readouterr().out
        assert rc == 0, f"false regression r0{i+3}->r0{i+4}:\n{out}"
        assert "REGRESSED" not in out

    def test_poisoned_candidate_fails_with_rc_1(self, tmp_path, capsys):
        # worsen every direction-gated metric far past any noise band
        base = self.R05
        with open(base) as f:
            rec = json.load(f)
        parsed = rec.get("parsed", rec)
        records = [parsed] + list(
            (parsed.get("detail") or {}).get("configs") or [])
        poisoned = []
        for r in records:
            d = bench._cmp_direction(str(r.get("metric")))
            if d and isinstance(r.get("value"), (int, float)) and r["value"]:
                r["value"] = r["value"] * (0.01 if d > 0 else 100.0)
                poisoned.append(r["metric"])
        assert poisoned, "no direction-gated metric in the round record"
        cand = tmp_path / "poisoned.json"
        cand.write_text(json.dumps(rec))
        rc = bench.bench_compare(base, str(cand))
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSED" in out

    def test_zero_valued_candidate_metric_is_not_gated(self, capsys):
        # r06's headline was recorded on the wrong device (value 0.0):
        # an unmeasured rung must be skipped, not flagged as -100%
        rc = bench.bench_compare(self.R05, self.R06)
        out = capsys.readouterr().out
        assert rc == 0
        assert "not gated" in out

    def test_missing_baseline_arg_exits_2(self, monkeypatch):
        monkeypatch.setattr(sys, "argv", ["bench.py", "--compare"])
        with pytest.raises(SystemExit) as ei:
            bench.main()
        assert ei.value.code == 2

    def test_no_rounds_next_to_baseline_is_rc_2(self, tmp_path, capsys):
        lone = tmp_path / "lone.json"
        lone.write_text("{}")
        assert bench.bench_compare(str(lone)) == 2


pytestmark = pytest.mark.smoke
