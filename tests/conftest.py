"""Test harness: 8 virtual CPU devices so sharding/collective tests run
anywhere (the analog of the reference's single-host multi-process harness,
test/legacy_test/test_parallel_dygraph_dataparallel.py:30).

Tests run on the virtual CPU mesh: JAX_PLATFORMS=cpu and the device-count
flag are set here before jax is first imported, which is all this
installation needs. The `jax.config.update` below only matters when
something imported jax before this file (a pytest plug-in): jax reads the
variable once, at import.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"

# libtpu's GCP instance-metadata discovery retries ~8 variables x 30
# HTTP attempts against a 403ing metadata server — ~460s of pure wall
# wait the first time a process instantiates a deviceless topology
# client (test_v5p_aot), plus ~110s for the AOT compile client. No TPU
# metadata exists in this container; skip the query outright.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# -- test tiers (VERDICT r3 Next#7) ------------------------------------------
# `heavy` marks the modules dominated by model builds / multi-device scans /
# subprocesses; `pytest -m "not heavy"` is the fast iteration tier (<60s on
# 6 workers). The full suite (no -m) remains the CI default.
_HEAVY_MODULES = {
    "test_vision", "test_detection", "test_rnn_ocr", "test_pallas_and_pp",
    "test_moe", "test_models", "test_multihost", "test_launch",
    "test_flash_varlen", "test_generation", "test_pp_schedules",
    "test_sharding_stages", "test_distributed", "test_auto_parallel_engine",
    "test_weight_only_quant", "test_graph_rnnt", "test_ops_tranche2",
    "test_ops_tranche2_grad", "test_io_amp_jit", "test_sot",
    "test_checkpoint", "test_incubate_inference", "test_compat_tranche",
    "test_linalg_fft", "test_domains_misc", "test_distribution",
    "test_fleet_utils", "test_sparse", "test_nn", "test_ops_ext",
    "test_hapi_metric", "test_capi", "test_autograd_functional",
    "test_tp_attention",
}


_HEAVY_TESTS = {
    "test_multiprocess_rendezvous",   # 4-process TCPStore barrier, ~17s
}

# -- tier-1 runtime audit (PR 4) ---------------------------------------------
# The tier-1 budget is 870s and the SEED already overran it on this host
# (timeout at ~96%, 1024/1095 dots). These are the slowest REDUNDANT
# parametrizations — coverage another tier-1 test keeps — moved to
# `slow` so the suite finishes inside the budget (the full suite still
# runs them without `-m 'not slow'`). Durations from this host's
# profiled run. (The per-process ~460s TPU topology-client init that
# used to land on whichever topology test ran first is gone — see the
# TPU_SKIP_MDS_QUERY note above.)
# PR 20 audit: whole modules whose fixture cost IS the cost. The only
# member, test_v5p_aot, pays a ~110s module-scoped deviceless XLA:TPU
# AOT compile before its first dot — the definition of a slow test, and
# the single longest stretch in the suite. With the suite within ~60s
# of the tier-1 box on this 1-core host, the compile is the one move
# that buys real margin. Tier-1 keeps the plan machinery covered
# elsewhere: AOT-plan cache round-trip in test_exec_store, ZeRO-1
# sharding semantics in test_sharding_stages, shard_map'd flash lowering
# in test_tp_attention; the full compile still runs in slow CI.
_SLOW_MODULES = {
    "test_v5p_aot",
}

_SLOW_TESTS = {
    # second full v5p plan compile (~17s + recompile pressure); ZeRO-1
    # state-sharding semantics stay covered by test_sharding_stages
    ("test_v5p_aot", "test_zero1_shrinks_per_chip_state"),
    # 16s training smoke on the same YOLOv3 whose forward/loss/predict
    # test stays tier-1
    ("test_detection", "test_training_reduces_loss"),
    # vision-zoo forward-only dups of the same conv/BN machinery;
    # resnet18/50, vgg and alexnet remain tier-1
    ("test_vision", "test_densenet121"),
    ("test_vision", "test_mobilenet_v2"),
    ("test_vision", "test_mobilenet_v3_small"),
    ("test_vision", "test_inception_v3"),
    ("test_vision", "test_googlenet"),
    ("test_vision", "test_squeezenet"),
    ("test_vision", "test_shufflenet_v2"),
    # 11s two-process elastic rerank end-to-end; the other elastic /
    # launch paths (rendezvous, scale events) remain tier-1
    ("test_launch", "test_node_death_reranks_survivors"),
    # PR 18 audit: 15s 3-step EP training smoke; EP numerics stay
    # tier-1 via test_ep_matches_local + the router/capacity tests
    ("test_moe", "test_moe_model_trains_under_ep"),
    # PR 20 audit (the suite crossed the 870s box on a 1-core host; each
    # entry below is a whole-model/variant smoke whose machinery keeps
    # dedicated fast tier-1 coverage in the same module):
    # 17s full-YOLOv3 forward/loss/predict; every yolo component (loss
    # matching/masks, NMS, deform conv, numpy parity, gradients) stays
    ("test_detection", "test_forward_loss_predict"),
    # 14s VGG-11 forward; resnet18/50/resnext train-step smokes stay
    ("test_vision", "test_vgg11"),
    # 12s DBNet det forward+loss; CRNN/CTC keeps the OCR pipeline
    # tier-1 and the LSTM/GRU parity tests stay
    ("test_rnn_ocr", "test_dbnet_forward_and_loss_step"),
    # 12s virtual-pipeline grad parity; plain-PP parity, the VPP
    # schedule validity + bubble tests stay tier-1
    ("test_pallas_and_pp", "test_vpp_loss_and_grad_parity"),
    # 7s ring-attention-in-Llama smoke; ring-vs-composite stays tier-1
    ("test_pallas_and_pp", "test_llama_sep_parity"),
    # 6s multiprocess-worker resume; the no-worker mid-epoch resume
    # byte-identity test stays tier-1
    ("test_anomaly", "test_resume_with_workers_byte_identical"),
    # 6s worker-pool recreation; worker error propagation + persistent
    # pool reuse/abandoned-epoch tests stay tier-1
    ("test_io_amp_jit", "test_pool_recreated_after_worker_error"),
    # 6s two-process P2P send/recv; the two-process cross-host
    # allreduce bootstrap test stays tier-1
    ("test_multihost", "test_cross_host_send_recv"),
    # 8s dead-program GC sweep; executable-cache reuse + the
    # live-programs-keep-distinct-entries tests stay tier-1
    ("test_static", "test_dead_program_never_replays_stale_executable"),
    # 7s ring-attention Pallas block-path parity; ring-vs-composite
    # stays tier-1
    ("test_pallas_and_pp", "test_ring_pallas_block_path"),
    # 5s varlen flash gradient parity; varlen forward parity /
    # packing / leakage tests stay, and flash-kernel gradients stay
    # tier-1 via test_forward_and_grads_causal_gqa
    ("test_flash_varlen", "test_gradients_parity"),
    # 5s resnet50 bottleneck-block smoke; resnet18 stays tier-1
    ("test_vision", "test_resnet50_bottleneck"),
    # 5s end-to-end shed-then-client-retry; the retry-after hint unit
    # tests stay, and the fleet bench micro asserts sheds + hint
    ("test_serving_fleet", "test_shed_then_retry"),
}

# Class-qualified entries (same audit, PR 7 refresh; PR 18 refresh):
# the WALL-CLOCK bench-micro smokes are the slowest and least
# time-box-appropriate tier-1 members — each guards a timing RATIO the
# bench artifact already records every round (BENCH_rXX), and each
# feature's machinery keeps its own dedicated tier-1 file
# (test_resilience 27 tests, test_step_capture 39, test_observability
# 35). The newest micro's smoke (TestServingFleetMicro, which carries
# the PR 18 incident-overhead acceptance gates) stays tier-1 until the
# next audit.
_SLOW_CLASS_TESTS = {
    # 24s checkpoint-overlap wall-clock gate (has its own busy-host retry)
    ("test_bench_robustness", "TestCheckpointOverlapMicro",
     "test_micro_runs_and_meets_gate"),
    # 13s captured-vs-eager wall-clock micro
    ("test_bench_robustness", "TestStepCaptureMicro",
     "test_micro_runs_and_reports"),
    # 6s metrics-overhead wall-clock micro
    ("test_bench_robustness", "TestObservabilityMicro",
     "test_micro_runs_and_reports"),
    # 37s K-block-vs-single-step wall-clock gate (busy-host retry
    # inside); the multi-step machinery keeps tier-1 coverage in
    # test_multi_step (34 fast tests)
    ("test_bench_robustness", "TestMultiStepMicro",
     "test_micro_runs_and_meets_gate"),
    # ~80s full-grid fused-vs-chain wall-clock gate (busy-host retry
    # inside); the megakernel keeps tier-1 coverage in
    # test_fused_optimizer (64 fast tests)
    ("test_bench_robustness", "TestFusedOptimizerMicro",
     "test_micro_runs_and_meets_gate"),
    # PR 18 audit: ~11-20s detector-tax wall-clock gate (flaked under
    # host load even with its retry); the anomaly machinery keeps
    # tier-1 coverage in test_anomaly (29 fast tests)
    ("test_bench_robustness", "TestAnomalyOverheadMicro",
     "test_micro_runs_and_meets_gate"),
    # PR 18 audit: ~7s ragged-batching wall-clock micro; continuous
    # batching keeps tier-1 coverage in test_continuous_batching (21)
    ("test_bench_robustness", "TestServingRaggedMicro",
     "test_micro_runs_and_reports"),
    # PR 20: ~40s four-regime (kv_dtype x spec) wall-clock micro with a
    # >=1.3x speculative-decode gate; the int8-pool and spec machinery
    # keep tier-1 coverage in test_continuous_batching (TestQuantizedKV
    # + TestSpeculativeDecode) and test_ragged_attention
    ("test_bench_robustness", "TestServingRegimesMicro",
     "test_matrix_runs_and_meets_gates"),
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.module.__name__ in _HEAVY_MODULES
                or item.originalname in _HEAVY_TESTS):
            item.add_marker(pytest.mark.heavy)
        if (item.module.__name__ in _SLOW_MODULES
                or (item.module.__name__, item.originalname) in _SLOW_TESTS):
            item.add_marker(pytest.mark.slow)
        if (item.module.__name__,
                getattr(item.cls, "__name__", None),
                item.originalname) in _SLOW_CLASS_TESTS:
            item.add_marker(pytest.mark.slow)
    # Schedule the suite's long pole LAST: test_v5p_aot's module-scoped
    # ~2 min XLA:TPU AOT compile is the single longest stretch with no
    # intermediate dots. Alphabetical order parks ~50 fast vision/quant
    # tests behind it, so a time-boxed run that hits the budget dies on
    # the compile AND forfeits all of them; running it last, the same
    # kill costs only the compile itself. (Moot under `-m 'not slow'`
    # now that the module is in _SLOW_MODULES, but full/slow runs are
    # time-boxed too.) Stable sort — every other
    # module keeps its alphabetical position. (The module is order-safe:
    # its autouse fixture clears ambient TP-mesh state on entry/exit.)
    items.sort(key=lambda it: it.module.__name__ == "test_v5p_aot")
