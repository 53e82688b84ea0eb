"""Deviceless v5p topology-AOT compile evidence (VERDICT r4 Missing#2).

BASELINE's north star is Llama-3-8B TP+DP on v5p-64 at >=40% MFU; no
64-chip hardware exists here, so the evidence is ahead-of-time: the REAL
train step (fwd+bwd+AdamW through TrainStep) lowered against a named TPU
topology and compiled by the actual XLA:TPU compiler, with per-chip HBM
and the SPMD collective schedule asserted. Reference analog: the static
auto-parallel Engine planning whole-cluster programs
(python/paddle/distributed/auto_parallel/static/engine.py:991).

Tests here compile a depth-reduced geometry to keep CI under ~3 min;
the full 32-layer 8B plan is ``aot.plan_llama3_8b_v5p64()`` with
``layers=None``.
"""
import numpy as np
import pytest

from paddle_tpu.distributed.auto_parallel import aot

V5P_HBM_BYTES = 95 * 1024 ** 3          # 95 GiB per v5p chip


@pytest.fixture(scope="module", autouse=True)
def _isolated_tp_mesh_state():
    """Cross-module TP-mesh isolation (CHANGES.md PR 5 flagged errors).

    mp_layers/tp_attention read the AMBIENT hybrid-communicate-group at
    trace time, so an hcg another module built on the 8-device CPU mesh
    and never cleared makes the v5p-topology lowering device_put onto
    retired CPU devices ("incompatible devices for jitted computation").
    Clear it for this module — set_hybrid_communicate_group(None) also
    bumps the mesh epoch and drops mesh-keyed kernel caches — and clear
    again on exit so the plans built HERE don't leak state either way.
    """
    from paddle_tpu.distributed import topology as topo
    topo.set_hybrid_communicate_group(None)
    yield
    topo.set_hybrid_communicate_group(None)


class TestTopologyMesh:
    def test_v5p_64_mesh(self):
        mesh = aot.topology_mesh("v5p:4x4x4", {"dp": 8, "mp": 8})
        assert mesh.devices.shape == (8, 8)
        assert mesh.axis_names == ("dp", "mp")

    def test_wrong_factorization_rejected(self):
        with pytest.raises(ValueError, match="64 devices"):
            aot.topology_mesh("v5p:4x4x4", {"dp": 4, "mp": 8})


class TestParamSpecs:
    def test_llama_tp_rules(self):
        import paddle_tpu as paddle
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(vocab_size=64, hidden_size=16,
                          intermediate_size=32, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=32,
                          use_scan_layers=True)
        with paddle.LazyGuard():
            model = LlamaForCausalLM(cfg)
        specs = aot.llama_param_pspecs(model)
        assert specs["llama.embed_tokens.weight"] == P("mp", None)
        assert specs["lm_head.weight"] == P(None, "mp")
        # stacked q (idx 0) column-parallel, o (idx 3) row-parallel
        assert specs["llama.layer_stack.stacked_0"] == P(None, None, "mp")
        assert specs["llama.layer_stack.stacked_3"] == P(None, "mp", None)
        # norms replicated
        assert specs["llama.norm.weight"] == P()


@pytest.fixture(scope="module")
def plan():
    # depth-reduced 8B geometry (hidden 4096 / ffn 14336 / GQA 32:8)
    # on a real v5p-64 topology — same sharded program structure as
    # the full model, ~2 min compile; module scope so every assertion
    # class shares the ONE compile (and aot._topology_desc memoizes the
    # topology client underneath it)
    return aot.plan_llama3_8b_v5p64(tp=8, dp=8, layers=2, seq=2048)


@pytest.mark.heavy
class TestV5pAotCompile:

    def test_compile_succeeds(self, plan):
        assert plan["compile_seconds"] > 0
        # 2-layer slice of 8B: embed+lm_head ~1.05B + 2x218M blocks
        assert plan["params"] > 1.4e9

    def test_pallas_flash_lowered(self, plan):
        # ISSUE 4 acceptance: the TP plan lowers WITH the shard_map'd
        # Pallas flash kernel — real Mosaic custom calls in the compiled
        # HLO (0 would mean the sharded path silently fell back to the
        # composite; aot.py no longer disables the kernel) and zero
        # recorded guard fallbacks during the trace
        assert plan["pallas_custom_calls"] > 0
        assert plan["attention"]["sharded"] > 0
        assert plan["attention"]["fallback"] == 0

    def test_per_chip_hbm_within_budget(self, plan):
        live = plan["per_chip_bytes"]["live"]
        assert live < V5P_HBM_BYTES, (
            f"per-chip live {live / 1e9:.1f}GB exceeds v5p budget")
        # sanity: sharded args are GBs, not the full replicated model
        assert plan["per_chip_bytes"]["arguments"] < 0.5 * V5P_HBM_BYTES

    def test_projected_throughput_reported(self, plan):
        # the plan must project THROUGHPUT, not just prove fit: roofline
        # step time from the compiled program's own cost_analysis()
        proj = plan["projected"]
        assert proj["flops_per_chip"] > 0
        assert proj["hbm_bytes_per_chip"] > 0
        assert proj["step_seconds"] > 0
        assert proj["tokens_per_sec"] > 0
        assert proj["bound"] in ("compute", "memory")
        assert 0.0 < proj["mfu_upper_bound"] <= 1.0
        # consistency: the roofline is the max of its two legs
        assert proj["step_seconds"] >= proj["compute_seconds"]
        assert proj["step_seconds"] >= proj["memory_seconds"]

    def test_collective_schedule(self, plan):
        c = plan["collectives"]
        # canonical Megatron TP: col-shard qkv/gate/up -> local per-head
        # attention -> row-shard o/down -> ONE all-reduce per block, no
        # forward all-gathers; dp grad sync folds into the same
        # all-reduces under GSPMD. 2 layers x (attn+ffn) x (fwd+bwd) = 8.
        assert c["all-gather"] == 0
        assert c["all-reduce"] >= 2 * 2 * 2
        assert c["collective-permute"] == 0   # nothing rides DCN-shaped paths

    @pytest.mark.slow
    def test_zero1_shrinks_per_chip_state(self, plan):
        # a SECOND full XLA:TPU compile (~2 min) — the only test here
        # that can't share the module-scoped plan, so it rides the slow
        # tier; tier-1 keeps the six assertions on the shared compile
        z = aot.plan_llama3_8b_v5p64(tp=8, dp=8, layers=2, seq=2048,
                                     zero1=True)
        assert (z["per_chip_bytes"]["arguments"]
                < 0.7 * plan["per_chip_bytes"]["arguments"]), (
            "ZeRO-1 state sharding should cut per-chip argument bytes")
        zc = z["collectives"]
        # dp-sharded state forces a param regather, and the TPU backend
        # marks it async (latency-hiding evidence)
        assert zc["all-gather"] + zc["all-to-all"] > 0
        assert zc["async_annotated"] > 0


pytestmark = pytest.mark.smoke
