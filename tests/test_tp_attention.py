"""GSPMD-composable Pallas attention (ISSUE 4): shard_map'd flash /
varlen kernels under a forced multi-device CPU mesh (the serving
kernel's wrap is held in test_ragged_attention.py::TestShardedRagged).

Acceptance evidence: sharded output == the unsharded single-device
reference (allclose + EXACT dtype) for the training (flash/varlen)
flows; every guard edge (heads not divisible by
tp, KV-heads < tp i.e. GQA replication, FLAGS_use_pallas_kernels off)
takes the composite path with a flight-recorder-visible reason and
never errors; per-op executables traced under a mesh never replay
after the topology changes (the flags mesh-epoch key).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability import flight_recorder as fr
from paddle_tpu.ops.dispatcher import call_op
from paddle_tpu.ops.kernels.pallas import flash_attention as fa
from paddle_tpu.ops.kernels.pallas import flash_varlen as fv
from paddle_tpu.ops.kernels.pallas import tp_attention as tpa

pytestmark = [
    pytest.mark.smoke,
    pytest.mark.skipif(jax.device_count() < 8,
                       reason="needs the forced 8-device CPU mesh"),
]


@pytest.fixture(autouse=True)
def _fresh_topology():
    from paddle_tpu.distributed import topology
    prev = topology.get_hybrid_communicate_group()
    topology.set_hybrid_communicate_group(None)
    yield
    topology.set_hybrid_communicate_group(prev)


def _mp_mesh(tp=4):
    return jax.make_mesh((tp,), ("mp",))


def _fallback_reasons(kind=None):
    ents = [e for e in fr.recorder().entries()
            if str(e[3]).startswith("tp_attention.fallback")]
    if kind is not None:
        ents = [e for e in ents if f"[{kind}]" in e[3]]
    return [e[4][0] for e in ents]


def _qkv(rng, b, s, hq, hk, d, dtype=jnp.float32):
    q = jnp.asarray(rng.randn(b, s, hq, d), dtype)
    k = jnp.asarray(rng.randn(b, s, hk, d), dtype)
    v = jnp.asarray(rng.randn(b, s, hk, d), dtype)
    return q, k, v


class TestShardedFlash:
    def test_matches_unsharded_reference(self):
        rng = np.random.RandomState(0)
        q, k, v = _qkv(rng, 2, 256, 8, 4, 32)
        mesh = _mp_mesh(4)
        out = tpa.sharded_flash_attention(q, k, v, mesh, "mp", None,
                                          causal=True)
        ref = fa.flash_attention(q, k, v, causal=True)
        assert out.dtype == ref.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        # heads really ride the mp axis
        spec = out.sharding.spec
        assert len(spec) >= 3 and spec[2] == "mp"

    def test_bf16_exact_dtype(self):
        rng = np.random.RandomState(1)
        q, k, v = _qkv(rng, 1, 128, 4, 4, 32, jnp.bfloat16)
        out = tpa.sharded_flash_attention(q, k, v, _mp_mesh(4), "mp",
                                          None, causal=False)
        assert out.dtype == jnp.bfloat16
        ref = fa.flash_attention(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

    def test_grads_match_unsharded(self):
        rng = np.random.RandomState(2)
        q, k, v = _qkv(rng, 1, 256, 8, 4, 32)
        mesh = _mp_mesh(4)

        def loss_tp(a, b_, c):
            return (tpa.sharded_flash_attention(
                a, b_, c, mesh, "mp", None, causal=True) ** 2).sum()

        def loss_ref(a, b_, c):
            return (fa.flash_attention(a, b_, c, causal=True) ** 2).sum()

        g = jax.grad(loss_tp, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, r in zip(g, gr):
            assert a.dtype == r.dtype
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=2e-4, rtol=2e-4)

    def test_dp_x_mp_mesh_batch_sharding(self):
        rng = np.random.RandomState(3)
        q, k, v = _qkv(rng, 4, 128, 8, 8, 16)
        mesh = jax.make_mesh((2, 4), ("dp", "mp"))
        out = tpa.sharded_flash_attention(q, k, v, mesh, "mp", "dp",
                                          causal=True)
        ref = fa.flash_attention(q, k, v, causal=True)
        assert out.dtype == ref.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestShardedVarlen:
    def test_matches_unsharded_reference(self):
        rng = np.random.RandomState(4)
        T, h, hk, d = 384, 8, 4, 32
        q = jnp.asarray(rng.randn(T, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(T, hk, d), jnp.float32)
        v = jnp.asarray(rng.randn(T, hk, d), jnp.float32)
        cu = jnp.asarray([0, 150, 384], jnp.int32)
        out = tpa.sharded_flash_varlen(q, k, v, cu, cu, _mp_mesh(4), "mp",
                                       causal=True, tok_skip=True)
        ref = fv.flash_attn_unpadded(q, k, v, cu, cu, causal=True)
        assert out.dtype == ref.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_grads_and_composite_agreement(self):
        rng = np.random.RandomState(5)
        T, h, d = 256, 4, 16
        q = jnp.asarray(rng.randn(T, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(T, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(T, h, d), jnp.float32)
        cu = jnp.asarray([0, 100, 256], jnp.int32)
        mesh = _mp_mesh(4)

        def loss_tp(a, b_, c):
            return (tpa.sharded_flash_varlen(
                a, b_, c, cu, cu, mesh, "mp", causal=True) ** 2).sum()

        def loss_comp(a, b_, c):
            return (fv.varlen_composite(a, b_, c, cu, cu,
                                        causal=True) ** 2).sum()

        g = jax.grad(loss_tp, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_comp, argnums=(0, 1, 2))(q, k, v)
        for a, r in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=2e-3, rtol=2e-3)


class TestFallbackEdges:
    """Guard edges must take the composite path with a recorded reason,
    never error (reasons record at trace time — once per compiled
    specialization)."""

    def test_heads_not_divisible(self):
        rng = np.random.RandomState(8)
        q, k, v = _qkv(rng, 1, 128, 6, 6, 16)   # 6 % 4 != 0
        mesh = _mp_mesh(4)
        with tpa.tp_shard_context(mesh, "mp"):
            from paddle_tpu.ops.kernels.nn import flash_attention as fk
            out = fk(q, k, v, is_causal=True)
        from paddle_tpu.ops.kernels.nn import scaled_dot_product_attention
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(scaled_dot_product_attention(q, k, v,
                                                    is_causal=True)),
            atol=1e-5, rtol=1e-5)
        assert any("num_heads 6 not divisible" in r
                   for r in _fallback_reasons("flash"))

    def test_gqa_kv_heads_below_tp(self):
        rng = np.random.RandomState(9)
        q, k, v = _qkv(rng, 1, 128, 8, 2, 16)   # kv 2 < tp 4
        mesh = _mp_mesh(4)
        with tpa.tp_shard_context(mesh, "mp"):
            from paddle_tpu.ops.kernels.nn import flash_attention as fk
            out = fk(q, k, v, is_causal=True)
        from paddle_tpu.ops.kernels.nn import scaled_dot_product_attention
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(scaled_dot_product_attention(q, k, v,
                                                    is_causal=True)),
            atol=1e-5, rtol=1e-5)
        assert any("GQA replication" in r
                   for r in _fallback_reasons("flash"))

    def test_flags_off_records_and_composites(self):
        rng = np.random.RandomState(10)
        q, k, v = _qkv(rng, 1, 128, 4, 4, 16)
        prev = paddle.get_flags("FLAGS_use_pallas_kernels")
        paddle.set_flags({"FLAGS_use_pallas_kernels": False})
        try:
            with tpa.tp_shard_context(_mp_mesh(4), "mp"):
                from paddle_tpu.ops.kernels.nn import flash_attention as fk
                out = fk(q, k, v, is_causal=True)
        finally:
            paddle.set_flags(prev)
        from paddle_tpu.ops.kernels.nn import scaled_dot_product_attention
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(scaled_dot_product_attention(q, k, v,
                                                    is_causal=True)),
            atol=1e-5, rtol=1e-5)
        assert any("FLAGS_use_pallas_kernels off" in r
                   for r in _fallback_reasons())

    def test_varlen_fallback_composite(self):
        rng = np.random.RandomState(12)
        T, h, d = 128, 6, 16   # 6 % 4 != 0
        q = jnp.asarray(rng.randn(T, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(T, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(T, h, d), jnp.float32)
        cu = jnp.asarray([0, 50, 128], jnp.int32)
        from paddle_tpu.ops.kernels.nn import flash_attn_unpadded_kernel
        with tpa.tp_shard_context(_mp_mesh(4), "mp"):
            out = flash_attn_unpadded_kernel(q, k, v, cu, cu, causal=True)
        ref = fv.flash_attn_unpadded(q, k, v, cu, cu, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)
        assert any("not divisible" in r for r in _fallback_reasons("varlen"))


class TestOpDispatchUnderTopology:
    """The full eager path: fleet hybrid topology -> dispatcher -> kernel
    gate -> shard_map'd Pallas, plus the mesh-epoch exec-cache key."""

    def _install(self, dp=2, mp=4):
        from paddle_tpu.distributed import topology
        topo = topology.CommunicateTopology(dims=[dp, 1, 1, 1, mp])
        hcg = topology.HybridCommunicateGroup(topo)
        topology.set_hybrid_communicate_group(hcg)
        return hcg

    def test_flash_op_and_epoch_invalidation(self):
        from paddle_tpu.distributed import topology
        rng = np.random.RandomState(13)
        qn = rng.randn(2, 128, 8, 16).astype(np.float32)
        kn = rng.randn(2, 128, 4, 16).astype(np.float32)
        vn = rng.randn(2, 128, 4, 16).astype(np.float32)
        ref = call_op("flash_attention", Tensor(qn), Tensor(kn),
                      Tensor(vn), is_causal=True).numpy()
        self._install()
        out = call_op("flash_attention", Tensor(qn), Tensor(kn),
                      Tensor(vn), is_causal=True).numpy()
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        # clearing the topology must NOT replay the shard_map executable
        topology.set_hybrid_communicate_group(None)
        out2 = call_op("flash_attention", Tensor(qn), Tensor(kn),
                       Tensor(vn), is_causal=True).numpy()
        np.testing.assert_allclose(out2, ref, atol=2e-5, rtol=2e-5)

    def test_ragged_op_under_topology(self):
        """The serving op under the fleet topology: decode-only rows
        (`q_len = 1`) run head-sharded over mp with rows replicated."""
        from paddle_tpu.distributed import topology
        rng = np.random.RandomState(14)
        B, H, KV, D, NB, BS, MB = 4, 8, 4, 16, 16, 16, 4
        args = (rng.randn(B, H, D).astype(np.float32),
                rng.randn(NB, BS, KV, D).astype(np.float32),
                rng.randn(NB, BS, KV, D).astype(np.float32),
                rng.randint(0, NB, (B, MB)).astype(np.int32),
                rng.randint(BS, MB * BS, B).astype(np.int32),
                np.arange(B + 1, dtype=np.int32))
        self._install()
        out = call_op("ragged_paged_attention", *map(Tensor, args)).numpy()
        assert any("rows replicated" in r for r in _fallback_reasons("ragged"))
        topology.set_hybrid_communicate_group(None)
        ref = call_op("ragged_paged_attention", *map(Tensor, args)).numpy()
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_sharded_metric_counts(self):
        from paddle_tpu.observability import metrics
        before = metrics.registry().get("tp_attention.sharded").value
        rng = np.random.RandomState(15)
        q, k, v = _qkv(rng, 1, 128, 8, 4, 16)
        out = tpa.sharded_flash_attention(q, k, v, _mp_mesh(4), "mp",
                                          None, causal=False)
        assert out is not None
        assert metrics.registry().get("tp_attention.sharded").value \
            > before


class TestDpOnlyPlanStillWraps:
    def test_tp_degree_one_explicit_context_wraps(self):
        """A dp-only plan (tp axis present at degree 1) must STILL take
        the shard_map wrap under an explicit context: a bare pallas_call
        against dp-sharded GSPMD inputs is exactly the partitioner abort
        the wrap exists to prevent."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.kernels.nn import (flash_attention as fk,
                                               scaled_dot_product_attention)
        rng = np.random.RandomState(22)
        mesh = jax.make_mesh((8, 1), ("dp", "mp"))
        b, s, h, d = 8, 128, 4, 16
        qn = rng.randn(b, s, h, d).astype(np.float32)
        kn = rng.randn(b, s, h, d).astype(np.float32)
        vn = rng.randn(b, s, h, d).astype(np.float32)
        ctx = tpa.current_tp_context
        with tpa.tp_shard_context(mesh, "mp", "dp"):
            assert ctx() is not None   # degree-1 mp keeps the wrap
            sds = jax.ShapeDtypeStruct(
                (b, s, h, d), jnp.float32,
                sharding=NamedSharding(mesh, P("dp", None, None, None)))
            compiled = jax.jit(
                lambda q, k, v: fk(q, k, v, is_causal=True)).lower(
                sds, sds, sds).compile()
            out = compiled(jnp.asarray(qn), jnp.asarray(kn),
                           jnp.asarray(vn))
        ref = scaled_dot_product_attention(
            jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn),
            is_causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)


class TestRingTpComposition:
    def test_ring_heads_coshard_over_mp(self):
        from paddle_tpu.ops.kernels.nn import scaled_dot_product_attention
        from paddle_tpu.ops.kernels.pallas import ring_attention as ra
        rng = np.random.RandomState(20)
        mesh = jax.make_mesh((2, 4), ("sep", "mp"))
        b, s, hq, hk, d = 1, 256, 8, 4, 32
        q, k, v = _qkv(rng, b, s, hq, hk, d)
        out = ra.ring_attention(q, k, v, mesh, "sep", causal=True,
                                head_axis="mp")
        ref = scaled_dot_product_attention(q, k, v, is_causal=True)
        assert out.dtype == ref.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)

    def test_ring_head_replication_fallback_recorded(self):
        from paddle_tpu.ops.kernels.pallas import ring_attention as ra
        rng = np.random.RandomState(21)
        mesh = jax.make_mesh((2, 4), ("sep", "mp"))
        q, k, v = _qkv(rng, 1, 256, 6, 6, 16)   # 6 % 4 != 0
        out = ra.ring_attention(q, k, v, mesh, "sep", causal=True,
                                head_axis="mp")
        assert out.shape == q.shape
        assert any("head-replicated ring" in r
                   for r in _fallback_reasons("ring"))


class TestAotStyleLowering:
    """The deviceless-plan pattern on a CPU mesh: jit().lower().compile()
    with sharded avals under tp_shard_context — the kernel tier composes
    with GSPMD instead of aborting the partitioner."""

    def test_lower_compile_run_matches_composite(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.kernels.nn import (flash_attention as fk,
                                               scaled_dot_product_attention)
        rng = np.random.RandomState(16)
        mesh = jax.make_mesh((2, 4), ("dp", "mp"))
        b, s, hq, hk, d = 4, 128, 8, 4, 16
        qn = rng.randn(b, s, hq, d).astype(np.float32)
        kn = rng.randn(b, s, hk, d).astype(np.float32)
        vn = rng.randn(b, s, hk, d).astype(np.float32)

        def sds(shape, h_heads):
            return jax.ShapeDtypeStruct(
                shape, jnp.float32,
                sharding=NamedSharding(mesh, P("dp", None, "mp", None)))

        with tpa.tp_shard_context(mesh, "mp", "dp"):
            step = jax.jit(lambda q, k, v: fk(q, k, v, is_causal=True))
            compiled = step.lower(sds((b, s, hq, d), hq),
                                  sds((b, s, hk, d), hk),
                                  sds((b, s, hk, d), hk)).compile()
            out = compiled(jnp.asarray(qn), jnp.asarray(kn),
                           jnp.asarray(vn))
        ref = scaled_dot_product_attention(jnp.asarray(qn),
                                           jnp.asarray(kn),
                                           jnp.asarray(vn), is_causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)
