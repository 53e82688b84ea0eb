"""Serving path: the dense KV cache and generate loop; the paged pool's
allocator and write, read back through the ragged attention op."""

import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.autograd.engine import no_grad
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import KVCache, PagedKVCache
from paddle_tpu.ops.dispatcher import call_op


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=96,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return cfg, m


class TestKVCacheDecode:
    def test_prefill_matches_full_forward(self, tiny_llama):
        cfg, m = tiny_llama
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 128, (2, 6)).astype(np.int32))
        with no_grad():
            full = m(ids).numpy()
            cache = KVCache(2, 2, 16, cfg.num_key_value_heads, 8)
            pre = m(ids, cache=cache,
                    start_pos=Tensor(jnp.asarray(0, jnp.int32))).numpy()
        np.testing.assert_allclose(pre, full, atol=2e-4)

    def test_token_by_token_matches(self, tiny_llama):
        cfg, m = tiny_llama
        ids_np = np.random.RandomState(1).randint(0, 128, (1, 5)).astype(
            np.int32)
        with no_grad():
            full = m(paddle.to_tensor(ids_np)).numpy()
            cache = KVCache(2, 1, 8, cfg.num_key_value_heads, 8)
            outs = []
            for t in range(5):
                lg = m(paddle.to_tensor(ids_np[:, t:t + 1]), cache=cache,
                       start_pos=Tensor(jnp.asarray(t, jnp.int32)))
                outs.append(lg.numpy())
        np.testing.assert_allclose(np.concatenate(outs, axis=1), full,
                                   atol=3e-4)

    def test_generate_greedy_deterministic(self, tiny_llama):
        cfg, m = tiny_llama
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, 128, (2, 4)).astype(np.int32))
        a = m.generate(ids, max_new_tokens=6, temperature=0.0).numpy()
        b = m.generate(ids, max_new_tokens=6, temperature=0.0).numpy()
        np.testing.assert_array_equal(a, b)
        assert a.shape == (2, 10)
        np.testing.assert_array_equal(a[:, :4], ids.numpy())

    def test_generate_sampling_shapes(self, tiny_llama):
        cfg, m = tiny_llama
        ids = paddle.to_tensor(np.zeros((1, 3), np.int32))
        out = m.generate(ids, max_new_tokens=4, temperature=0.9, top_k=20,
                         top_p=0.9)
        assert tuple(out.shape) == (1, 7)


def _ragged_decode(cache, layer, q, lens):
    """One-token rows over the pool: row r attends its ``lens[r]`` tokens.
    q[B, H, D] -> [B, H, D] through the `ragged_paged_attention` op."""
    B = q.shape[0]
    return call_op("ragged_paged_attention", q,
                   cache.k[layer], cache.v[layer],
                   Tensor(jnp.asarray(cache.block_tables)),
                   Tensor(jnp.asarray(lens, jnp.int32)),
                   Tensor(jnp.arange(B + 1, dtype=jnp.int32)),
                   **cache.scale_kwargs(layer))


def _append(cache, layer, seq, pos0, k, v):
    """Writes k/v [1, n, KV, D] of sequence ``seq`` at ``pos0``: slots
    from the allocator, then THE pool write, as the engine's step does."""
    slots = cache.alloc_slots(seq, pos0, k.shape[1])
    cache.write(layer, k, v, Tensor(jnp.asarray(slots, jnp.int32)))


class TestPagedCache:
    # what the pool's storage loses against the float32 dense cache, on
    # values in [0, 1): nothing, bf16's 8 mantissa bits, int8's 1/254 of
    # each token's absmax
    @pytest.mark.parametrize("kv_dtype,atol", [("auto", 1e-5),
                                               ("bf16", 4e-3),
                                               ("int8", 4e-3)],
                             ids=["float32", "bf16-pool", "int8-pool"])
    def test_paged_matches_contiguous_attention(self, kv_dtype, atol):
        """ragged_paged_attention (one-token rows) over scattered blocks
        == cache_attention over a contiguous buffer with the same
        contents."""
        B, T, KV, D, H = 2, 12, 2, 8, 4
        BS = 4  # block size
        rng = np.random.RandomState(0)
        q = rng.rand(B, H, D).astype(np.float32)
        kv_data = rng.rand(2, B, T, KV, D).astype(np.float32)
        lens = np.array([10, 7], np.int32)

        # contiguous reference; cache_attention masks by pos: q position
        # = len-1
        outs_ref = []
        for b in range(B):
            o = call_op("cache_attention",
                        Tensor(q[b:b + 1, None]),
                        Tensor(kv_data[0][b:b + 1]),
                        Tensor(kv_data[1][b:b + 1]),
                        Tensor(jnp.asarray(int(lens[b]) - 1, jnp.int32)))
            outs_ref.append(o.numpy()[:, 0])
        ref = np.concatenate(outs_ref, axis=0)

        # paged: the sequences grow token by token, so their blocks
        # interleave in the pool
        cache = PagedKVCache(1, B, num_blocks=8, block_size=BS,
                             num_kv_heads=KV, head_dim=D,
                             max_blocks_per_seq=3, kv_dtype=kv_dtype)
        assert cache.quantized == (kv_dtype == "int8")
        for t in range(int(lens.max())):
            for b in np.nonzero(t < lens)[0]:
                _append(cache, 0, b, t,
                        Tensor(kv_data[0][b:b + 1, t:t + 1]),
                        Tensor(kv_data[1][b:b + 1, t:t + 1]))
        assert (np.abs(np.diff(cache.block_tables[0, :3])) > 1).all()
        out = _ragged_decode(cache, 0, Tensor(q), lens).numpy()
        np.testing.assert_allclose(out, ref, atol=atol)

    def test_allocator_reuse(self):
        cache = PagedKVCache(1, 2, num_blocks=4, block_size=2,
                             num_kv_heads=1, head_dim=4,
                             max_blocks_per_seq=4)
        slots = cache.alloc_slots(0, 0, 6)
        assert len(set(slots.tolist())) == 6
        assert cache._allocated[0] == 3
        held = set(cache.block_tables[0, :3].tolist())
        used_before = len(cache._free)
        cache.release(0)
        assert len(cache._free) == used_before + 3
        assert cache._allocated[0] == 0
        # the next sequence is handed the blocks just released
        cache.alloc_slots(1, 0, 6)
        assert set(cache.block_tables[1, :3].tolist()) == held

    def test_alloc_slots_on_an_exhausted_pool_raises(self):
        cache = PagedKVCache(1, 1, num_blocks=1, block_size=2,
                             num_kv_heads=1, head_dim=4,
                             max_blocks_per_seq=2)
        first = cache.alloc_slots(0, 0, 2)
        with pytest.raises(RuntimeError, match="exhausted"):
            cache.alloc_slots(0, 2, 1)
        # what the sequence held before is still its own
        assert cache._allocated[0] == 1
        np.testing.assert_array_equal(cache.alloc_slots(0, 0, 2), first)

    def test_alloc_slots_takes_blocks_from_the_override(self):
        """The engine's allocator (free list, then evictable cached
        blocks) stands in for the free-list pop."""
        cache = PagedKVCache(1, 1, num_blocks=8, block_size=4,
                             num_kv_heads=1, head_dim=4,
                             max_blocks_per_seq=4)
        free_before = list(cache._free)
        handed = iter([5, 2])
        slots = cache.alloc_slots(0, 2, 5, alloc_block=lambda: next(handed))
        # positions 2..6: two in block 5, three in block 2
        np.testing.assert_array_equal(slots, [22, 23, 8, 9, 10])
        assert cache.block_tables[0, :2].tolist() == [5, 2]
        assert cache._free == free_before      # its own list was not asked
        # positions inside blocks it holds ask nobody
        np.testing.assert_array_equal(
            cache.alloc_slots(0, 7, 1, alloc_block=lambda: 1 / 0), [11])


class TestSampling:
    def test_greedy_is_argmax(self):
        logits = Tensor(np.array([[0.1, 5.0, 0.2], [3.0, 0.0, 0.1]],
                                 np.float32))
        tok = call_op("sample_logits", logits, temperature=0.0)
        np.testing.assert_array_equal(tok.numpy(), [1, 0])

    def test_top_k_restricts_support(self):
        logits = Tensor(np.array([[10.0, 9.0, -50.0, -50.0]] * 8,
                                 np.float32))
        for _ in range(5):
            tok = call_op("sample_logits", logits, temperature=1.0, top_k=2)
            assert set(np.asarray(tok.numpy()).tolist()) <= {0, 1}

    def test_top_p_keeps_mass(self):
        # one dominant token with p > top_p → always selected
        logits = Tensor(np.array([[20.0, 1.0, 1.0, 1.0]] * 4, np.float32))
        tok = call_op("sample_logits", logits, temperature=1.0, top_p=0.5)
        np.testing.assert_array_equal(tok.numpy(), [0, 0, 0, 0])


class TestReviewRegressions:
    def test_paged_cache_multilayer(self):
        """Layer writes share ONE block table; layer>0 must not re-allocate."""
        cache = PagedKVCache(2, 1, num_blocks=4, block_size=2,
                             num_kv_heads=1, head_dim=4,
                             max_blocks_per_seq=2)
        k0 = Tensor(np.full((1, 1, 1, 4), 1.0, np.float32))
        k1 = Tensor(np.full((1, 1, 1, 4), 2.0, np.float32))
        _append(cache, 0, 0, 0, k0, k0)
        _append(cache, 1, 0, 0, k1, k1)
        assert len(cache._free) == 3  # exactly one block allocated
        q = Tensor(np.ones((1, 2, 4), np.float32))
        out0 = _ragged_decode(cache, 0, q, [1]).numpy()
        out1 = _ragged_decode(cache, 1, q, [1]).numpy()
        np.testing.assert_allclose(out0, 1.0)  # layer-0 data reachable
        np.testing.assert_allclose(out1, 2.0)

    def test_generate_capacity_validation(self, tiny_llama):
        cfg, m = tiny_llama
        ids = paddle.to_tensor(np.zeros((1, 10), np.int32))
        with pytest.raises(ValueError, match="max_cache_len"):
            m.generate(ids, max_new_tokens=100, max_cache_len=16)
        with pytest.raises(ValueError, match="max_position_embeddings"):
            m.generate(ids, max_new_tokens=1000)

    def test_eos_pads_finished_rows(self, tiny_llama):
        cfg, m = tiny_llama
        ids = paddle.to_tensor(
            np.random.RandomState(3).randint(0, 128, (2, 3)).astype(np.int32))
        # greedy with an eos id that will be hit quickly for at least one row
        out = m.generate(ids, max_new_tokens=8, temperature=0.0,
                         eos_token_id=int(np.argmax(np.random.RandomState(3)
                                                    .rand(128))))
        gen = out.numpy()[:, 3:]
        # structural assertion: after the first eos, all tokens equal eos
        eos = int(np.argmax(np.random.RandomState(3).rand(128)))
        for row in gen:
            idx = np.where(row == eos)[0]
            if len(idx):
                assert (row[idx[0]:] == eos).all()

    def test_cache_prefill_honors_attn_mask(self, tiny_llama):
        cfg, m = tiny_llama
        ids = paddle.to_tensor(
            np.random.RandomState(4).randint(0, 128, (1, 6)).astype(np.int32))
        # mask out the FIRST two positions (left padding) — the causal mask
        # alone would still let later queries attend to them
        mask = np.ones((1, 1, 6, 6), bool)
        mask[..., :2] = False
        with no_grad():
            cache = KVCache(2, 1, 6, cfg.num_key_value_heads, 8)
            masked = m(ids, attn_mask=paddle.to_tensor(mask), cache=cache,
                       start_pos=Tensor(jnp.asarray(0, jnp.int32))).numpy()
            cache2 = KVCache(2, 1, 6, cfg.num_key_value_heads, 8)
            unmasked = m(ids, cache=cache2,
                         start_pos=Tensor(jnp.asarray(0, jnp.int32))).numpy()
        assert not np.allclose(masked[:, 2:], unmasked[:, 2:])

    def test_rnn_attr_initializer_honored(self):
        import paddle_tpu.nn.initializer as I

        class Attr:
            initializer = I.Constant(0.25)
            trainable = True

        lstm = paddle.nn.LSTM(3, 4, weight_ih_attr=Attr())
        np.testing.assert_allclose(lstm.weight_ih_l0.numpy(), 0.25)

    def test_paged_exceed_max_blocks_raises_cleanly(self):
        cache = PagedKVCache(1, 1, num_blocks=8, block_size=2,
                             num_kv_heads=1, head_dim=4,
                             max_blocks_per_seq=2)
        cache.alloc_slots(0, 0, 4)
        free_before = len(cache._free)
        with pytest.raises(RuntimeError, match="max_blocks_per_seq"):
            cache.alloc_slots(0, 4, 1)
        assert len(cache._free) == free_before  # no leaked block

    def test_cache_attention_additive_mask_convention(self):
        B, T, KV, H, D = 1, 4, 1, 2, 4
        rng = np.random.RandomState(0)
        q = Tensor(rng.rand(B, 1, H, D).astype(np.float32))
        kc = Tensor(rng.rand(B, T, KV, D).astype(np.float32))
        vc = Tensor(rng.rand(B, T, KV, D).astype(np.float32))
        pos = Tensor(jnp.asarray(3, jnp.int32))
        add_mask = np.zeros((1, 1, 1, T), np.float32)
        add_mask[..., 0] = -1e9          # drop slot 0
        bool_mask = np.ones((1, 1, 1, T), bool)
        bool_mask[..., 0] = False
        out_add = call_op("cache_attention", q, kc, vc, pos,
                          Tensor(add_mask)).numpy()
        out_bool = call_op("cache_attention", q, kc, vc, pos,
                           Tensor(bool_mask)).numpy()
        np.testing.assert_allclose(out_add, out_bool, rtol=1e-5)

    def test_rope_interleaved_style(self):
        import jax.numpy as jnp_
        q = paddle.to_tensor(np.random.RandomState(1).rand(1, 3, 1, 4)
                             .astype(np.float32))
        cos = paddle.to_tensor(np.random.RandomState(2).rand(3, 4)
                               .astype(np.float32))
        sin = paddle.to_tensor(np.random.RandomState(3).rand(3, 4)
                               .astype(np.float32))
        out = call_op("rope", q, None, cos=cos, sin=sin,
                      rotate_half_style=False)
        # manual GPT-J interleaved reference
        c = np.repeat(cos.numpy()[:, :2], 2, axis=-1)[None, :, None, :]
        s = np.repeat(sin.numpy()[:, :2], 2, axis=-1)[None, :, None, :]
        x = q.numpy()
        rot = np.stack([-x[..., 1::2], x[..., ::2]], axis=-1).reshape(x.shape)
        np.testing.assert_allclose(out.numpy(), x * c + rot * s, rtol=1e-5)

# multi-device / subprocess / long-compile module (`-m "not heavy"` skips)
import pytest as _pytest_mark  # noqa: E402
pytestmark = _pytest_mark.mark.heavy
