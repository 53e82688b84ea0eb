"""A model whose layers keep row state, served through
`ContinuousBatchingEngine` (ISSUE 34): Jamba's Mamba-1 layers beside its
attention layers, tiny widths, float32, seeded.

Everything is held against the plain reference, `chipbench/reference_jamba.py`
(the repo's one copy: the tests run from the root, so `chipbench` is on the
path), and logits are compared, not tokens. The layer pattern here has
period 4 and offset 1 over 8 layers: both kinds of layer, and both orders
of neighbour. The inner width is 128, so the Pallas kernels run (in
interpret mode) inside the engine's step program.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import (PagedKV, PagedKVCache, RowState,
                                          layer_states)
from paddle_tpu.models.jamba import JambaForCausalLM
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import tracing
from paddle_tpu.ops.kernels import serving as serving_kernels
from paddle_tpu.ops.kernels.pallas import ragged_selective_scan as rss

from chipbench import reference_jamba
from chipbench.families import jamba as family

SIZES = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=1,
    attn_layer_period=4, attn_layer_offset=1, mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
    max_position_embeddings=256, rms_norm_eps=1e-6,
    tie_word_embeddings=True, torch_dtype="float32",
    # wider than the published 0.02, so that a state gone wrong moves logits
    initializer_range=0.2)
ENGINE = dict(max_batch=4, num_blocks=40, block_size=16, token_budget=48,
              prefill_chunk=16)
# float32 both sides and logits that reach 7: the engine's runs read up to
# 4e-4, a row that starts from another row's state reads 8
TOL = 2e-3


@pytest.fixture(scope="module")
def built():
    model, cfg, weights = family.build_model(SIZES, 3)
    model.eval()
    return model, weights


class _Tap:
    """Stands where the engine holds its model and keeps every packed
    token's logits under (request, position)."""

    def __init__(self, eng):
        self.eng = eng
        self.model = eng.model
        self.config = eng.model.config
        self.got = {}           # rid -> {position: logits [V]}
        eng.model = self

    def __call__(self, ids, cache=None, start_pos=None):
        out = self.model(ids, cache=cache, start_pos=start_pos)
        cu = np.asarray(cache._cu._data)
        lens = np.asarray(cache._lens._data)
        logits = np.asarray(out._data[0])
        for i, req in enumerate(self.eng.slots):
            n = int(cu[i + 1] - cu[i])
            if req is None or n == 0:
                continue
            first = int(lens[i]) - n
            rows = self.got.setdefault(req.rid, {})
            for j in range(n):
                rows[first + j] = logits[cu[i] + j]
        return out


def _engine(model, width, **over):
    eng = ContinuousBatchingEngine(model, **{**ENGINE, **over})
    assert len(eng.geometries) == 2
    if width == "budget":           # every step runs the full-width program
        eng.geometries = eng.geometries[-1:]
    return eng, _Tap(eng)


def _check(tap, weights, rid, prompt, out):
    """Every position the engine computed for ``rid``, against the
    reference's forward over the prompt and the tokens it then fed."""
    ids = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    want = np.asarray(reference_jamba.logits(SIZES, weights, ids,
                                             query_block=64))
    got = tap.got[rid]
    assert sorted(got) == list(range(len(ids)))
    gap = max(float(np.max(np.abs(got[p] - want[p]))) for p in got)
    assert gap < TOL, gap


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n)


def test_whole_sequence_forward_matches_the_reference(built):
    model, weights = built
    ids = np.stack([_prompt(0, 37), _prompt(1, 37)])
    got = np.asarray(model(Tensor(jnp.asarray(ids, jnp.int32)))._data)
    for b in range(2):
        want = np.asarray(reference_jamba.logits(SIZES, weights, ids[b],
                                                 query_block=16))
        assert float(np.max(np.abs(got[b] - want))) < TOL


WIDTHS = pytest.mark.parametrize("width", ["both", "budget"])


@WIDTHS
def test_chunked_prefill_over_three_chunks_then_decode(built, width):
    model, weights = built
    # a step holds one chunk: the prompt goes in as 16, 16 and 8 tokens,
    # the state carried from chunk to chunk
    eng, tap = _engine(model, width, token_budget=16)
    prompt = _prompt(2, 40)
    rid = eng.add_request(prompt, max_new_tokens=5)
    out = eng.run()[rid]
    assert eng.steps == 3 + 4
    _check(tap, weights, rid, prompt, out)


@WIDTHS
def test_request_admitted_beside_decoding_rows(built, width):
    model, weights = built
    eng, tap = _engine(model, width)
    first, second = _prompt(3, 9), _prompt(4, 20)
    a = eng.add_request(first, max_new_tokens=8)
    for _ in range(3):
        eng.step()
    b = eng.add_request(second, max_new_tokens=4)
    out = eng.run()
    _check(tap, weights, a, first, out[a])
    _check(tap, weights, b, second, out[b])


@WIDTHS
def test_row_slot_reused_starts_from_zero(built, width):
    # one row slot: the second request runs where the first one's state
    # still lies, and must not see it
    model, weights = built
    eng, tap = _engine(model, width, max_batch=1)
    first, second = _prompt(5, 21), _prompt(6, 18)
    a = eng.add_request(first, max_new_tokens=4)
    b = eng.add_request(second, max_new_tokens=4)
    out = eng.run()
    _check(tap, weights, a, first, out[a])
    _check(tap, weights, b, second, out[b])


@WIDTHS
def test_forced_preemption_and_resume(built, width):
    model, weights = built
    eng, tap = _engine(model, width)
    first, second = _prompt(7, 12), _prompt(8, 19)
    a = eng.add_request(first, max_new_tokens=9)
    b = eng.add_request(second, max_new_tokens=9)
    for _ in range(5):
        eng.step()
    before = dict(tap.got[b])
    eng._preempt_lifo()                         # b: admitted last
    assert eng.preempt_count == 1 and eng.results[b].ctx == 0
    out = eng.run()
    _check(tap, weights, a, first, out[a])
    _check(tap, weights, b, second, out[b])
    # the resumed row computed its first positions again, from a zero state
    assert all(np.max(np.abs(tap.got[b][p] - before[p])) < TOL
               for p in before)


@WIDTHS
def test_step_that_is_all_padding_but_one_row(built, width):
    model, weights = built
    eng, tap = _engine(model, width)
    prompt = _prompt(9, 3)
    rid = eng.add_request(prompt, max_new_tokens=4)
    out = eng.run()[rid]
    _check(tap, weights, rid, prompt, out)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "keyed"])
def test_one_step_in_flight_gives_the_serial_tokens(built, temperature):
    # ISSUE 35: step N+1 is launched before step N's tokens are read, its
    # decode ids taken on the device, and the in-place state updates follow
    # launch order like the pool writes. Prompts over a chunk, a request
    # that arrives mid-run into a row another one left: token for token
    # what the same engine gives when every step is committed in the call
    # that launched it, and (greedy) the reference's logits
    model, weights = built
    prompts = [_prompt(21, 40), _prompt(22, 7), _prompt(23, 19)]
    late = _prompt(24, 26)
    got = {}
    for serial in (False, True):
        eng, tap = _engine(model, "both", max_batch=3, token_budget=24,
                           temperature=temperature, seed=11)
        over0 = _counter("serving.pipeline.overlapped")
        rids = [eng.add_request(p, max_new_tokens=n)
                for p, n in zip(prompts, (6, 3, 9))]
        calls = 0
        while eng.pending or eng.num_active:
            if calls == 4:
                rids.append(eng.add_request(late, max_new_tokens=5))
            eng.step()
            if serial:
                eng._drain()
            calls += 1
        got[serial] = [list(eng.results[r].out_tokens) for r in rids]
        over = _counter("serving.pipeline.overlapped") - over0
        assert over == 0 if serial else over >= eng.steps - 2
        if temperature == 0.0 and not serial:
            for r, p in zip(rids, prompts + [late]):
                _check(tap, weights, r, p, eng.results[r].out_tokens)
    assert got[False] == got[True]
    assert [len(t) for t in got[False]] == [6, 3, 9, 5]


# -- the two ragged ops -------------------------------------------------------

# rows' token counts and first positions: decode rows alone; a chunk that
# starts at position 0; a chunk that continues; rows without a token
CASES = {
    "segments_of_one": ([1, 1, 1, 1], [5, 9, 1, 30]),
    "chunk_from_zero": ([1, 6, 1], [4, 0, 2]),
    "chunk_continues": ([7, 1], [16, 3]),
    "empty_rows": ([0, 3, 0, 1, 0], [0, 8, 0, 2, 0]),
}
D, N, K = 256, 16, 4


def _case(name):
    qlen, pos0 = CASES[name]
    rows = len(qlen)
    cu = np.concatenate([[0], np.cumsum(qlen)]).astype(np.int32)
    tokens = int(cu[-1]) + 3                    # three slots of step padding
    rng = np.random.default_rng(sum(map(ord, name)))

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return dict(
        x=draw(tokens, D), dt=draw(tokens, D), z=draw(tokens, D),
        B=draw(tokens, N), C=draw(tokens, N), D=draw(D),
        A_log=jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None]
        * jnp.ones((1, D)),
        w=draw(K, D), b=draw(D), cu=jnp.asarray(cu),
        slots=jnp.asarray(np.where(np.asarray(qlen) > 0, np.arange(rows),
                                   rows), jnp.int32),
        pos0=jnp.asarray(pos0, jnp.int32),
        state=draw(rows + 1, N, D // 128, 128),
        tail=draw(rows + 1, K - 1, D // 128, 128))


def _scan_args(c):
    return (c["x"], c["dt"], c["B"], c["C"], c["z"], c["A_log"], c["D"],
            c["cu"], c["slots"], c["pos0"], c["state"])


def _conv_args(c):
    return (c["x"], c["w"], c["b"], c["cu"], c["slots"], c["pos0"],
            c["tail"])


def _scan_row_loop(c):
    """The recurrence row by row and token by token, in numpy."""
    x, dt, z, B, C = (np.asarray(c[k], np.float64)
                      for k in ("x", "dt", "z", "B", "C"))
    a = -np.exp(np.asarray(c["A_log"], np.float64))
    state = np.asarray(c["state"], np.float64).reshape(-1, N, D).copy()
    cu, pos0 = np.asarray(c["cu"]), np.asarray(c["pos0"])
    y = np.zeros_like(x)
    for r in range(len(pos0)):
        if cu[r + 1] == cu[r]:
            continue
        s = np.zeros((N, D)) if pos0[r] == 0 else state[r]
        for t in range(cu[r], cu[r + 1]):
            delta = np.log1p(np.exp(dt[t]))
            s = np.exp(delta[None] * a) * s + (delta * x[t])[None] * B[t][:, None]
            y[t] = ((s * C[t][:, None]).sum(0) + np.asarray(c["D"]) * x[t]) \
                * z[t] / (1 + np.exp(-z[t]))
        state[r] = s
    return y, state.reshape(c["state"].shape)


def _conv_row_loop(c):
    x = np.asarray(c["x"], np.float64)
    w, b = np.asarray(c["w"], np.float64), np.asarray(c["b"], np.float64)
    tail = np.asarray(c["tail"], np.float64).reshape(-1, K - 1, D).copy()
    cu, pos0 = np.asarray(c["cu"]), np.asarray(c["pos0"])
    y = np.zeros_like(x)
    for r in range(len(pos0)):
        if cu[r + 1] == cu[r]:
            continue
        window = np.zeros((K - 1, D)) if pos0[r] == 0 else tail[r]
        for t in range(cu[r], cu[r + 1]):
            window = np.concatenate([window, x[t][None]])
            acc = b + (window * w).sum(0)
            y[t] = acc / (1 + np.exp(-acc))
            window = window[1:]
        tail[r] = window
    return y, tail.reshape(c["tail"].shape)


def _same(got, want, rows):
    (y, state), (y_want, state_want) = got, want
    assert np.max(np.abs(np.asarray(y) - y_want)) < 1e-4
    # the cache's last row is the step padding's: any value will do there
    assert np.max(np.abs(np.asarray(state)[:rows] - state_want[:rows])) < 1e-4


@pytest.mark.parametrize("case", sorted(CASES))
def test_scan_composite_matches_a_row_loop(case):
    c = _case(case)
    _same(jax.jit(serving_kernels._scan_composite)(*_scan_args(c)),
          _scan_row_loop(c), len(CASES[case][0]))


@pytest.mark.parametrize("case", sorted(CASES))
def test_conv_composite_matches_a_row_loop(case):
    c = _case(case)
    _same(jax.jit(serving_kernels._conv_composite)(*_conv_args(c)),
          _conv_row_loop(c), len(CASES[case][0]))


@pytest.mark.parametrize("case", sorted(CASES))
def test_scan_kernel_matches_the_composite(case):
    c = _case(case)
    y, state = jax.jit(rss.ragged_selective_scan)(*_scan_args(c))
    _same((y, state), [np.asarray(a) for a in jax.jit(
        serving_kernels._scan_composite)(*_scan_args(c))],
        len(CASES[case][0]))
    # a row without a token is neither read nor written
    for r, n in enumerate(CASES[case][0]):
        if n == 0:
            assert np.array_equal(np.asarray(state[r]),
                                  np.asarray(c["state"][r]))


@pytest.mark.parametrize("case", sorted(CASES))
def test_conv_kernel_matches_the_composite(case):
    c = _case(case)
    y, tail = jax.jit(rss.ragged_causal_conv)(*_conv_args(c))
    _same((y, tail), [np.asarray(a) for a in jax.jit(
        serving_kernels._conv_composite)(*_conv_args(c))],
        len(CASES[case][0]))
    for r, n in enumerate(CASES[case][0]):
        if n == 0:
            assert np.array_equal(np.asarray(tail[r]),
                                  np.asarray(c["tail"][r]))


# -- the layout seam ----------------------------------------------------------

@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128))
    m.eval()
    return m


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_model_that_declares_nothing_gets_todays_pools(llama, kv_dtype):
    # guards the Mistral cells: K and V (and an int8 pool's scales) for
    # every layer, in this order, and nothing else
    assert layer_states(llama) == (PagedKV(2, 16),) * 3
    eng = ContinuousBatchingEngine(llama, max_batch=2, num_blocks=9,
                                   block_size=8, kv_dtype=kv_dtype)
    names = (("k", "v", "k_scale", "v_scale") if kv_dtype == "int8"
             else ("k", "v"))
    store = jnp.int8 if kv_dtype == "int8" else jnp.float32
    assert eng.cache.pool_names == names and not eng.recurrent
    want = [((9, 8, 2, 16), store)] * 6
    if kv_dtype == "int8":
        want += [((9, 8, 2), jnp.float32)] * 6
    assert [(a.shape, a.dtype) for a in eng.cache.pools()] == want
    again = PagedKVCache.over(eng.cache.spec, eng.cache.pools())
    assert again.pool_names == names and again.num_layers == 3
    assert all(a is b for a, b in zip(again.pools(), eng.cache.pools()))


def test_jamba_declares_two_kinds_of_state(built):
    model, _ = built
    eng = ContinuousBatchingEngine(model, **ENGINE)
    kinds = [type(l) for l in layer_states(model)]
    assert kinds == [RowState, PagedKV, RowState, RowState] * 2
    cache = eng.cache
    assert cache.pool_names == ("k", "v", "conv", "ssm") and eng.recurrent
    assert [len(l) for l in cache.pool_lists()] == [2, 2, 6, 6]
    # a row-state array holds every row and one more, for step padding
    assert cache.row(0, "ssm").shape == [5, 16, 1, 128]
    assert cache.row(7, "conv").shape == [5, 3, 1, 128]
    assert cache.row(2, "ssm") is cache.row_state["ssm"][1]
    assert cache.kv(5)[0] is cache.k[1]
    # attention layers alone count towards a token's bytes
    assert cache.kv_bytes_per_token() == 2 * 2 * 16 * 4
    gauge = obs_metrics.registry().get("serving.state.bytes")
    assert gauge.value == cache.row_state_bytes() == 6 * 5 * 19 * 128 * 4


def test_single_kv_head_is_pooled_in_float32():
    # a bfloat16 pool packs KV heads in pairs on the chip: one head alone
    # would be padded to two, so it is kept in float32, the same bytes
    def pool(heads):
        return PagedKVCache(1, 1, num_blocks=2, block_size=8,
                            num_kv_heads=heads, head_dim=16,
                            max_blocks_per_seq=2, dtype="bfloat16")

    assert pool(1).k[0]._data.dtype == jnp.float32
    assert pool(2).k[0]._data.dtype == jnp.bfloat16
    assert pool(1).kv_bytes_per_token() == pool(2).kv_bytes_per_token()


# -- the scheduler's rules for a model with row state -------------------------

def _counter(name):
    return obs_metrics.registry().get(name).value


def test_no_prefix_hit_is_taken_and_the_counter_says_why(built):
    model, weights = built
    eng, tap = _engine(model, "both")
    shared = _prompt(10, 35)                    # two full blocks of 16
    skipped, hits = (_counter("serving.prefix.skipped_recurrent"),
                     _counter("serving.prefix_cache.hit_blocks"))
    a = eng.add_request(shared, max_new_tokens=3)
    eng.run()
    b = eng.add_request(shared, max_new_tokens=3)
    out = eng.run()
    assert _counter("serving.prefix.skipped_recurrent") == skipped + 2
    assert _counter("serving.prefix_cache.hit_blocks") == hits
    assert len(eng._pc) == 0                    # and nothing was registered
    _check(tap, weights, b, shared, out[b])     # every token was prefilled


def test_speculation_is_refused_at_construction(built):
    model, _ = built
    with pytest.raises(ValueError, match="cannot be taken back out"):
        ContinuousBatchingEngine(model, speculative_k=2, **ENGINE)


def test_preempted_row_restarts_from_zero(built):
    model, _ = built
    eng, _ = _engine(model, "both")
    resets = _counter("serving.state.resets")
    rid = eng.add_request(_prompt(11, 20), max_new_tokens=6)
    for _ in range(3):
        eng.step()
    assert _counter("serving.state.resets") == resets + 1   # admission
    eng._preempt_lifo()
    assert eng.results[rid].ctx == 0
    eng.step()
    assert _counter("serving.state.resets") == resets + 2   # resume
    steps = [s for s in tracing.finished_spans("serving.step")
             if s.name == "serving.step"][-4:]
    assert [s.attrs["state_rows"] for s in steps] == [1, 1, 1, 1]
    # the prompt; two decode steps; the prompt and two answers again
    assert [s.attrs["scan_tokens"] for s in steps] == [20, 1, 1, 22]


def test_step_span_of_a_model_without_row_state_is_as_it_was(llama):
    eng = ContinuousBatchingEngine(llama, max_batch=2, num_blocks=9,
                                   block_size=8)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.run()
    last = [s for s in tracing.finished_spans("serving.step")
            if s.name == "serving.step"][-1]
    assert "state_rows" not in last.attrs and "scan_tokens" not in last.attrs


def test_generate_refuses_a_model_with_row_state(built):
    model, _ = built
    with pytest.raises(NotImplementedError, match="ContinuousBatchingEngine"):
        model.generate(Tensor(jnp.zeros((1, 4), jnp.int32)), max_new_tokens=2)
