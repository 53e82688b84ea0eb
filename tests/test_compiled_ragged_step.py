"""The ragged step's model call as one XLA program that owns its pools
(ISSUE 30).

`ContinuousBatchingEngine.step` hands the model a `_RaggedView` that names
the engine's `_StepProgram`; the forward runs as that one donated program,
traced once a geometry, whatever stands at ``eng.model``. These cases hold
the program to the tokens the per-op path gave (the uncached ``generate``
for a float32 pool, schedule independence and exact speculation for every
pool), to its launch count, to one trace a geometry, all on the first step,
and to the pools' hand-over.

Since ISSUE 32 the program has two geometries, half the token budget and the
budget (``eng.geometries``), and a step runs the smaller that holds what the
scheduler packed: the cases below the logits' hold it to the choice, to the
counter and span attribute that report it, and to the same tokens and logits
in either.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.autograd.engine import no_grad
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import tracing

VOCAB = 128


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64,
                      intermediate_size=160, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _metric(name):
    m = obs_metrics.registry().get(name)
    return 0 if m is None else (m.value or 0)


def _generate(model, prompt, n_new):
    """Greedy tokens of the plain decode loop over the contiguous cache:
    no pool, no packing, no compiled step."""
    ids = Tensor(jnp.asarray(np.asarray(prompt, np.int32)[None]))
    out = model.generate(ids, max_new_tokens=n_new, temperature=0.0)
    return list(np.asarray(out._data)[0, len(prompt):])


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).tolist() for n in lengths]


def _step_spans():
    """The attributes of every `serving.step` span on record."""
    return [s.attrs for s in tracing.finished_spans("serving.step")
            if s.name == "serving.step"]


def _step_launches():
    """The ``launches`` attribute of every `serving.step` span on record."""
    return [a["launches"] for a in _step_spans()]


def _serve(model, prompts, n_new, **engine):
    eng = ContinuousBatchingEngine(model, num_blocks=64, block_size=16,
                                   temperature=0.0, **engine)
    rids = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    res = eng.run()
    return eng, [res[r] for r in rids]


@pytest.mark.parametrize("spec_k", [0, 2], ids=["spec0", "spec2"])
@pytest.mark.parametrize("kv_dtype", ["auto", "bf16", "int8"],
                         ids=["float32", "bf16-pool", "int8-pool"])
def test_tokens_and_launches(model, kv_dtype, spec_k):
    # 21- and 37-token prompts in chunks of 8 beside decode rows: every
    # step is the same program, and launches it, the logits' reshape,
    # gather and sampling
    prompts = _prompts(31, (5, 21, 9, 37))
    tracing.clear()
    eng, got = _serve(model, prompts, 7, max_batch=3, token_budget=16,
                      prefill_chunk=8, kv_dtype=kv_dtype,
                      speculative_k=spec_k)
    launches = _step_launches()
    assert len(launches) == eng.steps > 8
    assert set(launches) == {launches[0]} and launches[0] <= 4, launches
    # another schedule, speculation off: the same tokens, whatever the pool
    # rounds to (per-token quantization and exact-match verification)
    _, other = _serve(model, prompts, 7, max_batch=2, token_budget=24,
                      prefill_chunk=16, kv_dtype=kv_dtype, speculative_k=0)
    assert got == other
    if kv_dtype == "auto":
        assert got == [_generate(model, p, 7) for p in prompts]


def test_one_trace_through_prefill_decode_preemption_and_cow(model):
    # one trace and one executable a geometry, all of them after the first
    # step, none later
    tracing.clear()
    want_a = _generate(model, [3, 4, 5], 24)
    want_b = _generate(model, [9, 8, 7], 24)
    want_c = _generate(model, [7, 8, 9], 6)
    traces0, cow0 = _metric("serving.step.traces"), _metric("serving.cow_copies")
    # a pool of 3 usable blocks for two requests of 2 blocks each: the
    # starved head preempts the running row, which later resumes by
    # prefilling prompt + what it had generated in chunks of 16, beside
    # the other row's decode tokens
    eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=4,
                                   block_size=16, temperature=0.0,
                                   preempt_after=4)
    a = eng.add_request([3, 4, 5], max_new_tokens=24)
    b = eng.add_request([9, 8, 7], max_new_tokens=24)
    assert eng.geometries == (9, 18)
    eng.step()
    assert _metric("serving.step.traces") - traces0 == 2
    assert all(eng._program.compiled(n) is not None for n in eng.geometries)
    compiles0 = _metric("jit.compiles")
    res = eng.run()
    assert eng.preempt_count >= 1
    assert {a["slots"] for a in _step_spans()} >= {9, 18}
    # the resumed row's 16-token chunks ran the wide program, which the
    # first step (three prompt tokens) had made and not run
    assert _metric("jit.compiles") == compiles0
    assert res[a] == want_a and res[b] == want_b
    # then a row whose partial block another holder has cached: its next
    # write copies the block first
    c = eng.add_request([7, 8, 9], max_new_tokens=6)
    eng.step()
    req = eng.results[c]
    blk = int(eng.cache.block_tables[req.slot, req.ctx // 16])
    eng._pc.register(b"held-elsewhere", blk)
    eng._pc.acquire(blk)
    assert eng.run()[c] == want_c
    assert _metric("serving.cow_copies") > cow0
    assert _metric("serving.step.traces") - traces0 == 2


class _Tap:
    """Stands where the engine holds its model, as chipbench's check does,
    and keeps what each call returned."""

    def __init__(self, model):
        self._model = model
        self.config = model.config
        self.logits = []

    def __call__(self, *args, **kwargs):
        out = self._model(*args, **kwargs)
        self.logits.append(out._data)
        return out


def test_a_tap_at_eng_model_sees_concrete_logits_of_the_same_program(model):
    prompts = _prompts(32, (19, 6))
    tracing.clear()
    plain, want = _serve(model, prompts, 5, max_batch=2, token_budget=12,
                         prefill_chunk=8)
    plain_launches = _step_launches()
    tracing.clear()
    traces0 = _metric("serving.step.traces")
    eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=64,
                                   block_size=16, temperature=0.0,
                                   token_budget=12, prefill_chunk=8)
    tap = _Tap(model)
    eng.model = tap
    rids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    res = eng.run()
    assert [res[r] for r in rids] == want
    assert len(tap.logits) == eng.steps == plain.steps
    for logits in tap.logits:
        assert isinstance(logits, jax.Array)
        assert not isinstance(logits, jax.core.Tracer)
        assert logits.shape in [(1, n, VOCAB) for n in eng.geometries]
        assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert _step_launches() == plain_launches
    # engines over one model share its program: under the tap the second
    # engine ran the very executable the first had traced
    assert _metric("serving.step.traces") == traces0


# the largest gap between the engine's logits and the uncached forward's,
# as a share of the largest logit: float32 differs by summation order,
# a bf16 pool by K and V at 8 mantissa bits, an int8 pool by 1/254 of each
# token's absmax
@pytest.mark.parametrize("kv_dtype,tol", [("auto", 1e-5), ("bf16", 1e-2),
                                          ("int8", 2e-2)],
                         ids=["float32", "bf16-pool", "int8-pool"])
def test_logits_at_the_tap_match_the_uncached_forward(model, kv_dtype, tol):
    # a 21-token prompt in chunks of 8 beside another request's decode
    # rows, then 6 decode steps. The request holds row 0, so the first
    # tokens each step packed are its own: its logits at those positions
    prompt, other = _prompts(34, (21, 4))
    eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=64,
                                   block_size=16, temperature=0.0,
                                   token_budget=12, prefill_chunk=8,
                                   kv_dtype=kv_dtype)
    tap = _Tap(model)
    eng.model = tap
    rid = eng.add_request(prompt, max_new_tokens=6)
    eng.add_request(other, max_new_tokens=12)
    req = eng.results[rid]
    rows, slots, ctx_before = [], [], 0
    while not req.done:
        eng.step()
        n = req.ctx - ctx_before        # what this call's launch packed
        rows.append(np.asarray(tap.logits[-1][0, :n], np.float32))
        slots.append(tap.logits[-1].shape[1])
        ctx_before = req.ctx
    # three prefill chunks (the later two beside the other row's decode
    # token), then one token a step: the first two in the 12-slot program,
    # every later step in the 6-slot one. The other row decodes on, so the
    # request's last token is committed by the call after the one that
    # launched it (ISSUE 35), which packs nothing of the request's
    assert [len(r) for r in rows] == [8, 11, 2, 1, 1, 1, 1, 1, 0]
    assert slots == [12, 12, 6, 6, 6, 6, 6, 6, 6]
    got = np.concatenate(rows)
    out = list(req.out_tokens)
    ids = np.asarray(prompt + out[:-1], np.int32)[None]
    with no_grad():
        want = np.asarray(model(Tensor(jnp.asarray(ids)))._data[0],
                          np.float32)
    assert got.shape == want.shape == (26, VOCAB)
    at = np.repeat(slots, [len(r) for r in rows])
    for n in eng.geometries:        # the file's tolerance, in each geometry
        gap = np.abs(got - want)[at == n].max() / np.abs(want).max()
        assert gap <= tol, (n, gap)
    # and the tokens are the argmax of the engine's own rows
    assert out == got[len(prompt) - 1:].argmax(-1).tolist()


# -- the two geometries (ISSUE 32) ---------------------------------------------

POOLS = pytest.mark.parametrize("kv_dtype", ["auto", "bf16", "int8"],
                                ids=["float32", "bf16-pool", "int8-pool"])
SPEC = pytest.mark.parametrize("spec_k", [0, 2], ids=["spec0", "spec2"])


class _Compiles:
    """Lowerings and backend compilations while ``armed``, as chipbench
    counts them inside a measured window."""
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count, self.armed = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


def _smallest(geometries, tokens):
    return min(n for n in geometries if tokens <= n)


@SPEC
@POOLS
def test_a_step_runs_the_smallest_geometry_that_holds_it(model, compiles,
                                                         kv_dtype, spec_k):
    eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=64,
                                   block_size=16, temperature=0.0,
                                   token_budget=16, prefill_chunk=16,
                                   kv_dtype=kv_dtype, speculative_k=spec_k)
    assert eng.geometries == (8, 16)
    tap = _Tap(model)
    eng.model = tap
    fits, over, short = _prompts(41, (8, 9, 3))
    slots0 = _metric("serving.step_slots")
    tokens0 = _metric("serving.step_tokens")
    tracing.clear()
    # t = T_small: the whole prompt in the half-width program, which is
    # this engine's first call: both executables exist when it returns
    a = eng.add_request(fits, max_new_tokens=5)
    eng.step()
    assert [(s["tokens"], s["slots"]) for s in _step_spans()] == [(8, 8)]
    assert all(eng._program.compiled(n) is not None for n in (8, 16))
    compiles.count, compiles.armed = 0, True
    try:
        out = eng.run()
        # t = T_small + 1: one token more takes the full width
        b = eng.add_request(over, max_new_tokens=5)
        eng.step()
        assert (_step_spans()[-1]["tokens"], _step_spans()[-1]["slots"]) \
            == (9, 16)
        # and a prompt beside a decoding row: 1 (+ drafts) + 3 tokens
        c = eng.add_request(short, max_new_tokens=4)
        out.update(eng.run())
    finally:
        compiles.armed = False
    assert compiles.count == 0, "a step after the first lowered or compiled"
    spans = _step_spans()
    assert len(spans) == eng.steps == len(tap.logits)
    for sp, logits in zip(spans, tap.logits):
        assert sp["slots"] == _smallest(eng.geometries, sp["tokens"])
        assert logits.shape == (1, sp["slots"], VOCAB)
    assert {sp["slots"] for sp in spans} == {8, 16}
    # the counter beside `serving.step_tokens` adds up the geometries run
    assert _metric("serving.step_slots") - slots0 \
        == sum(sp["slots"] for sp in spans)
    assert _metric("serving.step_tokens") - tokens0 \
        == sum(sp["tokens"] for sp in spans)
    if kv_dtype == "auto":
        assert [out[a], out[b], out[c]] == [_generate(model, fits, 5),
                                            _generate(model, over, 5),
                                            _generate(model, short, 4)]


@SPEC
def test_a_budget_that_cannot_be_halved_keeps_one_geometry(model, compiles,
                                                           spec_k):
    # half the budget must hold a full decode-or-verify step, max_batch x
    # (k + 1) tokens: one short of that and the engine is as before, one
    # program of token_budget slots for every step, traced once
    rows = 3
    budget = 2 * rows * (spec_k + 1) - 1
    prompts = _prompts(42, (11, 4, 7))
    want = [_generate(model, p, 6) for p in prompts]
    traces0 = _metric("serving.step.traces")
    tracing.clear()
    eng = ContinuousBatchingEngine(model, max_batch=rows, num_blocks=48,
                                   block_size=16, temperature=0.0,
                                   token_budget=budget, prefill_chunk=4,
                                   speculative_k=spec_k)
    assert eng.geometries == (budget,)
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    eng.step()
    assert _metric("serving.step.traces") - traces0 == 1
    compiles.count, compiles.armed = 0, True
    try:
        res = eng.run()
    finally:
        compiles.armed = False
    assert compiles.count == 0
    assert _metric("serving.step.traces") - traces0 == 1
    assert [res[r] for r in rids] == want
    spans = _step_spans()
    assert len(spans) == eng.steps
    assert {sp["slots"] for sp in spans} == {budget}
    assert set(_step_launches()) == {4}


@SPEC
@POOLS
def test_a_stream_that_alternates_geometries_gives_the_oracles_tokens(
        model, kv_dtype, spec_k):
    # prompts arrive while others decode, so the stream goes 16, 8, 8, 16,
    # 8, ... slots: greedy tokens are the dense generate()'s (float32 pool)
    # and, whatever the pool rounds to, those of the same stream through an
    # engine held to the full width (what every step ran before ISSUE 32)
    prompts = _prompts(43, (12, 10, 5, 13))
    n_new = (9, 7, 8, 6)

    def stream(full_width_only):
        eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=64,
                                       block_size=16, temperature=0.0,
                                       token_budget=16, prefill_chunk=16,
                                       kv_dtype=kv_dtype,
                                       speculative_k=spec_k)
        if full_width_only:
            eng.geometries = eng.geometries[-1:]
        tracing.clear()
        rids = []
        for p, n in zip(prompts, n_new):
            rids.append(eng.add_request(p, max_new_tokens=n))
            for _ in range(3):
                eng.step()
        res = eng.run()
        return [res[r] for r in rids], [sp["slots"] for sp in _step_spans()]

    got, slots = stream(False)
    want, wide = stream(True)
    assert set(wide) == {16}
    changes = sum(a != b for a, b in zip(slots, slots[1:]))
    assert set(slots) == {8, 16} and changes >= 4, slots
    assert got == want
    assert [len(t) for t in got] == list(n_new)
    if kv_dtype == "auto":
        assert got == [_generate(model, p, n) for p, n in zip(prompts, n_new)]


# -- one step in flight (ISSUE 35) ---------------------------------------------

class _ViewTap(_Tap):
    """A tap that also keeps the view each call was handed."""

    def __init__(self, model):
        super().__init__(model)
        self.views = []

    def __call__(self, *args, cache=None, **kwargs):
        self.views.append(cache)
        return super().__call__(*args, cache=cache, **kwargs)


def test_a_request_alone_sees_one_model_call_a_step_and_ends_in_its_last(
        model):
    # what chipbench's warm-up leans on (`drivers/serve.py:_warm_up`): while
    # a lone request has work every call makes exactly one model call,
    # `req.ctx` grows by what that call packed, and the call that launches
    # the last step returns the request finished
    prompt, = _prompts(51, (21,))
    tracing.clear()
    eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=64,
                                   block_size=16, temperature=0.0,
                                   token_budget=12, prefill_chunk=8)
    tap = _Tap(model)
    eng.model = tap
    rid = eng.add_request(prompt, max_new_tokens=4)
    req = eng.results[rid]
    packed, ctx_before, finished = [], 0, []
    while not req.done:
        assert finished == []
        calls = len(tap.logits)
        finished = eng.step()
        assert len(tap.logits) == calls + 1
        packed.append(req.ctx - ctx_before)
        assert packed[-1] == _step_spans()[-1]["tokens"]
        ctx_before = req.ctx
    assert finished == [req] and eng._inflight is None
    # the prompt in a 12-token step and a 9-token one (a lone row takes
    # chunks until the budget is spent), then the three tokens fed back
    assert packed == [12, 9, 1, 1, 1]
    assert eng.steps == len(packed) == len(tap.logits)
    assert req.out_tokens == _generate(model, prompt, 4)
    assert eng.step() == []


def test_a_launched_step_keeps_the_block_table_it_was_packed_with(model):
    # commit clears a finished row's line of the long-lived host table while
    # the next launch may not have read its upload yet, and an upload on the
    # CPU may alias host memory: the launch takes a snapshot
    eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=64,
                                   block_size=16, temperature=0.0)
    # the CPU backend aliases a host array that starts on a 64-byte line,
    # which numpy's own need not: give the table such a start
    table = eng.cache.block_tables
    raw = np.zeros(table.size + 16, table.dtype)
    off = -raw.ctypes.data % 64 // table.itemsize
    eng.cache.block_tables = raw[off:off + table.size].reshape(table.shape)
    tap = _ViewTap(model)
    eng.model = tap
    eng.add_request(_prompts(52, (20,))[0], max_new_tokens=6)
    eng.step()
    assert eng._inflight is not None
    packed_with = eng.cache.block_tables.copy()
    assert packed_with.any()
    eng.cache.block_tables[:] = 0
    np.testing.assert_array_equal(
        np.asarray(tap.views[-1]._tables._data), packed_with)
    eng.cache.block_tables[:] = packed_with


@SPEC
def test_no_step_compiles_once_the_first_call_returned(model, compiles,
                                                       spec_k):
    # the program's two new inputs follow the geometry (`src`) or nothing
    # (`prev`: zeros of the sampled tokens' shape before any launch, the
    # last launch's device array after): both executables are the first
    # call's, whichever `prev` a later step is handed
    eng = ContinuousBatchingEngine(model, max_batch=2, num_blocks=64,
                                   block_size=16, temperature=0.0,
                                   token_budget=16, prefill_chunk=16,
                                   speculative_k=spec_k)
    tap = _ViewTap(model)
    eng.model = tap
    short, long_ = _prompts(53, (5, 14))
    over0 = _metric("serving.pipeline.overlapped")
    eng.add_request(short, max_new_tokens=6)
    zeros = eng._prev
    eng.step()                          # 8 slots, `prev` the zeros
    assert tap.views[-1]._prev._data is zeros
    compiles.count, compiles.armed = 0, True
    try:
        eng.step()                      # 8 slots, `prev` a real sample
        eng.add_request(long_, max_new_tokens=6)
        eng.step()                      # 16 slots, ids from both sources
        eng.run()
        eng.add_request(long_, max_new_tokens=3)
        eng.run()                       # a pipeline that starts again
    finally:
        compiles.armed = False
    assert compiles.count == 0, "a step after the first lowered or compiled"
    assert {v._slots._data.shape[0] for v in tap.views} == {8, 16}
    # every later launch was handed the tokens the one before it sampled
    assert all(v._prev._data is not zeros for v in tap.views[1:])
    assert len({id(v._prev._data) for v in tap.views}) == len(tap.views)
    took = [int((np.asarray(v._src._data) >= 0).sum()) for v in tap.views]
    if spec_k:
        assert _metric("serving.pipeline.overlapped") == over0
        assert set(took) == {0}         # depth 0: every id from the host
    else:
        assert _metric("serving.pipeline.overlapped") > over0
        assert took[0] == 0 and max(took) == 2


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"],
                         ids=["float32", "int8-pool"])
def test_pools_are_handed_over_and_a_block_still_copies(model, kv_dtype):
    eng = ContinuousBatchingEngine(model, max_batch=1, num_blocks=16,
                                   block_size=16, temperature=0.0,
                                   kv_dtype=kv_dtype)
    rid = eng.add_request([7, 8, 9, 10, 11], max_new_tokens=6)
    n_pools = (4 if kv_dtype == "int8" else 2) * eng.cache.num_layers
    held = eng.cache.pools()
    assert len(held) == n_pools
    eng.step()
    # the step program took the arrays it was given and the cache holds
    # the ones it gave back
    if held[0].is_deleted():         # this backend donates
        assert all(a.is_deleted() for a in held)
    now = eng.cache.pools()
    assert not any(a.is_deleted() for a in now)
    assert all(a is not b for a, b in zip(held, now))
    # copy-on-write reads the pools of after the step
    req = eng.results[rid]
    blk = int(eng.cache.block_tables[req.slot, 0])
    before = [np.asarray(a[blk]) for a in now]
    assert any(np.abs(b.astype(np.float32)).sum() > 0 for b in before)
    eng._pc.register(b"held-elsewhere", blk)
    eng._pc.acquire(blk)
    eng._ensure_writable(req.slot, 0)
    fresh = int(eng.cache.block_tables[req.slot, 0])
    assert fresh != blk
    for a, want in zip(eng.cache.pools(), before):
        np.testing.assert_array_equal(np.asarray(a[fresh]), want)
        np.testing.assert_array_equal(np.asarray(a[blk]), want)
    # and the next step donates what the copy left in the cache
    held = eng.cache.pools()
    eng.step()
    assert not any(a.is_deleted() for a in eng.cache.pools())
    if held[0].is_deleted():
        assert all(a.is_deleted() for a in held)
    # the copy changed where the row's tokens live, not what they are
    _, (want,) = _serve(model, [[7, 8, 9, 10, 11]], 6, max_batch=1,
                        kv_dtype=kv_dtype)
    assert eng.run()[rid] == want


def test_two_threads_trace_one_shared_model(model):
    # replicas of a fleet share their model: while one thread traces its
    # program the parameters hold tracers, which the other must never read
    prompts = _prompts(33, (11, 4))
    want = [_generate(model, p, 6) for p in prompts]
    got, errors = {}, []
    gate = threading.Barrier(2)

    def replica(k):
        try:
            gate.wait(timeout=60)
            got[k] = _serve(model, prompts, 6, max_batch=2,
                            token_budget=8 + 4 * k, prefill_chunk=4)[1]
        except Exception as e:          # a leaked tracer raises here
            errors.append(e)

    threads = [threading.Thread(target=replica, args=(k,)) for k in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert got == {0: want, 1: want}
