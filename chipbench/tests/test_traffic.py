"""The generator: the same seed gives the same schedule, another seed the
same work in another order."""

import numpy as np

from chipbench import traffic

SPEC = {"arrivals": "open", "rate_per_s": 2.0,
        "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                          "min": 64, "max": 3072},
        "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                          "min": 16, "max": 512}}


def plan(seed, spec=SPEC, seconds=50, **kw):
    return traffic.plan(spec, seconds, 32768, np.random.default_rng(seed), **kw)


def same(a, b):
    return (len(a) == len(b) and all(
        x.due_s == y.due_s and x.output_tokens == y.output_tokens
        and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b)))


def test_same_seed_same_schedule():
    assert same(plan(2 ** 31 + 7), plan(2 ** 31 + 7))


def test_another_seed_another_order_of_the_same_work():
    a, b = plan(1), plan(2)
    assert not same(a, b)
    assert len(a) == len(b) == 100
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert sorted(p.output_tokens for p in a) == sorted(p.output_tokens for p in b)
    assert abs(a[-1].due_s - b[-1].due_s) < 1e-9     # the gaps' sum is fixed


def test_every_five_hold_one_of_each_fifth():
    vals = np.arange(125) * 3.0
    out = traffic.spread_out(vals, np.random.default_rng(4))
    assert sorted(out) == sorted(vals)
    for i in range(0, 125, 5):
        assert sorted(int(v // 75) for v in out[i:i + 5]) == [0, 1, 2, 3, 4]
    odd = traffic.spread_out(np.arange(7), np.random.default_rng(4))
    assert sorted(odd) == list(range(7))


def test_open_loop_shape():
    a = plan(3)
    due = [p.due_s for p in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 50
    prompts = [len(p.prompt) for p in a]
    outs = [p.output_tokens for p in a]
    assert min(prompts) >= 64 and max(prompts) <= 3072
    assert min(outs) >= 16 and max(outs) <= 512
    assert 450 < np.median(prompts) < 580 and 110 < np.median(outs) < 150
    assert all(0 <= int(t) < 32768 for p in a for t in p.prompt[:4])


def test_bursty_gaps_offer_the_same_load():
    bursty = traffic.open_gaps(2.0, 100, np.random.default_rng(0), cv=3.0)
    assert abs(bursty.sum() - 50.0) < 1e-9
    assert bursty.std() / bursty.mean() > 1.5


def test_closed_loop_and_shared_prefix():
    spec = {**SPEC, "arrivals": "closed", "clients": 4,
            "prefix": {"groups": 2, "tokens": 128}}
    a = plan(5, spec, requests=12)
    assert len(a) == 12 and all(p.due_s is None for p in a)
    heads = {tuple(p.prompt[:128]) for p in a}
    assert len(heads) == 2


def test_training_batches_repeat_for_a_seed():
    a = traffic.token_batches(9, 256, 1, 64)
    b = traffic.token_batches(9, 256, 1, 64)
    c = traffic.token_batches(10, 256, 1, 64)
    first = next(a)
    assert first.shape == (1, 64) and np.array_equal(first, next(b))
    assert not np.array_equal(first, next(c))
    assert not np.array_equal(first, next(a))
