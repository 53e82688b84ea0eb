"""``itl_over_half_budget_share`` and the gaps it is a share of, on a
hand-made phase of steps and stamps."""

import types

from chipbench.drivers import serve


def sent(*stamps):
    return types.SimpleNamespace(stamps=list(stamps))


def test_gaps_carry_the_stamp_of_their_later_token():
    # a window [10, 20): a token before it opens a gap, one after is out
    tokens, gaps, stamps = serve.window_tokens(
        [sent(9.0, 10.5, 11.0, 20.5), sent(12.0), sent(13.0, 13.25)],
        10.0, 20.0)
    assert tokens == 5
    assert [round(g) for g in gaps] == [1500, 500, 250]
    assert stamps == [10.5, 11.0, 13.25]


def test_share_of_gaps_from_steps_that_packed_over_the_small_geometry():
    steps = [{"t_end": 1.0, "tokens": 7}, {"t_end": 2.0, "tokens": 256},
             {"t_end": 3.0, "tokens": 257}, {"t_end": 4.0, "tokens": 512}]
    # ten gap samples: 3 from the 257-token step, 1 from the 512-token step
    stamps = [1.0] * 2 + [2.0] * 4 + [3.0] * 3 + [4.0]
    assert serve.over_half_budget_share(stamps, steps, 256) == 40.0
    # one geometry only (the budget itself): no step can pack more
    assert serve.over_half_budget_share(stamps, steps, 512) == 0.0
    assert serve.over_half_budget_share([], steps, 256) is None
