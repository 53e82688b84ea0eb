"""What decides ``correct`` in ``serve-chat-steady``, driven at a size a
test can hold, with the harness's look for a chip skipped (a rehearsal).

The rehearsal's model is float32, so its control is the reference in
bfloat16; on the chip the configuration is bfloat16 and the control int8 and
fp8 (``PERF.md`` has those readings). The sound run passes, the control
fails ``served_gap``, and a token altered where it is produced makes the
whole run come out not correct."""

import os
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def context(seed, control=False):
    """``serve-chat-steady``'s rehearsal with answers long enough that some
    hundred served tokens are compared."""
    from chipbench import run as cli
    from chipbench.drivers.common import Context
    cell = cli.load_json("workloads", "serve-chat-steady.json")
    config = cli.load_json("configs", cell["config"] + ".json")
    cell["rehearse"]["traffic"]["output_tokens"] = {
        "dist": "uniform", "min": 24, "max": 48}
    cell["rehearse"]["check"]["served"]["requests"] = 8
    config["rehearse"]["model"]["max_position_embeddings"] = 256
    return Context(cell_name="served-check", cell=cell, config=config,
                   seed=seed, seconds=3.0, trace=False, rehearse=True,
                   t_process=time.perf_counter(),
                   scratch=os.path.join(ROOT, ".chipbench_tmp"),
                   device_kind="cpu", control=control)


@pytest.fixture(scope="module")
def sound():
    """One sound run with its control, for the two tests that read it."""
    from chipbench.drivers import serve
    seen = []
    ctx = context(2 ** 31 + 17, control=True)
    ctx.emit = lambda event, **fields: seen.append((event, fields))
    return serve.run(ctx), dict(seen)


def test_a_sound_run_is_correct_and_says_what_it_compared(sound):
    result, events = sound
    assert result.correct and result.failed == 0
    number, limit = result.compared["served_gap"]
    assert 0.0 <= number <= limit
    assert set(result.compared) == {"logit_gap", "compiles_in_window",
                                    "served_gap"}
    found = events["served_check"]
    assert found["requests"] == 8 and found["served_tokens"] >= 200
    # the longest finished request is in the sample
    assert found["longest_tokens"] >= 24 + 4


def test_the_control_fails_the_served_gap(sound):
    """The reference in bfloat16, put in the program's place: the tokens it
    puts first lie further below the float32 reference's best than the
    limit allows, and at least three times further than the program's."""
    result, events = sound
    found = events["served_check"]
    assert found["control_gap_bf16"] > found["served_gap_tol"]
    assert found["control_gap_bf16"] > 3 * found["served_gap"]


def test_an_altered_token_makes_the_run_not_correct(monkeypatch):
    """The fault a serving cell can have: a token altered where it is
    produced. From the twentieth sampling call on, the first lane's token is
    the next one in the vocabulary."""
    from paddle_tpu.models.serving import ContinuousBatchingEngine
    from chipbench.drivers import serve
    sample = ContinuousBatchingEngine._sample
    calls = []

    def altered(self, logits, *tail):
        nxt = sample(self, logits, *tail)
        calls.append(1)
        if len(calls) >= 20:
            data = nxt._data
            nxt._set_data(data.at[0].set((data[0] + 1) % logits.shape[-1]))
        return nxt

    monkeypatch.setattr(ContinuousBatchingEngine, "_sample", altered)
    ctx = context(2 ** 31 + 17)
    result = serve.run(ctx)
    assert len(calls) > 40
    number, limit = result.compared["served_gap"]
    assert number > 100 * limit
    assert result.correct is False
    # nothing else noticed: the tokens are in the vocabulary, none failed
    assert result.failed == 0
    assert result.compared["logit_gap"][0] <= result.compared["logit_gap"][1]
