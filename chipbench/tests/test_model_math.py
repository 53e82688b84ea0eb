"""``model_math.attention_layers`` and the roofline reader that uses it."""

import types

from chipbench import model_math, run as cli, trace

MISTRAL = {"num_hidden_layers": 8, "hidden_size": 4096,
           "num_attention_heads": 32, "num_key_value_heads": 8}


def test_no_published_pattern_means_every_layer():
    assert model_math.attention_layers(MISTRAL) == 8


def test_jamba_period_and_offset():
    # AI21-Jamba2-3B: 28 layers, attention at 7 and 21
    cfg = {"num_hidden_layers": 28, "attn_layer_period": 14,
           "attn_layer_offset": 7}
    assert model_math.attention_layers(cfg) == 2
    assert model_math.attention_layers({**cfg, "num_hidden_layers": 8}) == 1


def test_a_list_of_layer_types_read_as_far_as_the_layers_run():
    kinds = ["mamba"] * 5 + ["attention"] + ["mamba"] * 6 + ["attention"]
    assert model_math.attention_layers(
        {"num_hidden_layers": 13, "layers_block_type": kinds}) == 2
    assert model_math.attention_layers(
        {"num_hidden_layers": 6, "layers_block_type": kinds}) == 1
    kinds = ["linear_attention"] * 3 + ["full_attention"]
    assert model_math.attention_layers(
        {"num_hidden_layers": 8, "layer_types": kinds * 2}) == 2
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    assert model_math.attention_layers(
        {"num_hidden_layers": 4, "layer_types": kinds}) == 4


def reader():
    return cli.layer_metric_files(
        "serve", ["serve_tokens_per_s"])["kernel.hbm_share.serve"]


def run_of(config):
    reduced = trace.Reduced(window_s=1.0, busy_s=0.5,
                            ops=[("ragged_paged_attention", 0.004)],
                            idle_gaps=[], events=[], spans=[])
    steps = [{"live_context": 100_000, "tokens": 300}] * 2
    return types.SimpleNamespace(reduced=reduced, traced_steps=steps,
                                 config=config, device_kind="TPU v5 lite")


def test_the_roofline_share_counts_the_layers_that_have_attention():
    m = reader()
    need = 2 * model_math.ragged_attention_bytes(MISTRAL, 100_000, 300)
    want = 100.0 * 8 * need / 819e9 / 0.004
    assert abs(m.compute(run_of(MISTRAL)) - want) < 1e-9
    hybrid = {**MISTRAL, "num_hidden_layers": 28, "attn_layer_period": 14,
              "attn_layer_offset": 7}
    assert abs(m.compute(run_of(hybrid)) - want * 2 / 8) < 1e-9
