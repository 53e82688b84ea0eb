"""A family with row state (``families/jamba.py``): it builds the
reference's weights into the program's model, its byte and parameter counts
are the published model's, and the three per-layer metrics that read them
(``kernel.scan_roofline.serve``, ``state.update_share.serve``,
``step_mfu.tokens.serve``) on hand-made step records; nothing in a cell
whose family has no row state."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import program_spans, run as cli, trace
from chipbench.program_spans import Span

HERE = os.path.dirname(os.path.abspath(__file__))
MISTRAL = {"num_hidden_layers": 8, "hidden_size": 4096,
           "intermediate_size": 14336, "vocab_size": 32768,
           "num_attention_heads": 32, "num_key_value_heads": 8}


def published():
    from chipbench.drivers.common import model_sizes
    with open(os.path.join(HERE, "..", "configs",
                           "jamba2-3b-serve.json")) as f:
        return model_sizes(json.load(f))


def readers():
    return cli.layer_metric_files("serve", ["serve_tokens_per_s"])


def test_the_configuration_holds_every_published_key_and_cuts_nothing():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    with open(os.path.join(HERE, "..", "configs",
                           "jamba2-3b-serve.json")) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    assert {k: config[k] for k in row["config"]} == row["config"]
    assert config["reduced"] == {} and config["family"] == "jamba"


def test_counts_of_the_published_model():
    from chipbench.families import jamba
    cfg = published()
    assert jamba.state_layers(cfg) == 26
    assert jamba.ssm_state_bytes(cfg) == 5120 * 16 * 4 == 327_680
    # 26 Mamba layers of 41.12 M and 2 attention layers of 13.76 M matrix
    # parameters, the feed-forward's 62.91 M in each of the 28
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert jamba.matmul_params(cfg) == (28 * 3 * 2560 * 8192 + 26 * mamba
                                        + 2 * attention) == 2_858_352_640
    # every weight the reference makes: 3.03 G parameters, 6.06 GB in bf16
    from chipbench import reference_jamba
    total = sum(int(np.prod(s))
                for s in reference_jamba.weight_shapes(cfg).values())
    assert total == 3_029_337_472


def test_scan_bytes_by_hand():
    from chipbench.families import jamba
    cfg = published()
    # 256 decode rows: each row's state in and out, and 256 tokens' x, dt,
    # z, y over 5120 channels and B, C over 16, in bfloat16
    state = 2 * 256 * 327_680
    tokens = 256 * (4 * 5120 + 2 * 16) * 2
    assert jamba.scan_bytes(cfg, 256, 256) == state + tokens == 178_274_304
    # a chunk beside them adds tokens and one row, no more state than that
    assert jamba.scan_bytes(cfg, 256, 511) - jamba.scan_bytes(
        cfg, 256, 256) == 255 * (4 * 5120 + 2 * 16) * 2
    assert jamba.scan_bytes(cfg, 0, 0) == 0


def run_of(config, ops, spans, monkeypatch, busy_s=0.5):
    steps = [{"t_begin": 10.0, "t_end": 10.4, "tokens": 256, "rows": 256},
             {"t_begin": 10.5, "t_end": 11.0, "tokens": 511, "rows": 256}]
    monkeypatch.setattr(program_spans, "read",
                        lambda prefix, t_lo, t_hi: spans)
    reduced = trace.Reduced(window_s=1.0, busy_s=busy_s, ops=ops,
                            idle_gaps=[], events=[], spans=[])
    return types.SimpleNamespace(reduced=reduced, traced_steps=steps,
                                 config=config, device_kind="TPU v5 lite")


SPANS = [Span("serving.step", 10.1, 10.3,
              {"tokens": 256, "state_rows": 256, "scan_tokens": 256}),
         Span("serving.step.pack", 10.0, 10.1, {}),
         Span("serving.step", 10.6, 10.9,
              {"tokens": 511, "state_rows": 256, "scan_tokens": 511}),
         Span("serving.step", 12.0, 12.1,            # after the slice: out
              {"tokens": 256, "state_rows": 256, "scan_tokens": 256})]
OPS = [("fusion bf16[256,8192]", 0.2), ("ragged_selective_scan", 0.02),
       ("ragged_causal_conv", 0.005), ("ragged_paged_attention", 0.01)]


def test_scan_roofline_reads_the_spans_rows_and_tokens(monkeypatch):
    from chipbench.families import jamba
    cfg = published()
    m = readers()["kernel.scan_roofline.serve"]
    need = 26 * (jamba.scan_bytes(cfg, 256, 256)
                 + jamba.scan_bytes(cfg, 256, 511))
    got = m.compute(run_of(cfg, OPS, SPANS, monkeypatch))
    assert abs(got - 100.0 * need / 819e9 / 0.02) < 1e-9
    # a program whose spans lack the attributes, or that ran no such kernel
    bare = [Span("serving.step", 10.1, 10.3, {"tokens": 256})]
    assert m.compute(run_of(cfg, OPS, bare, monkeypatch)) is None
    assert m.compute(run_of(cfg, OPS[:1], SPANS, monkeypatch)) is None
    # a family without row state
    assert m.compute(run_of(MISTRAL, OPS, SPANS, monkeypatch)) is None


def test_state_update_share_is_both_kernels_over_busy_time(monkeypatch):
    m = readers()["state.update_share.serve"]
    got = m.compute(run_of(published(), OPS, SPANS, monkeypatch))
    assert abs(got - 100.0 * 0.025 / 0.5) < 1e-9
    assert m.compute(run_of(MISTRAL, OPS[:1] + OPS[3:], SPANS,
                            monkeypatch)) is None


def test_step_mfu_counts_the_familys_own_matrices(monkeypatch):
    from chipbench.families import jamba
    cfg = published()
    m = readers()["step_mfu.tokens.serve"]
    flops = (jamba.serve_flops(cfg, 256, 256)
             + jamba.serve_flops(cfg, 511, 256))
    assert jamba.serve_flops(cfg, 256, 256) == 2.0 * 256 * (
        2_858_352_640 + 2560 * 65536)
    got = m.compute(run_of(cfg, OPS, SPANS, monkeypatch))
    assert abs(got - 100.0 * flops / (197e12 * 1.0)) < 1e-9
    assert m.compute(run_of(MISTRAL, OPS, SPANS, monkeypatch)) is None
    cpu = run_of(cfg, OPS, SPANS, monkeypatch)
    cpu.device_kind = "cpu"
    assert m.compute(cpu) is None


def test_the_family_builds_the_references_weights():
    import jax.numpy as jnp
    from chipbench import reference_jamba
    from chipbench.drivers.common import family, model_sizes, sized
    from chipbench.families import jamba
    with open(os.path.join(HERE, "..", "configs",
                           "jamba2-3b-serve.json")) as f:
        config = sized(json.load(f), rehearse=True)
    sizes = model_sizes(config)
    assert family(config) is jamba
    assert not hasattr(jamba, "loss") and not hasattr(jamba,
                                                       "build_criterion")
    model, cfg, weights = jamba.build_model(sizes, 2 ** 31 + 5)
    plain = reference_jamba.make_weights(sizes, 2 ** 31 + 5,
                                         jnp.dtype(cfg.dtype))
    held = {n: p._data for n, p in model.named_parameters()}
    assert set(held) == set(plain)
    assert all(np.array_equal(np.asarray(held[n]), np.asarray(plain[n]))
               for n in plain)
    a_log = np.asarray(plain["jamba.layers.0.mamba.A_log"])
    assert np.allclose(a_log[:, 0], np.log(np.arange(1, 17)))
    assert float(np.max(np.abs(np.asarray(
        plain["jamba.layers.0.mamba.dt_proj.bias"])))) == 0.0
    # the lazily built model must not run its own initializers later
    ids = np.arange(6, dtype=np.int32)[None]
    from paddle_tpu.core.tensor import Tensor
    got = np.asarray(model(Tensor(jnp.asarray(ids)))._data[0])
    want = np.asarray(reference_jamba.logits(sizes, plain, ids[0], 16))
    assert np.max(np.abs(got - want)) < 1e-4


def test_the_reference_controls_change_the_logits():
    import jax.numpy as jnp
    from chipbench import reference_jamba
    from chipbench.drivers.common import model_sizes, sized
    with open(os.path.join(HERE, "..", "configs",
                           "jamba2-3b-serve.json")) as f:
        sizes = model_sizes(sized(json.load(f), rehearse=True))
    sizes = {**sizes, "initializer_range": 0.2}
    weights = reference_jamba.make_weights(sizes, 11, jnp.float32)
    ids = np.random.default_rng(0).integers(0, sizes["vocab_size"], 48)
    plain = np.asarray(reference_jamba.logits(sizes, weights, ids, 16))
    part = np.asarray(reference_jamba.logits(sizes, weights, ids, 16,
                                             rows=(40, 8)))
    assert np.array_equal(part, plain[40:48])
    for precision in ("bf16", "int8", "state_bf16"):
        low = np.asarray(reference_jamba.logits(sizes, weights, ids, 16,
                                                precision=precision))
        # another result, and still a finite one
        assert 1e-5 < np.max(np.abs(low - plain)) < np.inf, precision
