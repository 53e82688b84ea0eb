"""The plain reference against a tiny ``LlamaForCausalLM`` on the CPU."""

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny_sizes():
    from chipbench.drivers.common import model_sizes, sized
    with open(os.path.join(HERE, "..", "configs",
                           "mistral-7b-v0.3-serve.json")) as f:
        return model_sizes(sized(json.load(f), rehearse=True))


def test_logits_and_loss_agree_with_the_program():
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import LlamaPretrainingCriterion
    from chipbench import reference
    from chipbench.families.llama import build_model
    sizes = tiny_sizes()
    model, cfg, weights = build_model(sizes, seed=2 ** 31 + 5)
    model.eval()
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 96))
    with paddle.no_grad():
        logits = model(Tensor(jnp.asarray(ids, jnp.int32)))
        loss = LlamaPretrainingCriterion(cfg)(
            logits, Tensor(jnp.asarray(ids, jnp.int32)))
    want = reference.logits(sizes, weights, ids[0], query_block=32)
    got = np.asarray(logits._data[0], np.float32)
    assert got.shape == want.shape
    assert np.max(np.abs(got - np.asarray(want))) < 1e-5
    ref_loss = reference.loss(sizes, weights, ids[0], query_block=32)
    assert abs(float(loss._data) - ref_loss) < 1e-5
    # the blocks change nothing
    assert abs(reference.loss(sizes, weights, ids[0], 96) - ref_loss) < 1e-6


def test_weights_come_from_the_seed():
    import jax.numpy as jnp
    from chipbench import reference
    sizes = tiny_sizes()
    a = reference.make_weights(sizes, 7, jnp.float32)
    b = reference.make_weights(sizes, 7, jnp.float32)
    c = reference.make_weights(sizes, 8, jnp.float32)
    name = "llama.layers.0.mlp.up_proj.weight"
    assert set(a) == set(reference.weight_shapes(sizes))
    assert np.array_equal(a[name], b[name])
    assert not np.array_equal(a[name], c[name])
    assert np.all(np.asarray(a["llama.norm.weight"]) == 1.0)
    assert abs(float(jnp.std(a[name])) - 0.02) < 0.002
