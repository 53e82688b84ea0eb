"""The Llama family (Llama, Mistral without a sliding window): how a
configuration of it becomes the program's model, and where its plain
reference is.

A family is what a driver reaches a model through. It gives
``build_model(sizes, seed, train_options)``, the program's model holding the
reference's seeded weights; ``build_criterion(cfg)``, the program's training
loss; and the reference's ``make_weights``, ``logits`` and ``loss``. A
configuration names its family with the key ``family`` (default ``llama``).
A model of another family brings ``families/<name>.py`` and a reference file
and runs under the drivers that are there.
"""

from __future__ import annotations

from typing import Dict, Optional

from .. import reference

make_weights = reference.make_weights
logits = reference.logits
loss = reference.loss


def build_model(sizes: Dict, seed: int, train_options: Optional[Dict] = None):
    """``LlamaForCausalLM`` at the configuration's sizes, holding weights
    made by ``reference.make_weights`` from the seed. Returns (model,
    LlamaConfig, weights); the weights are the arrays the model holds."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    if sizes.get("sliding_window") is not None:
        raise ValueError("LlamaForCausalLM has no sliding window")
    options = train_options or {}
    cfg = LlamaConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        max_position_embeddings=sizes["max_position_embeddings"],
        rms_norm_eps=sizes["rms_norm_eps"], rope_theta=sizes["rope_theta"],
        tie_word_embeddings=sizes["tie_word_embeddings"],
        use_flash_attention=options.get("use_flash_attention", True),
        recompute=options.get("recompute", False),
        dtype=sizes["torch_dtype"])
    paddle.seed(seed % (2 ** 31 - 1))
    model = LlamaForCausalLM(cfg)
    weights = make_weights(sizes, seed, jnp.dtype(cfg.dtype))
    named = dict(model.named_parameters())
    if set(named) != set(weights):
        raise RuntimeError(
            f"the model's parameters are not the reference's: "
            f"{sorted(set(named) ^ set(weights))}")
    for name, p in named.items():
        if tuple(p._data.shape) != tuple(weights[name].shape):
            raise RuntimeError(f"{name}: {p._data.shape} in the model, "
                               f"{weights[name].shape} in the reference")
        p._set_data(weights[name])
    return model, cfg, weights


def build_criterion(cfg):
    """The program's next-token loss for a model of this family."""
    from paddle_tpu.models import LlamaPretrainingCriterion
    return LlamaPretrainingCriterion(cfg)
