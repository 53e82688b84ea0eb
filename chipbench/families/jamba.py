"""The Jamba family (Mamba-1 layers with an attention layer every
``attn_layer_period``): how a configuration of it becomes the program's
model, where its plain reference is, and what its row state costs.

Served only: it gives ``build_model``, ``make_weights`` and ``logits``, no
``loss`` and no ``build_criterion``. A family whose layers keep row state
also keeps, here, the arithmetic its per-layer metrics need (the benchmark's
own, not the program's): how many layers hold the state, the bytes a scan
must move, and the matrix parameters a token is multiplied with, which
`model_math.matmul_params` counts for a Llama layer only.
"""

from __future__ import annotations

from typing import Dict, Optional

from .. import reference_jamba

make_weights = reference_jamba.make_weights
logits = reference_jamba.logits


def build_model(sizes: Dict, seed: int, train_options: Optional[Dict] = None):
    """``JambaForCausalLM`` at the configuration's sizes, holding weights
    made by ``reference_jamba.make_weights`` from the seed. Returns (model,
    JambaConfig, weights); the weights are the arrays the model holds."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM
    if sizes.get("num_experts", 1) != 1 or not sizes["tie_word_embeddings"]:
        raise ValueError("JambaForCausalLM has one expert and a tied head")
    cfg = JambaConfig(**{k: sizes[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "attn_layer_period",
        "attn_layer_offset", "mamba_d_state", "mamba_d_conv", "mamba_expand",
        "mamba_dt_rank", "max_position_embeddings", "rms_norm_eps")},
        dtype=sizes["torch_dtype"])
    paddle.seed(seed % (2 ** 31 - 1))
    with paddle.LazyGuard():    # the reference's weights replace every one
        model = JambaForCausalLM(cfg)
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
        # the placeholder's initializer must not run at the first forward
        del p._lazy_spec
    return model, cfg, weights


# -- the benchmark's arithmetic of the row state -----------------------------

def state_layers(cfg: Dict) -> int:
    """The layers that keep row state: every one that is not attention."""
    return sum(not reference_jamba.is_attention(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def ssm_state_bytes(cfg: Dict) -> int:
    """One row's float32 SSM state in one layer."""
    return cfg["mamba_expand"] * cfg["hidden_size"] * cfg["mamba_d_state"] * 4


def scan_bytes(cfg: Dict, state_rows: int, tokens: int,
               bytes_per_value: int = 2) -> float:
    """The least one ragged selective scan (one layer, one step) must move:
    the SSM state of every row that has a token, read and written once,
    and for each packed token ``x``, ``dt`` and ``z`` in and ``y`` out over
    the inner width, and ``B`` and ``C``. Scaled by the layers and steps of
    a slice by the caller."""
    d, n = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"]
    return (2.0 * state_rows * ssm_state_bytes(cfg)
            + tokens * (4 * d + 2 * n) * bytes_per_value)


def matmul_params(cfg: Dict) -> int:
    """Parameters that every token is multiplied with in the layers: the
    feed-forward's three matrices in every layer, q, k, v and o in an
    attention layer, and the mixer's four projections in a Mamba layer.
    The tied head is the embedding, counted by `serve_flops` for the rows
    that sample."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    d, n, r = (cfg["mamba_expand"] * h, cfg["mamba_d_state"],
               cfg["mamba_dt_rank"])
    mamba = h * 2 * d + d * (r + 2 * n) + r * d + d * h
    attention = 2 * h * h + 2 * h * kv
    layers = cfg["num_hidden_layers"]
    return (layers * 3 * h * f + state_layers(cfg) * mamba
            + (layers - state_layers(cfg)) * attention)


def serve_flops(cfg: Dict, tokens: int, sampled_rows: int) -> float:
    """The least a serving step must compute, as `model_math.serve_flops`
    reckons it: every packed token through the layers' matrices (2 per
    parameter), the head for the rows that sample a token. The scan's and
    attention's own products are left out, so a share made of this reads a
    little low, never high."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return 2.0 * (tokens * matmul_params(cfg) + sampled_rows * head)
