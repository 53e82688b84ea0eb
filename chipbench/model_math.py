"""What the model's mathematics needs: operations and bytes from shapes,
and the chip's peaks. The benchmark's own arithmetic, not the program's.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDevice(RuntimeError):
    """Not a device the benchmark measures on: no TPU, too few chips, or a
    kind that is not in ``peaks.json``, so that no share can be computed."""


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {sorted(table)})")
    return table[device_kind]


def head_dim(cfg: Dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg: Dict) -> int:
    """Parameters that every token is multiplied with: the layers' seven
    matrices and the head. The embedding is a lookup and the norms are
    vectors, so neither counts."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    layer = 2 * h * h + 2 * h * kv + 3 * h * f
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def serve_flops(cfg: Dict, tokens: int, sampled_rows: int) -> float:
    """The least a serving step must compute: every packed token through
    the layers' matrices (2 per parameter), the head for the rows that
    sample a token and no others. Attention's own products are left out
    (4 x context x hidden a token and layer: under 3 % at a context of 700),
    so a share made of this reads a little low, never high."""
    h = cfg["hidden_size"]
    layers = matmul_params(cfg) - h * cfg["vocab_size"]
    return 2.0 * (tokens * layers + sampled_rows * h * cfg["vocab_size"])


def train_flops_per_token(cfg: Dict, sequence_tokens: int) -> float:
    """Forward and backward of one token in a causal sequence: 6 per matmul
    parameter, and per layer the causal half of QK^T and PV, 2 x 2 x
    (s / 2) x hidden forward, three times that with the backward pass.
    Recomputed operations would not count; the cell recomputes nothing."""
    attention = 3 * 2 * sequence_tokens * cfg["hidden_size"]
    return 6.0 * matmul_params(cfg) + cfg["num_hidden_layers"] * attention


def attention_layers(cfg: Dict) -> int:
    """How many of the configuration's layers hold attention over cached
    keys and values, read from its published layer pattern: every
    ``attn_layer_period``-th layer from ``attn_layer_offset`` (Jamba), or the
    entries of a list of layer types (``layer_types``,
    ``layers_block_type``) that name an attention and not a linear one.
    ``num_hidden_layers`` where the configuration publishes no pattern. A
    list is read as far as the layers that are run."""
    n = cfg["num_hidden_layers"]
    if "attn_layer_period" in cfg and "attn_layer_offset" in cfg:
        return sum(i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
                   for i in range(n))
    for key in ("layer_types", "layers_block_type"):
        if isinstance(cfg.get(key), (list, tuple)):
            return sum("attention" in kind and "linear" not in kind
                       for kind in cfg[key][:n])
    return n


def kv_bytes_per_token_per_layer(cfg: Dict, bytes_per_value: int = 2) -> int:
    """One token's K and V in one layer's pool."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * bytes_per_value


def ragged_attention_bytes(cfg: Dict, live_context_tokens: int,
                           query_tokens: int, bytes_per_value: int = 2) -> float:
    """The least one ragged paged-attention call (one layer, one step) must
    move: each row's live context of K and V once, the queries in and the
    outputs out. Scaled by the layers and steps of a slice by the caller."""
    q_and_out = 2 * query_tokens * cfg["hidden_size"] * bytes_per_value
    return (live_context_tokens
            * kv_bytes_per_token_per_layer(cfg, bytes_per_value) + q_and_out)
