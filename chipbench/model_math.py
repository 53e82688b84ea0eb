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


def train_flops_per_token(cfg: Dict, sequence_tokens: int) -> float:
    """Forward and backward of one token in a causal sequence: 6 per matmul
    parameter, and per layer the causal half of QK^T and PV, 2 x 2 x
    (s / 2) x hidden forward, three times that with the backward pass.
    Recomputed operations would not count; the cell recomputes nothing."""
    attention = 3 * 2 * sequence_tokens * cfg["hidden_size"]
    return 6.0 * matmul_params(cfg) + cfg["num_hidden_layers"] * attention


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
