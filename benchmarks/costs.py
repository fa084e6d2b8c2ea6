"""What the algorithm needs, counted from the configuration's sizes.

The benchmark's own functions of operations and bytes (the program has
byte models of its kernels in ``dynamo_tpu/ops/costs.py``; a yardstick the
program can edit is no yardstick). ``cfg`` is a configuration file's dict
with the public ``config.json`` keys. Needed means needed by the
mathematics: padding, re-reads and recomputation do not count, so a share
of a peak worked out from these can only be pushed up by doing the needed
work faster.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r}; add it, "
            f"with its source, to benchmarks/peaks.json"
        )
    return table[device_kind]


def head_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def kv_bytes_per_token_per_layer(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def matmul_params_per_layer(cfg: Dict[str, Any]) -> int:
    h, hd = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return h * (q + 2 * kv) + q * h + 3 * h * cfg["intermediate_size"]


def prefill_flops(cfg: Dict[str, Any], new_tokens: int, context_before: int,
                  samples: int = 0) -> float:
    """FLOPs of pushing ``new_tokens`` through the stack after
    ``context_before`` cached tokens: 2 per weight per token in the layers'
    matrices, causal attention (QK^T and PV, 2 FLOPs per multiply-add, each
    new token against the context before it and the new tokens up to it),
    and the output head for ``samples`` positions."""
    L, hd = cfg["num_hidden_layers"], head_dim(cfg)
    nh = cfg["num_attention_heads"]
    mm = 2.0 * new_tokens * matmul_params_per_layer(cfg) * L
    # sum over the new tokens of the keys each attends to
    attended = new_tokens * context_before + new_tokens * (new_tokens + 1) / 2.0
    attn = 2.0 * 2.0 * attended * nh * hd * L
    head = 2.0 * samples * cfg["hidden_size"] * cfg["vocab_size"]
    return mm + attn + head


def decode_attention_bytes(cfg: Dict[str, Any], context_tokens: float,
                           itemsize: int = 2) -> float:
    """Bytes of keys and values ONE layer's decode attention has to read for
    rows whose contexts sum to ``context_tokens``."""
    return context_tokens * kv_bytes_per_token_per_layer(cfg, itemsize)
