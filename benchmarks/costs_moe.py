"""What the expert layer and the windowed attention layers need, counted
from the configuration's sizes (the file beside ``costs.py`` that a new
kernel brings). ``cfg`` is a configuration file's dict with the public
``config.json`` keys. Needed means needed by the mathematics: an expert that
got no row is not read, a row is multiplied by its own expert only, a
sliding layer reads no key older than its window. Padding rows, row tiles
wider than a group, re-reads of an expert per row tile and pages a window
only partly covers are the kernel's business: they take time and add no
needed byte or FLOP.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks import costs


def expert_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of ONE expert's three matrices (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def expert_flops_per_row(cfg: Dict[str, Any]) -> int:
    """FLOPs of one routed row through one expert: 2 per weight."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def grouped_matmul_least_s(cfg: Dict[str, Any], experts_touched: float,
                           rows_routed: float, peaks: Dict[str, Any]) -> float:
    """Least time of the grouped multiplications that ``experts_touched``
    (expert, layer) pairs and ``rows_routed`` (token, expert) rows need: the
    larger of reading each touched expert once and of the routed rows'
    FLOPs."""
    return max(
        experts_touched * expert_bytes(cfg) / peaks["hbm_bytes_per_s"],
        rows_routed * expert_flops_per_row(cfg) / peaks["bf16_flops_per_s"],
    )


def sliding_layers(cfg: Dict[str, Any]) -> int:
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return sum(1 for k in kinds if k == "sliding_attention")


def windowed_row_tokens(cfg: Dict[str, Any], context: float, new_tokens: float = 1) -> float:
    """Keys a row of ``new_tokens`` queries at the tail of ``context`` tokens
    has to read in ONE sliding layer: its own span and the window before its
    first query."""
    before = context - new_tokens
    return new_tokens + min(before, cfg["sliding_window"] - 1)


def windowed_attention_bytes(cfg: Dict[str, Any], tokens: float, itemsize: int = 2) -> float:
    """Bytes of keys and values ONE sliding layer reads for ``tokens`` keys."""
    return tokens * costs.kv_bytes_per_token_per_layer(cfg, itemsize)
