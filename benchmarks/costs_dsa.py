"""What latent attention over selected keys needs, counted from the
configuration's sizes (the file a new kernel brings, beside ``costs.py`` and
``costs_moe.py``). ``cfg`` is a configuration file's dict with the public
``config.json`` keys. Needed means needed by the mathematics: a query reads
the latent row of each key it selected, once (its values are the same row),
and selects ``min(context, index_topk)`` of them. Rows padded to whole tiles,
the index key copied beside ``k_pe``, and a row that two queries of a chunk
both selected being copied twice are the kernel's business: they take time
and add no needed byte.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def latent_row_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of ONE token's latent row: ``c`` and the shared rotary key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def selected_keys(cfg: Dict[str, Any], context: float) -> float:
    """Keys ONE query at the end of ``context`` tokens attends over in one layer."""
    return min(context, cfg["index_topk"])


def layers_run(cfg: Dict[str, Any]) -> List[Tuple[str, str]]:
    """(indexer kind, ffn kind) of each layer run."""
    lo, L = int(cfg.get("layer_offset", 0)), int(cfg["num_hidden_layers"])
    return list(zip(cfg["indexer_types"][lo:lo + L], cfg["mlp_layer_types"][lo:lo + L]))


def sparse_layers(cfg: Dict[str, Any]) -> int:
    return sum(1 for _, ffn in layers_run(cfg) if ffn == "sparse")


def sparse_attention_least_s(cfg: Dict[str, Any], keys: float, peaks: Dict[str, Any]) -> float:
    """Least time of the launches that attend over ``keys`` (query, key)
    pairs in all: each pair one latent row over the HBM peak. Bound: bytes
    (a pair is 2 x 64 heads x 1088 FLOP per 1152 bytes: 8 us of matrix unit
    for 11.5 us of reads per 8 decode rows)."""
    return keys * latent_row_bytes(cfg) / peaks["hbm_bytes_per_s"]
