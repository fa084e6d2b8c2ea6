"""What latent attention over EVERY causal key needs, counted from the
configuration's sizes (the file a new kernel brings, beside ``costs.py``,
``costs_moe.py`` and ``costs_dsa.py``). ``cfg`` is a configuration file's
dict with the public ``config.json`` keys.

Needed means needed by the mathematics, in whichever of its formulations is
cheapest, so that no kernel computing the same attention can read over 100%:

- bytes: a launch reads one latent row (``kv_lora_rank + qk_rope_head_dim``
  lanes: ``c`` and the shared rotary key; the values are the same row) for
  every key of each of its rows' contexts, ONCE a row: a chunk of many
  queries reads its context once, not once a query. Rows padded to whole
  tiles, the second array's unread lanes and a chunk's context read again
  for every tile of queries are the kernel's business.
- FLOP: every causal (query, key) pair, every head, one multiply-add a lane
  of the scores and one of the values, at the SMALLER of the two widths the
  same attention can be computed at: absorbed (``kv_lora_rank +
  qk_rope_head_dim`` for the scores, ``kv_lora_rank`` for the values) or
  materialised per head (``qk_nope_head_dim + qk_rope_head_dim`` and
  ``v_head_dim``; the up-projections it needs are not counted).

A launch needs the LARGER of its bytes over the HBM peak and its FLOP over
the matrix unit's peak. At 64 heads and 512 + 64 lanes a one-token row is
bound by bytes (1 152 B for 2 x 64 x 320 = 41 k FLOP a key: 1.41 ns against
0.21) and a chunk by FLOP from 7 queries on.
"""

from __future__ import annotations

from typing import Any, Dict


def latent_row_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of ONE token's latent row: ``c`` and the shared rotary key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def pair_flops(cfg: Dict[str, Any]) -> int:
    """FLOP of ONE causal (query, key) pair over all heads, at the cheaper
    of the absorbed and the materialised widths."""
    absorbed = 2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    materialised = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return 2 * cfg["num_attention_heads"] * min(absorbed, materialised)


def launch_least_s(cfg: Dict[str, Any], keys_read: float, pairs: float, peaks: Dict[str, Any]) -> float:
    """Least time of ONE kind of launch that reads ``keys_read`` latent rows
    and scores ``pairs`` (query, key) pairs, all its launches together."""
    return max(keys_read * latent_row_bytes(cfg) / peaks["hbm_bytes_per_s"],
               pairs * pair_flops(cfg) / peaks["bf16_flops_per_s"])


def sparse_layers(cfg: Dict[str, Any]) -> int:
    """Layers run that hold experts: all after the leading dense ones."""
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
