"""What MiniCPM-SALA's two new launches and its chunk-carrying steps need,
counted from the configuration's sizes alone (the file a new kernel brings,
beside ``costs.py`` and the other ``costs_*.py``). ``cfg`` is a
configuration file's dict with the public ``config.json`` keys and its
``assumed_sizes`` (the selection's).

- ``lightning_state_update``: one token of one row of one lightning layer
  reads the row's whole matrix state and writes it back (``heads x d x d``
  float32 each way), reads the token's q (float32, scaled), k and v (bf16)
  and writes y (float32). About 4 operations an element of state: bound by
  bytes by an order of magnitude.
- ``infllm_decode_attention``: one decode row of one sparse layer reads, once,
  the keys and values of the blocks EACH KV HEAD chose, of THAT kv head alone
  (a page's rows interleave the kv heads: a launch that copies whole pages
  reads the other head's rows too, which is the launch's business and no
  needed byte), and its query in and output out. The pooled keys the
  selection reads are the selection's (XLA), not this launch's.
- a chunk-carrying step: 2 FLOPs a matrix weight a token (by layer kind),
  the attention of each token over the keys it attends (every causal key up
  to ``dense_len``, the chosen blocks' past it) and over the pooled keys it
  scores, and the recurrence's two products a lightning layer (``k^T v`` and
  ``q S``: the mathematics, whatever blocked form computes it).
"""

from __future__ import annotations

from typing import Any, Dict, List

STATE_ITEMSIZE = 4  # float32
ITEMSIZE = 2        # bf16 pages


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    first = int(cfg.get("first_layer_run", 0))
    return list(cfg["mixer_types"])[first:first + int(cfg["num_hidden_layers"])]


def lightning_layers(cfg: Dict[str, Any]) -> int:
    return sum(1 for k in layer_kinds(cfg) if k == "lightning-attn")


def sparse_layers(cfg: Dict[str, Any]) -> int:
    return sum(1 for k in layer_kinds(cfg) if k == "minicpm4")


# -- lightning_state_update ---------------------------------------------------


def state_elements(cfg: Dict[str, Any]) -> int:
    """Elements of ONE row's matrix state in ONE layer."""
    return int(cfg["lightning_nh"]) * int(cfg["lightning_head_dim"]) ** 2


def state_row_vector_bytes(cfg: Dict[str, Any]) -> int:
    """The token's own operands: q in (float32), k and v in (bf16), y out
    (float32)."""
    n = int(cfg["lightning_nh"]) * int(cfg["lightning_head_dim"])
    return n * 4 + 2 * n * 2 + n * 4


def state_update_bytes(cfg: Dict[str, Any], rows: float) -> float:
    """Bytes ``rows`` (live decode row, lightning layer) pairs need, one
    token each: the state read and written, and the row's operands."""
    return rows * (2 * state_elements(cfg) * STATE_ITEMSIZE + state_row_vector_bytes(cfg))


# -- infllm_decode_attention --------------------------------------------------


def own_head_key_value_bytes(cfg: Dict[str, Any]) -> int:
    """One chosen key and its value of ONE kv head."""
    return 2 * int(cfg["head_dim"]) * ITEMSIZE


def attention_row_vector_bytes(cfg: Dict[str, Any]) -> int:
    """A row's own query in and output out, one layer."""
    return 2 * int(cfg["num_attention_heads"]) * int(cfg["head_dim"]) * ITEMSIZE


def decode_attention_bytes(cfg: Dict[str, Any], keys_selected: float, rows: float) -> float:
    """Bytes the decode rows need, from the program's counters:
    ``keys_selected`` the keys a row's launch was handed (a kv head), summed over
    rows and sparse layers (``StepStats.infllm_keys_selected``), ``rows`` the
    (row, sparse layer) pairs. Every kv head reads its own choice, its own
    rows only."""
    return (keys_selected * int(cfg["num_key_value_heads"]) * own_head_key_value_bytes(cfg)
            + rows * attention_row_vector_bytes(cfg))


# -- a chunk-carrying step ----------------------------------------------------


def ffn_params(cfg: Dict[str, Any]) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def sparse_layer_params(cfg: Dict[str, Any]) -> int:
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    q, kv = int(cfg["num_attention_heads"]) * d, int(cfg["num_key_value_heads"]) * d
    return h * (q + 2 * kv) + q * h + h * q + ffn_params(cfg)      # W_q, W_k, W_v, W_o, gate


def lightning_layer_params(cfg: Dict[str, Any]) -> int:
    n = int(cfg["lightning_nh"]) * int(cfg["lightning_head_dim"])
    return 5 * int(cfg["hidden_size"]) * n + ffn_params(cfg)       # W_q, W_k, W_v, W_o, gate


def matrix_flops_per_token(cfg: Dict[str, Any]) -> float:
    return 2.0 * (sparse_layers(cfg) * sparse_layer_params(cfg)
                  + lightning_layers(cfg) * lightning_layer_params(cfg))


def keys_attended(cfg: Dict[str, Any], position: int) -> int:
    """Keys the query at ``position`` attends over in a sparse layer."""
    sz = cfg["assumed_sizes"]
    n = position + 1
    if n <= int(sz["dense_len"]):
        return n
    block = int(sz["block_size"])
    last = position // block
    local = int(sz["window_size"]) // block + 1
    forced = min(int(sz["init_blocks"]), last + 1)
    forced += min(local, last + 1 - forced)
    others = max(last + 1 - local - int(sz["init_blocks"]), 0)
    return (forced + min(others, int(sz["topk"])) - 1) * block + position % block + 1


def pooled_keys_scored(cfg: Dict[str, Any], position: int) -> int:
    sz = cfg["assumed_sizes"]
    n = position + 1
    if n <= int(sz["dense_len"]):
        return 0
    return max((n - int(sz["kernel_size"])) // int(sz["kernel_stride"]) + 1, 0)


def prompt_flops(cfg: Dict[str, Any], prompt_tokens: int) -> float:
    """Needed FLOPs of prefilling a prompt whole (nothing cached: the family
    declines prefix hits)."""
    nh, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    attended = sum(keys_attended(cfg, t) for t in range(prompt_tokens))
    scored = sum(pooled_keys_scored(cfg, t) for t in range(prompt_tokens))
    attention = sparse_layers(cfg) * (2.0 * 2.0 * attended + 2.0 * scored) * nh * d
    scan = lightning_layers(cfg) * prompt_tokens * 2.0 * 2.0 * state_elements(cfg)
    return prompt_tokens * matrix_flops_per_token(cfg) + attention + scan
