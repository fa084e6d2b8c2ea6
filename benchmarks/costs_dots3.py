"""What dots3-note-prev's two kinds of layer need, counted from the
configuration's sizes alone (the file a new configuration brings, beside
``costs.py``, ``costs_dsa.py``, ``costs_moe.py`` and the other families').
``cfg`` is a configuration file's dict with the public ``config.json`` keys.
Needed means needed by the mathematics in its absorbed form (the form every
serving system runs: a key is one ``[c | k_r]`` row for all heads): a sliding
layer's query reads no key older than its window, a full layer's query scores
every causal index key and attends the ``index_topk`` it keeps, a token passes
the routed experts it chose OF THOSE HELD HERE (``num_experts_per_tok x
n_routed_experts / router_outputs`` of them on average: one in eight of its
eight). Bucket padding, whole pages and chunks a window only partly covers,
keys copied for the mask to drop, the index key copied beside ``k_r`` are the
kernel's business: they take time and add no needed byte or FLOP.
"""

from __future__ import annotations

from typing import Any, Dict

ITEMSIZE = 2          # bf16 weights and pages (the configuration's ``assumed``)
FULL, SLIDING = "full_attention", "sliding_attention"


def kind_sizes(cfg: Dict[str, Any], kind: str) -> Dict[str, int]:
    """The attention sizes of a layer kind, off the public keys."""
    pre = "" if kind == FULL else "swa_"
    names = dict(heads="num_attention_heads", nope="qk_nope_head_dim", rope="qk_rope_head_dim",
                 v="v_head_dim", r_q="q_lora_rank", rank="kv_lora_rank")
    return {k: int(cfg[pre + name]) for k, name in names.items()}


def layers_of_kind(cfg: Dict[str, Any], kind: str) -> int:
    return sum(1 for k in cfg["layer_types"][: cfg["num_hidden_layers"]] if k == kind)


def routed_layers(cfg: Dict[str, Any]) -> int:
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def latent_row_bytes(cfg: Dict[str, Any], kind: str) -> int:
    """Bytes of ONE key of a kind: ``c`` and the shared rotary key (2 176 in a
    sliding layer, 1 152 in a full one)."""
    sz = kind_sizes(cfg, kind)
    return (sz["rank"] + sz["rope"]) * ITEMSIZE


def pair_flops(cfg: Dict[str, Any], kind: str) -> int:
    """FLOPs of one (query, key) pair of a kind over all its heads, absorbed:
    the score over ``rank + rope`` lanes and the value over ``rank``."""
    sz = kind_sizes(cfg, kind)
    return 2 * sz["heads"] * (2 * sz["rank"] + sz["rope"])


def index_pair_flops(cfg: Dict[str, Any]) -> int:
    """FLOPs of one (query, causal key) pair of a full layer's indexer."""
    return 2 * int(cfg["index_n_heads"]) * int(cfg["index_head_dim"])


def _upto(n: float, cap: float) -> float:
    """sum over positions p < n of min(p + 1, cap)."""
    m = min(n, cap)
    return m * (m + 1) / 2 + (n - m) * cap


def window_pairs(cfg: Dict[str, Any], new: float, before: float) -> float:
    """(query, visible key) pairs of ``new`` queries behind ``before`` tokens
    in ONE sliding layer."""
    W = int(cfg["sliding_window_size"])
    return _upto(before + new, W) - _upto(before, W)


def selected_pairs(cfg: Dict[str, Any], new: float, before: float) -> float:
    """(query, selected key) pairs in ONE full layer."""
    K = int(cfg["index_topk"])
    return _upto(before + new, K) - _upto(before, K)


def causal_pairs(new: float, before: float) -> float:
    """(query, causal key) pairs: what ONE full layer's indexer scores."""
    end = before + new
    return end * (end + 1) / 2 - before * (before + 1) / 2


def window_row_keys(cfg: Dict[str, Any], context: float) -> float:
    """Keys a decode row at the end of ``context`` tokens reads in ONE
    sliding layer."""
    return min(context, int(cfg["sliding_window_size"]))


def windowed_least_s(cfg: Dict[str, Any], row_keys: float, chunk_pairs: float,
                     chunk_keys: float, peaks: Dict[str, Any]) -> float:
    """Least time of the launches named ``windowed_latent_attention`` that
    serve decode rows reading ``row_keys`` keys in all (bound: bytes, a key
    once for all 64 heads) and chunks of ``chunk_pairs`` visible pairs over
    ``chunk_keys`` keys (the larger of their products and their keys'
    bytes)."""
    row = latent_row_bytes(cfg, SLIDING)
    return row_keys * row / peaks["hbm_bytes_per_s"] + max(
        chunk_pairs * pair_flops(cfg, SLIDING) / peaks["bf16_flops_per_s"],
        chunk_keys * row / peaks["hbm_bytes_per_s"],
    )


def attention_params(cfg: Dict[str, Any], kind: str) -> int:
    """Weights a token passes in ONE layer's attention of a kind: the two
    down- and up-projections, the output projection, the gate and, in a full
    layer, the indexer."""
    h, sz = int(cfg["hidden_size"]), kind_sizes(cfg, kind)
    n = sz["heads"]
    params = (h * sz["r_q"] + sz["r_q"] * n * (sz["nope"] + sz["rope"])
              + h * (sz["rank"] + sz["rope"]) + sz["rank"] * n * (sz["nope"] + sz["v"])
              + n * sz["v"] * h + h * n)
    if kind == FULL:
        nI, dI = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
        params += sz["r_q"] * nI * dI + h * dI + h * nI
    return params


def ffn_params(cfg: Dict[str, Any], layer: int) -> float:
    """Weights a token passes in layer ``layer``'s feed-forward HERE: the
    dense SwiGLU, or the router, the shared expert and the routed experts it
    chose of those this chip holds (on average)."""
    h = int(cfg["hidden_size"])
    if layer < int(cfg["first_k_dense_replace"]):
        return 3 * h * int(cfg["intermediate_size"])
    one = 3 * h * int(cfg["moe_intermediate_size"])
    here = (int(cfg["num_experts_per_tok"]) * int(cfg["n_routed_experts"])
            / int(cfg.get("router_outputs", cfg["n_routed_experts"])))
    return h * int(cfg.get("router_outputs", cfg["n_routed_experts"])) \
        + one * int(cfg["n_shared_experts"]) + one * here


def matrix_flops_per_token(cfg: Dict[str, Any]) -> float:
    """2 FLOPs a weight a token, every layer run (the head, one row a
    sampled token, left out)."""
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return 2.0 * sum(attention_params(cfg, k) + ffn_params(cfg, i) for i, k in enumerate(kinds))


def attention_flops(cfg: Dict[str, Any], new: float, before: float) -> float:
    """FLOPs of the attention of ``new`` tokens behind ``before``: the full
    layers' index scores over causal keys and their selected products, the
    sliding layers' windowed products."""
    n_full, n_win = layers_of_kind(cfg, FULL), layers_of_kind(cfg, SLIDING)
    return (n_full * (causal_pairs(new, before) * index_pair_flops(cfg)
                      + selected_pairs(cfg, new, before) * pair_flops(cfg, FULL))
            + n_win * window_pairs(cfg, new, before) * pair_flops(cfg, SLIDING))


def chunk_flops(cfg: Dict[str, Any], new: float, before: float) -> float:
    """Needed FLOPs of prefilling ``new`` tokens behind ``before`` cached ones."""
    return new * matrix_flops_per_token(cfg) + attention_flops(cfg, new, before)
