"""What Command A+'s parallel block needs a decode step, counted from the
configuration's sizes alone (the file a new configuration brings, beside
``costs.py``, ``costs_moe.py`` and the other families'). ``cfg`` is a
configuration file's dict with the public ``config.json`` keys.

The block's launches are the accepted benchmark's (``moe_grouped_matmul``,
``paged_decode_attention``, ``ragged_paged_attention_windowed``) and their
rooflines read ``costs.py`` / ``costs_moe.py``, which hold for this model as
they stand: a full layer's decode row reads every key of its context, a
sliding layer's ``min(context, sliding_window)``, a touched held expert its
three ``[hidden, intermediate]`` matrices once. What is counted here is what
those files have no function for: the layers of each kind, the pages ONE
table would hold for a context (what the windowed group's share is taken of),
and the bytes of a step's weights outside the routed experts.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks import costs

ITEMSIZE = 2  # bf16 weights and pages (the configuration's ``assumed``)


def layers_of_kind(cfg: Dict[str, Any], kind: str) -> int:
    """Layers run whose ``layer_types`` entry is ``kind``."""
    return sum(1 for k in cfg["layer_types"][: cfg["num_hidden_layers"]] if k == kind)


def routed_layers(cfg: Dict[str, Any]) -> int:
    """Layers run that route (``first_k_dense_replace`` leading ones do not)."""
    return int(cfg["num_hidden_layers"]) - int(cfg.get("first_k_dense_replace", 0))


def one_table_pages(context: float, page: int) -> float:
    """Pages ONE block table holds a layer for a context of ``context``
    tokens: every page from the first, whatever a layer can still read."""
    return -(-context // page)


def windowed_pages(cfg: Dict[str, Any], context: float, page: int) -> float:
    """Pages a sliding layer can still read at the end of ``context``
    tokens: the window's, and the page its first key shares."""
    return one_table_pages(min(context, int(cfg["sliding_window"]) + page - 1), page)


def shared_expert_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of ONE layer's shared experts: three matrices each."""
    width = int(cfg["intermediate_size"]) * int(cfg["num_shared_experts"])
    return 3 * int(cfg["hidden_size"]) * width * ITEMSIZE


def attention_weight_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of ONE layer's four attention projections."""
    hd = costs.head_dim(cfg)
    q = int(cfg["num_attention_heads"]) * hd
    kv = int(cfg["num_key_value_heads"]) * hd
    return int(cfg["hidden_size"]) * (2 * q + 2 * kv) * ITEMSIZE
