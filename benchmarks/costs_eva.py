"""What EVA's decode attention (one query a row over a ring of pages and
summaries by window) needs, counted from the configuration's sizes alone (the
file a new kernel brings, beside ``costs.py``, ``costs_moe.py``,
``costs_dsa.py``, ``costs_mla.py``, ``costs_ssm.py`` and ``costs_kda.py``).
``cfg`` is a configuration file's dict with the public ``config.json`` keys.

One decode row of one layer has to read, once, the keys and values of its
open window up to its own position (``(p mod window) + 1`` of them) and one
summary key and value a chunk of every closed window before it (``floor(p /
window) x window / chunk``), every head's (multi-head: no key is shared
between heads), and its own query in and output out. About 4 operations a
byte read: bound by bytes. Pages read past a row's position, a query head
scored against the other heads' keys, padding and discarded horizon steps
are the kernel's and the program's business: they take time and add no
needed byte.
"""

from __future__ import annotations

from typing import Any, Dict

ITEMSIZE = 2  # bf16 pages and bf16 summaries (the configuration's ``assumed``)


def head_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def key_value_bytes(cfg: Dict[str, Any]) -> int:
    """One key and its value, every head's, in one layer: an exact key of
    the ring or a chunk's summary alike."""
    return 2 * int(cfg["num_key_value_heads"]) * head_dim(cfg) * ITEMSIZE


def row_vector_bytes(cfg: Dict[str, Any]) -> int:
    """A row's own query in and output out, one layer."""
    return 2 * int(cfg["num_attention_heads"]) * head_dim(cfg) * ITEMSIZE


def summaries_per_window(cfg: Dict[str, Any]) -> int:
    return int(cfg["window_size"]) // int(cfg["chunk_size"])


def decode_attention_bytes(cfg: Dict[str, Any], window_keys: float, summaries: float,
                           rows: float) -> float:
    """Bytes the decode rows need, from the program's counters, each summed
    over rows AND layers (``StepStats.eva_window_keys``,
    ``.eva_summaries_read``, ``.eva_rows_attended``)."""
    return (window_keys + summaries) * key_value_bytes(cfg) + rows * row_vector_bytes(cfg)


def row_bytes_at(cfg: Dict[str, Any], position: int) -> int:
    """What ONE decode row at ``position`` needs in ONE layer."""
    W = int(cfg["window_size"])
    return int(decode_attention_bytes(
        cfg, position % W + 1, (position // W) * summaries_per_window(cfg), 1))
