"""What a state-space mixer's decode recurrence needs, counted from the
configuration's sizes alone (the file a new kernel brings, beside
``costs.py``, ``costs_moe.py``, ``costs_dsa.py`` and ``costs_mla.py``).
``cfg`` is a configuration file's dict with the public ``config.json`` keys.

One token of one row of one layer reads the row's whole recurrent state and
writes it back (``mamba_n_heads x mamba_d_head x mamba_d_state`` float32
elements each way: the state is held in float32, the configuration's
``assumed`` says why), and reads the token's ``x``, ``B``, ``C`` (bf16) and
``dt`` (float32) and writes ``y`` (bf16). 3 multiply-adds an element of
state: bound by bytes by two orders of magnitude. Rows that are not live
need nothing; a layout's padding and a second pass over the state are the
kernel's business.
"""

from __future__ import annotations

from typing import Any, Dict

STATE_ITEMSIZE = 4  # float32


def state_elements(cfg: Dict[str, Any]) -> int:
    """Elements of ONE row's recurrent state in ONE layer."""
    return int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"]) * int(cfg["mamba_d_state"])


def row_vector_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """The token's own operands: x, B, C in, y out (bf16), dt (float32)."""
    d = int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"])
    bc = int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])
    return (2 * d + 2 * bc) * itemsize + int(cfg["mamba_n_heads"]) * 4


def state_update_bytes(cfg: Dict[str, Any], rows: float, layers: float = 1) -> float:
    """Bytes ``rows`` live decode rows need in ``layers`` layers, one token
    each: the state read and written, and the row's operands."""
    return rows * layers * (2 * state_elements(cfg) * STATE_ITEMSIZE + row_vector_bytes(cfg))

