"""What a KDA layer's decode recurrence (the gated delta rule with a decay a
channel) needs, counted from the configuration's sizes alone (the file a new
kernel brings, beside ``costs.py``, ``costs_moe.py``, ``costs_dsa.py``,
``costs_mla.py`` and ``costs_ssm.py``). ``cfg`` is a configuration file's
dict with the public ``config.json`` keys.

One token of one row of one KDA layer reads the row's whole matrix state and
writes it back (``heads x d_k x d_v`` float32 elements each way: the state
is held in float32, the configuration's ``assumed`` says why), reads the
token's ``q``, ``k`` and ``alpha`` (float32: normalised, and a decay a
channel), ``v`` (bf16) and ``beta`` (float32 a head), and writes ``y``
(float32). About 7 operations an element of state: bound by bytes by an
order of magnitude. Rows that are not live need nothing; a layout's padding,
a transposed copy of what a key channel indexes and a second pass over the
state are the kernel's business.
"""

from __future__ import annotations

from typing import Any, Dict

STATE_ITEMSIZE = 4  # float32


def kda_layers(cfg: Dict[str, Any]) -> int:
    """The layers held that are KDA: all but ``gqa_layers``."""
    L = int(cfg["num_hidden_layers"])
    return L - sum(1 for i in cfg["gqa_layers"] if int(i) < L)


def state_elements(cfg: Dict[str, Any]) -> int:
    """Elements of ONE row's matrix state in ONE layer."""
    lin = cfg["linear_attn_config"]
    return int(lin["num_heads"]) * int(lin["head_dim"]) ** 2


def row_vector_bytes(cfg: Dict[str, Any]) -> int:
    """The token's own operands: q, k, alpha in (float32), v in (bf16), beta
    in (float32 a head), y out (float32)."""
    lin = cfg["linear_attn_config"]
    n = int(lin["num_heads"]) * int(lin["head_dim"])
    return 3 * n * 4 + n * 2 + int(lin["num_heads"]) * 4 + n * 4


def state_update_bytes(cfg: Dict[str, Any], rows: float, layers: float = 1) -> float:
    """Bytes ``rows`` live decode rows need in ``layers`` KDA layers, one
    token each: the state read and written, and the row's operands."""
    return rows * layers * (2 * state_elements(cfg) * STATE_ITEMSIZE + row_vector_bytes(cfg))
