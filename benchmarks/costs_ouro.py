"""What a looped decoder (``model_type`` ``ouro``: one stack of dense layers
run ``total_ut_steps`` times a token, a cache slot a (pass, layer)) needs,
counted from the configuration's sizes alone (the file a new configuration
brings, beside ``costs.py`` and the other families'). ``cfg`` is a
configuration file's dict with the public ``config.json`` keys.

WEIGHTS COUNT ONCE A PASS. A decode step reads every layer's matrices
``passes`` times: pass ``t + 1``'s first layer waits on pass ``t``'s last (the
state it starts from is that pass's normed output), and a layer's 103 MB does
not stay on chip between its two uses, 47 layers apart, beside a 128 MiB
on-chip memory. So the needed bytes of a step are not the model's size but
``passes`` times the layers' share of it, and the step's floor is FOUR reads
of the weights where every other cell's is one. The keys and values are read
once a SLOT (pass ``t`` of layer ``l`` attends over what pass ``t`` of layer
``l`` wrote: ``passes x num_hidden_layers`` slots), and the fed token's are
written once a slot. Padding, discarded horizon steps and pages re-read a kv
head are the program's and the kernel's business: they take time and add no
needed byte or FLOP.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks import costs

ITEMSIZE = 2          # bf16 weights and pages (the configuration's ``assumed``)


def passes(cfg: Dict[str, Any]) -> int:
    return int(cfg["total_ut_steps"])


def page_slots(cfg: Dict[str, Any]) -> int:
    """Cache slots a token: one a (pass, layer)."""
    return passes(cfg) * int(cfg["num_hidden_layers"])


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = ITEMSIZE) -> int:
    """Keys and values of ONE token over every slot (1 572 864 B published)."""
    return costs.kv_bytes_per_token_per_layer(cfg, itemsize) * page_slots(cfg)


def layer_params(cfg: Dict[str, Any]) -> int:
    """A layer's matrices and its four norms (51 388 416 published)."""
    return costs.matmul_params_per_layer(cfg) + 4 * int(cfg["hidden_size"])


def head_params(cfg: Dict[str, Any]) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def weight_bytes_per_step(cfg: Dict[str, Any], itemsize: int = ITEMSIZE) -> int:
    """Weights ONE step has to read: the layers once a pass, the final norm a
    pass, the head once (the embedding's rows of the fed tokens are a few
    KiB and left out)."""
    L, T = int(cfg["num_hidden_layers"]), passes(cfg)
    return itemsize * (T * (L * layer_params(cfg) + int(cfg["hidden_size"])) + head_params(cfg))


def decode_step_bytes(cfg: Dict[str, Any], rows: float, context_tokens: float,
                      itemsize: int = ITEMSIZE) -> float:
    """Needed bytes of ONE decode step of ``rows`` live rows whose contexts
    sum to ``context_tokens``: the weights (``weight_bytes_per_step``), the
    rows' keys and values once a slot, the fed tokens' keys and values
    written once a slot."""
    return (weight_bytes_per_step(cfg, itemsize)
            + (context_tokens + rows) * kv_bytes_per_token(cfg, itemsize))


def attention_flops_per_key(cfg: Dict[str, Any]) -> float:
    """QK^T and PV of one query against ONE key position in ONE slot, all
    heads: 2 products x 2 FLOPs a multiply-add."""
    return 2.0 * 2.0 * int(cfg["num_attention_heads"]) * costs.head_dim(cfg)


def step_flops(cfg: Dict[str, Any], tokens: float, slot_keys: float,
               sampled_rows: float) -> float:
    """Needed FLOPs of a step that ran ``tokens`` real tokens through the
    stack (a chunk's and the decode rows), whose queries attended over
    ``slot_keys`` key positions summed over queries and SLOTS, and sampled
    ``sampled_rows`` rows: 2 a matrix weight a token a PASS, the attention's
    products, the head a sampled row."""
    L, T = int(cfg["num_hidden_layers"]), passes(cfg)
    mm = 2.0 * tokens * T * L * costs.matmul_params_per_layer(cfg)
    return mm + attention_flops_per_key(cfg) * slot_keys + 2.0 * sampled_rows * head_params(cfg)


def chunk_slot_keys(cfg: Dict[str, Any], new_tokens: int, context_before: int) -> float:
    """Key positions a chunk's queries attend over, summed over queries and
    slots: each new token against the context before it and the new tokens
    up to it, in every slot."""
    attended = new_tokens * context_before + new_tokens * (new_tokens + 1) / 2.0
    return attended * page_slots(cfg)
