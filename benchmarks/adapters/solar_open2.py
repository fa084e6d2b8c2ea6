"""Configurations that run through
``dynamo_tpu.models.solar_open2.SolarOpen2Config`` (``model_type``
``solar_open2``): Kimi Delta Attention in three layers of four, softmax
grouped-query attention without positions and with an output gate in the
fourth, a routed feed-forward in every layer of which this chip holds a
share.

The layers run are published layers ``0 .. num_hidden_layers - 1`` (whole
periods of the 4-layer pattern from its start); ``n_routed_experts`` counts
the experts held, from ``experts_held_first``, of ``router_outputs``; the
vocabulary is the slice the file holds. A program without the family fails
at this module's import of it (``model_config``), before anything is placed
on a device.
"""

from __future__ import annotations

from typing import Any, Dict


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.solar_open2 import SolarOpen2Config

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    lin = cfg["linear_attn_config"]
    L = int(cfg["num_hidden_layers"])
    if cfg["model_type"] != "solar_open2" or cfg["use_rope"] or cfg["kda_use_full_proj"]:
        raise ValueError("this adapter runs solar_open2 without positions and with low-rank "
                         "decay and gate projections")
    if int(cfg["first_k_dense_replace"]) != 0:
        raise ValueError("this adapter runs the routed feed-forward in every layer")
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("this adapter runs KDA with as many key heads as heads")
    held = (int(cfg["experts_held_first"]), int(cfg["n_routed_experts"]))
    whole = held == (0, int(cfg["router_outputs"]))
    return SolarOpen2Config(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=L,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position=cfg["max_position_embeddings"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=dtypes[cfg["torch_dtype"]],
        gqa_layers=tuple(int(i) for i in cfg["gqa_layers"] if int(i) < L),
        use_gqa_gate=bool(cfg["use_gqa_gate"]),
        kda_num_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        kda_conv_kernel=int(lin["short_conv_kernel_size"]),
        kda_low_rank=int(cfg["assumed_sizes"]["kda_low_rank"]),
        kda_allow_neg_eigval=bool(cfg["kda_allow_neg_eigval"]),
        num_experts=int(cfg["router_outputs"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        moe_scoring="sigmoid",
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        num_shared_experts=int(cfg["n_shared_experts"]),
        experts_held=None if whole else held,
    )


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names: the program's
    pytree already uses them (``benchmarks/reference/solar_open2_decoder.py``
    lists them; matrices [in, out], ``w_qkv``'s columns q | k | v, ``conv_w``
    row ``j`` for the input ``kernel - 1 - j`` tokens back, the expert stacks
    over the experts held). Beside them, under ``held``, what the engine
    HOLDS as it stands (called after the samples ended, before anything else
    runs): the slot states ``kda`` [slots, heads, d_k, d_v], one array a KDA
    layer in order, and the page pools ``k``, ``v`` [pages, page, kv heads,
    head_dim], one a GQA layer in order: the arrays themselves, not copies.
    A request that ended at its ``max_tokens`` leaves in its slot the state
    after its last fed token (``decode_multi``'s ``max_new``), and its pages
    are freed but not yet written again."""
    held = {"kda": engine.state.arrays["kda"], "k": engine.k_caches, "v": engine.v_caches}
    return {**engine.params, "held": held}
