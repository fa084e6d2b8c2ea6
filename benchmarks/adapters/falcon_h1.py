"""Configurations that run through
``dynamo_tpu.models.falcon_h1.FalconH1Config`` (``model_type`` ``falcon_h1``):
a Mamba-2 state-space mixer beside grouped-query attention in every layer,
the publication's multipliers as data.

The layers run are published layers ``0 .. num_hidden_layers - 1`` (every
layer is the same); the vocabulary is the slice the file holds. A program
without the family fails at this module's import of it (``model_config``),
before anything is placed on a device.
"""

from __future__ import annotations

from typing import Any, Dict


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.falcon_h1 import FalconH1Config

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if cfg["model_type"] != "falcon_h1" or not cfg["mamba_rms_norm"]:
        raise ValueError("this adapter runs falcon_h1 with the mixer's gated RMSNorm")
    if cfg["mamba_proj_bias"] or cfg["attention_bias"] or cfg["mlp_bias"] or cfg["projectors_bias"]:
        raise ValueError("this adapter runs the projections without biases, as published")
    if cfg["rope_scaling"] is not None or cfg["attn_layer_indices"] is not None:
        raise ValueError("plain rotary positions, and attention in every layer")
    if cfg["mamba_d_ssm"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
    return FalconH1Config(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position=cfg["max_position_embeddings"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=dtypes[cfg["torch_dtype"]],
        mamba_d_ssm=cfg["mamba_d_ssm"],
        mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        mamba_conv_bias=bool(cfg["mamba_conv_bias"]),
        mamba_norm_before_gate=bool(cfg["mamba_norm_before_gate"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        lm_head_multiplier=float(cfg["lm_head_multiplier"]),
        attention_in_multiplier=float(cfg["attention_in_multiplier"]),
        attention_out_multiplier=float(cfg["attention_out_multiplier"]),
        key_multiplier=float(cfg["key_multiplier"]),
        ssm_in_multiplier=float(cfg["ssm_in_multiplier"]),
        ssm_out_multiplier=float(cfg["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in cfg["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in cfg["mlp_multipliers"]),
    )


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names: the program's
    pytree already uses them (``benchmarks/reference/falcon_h1_decoder.py``
    lists them; matrices [in, out], ``w_inproj``'s columns z | x | B | C |
    dt, ``conv_w`` row ``j`` for the input ``kernel - 1 - j`` tokens back).
    Beside them, under ``held``, what the engine HOLDS as it stands (called
    after the samples ended, before anything else runs): a layer's slot
    states ``ssm`` [slots, heads, state, head] (the program's transposed
    layout) and its page pools ``k``, ``v`` [pages, page, kv heads,
    head_dim], the arrays themselves, not copies. A request that ended at
    its ``max_tokens`` leaves in its slot the state after its last fed token
    (``decode_multi``'s ``max_new``), and its pages are freed but not yet
    written again."""
    held = {"ssm": engine.state.arrays["ssm"], "k": engine.k_caches, "v": engine.v_caches}
    return {**engine.params, "held": held}
