"""Configurations that run through ``dynamo_tpu.models.moe.MoeConfig`` with
per-layer attention kinds: every layer sparse experts (softmax router, top-k,
renormalised), sliding-window layers beside full ones, a rotary table per
layer kind (Mellum2-12B-A2.5B-Instruct).

The configuration file keeps the public ``config.json`` lists whole; the
layers run are the first ``num_hidden_layers`` entries of ``layer_types``.
A program whose ``MoeConfig`` knows no layer kinds raises ``TypeError`` here,
before anything is placed on a device.
"""

from __future__ import annotations

from typing import Any, Dict


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.moe import MoeConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    L = int(cfg["num_hidden_layers"])
    if set(cfg["mlp_layer_types"][:L]) != {"sparse"}:
        raise ValueError("this adapter runs configurations whose every layer is sparse")
    full = cfg["rope_parameters"]["full_attention"]
    sliding = cfg["rope_parameters"]["sliding_attention"]
    if sliding.get("rope_type", "default") != "default" or sliding["rope_theta"] != full["rope_theta"]:
        raise ValueError("sliding layers are run with plain rotary positions at the full layers' theta")
    yarn = full.get("rope_type") == "yarn"
    return MoeConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=L,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],  # used by no layer
        rope_theta=float(full["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position=cfg["max_position_embeddings"],
        qkv_bias=bool(cfg["attention_bias"]),
        qk_norm=bool(cfg["qk_norm"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=dtypes[cfg["torch_dtype"]],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        layer_types=tuple(cfg["layer_types"][:L]),
        sliding_window=int(cfg["sliding_window"]),
        rope_scaling_factor=float(full["factor"]) if yarn else 0.0,
        rope_original_max_position=int(full.get("original_max_position_embeddings", 0)) or 8192,
        rope_beta_fast=float(full.get("beta_fast", 32.0)),
        rope_beta_slow=float(full.get("beta_slow", 1.0)),
        rope_truncate=bool(full.get("truncate", True)),
        rope_attention_factor=full.get("attention_factor"),
    )


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names. The program's
    pytree already uses them (embed, final_norm, lm_head, layers[i]:
    attn_norm, wq, wk, wv, wo, q_norm, k_norm, mlp_norm, w_router, w_gate,
    w_up, w_down; matrices stored [in, out], experts stacked in front)."""
    return engine.params
