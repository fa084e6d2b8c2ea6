"""Configurations that run through ``dynamo_tpu.models.mla.MlaConfig``
WITHOUT an indexer (``model_type`` ``axk1``: A.X-K1; the DeepSeek-V3 layer):
latent attention over every causal key, YaRN positions with their softmax
factor, group-limited sigmoid routing with a bias, one shared expert, and a
held share of each sparse layer's experts.

The layers run are published layers ``0 .. num_hidden_layers - 1``: the
first ``first_k_dense_replace`` dense, the others sparse. A program whose
``MlaConfig`` lacks the YaRN fields raises ``TypeError`` here, before
anything is placed on a device (the constructor refuses the keyword).

``reference_params`` is ``mla_dsa``'s: the program keeps ``W_uk`` transposed
and rotates rotary dims as halves where the publication rotates interleaved
pairs, so the columns that feed a rotation are re-interleaved.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.adapters.mla_dsa import reference_params  # noqa: F401


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.mla import MlaConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    rs = cfg["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError("this adapter runs YaRN positions (rope_scaling.type yarn)")
    if cfg["scoring_func"] != "sigmoid" or not cfg["rope_interleave"]:
        raise ValueError("this adapter runs the sigmoid router with its bias, and interleaved rotary pairs")
    if int(cfg["router_outputs"]) % int(cfg["n_group"]):
        raise ValueError("the router's outputs divide into n_group groups")
    held = (int(cfg["experts_held_first"]), int(cfg["n_routed_experts"]))
    whole = held == (0, int(cfg["router_outputs"]))
    return MlaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position=cfg["max_position_embeddings"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=dtypes[cfg["torch_dtype"]],
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        num_experts=cfg["router_outputs"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        moe_scoring="sigmoid",
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        num_shared_experts=cfg["n_shared_experts"],
        first_dense_layers=int(cfg["first_k_dense_replace"]),
        n_group=int(cfg["n_group"]),
        topk_group=int(cfg["topk_group"]),
        rope_interleave=True,
        experts_held=None if whole else held,
        rope_scaling_factor=float(rs["factor"]),
        rope_original_max_position=int(rs["original_max_position_embeddings"]),
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
    )
