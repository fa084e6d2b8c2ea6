"""Configurations that run through ``dynamo_tpu.models.llama.LlamaConfig``:
the decoder-only family with RMSNorm, rotary positions, grouped-query
attention and a SwiGLU feed-forward (InternLM2, Mistral, ...).

An adapter turns a configuration file (public ``config.json`` keys) into the
program's model class and hands the program's parameters to the plain
reference under the reference's names. A configuration file names its
adapter (``"adapter": "llama"``); a family the program runs through another
class gets an adapter file of its own.
"""

from __future__ import annotations

from typing import Any, Dict


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import LlamaConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=head_dim,
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position=cfg["max_position_embeddings"],
        qkv_bias=bool(cfg.get("bias", False)),
        qk_norm=False,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=dtypes[cfg["torch_dtype"]],
    )


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names. The program's
    pytree already uses them (embed, final_norm, lm_head, layers[i]: attn_norm,
    wq, wk, wv, wo, mlp_norm, w_gate, w_up, w_down; matrices stored
    [in, out])."""
    return engine.params
