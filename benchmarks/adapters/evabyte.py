"""Configurations that run through
``dynamo_tpu.models.evabyte.EvaByteConfig`` (``model_type`` ``evabyte``,
``attention_class`` ``eva``): every layer attends exactly inside a window and
to one learned summary a chunk of every window before it; a request's pages
are a ring of one window, its summaries are kept by window.

The layers run are published layers ``0 .. num_hidden_layers - 1`` (every
layer is alike); every width, every head, the whole vocabulary and all the
prediction heads are as published. A program without the family fails at
this module's import of it (``model_config``), before anything is placed on
a device.
"""

from __future__ import annotations

from typing import Any, Dict


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.evabyte import EvaByteConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if cfg["model_type"] != "evabyte" or cfg["attention_class"] != "eva":
        raise ValueError("this adapter runs evabyte with EVA attention")
    if not (cfg["norm_add_unit_offset"] and cfg["fp32_skip_add"] and cfg["fp32_logits"]
            and cfg["mixedp_attn"]) or cfg["fp32_ln"] or cfg["attention_bias"]:
        raise ValueError("this adapter runs the published switches: norms of 1 + w, a float32 "
                         "stream, float32 logits and softmaxes, no bias")
    if cfg["rope_scaling"] is not None or cfg["tie_word_embeddings"]:
        raise ValueError("this adapter runs plain rotary positions and an untied head")
    return EvaByteConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]),
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position=cfg["max_position_embeddings"],
        window_size=int(cfg["window_size"]),
        chunk_size=int(cfg["chunk_size"]),
        num_pred_heads=int(cfg["num_pred_heads"]),
        dtype=dtypes[cfg["torch_dtype"]],
    )


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names: the program's
    pytree already uses them (``benchmarks/reference/evabyte_decoder.py``
    lists them; matrices [in, out], ``lm_head``'s columns head by head).
    Beside them, under ``held``, what the engine HOLDS as it stands (called
    after the samples ended, before anything else runs): the pools ``k``,
    ``v`` [pages, page, heads, head_dim], one a layer, the arrays themselves
    and not copies, and ``summary_base``, the first page of the summary
    blocks (the ring's pages lie below it). A request that ended leaves its
    summary blocks freed but not yet written again."""
    held = {"k": engine.k_caches, "v": engine.v_caches,
            "summary_base": int(engine.cfg.num_blocks)}
    return {**engine.params, "held": held}
