"""Configurations that run through
``dynamo_tpu.models.dots3_note.Dots3NoteConfig`` (``model_type``
``dots3_note``: dots3-note-prev): latent attention of two widths by layer
kind, an indexer's selection in the full layers and a window in the sliding
ones, a headwise gate and the latents' rescale on both, sigmoid-routed
experts of which this chip holds a share beside one shared expert; pages
kept, and shaped, by layer kind.

The layers run are published layers ``0 .. num_hidden_layers - 1``;
``n_routed_experts`` counts the experts held, from ``experts_held_first``,
of ``router_outputs``; the vocabulary is the slice the file holds. A program
without the family fails at this module's import of it (``model_config``),
before anything is placed on a device.

``reference_params`` hands the engine's parameters to the plain reference
under the reference's names and in the publication's layouts
(``adapters/mla_dsa.py`` ``layer_reference_params``, a layer at its KIND's
sizes: ``W_uk`` transposed back, the columns that feed a rotation
re-interleaved); the gate ``w_g`` goes as it is.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.adapters.mla_dsa import layer_reference_params


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.dots3_note import Dots3NoteConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    L = int(cfg["num_hidden_layers"])
    if cfg["model_type"] != "dots3_note" or cfg["rope_scaling"] is not None:
        raise ValueError("this adapter runs dots3_note with plain rotary positions")
    if (cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc"
            or cfg["hidden_act"] != "silu" or cfg["attention_bias"]
            or int(cfg["moe_layer_freq"]) != 1 or cfg["tie_word_embeddings"]):
        raise ValueError("this adapter runs the sigmoid router with its bias, SwiGLU, "
                         "no attention bias, experts in every layer past the dense ones, "
                         "an untied head")
    gates = {cfg["attention_gate_type"], cfg["swa_attention_gate_type"]}
    if gates != {"headwise"}:
        raise ValueError(f"this adapter runs the headwise gate on both kinds, not {gates}")
    if (int(cfg["swa_num_key_value_heads"]) != int(cfg["swa_num_attention_heads"])
            or int(cfg["num_key_value_heads"]) != int(cfg["num_attention_heads"])):
        raise ValueError("latent attention: a key head a query head, both kinds")
    held = (int(cfg["experts_held_first"]), int(cfg["n_routed_experts"]))
    whole = held == (0, int(cfg["router_outputs"]))
    return Dots3NoteConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=L,
        layer_types=tuple(cfg["layer_types"][:L]),
        intermediate_size=cfg["intermediate_size"],
        first_dense_layers=int(cfg["first_k_dense_replace"]),
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        index_topk=cfg["index_topk"],
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        swa_num_heads=cfg["swa_num_attention_heads"],
        swa_q_lora_rank=cfg["swa_q_lora_rank"],
        swa_kv_lora_rank=cfg["swa_kv_lora_rank"],
        swa_qk_nope_head_dim=cfg["swa_qk_nope_head_dim"],
        swa_qk_rope_head_dim=cfg["swa_qk_rope_head_dim"],
        swa_v_head_dim=cfg["swa_v_head_dim"],
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        sliding_window=int(cfg["sliding_window_size"]),
        lora_rescale=bool(cfg["apply_mla_qkv_lora_rescale"]),
        num_experts=int(cfg["router_outputs"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        moe_scoring="sigmoid",
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        num_shared_experts=int(cfg["n_shared_experts"]),
        experts_held=None if whole else held,
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position=cfg["max_position_embeddings"],
        tie_embeddings=False,
        dtype=dtypes[cfg["torch_dtype"]],
    )


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names and layouts
    (``benchmarks/reference/dots3_note_decoder.py`` lists them), a layer at
    its kind's sizes."""
    p, mcfg = engine.params, engine.mcfg
    out = {k: v for k, v in p.items() if k != "layers"}
    out["layers"] = [
        layer_reference_params(lp, mcfg.kind(i)) for i, lp in enumerate(p["layers"])
    ]
    return out
