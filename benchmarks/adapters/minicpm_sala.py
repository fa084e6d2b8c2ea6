"""Configurations that run through
``dynamo_tpu.models.minicpm_sala.MiniCpmSalaConfig`` (``model_type``
``minicpm_sala``): block-sparse grouped-query attention over a cache of
pooled keys in one layer of four, lightning attention (a fixed decay a head,
a matrix state a slot) in the other three, a dense SwiGLU and muP scalings
everywhere.

The layers run are ``num_hidden_layers`` consecutive published layers from
``first_layer_run`` on (``mixer_types`` is the published list, whole);
``mup_denominator`` stays the published depth. The selection's sizes, which the public configuration does
not fix, are the file's ``assumed_sizes``. A program without the family
fails at this module's import of it (``model_config``), before anything is
placed on a device.
"""

from __future__ import annotations

from typing import Any, Dict


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.minicpm_sala import MiniCpmSalaConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    L = int(cfg["num_hidden_layers"])
    first = int(cfg.get("first_layer_run", 0))
    kinds = list(cfg["mixer_types"])[first:first + L]
    if cfg["model_type"] != "minicpm_sala" or len(kinds) != L or set(kinds) - {"minicpm4", "lightning-attn"}:
        raise ValueError("this adapter runs minicpm_sala with a mixer type a layer held")
    if (cfg["attn_use_rope"] or not cfg["lightning_use_rope"] or not cfg["qk_norm"]
            or cfg["attention_bias"] or not cfg["use_output_gate"] or not cfg["use_output_norm"]
            or not cfg["attn_use_output_gate"] or cfg["hidden_act"] != "silu"
            or cfg["lightning_scale"] != "1/sqrt(d)"
            or int(cfg["lightning_nkv"]) != int(cfg["lightning_nh"])):
        raise ValueError("this adapter runs sparse layers without positions, lightning layers "
                         "with rotary and as many key heads as heads, q/k norms, output gates "
                         "and the lightning output norm, no bias")
    return MiniCpmSalaConfig(
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
        sparse_layers=tuple(i for i, kind in enumerate(kinds) if kind == "minicpm4"),
        scale_emb=float(cfg["scale_emb"]),
        scale_depth=float(cfg["scale_depth"]),
        mup_denominator=int(cfg["mup_denominator"]),
        dim_model_base=int(cfg["dim_model_base"]),
        lightning_heads=int(cfg["lightning_nh"]),
        lightning_head_dim=int(cfg["lightning_head_dim"]),
        **{k: int(v) for k, v in cfg["assumed_sizes"].items()},
    )


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names: the program's
    pytree already uses them (``benchmarks/reference/minicpm_sala_decoder.py``
    lists them; matrices [in, out]). Beside them, under ``held``, what the
    engine HOLDS as it stands (called after the samples ended, before
    anything else runs): the slot states ``state`` [slots, heads, d, d], one
    array a lightning layer in order; the page pools ``k``, ``v`` [pages,
    page, kv heads, head_dim], one a sparse layer in order; and
    ``pool_base``, the K pool's first page of pooled keys (one row a block
    id above the requests' pages): the arrays themselves, not copies. A
    request that ended at its ``max_tokens`` leaves in its slot the state
    after its last fed token (``decode_multi``'s ``max_new``), and its pages
    and pooled keys are freed but not yet written again."""
    held = {"state": engine.state.arrays["lightning"], "k": engine.k_caches,
            "v": engine.v_caches, "pool_base": engine.cfg.num_blocks}
    return {**engine.params, "held": held}
