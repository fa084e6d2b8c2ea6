"""Configurations that run through
``dynamo_tpu.models.cohere2_moe.Cohere2MoeConfig`` (``model_type``
``cohere2_moe``): a parallel attention + expert block under one LayerNorm,
sliding-window layers with rotary positions beside full layers without any,
a sigmoid-routed feed-forward of which this chip holds a share beside shared
experts that are averaged; pages kept by layer kind.

The layers run are published layers ``0 .. num_hidden_layers - 1`` (whole
periods of the 4-layer pattern from its start); ``num_experts`` counts the
experts held, from ``experts_held_first``, of ``router_outputs``; the
vocabulary is the slice the file holds. A program without the family fails
at this module's import of it (``model_config``), before anything is placed
on a device.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.cohere2_moe import Cohere2MoeConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    L = int(cfg["num_hidden_layers"])
    if cfg["model_type"] != "cohere2_moe" or not cfg["use_parallel_block"]:
        raise ValueError("this adapter runs cohere2_moe with the parallel block")
    if (cfg["position_embedding_type"] != "rope_gptj" or cfg["rotary_pct"] != 1
            or cfg["use_qk_norm"] or cfg["attention_bias"] or cfg["rms_norm_eps"] is not None):
        raise ValueError("this adapter runs interleaved rotary positions over the whole head, "
                         "no q/k norm, no bias, a LayerNorm")
    if (int(cfg["first_k_dense_replace"]) != 0 or cfg["expert_selection_fn"] != "sigmoid"
            or not cfg["use_gated_activation"] or cfg["hidden_act"] != "silu"
            or not cfg["tie_word_embeddings"]):
        raise ValueError("this adapter runs the sigmoid-routed SwiGLU feed-forward in every "
                         "layer and a tied head")
    strategy = {"average": "average", "sum": "sum"}[cfg["shared_expert_combination_strategy"]]
    held = (int(cfg["experts_held_first"]), int(cfg["num_experts"]))
    whole = held == (0, int(cfg["router_outputs"]))
    return Cohere2MoeConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=L,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"][:L]),
        sliding_window=int(cfg["sliding_window"]),
        rope_theta=float(cfg["rope_theta"]),
        layer_norm_eps=float(cfg["layer_norm_eps"]),
        max_position=cfg["max_position_embeddings"],
        tie_embeddings=True,
        logit_scale=float(cfg["logit_scale"]),
        dtype=dtypes[cfg["torch_dtype"]],
        num_experts=int(cfg["router_outputs"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(cfg["intermediate_size"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        num_shared_experts=int(cfg["num_shared_experts"]),
        shared_expert_combination=strategy,
        experts_held=None if whole else held,
    )


def published_layout(w, heads: int) -> np.ndarray:
    """A rotated projection [in, heads x d] as PUBLISHED: a head's rotary
    pairs at lanes ``(2i, 2i + 1)``. The engine rotates first-half /
    second-half pairs and loads such a matrix de-interleaved a head
    (``engine/weights.py`` ``_deinterleave_rope_rows``): this is the inverse,
    served lane ``i`` to ``2i`` and ``d/2 + i`` to ``2i + 1``, on the host."""
    w = np.asarray(w)
    rows, cols = w.shape
    d = cols // heads
    return w.reshape(rows, heads, 2, d // 2).transpose(0, 1, 3, 2).reshape(rows, cols)


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names: the program's
    pytree already uses them (``benchmarks/reference/cohere2_moe_decoder.py``
    lists them; matrices [in, out], the expert stacks over the experts held,
    the shared experts side by side). ``wq`` and ``wk`` of the layers that
    rotate go in the published pair layout (``published_layout``), as host
    arrays the reference places a layer at a time. Beside them, under
    ``held``, what the engine HOLDS as it stands (called after the samples
    ended, before anything else runs): the pools ``k``, ``v`` [pages, page,
    kv heads, head_dim], one a layer in order (each of its own group's
    size), the arrays themselves and not copies. A request that ended leaves
    its pages freed but not yet written again."""
    mcfg = engine.mcfg
    layers = []
    for i, lp in enumerate(engine.params["layers"]):
        if mcfg.window_for_layer(i) is not None:
            lp = {**lp, "wq": published_layout(lp["wq"], mcfg.num_heads),
                  "wk": published_layout(lp["wk"], mcfg.num_kv_heads)}
        layers.append(lp)
    held = {"k": engine.k_caches, "v": engine.v_caches}
    return {**engine.params, "layers": layers, "held": held}
