"""Configurations that run through ``dynamo_tpu.models.mla.MlaConfig`` with a
learned indexer (``model_type`` ``glm_moe_dsa``: GLM-5.2): latent attention
over the keys the indexer selects, the selection shared by the layers after
a selecting one, sigmoid-routed experts of which this chip holds a share,
one shared expert.

The configuration file keeps the public ``config.json`` lists whole; the
layers run are ``num_hidden_layers`` entries from ``layer_offset`` on. A
program whose ``MlaConfig`` knows no indexer raises ``TypeError`` here,
before anything is placed on a device.

``reference_params`` hands the engine's parameters to the plain reference
under the reference's names and in the publication's layouts, which are not
the program's: the program keeps ``W_uk`` transposed (it folds it into the
query) and rotates rotary dims as halves ``(j, j + d/2)`` where the
publication rotates interleaved pairs ``(2j, 2j + 1)``, so the columns that
feed a rotation are re-interleaved here (random weights: a permutation of
columns is the same model in the other layout).
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.mla import MlaConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    lo, L = int(cfg.get("layer_offset", 0)), int(cfg["num_hidden_layers"])
    rp = cfg["rope_parameters"]
    if rp.get("rope_type", "default") != "default":
        raise ValueError("rotary positions are run plain, at rope_theta")
    if int(cfg["n_group"]) != 1 or cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("this adapter runs the sigmoid router with its bias and no group limit")
    if not (cfg["rope_interleave"] and cfg["indexer_rope_interleave"]):
        raise ValueError("reference_params re-interleaves rotary columns: both layouts are interleaved")
    held = (int(cfg["experts_held_first"]), int(cfg["n_routed_experts"]))
    whole = held == (0, int(cfg["router_outputs"]))
    return MlaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=L,
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(rp["rope_theta"]),
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
        rope_interleave=True,
        mlp_layer_types=tuple(cfg["mlp_layer_types"][lo:lo + L]),
        experts_held=None if whole else held,
        index_topk=cfg["index_topk"],
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        indexer_types=tuple(cfg["indexer_types"][lo:lo + L]),
    )


def _interleave(w, start: int, n: int):
    """Columns ``start .. start + n`` of the last dim from halves
    ``[x0 .. | y0 ..]`` to pairs ``[x0 y0 x1 y1 ..]``."""
    order = np.arange(w.shape[-1])
    order[start:start + n] = start + np.stack([np.arange(n // 2), n // 2 + np.arange(n // 2)], 1).reshape(-1)
    return w[..., order]


def layer_reference_params(lp: Dict[str, Any], mcfg) -> Dict[str, Any]:
    nh, nope, rope = mcfg.num_heads, mcfg.qk_nope_head_dim, mcfg.qk_rope_head_dim
    out = {k: v for k, v in lp.items() if k not in ("w_uq", "w_dkv", "w_uk", "w_uv", "wo", "w_iq", "w_ik")}
    out["w_uq"] = _interleave(lp["w_uq"].reshape(-1, nh, nope + rope), nope, rope)
    out["w_dkv"] = _interleave(lp["w_dkv"], mcfg.kv_lora_rank, rope)
    out["w_uk"] = lp["w_uk"].transpose(0, 2, 1)                    # [heads, rank, nope]
    out["w_uv"] = lp["w_uv"]                                       # [heads, rank, v]
    out["wo"] = lp["wo"].reshape(nh, mcfg.v_head_dim, -1)
    if "w_iq" in lp:
        out["w_iq"] = _interleave(lp["w_iq"].reshape(-1, mcfg.index_n_heads, mcfg.index_head_dim), 0, rope)
        out["w_ik"] = _interleave(lp["w_ik"], 0, rope)
        out["ik_norm_w"] = _interleave(lp["ik_norm_w"], 0, rope)
        out["ik_norm_b"] = _interleave(lp["ik_norm_b"], 0, rope)
    return out


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names and layouts
    (``benchmarks/reference/mla_dsa_decoder.py`` lists them). Only the
    matrices that feed a rotation or are stored transposed are copied."""
    p = engine.params
    out = {k: v for k, v in p.items() if k != "layers"}
    out["layers"] = [layer_reference_params(lp, engine.mcfg) for lp in p["layers"]]
    return out
