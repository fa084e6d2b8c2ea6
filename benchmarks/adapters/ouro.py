"""Configurations that run through ``dynamo_tpu.models.ouro.OuroConfig``
(``model_type`` ``ouro``: ByteDance Ouro-2.6B): one stack of dense layers run
``total_ut_steps`` times a token, a cache slot a (pass, layer).

A program without the family fails at this module's import of it
(``model_config``), before anything is placed on a device.

``reference_params`` hands the engine's parameters to the plain reference
under the reference's names, which the program's pytree already uses
(``benchmarks/reference/ouro_decoder.py`` lists them; matrices [in, out]).
Beside them, under ``held``, what the engine HOLDS of the first and the last
slot as it stands (called after the samples ended, before anything else
runs): a page layer's arrays hold a pool a pass, one behind another, each of
``num_blocks`` pages (``models/registry.page_passes``), so slot ``(t, l)`` is
pages ``t x num_blocks .. (t + 1) x num_blocks`` of layer ``l``'s arrays, and
a block id names its page in every slot. A request that ended leaves its
pages freed but not yet written again.
"""

from __future__ import annotations

from typing import Any, Dict


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from dynamo_tpu.models.ouro import OuroConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if cfg["model_type"] != "ouro" or cfg["rope_scaling"] is not None:
        raise ValueError("this adapter runs ouro with plain rotary positions")
    if (cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] or cfg["use_sliding_window"]
            or cfg["sliding_window"] is not None
            or set(cfg["layer_types"]) != {"full_attention"}):
        raise ValueError("this adapter runs SwiGLU, an untied head and full attention "
                         "in every layer")
    return OuroConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=int(cfg["head_dim"]),
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position=cfg["max_position_embeddings"],
        tie_embeddings=False,
        passes=int(cfg["total_ut_steps"]),
        early_exit_threshold=float(cfg["early_exit_threshold"]),
        dtype=dtypes[cfg["torch_dtype"]],
    )


def reference_params(engine) -> Dict[str, Any]:
    """The engine's parameters under the reference's names, and ``held``:
    the pools ``(k, v)`` [num_blocks, page, kv heads, head_dim] of slot
    ``(0, 0)`` (``first``) and slot ``(passes - 1, num_layers - 1)``
    (``last``)."""
    n, last = engine.cfg.num_blocks, engine.mcfg.passes - 1
    k, v = engine.k_caches, engine.v_caches
    held = {
        "first": (k[0][:n], v[0][:n]),
        "last": (k[-1][last * n : (last + 1) * n], v[-1][last * n : (last + 1) * n]),
    }
    return {**engine.params, "held": held}
