"""HF checkpoint loading: safetensors -> llama param pytree.

Loads local HuggingFace-format checkpoints (config.json + *.safetensors) into
the functional param layout of models/llama.py. Works fully offline; when no
checkpoint is given the engine random-initializes (benchmark throughput does
not depend on trained weights).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from ..models.llama import LlamaConfig
from ..runtime.logging import get_logger

log = get_logger("engine.weights")


def config_from_hf(path: str) -> LlamaConfig:
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    if hf.get("model_type", "") in ("deepseek_v2", "deepseek_v3", "axk1"):
        return _mla_config_from_hf(hf)
    if hf.get("model_type", "") == "gpt_oss":
        return _gptoss_config_from_hf(hf)
    if hf.get("model_type", "") in ("gemma2", "gemma3", "gemma3_text"):
        return _gemma_config_from_hf(hf)
    if hf.get("model_type", "") == "falcon_h1":
        return _falcon_h1_config_from_hf(hf)
    if hf.get("model_type", "") == "solar_open2":
        return _solar_open2_config_from_hf(hf)
    if hf.get("model_type", "") == "cohere2_moe":
        return _cohere2_moe_config_from_hf(hf)
    if hf.get("model_type", "") == "minicpm_sala":
        return _minicpm_sala_config_from_hf(hf)
    if hf.get("model_type", "") == "dots3_note":
        return _dots3_note_config_from_hf(hf)
    head_dim = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return LlamaConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=head_dim,
        intermediate_size=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        max_position=hf.get("max_position_embeddings", 8192),
        qkv_bias=hf.get("attention_bias", False)
        or hf.get("model_type", "") == "qwen2",
        qk_norm=hf.get("model_type", "") == "qwen3",
        tie_embeddings=hf.get("tie_word_embeddings", False),
    )


def _dots3_note_config_from_hf(hf: dict):
    """dots3_note's public config.json -> Dots3NoteConfig (the language
    model: the towers and the multi-token-prediction head have no key here).
    A key whose value would change the computation is refused, not ignored
    (benchmarks/adapters/dots3_note.py is the benchmark's side of this map,
    with a held share of the experts)."""
    from ..models.dots3_note import Dots3NoteConfig

    gates = {hf.get("attention_gate_type"), hf.get("swa_attention_gate_type")}
    if (gates != {"headwise"} or hf.get("rope_scaling") is not None
            or hf.get("scoring_func") != "sigmoid"
            or hf.get("topk_method") != "noaux_tc"
            or hf.get("attention_bias") or hf.get("tie_word_embeddings")
            or int(hf.get("moe_layer_freq", 1)) != 1):
        raise ValueError(
            "dots3_note with a gate that is not headwise on both kinds, "
            "scaled rotary positions, a router other than sigmoid noaux_tc, "
            "an attention bias, a tied head or experts in some layers only "
            "is not built"
        )
    L = int(hf["num_hidden_layers"])
    return Dots3NoteConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=L, layer_types=tuple(hf["layer_types"][:L]),
        intermediate_size=hf["intermediate_size"],
        first_dense_layers=int(hf.get("first_k_dense_replace", 0)),
        num_heads=hf["num_attention_heads"], q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
        rope_theta=float(hf["rope_theta"]), index_topk=hf["index_topk"],
        index_n_heads=hf["index_n_heads"], index_head_dim=hf["index_head_dim"],
        swa_num_heads=hf["swa_num_attention_heads"],
        swa_q_lora_rank=hf["swa_q_lora_rank"],
        swa_kv_lora_rank=hf["swa_kv_lora_rank"],
        swa_qk_nope_head_dim=hf["swa_qk_nope_head_dim"],
        swa_qk_rope_head_dim=hf["swa_qk_rope_head_dim"],
        swa_v_head_dim=hf["swa_v_head_dim"],
        swa_rope_theta=float(hf["swa_rope_theta"]),
        sliding_window=int(hf["sliding_window_size"]),
        lora_rescale=bool(hf.get("apply_mla_qkv_lora_rescale", False)),
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        num_shared_experts=hf.get("n_shared_experts", 0), experts_held=None,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        max_position=hf.get("max_position_embeddings", 524288),
    )


def _solar_open2_config_from_hf(hf: dict):
    """Solar Open 2's config.json -> SolarOpen2Config: every key that shapes
    the computation; what the keys do not fix is the class's own default
    (benchmarks/adapters/solar_open2.py is the benchmark's own copy of this
    mapping, with its held share of the experts)."""
    from ..models.solar_open2 import SolarOpen2Config

    lin = hf["linear_attn_config"]
    L = hf["num_hidden_layers"]
    if hf.get("use_rope") or hf.get("kda_use_full_proj") or hf.get("first_k_dense_replace"):
        raise ValueError("solar_open2 with rotary positions, full-rank decay "
                         "projections or leading dense layers is not built")
    return SolarOpen2Config(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_size=hf["intermediate_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        max_position=hf.get("max_position_embeddings", 8192),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        gqa_layers=tuple(i for i in hf["gqa_layers"] if i < L),
        use_gqa_gate=bool(hf.get("use_gqa_gate", True)),
        kda_num_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv_kernel=lin["short_conv_kernel_size"],
        kda_low_rank=lin["head_dim"],
        kda_allow_neg_eigval=bool(hf.get("kda_allow_neg_eigval", True)),
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        num_shared_experts=hf.get("n_shared_experts", 1),
    )


def _minicpm_sala_config_from_hf(hf: dict):
    """MiniCPM-SALA's config.json -> MiniCpmSalaConfig: every key that shapes
    the computation; the selection's sizes, which the keys do not fix, are
    ``sparse_config``'s where the file has one and the class's defaults (the
    family's convention) where not. benchmarks/adapters/minicpm_sala.py is
    the benchmark's own copy of this mapping, with the layers it runs."""
    from ..models.minicpm_sala import MiniCpmSalaConfig

    if (hf.get("attn_use_rope") or not hf.get("lightning_use_rope", True)
            or not hf.get("qk_norm", True) or hf.get("attention_bias")
            or not (hf.get("use_output_gate", True) and hf.get("use_output_norm", True)
                    and hf.get("attn_use_output_gate", True))
            or hf.get("lightning_nkv", hf["lightning_nh"]) != hf["lightning_nh"]):
        raise ValueError("minicpm_sala with rotary sparse layers, lightning "
                         "layers without rotary, no q/k norm, biases, no output "
                         "gate or norm, or grouped lightning keys is not built")
    sparse = hf.get("sparse_config") or {}
    sizes = {k: sparse[k] for k in ("kernel_size", "kernel_stride", "block_size", "topk",
                                    "init_blocks", "window_size", "dense_len") if k in sparse}
    return MiniCpmSalaConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_size=hf["intermediate_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        max_position=hf.get("max_position_embeddings", 8192),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        sparse_layers=tuple(i for i, kind in enumerate(hf["mixer_types"]) if kind == "minicpm4"),
        scale_emb=float(hf.get("scale_emb", 1.0)),
        scale_depth=float(hf.get("scale_depth", 1.0)),
        mup_denominator=int(hf.get("mup_denominator", hf["num_hidden_layers"])),
        dim_model_base=int(hf.get("dim_model_base", hf["hidden_size"])),
        lightning_heads=hf["lightning_nh"],
        lightning_head_dim=hf["lightning_head_dim"],
        **sizes,
    )


def _cohere2_moe_config_from_hf(hf: dict):
    """Command A+'s config.json -> Cohere2MoeConfig: every key that shapes
    the computation (benchmarks/adapters/cohere2_moe.py is the benchmark's
    own copy of this mapping, with its held share of the experts)."""
    from ..models.cohere2_moe import Cohere2MoeConfig

    L = hf["num_hidden_layers"]
    if (not hf.get("use_parallel_block", True) or hf.get("use_qk_norm")
            or hf.get("attention_bias") or hf.get("first_k_dense_replace")
            or hf.get("position_embedding_type", "rope_gptj") != "rope_gptj"
            or hf.get("expert_selection_fn", "sigmoid") != "sigmoid"):
        raise ValueError("cohere2_moe with a sequential block, a q/k norm, "
                         "biases, leading dense layers, another rotary layout "
                         "or another router is not built")
    return Cohere2MoeConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
        layer_types=tuple(hf["layer_types"][:L]),
        sliding_window=int(hf["sliding_window"]),
        rope_theta=float(hf.get("rope_theta", 50000.0)),
        layer_norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
        max_position=hf.get("max_position_embeddings", 8192),
        tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
        logit_scale=float(hf.get("logit_scale", 1.0)),
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["intermediate_size"],
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        num_shared_experts=hf.get("num_shared_experts", 0),
        shared_expert_combination=hf.get("shared_expert_combination_strategy", "average"),
    )


def _falcon_h1_config_from_hf(hf: dict):
    """Falcon-H1's config.json -> FalconH1Config: every key that shapes the
    computation, the multipliers as data (benchmarks/adapters/falcon_h1.py is
    the benchmark's own copy of this mapping)."""
    from ..models.falcon_h1 import FalconH1Config

    if hf.get("mamba_proj_bias") or hf.get("attention_bias") or hf.get("mlp_bias"):
        raise ValueError("falcon_h1 with projection biases is not built")
    if not hf.get("mamba_rms_norm", True) or hf.get("rope_scaling"):
        raise ValueError("falcon_h1 without the mixer's gated RMSNorm, or "
                         "with scaled rotary positions, is not built")
    return FalconH1Config(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_size=hf["intermediate_size"],
        rope_theta=float(hf.get("rope_theta", 1e11)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        max_position=hf.get("max_position_embeddings", 8192),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        mamba_d_ssm=hf["mamba_d_ssm"],
        mamba_n_heads=hf["mamba_n_heads"],
        mamba_d_head=hf["mamba_d_head"],
        mamba_d_state=hf["mamba_d_state"],
        mamba_n_groups=hf["mamba_n_groups"],
        mamba_d_conv=hf["mamba_d_conv"],
        mamba_chunk_size=hf.get("mamba_chunk_size", 128),
        mamba_conv_bias=hf.get("mamba_conv_bias", True),
        mamba_norm_before_gate=hf.get("mamba_norm_before_gate", False),
        embedding_multiplier=hf.get("embedding_multiplier", 1.0),
        lm_head_multiplier=hf.get("lm_head_multiplier", 1.0),
        attention_in_multiplier=hf.get("attention_in_multiplier", 1.0),
        attention_out_multiplier=hf.get("attention_out_multiplier", 1.0),
        key_multiplier=hf.get("key_multiplier", 1.0),
        ssm_in_multiplier=hf.get("ssm_in_multiplier", 1.0),
        ssm_out_multiplier=hf.get("ssm_out_multiplier", 1.0),
        ssm_multipliers=tuple(hf.get("ssm_multipliers", (1.0,) * 5)),
        mlp_multipliers=tuple(hf.get("mlp_multipliers", (1.0, 1.0))),
    )


def _mla_config_from_hf(hf: dict):
    """DeepSeek V2/V3 (and A.X-K1, the same layer) config.json -> MlaConfig
    (models/mla.py). A DeepSeek-style ``rope_scaling`` dict of ``type`` (or
    ``rope_type``) ``yarn`` becomes the YaRN fields; any other kind of
    scaling is refused, not run plain."""
    from ..models.mla import MlaConfig

    rs = hf.get("rope_scaling") or {}
    yarn = {}
    kind = rs.get("rope_type", rs.get("type", "default"))
    if kind == "yarn":
        yarn = dict(
            rope_scaling_factor=float(rs["factor"]),
            rope_original_max_position=int(
                rs.get("original_max_position_embeddings")
                or hf.get("max_position_embeddings", 4096)
            ),
            rope_beta_fast=float(rs.get("beta_fast") or 32.0),
            rope_beta_slow=float(rs.get("beta_slow") or 1.0),
            rope_mscale=float(rs.get("mscale") or 0.0),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim") or 0.0),
        )
    elif kind != "default":
        raise ValueError(
            f"rope_scaling of kind {kind!r} is not built for the MLA family"
        )
    return MlaConfig(
        **yarn,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        q_lora_rank=hf.get("q_lora_rank") or 0,
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        intermediate_size=hf["intermediate_size"],
        num_experts=hf.get("n_routed_experts") or 0,
        num_experts_per_tok=hf.get("num_experts_per_tok") or 2,
        moe_intermediate_size=hf.get("moe_intermediate_size") or 0,
        norm_topk_prob=hf.get("norm_topk_prob", True),
        moe_scoring=hf.get("scoring_func", "sigmoid"),
        routed_scaling_factor=hf.get("routed_scaling_factor", 1.0),
        num_shared_experts=hf.get("n_shared_experts") or 0,
        first_dense_layers=hf.get("first_k_dense_replace", 0),
        n_group=hf.get("n_group") or 1,
        topk_group=hf.get("topk_group") or 1,
        rope_interleave=hf.get("rope_interleave", True),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        max_position=hf.get("max_position_embeddings", 8192),
        tie_embeddings=hf.get("tie_word_embeddings", False),
    )


def _gemma_config_from_hf(hf: dict):
    """Gemma 2 / Gemma 3 config.json -> GemmaConfig (models/gemma.py).
    Multimodal gemma3 nests the language model under text_config."""
    from ..models.gemma import GemmaConfig

    mt = hf.get("model_type", "")
    if mt == "gemma3" and "text_config" in hf:
        hf = hf["text_config"]
        mt = hf.get("model_type", "gemma3_text")
    is3 = mt in ("gemma3", "gemma3_text")
    lt = hf.get("layer_types") or ()
    layer_types = tuple(
        "sliding" if t == "sliding_attention" else "full" for t in lt
    )
    rope_scaling = hf.get("rope_scaling") or {}
    return GemmaConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_size=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        max_position=hf.get("max_position_embeddings", 8192),
        tie_embeddings=hf.get("tie_word_embeddings", True),
        qk_norm=is3,
        query_pre_attn_scalar=float(hf.get("query_pre_attn_scalar", 256)),
        sliding_window=hf.get("sliding_window") or 4096,
        layer_types=layer_types,
        sliding_pattern=hf.get("sliding_window_pattern", 6 if is3 else 2),
        attn_logit_softcap=hf.get("attn_logit_softcapping"),
        final_logit_softcap=hf.get("final_logit_softcapping"),
        rope_local_theta=hf.get("rope_local_base_freq") if is3 else None,
        rope_scaling_factor=float(rope_scaling.get("factor", 1.0)),
    )


def _gptoss_config_from_hf(hf: dict):
    """gpt-oss config.json -> GptOssConfig (models/gptoss.py)."""
    from ..models.gptoss import GptOssConfig

    rs = hf.get("rope_scaling") or {}
    yarn = rs.get("rope_type") == "yarn"
    return GptOssConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_size=hf["intermediate_size"],
        num_experts=hf["num_local_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        sliding_window=hf.get("sliding_window") or 128,
        layer_types=tuple(hf.get("layer_types") or ()),
        rope_theta=hf.get("rope_theta", 150000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        max_position=hf.get("max_position_embeddings", 131072),
        qkv_bias=hf.get("attention_bias", True),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        rope_scaling_factor=rs.get("factor", 0.0) if yarn else 0.0,
        rope_beta_fast=rs.get("beta_fast", 32.0),
        rope_beta_slow=rs.get("beta_slow", 1.0),
        rope_truncate=rs.get("truncate", True),
        rope_original_max_position=rs.get(
            "original_max_position_embeddings",
            hf.get("max_position_embeddings", 4096),
        ),
    )


def _open_safetensors(path: str):
    """Yields (name, np.ndarray) from all safetensors shards in ``path``."""
    from safetensors import safe_open  # available via transformers dep

    files = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    for fname in files:
        with safe_open(os.path.join(path, fname), framework="np") as f:
            for name in f.keys():
                yield name, f.get_tensor(name)


def load_params(path: str, cfg: Optional[LlamaConfig] = None) -> Dict[str, Any]:
    """Map HF llama/qwen (or deepseek-MLA) tensor names onto our pytree."""
    from ..models.mla import MlaConfig

    from ..models.falcon_h1 import FalconH1Config
    from ..models.gptoss import GptOssConfig

    cfg = cfg or config_from_hf(path)
    if isinstance(cfg, FalconH1Config):
        raise NotImplementedError(
            "no checkpoint loader for falcon_h1 yet: the family serves random "
            "weights (models/falcon_h1.init_params); the mapping of "
            "model.layers.N.mamba.{in_proj,conv1d,dt_bias,A_log,D,norm,out_proj}"
            ", self_attn.*_proj and feed_forward.*_proj onto its pytree has "
            "not been held to a real checkpoint (ROADMAP R11)"
        )
    from ..models.solar_open2 import SolarOpen2Config

    if isinstance(cfg, SolarOpen2Config):
        raise NotImplementedError(
            "no checkpoint loader for solar_open2 yet: the family serves "
            "random weights (models/solar_open2.init_params); the mapping of "
            "the checkpoint's tensors onto its pytree has not been held to a "
            "real checkpoint (ROADMAP R11)"
        )
    from ..models.minicpm_sala import MiniCpmSalaConfig

    if isinstance(cfg, MiniCpmSalaConfig):
        raise NotImplementedError(
            "no checkpoint loader for minicpm_sala yet: the family serves "
            "random weights (models/minicpm_sala.init_params); the mapping of "
            "model.layers.N.self_attn.{q,k,v,o}_proj, {q,k}_norm, o_gate / "
            "z_proj, o_norm and mlp.*_proj onto its pytree has not been held "
            "to a real checkpoint (none is here; ROADMAP R11)"
        )
    from ..models.dots3_note import Dots3NoteConfig

    if isinstance(cfg, Dots3NoteConfig):
        raise NotImplementedError(
            "no checkpoint loader for dots3_note yet: the family serves "
            "random weights (models/dots3_note.init_params); a layer's tensors "
            "would map as DeepSeek-V3's do (_load_params_mla, at the layer "
            "kind's sizes: q_a_proj / q_b_proj / kv_a_proj_with_mqa / "
            "kv_b_proj / o_proj, the indexer's wq_b / wk / k_norm / "
            "weights_proj on full layers) plus the headwise gate onto w_g, "
            "but the gate's and the sliding layers' tensor names have not "
            "been held to a real checkpoint (none is here; ROADMAP R11)"
        )
    from ..models.cohere2_moe import Cohere2MoeConfig

    if isinstance(cfg, Cohere2MoeConfig):
        return _load_params_cohere2_moe(path, cfg)
    if isinstance(cfg, MlaConfig):
        return _load_params_mla(path, cfg)
    if isinstance(cfg, GptOssConfig):
        return _load_params_gptoss(path, cfg)
    from ..models.gemma import GemmaConfig

    if isinstance(cfg, GemmaConfig):
        return _load_params_gemma(path, cfg)
    layers: list = [dict() for _ in range(cfg.num_layers)]
    params: Dict[str, Any] = {"layers": layers}
    dt = cfg.dtype

    def put(arr: np.ndarray) -> jnp.ndarray:
        return jnp.asarray(arr, dt)

    for name, w in _open_safetensors(path):
        if name == "model.embed_tokens.weight":
            params["embed"] = put(w)
        elif name == "model.norm.weight":
            params["final_norm"] = put(w)
        elif name == "lm_head.weight":
            params["lm_head"] = put(w.T)
        elif name.startswith("model.layers."):
            parts = name.split(".")
            li = int(parts[2])
            rest = ".".join(parts[3:])
            lp = layers[li]
            # HF stores Linear as [out, in]; we use [in, out] -> transpose
            mapping = {
                "input_layernorm.weight": ("attn_norm", False),
                "post_attention_layernorm.weight": ("mlp_norm", False),
                "self_attn.q_proj.weight": ("wq", True),
                "self_attn.k_proj.weight": ("wk", True),
                "self_attn.v_proj.weight": ("wv", True),
                "self_attn.o_proj.weight": ("wo", True),
                "self_attn.q_proj.bias": ("bq", False),
                "self_attn.k_proj.bias": ("bk", False),
                "self_attn.v_proj.bias": ("bv", False),
                "self_attn.q_norm.weight": ("q_norm", False),
                "self_attn.k_norm.weight": ("k_norm", False),
                "mlp.gate_proj.weight": ("w_gate", True),
                "mlp.up_proj.weight": ("w_up", True),
                "mlp.down_proj.weight": ("w_down", True),
            }
            if rest in mapping:
                ours, transpose = mapping[rest]
                lp[ours] = put(w.T if transpose else w)
            else:
                log.debug("ignoring unmapped tensor %s", name)
    if cfg.tie_embeddings and "lm_head" not in params:
        pass  # lm_logits uses embed.T
    missing = [i for i, lp in enumerate(layers) if "wq" not in lp]
    if missing:
        raise ValueError(f"checkpoint at {path} missing layers {missing[:4]}...")
    log.info("loaded %d layers from %s", cfg.num_layers, path)
    return params


def _deinterleave_rope_rows(w: np.ndarray, nope: int, rope: int, heads: int) -> np.ndarray:
    """DeepSeek checkpoints store rope projections in interleaved pair
    layout (HF applies apply_rotary_pos_emb_interleave when
    config.rope_interleave); our apply_rope is rotate-half. Permute each
    head's rope OUTPUT rows [0,1,2,...] -> [evens..., odds...] so the
    rotate-half pairing reproduces the interleaved semantics exactly.

    ``w`` is HF [out, in] with out = heads * (nope + rope)."""
    out, inner = w.shape
    w = w.reshape(heads, nope + rope, inner)
    rot = w[:, nope:, :]
    perm = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    w = np.concatenate([w[:, :nope, :], rot[:, perm, :]], axis=1)
    return w.reshape(out, inner)


def _load_params_cohere2_moe(path: str, cfg) -> Dict[str, Any]:
    """Map a cohere2_moe checkpoint onto the models/cohere2_moe.py pytree.
    The checkpoint rotates pairs ``(2i, 2i + 1)`` (``rope_gptj``); our
    apply_rope is rotate-half, so ``q_proj`` / ``k_proj`` rows of the layers
    that rotate are de-interleaved a head (``_deinterleave_rope_rows`` over
    the whole head). The attention, norm and embedding names are the cohere2
    lineage's; the expert names (``mlp.gate``, ``mlp.experts.E.*``,
    ``mlp.shared_experts.J.*``) are ASSUMED from the MoE lineages the
    repository loads and have not been held to a real checkpoint (ROADMAP
    R11). The shared experts go side by side into one SwiGLU."""
    dt, d = cfg.dtype, cfg.head_dim
    layers: list = [dict() for _ in range(cfg.num_layers)]
    params: Dict[str, Any] = {"layers": layers}
    experts: Dict[tuple, np.ndarray] = {}

    def put(arr: np.ndarray) -> jnp.ndarray:
        return jnp.asarray(arr, dt)

    for name, w in _open_safetensors(path):
        if name == "model.embed_tokens.weight":
            params["embed"] = put(w)
        elif name == "model.norm.weight":
            params["final_norm"] = put(w)
        elif name == "lm_head.weight":
            params["lm_head"] = put(w.T)
        elif name.startswith("model.layers."):
            parts = name.split(".")
            li, rest = int(parts[2]), ".".join(parts[3:])
            if li >= cfg.num_layers:
                continue
            lp, rotates = layers[li], cfg.window_for_layer(li) is not None
            if rest == "input_layernorm.weight":
                lp["norm"] = put(w)
            elif rest in ("self_attn.q_proj.weight", "self_attn.k_proj.weight"):
                heads = cfg.num_heads if "q_proj" in rest else cfg.num_kv_heads
                w = _deinterleave_rope_rows(w, 0, d, heads) if rotates else w
                lp["wq" if "q_proj" in rest else "wk"] = put(w.T)
            elif rest == "self_attn.v_proj.weight":
                lp["wv"] = put(w.T)
            elif rest == "self_attn.o_proj.weight":
                lp["wo"] = put(w.T)
            elif rest == "mlp.gate.weight":
                lp["w_router"] = put(w.T)
            elif rest.startswith(("mlp.experts.", "mlp.shared_experts.")):
                kind, e, proj = parts[4], int(parts[5]), parts[6]
                experts[(li, kind, e, proj)] = w
            else:
                log.debug("ignoring unmapped tensor %s", name)
        else:
            log.debug("ignoring unmapped tensor %s", name)
    for li, lp in enumerate(layers):
        for proj, leaf in (("gate_proj", "gate"), ("up_proj", "up"), ("down_proj", "down")):
            routed = [experts[(li, "experts", e, proj)].T for e in range(cfg.num_experts)]
            shared = [experts[(li, "shared_experts", j, proj)].T
                      for j in range(cfg.num_shared_experts)]
            lp[f"w_e{leaf}"] = put(np.stack(routed))
            lp[f"w_shared_{leaf}"] = put(np.concatenate(shared, axis=0 if leaf == "down" else 1))
    return params


def _load_params_gemma(path: str, cfg) -> Dict[str, Any]:
    """Map HF Gemma 2/3 tensors onto the models/gemma.py pytree (sandwich
    norms get their own names; multimodal gemma3 checkpoints prefix the
    text stack with language_model., stripped here — the vision tower is
    not loaded)."""
    layers: list = [dict() for _ in range(cfg.num_layers)]
    params: Dict[str, Any] = {"layers": layers}
    dt = cfg.dtype

    def put(arr: np.ndarray) -> jnp.ndarray:
        return jnp.asarray(arr, dt)

    mapping = {
        "input_layernorm.weight": ("attn_norm", False),
        "post_attention_layernorm.weight": ("post_attn_norm", False),
        "pre_feedforward_layernorm.weight": ("pre_mlp_norm", False),
        "post_feedforward_layernorm.weight": ("post_mlp_norm", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "self_attn.q_norm.weight": ("q_norm", False),
        "self_attn.k_norm.weight": ("k_norm", False),
        "mlp.gate_proj.weight": ("w_gate", True),
        "mlp.up_proj.weight": ("w_up", True),
        "mlp.down_proj.weight": ("w_down", True),
    }
    for name, w in _open_safetensors(path):
        if name.startswith("language_model."):
            name = name[len("language_model."):]
        if name == "model.embed_tokens.weight":
            params["embed"] = put(w)
        elif name == "model.norm.weight":
            params["final_norm"] = put(w)
        elif name == "lm_head.weight":
            # untied finetunes: released gemma checkpoints tie, but a
            # finetune with tie_word_embeddings=false must not silently
            # fall back to embed.T (gemma.lm_logits prefers lm_head)
            params["lm_head"] = put(w.T)
        elif name.startswith("model.layers."):
            parts = name.split(".")
            li = int(parts[2])
            rest = ".".join(parts[3:])
            if rest in mapping:
                ours, transpose = mapping[rest]
                layers[li][ours] = put(w.T if transpose else w)
            else:
                log.debug("ignoring unmapped tensor %s", name)
        else:
            log.debug("ignoring unmapped tensor %s", name)
    if not cfg.tie_embeddings and "lm_head" not in params:
        raise ValueError(
            f"checkpoint at {path} has tie_word_embeddings=false but no "
            "lm_head.weight"
        )
    missing = [i for i, lp in enumerate(layers) if "wq" not in lp]
    if missing:
        raise ValueError(f"checkpoint at {path} missing layers {missing[:4]}...")
    log.info("loaded %d gemma layers from %s", cfg.num_layers, path)
    return params


def _load_params_mla(path: str, cfg) -> Dict[str, Any]:
    """Map HF DeepSeek V2/V3 tensors onto the models/mla.py pytree.

    kv_b_proj [heads*(nope+v), rank] splits into the absorbed per-head
    up-projections: rows [:nope] -> w_uk [h, nope, rank] (index-identical),
    rows [nope:] -> w_uv [h, rank, v] (transposed). Rope output rows of
    q(_b)_proj and kv_a_proj_with_mqa are de-interleaved (see above)."""
    layers: list = [dict() for _ in range(cfg.num_layers)]
    params: Dict[str, Any] = {"layers": layers}
    experts: Dict[int, Dict[str, Dict[int, np.ndarray]]] = {}
    dt = cfg.dtype
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    nh, rank = cfg.num_heads, cfg.kv_lora_rank
    interleave = cfg.rope_interleave

    def deint(w: np.ndarray, pre: int, heads: int) -> np.ndarray:
        return _deinterleave_rope_rows(w, pre, rope, heads) if interleave else w

    def put(arr: np.ndarray) -> jnp.ndarray:
        return jnp.asarray(arr, dt)

    for name, w in _open_safetensors(path):
        if name == "model.embed_tokens.weight":
            params["embed"] = put(w)
            continue
        if name == "model.norm.weight":
            params["final_norm"] = put(w)
            continue
        if name == "lm_head.weight":
            params["lm_head"] = put(w.T)
            continue
        if not name.startswith("model.layers."):
            log.debug("ignoring unmapped tensor %s", name)
            continue
        parts = name.split(".")
        li = int(parts[2])
        rest = ".".join(parts[3:])
        lp = layers[li]
        simple = {
            "input_layernorm.weight": ("attn_norm", False),
            "post_attention_layernorm.weight": ("mlp_norm", False),
            "self_attn.q_a_layernorm.weight": ("q_norm", False),
            "self_attn.kv_a_layernorm.weight": ("kv_norm", False),
            "self_attn.q_a_proj.weight": ("w_dq", True),
            "self_attn.o_proj.weight": ("wo", True),
            "mlp.gate_proj.weight": ("w_gate", True),
            "mlp.up_proj.weight": ("w_up", True),
            "mlp.down_proj.weight": ("w_down", True),
            "mlp.shared_experts.gate_proj.weight": ("w_shared_gate", True),
            "mlp.shared_experts.up_proj.weight": ("w_shared_up", True),
            "mlp.shared_experts.down_proj.weight": ("w_shared_down", True),
            "mlp.gate.weight": ("w_router", True),
        }
        if rest in simple:
            ours, transpose = simple[rest]
            lp[ours] = put(w.T if transpose else w)
        elif rest == "mlp.gate.e_score_correction_bias":
            lp["router_bias"] = jnp.asarray(w, jnp.float32)
        elif rest in ("self_attn.q_proj.weight", "self_attn.q_b_proj.weight"):
            ours = "wq" if rest == "self_attn.q_proj.weight" else "w_uq"
            lp[ours] = put(deint(w, nope, nh).T)
        elif rest == "self_attn.kv_a_proj_with_mqa.weight":
            # out rows = [latent (rank) | k_pe (rope)] — one "head" of rope
            lp["w_dkv"] = put(deint(w, rank, 1).T)
        elif rest == "self_attn.kv_b_proj.weight":
            kvb = w.reshape(nh, nope + vd, rank)
            lp["w_uk"] = put(kvb[:, :nope, :])
            lp["w_uv"] = put(np.swapaxes(kvb[:, nope:, :], 1, 2))
        elif parts[3] == "mlp" and parts[4] == "experts":
            ei, pname = int(parts[5]), parts[6]
            experts.setdefault(li, {}).setdefault(pname, {})[ei] = w
        else:
            log.debug("ignoring unmapped tensor %s", name)

    # stack per-expert FFN weights into [E, in, out]
    for li, groups in experts.items():
        for pname, ours in (
            ("gate_proj", "w_egate"), ("up_proj", "w_eup"),
            ("down_proj", "w_edown"),
        ):
            tensors = groups.get(pname, {})
            if len(tensors) != cfg.num_experts:
                raise ValueError(
                    f"layer {li}: {len(tensors)}/{cfg.num_experts} "
                    f"{pname} expert shards in checkpoint"
                )
            layers[li][ours] = put(
                np.stack([tensors[e].T for e in range(cfg.num_experts)])
            )
    missing = [
        i for i, lp in enumerate(layers)
        if ("wq" not in lp and "w_uq" not in lp) or "w_dkv" not in lp
    ]
    if missing:
        raise ValueError(f"checkpoint at {path} missing MLA layers {missing[:4]}...")
    log.info("loaded %d MLA layers from %s", cfg.num_layers, path)
    return params


# OCP MXFP4 e2m1 value table (public microscaling spec; also
# transformers.integrations.mxfp4.FP4_VALUES)
FP4_VALUES = (
    +0.0, +0.5, +1.0, +1.5, +2.0, +3.0, +4.0, +6.0,
    -0.0, -0.5, -1.0, -1.5, -2.0, -3.0, -4.0, -6.0,
)


def dequant_mxfp4(blocks: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Dequantize MXFP4 expert weights (the released gpt-oss checkpoints):
    ``blocks`` uint8 [E, out, G, B] packs two FP4 e2m1 nibbles per byte
    (low nibble first), ``scales`` uint8 [E, out, G] are e8m0 block
    exponents (bias 127). Returns float32 [E, in, out] — the input-major
    layout the bf16 checkpoints use."""
    lut = np.asarray(FP4_VALUES, np.float32)
    out = np.empty((*blocks.shape[:-1], blocks.shape[-1] * 2), np.float32)
    out[..., 0::2] = lut[blocks & 0x0F]
    out[..., 1::2] = lut[blocks >> 4]
    out *= np.exp2(scales.astype(np.int32) - 127)[..., None]
    out = out.reshape(*blocks.shape[:-2], -1)   # [E, out, in]
    return out.swapaxes(1, 2)                   # [E, in, out]


def _load_params_gptoss(path: str, cfg) -> Dict[str, Any]:
    """Map HF gpt-oss tensors onto the models/gptoss.py pytree. The fused
    per-expert projections (mlp.experts.gate_up_proj [E, H, 2I],
    down_proj [E, I, H]) are stored input-major in HF (used as x @ W), so
    they load without transposition; gate/up lanes stay interleaved (the
    expert kernel slices ::2 / 1::2 like the HF forward)."""
    layers: list = [dict() for _ in range(cfg.num_layers)]
    params: Dict[str, Any] = {"layers": layers}
    dt = cfg.dtype

    def put(arr: np.ndarray) -> jnp.ndarray:
        return jnp.asarray(arr, dt)

    mapping = {
        "input_layernorm.weight": ("attn_norm", False),
        "post_attention_layernorm.weight": ("mlp_norm", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "self_attn.q_proj.bias": ("bq", False),
        "self_attn.k_proj.bias": ("bk", False),
        "self_attn.v_proj.bias": ("bv", False),
        "self_attn.o_proj.bias": ("bo", False),
        "mlp.router.weight": ("w_router", True),
        "mlp.router.bias": ("b_router", False),
        "mlp.experts.gate_up_proj": ("w_gateup", False),
        "mlp.experts.gate_up_proj_bias": ("b_gateup", False),
        "mlp.experts.down_proj": ("w_edown", False),
        "mlp.experts.down_proj_bias": ("b_edown", False),
    }
    mx: Dict[int, Dict[str, np.ndarray]] = {}
    for name, w in _open_safetensors(path):
        if name == "model.embed_tokens.weight":
            params["embed"] = put(w)
        elif name == "model.norm.weight":
            params["final_norm"] = put(w)
        elif name == "lm_head.weight":
            params["lm_head"] = put(w.T)
        elif name.startswith("model.layers."):
            parts = name.split(".")
            li = int(parts[2])
            rest = ".".join(parts[3:])
            if rest == "self_attn.sinks":
                layers[li]["sinks"] = jnp.asarray(w, jnp.float32)
            elif rest in mapping:
                ours, transpose = mapping[rest]
                layers[li][ours] = put(w.T if transpose else w)
            elif rest.startswith("mlp.experts.") and (
                rest.endswith("_blocks") or rest.endswith("_scales")
            ):
                # MXFP4-quantized release: dequantize the moment both halves
                # of a tensor arrive and DROP the raw halves — peak host
                # memory stays one tensor, not the whole quantized model
                part = rest.removeprefix("mlp.experts.")
                lay = mx.setdefault(li, {})
                lay[part] = w
                base = part.rsplit("_", 1)[0]
                b = lay.get(f"{base}_blocks")
                sc = lay.get(f"{base}_scales")
                if b is not None and sc is not None:
                    ours = {"gate_up_proj": "w_gateup", "down_proj": "w_edown"}[base]
                    layers[li][ours] = put(dequant_mxfp4(b, sc))
                    del lay[f"{base}_blocks"], lay[f"{base}_scales"]
            else:
                log.debug("ignoring unmapped tensor %s", name)
        else:
            log.debug("ignoring unmapped tensor %s", name)
    for li, parts_d in mx.items():
        if parts_d:  # an unpaired half means a truncated/corrupt checkpoint
            raise ValueError(
                f"layer {li}: MXFP4 tensors missing their other half: "
                f"{sorted(parts_d)}"
            )
    missing = [
        i for i, lp in enumerate(layers)
        if "wq" not in lp or "sinks" not in lp or "w_gateup" not in lp
    ]
    if missing:
        raise ValueError(
            f"checkpoint at {path} missing gpt-oss layers {missing[:4]}..."
        )
    log.info("loaded %d gpt-oss layers from %s", cfg.num_layers, path)
    return params
