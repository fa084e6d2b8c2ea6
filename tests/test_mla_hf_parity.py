"""Gold-standard MLA parity: our loader + forward vs HuggingFace DeepseekV3.

Builds a tiny random DeepseekV3 model with transformers (torch CPU),
saves it as a real HF checkpoint, loads it through engine/weights.py into
the models/mla.py pytree, and compares logits token-for-token. This pins
every convention at once: tensor-name mapping, [out,in]->[in,out]
transposes, kv_b_proj head splitting, the interleaved-rope row permutation,
weight-absorbed attention, and the sigmoid+bias+group-limited router; and,
with a DeepSeek-style ``rope_scaling`` dict, YaRN's blended frequencies, the
factor on cos and sin and the ``mscale`` factor on the softmax scale.
"""

import dataclasses
import json

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp  # noqa: E402

from dynamo_tpu.engine import weights as W  # noqa: E402
from dynamo_tpu.models import mla  # noqa: E402
from dynamo_tpu.ops import attention as att  # noqa: E402


def _make_hf_checkpoint(tmp_path, q_lora_rank, **extra):
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

    hf_cfg = DeepseekV3Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        n_group=2, topk_group=1, first_k_dense_replace=1,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        q_lora_rank=q_lora_rank, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        max_position_embeddings=256, tie_word_embeddings=False,
        attention_bias=False, rope_theta=10000.0,
        attn_implementation="eager", **extra,
    )
    torch.manual_seed(0)
    model = DeepseekV3ForCausalLM(hf_cfg).eval().to(torch.float32)
    # give the aux-free balancing bias a nonzero value so the test actually
    # exercises biased selection vs unbiased combine weights
    with torch.no_grad():
        for layer in model.model.layers[hf_cfg.first_k_dense_replace:]:
            layer.mlp.gate.e_score_correction_bias.uniform_(-0.2, 0.2)
    ckpt = tmp_path / "ckpt"
    model.save_pretrained(str(ckpt), safe_serialization=True)
    return model, str(ckpt)


@pytest.mark.parametrize("q_lora_rank", [None, 24])
def test_logits_match_hf_deepseek_v3(tmp_path, q_lora_rank):
    model, ckpt = _make_hf_checkpoint(tmp_path, q_lora_rank)

    with open(f"{ckpt}/config.json") as f:
        assert json.load(f)["model_type"] == "deepseek_v3"
    cfg = W.config_from_hf(ckpt)
    assert isinstance(cfg, mla.MlaConfig)
    assert cfg.q_lora_rank == (q_lora_rank or 0)
    assert cfg.n_group == 2 and cfg.moe_scoring == "sigmoid"
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = W.load_params(ckpt, cfg)

    token_ids = np.array([5, 99, 23, 77, 1, 42, 17, 63], np.int64)
    with torch.no_grad():
        hf_logits = model(torch.tensor(token_ids)[None]).logits[0].numpy()

    toks = jnp.asarray(token_ids, jnp.int32)
    pos = jnp.arange(len(token_ids), dtype=jnp.int32)
    hidden = mla.forward(
        params, cfg, toks, pos,
        lambda q, k, v, i: att.causal_attention(q, k, v),
    )
    ours = np.asarray(mla.lm_logits(params, cfg, hidden))

    np.testing.assert_allclose(ours, hf_logits, rtol=2e-3, atol=2e-3)
    # and the distributions argmax-match everywhere (the serving-visible bar)
    assert (ours.argmax(-1) == hf_logits.argmax(-1)).all()


@pytest.mark.parametrize("mscale,mscale_all_dim", [(1.0, 1.0), (1.0, 0.5), (0.707, 0.0)])
def test_yarn_and_its_softmax_scale_match_hf_deepseek_v3(tmp_path, mscale, mscale_all_dim):
    """A ``rope_scaling`` dict of type yarn (factor 4 from 16 positions; the
    40 tokens run past the original maximum): both stated and equal (A.X-K1:
    tables unscaled, the scale times m^2), both stated and different (tables
    scaled by their ratio), and ``mscale_all_dim`` left at 0 (the recipe's
    own factor on the tables, none on the scale)."""
    rope_scaling = {
        "type": "yarn", "rope_type": "yarn", "factor": 4.0, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": mscale, "mscale_all_dim": mscale_all_dim,
    }
    # max_position_embeddings stays the helper's 256 (transformers only warns
    # that 256 / 16 is not the stated factor and uses the stated one)
    model, ckpt = _make_hf_checkpoint(tmp_path, 24, rope_scaling=rope_scaling)
    cfg = W.config_from_hf(ckpt)
    assert cfg.rope_scaling_factor == 4.0 and cfg.rope_original_max_position == 16
    assert (cfg.rope_mscale, cfg.rope_mscale_all_dim) == (mscale, mscale_all_dim)
    m = 0.1 * mscale_all_dim * np.log(4.0) + 1.0
    np.testing.assert_allclose(cfg.softmax_scale, 24 ** -0.5 * m * m, rtol=1e-12)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = W.load_params(ckpt, cfg)

    token_ids = np.random.default_rng(0).integers(0, 128, 40)
    with torch.no_grad():
        hf_logits = model(torch.tensor(token_ids)[None]).logits[0].numpy()
    hidden = mla.forward(
        params, cfg, jnp.asarray(token_ids, jnp.int32), jnp.arange(40, dtype=jnp.int32),
        lambda q, k, v, i: att.causal_attention(q, k, v),
    )
    ours = np.asarray(mla.lm_logits(params, cfg, hidden))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-3, atol=2e-3)
    # and plain positions would not have passed: YaRN is doing something here
    plain = dataclasses.replace(cfg, rope_scaling_factor=1.0)
    off = np.asarray(mla.lm_logits(params, plain, mla.forward(
        params, plain, jnp.asarray(token_ids, jnp.int32), jnp.arange(40, dtype=jnp.int32),
        lambda q, k, v, i: att.causal_attention(q, k, v),
    )))
    assert np.abs(off - hf_logits).max() > 10 * np.abs(ours - hf_logits).max()
