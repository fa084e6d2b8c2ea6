"""Ouro (``model_type`` ``ouro``: ByteDance Ouro-1.4B / 2.6B, "Scaling Latent
Reasoning via Looped Language Models", arXiv 2510.25741): ONE stack of dense
layers run ``passes`` (``total_ut_steps``) times a token.

    h = E[tok]
    for pass t = 0 .. T-1:
        for layer l = 0 .. L-1:
            h += RMSNorm(Attn_l(RMSNorm(h; attn_norm)); attn_out_norm)
            h += RMSNorm(MLP_l(RMSNorm(h; mlp_norm)); mlp_out_norm)
        h = RMSNorm(h; final_norm)          # every pass, and fed forward
    logits = h W_head                       # the last pass's

The layers' weights and ``final_norm`` are the same in every pass; the keys
and values are not: pass ``t`` of layer ``l`` attends over what pass ``t`` of
layer ``l`` wrote, so the cache has a SLOT a (pass, layer), ``passes x
num_layers`` of them over ``num_layers`` layers' weights. The family tells
the registry so with ``page_passes`` (models/registry.py): a page layer's two
arrays hold ``passes`` pools one behind another, a block id ``b`` of pass
``t`` is the page ``t x num_blocks + b``, and ``forward`` hands ``attend`` the
pass (``page_pass``), which the engine's seams add to the tables they hold.
One block table, one allocator and one block hash serve every slot.

The passes are ONE traced loop where the engine hands ``forward`` its
``loop`` (the caches ride its carry, so a step program holds ``num_layers``
layer bodies, not ``passes x num_layers``); without one (a stateless attend:
tests, the embedding path) they are unrolled.

Shared with models/llama.py, not copied: the layer's parameters and their
init (``init_layer_params``), ``rms_norm``, ``rope_cos_sin`` / ``apply_rope``,
``lm_logits``, the tensor-parallel specs. NOT shared: ``layer_forward``, for
the two norms on the sub-layers' OUTPUTS (``llama.layer_forward`` adds a
sub-layer's output to the residual inside itself); with ``out_norms=False``
and one pass this module's stack IS the dense one (tests/test_ouro.py).

The exit gate (``early_exit_gate``: Linear(hidden -> 1) with bias, read on
each pass's normed state) is held in the parameters and computed by no step
program: at the published ``early_exit_threshold`` of 1 every token leaves at
the last pass, exactly. A threshold under 1 would make rows of one step run
different numbers of passes and is refused at construction (ROADMAP R15).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention as att
from . import checkpoint, llama
from .llama import AttendFn, LlamaConfig, Params, apply_rope, rms_norm, rope_cos_sin


@dataclasses.dataclass(frozen=True)
class OuroConfig(LlamaConfig):
    """A ``LlamaConfig`` (the layer's sizes are the dense family's) with the
    number of passes; a subclass, so everything that reads a dense model's
    sizes reads these, and ``registry.family`` finds this module first."""

    passes: int = 4                     # total_ut_steps
    early_exit_threshold: float = 1.0
    # the norms on the sub-layers' outputs; off only to tie the stack to
    # llama.layer_forward in a test
    out_norms: bool = True
    tie_embeddings: bool = False

    def __post_init__(self):
        if self.passes < 1:
            raise ValueError(f"OuroConfig: passes must be at least 1, not {self.passes}")
        if self.early_exit_threshold < 1.0:
            raise ValueError(
                f"OuroConfig: early_exit_threshold {self.early_exit_threshold} "
                "< 1 lets a token leave before the last pass, so rows of one "
                "step would run different numbers of passes; the step "
                "programs run every row through all of them (ROADMAP R15)"
            )

    @classmethod
    def tiny(cls, **kw) -> "OuroConfig":
        """Test scale: passes != layers != heads, so a swapped index shows."""
        base = dict(num_layers=3, passes=3, num_heads=4, num_kv_heads=4,
                    head_dim=64, hidden_size=128, intermediate_size=352)
        return cls(**{**base, **kw})

    @classmethod
    def ouro_2_6b(cls, vocab_size: int = 49152) -> "OuroConfig":
        return cls(
            vocab_size=vocab_size, hidden_size=2048, num_layers=48,
            num_heads=16, num_kv_heads=16, head_dim=128,
            intermediate_size=5632, rope_theta=1000000.0, rms_norm_eps=1e-6,
            max_position=65536, passes=4,
        )


def init_params(rng: jax.Array, cfg: OuroConfig) -> Params:
    """The dense family's draw, plus the two output norms a layer (ones) and
    the exit gate (zeros: it is part of no logit)."""
    params = llama.init_params(rng, cfg)
    if cfg.out_norms:
        for p in params["layers"]:
            p["attn_out_norm"] = jnp.ones((cfg.hidden_size,), cfg.dtype)
            p["mlp_out_norm"] = jnp.ones((cfg.hidden_size,), cfg.dtype)
    params["exit_gate_w"] = jnp.zeros((cfg.hidden_size, 1), cfg.dtype)
    params["exit_gate_b"] = jnp.zeros((1,), cfg.dtype)
    return params


def layer_forward(p: Params, cfg: OuroConfig, x: jax.Array, cos: jax.Array,
                  sin: jax.Array, attend: AttendFn, layer_idx: int) -> jax.Array:
    eps = cfg.rms_norm_eps
    lead = x.shape[:-1]
    h = rms_norm(x, p["attn_norm"], eps)
    q, k, v = jax.lax.optimization_barrier((h @ p["wq"], h @ p["wk"], h @ p["wv"]))
    q = q.reshape(*lead, cfg.num_heads, cfg.head_dim)
    k = k.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    o = attend(apply_rope(q, cos, sin), apply_rope(k, cos, sin), v, layer_idx)
    a = o.reshape(*lead, cfg.q_size) @ p["wo"]
    x = x + (rms_norm(a, p["attn_out_norm"], eps) if cfg.out_norms else a)
    h = rms_norm(x, p["mlp_norm"], eps)
    gate = jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    m = (gate * (h @ p["w_up"])) @ p["w_down"]
    return x + (rms_norm(m, p["mlp_out_norm"], eps) if cfg.out_norms else m)


def forward(params: Params, cfg: OuroConfig, token_ids: jax.Array,
            positions: jax.Array, attend: Callable, stats=None,
            loop: Optional[Callable] = None) -> jax.Array:
    """The stack, ``cfg.passes`` times -> the last pass's normed state
    [..., S, hidden]. ``attend(q, k, v, layer, page_pass=t)`` reads and
    writes slot ``(t, layer)``. ``loop(one_pass, x, passes)`` (the engine's:
    the page arrays ride its carry) runs the passes as one traced loop;
    without it they are unrolled. ``stats`` (models/moe.RoutingStats, used
    for its readback alone): what the step's tokens went through, under
    ``read_counters``' names."""
    x = params["embed"][token_ids]
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    cos, sin = cos[..., None, :], sin[..., None, :]

    def one_pass(x, t):
        # the token's position is the same at every pass: one rotary table
        with jax.named_scope("ouro_pass"):
            at = partial(attend, page_pass=t)
            for i, layer in enumerate(params["layers"]):
                x = layer_forward(layer, cfg, x, cos, sin, at, i)
        with jax.named_scope("ouro_pass_norm"):
            return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)

    if loop is None:
        for t in range(cfg.passes):
            x = one_pass(x, t)
    else:
        x = loop(one_pass, x, cfg.passes)
    if stats is not None:
        real = stats.valid.reshape(-1)
        rows = stats.decode_rows.reshape(-1)
        tokens = real.sum()
        stats.add_reads(
            ouro_stack_tokens=tokens,
            # every token goes through every pass today (threshold 1)
            ouro_pass_tokens=tokens * cfg.passes,
            # a decode row at position p attends over p + 1 keys in each slot
            ouro_slot_keys_read=(
                jnp.where(rows, positions.reshape(-1) + 1, 0).sum()
                * cfg.passes * cfg.num_layers
            ),
        )
    return x


lm_logits = llama.lm_logits


def stateless_attend(q, k, v, layer_idx, page_pass=None):
    """``attend`` over ONE whole sequence from nothing: every slot of a
    sequence taken whole is the causal attention over that pass's own keys."""
    return att.causal_attention(q, k, v)


# what models/registry.py asks of the family
CONFIG = OuroConfig
PRESETS = {"tiny-ouro": OuroConfig.tiny, "ouro-2.6b": OuroConfig.ouro_2_6b}
layer_specs = llama.layer_specs


def page_passes(cfg: OuroConfig) -> int:
    """A page layer's arrays hold this many pools, one a pass: the cache's
    slots are ``passes x num_layers`` (registry.page_slots)."""
    return cfg.passes


def read_counters(cfg: OuroConfig) -> Tuple[str, ...]:
    """The ``StepStats`` fields ``forward`` adds to its ``stats``, in the
    order they ride a step's readback (decode and mixed steps; a prefill
    alone has no readback of counters)."""
    return ("ouro_stack_tokens", "ouro_pass_tokens", "ouro_slot_keys_read")


HF_MODEL_TYPES = ("ouro",)


def config_from_hf(hf: dict) -> OuroConfig:
    return OuroConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_size=hf["intermediate_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        max_position=hf.get("max_position_embeddings", 65536),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        passes=int(hf.get("total_ut_steps", 4)),
        early_exit_threshold=float(hf.get("early_exit_threshold", 1.0)),
    )


def load_params(path: str, cfg: OuroConfig) -> Params:
    """Map the published tensor names onto the pytree, as I know
    ``modeling_ouro.py`` (no checkpoint is here to hold this to account:
    tests/test_ouro.py round-trips a checkpoint written under these names)."""
    params, layers, put = checkpoint.begin(cfg)
    mapping = {
        "input_layernorm.weight": ("attn_norm", False),
        "input_layernorm_2.weight": ("attn_out_norm", False),
        "post_attention_layernorm.weight": ("mlp_norm", False),
        "post_attention_layernorm_2.weight": ("mlp_out_norm", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "mlp.gate_proj.weight": ("w_gate", True),
        "mlp.up_proj.weight": ("w_up", True),
        "mlp.down_proj.weight": ("w_down", True),
    }
    gate = {"model.early_exit_gate.weight": ("exit_gate_w", True),
            "model.early_exit_gate.bias": ("exit_gate_b", False)}
    for li, rest, w in checkpoint.layer_tensors(path, params, put, top=gate):
        if rest in mapping:
            ours, transpose = mapping[rest]
            layers[li][ours] = put(w.T if transpose else w)
        else:
            checkpoint.log.debug("ignoring unmapped tensor %s of layer %d", rest, li)
    missing = [i for i, lp in enumerate(layers) if len(lp) != len(mapping)]
    if missing or "exit_gate_w" not in params:
        raise ValueError(
            f"checkpoint at {path}: layers {missing[:4]} lack a tensor of "
            f"{sorted(mapping)}, or the exit gate is missing"
        )
    checkpoint.log.info("loaded %d ouro layers from %s", cfg.num_layers, path)
    return params
