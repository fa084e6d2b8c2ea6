"""What the families' checkpoint loaders share (a module's ``load_params``;
engine/weights.py looks the loader up): the reader of a local HF checkpoint's
safetensors shards, the skeleton every loader fills, and DeepSeek's rope
rows."""

from __future__ import annotations

import logging
import os

import jax.numpy as jnp
import numpy as np

log = logging.getLogger("dynamo_tpu.engine.weights")


def open_safetensors(path: str):
    """Yields (name, np.ndarray) from all safetensors shards in ``path``."""
    from safetensors import safe_open  # available via transformers dep

    files = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    for fname in files:
        with safe_open(os.path.join(path, fname), framework="np") as f:
            for name in f.keys():
                yield name, f.get_tensor(name)


def begin(cfg):
    """A loader's empty pytree, its layers, and the cast to ``cfg.dtype``."""
    layers = [dict() for _ in range(cfg.num_layers)]
    return {"layers": layers}, layers, lambda arr: jnp.asarray(arr, cfg.dtype)


def layer_tensors(path: str, params, put, strip: str = "", top=None):
    """Yields ``(layer, name under model.layers.N, array)`` for a loader to
    place; the embedding, the final norm and the head are placed here (HF
    stores a Linear as [out, in]; ours are [in, out]), and a tensor that is
    none of these is passed over. ``strip``: a prefix some checkpoints put
    before every name. ``top``: further tensors outside the layers that a
    family names, ``name -> (ours, transpose)``."""
    top = top or {}
    for name, w in open_safetensors(path):
        name = name.removeprefix(strip)
        if name in top:
            ours, transpose = top[name]
            params[ours] = put(w.T if transpose else w)
        elif name == "model.embed_tokens.weight":
            params["embed"] = put(w)
        elif name == "model.norm.weight":
            params["final_norm"] = put(w)
        elif name == "lm_head.weight":
            params["lm_head"] = put(w.T)
        elif name.startswith("model.layers."):
            _, _, layer, rest = name.split(".", 3)
            yield int(layer), rest, w
        else:
            log.debug("ignoring unmapped tensor %s", name)


def deinterleave_rope_rows(w: np.ndarray, nope: int, rope: int, heads: int) -> np.ndarray:
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
