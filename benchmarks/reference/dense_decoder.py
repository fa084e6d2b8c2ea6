"""Plain reference: the decoder-only forward pass InternLM2 and Mistral share.

Written from the published architecture (pre-norm residual blocks of
grouped-query attention with rotary positions and a SwiGLU feed-forward,
RMSNorm, untied output head), in plain ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` — on a TPU a float32 matrix
multiplication otherwise runs in lower precision. No cache, no kernels, no
batching; it does not import ``dynamo_tpu``.

It reads the served bf16 parameters and upcasts them inside one jitted
layer, called in a Python loop: one compile, and the transient memory of
one layer's float32 matrices, not the model's.

``cfg`` carries the public ``config.json`` keys. Parameters: ``embed``
[vocab, hidden]; ``layers[i]`` with ``attn_norm``, ``wq``, ``wk``, ``wv``,
``wo``, ``mlp_norm``, ``w_gate``, ``w_up``, ``w_down``, matrices stored
[in, out]; ``final_norm``; ``lm_head`` [hidden, vocab] unless
``tie_word_embeddings``.

Departures from the publications, each with its reason: rotary positions in
the rotate-half layout of the Hugging Face code (the layout the checkpoints
are stored for); InternLM2's fused ``wqkv`` read as three projections (the
same mathematics).

TOLERANCE. The engine computes in bf16 (matrices, activations, the cache)
with float32 softmax and norms; this reference is float32 throughout, so
they differ by rounding, more on a wider and deeper model. The bounds are
therefore the configuration's (``reference_tolerance`` in its file, with the
numbers they were set from). Over the compared tokens (4 prompts x 32 greedy
tokens): every token's |engine logprob - reference logprob| <= ``worst_nat``;
every emitted token is the reference's argmax or scores within ``worst_nat``
of it (random weights give nearly flat logits, so ties within rounding are
real); and the MEAN of the logprob differences <= ``mean_nat``. The worst
difference catches a wrong computation (a skipped layer moves it to more than
1 nat); the mean is the steadier statistic and is what tells a cache held at
8 bits from bf16 rounding (it about doubles). PERF.md section 6 has the
measurements (chip, PR 23).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np



def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rotate(x, cos, sin):
    """x [T, heads, d]; cos/sin [T, 1, d/2]: rotate-half."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _fake_quant_int8(x):
    """What a cache held at 8 bits would return: per (16-token page, head)
    symmetric int8. Used only to show that the tolerance tells it apart."""
    T, h, d = x.shape
    pad = (-T) % 16
    xp = jnp.pad(x, ((0, pad), (0, 0), (0, 0))).reshape(-1, 16, h, d)
    amax = jnp.max(jnp.abs(xp), axis=(1, 3), keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(xp / scale).clip(-127, 127) * scale
    return q.reshape(-1, h, d)[:T]


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps", "kv_bits"))
def _layer(p, x, cos, sin, *, n_heads, n_kv, head_dim, eps, kv_bits=None):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
        T = x.shape[0]
        h = _rms_norm(x, p["attn_norm"], eps)
        q = (h @ p["wq"]).reshape(T, n_heads, head_dim)
        k = (h @ p["wk"]).reshape(T, n_kv, head_dim)
        v = (h @ p["wv"]).reshape(T, n_kv, head_dim)
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        if kv_bits == 8:
            k, v = _fake_quant_int8(k), _fake_quant_int8(v)
        group = n_heads // n_kv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
        causal = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(T, n_heads * head_dim) @ p["wo"]
        h = _rms_norm(x, p["mlp_norm"], eps)
        x = x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
        return x


@partial(jax.jit, static_argnames=("eps", "tied"))
def _head(final_norm, head, x, *, eps, tied):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
        w = head.astype(jnp.float32)
        logits = x @ (w.T if tied else w)
        return jax.nn.log_softmax(logits, axis=-1)


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None,
             device=None, skip_layer: Optional[int] = None,
             kv_bits: Optional[int] = None) -> np.ndarray:
    """Log-probabilities [len(rows), vocab] of the next token after each
    position in ``rows``, from one full forward over ``token_ids``.

    ``pad_to`` pads the sequence (causal attention: positions after the last
    real one cannot touch earlier ones) so that every prompt compiles the
    same shapes. ``device`` is where the reference runs; sharded parameters
    are gathered to it one layer at a time. ``skip_layer`` / ``kv_bits`` exist
    to show that the tolerance fails a wrong computation, never to pass one."""
    head_dim = int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    T = len(ids)
    if pad_to is not None and pad_to > T:
        ids = np.concatenate([ids, np.zeros(pad_to - T, np.int32)])
    pos = np.arange(len(ids), dtype=np.float32)
    inv = 1.0 / (float(cfg["rope_theta"]) ** (np.arange(0, head_dim // 2, dtype=np.float32) / (head_dim // 2)))
    ang = pos[:, None] * inv[None, :]
    cos, sin = put(np.cos(ang)[:, None, :]), put(np.sin(ang)[:, None, :])
    x = put(params["embed"])[put(ids)].astype(jnp.float32)
    for i, lp in enumerate(params["layers"]):
        if i == skip_layer:
            continue
        x = _layer(
            put(lp), x, cos, sin,
            n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
            head_dim=head_dim, eps=float(cfg["rms_norm_eps"]), kv_bits=kv_bits,
        )
    tied = bool(cfg.get("tie_word_embeddings"))
    head = params["embed"] if tied else params["lm_head"]
    out = _head(put(params["final_norm"]), put(head), x[np.asarray(rows)],
                eps=float(cfg["rms_norm_eps"]), tied=tied)
    return np.asarray(out)


def compare(cfg: Dict[str, Any], params: Dict[str, Any],
            samples: List[Dict[str, Any]], pad_to: int, device=None,
            **wrong) -> Dict[str, Any]:
    """Hold the engine's greedy continuations to the reference.

    ``samples``: ``{"prompt": [...], "tokens": [...], "logprobs": [...]}`` as
    the engine emitted them. Returns the worst differences and ``ok``."""
    worst_lp = 0.0
    worst_gap = 0.0
    sum_lp = 0.0
    n = 0
    for s in samples:
        P, emitted = len(s["prompt"]), list(s["tokens"])
        if not emitted or len(s["logprobs"]) != len(emitted):
            return {"ok": False, "reason": "a sample has no tokens or no logprobs",
                    "tokens_compared": n}
        seq = list(s["prompt"]) + emitted
        rows = [P - 1 + j for j in range(len(emitted))]
        ref = logprobs(cfg, params, seq, rows, pad_to=pad_to, device=device, **wrong)
        for j, tok in enumerate(emitted):
            diff = abs(float(ref[j, tok]) - float(s["logprobs"][j]))
            worst_lp = max(worst_lp, diff)
            sum_lp += diff
            worst_gap = max(worst_gap, float(ref[j].max()) - float(ref[j, tok]))
            n += 1
    tol = cfg["reference_tolerance"]
    mean_lp = sum_lp / max(n, 1)
    ok = worst_lp <= tol["worst_nat"] and worst_gap <= tol["worst_nat"] and mean_lp <= tol["mean_nat"]
    return {
        "ok": bool(ok), "tokens_compared": n,
        "worst_logprob_difference_nat": worst_lp,
        "worst_argmax_gap_nat": worst_gap,
        "mean_logprob_difference_nat": mean_lp,
        "worst_tolerance_nat": tol["worst_nat"], "mean_tolerance_nat": tol["mean_nat"],
    }
