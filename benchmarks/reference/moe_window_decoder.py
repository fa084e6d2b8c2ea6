"""Plain reference: a decoder whose every layer is sparse experts, three
sliding-window attention layers to one full (Mellum2-12B-A2.5B-Instruct).

Written from the public ``config.json`` keys, in plain ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. No cache, no
kernels, no batching; it does not import ``dynamo_tpu``.

The layer, for every layer ``i``::

    x += Wo . Attn(rope_i(qnorm(Wq h)), rope_i(knorm(Wk h)), Wv h),  h = RMSNorm(x)
    x += MoE(RMSNorm(x))

- ``layer_types[i]`` is ``sliding_attention`` or ``full_attention``. A
  sliding layer lets query ``i`` see keys ``j`` with ``i - sliding_window <
  j <= i`` and rotates by ``rope_parameters["sliding_attention"]`` (plain,
  ``rope_theta``). A full layer is causal over the whole context and rotates
  by ``rope_parameters["full_attention"]``: YaRN (``factor``,
  ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``; the
  correction range truncated to whole dimensions unless ``truncate`` is
  false, the Hugging Face default), with ``attention_factor`` on cos and sin.
- ``MoE(h)``: ``softmax(h . Wr)`` over ``num_experts``, the
  ``num_experts_per_tok`` largest, renormalised to sum 1 if
  ``norm_topk_prob``; each chosen expert ``Wd (silu(Wg h) * Wu h)`` of width
  ``moe_intermediate_size``; no shared expert. Every expert is applied to
  every token by a plain loop and weighted by the token's (mostly zero)
  routing weight.

It reads the served bf16 parameters and upcasts them piecewise, so that its
transient stays well under 1 GB beside a full device: one expert's three
matrices at a time, attention in blocks of 128 queries, the output head in
slices of the vocabulary.

``cfg`` carries the public keys; the layers run are the first
``num_hidden_layers`` entries of ``layer_types``. Parameters: ``embed``
[vocab, hidden]; ``layers[i]`` with ``attn_norm``, ``wq``, ``wk``, ``wv``,
``wo``, ``q_norm``, ``k_norm`` (both [head_dim]), ``mlp_norm``, ``w_router``
[hidden, experts], ``w_gate``, ``w_up`` [experts, hidden, width], ``w_down``
[experts, width, hidden]; ``final_norm``; ``lm_head`` [hidden, vocab].
Matrices are stored [in, out].

Departures from the publication, each with its reason: the multi-token
prediction head the model card mentions is left out (the config has no key
for it and serving does not need it); rotary positions in the rotate-half
layout of the Hugging Face code (the layout checkpoints are stored for);
per-head RMSNorm on q and k before the rotation is ASSUMED (the config has
no key for it; its other keys are the Qwen3-MoE lineage's, which has it):
``cfg["qk_norm"]``, listed under ``assumed`` in the configuration file.

TOLERANCE. As ``dense_decoder``: the configuration's ``reference_tolerance``
(``worst_nat``, ``mean_nat``) over the compared tokens, with the numbers it
was set from in the file. One thing is new here: a token whose 8th and 9th
router probabilities are within rounding of each other is routed to another
expert by the bf16 engine than by this reference, in that layer, and its
output there moves by a whole expert's contribution. That is rounding, not a
wrong computation, and the bounds leave room for it (PERF.md section 6).

``compare`` takes, besides ``skip_layer`` and ``kv_bits``, three switches
used by hand to show that the bounds catch this family's own mistakes:
``ignore_window`` (sliding layers see the whole context), ``no_yarn`` (full
layers rotate plainly), ``no_renorm`` (top-k weights not renormalised).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128          # queries per attention block
VOCAB_SLICE = 8192     # output-head columns upcast at a time


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


def rope_inv_freq(rp: Dict[str, Any], head_dim: int) -> (np.ndarray, float):
    """(inv_freq [d/2], factor on cos and sin) of one ``rope_parameters``
    entry: ``default`` or ``yarn``."""
    base = float(rp["rope_theta"])
    pos_freqs = base ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if rp.get("rope_type", "default") == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r} is not written down here")
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low, high = correction_dim(float(rp["beta_fast"])), correction_dim(float(rp["beta_slow"]))
    if rp.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1 where the dimension turns fast enough to extrapolate
    inv = (1.0 / (factor * pos_freqs)) * (1 - keep) + (1.0 / pos_freqs) * keep
    att = rp.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(att)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps", "window", "qk_norm", "kv_bits"))
def _attention(p, x, cos, sin, *, n_heads, n_kv, head_dim, eps, window, qk_norm, kv_bits=None):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
        T = x.shape[0]
        group = n_heads // n_kv
        h = _rms_norm(x, p["attn_norm"], eps)
        q = (h @ p["wq"]).reshape(T, n_heads, head_dim)
        k = (h @ p["wk"]).reshape(T, n_kv, head_dim)
        v = (h @ p["wv"]).reshape(T, n_kv, head_dim)
        if qk_norm:
            q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        if kv_bits == 8:
            k, v = _fake_quant_int8(k), _fake_quant_int8(v)
        q = q.reshape(T // Q_BLOCK, Q_BLOCK, n_kv, group, head_dim)
        key_pos = jnp.arange(T)

        def block(args):
            qb, b = args                                  # [Q_BLOCK, kv, g, d]
            q_pos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
            scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(head_dim)
            seen = key_pos[None, :] <= q_pos[:, None]
            if window is not None:
                seen = seen & (key_pos[None, :] > q_pos[:, None] - window)
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
            return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, axis=-1), v)

        attn = jax.lax.map(block, (q, jnp.arange(T // Q_BLOCK)))
        return x + attn.reshape(T, n_heads * head_dim) @ p["wo"]


@partial(jax.jit, static_argnames=("top_k", "eps", "renorm"))
def _experts(p, x, *, top_k, eps, renorm):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, p["mlp_norm"].astype(jnp.float32), eps)
        probs = jax.nn.softmax(h @ p["w_router"].astype(jnp.float32), axis=-1)
        top_w, top_i = jax.lax.top_k(probs, top_k)
        if renorm:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        E = probs.shape[-1]
        # [T, E]: a token's weight on each expert, zero where it was not chosen
        weight = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32) * top_w[..., None], axis=1)

        def one(y, e):  # every expert, one at a time, applied to every token
            wg, wu, wd = (p[n][e].astype(jnp.float32) for n in ("w_gate", "w_up", "w_down"))
            out = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
            return y + weight[:, e, None] * out, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(E))
        return x + y


@partial(jax.jit, static_argnames=("eps", "tied", "cols"))
def _head(final_norm, head, x, *, eps, tied, cols):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
        V = head.shape[0] if tied else head.shape[1]

        def part(c):
            if tied:
                w = jax.lax.dynamic_slice_in_dim(head, c * cols, cols, axis=0).astype(jnp.float32).T
            else:
                w = jax.lax.dynamic_slice_in_dim(head, c * cols, cols, axis=1).astype(jnp.float32)
            return x @ w                                   # [rows, cols]

        logits = jax.lax.map(part, jnp.arange(V // cols))  # [V/cols, rows, cols]
        logits = jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], V)
        return jax.nn.log_softmax(logits, axis=-1)


def _vocab_slice(V: int) -> int:
    return next(c for c in range(min(V, VOCAB_SLICE), 0, -1) if V % c == 0)


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, kv_bits: Optional[int] = None,
             ignore_window: bool = False, no_yarn: bool = False,
             no_renorm: bool = False) -> np.ndarray:
    """Log-probabilities [len(rows), vocab] of the next token after each
    position in ``rows``, from one full forward over ``token_ids``.

    ``pad_to`` pads the sequence (causal attention: positions after the last
    real one cannot touch earlier ones) so that every prompt compiles the
    same shapes; the length is then rounded up to whole query blocks.
    ``device`` is where the reference runs. ``skip_layer``, ``kv_bits``,
    ``ignore_window``, ``no_yarn`` and ``no_renorm`` exist to show that the
    tolerance fails a wrong computation, never to pass one."""
    head_dim = int(cfg["head_dim"])
    eps = float(cfg["rms_norm_eps"])
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    T = max(len(ids), pad_to or 0)
    T = -(-T // Q_BLOCK) * Q_BLOCK
    ids = np.concatenate([ids, np.zeros(T - len(ids), np.int32)])
    pos = np.arange(T, dtype=np.float32)
    tables = {}
    for kind, rp in cfg["rope_parameters"].items():
        if no_yarn and rp.get("rope_type") == "yarn":
            rp = {"rope_type": "default", "rope_theta": rp["rope_theta"]}
        inv, att = rope_inv_freq(rp, head_dim)
        ang = pos[:, None] * inv[None, :]
        tables[kind] = (put((np.cos(ang) * att)[:, None, :].astype(np.float32)),
                        put((np.sin(ang) * att)[:, None, :].astype(np.float32)))
    attn_keys = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
    x = put(params["embed"])[put(ids)].astype(jnp.float32)
    for i, lp in enumerate(params["layers"][: cfg["num_hidden_layers"]]):
        if i == skip_layer:
            continue
        kind = cfg["layer_types"][i]
        sliding = kind == "sliding_attention"
        cos, sin = tables[kind]
        x = _attention(
            {k: put(lp[k]) for k in attn_keys if k in lp}, x, cos, sin,
            n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
            head_dim=head_dim, eps=eps, qk_norm=bool(cfg.get("qk_norm", True)),
            window=int(cfg["sliding_window"]) if sliding and not ignore_window else None,
            kv_bits=kv_bits,
        )
        x = _experts(
            {k: put(lp[k]) for k in ("mlp_norm", "w_router", "w_gate", "w_up", "w_down")}, x,
            top_k=int(cfg["num_experts_per_tok"]), eps=eps,
            renorm=bool(cfg["norm_topk_prob"]) and not no_renorm,
        )
    tied = bool(cfg.get("tie_word_embeddings"))
    head = params["embed"] if tied else params["lm_head"]
    out = _head(put(params["final_norm"]), put(head), x[np.asarray(rows)],
                eps=eps, tied=tied, cols=_vocab_slice(int(cfg["vocab_size"])))
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
