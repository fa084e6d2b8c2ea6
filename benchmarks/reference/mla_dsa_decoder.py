"""Plain reference: a decoder with latent attention (MLA) that attends only
over the keys a learned indexer selects (DSA, with the selection shared by
the layers after a selecting one), sigmoid-routed experts with a shared one
(GLM-5.2, ``model_type`` ``glm_moe_dsa``), as ONE CHIP'S SHARE of a layer
divided over chips.

Written from the public ``config.json`` keys, in plain ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. No cache, no
kernels, no batching, no weight absorption; it does not import ``dynamo_tpu``.

The layer, for every layer ``i`` run (published layer ``layer_offset + i``)::

    h  = RMSNorm(x)
    cq = RMSNorm(h W_dq);  q = cq W_uq  -> heads x [q_nope | q_pe], q_pe rotated
    [c_raw | k_pe_raw] = h W_dkv;  c = RMSNorm(c_raw);  k_pe rotated (one for all heads)
    k_head = [c W_uk_head | k_pe],  v_head = c W_uv_head        (materialised)
    x += concat_heads(softmax_{s in S_t}(q_t . k_s / sqrt(qk_head_dim)) v_s) W_o
    x += FFN(RMSNorm(x))

- ``S_t``. On a layer whose ``indexer_types`` entry is ``full``:
  ``qI = cq W_Iq`` (``index_n_heads`` x ``index_head_dim``, the first
  ``qk_rope_head_dim`` dims of a head rotated), one key a token
  ``kI = LayerNorm(h W_Ik)`` (same dims rotated), head weights
  ``w = h W_Iw * index_n_heads^-1/2 * index_head_dim^-1/2``;
  ``I(t, s) = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t``
  is the ``index_topk`` positions with the largest ``I(t, .)`` (every causal
  position while there are at most that many; exact top-k, ties either way).
  On a ``shared`` layer ``S_t`` is the nearest ``full`` layer's before it.
- Rotation: interleaved pairs ``(2i, 2i + 1)`` (``rope_interleave``,
  ``indexer_rope_interleave`` true), plain ``rope_theta``.
- ``FFN``: ``mlp_layer_types`` ``dense``: SwiGLU of ``intermediate_size``.
  ``sparse``: ``s = sigmoid(h W_r)`` over ALL ``router_outputs`` experts;
  the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` are
  chosen (``n_group`` 1: no group limit); weights ``s_sel / sum(s_sel)`` (if
  ``norm_topk_prob``) ``* routed_scaling_factor``; plus one always-on shared
  SwiGLU. THE SHARE: this chip holds the ``n_routed_experts`` experts from
  ``experts_held_first`` on; it adds ``g_e SwiGLU_e(h)`` for the chosen
  experts it holds and nothing for the others (their chips would), and the
  weights stay those of the whole layer. The partial sum is what goes on.
  With ``router_outputs == n_routed_experts`` and first 0 it is the uncut
  layer. The vocabulary is the slice ``vocab_size`` states.

It reads bf16 parameters and upcasts them piecewise: one head's projections,
one expert, a slice of the dense width or of the vocabulary, a block of
queries' scores at a time.

Parameters (matrices stored [in, out]): ``embed`` [vocab, hidden];
``final_norm``; ``lm_head`` [hidden, vocab]; ``layers[i]``: ``attn_norm``,
``w_dq`` [hidden, q_lora_rank], ``q_norm``, ``w_uq`` [q_lora_rank, heads,
nope + rope], ``w_dkv`` [hidden, rank + rope], ``kv_norm``, ``w_uk`` [heads,
rank, nope], ``w_uv`` [heads, rank, v], ``wo`` [heads, v, hidden];
``mlp_norm``; on ``full`` layers ``w_iq`` [q_lora_rank, n, d], ``w_ik``
[hidden, d], ``ik_norm_w``, ``ik_norm_b`` [d], ``w_iw`` [hidden, n]; dense
``w_gate``, ``w_up``, ``w_down``; sparse ``w_router`` [hidden, router_outputs],
``router_bias`` [router_outputs], ``w_egate``, ``w_eup`` [held, hidden, width],
``w_edown`` [held, width, hidden], ``w_shared_gate``, ``w_shared_up``,
``w_shared_down``.

Departures from the publication, each listed under ``assumed`` in the
configuration file: the Hadamard rotation of ``qI`` / ``kI`` (orthogonal: it
leaves ``qI . kI`` unchanged) and their FP8 storage are left out; the
multi-token prediction head is left out; LayerNorm's epsilon is 1e-6.

TOLERANCE. ``reference_tolerance`` (``worst_nat``, ``mean_nat``) as in
``moe_window_decoder``, with the readings it was set from in the file. New
here: a bf16 index score puts some of a query's lowest-ranked selected keys
on the other side of the cut than float32 does (``selection_flips`` counts
them); that is rounding, and the bounds leave room for it. ``compare`` takes
switches used by hand to show that the bounds catch this family's own
mistakes: ``skip_layer``, ``kv_bits``, ``dense_attention`` (the selection
ignored), ``shared`` = ``"own"`` (a shared layer selects for itself, with the
nearest indexer) or ``"none"`` (attends over everything), ``topk_scale``,
``no_router_bias``, ``no_routed_scale``, ``no_shared_expert``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128          # the sequence is padded to a multiple of this
Q_ROWS = 1024          # at most this many queries' scores are held at a time
VOCAB_SLICE = 8192     # output-head columns upcast at a time
DENSE_SLICE = 2048     # dense feed-forward columns upcast at a time
LN_EPS = 1e-6

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + LN_EPS) * w + b


def _rotate_pairs(x, cos, sin):
    """x [..., d] with d even; cos/sin [T, d/2] broadcast over the middle
    dims: rotate the interleaved pairs (2i, 2i + 1)."""
    a, b = x[..., 0::2], x[..., 1::2]
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _rotate_front(x, cos, sin, rope):
    return jnp.concatenate([_rotate_pairs(x[..., :rope], cos, sin), x[..., rope:]], axis=-1)


def _fake_quant_int8(x, lanes=128):
    """What a cache held at 8 bits would return: symmetric int8 per (16-token
    page, row of ``lanes`` lanes). Only to show that the tolerance tells it apart."""
    T, d = x.shape
    lanes = min(lanes, d)
    pad = (-T) % 16
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, 16, d // lanes, lanes)
    amax = jnp.max(jnp.abs(xp), axis=(1, 3), keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return (jnp.round(xp / scale).clip(-127, 127) * scale).reshape(-1, d)[:T]


def _blocks(T: int) -> int:
    """Queries a block: the [block, T] scores of a block are what is held."""
    return _slice_of(T, Q_ROWS)


def _select(qI, kI, w, topk, dtype=F32):
    """Boolean [T, T]: for each query the ``topk`` largest causal index
    scores ``I(t, s) = sum_j w[t, j] relu(qI[t, j] . kI[s])`` (all causal
    positions while there are at most ``topk``); qI [T, n, d], kI [T, d],
    w [T, n]. A block of queries at a time, head by head. ``dtype`` bfloat16
    rounds the three inputs first (the products still add in float32): what
    a bf16 program scores."""
    qI, kI, w = (a.astype(dtype).astype(F32) for a in (qI, kI, w))
    T, n, _ = qI.shape
    B = _blocks(T)
    key_pos = jnp.arange(T)

    def block(args):
        qb, wb, b = args                                    # [B, n, d], [B, n]
        def head(acc, j):
            return acc + jax.nn.relu(qb[:, j] @ kI.T) * wb[:, j, None], None

        scores, _ = jax.lax.scan(head, jnp.zeros((B, T), F32), jnp.arange(n))
        causal = key_pos[None, :] <= (b * B + jnp.arange(B))[:, None]
        _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, T))
        picked = jnp.zeros((B, T), bool).at[jnp.arange(B)[:, None], idx].set(True)
        return picked & causal

    seen = jax.lax.map(block, (qI.reshape(T // B, B, n, -1), w.reshape(T // B, B, n), jnp.arange(T // B)))
    return seen.reshape(T, T)


@partial(jax.jit, static_argnames=("n", "d", "rope", "eps", "topk", "dtype", "kv_bits"))
def _indexer(p, x, cos, sin, *, n, d, rope, eps, topk, dtype="float32", kv_bits=None):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda w: w.astype(F32), p)
        h = _rms_norm(x, p["attn_norm"], eps)
        cq = _rms_norm(h @ p["w_dq"], p["q_norm"], eps)
        qI = _rotate_front(jnp.einsum("tr,rnd->tnd", cq, p["w_iq"]), cos, sin, rope)
        kI = _rotate_front(_layer_norm(h @ p["w_ik"], p["ik_norm_w"], p["ik_norm_b"]), cos, sin, rope)
        if kv_bits == 8:
            kI = _fake_quant_int8(kI)
        w = (h @ p["w_iw"]) * (n ** -0.5 * d ** -0.5)
        return _select(qI, kI, w, topk, jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("nope", "rope", "rank", "eps", "kv_bits"))
def _attention(p, x, seen, cos, sin, *, nope, rope, rank, eps, kv_bits=None):
    """x + attention over the keys ``seen`` [T, T] allows; K and V
    materialised, one head at a time."""
    with jax.default_matmul_precision("highest"):
        small = {k: p[k].astype(F32) for k in ("attn_norm", "w_dq", "q_norm", "w_dkv", "kv_norm")}
        h = _rms_norm(x, small["attn_norm"], eps)
        cq = _rms_norm(h @ small["w_dq"], small["q_norm"], eps)
        ckv = h @ small["w_dkv"]
        c = _rms_norm(ckv[:, :rank], small["kv_norm"], eps)
        k_pe = _rotate_pairs(ckv[:, rank:], cos, sin)
        if kv_bits == 8:
            c, k_pe = _fake_quant_int8(c), _fake_quant_int8(k_pe)
        scale = 1.0 / math.sqrt(nope + rope)

        T = x.shape[0]
        B = _blocks(T)

        def head(y, w):
            w_uq, w_uk, w_uv, wo = (a.astype(F32) for a in w)
            q = cq @ w_uq                                       # [T, nope + rope]
            q = jnp.concatenate([q[:, :nope], _rotate_pairs(q[:, nope:], cos, sin)], axis=-1)
            k = jnp.concatenate([c @ w_uk, k_pe], axis=-1)      # [T, nope + rope]
            v = c @ w_uv                                        # [T, v]

            def block(args):                                    # a block of queries
                qb, sb = args
                s = jnp.where(sb, (qb @ k.T) * scale, -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ v

            o = jax.lax.map(block, (q.reshape(T // B, B, -1), seen.reshape(T // B, B, T)))
            return y + o.reshape(T, -1) @ wo, None

        per_head = (jnp.moveaxis(p["w_uq"], 1, 0), p["w_uk"], p["w_uv"], p["wo"])
        y, _ = jax.lax.scan(head, jnp.zeros_like(x), per_head)
        return x + y


@partial(jax.jit, static_argnames=("eps", "cols"))
def _dense_ffn(p, x, *, eps, cols):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, p["mlp_norm"].astype(F32), eps)
        width = p["w_gate"].shape[1]

        def part(y, i):
            wg = jax.lax.dynamic_slice_in_dim(p["w_gate"], i * cols, cols, axis=1).astype(F32)
            wu = jax.lax.dynamic_slice_in_dim(p["w_up"], i * cols, cols, axis=1).astype(F32)
            wd = jax.lax.dynamic_slice_in_dim(p["w_down"], i * cols, cols, axis=0).astype(F32)
            return y + (jax.nn.silu(h @ wg) * (h @ wu)) @ wd, None

        y, _ = jax.lax.scan(part, jnp.zeros_like(x), jnp.arange(width // cols))
        return x + y


def _route(h, w_router, bias, *, top_k, renorm, scaling):
    """[T, E] weight of every expert for every token (zero where not chosen)."""
    s = jax.nn.sigmoid(h @ w_router)
    _, top_i = jax.lax.top_k(s + bias, top_k)
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if renorm:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    E = s.shape[-1]
    return jnp.sum(jax.nn.one_hot(top_i, E, dtype=F32) * (top_w * scaling)[..., None], axis=1)


@partial(jax.jit, static_argnames=("top_k", "eps", "renorm", "scaling", "first", "shared", "use_bias"))
def _experts(p, x, *, top_k, eps, renorm, scaling, first, shared=True, use_bias=True):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, p["mlp_norm"].astype(F32), eps)
        bias = p["router_bias"].astype(F32) if use_bias else 0.0
        weight = _route(h, p["w_router"].astype(F32), bias, top_k=top_k, renorm=renorm, scaling=scaling)
        held = p["w_egate"].shape[0]

        def one(y, e):  # every held expert, one at a time, applied to every token
            wg, wu, wd = (p[n][e].astype(F32) for n in ("w_egate", "w_eup", "w_edown"))
            out = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
            return y + jax.lax.dynamic_index_in_dim(weight, first + e, axis=1) * out, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
        if shared:
            sg, su, sd = (p[n].astype(F32) for n in ("w_shared_gate", "w_shared_up", "w_shared_down"))
            y = y + (jax.nn.silu(h @ sg) * (h @ su)) @ sd
        return x + y


@partial(jax.jit, static_argnames=("eps", "cols"))
def _head(final_norm, head, x, *, eps, cols):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, final_norm.astype(F32), eps)
        V = head.shape[1]

        def part(c):
            return x @ jax.lax.dynamic_slice_in_dim(head, c * cols, cols, axis=1).astype(F32)

        logits = jax.lax.map(part, jnp.arange(V // cols))  # [V/cols, rows, cols]
        logits = jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], V)
        return jax.nn.log_softmax(logits, axis=-1)


def _slice_of(n: int, most: int) -> int:
    return next(c for c in range(min(n, most), 0, -1) if n % c == 0)


def layer_kinds(cfg: Dict[str, Any]):
    """(indexer kind, ffn kind) of each layer run: the published lists from
    ``layer_offset`` on."""
    lo, L = int(cfg.get("layer_offset", 0)), int(cfg["num_hidden_layers"])
    return list(zip(cfg["indexer_types"][lo:lo + L], cfg["mlp_layer_types"][lo:lo + L]))


_INDEXER_KEYS = ("attn_norm", "w_dq", "q_norm", "w_iq", "w_ik", "ik_norm_w", "ik_norm_b", "w_iw")
_ATTN_KEYS = ("attn_norm", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo")
_DENSE_KEYS = ("mlp_norm", "w_gate", "w_up", "w_down")
_SPARSE_KEYS = ("mlp_norm", "w_router", "router_bias", "w_egate", "w_eup", "w_edown",
                "w_shared_gate", "w_shared_up", "w_shared_down")


def _tables(cfg, T, put):
    rope = int(cfg["qk_rope_head_dim"])
    rp = cfg["rope_parameters"]
    if rp.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rp['rope_type']!r} is not written down here")
    inv = 1.0 / float(rp["rope_theta"]) ** (np.arange(0, rope, 2, dtype=np.float64) / rope)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return put(np.cos(ang).astype(np.float32)), put(np.sin(ang).astype(np.float32))


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, kv_bits: Optional[int] = None,
             dense_attention: bool = False, shared: str = "inherit",
             topk_scale: float = 1.0, no_router_bias: bool = False,
             no_routed_scale: bool = False, no_shared_expert: bool = False) -> np.ndarray:
    """Log-probabilities [len(rows), vocab] of the next token after each
    position in ``rows``, from one full forward over ``token_ids``.

    ``pad_to`` pads the sequence (causal: positions after the last real one
    cannot touch earlier ones) so that every prompt compiles the same shapes.
    The switches exist to show that the tolerance fails a wrong computation,
    never to pass one."""
    eps = float(cfg["rms_norm_eps"])
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    T = -(-max(len(ids), pad_to or 0) // Q_BLOCK) * Q_BLOCK
    ids = np.concatenate([ids, np.zeros(T - len(ids), np.int32)])
    cos, sin = _tables(cfg, T, put)
    nope, rope, rank = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank"))
    topk = max(1, int(int(cfg["index_topk"]) * topk_scale))
    causal = put(np.tril(np.ones((T, T), bool)))
    x = put(params["embed"])[put(ids)].astype(F32)
    seen, indexer = causal, None
    for i, ((kind, ffn), lp) in enumerate(zip(layer_kinds(cfg), params["layers"])):
        if i == skip_layer:
            continue
        if kind == "full":
            indexer = {k: put(lp[k]) for k in _INDEXER_KEYS if k not in ("attn_norm", "w_dq", "q_norm")}
        own = kind == "full" or shared == "own"
        if own:
            ip = dict(indexer, **{k: put(lp[k]) for k in ("attn_norm", "w_dq", "q_norm")})
            seen = _indexer(ip, x, cos, sin, n=int(cfg["index_n_heads"]), d=int(cfg["index_head_dim"]),
                            rope=rope, eps=eps, topk=topk, kv_bits=kv_bits)
        attend_over = causal if dense_attention or (kind == "shared" and shared == "none") else seen
        x = _attention({k: put(lp[k]) for k in _ATTN_KEYS}, x, attend_over, cos, sin,
                       nope=nope, rope=rope, rank=rank, eps=eps, kv_bits=kv_bits)
        if ffn == "dense":
            x = _dense_ffn({k: put(lp[k]) for k in _DENSE_KEYS}, x, eps=eps,
                           cols=_slice_of(int(cfg["intermediate_size"]), DENSE_SLICE))
        else:
            x = _experts({k: put(lp[k]) for k in _SPARSE_KEYS}, x,
                         top_k=int(cfg["num_experts_per_tok"]), eps=eps,
                         renorm=bool(cfg["norm_topk_prob"]),
                         scaling=1.0 if no_routed_scale else float(cfg["routed_scaling_factor"]),
                         first=int(cfg.get("experts_held_first", 0)),
                         shared=not no_shared_expert, use_bias=not no_router_bias)
    out = _head(put(params["final_norm"]), put(params["lm_head"]), x[np.asarray(rows)],
                eps=eps, cols=_slice_of(int(cfg["vocab_size"]), VOCAB_SLICE))
    return np.asarray(out)


def selection_flips(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
                    device=None) -> Dict[str, float]:
    """How many of a query's selected keys a bf16 index score puts on the
    other side of the cut than float32 does, at the first layer (whose input
    is the embedding in both), over the queries that see more than
    ``index_topk`` keys: mean and worst count a query."""
    eps = float(cfg["rms_norm_eps"])
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    T = -(-len(ids) // Q_BLOCK) * Q_BLOCK
    ids = np.concatenate([ids, np.zeros(T - len(ids), np.int32)])
    cos, sin = _tables(cfg, T, put)
    x = put(params["embed"])[put(ids)].astype(F32)
    lp = params["layers"][0]
    kw = dict(n=int(cfg["index_n_heads"]), d=int(cfg["index_head_dim"]),
              rope=int(cfg["qk_rope_head_dim"]), eps=eps, topk=int(cfg["index_topk"]))
    ip = {k: put(lp[k]) for k in _INDEXER_KEYS}
    exact = _indexer(ip, x, cos, sin, **kw)
    rounded = _indexer(ip, x, cos, sin, dtype="bfloat16", **kw)
    lost = np.asarray(jnp.sum(exact & ~rounded, axis=1))[int(cfg["index_topk"]):len(token_ids)]
    if not len(lost):
        return {"queries": 0, "mean": 0.0, "worst": 0.0}
    return {"queries": int(len(lost)), "mean": float(lost.mean()), "worst": float(lost.max())}


def compare(cfg: Dict[str, Any], params: Dict[str, Any],
            samples: List[Dict[str, Any]], pad_to: int, device=None,
            **wrong) -> Dict[str, Any]:
    """Hold the engine's greedy continuations to the reference.

    ``samples``: ``{"prompt": [...], "tokens": [...], "logprobs": [...]}`` as
    the engine emitted them. Returns the worst differences and ``ok``."""
    worst_gap = 0.0
    diffs: List[float] = []
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
            diffs.append(abs(float(ref[j, tok]) - float(s["logprobs"][j])))
            worst_gap = max(worst_gap, float(ref[j].max()) - float(ref[j, tok]))
            n += 1
    tol = cfg["reference_tolerance"]
    worst_lp, mean_lp = max(diffs, default=0.0), float(np.mean(diffs)) if diffs else 0.0
    median_lp = float(np.median(diffs)) if diffs else 0.0
    ok = (worst_lp <= tol["worst_nat"] and worst_gap <= tol["worst_nat"] and mean_lp <= tol["mean_nat"]
          and median_lp <= tol.get("median_nat", math.inf))
    return {
        "ok": bool(ok), "tokens_compared": n,
        "worst_logprob_difference_nat": worst_lp,
        "worst_argmax_gap_nat": worst_gap,
        "mean_logprob_difference_nat": mean_lp,
        "median_logprob_difference_nat": median_lp,
        "quartiles_logprob_difference_nat": [float(q) for q in np.percentile(diffs or [0.0], [25, 75, 90])],
        "worst_tolerance_nat": tol["worst_nat"], "mean_tolerance_nat": tol["mean_nat"],
        "median_tolerance_nat": tol.get("median_nat"),
    }
