"""Plain reference: dots3-note-prev (``model_type`` ``dots3_note``), a decoder
whose attention sizes are a LAYER KIND's, as ONE CHIP'S SHARE of a layer
divided over chips.

Written from the public ``config.json`` keys, in plain ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. K and V are
materialised per head; no cache, no kernels, no batching, no weight
absorption; it does not import ``dynamo_tpu`` (it borrows the small helpers
of ``mla_dsa_decoder``: the norms, the pair rotation, the exact top-k
selection, the dense and the routed feed-forward, the output head and the
8-bit stand-in).

A layer ``l`` of kind ``layer_types[l]``, with the kind's sizes ``(n, d_n,
d_r, d_v, r_q, r_kv, theta)``: ``full_attention`` reads
``num_attention_heads, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
q_lora_rank, kv_lora_rank, rope_theta`` (128, 128, 64, 128, 1024, 512, 8e7),
``sliding_attention`` the same keys under ``swa_`` (64, 192, 64, 128, 1024,
1024, 5e4)::

    h  = RMSNorm(x)
    cq = s_q RMSNorm(h W_dq);  q = cq W_uq -> n heads x [q_n | q_r], q_r rotated at theta
    [c_raw | k_r] = h W_dkv;   c = s_kv RMSNorm(c_raw);  k_r rotated (one for all heads)
    k_head = [c W_uk_head | k_r],  v_head = c W_uv_head          (materialised)
    o_head = softmax_{j in S_i}(q_i . k_j (d_n + d_r)^-1/2) v_j
    g = sigmoid(h W_g)  [n];   o_head *= g_head
    x += concat_heads(o) W_o;  x += FFN(RMSNorm(x))

- ``s_q = sqrt(hidden / r_q)``, ``s_kv = sqrt(hidden / r_kv)``
  (``apply_mla_qkv_lora_rescale``).
- ``S_i``, sliding: ``i - sliding_window_size < j <= i`` (513, the query's
  own position among them). Full: the layer's OWN indexer (every full layer
  has one): ``qI = cq W_Iq`` (``index_n_heads`` x ``index_head_dim``, the
  first ``qk_rope_head_dim`` dims of a head rotated at the full layers'
  theta), ``kI = LayerNorm(h W_Ik)`` (same dims rotated), ``w = h W_Iw *
  index_n_heads^-1/2 * index_head_dim^-1/2``; ``I(i, j) = sum_h w_h
  relu(qI_h . kI_j)``; ``S_i`` is the ``index_topk`` positions ``j <= i``
  with the largest ``I(i, .)`` (all of them while there are at most that
  many; exact top-k, ties either way).
- Rotation: interleaved pairs ``(2i, 2i + 1)``, plain frequencies
  (``rope_scaling`` null).
- ``FFN``: layers below ``first_k_dense_replace``: SwiGLU of
  ``intermediate_size``. The others: ``s = sigmoid(h W_r)`` over ALL
  ``router_outputs`` experts; the ``num_experts_per_tok`` largest of ``s +
  e_score_correction_bias`` are chosen (one group); weights ``s_sel /
  sum(s_sel)`` (``norm_topk_prob``) ``* routed_scaling_factor``; plus one
  always-on shared SwiGLU. THE SHARE: this chip holds the
  ``n_routed_experts`` experts from ``experts_held_first`` on; it adds ``g_e
  SwiGLU_e(h)`` for the chosen experts it holds and nothing for the others
  (their chips would), and the weights stay those of the whole layer. The
  partial sum is what goes on. The vocabulary is the slice ``vocab_size``
  states.

Parameters (matrices stored [in, out]) as ``mla_dsa_decoder`` lists them, a
layer at its kind's sizes, plus ``w_g`` [hidden, n]; every full layer has the
indexer's five.

TOLERANCE. ``reference_tolerance`` (``worst_nat``, ``mean_nat``, optionally
``median_nat``) as in ``mla_dsa_decoder``, with the readings it was set from
in the file. ``compare`` takes switches used by hand to show that the bounds
catch this family's own mistakes (``calibrate`` runs them all): ``window``
(another window's size), ``no_gate``, ``no_rescale``, ``swa_theta_from_full``
(the sliding layers rotated at the full layers' base), ``sliding_dense`` (a
sliding layer attending every causal key), ``dense_attention`` (the selection
ignored), ``topk_scale``, ``kv_bits`` 8 (the latents, the rotary keys and the
index keys held at 8 bits), ``skip_layer``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.mla_dsa_decoder import (
    DENSE_SLICE,
    F32,
    Q_BLOCK,
    VOCAB_SLICE,
    _blocks,
    _dense_ffn,
    _experts,
    _fake_quant_int8,
    _head,
    _layer_norm,
    _rms_norm,
    _rotate_front,
    _rotate_pairs,
    _select,
    _slice_of,
)

FULL, SLIDING = "full_attention", "sliding_attention"


def kind_sizes(cfg: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """The attention sizes of a layer kind, off the public keys."""
    pre = "" if kind == FULL else "swa_"
    names = dict(heads="num_attention_heads", nope="qk_nope_head_dim", rope="qk_rope_head_dim",
                 v="v_head_dim", r_q="q_lora_rank", rank="kv_lora_rank", theta="rope_theta")
    out = {k: cfg[pre + name] for k, name in names.items()}
    out["theta"] = float(out["theta"])
    return out


def _tables(theta: float, rope: int, T: int, put):
    inv = 1.0 / theta ** (np.arange(0, rope, 2, dtype=np.float64) / rope)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return put(np.cos(ang).astype(np.float32)), put(np.sin(ang).astype(np.float32))


@partial(jax.jit, static_argnames=("n", "d", "rope", "eps", "topk", "s_q", "dtype", "kv_bits"))
def _indexer(p, x, cos, sin, *, n, d, rope, eps, topk, s_q, dtype="float32", kv_bits=None):
    """Boolean [T, T]: the keys a full layer's own indexer selects."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda w: w.astype(F32), p)
        h = _rms_norm(x, p["attn_norm"], eps)
        cq = s_q * _rms_norm(h @ p["w_dq"], p["q_norm"], eps)
        qI = _rotate_front(jnp.einsum("tr,rnd->tnd", cq, p["w_iq"]), cos, sin, rope)
        kI = _rotate_front(_layer_norm(h @ p["w_ik"], p["ik_norm_w"], p["ik_norm_b"]), cos, sin, rope)
        if kv_bits == 8:
            kI = _fake_quant_int8(kI)
        w = (h @ p["w_iw"]) * (n ** -0.5 * d ** -0.5)
        return _select(qI, kI, w, topk, jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("nope", "rank", "eps", "s_q", "s_kv", "gate", "kv_bits"))
def _attention(p, x, seen, cos, sin, *, nope, rank, eps, s_q, s_kv, gate=True, kv_bits=None):
    """x + gated attention over the keys ``seen`` [T, T] allows; K and V
    materialised, one head at a time, a block of queries' scores at a time."""
    with jax.default_matmul_precision("highest"):
        small = {k: p[k].astype(F32) for k in ("attn_norm", "w_dq", "q_norm", "w_dkv", "kv_norm", "w_g")}
        h = _rms_norm(x, small["attn_norm"], eps)
        cq = s_q * _rms_norm(h @ small["w_dq"], small["q_norm"], eps)
        ckv = h @ small["w_dkv"]
        c = s_kv * _rms_norm(ckv[:, :rank], small["kv_norm"], eps)
        k_r = _rotate_pairs(ckv[:, rank:], cos, sin)
        if kv_bits == 8:
            c, k_r = _fake_quant_int8(c), _fake_quant_int8(k_r)
        g = jax.nn.sigmoid(h @ small["w_g"]) if gate else jnp.ones((x.shape[0], p["w_uk"].shape[0]), F32)
        scale = 1.0 / math.sqrt(nope + k_r.shape[1])
        T = x.shape[0]
        B = _blocks(T)

        def head(y, w):
            w_uq, w_uk, w_uv, wo, gh = w
            w_uq, w_uk, w_uv, wo = (a.astype(F32) for a in (w_uq, w_uk, w_uv, wo))
            q = cq @ w_uq                                       # [T, nope + rope]
            q = jnp.concatenate([q[:, :nope], _rotate_pairs(q[:, nope:], cos, sin)], axis=-1)
            k = jnp.concatenate([c @ w_uk, k_r], axis=-1)       # [T, nope + rope]
            v = c @ w_uv                                        # [T, v]

            def block(args):                                    # a block of queries
                qb, sb = args
                s = jnp.where(sb, (qb @ k.T) * scale, -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ v

            o = jax.lax.map(block, (q.reshape(T // B, B, -1), seen.reshape(T // B, B, T)))
            return y + (o.reshape(T, -1) * gh[:, None]) @ wo, None

        per_head = (jnp.moveaxis(p["w_uq"], 1, 0), p["w_uk"], p["w_uv"], p["wo"], g.T)
        y, _ = jax.lax.scan(head, jnp.zeros_like(x), per_head)
        return x + y


_INDEXER_KEYS = ("attn_norm", "w_dq", "q_norm", "w_iq", "w_ik", "ik_norm_w", "ik_norm_b", "w_iw")
_ATTN_KEYS = ("attn_norm", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo", "w_g")
_DENSE_KEYS = ("mlp_norm", "w_gate", "w_up", "w_down")
_SPARSE_KEYS = ("mlp_norm", "w_router", "router_bias", "w_egate", "w_eup", "w_edown",
                "w_shared_gate", "w_shared_up", "w_shared_down")


def _rescales(cfg, sz, off: bool):
    if off or not cfg["apply_mla_qkv_lora_rescale"]:
        return 1.0, 1.0
    hidden = float(cfg["hidden_size"])
    return math.sqrt(hidden / sz["r_q"]), math.sqrt(hidden / sz["rank"])


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, kv_bits: Optional[int] = None,
             window: Optional[int] = None, no_gate: bool = False, no_rescale: bool = False,
             swa_theta_from_full: bool = False, sliding_dense: bool = False,
             dense_attention: bool = False, topk_scale: float = 1.0) -> np.ndarray:
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
    L = int(cfg["num_hidden_layers"])
    sizes = {kind: kind_sizes(cfg, kind) for kind in (FULL, SLIDING)}
    if swa_theta_from_full:
        sizes[SLIDING]["theta"] = sizes[FULL]["theta"]
    tables = {kind: _tables(sz["theta"], sz["rope"], T, put) for kind, sz in sizes.items()}
    pos = np.arange(T)
    causal_np = pos[None, :] <= pos[:, None]
    causal = put(causal_np)
    W = int(cfg["sliding_window_size"]) if window is None else int(window)
    in_window = causal if sliding_dense else put(causal_np & (pos[None, :] > pos[:, None] - W))
    topk = max(1, int(int(cfg["index_topk"]) * topk_scale))
    x = put(params["embed"])[put(ids)].astype(F32)
    for i, (kind, lp) in enumerate(zip(cfg["layer_types"][:L], params["layers"])):
        if i == skip_layer:
            continue
        sz = sizes[kind]
        cos, sin = tables[kind]
        s_q, s_kv = _rescales(cfg, sz, no_rescale)
        if kind == SLIDING:
            seen = in_window
        elif dense_attention:
            seen = causal
        else:
            seen = _indexer({k: put(lp[k]) for k in _INDEXER_KEYS}, x, cos, sin,
                            n=int(cfg["index_n_heads"]), d=int(cfg["index_head_dim"]),
                            rope=sz["rope"], eps=eps, topk=topk, s_q=s_q, kv_bits=kv_bits)
        x = _attention({k: put(lp[k]) for k in _ATTN_KEYS}, x, seen, cos, sin,
                       nope=sz["nope"], rank=sz["rank"], eps=eps, s_q=s_q, s_kv=s_kv,
                       gate=not no_gate, kv_bits=kv_bits)
        if i < int(cfg["first_k_dense_replace"]):
            x = _dense_ffn({k: put(lp[k]) for k in _DENSE_KEYS}, x, eps=eps,
                           cols=_slice_of(int(cfg["intermediate_size"]), DENSE_SLICE))
        else:
            x = _experts({k: put(lp[k]) for k in _SPARSE_KEYS}, x,
                         top_k=int(cfg["num_experts_per_tok"]), eps=eps,
                         renorm=bool(cfg["norm_topk_prob"]),
                         scaling=float(cfg["routed_scaling_factor"]),
                         first=int(cfg.get("experts_held_first", 0)))
    out = _head(put(params["final_norm"]), put(params["lm_head"]), x[np.asarray(rows)],
                eps=eps, cols=_slice_of(int(cfg["vocab_size"]), VOCAB_SLICE))
    return np.asarray(out)


def selection_flips(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
                    device=None) -> Dict[str, float]:
    """How many of a query's selected keys a bf16 index score puts on the
    other side of the cut than float32 does, at the first full layer (layer
    0, whose input is the embedding in both), over the queries that see more
    than ``index_topk`` keys: mean and worst count a query, and the share of
    the selection the two have in common."""
    eps = float(cfg["rms_norm_eps"])
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    T = -(-len(ids) // Q_BLOCK) * Q_BLOCK
    ids = np.concatenate([ids, np.zeros(T - len(ids), np.int32)])
    sz = kind_sizes(cfg, FULL)
    cos, sin = _tables(sz["theta"], sz["rope"], T, put)
    x = put(params["embed"])[put(ids)].astype(F32)
    lp = params["layers"][0]
    topk = int(cfg["index_topk"])
    kw = dict(n=int(cfg["index_n_heads"]), d=int(cfg["index_head_dim"]), rope=sz["rope"], eps=eps,
              topk=topk, s_q=_rescales(cfg, sz, False)[0])
    ip = {k: put(lp[k]) for k in _INDEXER_KEYS}
    exact = _indexer(ip, x, cos, sin, **kw)
    rounded = _indexer(ip, x, cos, sin, dtype="bfloat16", **kw)
    lost = np.asarray(jnp.sum(exact & ~rounded, axis=1))[topk:len(token_ids)]
    if not len(lost):
        return {"queries": 0, "mean": 0.0, "worst": 0.0, "overlap": 1.0}
    return {"queries": int(len(lost)), "mean": float(lost.mean()), "worst": float(lost.max()),
            "overlap": float(1.0 - lost.mean() / topk)}


def compare(cfg: Dict[str, Any], params: Dict[str, Any],
            samples: List[Dict[str, Any]], pad_to: int, device=None,
            **wrong) -> Dict[str, Any]:
    """Hold the engine's greedy continuations (prefill in chunks, then decode
    through both groups' pages: what the timed path produced) to the
    reference's one forward.

    ``samples``: ``{"prompt": [...], "tokens": [...], "logprobs": [...]}`` as
    the engine emitted them. Returns the worst differences and ``ok``."""
    worst_gap = 0.0
    diffs: List[float] = []
    for s in samples:
        P, emitted = len(s["prompt"]), list(s["tokens"])
        if not emitted or len(s["logprobs"]) != len(emitted):
            return {"ok": False, "reason": "a sample has no tokens or no logprobs",
                    "tokens_compared": len(diffs)}
        seq = list(s["prompt"]) + emitted
        rows = [P - 1 + j for j in range(len(emitted))]
        ref = logprobs(cfg, params, seq, rows, pad_to=pad_to, device=device, **wrong)
        for j, tok in enumerate(emitted):
            diffs.append(abs(float(ref[j, tok]) - float(s["logprobs"][j])))
            worst_gap = max(worst_gap, float(ref[j].max()) - float(ref[j, tok]))
    tol = cfg["reference_tolerance"]
    worst_lp, mean_lp = max(diffs, default=0.0), float(np.mean(diffs)) if diffs else 0.0
    median_lp = float(np.median(diffs)) if diffs else 0.0
    ok = (worst_lp <= tol["worst_nat"] and worst_gap <= tol["worst_nat"] and mean_lp <= tol["mean_nat"]
          and median_lp <= tol.get("median_nat", math.inf))
    return {
        "ok": bool(ok), "tokens_compared": len(diffs),
        "worst_logprob_difference_nat": worst_lp,
        "worst_argmax_gap_nat": worst_gap,
        "mean_logprob_difference_nat": mean_lp,
        "median_logprob_difference_nat": median_lp,
        "worst_tolerance_nat": tol["worst_nat"], "mean_tolerance_nat": tol["mean_nat"],
        "median_tolerance_nat": tol.get("median_nat"),
    }


# the named wrong computations the tolerance has to tell from the honest one
WRONG = {
    "window_512": dict(window=512),
    "window_1024": dict(window=1024),
    "no_gate": dict(no_gate=True),
    "no_rescale": dict(no_rescale=True),
    "swa_theta_from_full": dict(swa_theta_from_full=True),
    "sliding_dense": dict(sliding_dense=True),
    "selection_ignored": dict(dense_attention=True),
    "index_topk_halved": dict(topk_scale=0.5),
    "cache_8_bits": dict(kv_bits=8),
    "skipped_layer": dict(skip_layer=2),
}


def calibrate(cfg, params, samples, pad_to, device=None, only: Optional[Sequence[str]] = None):
    """``compare`` under the honest computation and under each of ``WRONG``
    (by hand, on the chip: what the tolerance in the file was set from)."""
    out = {"honest": compare(cfg, params, samples, pad_to, device=device)}
    for name, kw in WRONG.items():
        if only is None or name in only:
            out[name] = compare(cfg, params, samples, pad_to, device=device, **kw)
    return out
