"""Plain reference: a decoder with latent attention (MLA) over EVERY causal
key, YaRN rotary positions with their softmax factor, and group-limited
sigmoid routing with a bias over experts of which this chip holds a share,
plus one shared expert (A.X-K1, ``model_type`` ``axk1``: the DeepSeek-V3
layer), as ONE CHIP'S SHARE of a layer divided over chips.

Written from the public ``config.json`` keys, in plain ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. K and V are
materialised per head; no cache, no kernels, no batching, no weight
absorption; it does not import ``dynamo_tpu`` (it borrows the small helpers
of ``mla_dsa_decoder``: the norm, the pair rotation, the dense feed-forward,
the output head and the 8-bit stand-in).

The layer, for published layer ``i`` (every width as published)::

    h  = RMSNorm(x)
    cq = RMSNorm(h W_dq);  q = cq W_uq  -> heads x [q_nope | q_pe], q_pe rotated
    [c_raw | k_pe_raw] = h W_dkv;  c = RMSNorm(c_raw);  k_pe rotated (one for all heads)
    k_head = [c W_uk_head | k_pe],  v_head = c W_uv_head        (materialised)
    x += concat_heads(softmax_{s <= t}(q_t . k_s * scale) v_s) W_o
    x += FFN(RMSNorm(x))

- Rotation: interleaved pairs ``(2i, 2i + 1)`` over the ``d =
  qk_rope_head_dim`` rotary dims, at YaRN's frequencies (``rope_scaling``:
  ``factor`` f from ``original_max_position_embeddings`` L, ``beta_fast``,
  ``beta_slow``; base ``rope_theta`` b). Pair ``j`` has the plain frequency
  ``w_j = b^(-2j/d)``; ``dim(r) = d ln(L / (2 pi r)) / (2 ln b)`` is the pair
  that turns ``r`` times over L positions; ``low = floor(dim(beta_fast))``,
  ``high = ceil(dim(beta_slow))`` (kept inside ``0 .. d - 1``); ``ramp_j =
  clip((j - low) / (high - low), 0, 1)``; the frequency run is ``w_j (1 -
  ramp_j) + (w_j / f) ramp_j``. With ``m(a) = 0.1 a ln f + 1``: cos and sin
  are multiplied by ``m(mscale) / m(mscale_all_dim)`` and ``scale =
  (qk_nope_head_dim + qk_rope_head_dim)^-1/2 m(mscale_all_dim)^2``.
- ``FFN``: layers below ``first_k_dense_replace``: SwiGLU of
  ``intermediate_size``. The others: ``s = sigmoid(h W_r)`` over ALL
  ``router_outputs`` experts; ``sel = s + e_score_correction_bias``; the
  experts are ``n_group`` groups of equal size, in order; a group's score is
  the sum of its two largest ``sel``; the ``topk_group`` best groups stay and
  the others' ``sel`` become 0; the ``num_experts_per_tok`` largest ``sel``
  are chosen; weights ``s_chosen / sum(s_chosen)`` (if ``norm_topk_prob``)
  ``* routed_scaling_factor``; plus one always-on shared SwiGLU. THE SHARE:
  this chip holds the ``n_routed_experts`` experts from ``experts_held_first``
  on; it adds ``g_e SwiGLU_e(h)`` for the chosen experts it holds and nothing
  for the others (their chips would), and the weights stay those of the
  whole layer. The partial sum is what goes on. The vocabulary is the slice
  ``vocab_size`` states.

It reads bf16 parameters and upcasts them piecewise: one head's projections,
one expert, a slice of the dense width or of the vocabulary, a block of
queries' scores at a time; at the cell's own context (25k tokens) it holds
about 3 GB.

Parameters (matrices stored [in, out]) as ``mla_dsa_decoder`` lists them,
without an indexer's.

TOLERANCE. ``reference_tolerance`` (``worst_nat``, ``mean_nat``,
``median_nat``) as in ``mla_dsa_decoder``, with the readings it was set from
in the file. The median is the bound that tells a lower precision apart: a
near-tie routing flip moves a few tokens by tenths of a nat and the mean with
them, whatever the precision; the median moves with the precision alone.
``compare`` takes switches used by hand to show that the bounds catch this
family's own mistakes: ``no_mscale`` (the softmax scale without ``m^2``),
``plain_rope`` (plain frequencies), ``no_group_limit``, ``no_router_bias``,
``no_routed_scale``, ``no_shared_expert``, ``kv_bits`` 8 (the latent and the
rotary key held at 8 bits), ``skip_layer``.
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
    _fake_quant_int8,
    _head,
    _rms_norm,
    _rotate_pairs,
    _slice_of,
)


def yarn(cfg: Dict[str, Any], plain: bool = False):
    """(frequency of each rotary pair [d/2] float64, the factor on cos and
    sin, the factor on the softmax scale)."""
    d, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    w = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = cfg["rope_scaling"]
    f, L = float(rs["factor"]), float(rs["original_max_position_embeddings"])

    def m(a):
        return 0.1 * a * math.log(f) + 1.0

    on_tables = m(float(rs["mscale"])) / m(float(rs["mscale_all_dim"]))
    on_scale = m(float(rs["mscale_all_dim"])) ** 2
    if plain:
        return w, on_tables, on_scale

    def dim(r):
        return d * math.log(L / (2 * math.pi * r)) / (2 * math.log(base))

    low = max(math.floor(dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(rs["beta_slow"]))), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return w * (1.0 - ramp) + (w / f) * ramp, on_tables, on_scale


@partial(jax.jit, static_argnames=("nope", "rank", "eps", "scale", "kv_bits"))
def _attention(p, x, cos, sin, *, nope, rank, eps, scale, kv_bits=None):
    """x + attention over every causal key; K and V materialised, one head
    at a time, a block of queries' scores at a time."""
    with jax.default_matmul_precision("highest"):
        small = {k: p[k].astype(F32) for k in ("attn_norm", "w_dq", "q_norm", "w_dkv", "kv_norm")}
        h = _rms_norm(x, small["attn_norm"], eps)
        cq = _rms_norm(h @ small["w_dq"], small["q_norm"], eps)
        ckv = h @ small["w_dkv"]
        c = _rms_norm(ckv[:, :rank], small["kv_norm"], eps)
        k_pe = _rotate_pairs(ckv[:, rank:], cos, sin)
        if kv_bits == 8:
            c, k_pe = _fake_quant_int8(c), _fake_quant_int8(k_pe)
        T = x.shape[0]
        B = _blocks(T)
        key_pos = jnp.arange(T)

        def head(_, w):
            w_uq, w_uk, w_uv = (a.astype(F32) for a in w)
            q = cq @ w_uq                                       # [T, nope + rope]
            q = jnp.concatenate([q[:, :nope], _rotate_pairs(q[:, nope:], cos, sin)], axis=-1)
            k = jnp.concatenate([c @ w_uk, k_pe], axis=-1)      # [T, nope + rope]
            v = c @ w_uv                                        # [T, v]

            def block(args):                                    # a block of queries
                qb, b = args
                causal = key_pos[None, :] <= (b * B + jnp.arange(B))[:, None]
                s = jnp.where(causal, (qb @ k.T) * scale, -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ v

            return None, jax.lax.map(block, (q.reshape(T // B, B, -1), jnp.arange(T // B))).reshape(T, -1)

        _, o = jax.lax.scan(head, None, (jnp.moveaxis(p["w_uq"], 1, 0), p["w_uk"], p["w_uv"]))

        def rows(args):                                         # [heads, B, v] -> [B, hidden]
            ob, xb = args
            return xb + jnp.einsum("hbv,hvd->bd", ob, p["wo"].astype(F32))

        ob = jnp.moveaxis(o.reshape(o.shape[0], T // B, B, -1), 1, 0)
        return jax.lax.map(rows, (ob, x.reshape(T // B, B, -1))).reshape(x.shape)


def _route(h, w_router, bias, *, top_k, renorm, scaling, n_group, topk_group):
    """[T, E] weight of every expert for every token (zero where not chosen)."""
    s = jax.nn.sigmoid(h @ w_router)
    sel = s + bias
    T, E = s.shape
    if n_group > 1:
        per = E // n_group
        best_two = jnp.sort(sel.reshape(T, n_group, per), axis=-1)[..., -2:].sum(-1)    # [T, G]
        _, kept = jax.lax.top_k(best_two, topk_group)
        keep = jnp.zeros((T, n_group), bool).at[jnp.arange(T)[:, None], kept].set(True)
        sel = jnp.where(jnp.repeat(keep, per, axis=1), sel, 0.0)
    _, top_i = jax.lax.top_k(sel, top_k)
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if renorm:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(top_i, E, dtype=F32) * (top_w * scaling)[..., None], axis=1)


@partial(jax.jit, static_argnames=("top_k", "eps", "renorm", "scaling", "first", "n_group",
                                   "topk_group", "shared", "use_bias"))
def _experts(p, x, *, top_k, eps, renorm, scaling, first, n_group, topk_group, shared=True, use_bias=True):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, p["mlp_norm"].astype(F32), eps)
        bias = p["router_bias"].astype(F32) if use_bias else 0.0
        weight = _route(h, p["w_router"].astype(F32), bias, top_k=top_k, renorm=renorm, scaling=scaling,
                        n_group=n_group, topk_group=topk_group)
        held = p["w_egate"].shape[0]

        def one(y, e):  # every held expert, one at a time, applied to every token
            wg, wu, wd = (p[n][e].astype(F32) for n in ("w_egate", "w_eup", "w_edown"))
            out = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
            return y + jax.lax.dynamic_index_in_dim(weight, first + e, axis=1) * out, None

        y, _ = jax.lax.scan(one, x, jnp.arange(held))
        if shared:
            sg, su, sd = (p[n].astype(F32) for n in ("w_shared_gate", "w_shared_up", "w_shared_down"))
            y = y + (jax.nn.silu(h @ sg) * (h @ su)) @ sd
        return y


_ATTN_KEYS = ("attn_norm", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo")
_DENSE_KEYS = ("mlp_norm", "w_gate", "w_up", "w_down")
_SPARSE_KEYS = ("mlp_norm", "w_router", "router_bias", "w_egate", "w_eup", "w_edown",
                "w_shared_gate", "w_shared_up", "w_shared_down")


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, kv_bits: Optional[int] = None,
             no_mscale: bool = False, plain_rope: bool = False, no_group_limit: bool = False,
             no_router_bias: bool = False, no_routed_scale: bool = False,
             no_shared_expert: bool = False) -> np.ndarray:
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
    freq, on_tables, on_scale = yarn(cfg, plain=plain_rope)
    ang = np.arange(T, dtype=np.float64)[:, None] * freq[None, :]
    cos, sin = put((np.cos(ang) * on_tables).astype(np.float32)), put((np.sin(ang) * on_tables).astype(np.float32))
    nope, rope, rank = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank"))
    scale = (nope + rope) ** -0.5 * (1.0 if no_mscale else on_scale)
    x = put(params["embed"])[put(ids)].astype(F32)
    for i, lp in enumerate(params["layers"]):
        if i == skip_layer:
            continue
        x = _attention({k: put(lp[k]) for k in _ATTN_KEYS}, x, cos, sin,
                       nope=nope, rank=rank, eps=eps, scale=scale, kv_bits=kv_bits)
        if i < int(cfg["first_k_dense_replace"]):
            x = _dense_ffn({k: put(lp[k]) for k in _DENSE_KEYS}, x, eps=eps,
                           cols=_slice_of(int(cfg["intermediate_size"]), DENSE_SLICE))
        else:
            x = _experts({k: put(lp[k]) for k in _SPARSE_KEYS}, x,
                         top_k=int(cfg["num_experts_per_tok"]), eps=eps,
                         renorm=bool(cfg["norm_topk_prob"]),
                         scaling=1.0 if no_routed_scale else float(cfg["routed_scaling_factor"]),
                         first=int(cfg.get("experts_held_first", 0)),
                         n_group=1 if no_group_limit else int(cfg["n_group"]),
                         topk_group=int(cfg["topk_group"]),
                         shared=not no_shared_expert, use_bias=not no_router_bias)
    out = _head(put(params["final_norm"]), put(params["lm_head"]), x[np.asarray(rows)],
                eps=eps, cols=_slice_of(int(cfg["vocab_size"]), VOCAB_SLICE))
    return np.asarray(out)


def compare(cfg: Dict[str, Any], params: Dict[str, Any],
            samples: List[Dict[str, Any]], pad_to: int, device=None,
            **wrong) -> Dict[str, Any]:
    """Hold the engine's greedy continuations to the reference.

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
