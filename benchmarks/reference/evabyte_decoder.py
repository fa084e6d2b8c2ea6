"""Plain reference: EvaByte's decoder (``model_type`` ``evabyte``,
``attention_class`` ``eva``: "Efficient Attention via Control Variates",
arXiv 2302.04542), a byte-level model whose every layer attends EXACTLY
inside the query's own window and to ONE LEARNED SUMMARY a chunk of every
window before it.

Written from the published configuration and the layer as ISSUE 46 wrote it
down, in plain ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No cache, no pages, no ring, no
kernels; it does not import ``dynamo_tpu``. The equations run over the whole
sequence, a window of queries at a time (and a block of a window's queries at
a time) so that the scores fit beside a resident engine:

    x0 = E[byte]                                    (float32 stream: fp32_skip_add)
    u  = rmsnorm(x) * (1 + w_1)                     (norm_add_unit_offset)
    q, k, v = W_q u, W_k u, W_v u;  q, k rotated at the absolute position (theta 100 000)
    chunk c = positions [16c, 16c + 16), in window floor(16c / W); per head, from its own 16:
        k~_c = sum_j softmax_j(mu . k_j) k_j
        v~_c = sum_j softmax_j(phi . k_j - |k_j|^2 / 2) v_j
    query n, window w(n) = floor(n / W), ONE softmax over both sets (s = d^-0.5):
        num = sum_{m <= n, w(m) = w(n)} exp(s q_n . k_m) v_m
            + sum_{c : window(c) < w(n)} exp(s q_n . k~_c) v~_c
        den = the same with every v replaced by 1;  o_n = num / den
    x = x + W_o o;  t = rmsnorm(x) * (1 + w_2);  x = x + W_down(silu(W_gate t) * W_up t)
    logits = W_head[:, head 0] (rmsnorm(x) * (1 + w_f))      (float32; head 0 of 8: the next byte)

DEPARTURES from the publication, each the configuration's (``reduced``,
``assumed``): the layers held are published layers ``0 .. num_hidden_layers -
1``; the two chunk softmaxes take their logits unscaled [line: _summaries];
``k~`` is the ``mu``-weighted sum [same]; rotary in the rotate-half layout
[_rotate]; prediction heads 1-7 are held and not run [_head].

It reads the served bf16 parameters and raises them to float32 a layer at a
time inside one jitted function called in a Python loop. Parameters, matrices
stored [in, out]: ``embed`` [vocab, hidden]; ``layers[i]``: ``attn_norm``,
``mlp_norm`` (the norms' ``w``: the weight is ``1 + w``), ``wq``, ``wk``,
``wv``, ``wo``, ``mu``, ``phi`` [heads, head_dim], ``w_gate``, ``w_up``,
``w_down``; ``final_norm``; ``lm_head`` [hidden, num_pred_heads x vocab],
head ``j``'s columns ``[j V, (j + 1) V)``.

THE SWITCHES compute a mistake each, to show that the tolerance fails it and
never to pass one (``wrong_variants`` names them): ``no_summary_denominator``
(the summaries add to ``num`` and not to ``den``), ``open_window_visible``
(the finished chunks of the query's OWN window are read as summaries too,
beside their exact keys), ``mean_key`` (``k~`` the chunk's plain mean),
``no_norm_term`` (``v~`` without ``- |k|^2 / 2``), ``stale_ring`` (the keys
of the window before, at the ring's entries the open window has not yet
written, read as this window's), ``summary_bits=8`` (summaries as a store
held at 8 bits would return them), ``chunk_softmax_bf16`` (the two chunk
softmaxes, logits and weights, in bf16: the precision below the
configuration's ``mixedp_attn``), ``skip_layer``. ``kv_bits=8`` is what
``run.py --calibrate`` passes for its second wrong computation: here it runs
EVERY switch above in turn and returns their readings by name.

TOLERANCE: the configuration's ``reference_tolerance`` (with what it was set
from): over the compared tokens the worst, the mean and the median of |engine
logprob - reference logprob|, every emitted token the reference's argmax or
within ``worst_nat`` of it, and, because a logprob does not tell a summary
held at 8 bits (or made by a bf16 softmax) from the honest engine's bf16
rounding, ``first_summary_rel`` on what the engine HOLDS when a sample ends:
the first layer's summary blocks of the sample's closed windows against the
reference's summaries (``held_differences``: relative norms a page of 16
summaries, the median over the sample's pages, the larger of keys and
values).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512          # queries a block of attention (memory, not a cache)
PAGE = 16              # summaries a page of the store (the 8-bit switch's group)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)   # norm_add_unit_offset


def _rotate(x, cos, sin):
    """x [T, heads, d]; cos/sin [T, 1, d/2]: rotate-half (assumed: the
    layout the checkpoints are stored for)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _fake_quant_int8(x):
    """What a store held at 8 bits would return: per (page of 16, head)
    symmetric int8."""
    T, h, d = x.shape
    xp = jnp.pad(x, ((0, (-T) % PAGE), (0, 0), (0, 0))).reshape(-1, PAGE, h, d)
    amax = jnp.max(jnp.abs(xp), axis=(1, 3), keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return (jnp.round(xp / scale).clip(-127, 127) * scale).reshape(-1, h, d)[:T]


def _summaries(k, v, mu, phi, C, *, mean_key, no_norm_term, chunk_softmax_bf16):
    """k, v [T, h, d] -> k~, v~ [T / C, h, d], each chunk from its own C
    keys and values. Both softmaxes take their logits UNSCALED (assumed;
    alternative: times d^-0.5) in float32 (mixedp_attn)."""
    T, h, d = k.shape
    kc, vc = k.reshape(T // C, C, h, d), v.reshape(T // C, C, h, d)
    dt = jnp.bfloat16 if chunk_softmax_bf16 else F32
    kl = kc.astype(dt)
    a = jnp.sum(kl * mu.astype(dt), axis=-1, dtype=dt)                 # [n, C, h]
    b = jnp.sum(kl * phi.astype(dt), axis=-1, dtype=dt)
    if not no_norm_term:
        b = b - (0.5 * jnp.sum(kl * kl, axis=-1, dtype=dt)).astype(dt)
    a = jax.nn.softmax(a, axis=1).astype(F32)
    b = jax.nn.softmax(b, axis=1).astype(F32)
    if mean_key:
        a = jnp.full_like(a, 1.0 / C)
    # k~ the mu-weighted sum (assumed; alternative: the chunk's mean + mu)
    return jnp.sum(a[..., None] * kc, axis=1), jnp.sum(b[..., None] * vc, axis=1)


@partial(jax.jit, static_argnames=(
    "n_heads", "head_dim", "eps", "W", "C", "no_summary_denominator", "open_window_visible",
    "mean_key", "no_norm_term", "stale_ring", "summary_bits", "chunk_softmax_bf16"))
def _layer(p, x, cos, sin, *, n_heads, head_dim, eps, W, C, no_summary_denominator=False,
           open_window_visible=False, mean_key=False, no_norm_term=False, stale_ring=False,
           summary_bits=None, chunk_softmax_bf16=False):
    """One layer over the whole (padded) sequence; also the summaries it
    made, [T / C, heads, head_dim] each."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda w: w.astype(F32), p)
        T = x.shape[0]
        u = _rms_norm(x, p["attn_norm"], eps)
        q = _rotate((u @ p["wq"]).reshape(T, n_heads, head_dim), cos, sin)
        k = _rotate((u @ p["wk"]).reshape(T, n_heads, head_dim), cos, sin)
        v = (u @ p["wv"]).reshape(T, n_heads, head_dim)
        ks, vs = _summaries(k, v, p["mu"], p["phi"], C, mean_key=mean_key,
                            no_norm_term=no_norm_term, chunk_softmax_bf16=chunk_softmax_bf16)
        if summary_bits == 8:
            ks, vs = _fake_quant_int8(ks), _fake_quant_int8(vs)
        made = (ks, vs)                                 # as a store would return them
        s = 1.0 / math.sqrt(head_dim)
        spw = W // C
        out = []
        for w0 in range(0, T, W):                       # a window of queries at a time
            w1 = min(w0 + W, T)
            kw, vw = k[w0:w1], v[w0:w1]
            if stale_ring and w0:
                # the ring as the window before left it: read below
                # wherever this window has not written yet
                kw = jnp.concatenate([kw, k[w0 - W:w0]])
                vw = jnp.concatenate([vw, v[w0 - W:w0]])
            n_closed = (w0 // W) * spw                  # summaries of the windows before
            n_sum = (w1 // C) if open_window_visible else n_closed
            for q0 in range(w0, w1, Q_BLOCK):
                q1 = min(q0 + Q_BLOCK, w1)
                n = jnp.arange(q0, q1)[:, None]
                seen = w0 + jnp.arange(w1 - w0)[None, :] <= n
                if stale_ring and w0:
                    # entry e of the ring holds position w0 + e if that is
                    # <= n, else the window before's: a stale ring reads both
                    seen = jnp.concatenate([seen, jnp.arange(W)[None, :] > n - w0], axis=1)
                e = jnp.einsum("qhd,khd->hqk", q[q0:q1], kw) * s
                e = jnp.where(seen[None], e, -jnp.inf)
                g = jnp.einsum("qhd,khd->hqk", q[q0:q1], ks[:n_sum]) * s
                if open_window_visible:
                    # a chunk of the open window counts once it is whole
                    c_end = (jnp.arange(n_sum)[None, :] + 1) * C
                    g = jnp.where((c_end <= n + 1)[None], g, -jnp.inf)
                top = jnp.maximum(jnp.max(e, axis=-1), jnp.max(g, axis=-1, initial=-jnp.inf))
                pe, pg = jnp.exp(e - top[..., None]), jnp.exp(g - top[..., None])
                num = (jnp.einsum("hqk,khd->qhd", pe, vw)
                       + jnp.einsum("hqk,khd->qhd", pg, vs[:n_sum]))
                den = jnp.sum(pe, axis=-1)
                if not no_summary_denominator:
                    den = den + jnp.sum(pg, axis=-1)
                out.append(num / den.T[..., None])
        o = jnp.concatenate(out).reshape(T, n_heads * head_dim)
        x = x + o @ p["wo"]
        t = _rms_norm(x, p["mlp_norm"], eps)
        x = x + (jax.nn.silu(t @ p["w_gate"]) * (t @ p["w_up"])) @ p["w_down"]
        return x, made


@partial(jax.jit, static_argnames=("eps", "vocab"))
def _head(final_norm, head, x, *, eps, vocab):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, final_norm.astype(F32), eps)
        # prediction head 0 of num_pred_heads: the next byte (assumed: the
        # served path samples it; heads 1-7 are held and not run)
        logits = x @ head[:, :vocab].astype(F32)
        return jax.nn.log_softmax(logits, axis=-1)


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, held_after: Optional[int] = None, **wrong):
    """Log-probabilities [len(rows), vocab] of the next byte after each
    position in ``rows``, from one full forward over ``token_ids``; with
    ``held_after`` = n also what a server that has taken the first ``n``
    tokens would hold of the FIRST layer's closed windows: ``ks``, ``vs``
    [closed windows x summaries a window, heads, head_dim].

    ``pad_to`` pads the sequence to a whole number of chunks (causal: a
    later position cannot touch an earlier one, nor a later chunk an earlier
    chunk's summary). ``skip_layer`` and ``wrong`` (the switches) exist to
    show that the tolerance fails a wrong computation, never to pass one."""
    head_dim = int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])
    W, C = int(cfg["window_size"]), int(cfg["chunk_size"])
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    T = max(len(ids), pad_to or 0)
    T = -(-T // C) * C
    ids = np.concatenate([ids, np.zeros(T - len(ids), np.int32)])
    pos = np.arange(T, dtype=np.float32)
    inv = 1.0 / (float(cfg["rope_theta"]) ** (np.arange(0, head_dim // 2, dtype=np.float32) / (head_dim // 2)))
    ang = pos[:, None] * inv[None, :]
    cos, sin = put(np.cos(ang)[:, None, :]), put(np.sin(ang)[:, None, :])
    x = put(params["embed"])[put(ids)].astype(F32)
    would_hold = None
    for i, lp in enumerate(params["layers"]):
        if i == skip_layer:
            continue
        x, made = _layer(put(lp), x, cos, sin, n_heads=cfg["num_attention_heads"],
                         head_dim=head_dim, eps=float(cfg["rms_norm_eps"]), W=W, C=C, **wrong)
        if would_hold is None and held_after is not None:
            n = (held_after // W) * (W // C)
            would_hold = {"ks": made[0][:n], "vs": made[1][:n]}
    out = _head(put(params["final_norm"]), put(params["lm_head"]), x[np.asarray(rows)],
                eps=float(cfg["rms_norm_eps"]), vocab=int(cfg["vocab_size"]))
    return np.asarray(out), would_hold


# ---------------------------------------------------------------------------
# what the server holds against what the reference would hold
# ---------------------------------------------------------------------------


@jax.jit
def _nearest_pages(a, pool):
    """The page of ``pool`` [pages, page, heads, d] nearest to each page of
    a [n, page, heads, d]."""
    a, b = a.reshape(a.shape[0], -1), pool.astype(F32).reshape(pool.shape[0], -1)
    d = jnp.sum(a * a, axis=1)[:, None] + jnp.sum(b * b, axis=1)[None] - 2 * a @ b.T
    return jnp.argmin(d, axis=1)


@jax.jit
def _page_differences(a, pool, ids):
    d = pool[ids].astype(F32) - a
    return jnp.sqrt(jnp.sum(d * d, axis=(1, 2, 3)) / jnp.sum(a * a, axis=(1, 2, 3)))


def held_differences(ref: Dict[str, Any], held: Dict[str, Any]) -> Optional[np.ndarray]:
    """How far the first layer's summary blocks the server HOLDS for a sample
    lie from the reference's summaries of its closed windows (``ref``:
    ``logprobs(held_after=...)``'s), as relative norms a page of summaries:
    [2, pages] for keys and values. ``held``: ``k``, ``v`` the pools a layer
    [pages, page, heads, head_dim] and ``summary_base``, the first page of
    the summary blocks. The sample's pages are found by content (the nearest
    page to each of the reference's, by the summary keys); None where the
    sample closed no window."""
    if not ref["ks"].shape[0]:
        return None
    base = int(held["summary_base"])
    kp, vp = held["k"][0][base:], held["v"][0][base:]
    size = kp.shape[1]
    a = ref["ks"].reshape(-1, size, *ref["ks"].shape[1:])
    b = ref["vs"].reshape(a.shape)
    ids = _nearest_pages(a, kp)
    return np.stack([np.asarray(_page_differences(a, kp, ids)),
                     np.asarray(_page_differences(b, vp, ids))])


def wrong_variants(cfg: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Every mistake the switches compute, by name (``skip_layer`` is the
    harness's own first slot)."""
    out: Dict[str, Dict[str, Any]] = {"summary_int8": {"summary_bits": 8}}
    for name in ("no_summary_denominator", "open_window_visible", "mean_key", "no_norm_term",
                 "stale_ring", "chunk_softmax_bf16"):
        out[name] = {name: True}
    return out


# each limit of ``reference_tolerance`` and the readings it bounds
LIMITS = (
    ("worst_nat", "worst_tolerance_nat", ("worst_logprob_difference_nat", "worst_argmax_gap_nat")),
    ("mean_nat", "mean_tolerance_nat", ("mean_logprob_difference_nat",)),
    ("median_nat", "median_tolerance_nat", ("median_logprob_difference_nat",)),
    ("first_summary_rel", "first_summary_tolerance_rel", ("first_layer_summary_difference",)),
)


def compare(cfg: Dict[str, Any], params: Dict[str, Any],
            samples: List[Dict[str, Any]], pad_to: int, device=None,
            kv_bits: Optional[int] = None, **wrong) -> Dict[str, Any]:
    """Hold the engine's greedy continuations, and the summaries it holds
    for them when they end, to the reference.

    ``samples``: ``{"prompt": [...], "tokens": [...], "logprobs": [...]}`` as
    the engine emitted them; ``params["held"]`` (the adapter's): the engine's
    pools as they stand after the samples. A request that emitted ``m``
    tokens has taken its prompt and the first ``m - 1``. Returns the worst
    differences and ``ok``."""
    if kv_bits is not None:
        return {name: compare(cfg, params, samples, pad_to, device, **sw)
                for name, sw in wrong_variants(cfg).items()}
    held = params.get("held")
    worst_gap = 0.0
    diffs: List[float] = []
    pages: List[np.ndarray] = []
    for s in samples:
        P, emitted = len(s["prompt"]), list(s["tokens"])
        if not emitted or len(s["logprobs"]) != len(emitted):
            return {"ok": False, "reason": "a sample has no tokens or no logprobs",
                    "tokens_compared": len(diffs)}
        seq = list(s["prompt"]) + emitted
        rows = [P - 1 + j for j in range(len(emitted))]
        ref, would_hold = logprobs(cfg, params, seq, rows, pad_to=pad_to, device=device,
                                   held_after=len(seq) - 1, **wrong)
        for j, tok in enumerate(emitted):
            diffs.append(abs(float(ref[j, tok]) - float(s["logprobs"][j])))
            worst_gap = max(worst_gap, float(ref[j].max()) - float(ref[j, tok]))
        if held is not None:
            d = held_differences(would_hold, held)
            if d is not None:
                pages.append(d)
    tol = cfg["reference_tolerance"]
    res: Dict[str, Any] = {
        "tokens_compared": len(diffs),
        "worst_logprob_difference_nat": max(diffs, default=0.0),
        "worst_argmax_gap_nat": worst_gap,
        "mean_logprob_difference_nat": float(np.mean(diffs)) if diffs else 0.0,
        "median_logprob_difference_nat": float(np.median(diffs)) if diffs else 0.0,
    }
    if pages:
        # the median over a sample's pages (a page an earlier finisher freed
        # may be another request's by now), the worst of the samples, keys
        # and values apart
        by_kind = np.max([np.median(d, axis=1) for d in pages], axis=0)
        res.update({
            "summary_pages_compared": int(sum(d.shape[1] for d in pages)),
            "first_layer_summary_key_difference": float(by_kind[0]),
            "first_layer_summary_value_difference": float(by_kind[1]),
            "first_layer_summary_difference": float(by_kind.max()),
        })
    ok = True
    for limit, shown_as, readings in LIMITS:
        if limit not in tol:
            continue
        res[shown_as] = tol[limit]
        # a limit whose reading is missing (nothing held was handed over) fails
        ok = ok and all(res.get(r, math.inf) <= tol[limit] for r in readings)
    return {"ok": bool(ok), **res}
