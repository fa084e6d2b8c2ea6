"""Plain reference: Command A+'s decoder (``model_type`` ``cohere2_moe``): a
PARALLEL block under one mean-centred LayerNorm, three sliding-window layers
with rotary positions to one full layer WITHOUT positions, a sigmoid-routed
feed-forward beside shared experts whose outputs are averaged.

Written from the published configuration and the layer as ISSUE 49 wrote it
down, in plain ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No cache, no pages, no page
groups, no kernels; it does not import ``dynamo_tpu``. The equations run over
the whole sequence, a kv head and a block of queries (and a block of tokens,
an expert) at a time so that 12k tokens at the published widths fit beside a
resident engine (W = ``sliding_window``, d = ``head_dim``, s = d^-0.5, head h
reads kv head floor(h / (heads / kv heads))):

    u = g * (x - mean(x)) / sqrt(var(x) + eps)            (ONE norm a layer, no bias)
    q, k, v = W_q u, W_k u, W_v u                          (no bias, no q/k norm)
    sliding layer: q, k rotated at the absolute position, pairs (2i, 2i + 1)
                   (rope_gptj: interleaved); query n sees keys n - W < m <= n
    full layer:    no rotation of any kind; query n sees every m <= n
    a = W_o softmax_m(s q . k) v                           (softmax in float32)
    p = sigmoid(W_r u) in R^router_outputs; T = its top k; w_e = p_e / sum_T p
    r = sum_{e in T, e held} w_e E_e(u),  E_e(u) = W_down^e (silu(W_gate^e u) * W_up^e u)
    sh = (1 / n_shared) sum_j S_j(u)                       (``average``)
    x <- x + a + r + sh                                    (one residual sum)
    logits = logit_scale * E^T LN_final(x)                 (tied, over the slice held)

DEPARTURES from the publication, each the configuration's (``reduced``,
``assumed``): the layers run are published layers ``0 .. num_hidden_layers -
1``; of the ``router_outputs`` experts a token may choose, this chip computes
the ``num_experts`` it holds from ``experts_held_first`` [line: _experts,
``held``] and what the absent ones would add is left out; the vocabulary is
the slice held [_head]; the vision tower is left out.

It reads the served bf16 parameters and raises them to float32 a piece at a
time. Parameters, matrices stored [in, out]: ``embed`` [vocab, hidden];
``layers[i]``: ``norm``, ``wq``, ``wk``, ``wv``, ``wo``, ``w_router`` [hidden,
router_outputs], ``w_egate``, ``w_eup`` [held, hidden, width], ``w_edown``
[held, width, hidden], ``w_shared_gate``, ``w_shared_up`` [hidden, n_shared x
width] (expert j's columns ``[j width, (j + 1) width)``), ``w_shared_down``
[n_shared x width, hidden]; ``final_norm``. ``wq`` / ``wk`` come in the
PUBLISHED layout, a head's rotary pairs at ``(2i, 2i + 1)`` (the adapter
hands the inverse of the permutation the engine loads them with).

THE SWITCHES compute a mistake each, to show that the tolerance fails it and
never to pass one (``wrong_variants`` names them): ``ignore_window`` (sliding
layers see every causal key), ``stale_page`` (a window one 16-token page too
long: a page behind the window read), ``rope_on_full`` (the full layers
rotated too), ``rotate_half`` (first-half / second-half pairs on weights in
the interleaved layout), ``rms_norm`` (the mean not removed), ``sequential``
(the feed-forward reads ``LN(x + a)``), ``shared_sum`` (the shared sum
unscaled), ``no_shared`` (the shared branch dropped), ``no_renorm`` (top-k
weights not normalised), ``softmax_router``, ``cache_bits=8`` (keys and
values as pages held at 8 bits would return them), ``skip_layer``.
``kv_bits=8`` is what ``run.py --calibrate`` passes for its second wrong
computation: here it runs EVERY switch above in turn and returns their
readings by name.

TOLERANCE: the configuration's ``reference_tolerance`` (with what it was set
from): over the compared tokens the worst, the mean and the median of |engine
logprob - reference logprob|, every emitted token the reference's argmax or
within ``worst_nat`` of it, and, because a logprob does not tell pages held
at 8 bits from the honest engine's bf16 rounding, ``first_cache_rel`` on what
the engine HOLDS when a sample ends: the FIRST layer's pages (a sliding
layer: the windowed group's pool) of the sample's last window against the
reference's keys and values (``held_differences``). Nor does a logprob LIMIT
tell a window one page too long from the honest engine's rounding (16 keys of
4 096 move a logprob by less than bf16 does, and the honest median differs
more between seeds than that), so the comparison also reads WHERE the engine's
logprobs lie: the reference is run again with its window a page shorter and a
page longer, and ``window_edge_lean`` bounds how far the engine's logprobs
lean from the window as stated toward either neighbour: the least-squares
``b`` in ``engine - ref = b (neighbour - ref)`` over the compared tokens
whose difference, and whose distance between the two references, are within
``LEAN_TRIM`` x their medians (a token whose 8th and 9th expert swap, under
bf16 or between the two windows, differs by tenths of a nat and would decide
the sums alone), 0 for an engine at the stated window whatever its rounding (which
does not follow the neighbour's direction), 1 for one that reads the
neighbour's window. It costs two more forwards, made only where every other
limit holds.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 64           # queries a block of attention (memory, not a cache)
TOKEN_BLOCK = 256      # tokens a block of the feed-forward
PAGE = 16              # tokens a page (the 8-bit switch's group, a stale page)
VOCAB_SLICE = 8192     # output-head columns raised to float32 at a time
LEAN_TRIM = 4.0        # x the median difference: the tokens the lean is fitted over


@partial(jax.jit, static_argnames=("eps", "rms"))
def _norm(x, w, eps, rms):
    if not rms:
        x = x - jnp.mean(x, axis=-1, keepdims=True)        # mean-centred: LayerNorm
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w                # a weight and NO bias


def _rotate(x, cos, sin, half_pairs):
    """x [T, heads, d]; cos/sin [T, 1, d/2]. Published: pairs (2i, 2i + 1)."""
    if half_pairs:                                         # the mistake: rotate-half
        h = x.shape[-1] // 2
        a, b = x[..., :h], x[..., h:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _fake_quant_int8(x):
    """What pages held at 8 bits would return: per (16-token page, head)
    symmetric int8. Used only to show that the tolerance tells it apart."""
    T, h, d = x.shape
    xp = x.reshape(-1, PAGE, h, d)
    amax = jnp.max(jnp.abs(xp), axis=(1, 3), keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return (jnp.round(xp / scale).clip(-127, 127) * scale).reshape(T, h, d)


@partial(jax.jit, static_argnames=("n_kv", "head_dim", "rotated", "half_pairs", "cache_bits"))
def _keys_values(p, u, cos, sin, *, n_kv, head_dim, rotated, half_pairs, cache_bits):
    with jax.default_matmul_precision("highest"):
        T = u.shape[0]
        k = (u @ p["wk"].astype(F32)).reshape(T, n_kv, head_dim)
        v = (u @ p["wv"].astype(F32)).reshape(T, n_kv, head_dim)
        if rotated:
            k = _rotate(k, cos, sin, half_pairs)
        if cache_bits == 8:
            k, v = _fake_quant_int8(k), _fake_quant_int8(v)
        return k, v


@partial(jax.jit, donate_argnums=(0,),
         static_argnames=("group", "head_dim", "window", "rotated", "half_pairs"))
def _attend_head(out, wq, wo, u, k, v, cos, sin, *, group, head_dim, window, rotated, half_pairs):
    """ONE kv head's ``group`` query heads, a block of queries at a time,
    ADDED to ``out`` [T, hidden] (the residual sum, built in place so that
    the reference fits beside a resident engine): u [T, hidden], k, v [T, d]."""
    with jax.default_matmul_precision("highest"):
        T, H = u.shape
        wq, wo = wq.astype(F32), wo.astype(F32)
        key_pos = jnp.arange(T)

        def block(b, out):
            at = b * Q_BLOCK
            q = (jax.lax.dynamic_slice_in_dim(u, at, Q_BLOCK) @ wq).reshape(Q_BLOCK, group, head_dim)
            if rotated:
                q = _rotate(q, jax.lax.dynamic_slice_in_dim(cos, at, Q_BLOCK),
                            jax.lax.dynamic_slice_in_dim(sin, at, Q_BLOCK), half_pairs)
            q_pos = at + jnp.arange(Q_BLOCK)
            scores = jnp.einsum("qgd,td->gqt", q, k) / math.sqrt(head_dim)
            seen = key_pos[None, :] <= q_pos[:, None]      # causal
            if window is not None:                         # n - W < m: W keys, its own among them
                seen = seen & (key_pos[None, :] > q_pos[:, None] - window)
            scores = jnp.where(seen[None], scores, -jnp.inf)
            o = jnp.einsum("gqt,td->qgd", jax.nn.softmax(scores, axis=-1), v)
            add = o.reshape(Q_BLOCK, group * head_dim) @ wo
            return jax.lax.dynamic_update_slice_in_dim(
                out, jax.lax.dynamic_slice_in_dim(out, at, Q_BLOCK) + add, at, 0)

        return jax.lax.fori_loop(0, T // Q_BLOCK, block, out)


@partial(jax.jit, donate_argnums=(0,),
         static_argnames=("top_k", "first", "renorm", "softmax_router", "n_shared", "shared_scale"))
def _experts(out, p, u, *, top_k, first, renorm, softmax_router, n_shared, shared_scale):
    """r + sh of the same ``u`` [T, hidden], an expert and a block of tokens
    at a time, ADDED to ``out`` in place."""
    with jax.default_matmul_precision("highest"):
        T, H = u.shape
        held = p["w_egate"].shape[0]
        logits = u @ p["w_router"].astype(F32)
        probs = jax.nn.softmax(logits, axis=-1) if softmax_router else jax.nn.sigmoid(logits)
        top_w, top_i = jax.lax.top_k(probs, top_k)          # over ALL router outputs
        if renorm:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)   # over all top_k chosen
        E = probs.shape[-1]
        weight = jnp.sum(jax.nn.one_hot(top_i, E, dtype=F32) * top_w[..., None], axis=1)
        weight = jax.lax.dynamic_slice_in_dim(weight, first, held, axis=1)   # held: this chip's
        blocks = T // TOKEN_BLOCK

        def swiglu(h, wg, wu, wd):
            return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd

        def add_blocks(out, scale_of, wg, wu, wd):
            """out += scale_of(block) * swiglu(u block), every block of tokens."""
            def one_block(b, out):
                at = b * TOKEN_BLOCK
                add = scale_of(at) * swiglu(jax.lax.dynamic_slice_in_dim(u, at, TOKEN_BLOCK), wg, wu, wd)
                return jax.lax.dynamic_update_slice_in_dim(
                    out, jax.lax.dynamic_slice_in_dim(out, at, TOKEN_BLOCK) + add, at, 0)
            return jax.lax.fori_loop(0, blocks, one_block, out)

        def one(out, e):  # every held expert, one at a time, applied to every token
            wg, wu, wd = (p[n][e].astype(F32) for n in ("w_egate", "w_eup", "w_edown"))
            w_e = jax.lax.dynamic_index_in_dim(weight, e, axis=1, keepdims=True)
            return add_blocks(
                out, lambda at: jax.lax.dynamic_slice_in_dim(w_e, at, TOKEN_BLOCK), wg, wu, wd), None

        out, _ = jax.lax.scan(one, out, jnp.arange(held))
        if n_shared:
            width = p["w_shared_gate"].shape[1] // n_shared

            def shared(out, j):  # the shared experts, one at a time, every token
                wg = jax.lax.dynamic_slice_in_dim(p["w_shared_gate"], j * width, width, 1).astype(F32)
                wu = jax.lax.dynamic_slice_in_dim(p["w_shared_up"], j * width, width, 1).astype(F32)
                wd = jax.lax.dynamic_slice_in_dim(p["w_shared_down"], j * width, width, 0).astype(F32)
                return add_blocks(out, lambda at: shared_scale, wg, wu, wd), None

            out, _ = jax.lax.scan(shared, out, jnp.arange(n_shared))
        return out


@partial(jax.jit, static_argnames=("eps", "cols", "scale", "rms"))
def _head(final_norm, embed, x, *, eps, cols, scale, rms):
    with jax.default_matmul_precision("highest"):
        x = _norm(x, final_norm.astype(F32), eps, rms)
        V = embed.shape[0]

        def part(c):  # tied: the embedding's rows are the head's columns
            w = jax.lax.dynamic_slice_in_dim(embed, c * cols, cols, axis=0).astype(F32)
            return x @ w.T

        logits = jax.lax.map(part, jnp.arange(V // cols))
        logits = scale * jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], V)
        return jax.nn.log_softmax(logits, axis=-1)


def _layer(cfg, lp, x, cos, sin, put, *, sliding, sw):
    """One parallel block over the whole sequence."""
    n_heads, n_kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    head_dim, group = int(cfg["head_dim"]), n_heads // n_kv
    eps, rms = float(cfg["layer_norm_eps"]), bool(sw.get("rms_norm"))
    norm = partial(_norm, eps=eps, rms=rms)
    u = norm(x, put(lp["norm"]).astype(F32))
    rotated = sliding or bool(sw.get("rope_on_full"))
    half_pairs = bool(sw.get("rotate_half"))
    window = None
    if sliding and not sw.get("ignore_window"):
        window = (int(cfg["sliding_window"]) + (PAGE if sw.get("stale_page") else 0)
                  + int(sw.get("window_shift", 0)))
    k, v = _keys_values({n: put(lp[n]) for n in ("wk", "wv")}, u, cos, sin, n_kv=n_kv,
                        head_dim=head_dim, rotated=rotated, half_pairs=half_pairs,
                        cache_bits=sw.get("cache_bits"))
    out = x                                                # x + a + f, built in place
    for j in range(n_kv):                                  # a kv head at a time
        cols = slice(j * group * head_dim, (j + 1) * group * head_dim)
        out = _attend_head(out, put(lp["wq"][:, cols]), put(lp["wo"][cols]), u, k[:, j], v[:, j],
                           cos, sin, group=group, head_dim=head_dim, window=window,
                           rotated=rotated, half_pairs=half_pairs)
    if sw.get("sequential"):                               # the mistake: FFN reads LN(x + a)
        u = norm(out, put(lp["norm"]).astype(F32))
    n_shared = 0 if sw.get("no_shared") else int(cfg["num_shared_experts"])
    average = cfg["shared_expert_combination_strategy"] == "average" and not sw.get("shared_sum")
    out = _experts(
        out, {n: put(lp[n]) for n in ("w_router", "w_egate", "w_eup", "w_edown", "w_shared_gate",
                                      "w_shared_up", "w_shared_down")}, u,
        top_k=int(cfg["num_experts_per_tok"]), first=int(cfg.get("experts_held_first", 0)),
        renorm=bool(cfg["norm_topk_prob"]) and not sw.get("no_renorm"),
        softmax_router=bool(sw.get("softmax_router")), n_shared=n_shared,
        shared_scale=1.0 / max(n_shared, 1) if average else 1.0,
    )
    return out, (k, v)                                     # ONE residual sum of both branches


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, held_after: Optional[int] = None, **sw):
    """Log-probabilities [len(rows), vocab] of the next token after each
    position in ``rows``, from one full forward over ``token_ids``; with
    ``held_after`` = n also what a server that has taken the first ``n``
    tokens would hold of the FIRST layer's last window: ``k``, ``v`` [from
    ``first`` to n, kv heads, d] (keys rotated, in the published pair
    layout).

    ``pad_to`` pads the sequence (causal: a later position cannot touch an
    earlier one) so that every prompt compiles the same shapes; the length is
    then rounded up to whole blocks. ``skip_layer`` and ``sw`` (the switches)
    exist to show that the tolerance fails a wrong computation, never to
    pass one."""
    head_dim = int(cfg["head_dim"])
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    T = max(len(ids), pad_to or 0)
    T = -(-T // TOKEN_BLOCK) * TOKEN_BLOCK
    ids = np.concatenate([ids, np.zeros(T - len(ids), np.int32)])
    inv = 1.0 / (float(cfg["rope_theta"]) ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = put(np.cos(ang)[:, None, :].astype(np.float32))
    sin = put(np.sin(ang)[:, None, :].astype(np.float32))
    x = put(params["embed"])[put(ids)].astype(F32)
    would_hold = None
    for i, lp in enumerate(params["layers"][: cfg["num_hidden_layers"]]):
        if i == skip_layer:
            continue
        x, (k, v) = _layer(cfg, lp, x, cos, sin, put, sw=sw,
                           sliding=cfg["layer_types"][i] == "sliding_attention")
        if would_hold is None and held_after is not None:
            # its last window and a page: what ``held_differences`` reads
            lo = max(0, (held_after - int(cfg["sliding_window"])) // PAGE) * PAGE
            would_hold = {"k": k[lo:held_after], "v": v[lo:held_after], "first": lo,
                          "tokens": held_after}
    out = _head(put(params["final_norm"]), put(params["embed"]), x[np.asarray(rows)],
                eps=float(cfg["layer_norm_eps"]), scale=float(cfg["logit_scale"]),
                rms=bool(sw.get("rms_norm")),
                cols=next(c for c in range(min(int(cfg["vocab_size"]), VOCAB_SLICE), 0, -1)
                          if int(cfg["vocab_size"]) % c == 0))
    return np.asarray(out), would_hold


# ---------------------------------------------------------------------------
# what the server holds against what the reference would hold
# ---------------------------------------------------------------------------


@jax.jit
def _nearest_pages(a, pool):
    """The page of ``pool`` [pages, page, heads, d] nearest to each page of
    a [n, page, heads, d], by the values of each page's first token."""
    a, b = a[:, 0].reshape(a.shape[0], -1), pool[:, 0].astype(F32).reshape(pool.shape[0], -1)
    d = jnp.sum(a * a, axis=1)[:, None] + jnp.sum(b * b, axis=1)[None] - 2 * a @ b.T
    return jnp.argmin(d, axis=1)


@jax.jit
def _page_differences(a, pool, ids):
    d = pool[ids].astype(F32) - a
    return jnp.sqrt(jnp.sum(d * d, axis=(1, 2, 3)) / jnp.sum(a * a, axis=(1, 2, 3)))


def held_differences(cfg: Dict[str, Any], ref: Dict[str, Any], held: Dict[str, Any]) -> Optional[np.ndarray]:
    """How far the FIRST layer's pages the server HOLDS for a sample lie from
    what the reference would cache (``logprobs(held_after=...)``'s), as
    relative norms a full page of the sample's LAST window (a sliding
    layer's older pages were let go, and may be another request's by now):
    [2, pages] for keys and values. ``held``: ``k``, ``v`` the pools a layer
    [pages, page, kv heads, head_dim]. The server rotates first-half /
    second-half pairs on weights de-interleaved a head, so its key lane ``i``
    (``d/2 + i``) is the published lane ``2i`` (``2i + 1``). The sample's
    pages are found by content (the values of a page's first token)."""
    kp, vp = held["k"][0], held["v"][0]
    size = kp.shape[1]
    n, first = int(ref["tokens"]), int(ref["first"])
    lo = max(0, (n - int(cfg["sliding_window"])) // size + 1)
    hi = n // size
    if hi <= lo:
        return None
    k = ref["k"][lo * size - first : hi * size - first]
    k = jnp.concatenate([k[..., 0::2], k[..., 1::2]], axis=-1)      # published -> served lanes
    a = k.reshape(hi - lo, size, *k.shape[1:])
    b = ref["v"][lo * size - first : hi * size - first].reshape(a.shape)
    ids = _nearest_pages(b, vp)
    return np.stack([np.asarray(_page_differences(a, kp, ids)),
                     np.asarray(_page_differences(b, vp, ids))])


def wrong_variants(cfg: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Every mistake the switches compute, by name (``skip_layer`` is the
    harness's own first slot)."""
    out: Dict[str, Dict[str, Any]] = {"cache_int8": {"cache_bits": 8}}
    for name in ("ignore_window", "stale_page", "rope_on_full", "rotate_half", "rms_norm",
                 "sequential", "shared_sum", "no_shared", "no_renorm", "softmax_router"):
        out[name] = {name: True}
    return out


# each limit of ``reference_tolerance`` and the readings it bounds
LIMITS = (
    ("worst_nat", "worst_tolerance_nat", ("worst_logprob_difference_nat", "worst_argmax_gap_nat")),
    ("mean_nat", "mean_tolerance_nat", ("mean_logprob_difference_nat",)),
    ("median_nat", "median_tolerance_nat", ("median_logprob_difference_nat",)),
    ("first_cache_rel", "first_cache_tolerance_rel", ("first_layer_cache_difference",)),
)


def compare(cfg: Dict[str, Any], params: Dict[str, Any],
            samples: List[Dict[str, Any]], pad_to: int, device=None,
            kv_bits: Optional[int] = None, **wrong) -> Dict[str, Any]:
    """Hold the engine's greedy continuations, and the pages it holds for
    them when they end, to the reference.

    ``samples``: ``{"prompt": [...], "tokens": [...], "logprobs": [...]}`` as
    the engine emitted them; ``params["held"]`` (the adapter's): the engine's
    pools as they stand after the samples. A request that emitted ``m``
    tokens has taken its prompt and the first ``m - 1``. Returns the worst
    differences and ``ok``."""
    if kv_bits is not None:
        return {name: compare(cfg, params, samples, pad_to, device, **sw)
                for name, sw in wrong_variants(cfg).items()}
    wrong = dict(wrong)
    keep = wrong.pop("_keep", False)
    held = params.get("held")
    worst_gap = 0.0
    diffs: List[float] = []
    signed: List[float] = []       # engine - reference, and the reference's own,
    at_token: List[float] = []     # at each emitted token
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
            at_token.append(float(ref[j, tok]))
            signed.append(float(s["logprobs"][j]) - at_token[-1])
            diffs.append(abs(signed[-1]))
            worst_gap = max(worst_gap, float(ref[j].max()) - float(ref[j, tok]))
        if held is not None and would_hold is not None:
            d = held_differences(cfg, would_hold, held)
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
    res["_signed"], res["_ref"] = signed, at_token
    if pages:
        # the median over a sample's pages (a page an earlier finisher freed
        # may be another request's by now), the worst of the samples, keys
        # and values apart
        by_kind = np.max([np.median(d, axis=1) for d in pages], axis=0)
        res.update({
            "cache_pages_compared": int(sum(d.shape[1] for d in pages)),
            "first_layer_key_difference": float(by_kind[0]),
            "first_layer_value_difference": float(by_kind[1]),
            "first_layer_cache_difference": float(by_kind.max()),
        })
    ok = True
    for limit, shown_as, readings in LIMITS:
        if limit not in tol:
            continue
        res[shown_as] = tol[limit]
        # a limit whose reading is missing (nothing held was handed over) fails
        ok = ok and all(res.get(r, math.inf) <= tol[limit] for r in readings)
    off, at = np.asarray(res.pop("_signed")), np.asarray(res.pop("_ref"))
    if ok and "window_edge_lean" in tol and "window_shift" not in wrong:
        # a page shorter, a page longer: the logprobs alone, nothing held
        lean, medians = [], []
        for shift in (-PAGE, PAGE):
            other = compare({**cfg, "reference_tolerance": {}}, {**params, "held": None}, samples,
                            pad_to, device, window_shift=shift, _keep=True, **wrong)
            toward = np.asarray(other["_ref"]) - at
            # neither the engine's routing flips nor the REFERENCE's own between
            # the two windows (either decides the sums alone, and the trim on
            # the engine's side keeps only the flips that side with one window)
            fit = ((np.abs(off) <= LEAN_TRIM * max(res["median_logprob_difference_nat"], 1e-12))
                   & (np.abs(toward) <= LEAN_TRIM * max(float(np.median(np.abs(toward))), 1e-12)))
            fit |= ~np.any(fit)        # nothing within the trim (an exact engine): every token
            lean.append(float(np.sum((off * toward)[fit])
                              / max(np.sum((toward * toward)[fit]), 1e-30)))
            medians.append(other["median_logprob_difference_nat"])
        res.update({
            "lean_to_a_page_shorter": lean[0], "lean_to_a_page_longer": lean[1],
            "median_a_page_shorter_nat": medians[0], "median_a_page_longer_nat": medians[1],
            "window_edge_lean": max(lean), "window_edge_tolerance_lean": tol["window_edge_lean"],
        })
        ok = res["window_edge_lean"] <= tol["window_edge_lean"]
    if keep:
        res["_ref"] = at
    return {"ok": bool(ok), **res}
