"""Plain reference: MiniCPM-SALA's decoder (``model_type`` ``minicpm_sala``), a
hybrid of LAYER KINDS: one layer in four (``mixer_types`` ``minicpm4``) is
softmax grouped-query attention that, past ``dense_len`` keys, attends over
the blocks of keys each query CHOOSES, a kv head, from pooled keys (InfLLM-v2,
MiniCPM4 arXiv 2506.07900); the others (``lightning-attn``) are linear
attention with a fixed decay a head (Lightning Attention, arXiv 2401.04658);
every feed-forward is the dense SwiGLU; muP scalings on the embedding, every
residual branch and the head.

Written from the published configuration and the layers as ISSUE 54 wrote
them down, in plain ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No cache, no pages, no chunks,
no kernels; it does not import ``dynamo_tpu``. THE RECURRENCE IS THE
DEFINITION of a lightning layer, a ``lax.scan`` over tokens (not the blocked
matmul form the program prefills with); the selection is computed for every
query from pooled keys made of the sequence's own keys (a strided window, not
pairs of pages), its max-pool a ``reduce_window``, its top-k a rank by
``argsort``; attention runs a block of queries at a time (memory, not a cache).

    h0 = scale_emb * E[tok];   c = scale_depth / sqrt(mup_denominator)      (the PUBLISHED depth)
    u  = rmsnorm(h; in_norm)
    sparse: q, k, v = W_q u, W_k u, W_v u;  q_h = rmsnorm(q_h; q_norm), k_g = rmsnorm(k_g; k_norm)   (no positions)
        query t, n = t + 1 keys.  n <= dense_len: causal softmax over every key (scale d^-0.5)
        else kc_j = mean(k[16 j .. 16 j + 31]) for 16 j + 32 <= n;  p_h = softmax_j(q_h . kc_j d^-0.5)
             S_g = sum of p_h over the group's heads;  B_g[b] = max(S_g[4b-1 .. 4b+3])
             forced: block 0 and the window/block + 1 blocks ending at floor(t / block)
             chosen = forced + the topk best of the others;  one causal softmax over the chosen blocks' keys
        o = W_o (a * sigmoid(W_og u))
    lightning: q_h, k_h = rope(rmsnorm(.; q_norm / k_norm)), v_h;  S_t = lambda_h S_{t-1} + k_t^T v_t  (float32)
        y_t = d^-0.5 q_t S_t;  lambda_h = exp(-2^(-8 (h + 1) / H))
        o = W_o (rmsnorm_by_head(y; o_norm) * sigmoid(W_og u))
    h += c o;   h += c SwiGLU(rmsnorm(h; ff_norm))
    logits = W_head (rmsnorm(h; final_norm) / (hidden_size / dim_model_base))

DEPARTURES from the publication, each the configuration's (``reduced``,
``assumed``): the layers held are ``num_hidden_layers`` published layers from
``first_layer_run`` on; every ``assumed`` is computed as stated there.

Parameters (the served bf16 ones, raised to float32 inside jitted functions),
matrices [in, out]: ``embed``; ``layers[i]``: ``in_norm``, ``ff_norm``,
``w_gate``, ``w_up``, ``w_down``; ``wq``, ``wk``, ``wv``, ``wo``, ``w_ogate``,
``q_norm``, ``k_norm`` [head_dim]; a lightning layer also ``o_norm``;
``final_norm``; ``lm_head``.

THE SWITCHES compute a mistake each, to show that the tolerance fails it and
never to pass one: ``dense_attention`` (every causal key instead of the
selection), ``pool_kernel=16`` (pooled keys over 16 keys, not 32),
``forced_inside_topk`` (the forced blocks count inside the ``topk``),
``depth_held`` (``scale_depth / sqrt(layers held)``), ``state_bits=16`` (the
matrix state rounded to bf16 after every token: the precision below the
float32 ``assumed`` states), ``no_decay`` (lambda = 1), ``cache_bits=8``
(keys and values as an 8-bit cache would return them: the precision below the
configuration's bf16), ``skip_layer``. ``kv_bits=8`` is what ``run.py
--calibrate`` passes for its second wrong computation: here it runs EVERY
switch above in turn and returns their readings by name.

TOLERANCE: the configuration's ``reference_tolerance`` (with what it was set
from): over the compared tokens the worst, mean and median of |engine logprob
- reference logprob| and every emitted token the reference's argmax or within
``worst_nat`` of it; and, because a logprob with random weights barely tells
WHICH ten thousand keys a softmax ran over, what the engine HOLDS when a
sample ends against what the reference would hold: the first sparse layer's
pages (``first_cache_rel``), ITS POOLED KEYS as the engine keeps them by block
id (``pooled_key_rel``), THE BLOCKS CHOSEN for the last fed token from what
the engine holds (its pooled keys, the query rounded to bf16) against the
reference's own choice (``block_overlap_min``, a Jaccard share, the worst kv
head: a near-tie that flips between bf16 and float32 shows here and is
measured, the configuration says how often), the first lightning layer's
slot state by norm (``state_rel``) and the precision both states are kept at
(``state_precision_gap``).
"""

from __future__ import annotations

import math
from functools import partial, wraps
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _highest(fn):
    """Run ``fn`` (and trace what it jits) at the highest matmul precision:
    a context, never the process's default, which the engine's own programs
    are traced under."""
    @wraps(fn)
    def run(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return run

F32 = jnp.float32
NEG = -1e30
QUERY_BLOCK = 256
SIZES = ("kernel_size", "kernel_stride", "block_size", "topk", "init_blocks", "window_size",
         "dense_len")
_SPARSE_SWITCHES = ("dense_attention", "pool_kernel", "forced_inside_topk", "cache_bits")
_LIGHT_SWITCHES = ("state_bits", "no_decay")


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _up(p):
    return {k: v.astype(F32) for k, v in p.items()}


def sparse_layers(cfg: Dict[str, Any]) -> List[int]:
    """The held layers that are block-sparse attention."""
    first = int(cfg.get("first_layer_run", 0))
    kinds = cfg["mixer_types"][first:first + int(cfg["num_hidden_layers"])]
    return [i for i, kind in enumerate(kinds) if kind == "minicpm4"]


def sizes_of(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {k: int(cfg["assumed_sizes"][k]) for k in SIZES}


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------


def pooled_keys(k, kernel: int, stride: int):
    """k [T, kvh, d] -> kc [J, kvh, d], kc_j = mean(k[stride j : stride j +
    kernel]) for every whole window."""
    T = k.shape[0]
    J = max((T - kernel) // stride + 1, 0)
    idx = (jnp.arange(J) * stride)[:, None] + jnp.arange(kernel)[None]
    return jnp.mean(k[idx], axis=1)


def choose_blocks(q, kc, t, *, kernel, stride, block, topk, init_blocks, window, n_blocks,
                  forced_inside_topk=False):
    """Queries ``q`` [Q, kvh, g, d] at positions ``t`` [Q] against pooled keys
    ``kc`` [J, kvh, d] -> chosen [Q, kvh, n_blocks] bool."""
    J, d = kc.shape[0], kc.shape[-1]
    s = jnp.einsum("qkgd,jkd->qkgj", q, kc) * d ** -0.5
    final = (stride * jnp.arange(J)[None] + kernel <= (t + 1)[:, None])[:, None, None]   # [Q,1,1,J]
    p = jnp.where(final, jax.nn.softmax(jnp.where(final, s, NEG), axis=-1), 0.0)
    group = jnp.where(final[:, :, 0], jnp.sum(p, axis=2), -jnp.inf)                     # [Q,kvh,J]
    per = block // stride
    right = n_blocks * per - 1 - J          # windows 4b-1 .. 4b+3 for b < n_blocks
    score = jax.lax.reduce_window(
        group, -jnp.inf, jax.lax.max, (1, 1, per + 1), (1, 1, per),
        ((0, 0), (0, 0), (1, max(right, 0) + per)),
    )[..., :n_blocks]
    b = jnp.arange(n_blocks)[None]
    last = (t // block)[:, None]
    forced = (b <= last) & ((b < init_blocks) | (b > last - (window // block + 1)))
    others = (b <= last) & ~forced
    rank_of = jnp.where(others[:, None], score, -jnp.inf)
    order = jnp.argsort(-rank_of, axis=-1)
    rank = jnp.argsort(order, axis=-1)
    budget = topk - jnp.sum(forced, axis=-1)[:, None, None] if forced_inside_topk else topk
    best = (rank < budget) & others[:, None] & jnp.isfinite(rank_of)
    return forced[:, None] | best


def _fake_quant_int8(x):
    """x [T, kvh, d] as an 8-bit cache with one scale a 16-token page a kv
    head would return it."""
    T = x.shape[0] // 16 * 16
    pages = x[:T].reshape(T // 16, 16, *x.shape[1:])
    scale = jnp.max(jnp.abs(pages), axis=(1, 3), keepdims=True) / 127.0
    q = jnp.round(pages / jnp.maximum(scale, 1e-30)) * scale
    return jnp.concatenate([q.reshape(T, *x.shape[1:]), x[T:]])


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps", "cache_bits"))
def _sparse_qkv(p, u, *, n_heads, n_kv, head_dim, eps, cache_bits=None):
    p = _up(p)
    T = u.shape[0]
    q = _rms((u @ p["wq"]).reshape(T, n_heads, head_dim), p["q_norm"], eps)
    k = _rms((u @ p["wk"]).reshape(T, n_kv, head_dim), p["k_norm"], eps)
    v = (u @ p["wv"]).reshape(T, n_kv, head_dim)
    if cache_bits == 8:
        k, v = _fake_quant_int8(k), _fake_quant_int8(v)
    return q.reshape(T, n_kv, n_heads // n_kv, head_dim), k, v


@partial(jax.jit, static_argnames=("sizes", "dense_attention", "forced_inside_topk"))
def _sparse_block(q, k, v, kc, t, *, sizes, dense_attention=False, forced_inside_topk=False):
    """A block of queries ``q`` [Q, kvh, g, d] at positions ``t`` over the
    whole sequence's keys: (attention [Q, kvh * g * d], chosen [Q, kvh, nb])."""
    sz = dict(sizes)
    T, d = k.shape[0], k.shape[-1]
    nb = -(-T // sz["block_size"])
    chosen = choose_blocks(
        q, kc, t, kernel=sz["kernel_size"], stride=sz["kernel_stride"], block=sz["block_size"],
        topk=sz["topk"], init_blocks=sz["init_blocks"], window=sz["window_size"], n_blocks=nb,
        forced_inside_topk=forced_inside_topk,
    )
    dense = (t + 1 <= sz["dense_len"])[:, None, None]
    if dense_attention:
        dense = jnp.ones_like(dense)
    chosen = chosen | dense
    mask = jnp.repeat(chosen, sz["block_size"], axis=-1)[..., :T]
    mask = mask & (jnp.arange(T)[None] <= t[:, None])[:, None]
    s = jnp.einsum("qkgd,tkd->qkgt", q, k) * d ** -0.5
    w = jax.nn.softmax(jnp.where(mask[:, :, None], s, NEG), axis=-1)
    a = jnp.einsum("qkgt,tkd->qkgd", w, v)
    return a.reshape(q.shape[0], -1), chosen


@jax.jit
def _gated_out(p, a, u):
    p = _up(p)
    return (a * jax.nn.sigmoid(u @ p["w_ogate"])) @ p["wo"]


def _sparse(p, u, n_held, *, cfg, dense_attention=False, pool_kernel=None,
            forced_inside_topk=False, cache_bits=None):
    """One sparse layer over the whole sequence. Returns (o, what a server
    that has taken ``n_held`` tokens would hold: k, v, the pooled keys, the
    last fed token's grouped query and the blocks it chose)."""
    sz = sizes_of(cfg)
    q, k, v = _sparse_qkv(
        {n: p[n] for n in ("wq", "wk", "wv", "q_norm", "k_norm")}, u,
        n_heads=int(cfg["num_attention_heads"]), n_kv=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), eps=float(cfg["rms_norm_eps"]), cache_bits=cache_bits,
    )
    kc = pooled_keys(k, pool_kernel or sz["kernel_size"], sz["kernel_stride"])
    if pool_kernel:  # keys that would be final under the true kernel only
        kc = kc[: max((k.shape[0] - sz["kernel_size"]) // sz["kernel_stride"] + 1, 0)]
    T = u.shape[0]
    outs, at_last = [], None
    for lo in range(0, T, QUERY_BLOCK):
        t = jnp.arange(lo, min(lo + QUERY_BLOCK, T))
        a, chosen = _sparse_block(
            q[lo:lo + QUERY_BLOCK], k, v, kc, t, sizes=tuple(sz.items()),
            dense_attention=dense_attention, forced_inside_topk=forced_inside_topk,
        )
        outs.append(a)
        if lo <= n_held - 1 < lo + QUERY_BLOCK:
            at_last = chosen[n_held - 1 - lo]
    o = _gated_out({n: p[n] for n in ("w_ogate", "wo")}, jnp.concatenate(outs), u)
    return o, {"k": k, "v": v, "pooled": kc, "q_last": q[n_held - 1], "chosen": at_last}


# ---------------------------------------------------------------------------
# lightning attention: the recurrence token by token
# ---------------------------------------------------------------------------


def _rope(x, theta):
    T, _, d = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("heads", "d", "eps", "theta", "state_bits", "no_decay"))
def _lightning(p, u, n_held, *, heads, d, eps, theta, state_bits=None, no_decay=False):
    p = _up(p)
    T = u.shape[0]
    q = _rope(_rms((u @ p["wq"]).reshape(T, heads, d), p["q_norm"], eps), theta)
    k = _rope(_rms((u @ p["wk"]).reshape(T, heads, d), p["k_norm"], eps), theta)
    v = (u @ p["wv"]).reshape(T, heads, d)
    lam = jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(heads, dtype=F32) + 1.0) / heads)))
    if no_decay:
        lam = jnp.ones_like(lam)

    def step(carry, x):
        S, kept = carry
        q_t, k_t, v_t, t = x
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        if state_bits == 16:
            # (a convert there and back is excess precision XLA may drop)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        y = jnp.einsum("hk,hkv->hv", q_t, S) * d ** -0.5
        return (S, jnp.where(t == n_held - 1, S, kept)), y

    zero = jnp.zeros((heads, d, d), F32)
    (_, kept), y = jax.lax.scan(step, (zero, zero), (q, k, v, jnp.arange(T)))
    y = _rms(y, p["o_norm"], eps).reshape(T, heads * d)
    return (y * jax.nn.sigmoid(u @ p["w_ogate"])) @ p["wo"], kept


@jax.jit
def _ffn(p, x, eps):
    p = _up(p)
    t = _rms(x, p["ff_norm"], eps)
    return (jax.nn.silu(t @ p["w_gate"]) * (t @ p["w_up"])) @ p["w_down"]


@partial(jax.jit, static_argnames=("eps", "divisor"))
def _head(final_norm, head, x, *, eps, divisor):
    h = _rms(x, final_norm, eps) / divisor
    return jax.nn.log_softmax(h @ head.astype(F32), axis=-1)


_SPARSE_KEYS = ("wq", "wk", "wv", "wo", "w_ogate", "q_norm", "k_norm")
_LIGHT_KEYS = _SPARSE_KEYS + ("o_norm",)
_FFN_KEYS = ("ff_norm", "w_gate", "w_up", "w_down")


@_highest
def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, held_after: Optional[int] = None,
             depth_held: bool = False, **switches):
    """Log-probabilities [len(rows), vocab] of the next token after each
    position in ``rows``, from one full forward over ``token_ids``. With
    ``held_after=n`` also what a server that has taken the first ``n`` tokens
    would hold, a layer (None where the layer's kind holds nothing of the
    sort, or the layer is skipped).

    ``pad_to`` pads the sequence (causal attention, a selection a query's
    own, a recurrence: positions after the last real one cannot touch
    earlier ones) so that every prompt compiles the same shapes."""
    unknown = set(switches) - set(_SPARSE_SWITCHES) - set(_LIGHT_SWITCHES)
    if unknown:
        raise TypeError(f"no such switch: {sorted(unknown)}")
    eps = float(cfg["rms_norm_eps"])
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    n_held = len(ids) if held_after is None else held_after
    if pad_to is not None and pad_to > len(ids):
        ids = np.concatenate([ids, np.zeros(pad_to - len(ids), np.int32)])
    depth = int(cfg["num_hidden_layers"]) if depth_held else int(cfg["mup_denominator"])
    c = float(cfg["scale_depth"]) / math.sqrt(depth)
    sparse = set(sparse_layers(cfg))
    x = put(params["embed"])[put(ids)].astype(F32) * float(cfg["scale_emb"])
    held: Dict[str, List[Any]] = {"state": [], "sparse": []}
    for i, lp in enumerate(params["layers"]):
        kept = dict.fromkeys(held)
        if i != skip_layer:
            u = _rms(x, put(lp["in_norm"]), eps)
            if i in sparse:
                o, kept["sparse"] = _sparse(
                    {k: put(lp[k]) for k in _SPARSE_KEYS}, u, n_held, cfg=cfg,
                    **{k: v for k, v in switches.items() if k in _SPARSE_SWITCHES},
                )
            else:
                o, kept["state"] = _lightning(
                    {k: put(lp[k]) for k in _LIGHT_KEYS}, u, n_held,
                    heads=int(cfg["lightning_nh"]), d=int(cfg["lightning_head_dim"]), eps=eps,
                    theta=float(cfg["rope_theta"]),
                    **{k: v for k, v in switches.items() if k in _LIGHT_SWITCHES},
                )
            x = x + c * o
            x = x + c * _ffn({k: put(lp[k]) for k in _FFN_KEYS}, x, eps)
        for name, v in kept.items():
            held[name].append(v)
    out = _head(put(params["final_norm"]), put(params["lm_head"]), x[np.asarray(rows)], eps=eps,
                divisor=float(cfg["hidden_size"]) / float(cfg["dim_model_base"]))
    return np.asarray(out) if held_after is None else (np.asarray(out), held)


# ---------------------------------------------------------------------------
# what the server holds against what the reference would hold
# ---------------------------------------------------------------------------


@jax.jit
def _slot_of(S, slots):
    """The slot of ``slots`` [n, heads, d, d] that holds S [heads, d, d]."""
    return jnp.argmin(jnp.sum((slots - S[None]) ** 2, axis=(1, 2, 3)))


@jax.jit
def _state_difference(S, held):
    d = held - S
    return jnp.sqrt(jnp.sum(d * d) / jnp.sum(S * S))


@jax.jit
def _bf16_exact_share(S):
    """The share of a float32 state's elements that a bf16 holds exactly."""
    bits = jax.lax.bitcast_convert_type(S.astype(F32), jnp.uint32)
    return jnp.mean((bits & 0xFFFF) == 0)


@partial(jax.jit, static_argnames=("n_pages",))
def _pages_of(k, pool, n_pages):
    """The page of ``pool``'s first ``n_pages`` [., page, kvh, d] nearest to
    each whole page of k [T, kvh, d]."""
    size = pool.shape[1]
    a = k[: k.shape[0] // size * size].reshape(-1, size * k.shape[1] * k.shape[2])
    b = pool[:n_pages].astype(F32).reshape(n_pages, -1)
    d = jnp.sum(a * a, axis=1)[:, None] + jnp.sum(b * b, axis=1)[None] - 2 * a @ b.T
    return jnp.argmin(d, axis=1)


def _rel_by_row(held, want):
    d = held - want
    axes = tuple(range(1, want.ndim))
    return jnp.sqrt(jnp.sum(d * d, axis=axes) / jnp.maximum(jnp.sum(want * want, axis=axes), 1e-30))


@jax.jit
def _cache_difference(x, pool, ids, n):
    """The median, over the first ``n`` whole pages of x, of a page's
    || held - x || / || x || (the median: a page an earlier finisher freed
    may be another request's by now)."""
    size = pool.shape[1]
    a = x[: x.shape[0] // size * size].reshape(-1, size, *x.shape[1:])
    rel = _rel_by_row(pool[ids].astype(F32), a)
    return jnp.nanmedian(jnp.where(jnp.arange(a.shape[0]) < n, rel, jnp.nan))


@_highest
def held_differences(cfg, would: Dict[str, List[Any]], held: Dict[str, Any], n: int):
    """How far what the server HOLDS for a request that has taken ``n``
    tokens lies from what the reference would hold (``logprobs(held_after=
    n)``). ``held``: ``state`` one array [slots, heads, d, d] a lightning
    layer the server runs, in order; ``k``, ``v`` one pool a sparse layer, in
    order, whose pages from ``pool_base`` on hold the pooled keys by block id
    (row ``id % page`` of page ``pool_base + id // page`` of the K pool). The
    request's slot and pages are found by content on the first layer of each
    kind the reference ran."""
    sz = sizes_of(cfg)
    L = len(would["state"])
    sparse = set(sparse_layers(cfg))
    light = [(j, i) for j, i in enumerate(i for i in range(L) if i not in sparse)
             if would["state"][i] is not None]
    sp = [(j, i) for j, i in enumerate(i for i in range(L) if i in sparse)
          if would["sparse"][i] is not None]
    out: Dict[str, Any] = {}
    if light:
        j0, i0 = light[0]
        slot = _slot_of(would["state"][i0], held["state"][j0])
        out["state_difference_by_layer"] = [
            float(_state_difference(would["state"][i], held["state"][j][slot])) for j, i in light]
        out["first_state_difference"] = out["state_difference_by_layer"][0]
        out["held_state_precision_gap"] = max(
            abs(float(_bf16_exact_share(held["state"][j][slot]))
                - float(_bf16_exact_share(would["state"][i]))) for j, i in light)
    if sp:
        j0, i0 = sp[0]
        w = would["sparse"][i0]
        base, pool_k = int(held["pool_base"]), held["k"][j0]
        page = pool_k.shape[1]
        ids = _pages_of(w["k"], pool_k, base)
        full = n // page
        out["first_layer_cache_difference"] = max(
            float(_cache_difference(w[x], held[x][j0], ids, full)) for x in ("k", "v"))
        # the pooled keys final after n tokens, as the engine keeps them
        J = max((n - sz["kernel_size"]) // sz["kernel_stride"] + 1, 0)
        J = min(J, w["pooled"].shape[0])
        mine = pool_k[base + ids[:J] // page, ids[:J] % page].astype(F32)
        rel = _rel_by_row(mine, w["pooled"][:J])
        out["pooled_key_difference"] = float(jnp.nanmedian(rel)) if J else 0.0
        out["pooled_keys_compared"] = int(J)
        if n > sz["dense_len"] and w["chosen"] is not None:
            # the blocks the last fed token would choose from what the engine
            # holds (its pooled keys; the query as bf16 carries it)
            q = jax.lax.reduce_precision(w["q_last"], exponent_bits=8, mantissa_bits=7)[None]
            nb = w["chosen"].shape[-1]
            theirs = choose_blocks(
                q, mine, jnp.asarray([n - 1]), kernel=sz["kernel_size"],
                stride=sz["kernel_stride"], block=sz["block_size"], topk=sz["topk"],
                init_blocks=sz["init_blocks"], window=sz["window_size"], n_blocks=nb,
            )[0]
            ours = w["chosen"]
            both = jnp.sum(theirs & ours, axis=-1)
            either = jnp.maximum(jnp.sum(theirs | ours, axis=-1), 1)
            out["block_overlap_by_kv_head"] = [float(x) for x in both / either]
            out["blocks_chosen_by_kv_head"] = [int(x) for x in jnp.sum(ours, axis=-1)]
            out["kv_heads_choose_differently"] = bool(jnp.any(ours[0] != ours[-1]))
    return out


def wrong_variants(cfg: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Every mistake the switches compute, by name (``skip_layer`` is the
    harness's own first slot)."""
    return {
        "dense_attention": {"dense_attention": True}, "pool_kernel_16": {"pool_kernel": 16},
        "forced_inside_topk": {"forced_inside_topk": True}, "depth_held": {"depth_held": True},
        "state_bf16": {"state_bits": 16}, "no_decay": {"no_decay": True},
        "cache_int8": {"cache_bits": 8},
    }


# each limit of ``reference_tolerance``, how the line shows it, the readings
# it bounds, and whether it bounds them from above
LIMITS = (
    ("worst_nat", "worst_tolerance_nat",
     ("worst_logprob_difference_nat", "worst_argmax_gap_nat"), True),
    ("mean_nat", "mean_tolerance_nat", ("mean_logprob_difference_nat",), True),
    ("median_nat", "median_tolerance_nat", ("median_logprob_difference_nat",), True),
    ("state_rel", "state_tolerance_rel", ("first_state_difference",), True),
    ("state_precision_gap", "state_precision_tolerance_share", ("held_state_precision_gap",), True),
    ("first_cache_rel", "first_cache_tolerance_rel", ("first_layer_cache_difference",), True),
    ("pooled_key_rel", "pooled_key_tolerance_rel", ("pooled_key_difference",), True),
    ("block_overlap_min", "block_overlap_tolerance_share", ("block_overlap_worst",), False),
)


def compare(cfg: Dict[str, Any], params: Dict[str, Any],
            samples: List[Dict[str, Any]], pad_to: int, device=None,
            kv_bits: Optional[int] = None, **wrong) -> Dict[str, Any]:
    """Hold the engine's greedy continuations, and what it holds for them
    when they end, to the reference.

    ``samples``: ``{"prompt": [...], "tokens": [...], "logprobs": [...]}`` as
    the engine emitted them; ``params["held"]`` (the adapter's): the engine's
    slot states and page pools as they stand after the samples. A request
    that emitted ``m`` tokens has taken its prompt and the first ``m - 1``.
    Returns the worst differences and ``ok``."""
    if kv_bits is not None:
        return {name: compare(cfg, params, samples, pad_to, device, **sw)
                for name, sw in wrong_variants(cfg).items()}
    held = params.get("held")
    worst_gap = 0.0
    diffs: List[float] = []
    readings: List[Dict[str, Any]] = []
    for s in samples:
        P, emitted = len(s["prompt"]), list(s["tokens"])
        if not emitted or len(s["logprobs"]) != len(emitted):
            return {"ok": False, "reason": "a sample has no tokens or no logprobs",
                    "tokens_compared": len(diffs)}
        seq = list(s["prompt"]) + emitted
        rows = [P - 1 + j for j in range(len(emitted))]
        ref, would = logprobs(cfg, params, seq, rows, pad_to=pad_to, device=device,
                              held_after=len(seq) - 1, **wrong)
        for j, tok in enumerate(emitted):
            diffs.append(abs(float(ref[j, tok]) - float(s["logprobs"][j])))
            worst_gap = max(worst_gap, float(ref[j].max()) - float(ref[j, tok]))
        if held is not None:
            readings.append(held_differences(cfg, would, held, len(seq) - 1))
    res: Dict[str, Any] = {
        "tokens_compared": len(diffs),
        "worst_logprob_difference_nat": max(diffs, default=0.0),
        "worst_argmax_gap_nat": worst_gap,
        "mean_logprob_difference_nat": float(np.mean(diffs)) if diffs else 0.0,
        "median_logprob_difference_nat": float(np.median(diffs)) if diffs else 0.0,
    }
    for name in ("first_state_difference", "held_state_precision_gap",
                 "first_layer_cache_difference", "pooled_key_difference"):
        vals = [r[name] for r in readings if name in r]
        if vals:
            res[name] = max(vals)
    if readings and "state_difference_by_layer" in readings[0]:
        res["state_difference_by_layer"] = [
            max(col) for col in zip(*(r["state_difference_by_layer"] for r in readings))]
    overlaps = [r["block_overlap_by_kv_head"] for r in readings if "block_overlap_by_kv_head" in r]
    if overlaps:
        res["block_overlap_worst"] = min(min(o) for o in overlaps)
        res["block_overlap_by_sample"] = overlaps
        res["blocks_chosen_by_sample"] = [r["blocks_chosen_by_kv_head"] for r in readings
                                          if "blocks_chosen_by_kv_head" in r]
        res["kv_heads_choose_differently"] = any(
            r["kv_heads_choose_differently"] for r in readings
            if "kv_heads_choose_differently" in r)
    res["pooled_keys_compared"] = sum(r.get("pooled_keys_compared", 0) for r in readings)
    tol = cfg["reference_tolerance"]
    ok = True
    for limit, shown_as, names, above in LIMITS:
        if limit not in tol:
            continue
        res[shown_as] = tol[limit]
        for r in names:
            # a limit whose reading is missing (nothing held was handed over) fails
            got = res.get(r, math.inf if above else -math.inf)
            ok = ok and (got <= tol[limit] if above else got >= tol[limit])
    return {"ok": bool(ok), **res}
