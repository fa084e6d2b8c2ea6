"""Plain reference: Solar Open 2's decoder (``model_type`` ``solar_open2``), a
hybrid of LAYER KINDS: three layers in four mix tokens with Kimi Delta
Attention (KDA, arXiv 2510.26692: the gated delta rule with a decay a
channel), the fourth (``gqa_layers``) with softmax grouped-query attention
without positions and with an output gate; every layer's feed-forward is
routed (sigmoid scores, a selection bias, normalised top-k, a shared expert).

Written from the published configuration and the layer as ISSUE 41 wrote it
down, in plain ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No cache, no chunks, no
kernels; it does not import ``dynamo_tpu``. THE RECURRENCE IS THE DEFINITION
of a KDA layer, a ``lax.scan`` over tokens (not the chunked WY form the
program prefills with); the convolution is four shifted products; attention
runs a block of queries at a time (memory, not a cache); the experts run ONE
AT A TIME over the share of the layer's experts the configuration holds;
the vocabulary is the slice the configuration holds.

    x0 = E[token]
    u  = rmsnorm(x; in_norm)
    GQA (layer in gqa_layers): q, k, v = W_q u, W_k u, W_v u   (no positions)
        a = softmax(q k^T / sqrt(head_dim), causal) v;  o = W_o (a * sigmoid(W_gate u))
    KDA (else): [q|k|v] = silu(conv1d_causal_depthwise(W_qkv u))      kernel 4, no bias
        q_h = l2norm(q_h) * d^-0.5;  k_h = l2norm(k_h)
        g = -exp(A_log_h) * softplus(W_f2 (W_f1 u) + dt_bias)       [heads, d]: a decay a CHANNEL
        beta_h = 2 sigmoid(W_b u)_h
        S' = Diag(exp g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  y_t = S_t^T q_t
        o = W_o (rmsnorm_by_head(y; out_norm) * sigmoid(W_g2 (W_g1 u) + b_g))
    x = x + o
    v = rmsnorm(x; ff_norm);  s = sigmoid(W_r v);  top 8 of s + b;  w = s_top / sum(s_top)
    x = x + sum_{e held} w_e SwiGLU_e(v) + SwiGLU_shared(v)
    logits = W_head rmsnorm(x; final_norm)

DEPARTURES from the publication, each the configuration's (``reduced``,
``assumed``): the layers held are published layers ``0 .. num_hidden_layers -
1``; the routed sum runs over the experts this chip holds (``n_routed_experts``
of ``router_outputs``, from ``experts_held_first``: the others' chips would
add theirs), the router choosing among all and normalising over all 8
chosen; the vocabulary is a slice. Every ``assumed`` the configuration lists
is computed here as stated there.

It reads the served bf16 parameters and raises them to float32 a block at a
time inside jitted functions called in a Python loop. Parameters, matrices
stored [in, out]: ``embed`` [vocab, hidden]; ``layers[i]``: ``in_norm``,
``ff_norm``; a GQA layer ``wq``, ``wk``, ``wv``, ``wo``, ``w_gate``; a KDA
layer ``w_qkv`` (columns q | k | v), ``conv_w`` [kernel, channels] (row ``j``
multiplies the input ``kernel - 1 - j`` tokens back), ``w_f1``, ``w_f2``,
``dt_bias`` [heads x d], ``A_log`` [heads], ``w_b``, ``w_g1``, ``w_g2``,
``b_g``, ``out_norm`` [d], ``wo``; every layer ``w_router`` [hidden,
router_outputs], ``router_bias``, ``w_egate`` / ``w_eup`` [held, hidden,
width], ``w_edown`` [held, width, hidden], ``w_shared_gate``,
``w_shared_up``, ``w_shared_down``; ``final_norm``; ``lm_head``.

THE SWITCHES compute a mistake each, to show that the tolerance fails it and
never to pass one: ``cache_bits=8`` (keys and values as a cache held at 8
bits would return them: the precision below the configuration's bf16),
``state_bits`` (the matrix state rounded after every token: 16 = bf16, the
precision below the float32 the configuration's ``assumed`` states; 8 = an
8-bit float, e5m2), ``skip_layer``, ``beta_no_2`` (beta in (0, 1)),
``decay_a_head`` (one decay a head: the mean of its channels' steps),
``no_out_gate`` (KDA's output gate dropped), ``no_gqa_gate``,
``softmax_router`` (plain softmax top-8: the alternative the configuration's
``assumed`` names), ``no_router_bias``, ``no_shared_expert``, ``no_l2norm``.
``kv_bits=8`` is what ``run.py --calibrate`` passes for its second wrong
computation: here it runs EVERY switch above in turn and returns their
readings by name.

TOLERANCE: the configuration's ``reference_tolerance`` (with what it was set
from): over the compared tokens, the worst and the mean and the median of
|engine logprob - reference logprob|, and every emitted token the
reference's argmax or within ``worst_nat`` of it. A logprob does not tell a
bf16 state or an 8-bit cache from the honest engine's own bf16 rounding, so
three limits bound what the engine HOLDS when a sample ends, against what
the reference would hold (``held_differences``). Two are relative norms:
``slow_state_rel`` the slot's matrix state on each KDA layer's slowest
channels, ``first_cache_rel`` the first GQA layer's pages. The delta rule
FORGETS (a token's write erases what its key pointed at), so a state rounded
to bf16 a token does not compound as a state-space mixer's does: by norm it
reads within a fifth of the honest engine's own reading (the configuration's
``reference_tolerance`` has the numbers). What tells it apart is the third
limit, on the PRECISION the two states are kept at: ``state_precision_gap``
bounds the difference between the shares of the held state's and of the
reference state's elements that a bf16 holds exactly (both about 2^-16 when
both are float32, as the configuration's ``assumed`` states; 1 when either
side keeps 16 bits or fewer).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256          # queries a block of attention
SLOW_SHARE = 16        # a layer's slowest 1 / 16 of its (head, channel) rows


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _up(p):
    return jax.tree_util.tree_map(lambda w: w.astype(F32), p)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@partial(jax.jit, static_argnames=(
    "heads", "d", "kernel", "eps", "state_bits", "beta_no_2", "decay_a_head",
    "no_out_gate", "no_l2norm"))
def _kda(p, u, n_state, *, heads, d, kernel, eps, state_bits=None, beta_no_2=False,
         decay_a_head=False, no_out_gate=False, no_l2norm=False):
    """A KDA layer's output o [T, hidden] from the normalised input u [T,
    hidden], the matrix state [heads, d, d] after the first ``n_state``
    tokens, and each (head, channel)'s mean ``|g|`` over them (its row of the
    state forgets in about 1 / that many tokens)."""
    with jax.default_matmul_precision("highest"):
        p = _up(p)
        T, n = u.shape[0], heads * d
        qkv = u @ p["w_qkv"]
        # causal depthwise convolution as `kernel` shifted products, no bias
        padded = jnp.concatenate([jnp.zeros((kernel - 1, 3 * n), F32), qkv])
        conv = jax.nn.silu(sum(padded[j:j + T] * p["conv_w"][j] for j in range(kernel)))
        q, k, v = (conv[:, i * n:(i + 1) * n].reshape(T, heads, d) for i in range(3))
        if not no_l2norm:
            q, k = _l2norm(q), _l2norm(k)
        q = q * d ** -0.5
        step = jax.nn.softplus((u @ p["w_f1"]) @ p["w_f2"] + p["dt_bias"]).reshape(T, heads, d)
        if decay_a_head:
            step = jnp.broadcast_to(jnp.mean(step, axis=-1, keepdims=True), step.shape)
        g = -jnp.exp(p["A_log"])[:, None] * step                       # [T, heads, d] <= 0
        beta = jax.nn.sigmoid(u @ p["w_b"])                            # [T, heads]
        if not beta_no_2:
            beta = 2.0 * beta

        def token(carry, inp):
            S, kept = carry
            q_t, k_t, v_t, g_t, b_t, t = inp
            S1 = jnp.exp(g_t)[:, :, None] * S
            u_t = jnp.sum(S1 * k_t[:, :, None], axis=1)               # S'^T k: [heads, d_v]
            S = S1 + k_t[:, :, None] * (b_t[:, None] * (v_t - u_t))[:, None, :]
            if state_bits is not None:
                # not astype there and back: XLA may elide that pair
                e, m = {16: (8, 7), 8: (5, 2)}[state_bits]
                S = jax.lax.reduce_precision(S, exponent_bits=e, mantissa_bits=m)
            return (S, jnp.where(t < n_state, S, kept)), jnp.sum(S * q_t[:, :, None], axis=1)

        zero = jnp.zeros((heads, d, d), F32)
        (_, S_kept), y = jax.lax.scan(token, (zero, zero), (q, k, v, g, beta, jnp.arange(T)))
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * p["out_norm"]
        if not no_out_gate:
            gate = (u @ p["w_g1"]) @ p["w_g2"] + p["b_g"]
            y = y * jax.nn.sigmoid(gate.reshape(T, heads, d))
        rate = jnp.sum(jnp.where(jnp.arange(T)[:, None, None] < n_state, -g, 0.0), axis=0) / n_state
        return y.reshape(T, n) @ p["wo"], S_kept, rate


def _fake_quant_int8(x):
    """What a cache held at 8 bits would return: per (16-token page, head)
    symmetric int8."""
    T, h, d = x.shape
    xp = jnp.pad(x, ((0, (-T) % 16), (0, 0), (0, 0))).reshape(-1, 16, h, d)
    amax = jnp.max(jnp.abs(xp), axis=(1, 3), keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return (jnp.round(xp / scale).clip(-127, 127) * scale).reshape(-1, h, d)[:T]


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "cache_bits", "no_gqa_gate"))
def _gqa(p, u, *, n_heads, n_kv, head_dim, cache_bits=None, no_gqa_gate=False):
    """A GQA layer's output and its keys and values as a cache would return
    them. No positions. A block of ``Q_BLOCK`` queries at a time against
    every key (the mask makes it causal): memory, not a cache."""
    with jax.default_matmul_precision("highest"):
        p = _up(p)
        T = u.shape[0]
        q = (u @ p["wq"]).reshape(T, n_heads, head_dim)
        k = (u @ p["wk"]).reshape(T, n_kv, head_dim)
        v = (u @ p["wv"]).reshape(T, n_kv, head_dim)
        if cache_bits == 8:
            k, v = _fake_quant_int8(k), _fake_quant_int8(v)
        kr = jnp.repeat(k, n_heads // n_kv, axis=1)
        vr = jnp.repeat(v, n_heads // n_kv, axis=1)
        pad = (-T) % Q_BLOCK
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, n_heads, head_dim)
        at = jnp.arange(T + pad).reshape(-1, Q_BLOCK)

        def block(_, inp):
            q_b, t_b = inp
            s = jnp.einsum("qhd,khd->hqk", q_b, kr) / math.sqrt(head_dim)
            s = jnp.where((jnp.arange(T)[None, :] <= t_b[:, None])[None], s, -jnp.inf)
            return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vr)

        _, a = jax.lax.scan(block, None, (qb, at))
        a = a.reshape(-1, n_heads * head_dim)[:T]
        if "w_gate" in p and not no_gqa_gate:
            a = a * jax.nn.sigmoid(u @ p["w_gate"])
        return a @ p["wo"], k, v


@partial(jax.jit, static_argnames=(
    "eps", "top_k", "first", "scale", "norm_topk", "softmax_router", "no_router_bias",
    "no_shared_expert"))
def _ffn(p, x, *, eps, top_k, first, scale, norm_topk, softmax_router=False,
         no_router_bias=False, no_shared_expert=False):
    """The routed feed-forward over the held experts, ONE AT A TIME (a scan
    over the bf16 stacks, each raised to float32 inside its step), plus the
    shared expert. The router chooses among all its outputs and its weights
    are normalised over all ``top_k`` chosen; an expert held elsewhere adds
    nothing here."""
    with jax.default_matmul_precision("highest"):
        stacks = (p["w_egate"], p["w_eup"], p["w_edown"])
        p = _up({k: w for k, w in p.items() if not k.startswith("w_e")})
        v = _rms_norm(x, p["ff_norm"], eps)
        logits = v @ p["w_router"]
        s = jax.nn.softmax(logits, axis=-1) if softmax_router else jax.nn.sigmoid(logits)
        sel = s if no_router_bias else s + p["router_bias"]
        _, idx = jax.lax.top_k(sel, top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        w = w * scale

        def expert(acc, inp):
            (wg, wu, wd), e = inp
            weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)   # [T]
            h = jax.nn.silu(v @ wg.astype(F32)) * (v @ wu.astype(F32))
            return acc + weight[:, None] * (h @ wd.astype(F32)), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(v), (stacks, jnp.arange(stacks[0].shape[0])))
        if not no_shared_expert:
            y = y + (jax.nn.silu(v @ p["w_shared_gate"]) * (v @ p["w_shared_up"])) @ p["w_shared_down"]
        return y


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, head, x, *, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, final_norm.astype(F32), eps)
        return jax.nn.log_softmax(x @ head.astype(F32), axis=-1)


_KDA_KEYS = ("w_qkv", "conv_w", "w_f1", "w_f2", "dt_bias", "A_log", "w_b", "w_g1", "w_g2",
             "b_g", "out_norm", "wo")
_GQA_KEYS = ("wq", "wk", "wv", "wo", "w_gate")
_FFN_KEYS = ("ff_norm", "w_router", "router_bias", "w_egate", "w_eup", "w_edown",
             "w_shared_gate", "w_shared_up", "w_shared_down")
_KDA_SWITCHES = ("state_bits", "beta_no_2", "decay_a_head", "no_out_gate", "no_l2norm")
_FFN_SWITCHES = ("softmax_router", "no_router_bias", "no_shared_expert")


def gqa_layers(cfg: Dict[str, Any]) -> List[int]:
    """The held layers that are softmax attention."""
    return [i for i in cfg["gqa_layers"] if i < int(cfg["num_hidden_layers"])]


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, cache_bits: Optional[int] = None,
             held_after: Optional[int] = None, **switches):
    """Log-probabilities [len(rows), vocab] of the next token after each
    position in ``rows``, from one full forward over ``token_ids``. With
    ``held_after=n`` also what a server that has taken the first ``n`` tokens
    would hold, a layer (None where the layer's kind holds nothing of the
    sort, or the layer is skipped): a KDA layer's state ``kda`` [heads, d, d]
    after token ``n - 1`` with each (head, channel)'s ``rate`` (its mean
    ``|g|``), a GQA layer's ``k``, ``v`` [T, kv heads, head_dim] as its cache
    would return them.

    ``pad_to`` pads the sequence (causal attention, a causal convolution and
    a recurrence: positions after the last real one cannot touch earlier
    ones) so that every prompt compiles the same shapes."""
    eps = float(cfg["rms_norm_eps"])
    lin = cfg["linear_attn_config"]
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    if pad_to is not None and pad_to > len(ids):
        ids = np.concatenate([ids, np.zeros(pad_to - len(ids), np.int32)])
    unknown = set(switches) - set(_KDA_SWITCHES) - set(_FFN_SWITCHES) - {"no_gqa_gate"}
    if unknown:
        raise TypeError(f"no such switch: {sorted(unknown)}")
    kda_sw = {k: v for k, v in switches.items() if k in _KDA_SWITCHES}
    kda_sw["beta_no_2"] = bool(kda_sw.get("beta_no_2", False)) or not cfg["kda_allow_neg_eigval"]
    ffn_sw = {k: v for k, v in switches.items() if k in _FFN_SWITCHES}
    attn = set(gqa_layers(cfg))
    x = put(params["embed"])[put(ids)].astype(F32)
    held: Dict[str, List[Any]] = {"kda": [], "rate": [], "k": [], "v": []}
    for i, lp in enumerate(params["layers"]):
        kept = dict.fromkeys(held)
        if i != skip_layer:
            u = _rms_norm(x, put(lp["in_norm"]).astype(F32), eps)
            if i in attn:
                o, kept["k"], kept["v"] = _gqa(
                    {k: put(lp[k]) for k in _GQA_KEYS if k in lp}, u,
                    n_heads=int(cfg["num_attention_heads"]), n_kv=int(cfg["num_key_value_heads"]),
                    head_dim=int(cfg["head_dim"]), cache_bits=cache_bits,
                    no_gqa_gate=bool(switches.get("no_gqa_gate", False)) or not cfg["use_gqa_gate"],
                )
            else:
                o, kept["kda"], kept["rate"] = _kda(
                    {k: put(lp[k]) for k in _KDA_KEYS}, u,
                    len(ids) if held_after is None else held_after,
                    heads=int(lin["num_heads"]), d=int(lin["head_dim"]),
                    kernel=int(lin["short_conv_kernel_size"]), eps=eps, **kda_sw,
                )
            x = x + o
            x = x + _ffn(
                {k: put(lp[k]) for k in _FFN_KEYS}, x, eps=eps,
                top_k=int(cfg["num_experts_per_tok"]), first=int(cfg["experts_held_first"]),
                scale=float(cfg["routed_scaling_factor"]), norm_topk=bool(cfg["norm_topk_prob"]),
                **ffn_sw,
            )
        for name, v in kept.items():
            held[name].append(v)
    out = _head(put(params["final_norm"]), put(params["lm_head"]), x[np.asarray(rows)], eps=eps)
    return np.asarray(out) if held_after is None else (np.asarray(out), held)


# ---------------------------------------------------------------------------
# what the server holds against what the reference would hold
# ---------------------------------------------------------------------------


@jax.jit
def _slot_of(S, slots):
    """The slot of ``slots`` [n, heads, d, d] that holds S [heads, d, d]."""
    return jnp.argmin(jnp.sum((slots - S[None]) ** 2, axis=(1, 2, 3)))


@jax.jit
def _slow_rows_difference(S, rate, slots, slot):
    """|| held - S || / || S || over the rows of S (a head's key channels)
    that forget slowest: the 1 / ``SLOW_SHARE`` of them with the least
    ``rate``. Also those rows' largest rate."""
    heads, d, dv = S.shape
    flat, held = S.reshape(heads * d, dv), slots[slot].reshape(heads * d, dv)
    n = max(1, heads * d // SLOW_SHARE)
    neg, rows = jax.lax.top_k(-rate.reshape(-1), n)
    diff = held[rows] - flat[rows]
    return jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(flat[rows] ** 2)), -neg[-1]


@jax.jit
def _state_difference(S, slots, slot):
    d = slots[slot] - S
    return jnp.sqrt(jnp.sum(d * d) / jnp.sum(S * S))


@jax.jit
def _bf16_exact_share(S):
    """The share of a float32 state's elements that a bf16 holds exactly
    (the low 16 bits of the float32 are zero): about 2^-16 of a state that
    is computed and kept in float32, all of one rounded to 16 bits or fewer
    wherever it is kept."""
    bits = jax.lax.bitcast_convert_type(S.astype(F32), jnp.uint32)
    return jnp.mean((bits & 0xFFFF) == 0)


def _as_pages(x, pool):
    """x [T, kv heads, head_dim] in the pool's pages [T // page, page, ...]."""
    size = pool.shape[1]
    return x[: x.shape[0] // size * size].reshape(-1, size, *x.shape[1:])


@jax.jit
def _pages_of(k, pool):
    """The page of ``pool`` [pages, page, kv heads, head_dim] nearest to each
    page of k [T, kv heads, head_dim]: [T // page]."""
    a = _as_pages(k, pool)
    a, b = a.reshape(a.shape[0], -1), pool.astype(F32).reshape(pool.shape[0], -1)
    d = jnp.sum(a * a, axis=1)[:, None] + jnp.sum(b * b, axis=1)[None] - 2 * a @ b.T
    return jnp.argmin(d, axis=1)


@jax.jit
def _cache_difference(x, pool, ids, n):
    """The median, over the first ``n`` pages of x, of a page's
    || held - x || / || x ||. The median, because a page that an earlier
    finisher freed may be another request's by now: those read about 1.4,
    and are few."""
    a = _as_pages(x, pool)
    d = pool[ids].astype(F32) - a
    rel = jnp.sqrt(jnp.sum(d * d, axis=(1, 2, 3)) / jnp.sum(a * a, axis=(1, 2, 3)))
    return jnp.nanmedian(jnp.where(jnp.arange(a.shape[0]) < n, rel, jnp.nan))


def held_differences(ref: Dict[str, List[Any]], held: Dict[str, List[Any]], n: int,
                     attn_layers: Sequence[int]):
    """How far what the server HOLDS for a request that has taken ``n`` tokens
    lies from what the reference would hold (``logprobs(held_after=n)``), as
    relative norms. ``held``: ``kda`` one array [slots, heads, d, d] a KDA
    layer the server runs, in order; ``k`` and ``v`` one pool [pages, page, kv
    heads, head_dim] a GQA layer, in order. The request's slot and pages are
    found by content, on the first layer of each kind the reference ran: the
    nearest slot, and the nearest page to each of its FULL pages (the last
    one may hold what a later step wrote). Returns a KDA layer's reading on
    its slowest rows, those rows' largest rate, its reading over the whole
    state, the gap between the shares of the two states' elements that a
    bf16 holds exactly (each [KDA layers]), and a GQA layer's median page for
    keys and for values [GQA layers, 2]."""
    L = len(ref["kda"])
    attn = set(attn_layers)
    # (place among the layers of its kind, model layer), the layers the
    # reference ran
    kda = [(j, i) for j, i in enumerate(i for i in range(L) if i not in attn)
           if ref["kda"][i] is not None]
    gqa = [(j, i) for j, i in enumerate(i for i in range(L) if i in attn)
           if ref["k"][i] is not None]
    j0, i0 = kda[0]
    slot = _slot_of(ref["kda"][i0], held["kda"][j0])
    slow, rates, whole, exact = [], [], [], []
    for j, i in kda:
        s, r = _slow_rows_difference(ref["kda"][i], ref["rate"][i], held["kda"][j], slot)
        slow.append(float(s)), rates.append(float(r))
        whole.append(float(_state_difference(ref["kda"][i], held["kda"][j], slot)))
        exact.append(abs(float(_bf16_exact_share(held["kda"][j][slot]))
                         - float(_bf16_exact_share(ref["kda"][i]))))
    j0, i0 = gqa[0]
    ids = _pages_of(ref["k"][i0], held["k"][j0])
    full = n // held["k"][j0].shape[1]
    cache = [[float(_cache_difference(ref[w][i], held[w][j], ids, full)) for w in ("k", "v")]
             for j, i in gqa]
    return (np.asarray(slow), np.asarray(rates), np.asarray(whole), np.asarray(exact),
            np.asarray(cache))


def wrong_variants(cfg: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Every mistake the switches compute, by name (``skip_layer`` is the
    harness's own first slot)."""
    out: Dict[str, Dict[str, Any]] = {
        "cache_int8": {"cache_bits": 8}, "state_bf16": {"state_bits": 16},
        "state_fp8": {"state_bits": 8},
    }
    for name in ("beta_no_2", "decay_a_head", "no_out_gate", "no_gqa_gate", "softmax_router",
                 "no_router_bias", "no_shared_expert", "no_l2norm"):
        out[name] = {name: True}
    return out


# each limit of ``reference_tolerance`` and the readings it bounds
LIMITS = (
    ("worst_nat", "worst_tolerance_nat", ("worst_logprob_difference_nat", "worst_argmax_gap_nat")),
    ("mean_nat", "mean_tolerance_nat", ("mean_logprob_difference_nat",)),
    ("median_nat", "median_tolerance_nat", ("median_logprob_difference_nat",)),
    ("slow_state_rel", "slow_state_tolerance_rel", ("slowest_rows_state_difference",)),
    ("first_cache_rel", "first_cache_tolerance_rel", ("first_layer_cache_difference",)),
    ("state_precision_gap", "state_precision_tolerance_share", ("held_state_precision_gap",)),
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
    slow: List[np.ndarray] = []
    rates: List[np.ndarray] = []
    whole: List[np.ndarray] = []
    exact: List[np.ndarray] = []
    cache: List[np.ndarray] = []
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
            a, b, c, e, d = held_differences(would_hold, held, len(seq) - 1, gqa_layers(cfg))
            slow.append(a), rates.append(b), whole.append(c), exact.append(e), cache.append(d)
    tol = cfg["reference_tolerance"]
    res: Dict[str, Any] = {
        "tokens_compared": len(diffs),
        "worst_logprob_difference_nat": max(diffs, default=0.0),
        "worst_argmax_gap_nat": worst_gap,
        "mean_logprob_difference_nat": float(np.mean(diffs)) if diffs else 0.0,
        "median_logprob_difference_nat": float(np.median(diffs)) if diffs else 0.0,
    }
    if slow:
        # WHERE a lower precision of what is held shows. A state rounded a
        # token compounds in the rows that forget slowest, and there the
        # honest engine's own rounding (of each token's inputs, which does
        # not compound) averages out: a KDA layer's slowest rows, the worst
        # over layers and samples. A cache's rounding is a fixed share of a
        # key, and every layer above the first adds its own bf16 rounding to
        # what it is handed: the FIRST GQA layer's pages (layer 0)
        sl, ra, wh, ca = np.stack(slow), np.stack(rates), np.stack(whole), np.stack(cache)
        res.update({
            "slowest_rows_state_difference": float(sl.max()),
            "slowest_rows_state_difference_by_layer": [float(v) for v in sl.max(axis=0)],
            "first_layer_slowest_rows_state_difference": float(sl[:, 0].max()),
            "slowest_rows_rate_by_layer": [float(v) for v in ra.mean(axis=0)],
            "whole_state_difference_by_layer": [float(v) for v in wh.max(axis=0)],
            "held_state_precision_gap": float(np.max(exact)),
            "first_layer_cache_difference": float(ca[:, 0].max()),
            "cache_difference_by_layer": [float(v) for v in ca.mean(axis=(0, 2))],
        })
    ok = True
    for limit, shown_as, readings in LIMITS:
        if limit not in tol:
            continue
        res[shown_as] = tol[limit]
        # a limit whose reading is missing (nothing held was handed over) fails
        ok = ok and all(res.get(r, math.inf) <= tol[limit] for r in readings)
    return {"ok": bool(ok), **res}
