"""Plain reference: Ouro (``model_type`` ``ouro``: ByteDance Ouro-2.6B,
"Scaling Latent Reasoning via Looped Language Models", arXiv 2510.25741), a
looped decoder: ONE stack of dense layers run ``total_ut_steps`` times.

Written from the equations of ISSUE 60 (the published ``modeling_ouro.py`` is
their authority; what ``config.json`` does not fix is under ``assumed`` in
the configuration file), in plain ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No cache, no kernels, no
batching: EACH PASS IS A FULL CAUSAL FORWARD over the whole sequence. It does
not import ``dynamo_tpu``.

    T = total_ut_steps, L = num_hidden_layers;  h = E[tok]
    for pass t = 0 .. T-1:
        for layer l = 0 .. L-1:
            a = Attn_l(RMSNorm(h; attn_norm));  h += RMSNorm(a; attn_out_norm)
            m = MLP_l(RMSNorm(h; mlp_norm));    h += RMSNorm(m; mlp_out_norm)
        h = RMSNorm(h; final_norm)             [_forward: every pass, fed forward]
    logits = h W_head                           [the last pass's; untied]

``Attn_l`` at pass ``t``: q, k, v = x W_q, x W_k, x W_v (no bias); rotate-half
rotary over the whole head, the token's position THE SAME AT EVERY PASS;
causal softmax at ``head_dim ** -0.5`` over the keys and values that pass
``t`` of layer ``l`` made for positions <= i (slot ``(t, l)``: a pass never
reads another pass's keys); W_o. ``MLP_l``: W_down(silu(x W_gate) * x W_up).
RMSNorm: x * rsqrt(mean(x^2) + eps) * g. The exit gate (``exit_gate_w`` /
``exit_gate_b``: Linear(hidden -> 1) on each pass's normed state) is part of
no logit at ``early_exit_threshold`` 1; ``exit_distribution`` prints it.

Parameters (the adapter's names, matrices [in, out]): ``embed``;
``layers[l]``: ``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo``,
``attn_out_norm``, ``mlp_norm``, ``w_gate``, ``w_up``, ``w_down``,
``mlp_out_norm``; ``final_norm``; ``lm_head``; ``exit_gate_w``,
``exit_gate_b``; ``held`` (the adapter's): the pools the engine HOLDS of slot
``(0, 0)`` and slot ``(T-1, L-1)``.

WRONG COMPUTATIONS the switches compute (``WRONG``; never to pass one, only
to show that the tolerance fails it): one pass fewer; the final norm only
after the last pass; the output norms left out; every pass READING pass 0's
slot; every pass writing and reading ONE slot, so that what it reads of the
tokens before is the LAST pass's (the paper's shared last-pass cache: the
prompt as one block, then token by token); positions advanced by the pass
(rotary is relative inside a pass, so no logit moves: told by the pages held
of the last slot alone); a skipped layer; a cache held at 8 bits.

TOLERANCE: the configuration's ``reference_tolerance``, each limit with its
reason there. Over the compared tokens every |engine logprob - reference
logprob| <= ``worst_nat``, every emitted token the reference's argmax or
within ``worst_nat`` of it, the mean difference <= ``mean_nat``, and the
pages the engine holds of the two slots within ``first_slot_cache_rel`` /
``last_slot_cache_rel`` of the reference's keys and values by relative norm
(the median over a sample's full pages, the worst of the samples).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
PAGE = 16


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, cos, sin):
    """x [T, heads, d]; cos/sin [T, 1, d/2]: rotate-half."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _fake_quant_int8(x):
    """What a cache held at 8 bits would return: per (16-token page, head)
    symmetric int8."""
    T, h, d = x.shape
    pad = (-T) % PAGE
    xp = jnp.pad(x, ((0, pad), (0, 0), (0, 0))).reshape(-1, PAGE, h, d)
    amax = jnp.max(jnp.abs(xp), axis=(1, 3), keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return (jnp.round(xp / scale).clip(-127, 127) * scale).reshape(-1, h, d)[:T]


def _attention(q, k, v, mask, n_heads, head_dim):
    group = n_heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def _mlp(p, x, eps, out_norms):
    h = _rms_norm(x, p["mlp_norm"], eps)
    m = (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    return x + (_rms_norm(m, p["mlp_out_norm"], eps) if out_norms else m)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps", "kv_bits",
                                   "out_norms"))
def _layer(p, x, cos, sin, k_read=None, v_read=None, *, n_heads, n_kv, head_dim, eps,
           kv_bits=None, out_norms=True):
    """One layer over the whole sequence ``x [T, hidden]`` -> (x', k, v): the
    keys and values this pass made (what its slot holds). ``k_read`` /
    ``v_read``: attend over these in place of its own (a wrong slot)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda w: w.astype(F32), p)
        T = x.shape[0]
        h = _rms_norm(x, p["attn_norm"], eps)
        q = _rotate((h @ p["wq"]).reshape(T, n_heads, head_dim), cos, sin)
        k = _rotate((h @ p["wk"]).reshape(T, n_kv, head_dim), cos, sin)
        v = (h @ p["wv"]).reshape(T, n_kv, head_dim)
        if kv_bits == 8:
            k, v = _fake_quant_int8(k), _fake_quant_int8(v)
        causal = jnp.tril(jnp.ones((T, T), bool))
        o = _attention(q, k if k_read is None else k_read, v if v_read is None else v_read,
                       causal, n_heads, head_dim)
        a = o.reshape(T, n_heads * head_dim) @ p["wo"]
        x = x + (_rms_norm(a, p["attn_out_norm"], eps) if out_norms else a)
        return _mlp(p, x, eps, out_norms), k, v


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps"), donate_argnums=(4, 5))
def _layer_one_token(p, x, cos, sin, k_slot, v_slot, i, *, n_heads, n_kv, head_dim, eps):
    """The layer for ONE token at position ``i`` over ONE slot shared by all
    passes (``k_slot`` / ``v_slot`` [T, kv heads, d]): its key and value
    written at ``i``, attended over positions <= i -> (x', k_slot', v_slot')."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda w: w.astype(F32), p)
        h = _rms_norm(x, p["attn_norm"], eps)
        q = _rotate((h @ p["wq"]).reshape(1, n_heads, head_dim), cos, sin)
        k = _rotate((h @ p["wk"]).reshape(1, n_kv, head_dim), cos, sin)
        v = (h @ p["wv"]).reshape(1, n_kv, head_dim)
        k_slot = jax.lax.dynamic_update_slice(k_slot, k, (i, 0, 0))
        v_slot = jax.lax.dynamic_update_slice(v_slot, v, (i, 0, 0))
        mask = (jnp.arange(k_slot.shape[0]) <= i)[None]
        o = _attention(q, k_slot, v_slot, mask, n_heads, head_dim)
        a = o.reshape(1, n_heads * head_dim) @ p["wo"]
        x = x + _rms_norm(a, p["attn_out_norm"], eps)
        return _mlp(p, x, eps, True), k_slot, v_slot


@partial(jax.jit, static_argnames=("eps",))
def _pass_norm(w, x, *, eps):
    return _rms_norm(x, w.astype(F32), eps)


@jax.jit
def _head(head, x):
    with jax.default_matmul_precision("highest"):
        return jax.nn.log_softmax(x @ head.astype(F32), axis=-1)


def _sizes(cfg):
    head_dim = int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])
    return dict(n_heads=int(cfg["num_attention_heads"]), n_kv=int(cfg["num_key_value_heads"]),
                head_dim=head_dim, eps=float(cfg["rms_norm_eps"]))


def _tables(cfg, positions, put):
    half = _sizes(cfg)["head_dim"] // 2
    inv = 1.0 / (float(cfg["rope_theta"]) ** (np.arange(0, half, dtype=np.float32) / half))
    ang = np.asarray(positions, np.float32)[:, None] * inv[None, :]
    return put(np.cos(ang)[:, None, :]), put(np.sin(ang)[:, None, :])


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, kv_bits: Optional[int] = None,
             passes: Optional[int] = None, final_norm_once: bool = False,
             no_out_norms: bool = False, read_pass0: bool = False,
             advance_positions: bool = False, shared_slot_from: Optional[int] = None,
             states_out: Optional[list] = None):
    """Log-probabilities [len(rows), vocab] of the next token after each
    position in ``rows``, from ``passes`` full causal forwards over
    ``token_ids``, and what slots ``(0, 0)`` and ``(T-1, L-1)`` would hold
    (``{"first": (k, v), "last": (k, v)}``, each [len(token_ids), kv heads,
    head_dim]).

    ``pad_to`` pads the sequence (causal: positions after the last real one
    cannot touch earlier ones). The other keywords are ``WRONG``'s.
    ``states_out``: a list that receives each pass's normed state."""
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    sz = _sizes(cfg)
    ids = np.asarray(token_ids, np.int32)
    n = len(ids)
    if pad_to is not None and pad_to > n:
        ids = np.concatenate([ids, np.zeros(pad_to - n, np.int32)])
    T = int(cfg["total_ut_steps"]) if passes is None else int(passes)
    L = len(params["layers"])
    if shared_slot_from is not None:
        return _shared_slot(cfg, params, ids, n, rows, int(shared_slot_from), put), None
    x = put(params["embed"])[put(ids)].astype(F32)
    pos = np.arange(len(ids))
    cos, sin = _tables(cfg, pos, put)
    would_hold = {}
    pass0 = {}
    for t in range(T):
        if advance_positions and t:
            cos, sin = _tables(cfg, pos + t, put)
        for l, lp in enumerate(params["layers"]):
            if l == skip_layer:
                continue
            read = pass0.get(l, (None, None)) if read_pass0 and t else (None, None)
            x, k, v = _layer(put(lp), x, cos, sin, *read, **sz, kv_bits=kv_bits,
                             out_norms=not no_out_norms)
            if read_pass0 and t == 0:
                pass0[l] = (k, v)
            if (t, l) == (0, 0):
                would_hold["first"] = (k[:n], v[:n])
            if (t, l) == (T - 1, L - 1):
                would_hold["last"] = (k[:n], v[:n])
        if not final_norm_once or t == T - 1:
            x = _pass_norm(put(params["final_norm"]), x, eps=sz["eps"])
        if states_out is not None:
            states_out.append(np.asarray(x[:n]))
    out = _head(put(params["lm_head"]), x[np.asarray(rows)])
    return np.asarray(out), would_hold


def _shared_slot(cfg, params, ids, n, rows, first, put):
    """ONE slot a layer for every pass: positions below ``first`` (the
    prompt) as one block, each pass reading its own keys inside it and the
    last pass's left behind; then token by token, pass ``t`` reading the LAST
    pass's keys of every token before and its own of this one."""
    sz = _sizes(cfg)
    T = int(cfg["total_ut_steps"])
    pad = len(ids)
    x = put(params["embed"])[put(ids[:first])].astype(F32)
    cos, sin = _tables(cfg, np.arange(first), put)
    slots = []
    for t in range(T):
        for l, lp in enumerate(params["layers"]):
            x, k, v = _layer(put(lp), x, cos, sin, **sz)
            if t == T - 1:
                grow = ((0, pad - first), (0, 0), (0, 0))
                slots.append([jnp.pad(k, grow), jnp.pad(v, grow)])
        x = _pass_norm(put(params["final_norm"]), x, eps=sz["eps"])
    states = {first - 1: x[first - 1]}
    for i in range(first, n):
        x = put(params["embed"])[put(ids[i : i + 1])].astype(F32)
        cos, sin = _tables(cfg, [i], put)
        for t in range(T):
            for l, lp in enumerate(params["layers"]):
                x, slots[l][0], slots[l][1] = _layer_one_token(
                    put(lp), x, cos, sin, slots[l][0], slots[l][1], i, **sz)
            x = _pass_norm(put(params["final_norm"]), x, eps=sz["eps"])
        states[i] = x[0]
    return np.asarray(_head(put(params["lm_head"]), jnp.stack([states[r] for r in rows])))


def exit_distribution(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
                      device=None) -> np.ndarray:
    """[tokens, passes]: the exit gate's distribution over the passes
    (``lambda_t = sigmoid(gate(h_t))``, ``p_t = lambda_t prod_{s<t} (1 -
    lambda_s)``, the last pass the remainder). A diagnostic: at the published
    threshold 1 every token leaves at the last pass."""
    states: List[np.ndarray] = []
    logprobs(cfg, params, token_ids, [0], device=device, states_out=states)
    w = np.asarray(params["exit_gate_w"], np.float32)
    b = np.asarray(params["exit_gate_b"], np.float32)
    stay = np.ones(len(token_ids), np.float32)
    out = []
    for t, h in enumerate(states):
        lam = 1.0 / (1.0 + np.exp(-(h @ w + b)[:, 0]))
        out.append(stay if t == len(states) - 1 else lam * stay)
        stay = stay * (1.0 - lam)
    return np.stack(out, axis=-1)


# ---------------------------------------------------------------------------
# what the server holds against what the reference would hold
# ---------------------------------------------------------------------------


@jax.jit
def _nearest_pages(a, pool):
    """The page of ``pool`` [pages, page, heads, d] nearest to each page of
    a [n, page, heads, d], over the whole page."""
    a, b = a.reshape(a.shape[0], -1), pool.astype(F32).reshape(pool.shape[0], -1)
    with jax.default_matmul_precision("highest"):
        d = jnp.sum(a * a, axis=1)[:, None] + jnp.sum(b * b, axis=1)[None] - 2 * a @ b.T
    return jnp.argmin(d, axis=1)


@jax.jit
def _page_differences(a, pool, ids):
    d = pool[ids].astype(F32) - a
    return jnp.sqrt(jnp.sum(d * d, axis=(1, 2, 3)) / jnp.sum(a * a, axis=(1, 2, 3)))


def held_differences(would_hold: Dict[str, Any], held: Dict[str, Any], tokens: int):
    """How far the pages the server HOLDS of slot ``(0, 0)`` and slot ``(T-1,
    L-1)`` lie from the reference's keys and values over the first ``tokens``
    positions' full pages, as relative norms a page: ``{"first": [2, pages],
    "last": [2, pages]}`` (keys, values). ``held[slot]``: ``(k, v)`` pools
    [pages, page, kv heads, head_dim] of that slot. A sample's pages are
    found ONCE, by the content of slot ``(0, 0)``'s values, and read at the
    same block ids in the last slot: a block id names its page in every
    slot."""
    full = tokens // PAGE
    if full == 0:
        return None
    as_pages = lambda x: x[: full * PAGE].reshape(full, PAGE, *x.shape[1:])  # noqa: E731
    ids = _nearest_pages(as_pages(would_hold["first"][1]), held["first"][1])
    return {
        slot: np.stack([np.asarray(_page_differences(as_pages(would_hold[slot][i]),
                                                     held[slot][i], ids)) for i in (0, 1)])
        for slot in ("first", "last")
    }


# every wrong computation the switches compute, by name
def wrong_variants(cfg: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    return {
        "one_pass_fewer": {"passes": int(cfg["total_ut_steps"]) - 1},
        "final_norm_once": {"final_norm_once": True},
        "no_out_norms": {"no_out_norms": True},
        "read_pass0_slot": {"read_pass0": True},
        "read_last_pass_slot": {"shared_slot": True},
        "positions_advanced": {"advance_positions": True},
        "skipped_layer": {"skip_layer": int(cfg["num_hidden_layers"]) // 2},
        "cache_int8": {"kv_bits": 8},
    }


# each limit of ``reference_tolerance`` and the readings it bounds
LIMITS = (
    ("worst_nat", "worst_tolerance_nat", ("worst_logprob_difference_nat", "worst_argmax_gap_nat")),
    ("mean_nat", "mean_tolerance_nat", ("mean_logprob_difference_nat",)),
    ("first_slot_cache_rel", "first_slot_tolerance_rel", ("first_slot_cache_difference",)),
    ("last_slot_cache_rel", "last_slot_tolerance_rel", ("last_slot_cache_difference",)),
)


def compare(cfg: Dict[str, Any], params: Dict[str, Any], samples: List[Dict[str, Any]],
            pad_to: int, device=None, shared_slot: bool = False, **wrong) -> Dict[str, Any]:
    """Hold the engine's greedy continuations, and the pages it holds of the
    first and the last slot when they end, to the reference.

    ``samples``: ``{"prompt": [...], "tokens": [...], "logprobs": [...]}`` as
    the engine emitted them; ``params["held"]`` (the adapter's): the engine's
    pools of the two slots as they stand after the samples. A request that
    emitted ``m`` tokens has taken its prompt and the first ``m - 1``."""
    held = params.get("held")
    worst_gap = 0.0
    diffs: List[float] = []
    pages: Dict[str, List[np.ndarray]] = {"first": [], "last": []}
    for s in samples:
        P, emitted = len(s["prompt"]), list(s["tokens"])
        if not emitted or len(s["logprobs"]) != len(emitted):
            return {"ok": False, "reason": "a sample has no tokens or no logprobs",
                    "tokens_compared": len(diffs)}
        seq = list(s["prompt"]) + emitted
        rows = [P - 1 + j for j in range(len(emitted))]
        if shared_slot:
            wrong["shared_slot_from"] = P
        ref, would_hold = logprobs(cfg, params, seq, rows, pad_to=pad_to, device=device, **wrong)
        for j, tok in enumerate(emitted):
            diffs.append(abs(float(ref[j, tok]) - float(s["logprobs"][j])))
            worst_gap = max(worst_gap, float(ref[j].max()) - float(ref[j, tok]))
        if held is not None and would_hold:
            d = held_differences(would_hold, held, len(seq) - 1)
            for slot in pages:
                if d is not None and slot in would_hold:
                    pages[slot].append(d[slot])
    tol = cfg["reference_tolerance"]
    res: Dict[str, Any] = {
        "tokens_compared": len(diffs),
        "worst_logprob_difference_nat": max(diffs, default=0.0),
        "worst_argmax_gap_nat": worst_gap,
        "mean_logprob_difference_nat": float(np.mean(diffs)) if diffs else 0.0,
    }
    for slot, ds in pages.items():
        if ds:
            # the median over a sample's pages, the worst of the samples,
            # keys and values apart
            by_kind = np.max([np.median(d, axis=1) for d in ds], axis=0)
            res.update({
                f"{slot}_slot_key_difference": float(by_kind[0]),
                f"{slot}_slot_value_difference": float(by_kind[1]),
                f"{slot}_slot_cache_difference": float(by_kind.max()),
            })
    if pages["first"]:
        res["cache_pages_compared"] = int(sum(d.shape[1] for d in pages["first"]))
    ok = True
    for limit, shown_as, readings in LIMITS:
        if limit not in tol:
            continue
        res[shown_as] = tol[limit]
        # a limit whose reading is missing (nothing held was handed over) fails
        ok = ok and all(res.get(r, math.inf) <= tol[limit] for r in readings)
    res["ok"] = bool(ok)
    return res


def calibrate(cfg, params, samples, pad_to, device=None, only: Optional[Sequence[str]] = None):
    """``compare`` under the honest computation and under each of
    ``wrong_variants`` (by hand, on the chip: what the tolerance in the file
    was set from)."""
    out = {"honest": compare(cfg, params, samples, pad_to, device=device)}
    for name, kw in wrong_variants(cfg).items():
        if only is None or name in only:
            out[name] = compare(cfg, params, samples, pad_to, device=device, **kw)
    return out
