"""Plain reference: Falcon-H1's decoder (``model_type`` ``falcon_h1``), a
PARALLEL hybrid: every layer feeds one normalised input to a Mamba-2
state-space mixer and to grouped-query attention, adds both to the residual
together, then a gated MLP.

Written from the published configuration and transformers'
``modeling_falcon_h1`` as the issue wrote the layer down, in plain
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
No cache, no chunks, no kernels; it does not import ``dynamo_tpu``. The
recurrence is the DEFINITION, a ``lax.scan`` over tokens (not the chunked
dual form the program prefills with); the convolution is four shifted
products; the vocabulary is the slice the configuration holds.

    x0 = E[token] * embedding_multiplier
    u  = rmsnorm(x; in_norm)
    p  = (W_inproj (u * ssm_in_multiplier)) * mup     z | x | B | C | dt, mup = ssm_multipliers by segment
    [x|B|C] = silu(conv1d_causal_depthwise([x|B|C]) + bias)
    dt = softplus(dt + dt_bias);  A = -exp(A_log);  head i reads group i // (heads / groups)
    S_t = exp(dt A) S_{t-1} + dt * x_t (x) B_t;   y_t = S_t C_t + D x_t
    y  = rmsnorm_by_group(y * silu(z); ssm_norm)       (gate first: mamba_norm_before_gate false)
    o_s = (W_outproj y) * ssm_out_multiplier
    q, k, v = W_q u', W_k u', W_v u'   (u' = u * attention_in_multiplier);  k = k * key_multiplier
    rotary (rotate-half) on q, k;  softmax(q k^T / sqrt(head_dim)) v, causal;  o_a = (W_o a) * attention_out_multiplier
    x = x + o_s + o_a
    v = rmsnorm(x; ff_norm);  x = x + (W_down (silu((W_gate v) * mlp_multipliers[0]) * W_up v)) * mlp_multipliers[1]
    logits = (W_head rmsnorm(x; final_norm)) * lm_head_multiplier

It reads the served bf16 parameters and raises them to float32 a block at a
time inside jitted functions called in a Python loop (one MLP matrix is
0.44 GB in float32: beside the engine a float32 copy of the model does not
fit). Parameters, matrices stored [in, out]: ``embed`` [vocab, hidden];
``layers[i]``: ``in_norm``, ``w_inproj`` (columns z | x | B | C | dt),
``conv_w`` [kernel, channels] (row ``j`` multiplies the input ``kernel - 1 -
j`` tokens back), ``conv_b``, ``dt_bias``, ``A_log``, ``D``, ``ssm_norm``,
``w_outproj``, ``wq``, ``wk``, ``wv``, ``wo``, ``ff_norm``, ``w_gate``,
``w_up``, ``w_down``; ``final_norm``; ``lm_head`` [hidden, vocab].

THE SWITCHES compute a mistake each, to show that the tolerance fails it and
never to pass one: ``cache_bits=8`` (keys and values as a cache held at 8
bits would return them: the precision below the configuration's bf16),
``state_bits`` (the recurrent state rounded after every token: 16 = bf16,
the precision below the float32 the configuration's ``assumed`` states; 8 =
an 8-bit float, e5m2), ``skip_layer``, ``drop`` (one multiplier's name, run as 1),
``norm_before_gate``, ``no_D``, ``no_conv_bias``, ``groups_as_one`` (every
head reads group 0 and the gated norm runs over all lanes as one group).
``kv_bits=8`` is what ``run.py --calibrate`` passes for its second wrong
computation: here it runs EVERY switch above in turn and returns their
readings by name (the harness has one slot and this family has a dozen
mistakes to tell apart).

TOLERANCE: the configuration's ``reference_tolerance`` (with what it was set
from): over the compared tokens, the worst and the mean and the median of
|engine logprob - reference logprob|, and every emitted token the
reference's argmax or within ``worst_nat`` of it. A logprob does not tell a
bf16 state or an 8-bit cache from the honest engine's own bf16 rounding, so
two limits bound what the engine HOLDS when a sample ends, as relative norms
against what the reference would hold (``held_differences``):
``slow_state_rel`` the slot's recurrent state on each layer's slowest head,
``first_cache_rel`` the first layer's pages (``compare`` says why there).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MULTIPLIERS = (
    "embedding", "lm_head", "attention_out", "key", "ssm_in", "ssm_out",
    "ssm_z", "ssm_x", "ssm_B", "ssm_C", "ssm_dt", "mlp_gate", "mlp_down",
)


def multipliers(cfg: Dict[str, Any], drop: Optional[str] = None) -> Dict[str, float]:
    """Every multiplier by the name the switches use; ``drop`` runs as 1."""
    sm, mm = cfg["ssm_multipliers"], cfg["mlp_multipliers"]
    m = {
        "embedding": cfg["embedding_multiplier"], "lm_head": cfg["lm_head_multiplier"],
        "attention_in": cfg["attention_in_multiplier"],
        "attention_out": cfg["attention_out_multiplier"], "key": cfg["key_multiplier"],
        "ssm_in": cfg["ssm_in_multiplier"], "ssm_out": cfg["ssm_out_multiplier"],
        "ssm_z": sm[0], "ssm_x": sm[1], "ssm_B": sm[2], "ssm_C": sm[3], "ssm_dt": sm[4],
        "mlp_gate": mm[0], "mlp_down": mm[1],
    }
    if drop is not None:
        if drop not in m:
            raise KeyError(f"no multiplier named {drop!r}")
        m[drop] = 1.0
    return {k: float(v) for k, v in m.items()}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, cos, sin):
    """x [T, heads, d]; cos/sin [T, 1, d/2]: rotate-half."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _up(p):
    return jax.tree_util.tree_map(lambda w: w.astype(F32), p)


@partial(jax.jit, static_argnames=(
    "heads", "head", "state", "groups", "kernel", "eps",
    "state_bits", "norm_before_gate", "no_D", "no_conv_bias", "groups_as_one"))
def _mixer(p, u, m_in, m_out, mup, n_state, *, heads, head, state, groups, kernel, eps,
           state_bits=None, norm_before_gate=False, no_D=False,
           no_conv_bias=False, groups_as_one=False):
    """The state-space mixer's output o_s [T, hidden] from the normalised
    input u [T, hidden], the recurrent state [heads, head, state] after the
    first ``n_state`` tokens, and each head's mean ``dt |A|`` over them (its
    state forgets in about 1 / that many tokens). The multipliers are values, not constants:
    a dropped one is the same program."""
    with jax.default_matmul_precision("highest"):
        p = _up(p)
        T = u.shape[0]
        d, bc = heads * head, groups * state
        widths = (d, d, bc, bc, heads)
        scale = jnp.concatenate([jnp.full((w,), mup[i], F32) for i, w in enumerate(widths)])
        proj = ((u * m_in) @ p["w_inproj"]) * scale
        z, xBC, dt = proj[:, :d], proj[:, d:d + d + 2 * bc], proj[:, d + d + 2 * bc:]
        # causal depthwise convolution as `kernel` shifted products
        padded = jnp.concatenate([jnp.zeros((kernel - 1, xBC.shape[1]), F32), xBC])
        conv = sum(padded[j:j + T] * p["conv_w"][j] for j in range(kernel))
        if not no_conv_bias:
            conv = conv + p["conv_b"]
        conv = jax.nn.silu(conv)
        x = conv[:, :d].reshape(T, heads, head)
        B = conv[:, d:d + bc].reshape(T, groups, state)
        C = conv[:, d + bc:].reshape(T, groups, state)
        per = heads // groups
        if groups_as_one:
            B, C = jnp.repeat(B[:, :1], heads, axis=1), jnp.repeat(C[:, :1], heads, axis=1)
        else:
            B, C = jnp.repeat(B, per, axis=1), jnp.repeat(C, per, axis=1)   # [T, heads, state]
        dt = jax.nn.softplus(dt + p["dt_bias"])                             # [T, heads]
        A = -jnp.exp(p["A_log"])

        def token(carry, inp):
            S, kept = carry
            x_t, B_t, C_t, dt_t, t = inp
            S = (jnp.exp(dt_t * A)[:, None, None] * S
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
            if state_bits is not None:
                # not astype there and back: XLA may elide that pair
                e, m = {16: (8, 7), 8: (5, 2)}[state_bits]
                S = jax.lax.reduce_precision(S, exponent_bits=e, mantissa_bits=m)
            return (S, jnp.where(t < n_state, S, kept)), jnp.sum(S * C_t[:, None, :], axis=-1)

        zero = jnp.zeros((heads, head, state), F32)
        (_, S_kept), y = jax.lax.scan(token, (zero, zero), (x, B, C, dt, jnp.arange(T)))
        if not no_D:
            y = y + p["D"][:, None] * x
        y = y.reshape(T, d)
        n_norm = 1 if groups_as_one else groups

        def group_norm(v):
            g = v.reshape(T, n_norm, d // n_norm)
            g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            return g.reshape(T, d) * p["ssm_norm"]

        if norm_before_gate:
            y = group_norm(y) * jax.nn.silu(z)
        else:
            y = group_norm(y * jax.nn.silu(z))
        # a head's decay a token, exp(-rate), over the tokens the state took
        rate = jnp.sum(jnp.where(jnp.arange(T)[:, None] < n_state, dt, 0.0), axis=0) * -A / n_state
        return (y @ p["w_outproj"]) * m_out, S_kept, rate


def _fake_quant_int8(x):
    """What a cache held at 8 bits would return: per (16-token page, head)
    symmetric int8."""
    T, h, d = x.shape
    xp = jnp.pad(x, ((0, (-T) % 16), (0, 0), (0, 0))).reshape(-1, 16, h, d)
    amax = jnp.max(jnp.abs(xp), axis=(1, 3), keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return (jnp.round(xp / scale).clip(-127, 127) * scale).reshape(-1, h, d)[:T]


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "cache_bits"))
def _attention(p, u, cos, sin, m_in, m_key, m_out, *, n_heads, n_kv, head_dim, cache_bits=None):
    with jax.default_matmul_precision("highest"):
        p = _up(p)
        T = u.shape[0]
        ua = u * m_in
        q = (ua @ p["wq"]).reshape(T, n_heads, head_dim)
        k = ((ua @ p["wk"]) * m_key).reshape(T, n_kv, head_dim)
        v = (ua @ p["wv"]).reshape(T, n_kv, head_dim)
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        if cache_bits == 8:
            k, v = _fake_quant_int8(k), _fake_quant_int8(v)
        kr = jnp.repeat(k, n_heads // n_kv, axis=1)
        vr = jnp.repeat(v, n_heads // n_kv, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, kr) / math.sqrt(head_dim)
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], scores, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vr)
        return (a.reshape(T, n_heads * head_dim) @ p["wo"]) * m_out, k, v


@partial(jax.jit, static_argnames=("eps",))
def _mlp(p, x, m_gate, m_down, *, eps):
    with jax.default_matmul_precision("highest"):
        p = _up(p)
        v = _rms_norm(x, p["ff_norm"], eps)
        return ((jax.nn.silu((v @ p["w_gate"]) * m_gate) * (v @ p["w_up"])) @ p["w_down"]) * m_down


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, head, x, m_head, *, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, final_norm.astype(F32), eps)
        return jax.nn.log_softmax((x @ head.astype(F32)) * m_head, axis=-1)


_MIXER_KEYS = ("w_inproj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "ssm_norm", "w_outproj")
_ATTN_KEYS = ("wq", "wk", "wv", "wo")
_MLP_KEYS = ("ff_norm", "w_gate", "w_up", "w_down")


def logprobs(cfg: Dict[str, Any], params: Dict[str, Any], token_ids: Sequence[int],
             rows: Sequence[int], pad_to: Optional[int] = None, device=None,
             skip_layer: Optional[int] = None, drop: Optional[str] = None,
             cache_bits: Optional[int] = None, held_after: Optional[int] = None,
             **mixer_switches):
    """Log-probabilities [len(rows), vocab] of the next token after each
    position in ``rows``, from one full forward over ``token_ids``. With
    ``held_after=n`` also what a server that has taken the first ``n`` tokens
    would hold, a layer (a skipped layer holds nothing): the recurrent state
    ``ssm`` [heads, head, state] after token ``n - 1`` with each head's
    ``rate`` (its mean ``dt |A|``), and ``k``, ``v`` [T, kv heads, head_dim]
    as its cache would return them.

    ``pad_to`` pads the sequence (causal attention, a causal convolution and
    a recurrence: positions after the last real one cannot touch earlier
    ones) so that every prompt compiles the same shapes."""
    eps = float(cfg["rms_norm_eps"])
    head_dim = int(cfg["head_dim"])
    m = multipliers(cfg, drop)
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    ids = np.asarray(token_ids, np.int32)
    if pad_to is not None and pad_to > len(ids):
        ids = np.concatenate([ids, np.zeros(pad_to - len(ids), np.int32)])
    half = head_dim // 2
    inv = 1.0 / (float(cfg["rope_theta"]) ** (np.arange(0, half, dtype=np.float64) / half))
    ang = np.arange(len(ids), dtype=np.float64)[:, None] * inv[None, :]
    cos, sin = put(np.cos(ang)[:, None, :].astype(np.float32)), put(np.sin(ang)[:, None, :].astype(np.float32))
    x = put(params["embed"])[put(ids)].astype(F32) * m["embedding"]
    held: Dict[str, List[Any]] = {"ssm": [], "rate": [], "k": [], "v": []}
    for i, lp in enumerate(params["layers"]):
        if i == skip_layer:
            for kept in held.values():
                kept.append(None)
            continue
        u = _rms_norm(x, put(lp["in_norm"]).astype(F32), eps)
        o_s, S, rate = _mixer(
            {k: put(lp[k]) for k in _MIXER_KEYS}, u, m["ssm_in"], m["ssm_out"],
            np.asarray([m["ssm_z"], m["ssm_x"], m["ssm_B"], m["ssm_C"], m["ssm_dt"]], np.float32),
            len(ids) if held_after is None else held_after,
            heads=int(cfg["mamba_n_heads"]), head=int(cfg["mamba_d_head"]),
            state=int(cfg["mamba_d_state"]), groups=int(cfg["mamba_n_groups"]),
            kernel=int(cfg["mamba_d_conv"]), eps=eps, **mixer_switches,
        )
        o_a, k, v = _attention(
            {k: put(lp[k]) for k in _ATTN_KEYS}, u, cos, sin,
            m["attention_in"], m["key"], m["attention_out"],
            n_heads=int(cfg["num_attention_heads"]), n_kv=int(cfg["num_key_value_heads"]),
            head_dim=head_dim, cache_bits=cache_bits,
        )
        if held_after is not None:
            held["ssm"].append(S), held["rate"].append(rate)
            held["k"].append(k), held["v"].append(v)
        x = x + o_s + o_a
        x = x + _mlp({k: put(lp[k]) for k in _MLP_KEYS}, x, m["mlp_gate"], m["mlp_down"], eps=eps)
    out = _head(put(params["final_norm"]), put(params["lm_head"]), x[np.asarray(rows)],
                m["lm_head"], eps=eps)
    return np.asarray(out) if held_after is None else (np.asarray(out), held)


@jax.jit
def _slot_of(S, slots):
    """The slot that holds S [heads, head, state]; ``slots`` [n, heads, state,
    head] as the server lays them (the state dimension before the head's)."""
    return jnp.argmin(jnp.sum((slots - S.transpose(0, 2, 1)[None]) ** 2, axis=(1, 2, 3)))


@jax.jit
def _state_differences(S, slots, slot):
    """A head's || held - S || / || S ||: [heads]."""
    d = slots[slot].transpose(0, 2, 1) - S
    return jnp.sqrt(jnp.sum(d * d, axis=(1, 2)) / jnp.sum(S * S, axis=(1, 2)))


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


def held_differences(ref: Dict[str, List[Any]], held: Dict[str, List[Any]], n: int):
    """How far what the server HOLDS for a request that has taken ``n`` tokens
    lies from what the reference would hold (``logprobs(held_after=n)``), as
    relative norms: (a layer a state-space head [layers, heads], those
    heads' rates beside them, a layer for keys and for values [layers, 2]). ``held``: a layer's array each, ``ssm``
    [slots, heads, state, head], ``k`` and ``v`` [pages, page, kv heads,
    head_dim]. The request's slot and pages are found by content, on the
    first layer the reference ran: the nearest slot, and the nearest page to
    each of its FULL pages (the last one may hold what a later step wrote);
    a layer's reading for keys or values is its median page's."""
    layers = [i for i, S in enumerate(ref["ssm"]) if S is not None]
    first = layers[0]
    slot = _slot_of(ref["ssm"][first], held["ssm"][first])
    ids = _pages_of(ref["k"][first], held["k"][first])
    full = n // held["k"][first].shape[1]
    state = [np.asarray(_state_differences(ref["ssm"][i], held["ssm"][i], slot)) for i in layers]
    cache = [[float(_cache_difference(ref[w][i], held[w][i], ids, full)) for w in ("k", "v")]
             for i in layers]
    return np.stack(state), np.stack([np.asarray(ref["rate"][i]) for i in layers]), np.asarray(cache)


def wrong_variants(cfg: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Every mistake the switches compute, by name (``skip_layer`` is the
    harness's own first slot)."""
    out: Dict[str, Dict[str, Any]] = {
        "cache_int8": {"cache_bits": 8}, "state_bf16": {"state_bits": 16},
        "state_fp8": {"state_bits": 8}, "cache_int8_state_fp8": {"cache_bits": 8, "state_bits": 8},
    }
    for name in MULTIPLIERS:
        out[f"drop_{name}"] = {"drop": name}
    for name in ("norm_before_gate", "no_D", "no_conv_bias", "groups_as_one"):
        out[name] = {name: True}
    return out


# each limit of ``reference_tolerance`` and the readings it bounds
LIMITS = (
    ("worst_nat", "worst_tolerance_nat", ("worst_logprob_difference_nat", "worst_argmax_gap_nat")),
    ("mean_nat", "mean_tolerance_nat", ("mean_logprob_difference_nat",)),
    ("median_nat", "median_tolerance_nat", ("median_logprob_difference_nat",)),
    ("slow_state_rel", "slow_state_tolerance_rel", ("slowest_head_state_difference",)),
    ("first_cache_rel", "first_cache_tolerance_rel", ("first_layer_cache_difference",)),
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
    state: List[np.ndarray] = []
    rates: List[np.ndarray] = []
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
            by_head, rate, by_layer = held_differences(would_hold, held, len(seq) - 1)
            state.append(by_head), rates.append(rate), cache.append(by_layer)
    tol = cfg["reference_tolerance"]
    res: Dict[str, Any] = {
        "tokens_compared": len(diffs),
        "worst_logprob_difference_nat": max(diffs, default=0.0),
        "worst_argmax_gap_nat": worst_gap,
        "mean_logprob_difference_nat": float(np.mean(diffs)) if diffs else 0.0,
        "median_logprob_difference_nat": float(np.median(diffs)) if diffs else 0.0,
    }
    if state:
        # [samples, layers, heads] twice, [samples, layers, keys | values]
        st, ra, ca = np.stack(state), np.stack(rates), np.stack(cache)
        # WHERE a lower precision of what is held shows. A state rounded a
        # token compounds in the heads that forget slowest, and there the
        # honest engine's own rounding (of each token's inputs, which does
        # not compound) averages out: a layer's SLOWEST head (least mean
        # dt |A|), the worst over layers and samples. A cache's rounding is a
        # fixed share of a key, and every layer above the first adds its own
        # bf16 rounding to the keys it is handed: the FIRST layer's pages
        slow = np.take_along_axis(st, ra.argmin(axis=2)[..., None], axis=2)[..., 0]
        res.update({
            "slowest_head_state_difference": float(slow.max()),
            "slowest_head_state_difference_by_layer": [float(v) for v in slow.max(axis=0)],
            "slowest_head_rate_by_layer": [float(v) for v in ra.min(axis=2).mean(axis=0)],
            "worst_state_difference": float(st.max()),
            "mean_state_difference": float(st.mean()),
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
