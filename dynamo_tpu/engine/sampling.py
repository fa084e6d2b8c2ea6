"""On-device batched sampling: greedy / temperature / top-k / top-p / min-p,
frequency / presence / repetition penalties, and top-N logprobs.

Logits never leave the device (vocab-sized transfers per step would saturate
the host link); only sampled token ids (+ small top-k logprob rows) come back.
All branches are tensor-masked — no data-dependent *shapes* — but the
expensive paths (full-vocab sort for top-k/top-p, [B,V] gumbel draw, [B,V]
penalty tables) are gated behind ``lax.cond`` on whether any request in the
batch actually enables them, so a greedy batch pays only an argmax. This
mirrors how the reference folds per-request sampling options in its
preprocessor (lib/llm/src/preprocessor.rs) and leaves the hot loop branchless.

Penalty semantics match vLLM/OpenAI:
- repetition_penalty: tokens seen in prompt OR output; logit>0 ? l/r : l*r
- frequency_penalty:  logits -= fp * count(token in output)
- presence_penalty:   logits -= pp * (token in output)

A step program (engine ``_build_programs``) is a body, which runs the layers,
and an EPILOGUE, which turns ``hidden`` into tokens. Both epilogues are here,
beside the primitives they compose: ``rows_epilogue`` (decode rows) and
``first_token_epilogue`` (a prompt's final chunk). Nothing here knows the engine.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# top-N logprobs rows returned by the decode program when any request asks
# for them. OpenAI's schema allows top_logprobs up to 20, so the on-device
# top_k matches — requests are never silently clamped below what the API
# validated (lib/llm/src/protocols/openai/chat_completions/delta.rs analog).
TOP_LOGPROBS_K = 20


class SlotSampling(NamedTuple):
    """The per-slot arrays every step program takes: ``[B]`` each,
    ``prompt_masks`` ``[B, V]``, ``proc_masks`` ``[B, processors]``; and the
    guided tables where guidance is built in (``g_active is None`` is a
    TRACE-time branch: an engine without it has none of the mask ops)."""

    seeds: jax.Array
    temps: jax.Array
    top_ks: jax.Array
    top_ps: jax.Array
    min_ps: jax.Array
    pres: jax.Array
    freqs: jax.Array
    reps: jax.Array
    prompt_masks: jax.Array
    proc_masks: jax.Array
    g_active: Optional[jax.Array] = None   # [B] bool
    g_class: Optional[jax.Array] = None    # [B, V] token -> class
    g_trans: Optional[jax.Array] = None    # [B, S, C] state, class -> state


def pen_need(presence, frequency, repetition) -> jax.Array:
    """Scalar bool: some row has a penalty on."""
    return jnp.any(
        (presence != 0.0) | (frequency != 0.0) | (repetition != 1.0)
    )


def apply_penalties(
    logits: jax.Array,             # [B, V] float32
    output_counts: jax.Array,      # [B, V] int32 generated-token counts
    prompt_mask: jax.Array,        # [B, V] int8/bool tokens present in prompt
    presence: jax.Array,           # [B]
    frequency: jax.Array,          # [B]
    repetition: jax.Array,         # [B]
) -> jax.Array:
    """Returns penalized logits. Free (one cond + passthrough) when the whole
    batch has penalties disabled."""

    def with_pen(l):
        counts_f = output_counts.astype(jnp.float32)
        out_seen = output_counts > 0
        seen = out_seen | (prompt_mask != 0)
        rep = jnp.where(l > 0, l / repetition[:, None], l * repetition[:, None])
        l = jnp.where(seen, rep, l)
        l = l - frequency[:, None] * counts_f
        l = l - presence[:, None] * out_seen.astype(jnp.float32)
        return l

    need = pen_need(presence, frequency, repetition)
    return jax.lax.cond(need, with_pen, lambda l: l, logits)


def _mask_topk_topp(
    logits: jax.Array,       # [B, V] (already penalized)
    temp_safe: jax.Array,    # [B, 1] clamped temperature
    top_k: jax.Array,        # [B] <=0 => disabled
    top_p: jax.Array,        # [B] >=1 => disabled
) -> jax.Array:
    """One descending sort serves both filters: the top-k cutoff is the kth
    sorted value; top-p is computed over the top-k-surviving prefix of the
    same sorted array (softmax in sorted order, cumulative mass)."""
    B, V = logits.shape
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]              # [B, V]

    k_eff = jnp.where(top_k <= 0, V, jnp.minimum(top_k, V))       # [B]
    k_idx = jnp.clip(k_eff - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)  # [B,1]

    # top-p over the top-k set: positions >= k are excluded from the mass
    rank = jnp.arange(V)[None, :]
    in_topk = rank < k_eff[:, None]
    sorted_scaled = jnp.where(in_topk, sorted_desc / temp_safe, NEG_INF)
    probs_sorted = jax.nn.softmax(sorted_scaled, axis=-1)
    cumprobs = jnp.cumsum(probs_sorted, axis=-1)
    p = jnp.where(top_p >= 1.0, 1.0, top_p)[:, None]
    include = (cumprobs - probs_sorted < p) & in_topk
    count = jnp.maximum(include.sum(axis=-1), 1)                  # [B]
    cutoff_p = jnp.take_along_axis(sorted_desc, (count - 1)[:, None], axis=-1)

    cutoff = jnp.maximum(kth, cutoff_p)
    return jnp.where(logits >= cutoff, logits, NEG_INF)


def sample_tokens(
    logits: jax.Array,        # [B, V] float32
    seeds: jax.Array,         # [B] uint32 per-request seed
    steps: jax.Array,         # [B] int32 decode position (key = fold_in(seed, step))
    temperature: jax.Array,   # [B] 0 => greedy
    top_k: jax.Array,         # [B] int32, <=0 => disabled
    top_p: jax.Array,         # [B] float32, >=1 => disabled
    min_p: Optional[jax.Array] = None,  # [B] float32, <=0 => disabled
) -> jax.Array:
    """Returns sampled token ids [B] int32.

    Keys are derived statelessly from (seed, step): a seeded request
    reproduces its exact sample stream regardless of what else is in the
    batch or how long the engine has been running."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_branch(l):
        temp_safe = jnp.maximum(temperature, 1e-6)[:, None]

        need_sort = jnp.any((top_k > 0) | (top_p < 1.0))
        l = jax.lax.cond(
            need_sort,
            lambda x: _mask_topk_topp(x, temp_safe, top_k, top_p),
            lambda x: x,
            l,
        )
        if min_p is not None:
            # p_i/p_max >= min_p  <=>  l_i >= l_max + temp*ln(min_p)
            max_l = jnp.max(l, axis=-1, keepdims=True)
            mp = jnp.clip(min_p, 1e-10, 1.0)[:, None]
            thresh = max_l + temp_safe * jnp.log(mp)
            l = jnp.where(
                (min_p > 0.0)[:, None] & (l < thresh), NEG_INF, l
            )

        def row_gumbel(seed, step):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            return jax.random.gumbel(key, (V,), dtype=jnp.float32)

        gumbel = jax.vmap(row_gumbel)(seeds, steps)
        return jnp.argmax(l / temp_safe + gumbel, axis=-1).astype(jnp.int32)

    any_sampled = jnp.any(temperature > 0.0)
    sampled = jax.lax.cond(
        any_sampled, sampled_branch, lambda l: greedy, logits
    )
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


def logprobs_of(
    logits: jax.Array,        # [B, V] float32 (pre-penalty logits)
    token_ids: jax.Array,     # [B] the chosen tokens
) -> jax.Array:
    """Log-probability of each chosen token [B]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, token_ids[:, None].astype(jnp.int32), axis=-1)[:, 0]


def top_logprobs(
    logits: jax.Array,        # [B, V] float32
    need: jax.Array,          # scalar bool: any request wants top logprobs
    k: int = TOP_LOGPROBS_K,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k (ids, logprobs) rows, or zeros when nobody asked ([B,k] each).

    The cond keeps the top_k scan off the hot path for batches that don't
    request logprobs."""
    B, V = logits.shape

    def compute(l):
        vals, ids = jax.lax.top_k(l, k)
        lse = jax.nn.logsumexp(l, axis=-1, keepdims=True)
        return (vals - lse), ids.astype(jnp.int32)

    def zeros(l):
        return jnp.zeros((B, k), jnp.float32), jnp.zeros((B, k), jnp.int32)

    return jax.lax.cond(need, compute, zeros, logits)


def update_counts(
    output_counts: jax.Array,  # [B, V] int32
    tokens: jax.Array,         # [B] sampled this step
    active: jax.Array,         # [B] bool
    need: jax.Array,           # scalar bool: any penalties enabled
) -> jax.Array:
    """Scatter-add the sampled tokens into the per-slot output counts (only
    maintained while some request has penalties on)."""

    def upd(c):
        rows = jnp.arange(c.shape[0])
        return c.at[rows, tokens].add(active.astype(jnp.int32))

    return jax.lax.cond(need, upd, lambda c: c, output_counts)


def counts_need(procs, presence, frequency, repetition, proc_masks) -> jax.Array:
    """output_counts must be maintained for penalties AND for any opted-in
    logits processor (processors read counts as documented on-device state:
    logits_processing/)."""
    need = pen_need(presence, frequency, repetition)
    if procs:
        need = need | jnp.any(proc_masks)
    return need


def run_procs(procs, logits, masks, counts, steps, seq_lens) -> jax.Array:
    """``procs`` (``TpuEngineConfig.logits_processors``) over the rows that
    opted in (``masks``); no processors is a TRACE-time branch."""
    if not procs:
        return logits
    from ..logits_processing import apply_processors

    return apply_processors(procs, masks, logits, {
        "output_counts": counts, "steps": steps, "seq_lens": seq_lens,
    })


# guided decoding: one [B, C] row gather + one [B, V] class lookup a step
def gmask(logits, g_active, g_state, g_class, g_trans) -> jax.Array:
    """Mask logits to the tokens legal from each row's FSM state."""
    row = jnp.take_along_axis(
        g_trans, g_state[:, None, None], axis=1
    )[:, 0]                                             # [B, C]
    ok = jnp.take_along_axis(row, g_class, axis=1) >= 0  # [B, V]
    return jnp.where(g_active[:, None] & ~ok, NEG_INF, logits)


def gstep(g_state, toks, g_active, g_class, g_trans) -> jax.Array:
    """Advance each row's FSM by its sampled token."""
    cls = jnp.take_along_axis(g_class, toks[:, None], axis=1)[:, 0]
    row = jnp.take_along_axis(
        g_trans, g_state[:, None, None], axis=1
    )[:, 0]
    nxt = jnp.take_along_axis(row, cls[:, None], axis=1)[:, 0]
    return jnp.where(g_active, jnp.maximum(nxt, 0), g_state)


def rows_epilogue(
    logits: jax.Array,         # [B, V] float32
    s: SlotSampling,
    counts: jax.Array,         # [B, V] int32 output counts
    steps: jax.Array,          # [B] int32 tokens sampled so far (the key)
    seq_lens: jax.Array,       # [B] int32, 0 = an empty row
    lp_need: jax.Array,        # scalar bool: some row wants top logprobs
    *,
    active: Optional[jax.Array] = None,   # [B] bool; None: seq_lens > 0
    procs: Tuple[Tuple[str, Any], ...] = (),
    g_state: Optional[jax.Array] = None,  # [B] int32 FSM states (guided)
    advance_guided: bool = False,
    need: Optional[jax.Array] = None,
):
    """The decode rows' tail: penalties, processors, guided mask, sample,
    counts, logprob, top logprobs. Returns ``toks, lps, tlp_vals, tlp_ids,
    counts, g_state``; ``g_state`` is advanced by the sampled tokens where
    ``advance_guided`` (a horizon: the host walks it a horizon late), else
    as it was given. ``need``: ``counts_need`` of these rows, for a caller
    that computes it once outside its scan. An inactive row samples (its
    token is discarded by the host) and leaves ``counts`` alone."""
    pen = apply_penalties(
        logits, counts, s.prompt_masks, s.pres, s.freqs, s.reps
    )
    pen = run_procs(procs, pen, s.proc_masks, counts, steps, seq_lens)
    if s.g_active is not None:
        pen = gmask(pen, s.g_active, g_state, s.g_class, s.g_trans)
    toks = sample_tokens(
        pen, s.seeds, steps, s.temps, s.top_ks, s.top_ps, s.min_ps
    )
    if advance_guided and s.g_active is not None:
        g_state = gstep(g_state, toks, s.g_active, s.g_class, s.g_trans)
    counts = update_counts(
        counts, toks,
        seq_lens > 0 if active is None else active,
        counts_need(procs, s.pres, s.freqs, s.reps, s.proc_masks)
        if need is None else need,
    )
    lps = logprobs_of(logits, toks)
    tlp_vals, tlp_ids = top_logprobs(logits, lp_need)
    return toks, lps, tlp_vals, tlp_ids, counts, g_state


def first_token_epilogue(
    logits_fn: Callable[[jax.Array], jax.Array],  # [n, H] -> [n, V]
    hidden: jax.Array,         # [S, H]: the chunk's rows first
    positions: jax.Array,      # [S_pad] absolute positions of the chunk
    total_len: jax.Array,      # scalar: the context once the chunk is in
    slot: jax.Array,           # scalar: the request's row of ``s``
    is_final: jax.Array,       # scalar bool: the prompt ends in this chunk
    lp_need: jax.Array,        # scalar bool: the request wants top logprobs
    s: SlotSampling,
    counts: jax.Array,         # [B, V] int32, ``slot``'s row reset to zero
    *,
    procs: Tuple[Tuple[str, Any], ...] = (),
    g_state: Optional[jax.Array] = None,  # scalar int32: the FSM state
):
    """A chunk's tail: on a prompt's final chunk the first generated token,
    sampled as a decode row is from the one-row batch read at ``slot`` (no
    output counts yet, step 0). Returns ``counts, tok, lp, tlp_vals,
    tlp_ids`` (scalars and ``[K]`` rows). An intermediate chunk samples
    nothing: zeros, the ``counts`` it was given, and no vocabulary product
    (why this takes ``logits_fn`` and not logits)."""

    def row(x):
        return x[slot][None]

    def sample_branch(counts):
        # logits at the last real token (positions are absolute; the last
        # real new token sits where position == total_len - 1)
        last_idx = jnp.argmax(positions == total_len - 1)
        logits = logits_fn(hidden[last_idx][None])  # [1, V]
        pen = apply_penalties(
            logits, jnp.zeros_like(logits, jnp.int32), row(s.prompt_masks),
            row(s.pres), row(s.freqs), row(s.reps),
        )
        pen = run_procs(
            procs, pen, row(s.proc_masks), row(counts),
            jnp.zeros((1,), jnp.int32), total_len[None],
        )
        if s.g_active is not None:
            # FSM at g_state (0, or past a resume's prior tokens). The full
            # [B, ...] tables read at slot: the device-resident unit the
            # decode rows use, which multihost replays as shared state
            pen = gmask(
                pen, row(s.g_active), jnp.full((1,), g_state, jnp.int32),
                row(s.g_class), row(s.g_trans),
            )
        tok = sample_tokens(
            pen, row(s.seeds), jnp.zeros((1,), jnp.int32), row(s.temps),
            row(s.top_ks), row(s.top_ps), row(s.min_ps),
        )
        # the first generated token must enter the output counts, or the
        # first decode step's penalties miss it
        counts = jax.lax.cond(
            counts_need(
                procs, row(s.pres), row(s.freqs), row(s.reps),
                row(s.proc_masks),
            ),
            lambda c: c.at[slot, tok[0]].add(1),
            lambda c: c,
            counts,
        )
        lp = logprobs_of(logits, tok)
        tlp_vals, tlp_ids = top_logprobs(logits, lp_need)
        return counts, tok[0], lp[0], tlp_vals[0], tlp_ids[0]

    def no_sample(counts):
        K = TOP_LOGPROBS_K
        return (
            counts, jnp.int32(0), jnp.float32(0.0),
            jnp.zeros((K,), jnp.float32), jnp.zeros((K,), jnp.int32),
        )

    return jax.lax.cond(is_final, sample_branch, no_sample, counts)
