"""The two epilogues of a step program (engine/sampling.py), without an
engine: ``rows_epilogue`` turns the decode rows' logits into tokens,
``first_token_epilogue`` a final chunk's hidden state into a request's first
token. The engine's programs call these and nothing else behind ``hidden``;
tests/test_pp_serving.py and tests/test_mixed_batching.py pin the bodies.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.sampling import (
    TOP_LOGPROBS_K,
    SlotSampling,
    first_token_epilogue,
    rows_epilogue,
)
from dynamo_tpu.logits_processing import ban_tokens_processor

B, V, H = 4, 64, 16
BAN = (("ban", ban_tokens_processor([3, 5])),)


def rows(b=B, **over):
    """The per-slot arrays of ``b`` slots with everything off (greedy, no
    penalty, no processor, no guidance); ``over`` sets whole arrays."""
    base = dict(
        seeds=np.arange(b, dtype=np.uint32) + 11,
        temps=np.zeros(b, np.float32), top_ks=np.zeros(b, np.int32),
        top_ps=np.ones(b, np.float32), min_ps=np.zeros(b, np.float32),
        pres=np.zeros(b, np.float32), freqs=np.zeros(b, np.float32),
        reps=np.ones(b, np.float32), prompt_masks=np.zeros((b, V), np.int8),
        proc_masks=np.zeros((b, 1), bool),
    )
    base.update(over)
    return SlotSampling(**{k: jnp.asarray(v) for k, v in base.items()})


def logits_of(b=B, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(b, V)).astype(np.float32) * 3
    )


def run_rows(logits, s, counts=None, steps=None, seq_lens=None, lp=False, **kw):
    b = logits.shape[0]
    return rows_epilogue(
        logits, s,
        jnp.zeros((b, V), jnp.int32) if counts is None else counts,
        jnp.zeros(b, jnp.int32) if steps is None else jnp.asarray(steps),
        jnp.full(b, 9, jnp.int32) if seq_lens is None else jnp.asarray(seq_lens),
        jnp.bool_(lp), **kw,
    )


@pytest.mark.parametrize("lp", [False, True])
def test_greedy_rows_are_the_argmax_and_carry_its_logprob(lp):
    logits = logits_of()
    toks, lps, tlp_vals, tlp_ids, counts, g = run_rows(logits, rows(), lp=lp)
    want = np.argmax(np.asarray(logits), axis=-1)
    assert np.array_equal(toks, want)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    np.testing.assert_allclose(lps, logp[np.arange(B), want], rtol=1e-6)
    assert tlp_vals.shape == tlp_ids.shape == (B, TOP_LOGPROBS_K)
    if lp:  # the top row leads with the greedy token
        assert np.array_equal(tlp_ids[:, 0], want)
        np.testing.assert_allclose(tlp_vals[:, 0], lps, rtol=1e-6)
    else:   # nobody asked: zeros, the top-k scan skipped
        assert not np.any(tlp_vals) and not np.any(tlp_ids)
    assert not np.any(counts) and g is None  # nothing to count, no FSM


@pytest.mark.parametrize("beside", ["greedy rows", "sampled rows", "other logits"])
def test_a_seeded_temperature_row_samples_the_same_whatever_sits_beside_it(beside):
    logits = logits_of()
    temps = np.zeros(B, np.float32)
    temps[2] = 0.9
    alone = run_rows(logits[2:3], rows(1, seeds=[77], temps=[0.9]), steps=[5])[0]
    if beside == "sampled rows":
        temps[:] = 1.3
        temps[2] = 0.9
    if beside == "other logits":
        logits = logits.at[jnp.asarray([0, 1, 3])].set(logits_of(3, seed=9))
    seeds = np.array([1, 2, 77, 4], np.uint32)
    got = run_rows(logits, rows(seeds=seeds, temps=temps), steps=[0, 1, 5, 2])[0]
    assert int(got[2]) == int(alone[0])
    # and the step is part of the key
    later = [
        int(run_rows(logits[2:3], rows(1, seeds=[77], temps=[0.9]), steps=[k])[0][0])
        for k in range(6, 14)
    ]
    assert len({int(alone[0]), *later}) > 1


@pytest.mark.parametrize("what, over, procs, counted", [
    ("nothing on", {}, (), False),
    ("presence", dict(pres=[0, 0.5, 0, 0]), (), True),
    ("frequency", dict(freqs=[0, 0, 0.2, 0]), (), True),
    ("repetition", dict(reps=[1, 1, 1, 1.3]), (), True),
    ("a processor nobody opted into", {}, BAN, False),
    ("an opted-in processor", dict(proc_masks=[[False], [True], [False], [False]]), BAN, True),
    ("a mask without processors", dict(proc_masks=[[True]] * 4), (), False),
])
def test_counts_move_only_where_counts_need_says(what, over, procs, counted):
    over = {k: np.asarray(v, np.float32 if k != "proc_masks" else bool) for k, v in over.items()}
    before = jnp.asarray(np.random.default_rng(1).integers(0, 3, (B, V)), jnp.int32)
    toks, *_, counts, _ = run_rows(logits_of(), rows(**over), counts=before, procs=procs)
    moved = np.asarray(counts) - np.asarray(before)
    if not counted:
        assert not np.any(moved)
        return
    want = np.zeros((B, V), np.int32)
    want[np.arange(B), np.asarray(toks)] = 1   # every live row, not the opted-in one alone
    assert np.array_equal(moved, want)


def test_a_penalty_and_a_processor_change_what_is_sampled():
    logits = logits_of()
    top = np.argmax(np.asarray(logits), axis=-1)
    # a frequency penalty on a token already produced four times unseats it
    counts = jnp.zeros((B, V), jnp.int32).at[1, top[1]].set(4)
    toks = run_rows(logits, rows(freqs=[0, 5.0, 0, 0]), counts=counts)[0]
    assert int(toks[1]) != top[1] and np.array_equal(np.delete(toks, 1), np.delete(top, 1))
    # a processor touches the rows that opted in, and no other
    boosted = logits.at[:, 3].add(100.0)
    toks = run_rows(
        boosted, rows(proc_masks=[[True], [False], [True], [False]]), procs=BAN
    )[0]
    assert [int(t) == 3 for t in toks] == [False, True, False, True]


@pytest.mark.parametrize("how", ["active", "seq_lens"])
def test_an_inactive_row_leaves_counts_alone(how):
    live = np.array([True, False, True, False])
    kw = (
        dict(active=jnp.asarray(live)) if how == "active"
        else dict(seq_lens=np.where(live, 9, 0).astype(np.int32))  # the default: a row with a context
    )
    toks, *_, counts, _ = run_rows(logits_of(), rows(pres=np.full(B, 0.1, np.float32)), **kw)
    moved = np.asarray(counts)
    assert moved.sum() == 2 and not moved[~live].any()
    assert all(moved[i, int(toks[i])] == 1 for i in np.flatnonzero(live))
    # computed once by the caller (a horizon, outside its scan), the switch wins
    *_, counts, _ = run_rows(
        logits_of(), rows(pres=np.full(B, 0.1, np.float32)), need=jnp.bool_(False), **kw
    )
    assert not np.any(counts)


def guided_tables(b=B, states=3, classes=4):
    """Token t is of class t % classes. State 0 allows class 1 (-> 1) and
    class 2 (-> 2); state 1 allows class 3 alone (-> 0); state 2 nothing
    but class 0 (-> 2)."""
    g_class = np.tile(np.arange(V, dtype=np.int32) % classes, (b, 1))
    trans = -np.ones((states, classes), np.int32)
    trans[0, 1], trans[0, 2], trans[1, 3], trans[2, 0] = 1, 2, 0, 2
    return g_class, np.tile(trans, (b, 1, 1))


@pytest.mark.parametrize("temp", [0.0, 1.5])
@pytest.mark.parametrize("advance", [False, True])
def test_a_guided_row_samples_legal_tokens_and_its_state_follows(temp, advance):
    g_class, g_trans = guided_tables()
    g_active = np.array([True, True, False, True])
    state = jnp.asarray(np.array([0, 1, 1, 2], np.int32))
    s = rows(temps=np.full(B, temp, np.float32), g_active=g_active, g_class=g_class, g_trans=g_trans)
    legal = {0: {1, 2}, 1: {3}, 2: {0}}  # state -> the classes it allows
    for step in range(6):
        logits = logits_of(seed=step)
        toks, lps, *_, g = run_rows(
            logits, s, steps=np.full(B, step, np.int32), g_state=state, advance_guided=advance
        )
        for r in np.flatnonzero(g_active):
            assert int(toks[r]) % 4 in legal[int(state[r])], (r, int(toks[r]))
        if temp == 0.0:  # an unguided row is the plain argmax
            assert int(toks[2]) == int(np.argmax(np.asarray(logits[2])))
        # the logprob is the model's, not the masked distribution's
        logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        np.testing.assert_allclose(lps, logp[np.arange(B), np.asarray(toks)], rtol=1e-6)
        if not advance:  # the host walks the FSM (decode, a mixed step)
            assert g is state
            continue
        want = [g_trans[r, int(state[r]), int(toks[r]) % 4] if g_active[r] else int(state[r]) for r in range(B)]
        assert np.array_equal(g, want) and min(want) >= 0
        state = g


def chunk(s_pad=8, n_real=5, start=20, seed=3):
    """A bucketed chunk of ``n_real`` tokens from position ``start``, pad
    rows at a far position as the engine lays them out, and a stand-in for
    the family's logits function."""
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.normal(size=(s_pad + B, H)).astype(np.float32))
    positions = np.full(s_pad, 4095, np.int32)
    positions[:n_real] = start + np.arange(n_real)
    w = jnp.asarray(rng.normal(size=(H, V)).astype(np.float32))
    called = []

    def logits_fn(h):
        called.append(h.shape)
        return h @ w

    return hidden, jnp.asarray(positions), jnp.int32(start + n_real), logits_fn, called


def test_an_intermediate_chunk_samples_nothing_and_skips_the_vocabulary():
    hidden, positions, total_len, logits_fn, _ = chunk()
    counts = jnp.asarray(np.random.default_rng(2).integers(0, 3, (B, V)), jnp.int32)
    out = first_token_epilogue(
        logits_fn, hidden, positions, total_len, jnp.int32(1), jnp.bool_(False),
        jnp.bool_(True), rows(pres=np.full(B, 0.3, np.float32)), counts,
    )
    got_counts, tok, lp, tlp_vals, tlp_ids = out
    assert np.array_equal(got_counts, counts)
    assert int(tok) == 0 and float(lp) == 0.0
    assert tlp_vals.shape == tlp_ids.shape == (TOP_LOGPROBS_K,)
    assert not np.any(tlp_vals) and not np.any(tlp_ids)
    # the product is in the final chunk's branch alone
    text = jax.make_jaxpr(
        lambda h, f: first_token_epilogue(
            logits_fn, h, positions, total_len, jnp.int32(1), f, jnp.bool_(True), rows(), counts
        )
    )(hidden, jnp.bool_(False))
    outer = [e.primitive.name for e in text.jaxpr.eqns]
    assert "dot_general" not in outer and "cond" in outer


FIRST = {
    "greedy": (dict(), (), None),
    "sampled": (dict(temps=[0.0, 0.8, 0.0, 0.0], top_ks=[0, 7, 0, 0]), (), None),
    "penalised": (dict(reps=[1.0, 1.6, 1.0, 1.0], pres=[0.0, 0.4, 0.0, 0.0], prompt=True), (), None),
    "processed": (dict(proc_masks=[[False], [True], [False], [False]]), BAN, None),
    "guided": (dict(guided=True), (), 1),
}


@pytest.mark.parametrize("case", sorted(FIRST))
def test_a_final_chunk_samples_as_a_decode_row_of_its_slot_would(case):
    """``is_final``: the rows epilogue on the one-row batch read at ``slot``,
    logits at the chunk's last real token, zero output counts, step 0."""
    over, procs, g_state = FIRST[case]
    over = dict(over)
    if over.pop("prompt", False):
        over["prompt_masks"] = (np.random.default_rng(4).random((B, V)) < 0.5).astype(np.int8)
    if over.pop("guided", False):
        g_class, g_trans = guided_tables()
        over.update(g_active=np.array([False, True, False, False]), g_class=g_class, g_trans=g_trans)
    over = {k: np.asarray(v, np.float32) if k in ("temps", "reps", "pres") else np.asarray(v)
            for k, v in over.items()}
    s, slot, n_real = rows(**over), 1, 5
    hidden, positions, total_len, logits_fn, called = chunk(n_real=n_real)
    # other slots' counts are theirs; this slot's row was reset at admission
    counts = jnp.asarray(np.random.default_rng(2).integers(0, 3, (B, V)), jnp.int32).at[slot].set(0)
    got_counts, tok, lp, tlp_vals, tlp_ids = first_token_epilogue(
        logits_fn, hidden, positions, total_len, jnp.int32(slot), jnp.bool_(True),
        jnp.bool_(True), s, counts, procs=procs,
        g_state=None if g_state is None else jnp.int32(g_state),
    )
    assert called == [(1, H)]  # one row through the vocabulary, not the chunk

    one = SlotSampling(*(None if x is None else x[slot][None] for x in s))
    toks, lps, want_vals, want_ids, want_counts, _ = rows_epilogue(
        logits_fn(hidden[n_real - 1][None]), one, jnp.zeros((1, V), jnp.int32), jnp.zeros(1, jnp.int32), total_len[None],
        jnp.bool_(True), procs=procs,
        g_state=None if g_state is None else jnp.full((1,), g_state, jnp.int32),
    )
    assert int(tok) == int(toks[0])
    np.testing.assert_allclose(lp, lps[0], rtol=1e-6)
    assert np.array_equal(tlp_ids, want_ids[0])
    np.testing.assert_allclose(tlp_vals, want_vals[0], rtol=1e-6)
    # the first token enters the slot's counts where the rows' would, other slots' stay
    assert np.array_equal(got_counts[slot], want_counts[0])
    assert np.array_equal(np.delete(got_counts, slot, 0), np.delete(counts, slot, 0))
    assert int(got_counts[slot].sum()) == (case in ("penalised", "processed"))
    if case == "processed":
        assert int(tok) not in (3, 5)
    if case == "guided":
        assert int(tok) % 4 == 3
