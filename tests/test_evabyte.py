"""EvaByte (models/evabyte.py: exact attention inside a window, one learned
summary a chunk of every window before it) at a test's size: 4 heads x 16, a
window of 256 positions, chunk = page = 16 (a window's 16 summaries fill one
page: the published 128 fill 8), 2 layers, the vocabulary of 320 and 8 heads
of prediction. The family's attention against the dense family's and against
the benchmark's plain float32 reference
(benchmarks/reference/evabyte_decoder.py), the launch against its twin, the
engine with its third kind of state (a ring of pages, summary blocks by
window: engine/allocator.py ``Ring``) through prefill in every bucket, mixed
steps and decode horizons over five windows, what a request holds and gives
back, the counters and the refusals.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import system
from benchmarks.adapters import evabyte as adapter
from benchmarks.reference import evabyte_decoder as ref
from dynamo_tpu.engine.allocator import Ring
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models import evabyte, registry
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import pallas_eva
from dynamo_tpu.ops.paged_attention import PagedAttention
from dynamo_tpu.parallel.mesh import make_mesh

W, C, H, D, L = 256, 16, 4, 16, 2
RING = W // C          # pages of a ring
TOL = 2e-4             # nat, float32 engine against float32 reference


def file_cfg(dtype="float32", **kw):
    """A configuration file's dict (the public keys) at a test's size."""
    cfg = {
        "model_type": "evabyte", "attention_class": "eva", "vocab_size": 320, "hidden_size": 64,
        "num_hidden_layers": L, "num_attention_heads": H, "num_key_value_heads": H, "head_dim": D,
        "intermediate_size": 128, "rope_theta": 100000, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 32768, "tie_word_embeddings": False, "torch_dtype": dtype,
        "window_size": W, "chunk_size": C, "num_pred_heads": 8, "norm_add_unit_offset": True,
        "fp32_skip_add": True, "fp32_logits": True, "mixedp_attn": True, "fp32_ln": False,
        "attention_bias": False, "rope_scaling": None,
        "reference_tolerance": {"worst_nat": TOL, "mean_nat": 2e-5, "median_nat": 2e-5,
                                "first_summary_rel": 1e-5},
    }
    cfg.update(kw)
    return cfg


def engine_of(cfg=None, **kw):
    opts = dict(num_blocks=1 + 2 * RING, block_size=C, max_batch_size=2, max_context=6 * W,
                prefill_buckets=(32, 64, 128), seed=3, use_pallas=False, decode_steps=8,
                decode_pipeline=1, mixed_admission=True)
    opts.update(kw)
    return TpuEngine(TpuEngineConfig(model=adapter.model_config(cfg or file_cfg()), **opts))


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 320, n).tolist() for n in lengths]


def sample(prompt, rec):
    return {"prompt": prompt, "tokens": rec["tokens"], "logprobs": rec["logprobs"]}


def eva_query(key=0, window=W):
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    return att.EvaQuery(jax.random.normal(k1, (H, D)), jax.random.normal(k2, (H, D)), window, C)


def qkv(n, key=1):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return tuple(jax.random.normal(k, (n, H, D), jnp.float32) for k in ks)


# ---------------------------------------------------------------------------
# the attention itself
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [16, 64, 256])
def test_a_window_as_long_as_the_sequence_is_the_dense_familys_attention(n):
    """No window closes, so no summary is read: plain causal attention."""
    q, k, v = qkv(n)
    out = att.eva_attention(q, k, v, eva_query(window=256))
    np.testing.assert_allclose(np.asarray(out), np.asarray(att.causal_attention(q, k, v)),
                               atol=2e-6)


def test_a_closed_windows_keys_are_read_through_their_summaries_alone():
    """Past a boundary the output moves with the closed window's summary
    vectors and no longer with a single key of it beyond what its chunk's
    summary carries: two sequences that differ in window 0 only through
    ``mu`` read differently in window 1, alike in window 0."""
    q, k, v = qkv(2 * W)
    a = att.eva_attention(q, k, v, eva_query(0))
    b = att.eva_attention(q, k, v, eva_query(5))
    np.testing.assert_array_equal(np.asarray(a[:W]), np.asarray(b[:W]))
    assert float(jnp.abs(a[W:] - b[W:]).max()) > 1e-3


def test_the_summaries_are_the_equations():
    q, k, v = qkv(C)
    e = eva_query()
    ks, vs = att.eva_summarise(k, v, e)
    for h in range(H):
        a = jax.nn.softmax(k[:, h] @ e.mu[h])
        b = jax.nn.softmax(k[:, h] @ e.phi[h] - 0.5 * jnp.sum(k[:, h] ** 2, axis=-1))
        np.testing.assert_allclose(np.asarray(ks[h]), np.asarray(a @ k[:, h]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(vs[h]), np.asarray(b @ v[:, h]), atol=1e-5)


# ---------------------------------------------------------------------------
# rows as the seam holds them: a ring of pages, summary blocks by window
# ---------------------------------------------------------------------------

N_WIN = 5
BASE = 1 + 3 * RING                # the pool's first page of summary blocks


def held_rows(lengths, e, key=2, dtype=jnp.float32):
    """Pools and tables holding, for each row, a sequence of ``length``
    tokens as the engine would leave them: the open window's positions in
    the ring (the entries behind them still the window before's), every whole
    chunk's summary in its window's block (the open window's too). Returns
    the pools, the tables, each row's full q / k / v."""
    ppb = W // C // C
    pool_k = jnp.zeros((BASE + (1 + len(lengths) * N_WIN) * ppb, C, H, D), dtype)
    pool_v = jnp.zeros_like(pool_k)
    tables = np.zeros((len(lengths), RING + N_WIN), np.int32)
    seqs = []
    for r, n in enumerate(lengths):
        q, k, v = qkv(n, key + r)
        seqs.append((q, k, v))
        ring = 1 + r * RING + np.arange(RING)
        blocks = 1 + r * N_WIN + np.arange(N_WIN)
        tables[r, :RING], tables[r, RING:] = ring, blocks
        for p in range(n):                  # later positions overwrite: a ring
            pool_k = pool_k.at[ring[(p % W) // C], p % C].set(k[p].astype(dtype))
            pool_v = pool_v.at[ring[(p % W) // C], p % C].set(v[p].astype(dtype))
        whole = n // C
        if whole:
            ks, vs = att.eva_summarise(k[: whole * C].reshape(whole, C, H, D),
                                       v[: whole * C].reshape(whole, C, H, D), e)
            for c in range(whole):
                i = c % (W // C)
                page = BASE + blocks[c * C // W] * ppb + i // C
                pool_k = pool_k.at[page, i % C].set(ks[c].astype(dtype))
                pool_v = pool_v.at[page, i % C].set(vs[c].astype(dtype))
    return pool_k, pool_v, jnp.asarray(tables), seqs


def last_query_reference(seqs, e):
    """Each row's last position through the whole-sequence twin."""
    outs = []
    for q, k, v in seqs:
        n = q.shape[0]
        pad = (-n) % C
        full = [jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (q, k, v)]
        outs.append(att.eva_attention(*full, e)[n - 1])
    return jnp.stack(outs)


LENGTHS = [(5, 255, 256), (257, 300, 511), (512, 513, 1279), (1280, 16, 1024), (40, 0, 700)]


@pytest.mark.parametrize("lengths", LENGTHS)
def test_decode_rows_over_ring_and_summaries_are_the_whole_sequences_attention(lengths):
    e = eva_query()
    pk, pv, tables, seqs = held_rows([n for n in lengths if n], e)
    lens = jnp.asarray([n for n in lengths if n], jnp.int32)
    q = jnp.stack([s[0][-1] for s in seqs])
    out = att.eva_paged_decode_attention(q, pk, pv, tables, lens, e, BASE)
    np.testing.assert_allclose(np.asarray(out), np.asarray(last_query_reference(seqs, e)),
                               atol=5e-6)


@pytest.mark.parametrize("lengths", LENGTHS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_interpreted_launch_is_its_twin(lengths, dtype):
    """``eva_decode_attention`` (Pallas, interpreted) against the pure-JAX
    twin on the same pools; an empty row returns zeros."""
    e = eva_query()
    real = [n for n in lengths if n]
    pk, pv, tables, seqs = held_rows(real, e, dtype=dtype)
    q = jnp.stack([s[0][-1] for s in seqs]).astype(dtype)
    lens = jnp.asarray(real, jnp.int32)
    if len(real) < len(lengths):       # an empty row among them
        q = jnp.concatenate([q, q[:1]])
        tables = jnp.concatenate([tables, jnp.zeros_like(tables[:1])])
        lens = jnp.concatenate([lens, jnp.zeros((1,), jnp.int32)])
    twin = att.eva_paged_decode_attention(q, pk, pv, tables, lens, e, BASE)
    out = pallas_eva.eva_decode_attention(q, pk, pv, tables, lens, e, BASE, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(twin, np.float32), atol=tol)
    if len(real) < len(lengths):
        assert not np.asarray(out[-1], np.float32).any()


@pytest.mark.parametrize("n", [300, 512, 700])
def test_zeroing_the_open_windows_summary_block_changes_nothing(n):
    """A query reads the summaries of CLOSED windows only."""
    e = eva_query()
    pk, pv, tables, seqs = held_rows([n], e)
    q = seqs[0][0][-1][None]
    lens = jnp.asarray([n], jnp.int32)
    before = att.eva_paged_decode_attention(q, pk, pv, tables, lens, e, BASE)
    page = BASE + int(tables[0, RING + (n - 1) // W])      # one page a block here
    after = att.eva_paged_decode_attention(
        q, pk.at[page].set(0.0), pv.at[page].set(0.0), tables, lens, e, BASE)
    np.testing.assert_array_equal(np.asarray(before), np.asarray(after))
    closed = BASE + int(tables[0, RING])                   # window 0's: it is read
    moved = att.eva_paged_decode_attention(
        q, pk.at[closed].set(0.0), pv.at[closed].set(0.0), tables, lens, e, BASE)
    assert float(jnp.abs(moved - before).max()) > 1e-4


@pytest.mark.parametrize("use_pallas", [False, True])
def test_a_chunk_and_a_mixed_steps_rows_through_the_seam(use_pallas):
    """The seam's three questions on one set of rows: a chunk at a window's
    tail (its summaries written first), and ragged rows (that chunk beside
    two decode rows), against the whole-sequence twin."""
    e = eva_query()
    n, S = 600, 64                             # the chunk: positions 536-599
    pk, pv, tables, seqs = held_rows([n, 300, 1000], e)
    seam = PagedAttention(make_mesh(tp=1), use_pallas, interpret=True, summary_base=BASE)
    q, k, v = seqs[0]
    pad = (-n) % C
    full = att.eva_attention(*(jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (q, k, v)), e)
    pos = jnp.arange(n - S, n)
    out = seam.chunk(q[n - S:], pk, pv, tables[0], jnp.asarray(n - S), jnp.asarray(n), pos, eva=e)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[n - S:n]), atol=1e-5)
    packed = jnp.concatenate([q[n - S:], seqs[1][0][-1:], seqs[2][0][-1:]])
    out = seam.ragged(packed, pk, pv, tables, jnp.asarray([0, S, S + 1]),
                      jnp.asarray([S, 1, 1]), jnp.asarray([n, 300, 1000]), eva=e)
    want = jnp.concatenate([full[n - S:n], last_query_reference(seqs[1:], e)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_the_seam_writes_a_summary_when_a_page_fills():
    """``summarise_chunk`` writes a chunk's WHOLE pages' summaries and leaves
    a page the prompt does not fill to ``summarise_rows``, which writes it
    when a decode row's token fills the page, and only then."""
    e = eva_query()
    seam = PagedAttention(make_mesh(tp=1), False, summary_base=BASE)
    q, k, v = qkv(W + 48)
    pk, pv, tables, _ = held_rows([1], e)
    zero = jnp.zeros_like(pk)
    start, total = W, W + 40                   # a chunk of 40 real tokens of 48
    kc, vc = seam.summarise_chunk(zero, zero, k[start:], v[start:], tables[0],
                                  jnp.asarray(start), jnp.asarray(total), e)
    ks, vs = att.eva_summarise(k[start:].reshape(3, C, H, D), v[start:].reshape(3, C, H, D), e)
    page = BASE + int(tables[0, RING + 1])     # window 1's block
    np.testing.assert_allclose(np.asarray(kc[page, :2]), np.asarray(ks[:2]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vc[page, :2]), np.asarray(vs[:2]), atol=1e-6)
    assert not np.asarray(kc[page, 2:]).any()  # the third page is not whole
    # decode rows: one fills its page (offset 15), one does not
    ring = np.asarray(tables[0, :RING])
    pool = zero.at[ring[2]].set(k[start + 32:start + 48])
    poolv = zero.at[ring[2]].set(v[start + 32:start + 48])
    lens = jnp.asarray([W + 48, W + 47])
    wb = jnp.asarray([ring[2], ring[2]])
    kc, vc = seam.summarise_rows(pool, poolv, jnp.stack([tables[0]] * 2), lens, wb,
                                 jnp.asarray([15, 14]), e)
    np.testing.assert_allclose(np.asarray(kc[page, 2]), np.asarray(ks[2]), atol=1e-6)
    kc2, _ = seam.summarise_rows(pool, poolv, tables[:1], lens[1:], wb[1:], jnp.asarray([14]), e)
    assert not np.asarray(kc2[page]).any()


def test_the_ring_is_its_arithmetic():
    ring = Ring(2048, 16, 10240)
    assert (ring.pages, ring.windows, ring.pages_per_block, ring.table_width) == (128, 5, 8, 133)
    assert [ring.entry(p) for p in (0, 15, 16, 2047, 2048, 4095 + 17)] == [0, 0, 1, 127, 0, 1]
    assert ring.held(1) == (1, 1) and ring.held(2048) == (128, 1)
    assert ring.held(2049) == (128, 2) and ring.held(9728) == (128, 5)
    with pytest.raises(ValueError, match="whole number of pages of summaries"):
        Ring(64, 16, 512)


# ---------------------------------------------------------------------------
# the engine: one run of every scenario, shared by the tests below
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """The float32 engine (mixed steps, horizons of 8, 2 slots, buckets 32 /
    64 / 128) through ``generate``: A alone over five windows (prefill in
    chunks of 128 and a last chunk of 72, in the 128 bucket; the ring wraps
    four times, the last time while decoding); C and D alone (a lone chunk of
    each smaller bucket); A again while B's chunks ride its decode steps."""
    cfg = file_cfg()
    engine = engine_of(cfg)
    steps, held = [], []
    engine.stats_hook = lambda s: (steps.append(s), held.append(
        max((len(st.block_ids) for st in engine._slots if st is not None), default=0)))
    a, b, c, d = prompts_of(3 * W + 200, 700, 30, 50)

    async def run():
        out = {}
        out["a"] = await system.generate(engine, "a", a, 180)
        out["c"] = await system.generate(engine, "c", c, 12)
        out["d"] = await system.generate(engine, "d", d, 12)
        started = asyncio.Event()
        ta = asyncio.ensure_future(system.generate(
            engine, "a2", a, 180, on_chunk=lambda *_: started.set()))
        await started.wait()
        out["b"] = await system.generate(engine, "b", b, 100)
        out["a2"] = await ta
        return out

    try:
        recs = asyncio.run(run())
    finally:
        engine.stop()
    return {"cfg": cfg, "engine": engine, "recs": recs, "steps": steps, "held": held,
            "prompts": {"a": a, "a2": a, "b": b, "c": c, "d": d}}


def compare(served, *names, cfg=None):
    samples = [sample(served["prompts"][n], served["recs"][n]) for n in names]
    return ref.compare(cfg or served["cfg"], adapter.reference_params(served["engine"]),
                       samples, 5 * W)


def test_prefill_and_decode_through_ring_and_summaries_are_the_references_forward(served):
    """Five windows: chunks in four (summaries written by the chunks), then
    decode horizons that fill window 3 and close it (its last summaries
    written a page at a time by decode steps) and read four blocks."""
    for r in served["recs"].values():
        assert r["error"] is None and r["finish"] == "length"
    res = compare(served, "a", "c", "d")
    assert res["ok"], res
    assert res["tokens_compared"] == 204 and res["worst_argmax_gap_nat"] == 0.0
    assert res["summary_pages_compared"] == 4     # four closed windows, a page each


def test_a_mixed_step_is_its_two_halves(served):
    """B's chunks rode A's decode steps over a window boundary of each."""
    assert any(s.phase == "mixed" and s.eva_rows_attended for s in served["steps"])
    res = compare(served, "b", "a2")
    assert res["ok"], res
    assert served["recs"]["a2"]["tokens"] == served["recs"]["a"]["tokens"]


def test_every_bucket_ran(served):
    sizes = {s.tokens for s in served["steps"] if s.phase == "prefill"}
    assert {30, 50, 128, 72} <= sizes


def test_a_request_of_five_windows_never_holds_more_than_a_ring(served):
    assert max(served["held"]) == RING
    engine = served["engine"]
    assert engine._block_tables.shape[1] == RING + 6   # a ring, then a block a window


def test_everything_returns_to_the_free_lists_at_finish(served):
    engine = served["engine"]
    assert engine.allocator.free_blocks == engine.cfg.num_blocks - 1
    assert engine.summary_allocator.free_blocks == engine.summary_allocator.num_blocks - 1
    assert engine.allocator.cached_blocks == 0   # nothing registered for reuse


def test_a_repeated_prompt_takes_no_prefix_hit(served):
    assert not registry.prefix_reusable(served["engine"].mcfg)
    assert served["recs"]["a2"]["cached_tokens"] == 0


def test_the_step_counters_are_what_the_rows_positions_imply(served):
    steps = served["steps"]
    counted = [s for s in steps if s.eva_rows_attended]
    assert all(s.phase != "prefill" for s in counted)
    for s in steps:
        assert s.moe_tokens_routed is None        # the readback carries no routing
    # every emitted token but a request's first came from a decode row
    emitted = sum(len(r["tokens"]) - 1 for r in served["recs"].values())
    rows = sum(s.eva_rows_attended for s in counted)
    assert rows >= L * emitted and rows % L == 0
    # request a alone: decode rows from position 968 on (the last horizon
    # runs on to a multiple of 8), each reading its open window up to itself
    # and 16 summaries a closed window; one of them opens window 4
    first = next(i for i, s in enumerate(steps) if s.eva_rows_attended)
    alone = []
    for s in steps[first:]:
        if s.phase == "prefill":
            break
        alone.append(s)
    pos = np.arange(3 * W + 200, 3 * W + 200 + sum(s.eva_rows_attended for s in alone) // L)
    assert sum(s.eva_window_keys for s in alone) == L * int((pos % W + 1).sum())
    assert sum(s.eva_summaries_read for s in alone) == L * int((pos // W * (W // C)).sum())
    assert sum(s.eva_windows_closed for s in alone) == int((pos % W == 0).sum()) == 1
    assert sum(s.eva_decode_steps for s in alone) == len(pos)


@pytest.mark.parametrize("name,switch", sorted(ref.wrong_variants({}).items())
                         + [("skip_layer", {"skip_layer": 1})])
def test_each_wrong_variant_of_the_reference_fails_the_tolerance(served, name, switch):
    samples = [sample(served["prompts"]["a"], served["recs"]["a"])]
    res = ref.compare(served["cfg"], adapter.reference_params(served["engine"]), samples,
                      5 * W, **switch)
    assert not res["ok"], (name, res)


def test_the_interpreted_kernels_serve_the_same_tokens(served):
    """The Pallas side of the seam (the launch ``eva_decode_attention`` for
    decode rows, the ragged launch for chunks and mixed steps), interpreted,
    on a shorter run over two boundaries."""
    cfg = served["cfg"]
    engine = engine_of(cfg, use_pallas=True)
    (a,) = prompts_of(W + 200, seed=4)

    async def run():
        started = asyncio.Event()
        ta = asyncio.ensure_future(system.generate(
            engine, "a", a, 80, on_chunk=lambda *_: started.set()))
        await started.wait()
        rb = await system.generate(engine, "b", a[:300], 24)
        return await ta, rb

    try:
        ra, rb = asyncio.run(run())
    finally:
        engine.stop()
    res = ref.compare(cfg, adapter.reference_params(engine),
                      [sample(a, ra), sample(a[:300], rb)], 5 * W)
    assert res["ok"], res


# ---------------------------------------------------------------------------
# what a request holds, and what admission refuses
# ---------------------------------------------------------------------------


def run_until(engine, coro_of):
    try:
        return asyncio.run(coro_of())
    finally:
        engine.stop()


@pytest.mark.parametrize("how", ["cancel", "kill"])
def test_an_aborted_request_gives_back_its_pages_and_its_summary_blocks(how):
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)
    from dynamo_tpu.runtime.engine import Context

    engine = engine_of()
    (a,) = prompts_of(2 * W + 40)

    async def run():
        ctx = Context()
        req = PreprocessedRequest(
            request_id="a", model="m", token_ids=a,
            stop=StopConditions(max_tokens=400, ignore_eos=True),
            sampling=SamplingOptions(temperature=0.0))
        seen = 0
        async for out in engine.generate(req, ctx):
            seen += len(out.token_ids or [])
            if seen >= 16 and not ctx.is_stopped():
                st = next(s for s in engine._slots if s is not None)
                held = (len(st.block_ids), len(st.summary_ids))
                (ctx.stop_generating if how == "cancel" else ctx.kill)()
        for _ in range(50):
            if all(s is None for s in engine._slots):
                break
            await asyncio.sleep(0.05)
        return held

    held = run_until(engine, run)
    assert held == (RING, 3)
    assert engine.allocator.free_blocks == engine.cfg.num_blocks - 1
    assert engine.summary_allocator.free_blocks == engine.summary_allocator.num_blocks - 1


def test_admission_waits_for_what_ring_and_summaries_cannot_hold():
    """Two rings of pages, three requests: the third waits for a ring; with
    the summary store drained, a request waits for its blocks though pages
    are free; a prompt that could never fit is refused outright."""
    engine = engine_of(max_batch_size=3, num_blocks=1 + 2 * RING)
    prompts = prompts_of(W + 10, W + 20, W + 30)
    waited = []

    async def run():
        tasks = [asyncio.ensure_future(system.generate(engine, f"r{i}", p, 40))
                 for i, p in enumerate(prompts)]
        await asyncio.sleep(0.5)
        waited.append(len(engine._waiting))
        recs = await asyncio.gather(*tasks)
        # the summary store drained by hand: pages free, blocks not
        taken = engine.summary_allocator.allocate(engine.summary_allocator.free_blocks - 1)
        t = asyncio.ensure_future(system.generate(engine, "late", prompts[0], 8))
        await asyncio.sleep(0.5)
        waited.append((len(engine._waiting), engine.allocator.free_blocks))
        engine.summary_allocator.release(taken)
        engine._wake.set()
        return recs + [await t]

    recs = run_until(engine, run)
    assert waited[0] == 1                       # two rings held, the third waits
    assert waited[1] == (1, 2 * RING)           # every page free, one block short
    assert all(r["error"] is None and r["finish"] == "length" for r in recs)
    small = engine_of(num_blocks=RING)          # not a whole ring
    rec = run_until(small, lambda: system.generate(small, "x", prompts[0], 4))
    assert "cannot fit the KV pool" in rec["error"]


REFUSALS = [
    (dict(tp=2), "tp > 1"), (dict(sp=2), "pp / sp > 1"),
    (dict(spec_draft=LlamaConfig.tiny(vocab_size=320)), "speculative draft"),
    (dict(lora_max_adapters=2), "LoRA"), (dict(kv_dtype="int8"), "kv_dtype=int8"),
]


@pytest.mark.parametrize("opts,reason", REFUSALS)
def test_the_engine_refuses_what_the_family_cannot_do_yet(opts, reason):
    with pytest.raises(ValueError, match=reason):
        engine_of(**opts)


@pytest.mark.parametrize("asked,reason", [
    (dict(pp=2), "pp / sp > 1"), (dict(vision=True), "vision"),
    (dict(transfer=True), "transfer plane"), (dict(kvbm=True), "KVBM"),
])
def test_check_eva_supported_says_why(asked, reason):
    cfg = evabyte.EvaByteConfig.tiny()
    with pytest.raises(ValueError, match=reason):
        registry.check_eva_supported(cfg, **asked)
    registry.check_eva_supported(LlamaConfig.tiny(), **asked)   # no ring: nothing to refuse
    registry.check_eva_supported(cfg)


def test_the_geometry_is_checked_at_construction():
    with pytest.raises(ValueError, match="a page is a chunk"):
        engine_of(block_size=8, prefill_buckets=(32,))
    with pytest.raises(ValueError, match="straddle two windows"):
        engine_of(prefill_buckets=(48,))


def test_the_registrys_three_answers():
    cfg = evabyte.EvaByteConfig.evabyte_6_5b(num_layers=8)
    assert registry.window_ring(cfg) == 2048 and registry.is_evabyte(cfg)
    assert registry.summary_spec(cfg) == (
        ("k_summary", (128, 32, 128), jnp.bfloat16), ("v_summary", (128, 32, 128), jnp.bfloat16))
    assert registry.state_spec(cfg) == () and len(registry.page_layers(cfg)) == 8
    dense = LlamaConfig.tiny()
    assert registry.window_ring(dense) is None and registry.summary_spec(dense) == ()
    assert registry.read_counters(dense) == () and registry.prefix_reusable(dense)
    # a dense family's table is what it was: a page a block_size of max_context
    assert TpuEngineConfig(model=dense, max_context=8192, block_size=16).max_blocks_per_seq == 512
    assert TpuEngineConfig(model=cfg, max_context=10240, block_size=16).max_blocks_per_seq == 133
    with pytest.raises(ValueError, match="pp=1"):
        registry.check_pp_supported(cfg)


def test_head_zero_of_eight_is_the_served_head():
    cfg = evabyte.EvaByteConfig.tiny(dtype=jnp.float32)
    p = evabyte.init_params(jax.random.PRNGKey(0), cfg)
    assert p["lm_head"].shape == (64, 8 * 320)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (3, 64))
    logits = evabyte.lm_logits(p, cfg, hidden)
    assert logits.shape == (3, 320) and logits.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(logits), np.asarray(hidden @ p["lm_head"][:, :320]),
                               atol=1e-5)
