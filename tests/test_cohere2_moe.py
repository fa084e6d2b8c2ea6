"""Command A+ (``cohere2_moe``) and pages kept BY LAYER KIND, at test scale
on the CPU (hidden 64, 8 q / 2 kv heads x 16, window 64, page 16, one period
of 4 layers, 16 experts top 4 beside 2 shared, vocabulary 512): the engine's
prefill, mixed steps and decode against the plain reference's one forward
(benchmarks/reference/cohere2_moe_decoder.py), the page groups' bookkeeping
(engine/allocator.py ``WindowGroup``, engine ``_slide``), prefix hits over
two groups, the refusals, and that a family of ONE group runs the programs
it ran."""

import asyncio
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import system
from benchmarks.adapters import cohere2_moe as adapter
from benchmarks.reference import cohere2_moe_decoder as ref
from dynamo_tpu.engine.allocator import BlockAllocator, OutOfBlocks
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models import cohere2_moe, llama, mla, moe, registry
from dynamo_tpu.models.cohere2_moe import Cohere2MoeConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W, PAGE = 64, 16
TOL = {"worst_nat": 2e-4, "mean_nat": 2e-5, "median_nat": 2e-5, "first_cache_rel": 2e-5}


def file_cfg(**kw):
    """The benchmark's configuration file cut to test scale (the reference
    reads the public keys)."""
    with open(os.path.join(ROOT, "benchmarks/configs/command-a-plus-ep8-d4.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
               sliding_window=W, intermediate_size=32, moe_intermediate_size=32,
               num_experts=16, router_outputs=16, experts_held_first=0,
               num_experts_per_tok=4, num_shared_experts=2, vocab_size=512,
               torch_dtype="float32", reference_tolerance=dict(TOL))
    cfg.update(kw)
    return cfg


def build(cfg=None, **kw):
    cfg = cfg or file_cfg()
    opts = dict(num_blocks=160, block_size=PAGE, max_batch_size=4, max_context=512,
                prefill_buckets=(32, 64), decode_steps=4, decode_pipeline=2, seed=3)
    opts.update(kw)
    return TpuEngine(TpuEngineConfig(model=adapter.model_config(cfg), **opts))


def run(coro):
    """On ONE event loop for the module: an engine's loop task lives on the
    loop that first drove it."""
    if "loop" not in run.__dict__:
        run.loop = asyncio.new_event_loop()
    return run.loop.run_until_complete(coro)


def prompts(lengths, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).tolist() for n in lengths]


async def answer(eng, ps, n=24, prefix="r", resident=None):
    """Greedy continuations of ``ps``; with ``resident`` a request that
    decodes first, so that the others' chunks ride mixed steps."""
    started = asyncio.Event()
    res = None
    if resident is not None:
        res = asyncio.ensure_future(system.generate(
            eng, f"{prefix}-res", resident, 64, on_chunk=lambda *_: started.set()))
        await started.wait()
    recs = await asyncio.gather(*[
        system.generate(eng, f"{prefix}{i}", p, n) for i, p in enumerate(ps)])
    if res is not None:
        await res
    for r in recs:
        assert r["error"] is None and len(r["tokens"]) == n, (r["error"], r["finish"])
    return recs


def samples_of(ps, recs):
    return [{"prompt": p, "tokens": r["tokens"], "logprobs": r["logprobs"]}
            for p, r in zip(ps, recs)]


def free_everywhere(eng):
    """Every page of every group on a free list (or cached), none pinned."""
    allocs = [eng.allocator] + [g.allocator for g in eng._win_groups]
    return all(a.active_blocks == 0 and a.free_blocks == a.num_blocks - 1 for a in allocs)


# ---------------------------------------------------------------------------
# the engine against the reference's one forward, over 5 windows
# ---------------------------------------------------------------------------

SERVED = {
    # name: (engine options, file keys, a resident request beside the chunks)
    "bucket32": (dict(prefill_buckets=(32,)), {}, False),
    "buckets32-64": (dict(), {}, False),
    "mixed-steps": (dict(mixed_admission=True), {}, True),
    "single-step-decode": (dict(decode_steps=1, decode_pipeline=1), {}, False),
    "held-share-vocab-slice": (
        dict(mixed_admission=True),
        dict(num_experts=4, experts_held_first=8, vocab_size=256), True),
}


@pytest.fixture(scope="module", params=sorted(SERVED))
def served(request):
    opts, keys, with_resident = SERVED[request.param]
    cfg = file_cfg(**keys)
    eng = build(cfg, **opts)
    ps = prompts((330, 200, 70), vocab=cfg["vocab_size"])       # 5.2, 3.1, 1.1 windows
    resident = prompts((40,), vocab=cfg["vocab_size"], seed=9)[0] if with_resident else None
    phases = []
    eng.stats_hook = lambda s: phases.append(s)
    recs = run(answer(eng, ps, resident=resident))
    yield cfg, eng, ps, recs, phases
    eng.stop()


def test_engine_matches_the_reference_over_five_windows(served):
    cfg, eng, ps, recs, phases = served
    res = ref.compare(cfg, adapter.reference_params(eng), samples_of(ps, recs), 384)
    assert res["ok"], res
    assert res["tokens_compared"] == 72 and res["cache_pages_compared"] > 0
    if eng.mixed_enabled:
        assert any(s.phase == "mixed" for s in phases)


def test_every_page_of_every_group_returns_at_finish(served):
    _, eng, *_ = served
    assert len(eng._win_groups) == 1 and free_everywhere(eng)


def test_the_counters_ride_the_readback(served):
    cfg, eng, ps, recs, phases = served
    counted = [s for s in phases if s.win_decode_rows]
    assert counted and all(s.page_groups_held is not None for s in phases)
    rows = sum(s.win_decode_rows for s in counted)
    # three sliding layers to one full; a sliding row never reads past W keys
    assert rows == 3 * sum(s.full_decode_rows for s in counted)
    assert sum(s.win_keys_read for s in counted) <= rows * W
    assert sum(s.full_keys_read for s in counted) > sum(s.win_keys_read for s in counted) / 3
    assert sum(s.page_groups_released[1] for s in phases) > 0
    assert all(s.moe_held_experts_touched is not None for s in counted) == (
        eng.mcfg.experts_held is not None)


# ---------------------------------------------------------------------------
# the reference: its switches, its layout, the shares
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def honest():
    cfg = file_cfg()
    eng = build(cfg)
    ps = prompts((330, 200))
    recs = run(answer(eng, ps))
    params = adapter.reference_params(eng)
    yield cfg, params, samples_of(ps, recs)
    eng.stop()


@pytest.mark.parametrize("variant", sorted(ref.wrong_variants({})) + ["skip_layer"])
def test_each_wrong_variant_of_the_reference_differs(honest, variant):
    cfg, params, samples = honest
    sw = {"skip_layer": 2} if variant == "skip_layer" else ref.wrong_variants(cfg)[variant]
    res = ref.compare(cfg, params, samples, 384, **sw)
    assert not res["ok"], res
    # by a logprob limit, or (8-bit pages) by what the engine holds
    assert (res["mean_logprob_difference_nat"] > 100 * TOL["mean_nat"]
            or res["first_layer_cache_difference"] > 100 * TOL["first_cache_rel"])


def test_the_window_edge_lean_alone_tells_a_stale_page(honest):
    """With every logprob limit too loose to tell (as bf16 makes them on the
    chip), the engine's logprobs still lie at the window as stated and lean
    to neither neighbour; against a reference a page too long they lie at the
    SHORTER neighbour, and that reading alone fails it."""
    cfg, params, samples = honest
    loose = {"worst_nat": 10.0, "mean_nat": 10.0, "median_nat": 10.0, "window_edge_lean": 0.5}
    cfg = {**cfg, "reference_tolerance": loose}
    res = ref.compare(cfg, params, samples, 384)
    assert res["ok"] and abs(res["window_edge_lean"]) < 1e-3, res
    stale = ref.compare(cfg, params, samples, 384, stale_page=True)
    assert not stale["ok"], stale
    assert stale["lean_to_a_page_shorter"] == pytest.approx(1.0, abs=1e-3)
    assert stale["median_a_page_shorter_nat"] < stale["median_logprob_difference_nat"]


def test_a_window_as_long_as_the_sequence_is_one_kind(honest):
    """With the window at least the sequence, the sliding layers read every
    causal key: the reference with its window ignored gives the same."""
    cfg = file_cfg(sliding_window=512)
    eng = build(cfg)
    try:
        ps = prompts((330,))
        recs = run(answer(eng, ps))
        res = ref.compare(cfg, adapter.reference_params(eng), samples_of(ps, recs), 384,
                          ignore_window=True)
        assert res["ok"], res
    finally:
        eng.stop()


def test_interleaved_rotation_equals_rotate_half_on_permuted_weights():
    """The reference rotates pairs (2i, 2i + 1) on published weights; the
    engine rotates halves on weights de-interleaved a head. Same scores."""
    rng = np.random.default_rng(0)
    heads, d, T = 4, 16, 9
    w = rng.standard_normal((32, heads * d)).astype(np.float32)       # served layout
    u = rng.standard_normal((T, 32)).astype(np.float32)
    cos, sin = llama.rope_cos_sin(jnp.arange(T), d, 50000.0)
    cos, sin = cos[:, None, :], sin[:, None, :]
    served_q = llama.apply_rope(jnp.asarray(u @ w).reshape(T, heads, d), cos, sin)
    pub = adapter.published_layout(w, heads)
    ref_q = ref._rotate(jnp.asarray(u @ pub).reshape(T, heads, d), cos, sin, False)
    # a score is a dot product over a head's lanes: the layout cancels
    a = jnp.einsum("thd,shd->hts", served_q, served_q)
    b = jnp.einsum("thd,shd->hts", ref_q, ref_q)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # and lane for lane: served lane i is published lane 2i, d/2 + i is 2i + 1
    back = jnp.concatenate([ref_q[..., 0::2], ref_q[..., 1::2]], axis=-1)
    np.testing.assert_allclose(back, served_q, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_shares_add_up(seed):
    """The routed parts of all 8 shares, plus the shared branch counted once,
    equal the uncut layer (the program's and the reference's)."""
    whole = Cohere2MoeConfig.tiny(dtype=jnp.float32)
    p = cohere2_moe.init_layer_params(jax.random.PRNGKey(seed), whole)
    u = jax.random.normal(jax.random.PRNGKey(seed + 10), (24, whole.hidden_size))
    uncut = moe.routed_shared_ffn(p, whole, u)
    none = dataclasses.replace(whole, num_shared_experts=0)
    parts = jnp.zeros_like(uncut)
    for first in range(0, 16, 2):
        share = dataclasses.replace(none, experts_held=(first, 2))
        ps = {**p, **{n: p[n][first:first + 2] for n in ("w_egate", "w_eup", "w_edown")}}
        parts = parts + moe.routed_shared_ffn(ps, share, u)
    shared_once = uncut - moe.routed_shared_ffn(p, none, u)
    np.testing.assert_allclose(parts + shared_once, uncut, rtol=2e-5, atol=2e-5)
    # the reference's layer, uncut, is the same layer (T padded to a block)
    pad = jnp.zeros((ref.TOKEN_BLOCK, whole.hidden_size)).at[:24].set(u)
    r = ref._experts(jnp.zeros_like(pad), p, pad, top_k=4, first=0, renorm=True,
                     softmax_router=False, n_shared=2, shared_scale=0.5)[:24]
    np.testing.assert_allclose(r, uncut, rtol=2e-4, atol=2e-4)


def test_a_checkpoint_in_the_published_layout_loads_to_the_served_pytree(monkeypatch):
    """``engine/weights.py`` de-interleaves q_proj / k_proj of the layers
    that rotate: the inverse of what the adapter hands the reference."""
    from dynamo_tpu.engine import weights

    cfg = Cohere2MoeConfig.tiny(dtype=jnp.float32)
    params = cohere2_moe.init_params(jax.random.PRNGKey(4), cfg)
    tensors = {"model.embed_tokens.weight": params["embed"],
               "model.norm.weight": params["final_norm"]}
    for i, lp in enumerate(params["layers"]):
        pre = f"model.layers.{i}."
        rot = cfg.window_for_layer(i) is not None
        pub = adapter.published_layout if rot else (lambda w, heads: np.asarray(w))
        tensors[pre + "input_layernorm.weight"] = lp["norm"]
        tensors[pre + "self_attn.q_proj.weight"] = pub(lp["wq"], cfg.num_heads).T
        tensors[pre + "self_attn.k_proj.weight"] = pub(lp["wk"], cfg.num_kv_heads).T
        tensors[pre + "self_attn.v_proj.weight"] = np.asarray(lp["wv"]).T
        tensors[pre + "self_attn.o_proj.weight"] = np.asarray(lp["wo"]).T
        tensors[pre + "mlp.gate.weight"] = np.asarray(lp["w_router"]).T
        w = cfg.moe_intermediate_size
        for leaf, proj in (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj")):
            for e in range(cfg.num_experts):
                tensors[pre + f"mlp.experts.{e}.{proj}.weight"] = np.asarray(lp[f"w_e{leaf}"][e]).T
            for j in range(cfg.num_shared_experts):
                sh = np.asarray(lp[f"w_shared_{leaf}"])
                part = sh[j * w:(j + 1) * w] if leaf == "down" else sh[:, j * w:(j + 1) * w]
                tensors[pre + f"mlp.shared_experts.{j}.{proj}.weight"] = part.T
    monkeypatch.setattr(weights, "_open_safetensors",
                        lambda path: ((k, np.asarray(v)) for k, v in tensors.items()))
    loaded = weights.load_params("unused", cfg)
    jax.tree_util.tree_map(np.testing.assert_array_equal, loaded, params)


def test_layer_norm_removes_the_mean_and_has_no_bias():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 64)) + 3.0
    y = cohere2_moe.layer_norm(x, jnp.ones((64,)), 1e-5)
    np.testing.assert_allclose(jnp.mean(y, -1), 0.0, atol=1e-5)
    np.testing.assert_allclose(jnp.var(y, -1), 1.0, rtol=1e-3)
    assert not np.allclose(y, llama.rms_norm(x, jnp.ones((64,)), 1e-5), atol=1e-2)


# ---------------------------------------------------------------------------
# pages by layer kind: what a row holds, what returns, what a hit needs
# ---------------------------------------------------------------------------


def test_a_long_request_holds_one_window_while_the_full_group_grows():
    eng = build()
    held = []
    eng.stats_hook = lambda s: held.append(s.page_groups_held)
    try:
        run(answer(eng, prompts((330,)), n=40))
    finally:
        eng.stop()
    grp = eng._win_groups[0]
    most = (W + max(eng.cfg.prefill_buckets) + PAGE) // PAGE
    assert grp.pages <= most + 1
    assert max(h[1] for h in held) <= most                     # window + bucket + page
    assert max(h[0] for h in held) >= (330 + 39) // PAGE       # every page of the context
    assert max(h[0] for h in held) >= 3 * max(h[1] for h in held)


@pytest.mark.parametrize("when", ["mid-prefill", "mid-decode"])
def test_every_page_returns_at_abort(when):
    eng = build(prefill_buckets=(32,))

    async def killed():
        got = []
        task = asyncio.ensure_future(system.generate(
            eng, "x", prompts((330,))[0], 200, on_chunk=lambda _, n: got.append(n)))
        if when == "mid-prefill":
            while not any(s is not None and s.prefill_pos >= 96 for s in eng._slots):
                await asyncio.sleep(0)
            assert not got
        else:
            while sum(got) < 8:
                await asyncio.sleep(0)
        held = [len(s.win_ids[0]) for s in eng._slots if s is not None]
        assert held and held[0] > 0 and eng.allocator.active_blocks > 0
        task.cancel()                       # the caller goes away: the request is aborted
        await asyncio.gather(task, return_exceptions=True)
        for _ in range(500):
            if free_everywhere(eng) and all(s is None for s in eng._slots):
                break
            await asyncio.sleep(0.01)

    try:
        run(killed())
        assert free_everywhere(eng)
    finally:
        eng.stop()


def test_a_released_page_that_a_cached_prefix_shares_stays():
    a = BlockAllocator(8, PAGE, keep_hits=True)
    (b1,) = a.allocate(1)
    a.commit(b1, 111)
    assert a.acquire([a.lookup(111)]) == [b1]                  # a second request shares it
    a.release([b1], behind=True)                               # the first lets it go
    assert a.lookup(111) == b1 and a.active_blocks == 1        # still pinned, still there
    a.release([b1])
    assert a.lookup(111) == b1 and a.cached_blocks == 1        # cached, content kept


def test_a_windowed_pool_gives_up_hit_pages_last():
    a = BlockAllocator(5, PAGE, keep_hits=True)                # 4 pages
    tail, stale, behind, fresh = a.allocate(4)
    for h, b in enumerate((tail, stale, behind, fresh), 1):
        a.commit(b, h)
    a.release([tail])
    a.acquire([tail])                                          # a prefix hit used it
    a.release([tail], behind=True)                             # released FIRST
    a.release([stale])
    a.release([behind], behind=True)                           # never hit, behind a window
    a.release([fresh])
    order = [a.allocate(1)[0] for _ in range(4)]
    assert order == [behind, stale, fresh, tail]
    with pytest.raises(OutOfBlocks):
        a.allocate(1)


def test_the_default_allocator_gives_up_oldest_first_as_before():
    a = BlockAllocator(4, PAGE)
    x, y, z = a.allocate(3)
    for h, b in enumerate((x, y, z), 1):
        a.commit(b, h)
    a.release([y])
    a.acquire_prefix([2])
    a.release([y])
    a.release([x])
    a.release([z])
    assert [a.allocate(1)[0] for _ in range(3)] == [y, x, z]


@pytest.fixture(scope="module")
def sessions():
    eng = build(num_blocks=200, max_context=640)
    yield eng
    eng.stop()


def test_a_prefix_hit_with_its_windowed_tail_equals_the_whole_prefill(sessions):
    eng = sessions
    doc, q = prompts((384, 50), seed=5)
    run(answer(eng, [doc + [0]], n=1, prefix="doc"))
    hit = run(answer(eng, [doc + q], prefix="hit"))[0]
    assert hit["cached_tokens"] == 384
    run(eng.clear_kv_blocks(["g1"]))
    whole = run(answer(eng, [doc + q], prefix="whole"))[0]
    assert whole["cached_tokens"] == 0
    assert hit["tokens"] == whole["tokens"]
    np.testing.assert_allclose(hit["logprobs"], whole["logprobs"], atol=2e-5)


def test_a_hit_whose_windowed_page_is_gone_is_shortened_and_still_equal(sessions):
    eng = sessions
    run(eng.clear_kv_blocks(["g1"]))
    doc, q = prompts((384, 50), seed=6)
    run(answer(eng, [doc + [0]], n=1, prefix="doc2"))
    grp = eng._win_groups[0]
    hashes = eng._slots  # noqa: F841 (the allocator below is what is asked)
    from dynamo_tpu.tokens import TokenBlockSequence
    hs = TokenBlockSequence(doc, PAGE).sequence_hashes()
    # the pool gave up one page of the document's last window
    gone = grp.allocator.lookup(hs[22])
    assert gone is not None and eng._hit_blocks(hs, 24) == 24
    grp.allocator._lru.pop(gone, None)
    grp.allocator._lru_hit.pop(gone, None)
    grp.allocator._by_hash.pop(grp.allocator._hash_of.pop(gone))
    grp.allocator._free.append(gone)
    assert eng._hit_blocks(hs, 24) == 22                       # ends at the missing page
    short = run(answer(eng, [doc + q], prefix="short"))[0]
    assert 0 < short["cached_tokens"] == 22 * PAGE
    run(eng.clear_kv_blocks(["g1"]))
    whole = run(answer(eng, [doc + q], prefix="whole2"))[0]
    assert short["tokens"] == whole["tokens"]
    np.testing.assert_allclose(short["logprobs"], whole["logprobs"], atol=2e-5)


def test_a_hit_with_no_window_left_is_declined(sessions):
    eng = sessions
    run(eng.clear_kv_blocks(["g1"]))
    doc = prompts((384,), seed=7)[0]
    run(answer(eng, [doc + [0]], n=1, prefix="doc3"))
    eng._win_groups[0].allocator.clear()                       # every windowed page gone
    rec = run(answer(eng, [doc + [1, 2, 3]], prefix="none"))[0]
    assert rec["cached_tokens"] == 0 and free_everywhere(eng)


def test_sessions_keep_their_documents_tails_through_many_turns(sessions):
    """Closed-loop sessions over cached documents: every turn hits its whole
    document in BOTH groups, turn after turn (the pool gives up the stale
    question-and-answer pages first)."""
    eng = sessions
    run(eng.clear_kv_blocks(["g1"]))
    docs = prompts((384, 384, 384), seed=8)
    for i, d in enumerate(docs):
        run(answer(eng, [d + [0]], n=1, prefix=f"d{i}"))

    async def session(k):
        for turn in range(6):
            q = prompts((40 + 7 * turn,), seed=100 * k + turn)[0]
            (rec,) = await answer(eng, [docs[k] + q], n=20, prefix=f"s{k}t{turn}-")
            assert rec["cached_tokens"] == 384, (k, turn, rec["cached_tokens"])

    async def all_sessions():
        await asyncio.gather(*[session(k) for k in range(3)])

    run(all_sessions())
    assert free_everywhere(eng)


@pytest.mark.parametrize("pool", ["full", "windowed"])
def test_a_prompt_that_either_pool_can_never_hold_is_refused(pool):
    opts = dict(num_blocks=12) if pool == "full" else dict(window_blocks=4)
    eng = build(**opts)
    try:
        rec = run(system.generate(eng, "big", prompts((330,))[0], 4))
        assert "ContextLengthError" in rec["error"] and not rec["tokens"]
        assert not eng._waiting and free_everywhere(eng)
    finally:
        eng.stop()


def test_admission_waits_while_the_windowed_pool_is_held():
    """A windowed pool that holds one long request's pages at a time: the
    second request waits in admission, holding nothing, and runs after."""
    eng = build(window_blocks=10, max_batch_size=2)          # a row's table is 9 pages
    try:
        async def go():
            first = asyncio.ensure_future(
                system.generate(eng, "a", prompts((330,))[0], 60))
            while not any(s is not None and s.prefilled for s in eng._slots):
                await asyncio.sleep(0)
            second = asyncio.ensure_future(
                system.generate(eng, "b", prompts((330,), seed=1)[0], 8))
            for _ in range(20):
                await asyncio.sleep(0.005)
            waited = len(eng._waiting) == 1 and not first.done()
            recs = await asyncio.gather(first, second)
            return waited, recs
        waited, recs = run(go())
        assert waited
        assert [r["error"] for r in recs] == [None, None]
        assert [len(r["tokens"]) for r in recs] == [60, 8]
        assert free_everywhere(eng)
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# what more than one page group refuses, and what one group still builds
# ---------------------------------------------------------------------------

REFUSALS = {
    "tp": (dict(tp=2), "tp > 1"),
    "pp": (dict(pp=2), "pp / sp > 1"),
    "sp": (dict(sp=2), "pp / sp > 1"),
    "draft": (dict(spec=True), "speculative draft"),
    "lora": (dict(lora=True), "LoRA"),
    "int8": (dict(kv_quantized=True), "kv_dtype=int8"),
    "vision": (dict(vision=True), "vision"),
    "transfer": (dict(transfer=True), "transfer plane"),
    "kvbm": (dict(kvbm=True), "KVBM"),
}


@pytest.mark.parametrize("asked", sorted(REFUSALS))
def test_each_refusal_raises_with_its_reason(asked):
    kw, reason = REFUSALS[asked]
    with pytest.raises(ValueError, match=reason):
        registry.check_groups_supported(Cohere2MoeConfig.tiny(), **kw)
    # a family of one group is refused nothing here
    registry.check_groups_supported(llama.LlamaConfig.tiny(), **kw)


def test_engine_construction_refuses_an_8_bit_cache():
    with pytest.raises(ValueError, match="kv_dtype=int8"):
        build(kv_dtype="int8")


@pytest.mark.parametrize("cfg", [
    llama.LlamaConfig.tiny(), moe.MoeConfig.tiny_moe(), mla.MlaConfig.tiny_mla(),
    moe.MoeConfig.tiny_moe(
        num_layers=4, sliding_window=64,
        layer_types=("sliding_attention",) * 3 + ("full_attention",)),
], ids=["dense", "moe", "mla", "windowed-moe"])
def test_every_other_family_answers_one_group(cfg):
    assert registry.page_groups(cfg) == ((registry.page_layers(cfg), None),)


# the four step programs of a tiny engine of three families that were there,
# as the PARENT of PR 49 lowered them (sha256 of ``lower(...).as_text()``,
# this installation's JAX on the CPU): one group means the tables, the
# allocations and the programs they always had. A later PR that changes a
# step program on purpose re-records these (``_program_hashes`` prints them).
PARENT_PROGRAMS = json.loads(
    open(os.path.join(ROOT, "tests/data/step_programs_pr48.json")).read())


def _program_hashes(mcfg):
    """Drive a tiny engine through all four step programs, recording each
    program's arguments at its first call, and hash what it lowers to."""
    eng = TpuEngine(TpuEngineConfig(
        model=mcfg, num_blocks=64, block_size=PAGE, max_batch_size=2, max_context=256,
        prefill_buckets=(32,), decode_steps=4, decode_pipeline=1, mixed_admission=True,
        seed=1))
    seen = {}

    def spy(name):
        fn = getattr(eng, name)

        def call(*args, **kw):
            if name not in seen:
                shapes = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype)
                    if not hasattr(a, "dtype") else jax.ShapeDtypeStruct(a.shape, a.dtype),
                    (args, kw))
                seen[name] = hashlib.sha256(
                    fn.lower(*shapes[0], **shapes[1]).as_text().encode()).hexdigest()
            return fn(*args, **kw)
        setattr(eng, name, call)

    for name in ("_prefill_fn", "_decode_fn", "_decode_multi_fn", "_mixed_fn"):
        spy(name)
    try:
        vocab = mcfg.vocab_size
        # a resident decode, a chunk beside it (mixed), then three at once on
        # two slots (one waits: the single-step decode)
        run(answer(eng, prompts((40,), vocab), n=6, resident=prompts((20,), vocab, 2)[0]))
        run(answer(eng, prompts((20, 20, 20), vocab, 3), n=10))
    finally:
        eng.stop()
    return seen


@pytest.mark.parametrize("family", sorted(PARENT_PROGRAMS))
def test_step_programs_of_one_group_families_lower_to_the_parents_text(family):
    mcfg = {
        "dense": llama.LlamaConfig.tiny(dtype=jnp.float32),
        "moe": moe.MoeConfig.tiny_moe(),
        "mla": mla.MlaConfig.tiny_mla(dtype=jnp.float32),
    }[family]
    got = _program_hashes(mcfg)
    assert set(got) == set(PARENT_PROGRAMS[family])
    assert got == PARENT_PROGRAMS[family]
