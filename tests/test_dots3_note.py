"""dots3-note-prev (``dots3_note``) at test scale on the CPU (hidden 128; full
layers 4 heads over a 256-lane latent with an indexer keeping 24; sliding
layers 2 heads over a 512-lane latent of their own under a window of 9; page
8; the dense layer and two periods; 8 experts top 2 of which a share holds
4): the engine's prefill in chunks, mixed steps and decode against the plain
reference's one forward (benchmarks/reference/dots3_note_decoder.py), each
kind alone, the window's and the selection's edges, the gate, the rescale and
every other named wrong computation told apart, the shares adding up, a
prefix hit over both page groups, the sliding group's pages let go, the
windowed launch interpreted against its twin, and the refusals."""

import asyncio
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import system
from benchmarks.adapters import dots3_note as adapter
from benchmarks.reference import dots3_note_decoder as ref
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models import dots3_note, llama, registry
from dynamo_tpu.models import moe as moelib
from dynamo_tpu.models.dots3_note import FULL, SLIDING, Dots3NoteConfig
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import pallas_latent as plat

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W, TOPK, PAGE = 9, 24, 8
TOL = {"worst_nat": 2e-4, "mean_nat": 2e-5}
PERIODS = [FULL] + [FULL, SLIDING, SLIDING, SLIDING] * 2


def file_cfg(**kw):
    """The benchmark's configuration file cut to test scale (the reference
    reads the public keys)."""
    with open(os.path.join(ROOT, "benchmarks/configs/dots3-note-ep8-d5.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=128, num_hidden_layers=9, layer_types=list(PERIODS), intermediate_size=256,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=96, kv_lora_rank=256,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        index_topk=TOPK, index_n_heads=4, index_head_dim=32,
        swa_num_attention_heads=2, swa_num_key_value_heads=2, swa_q_lora_rank=96,
        swa_kv_lora_rank=512, swa_qk_nope_head_dim=48, swa_qk_rope_head_dim=16, swa_v_head_dim=32,
        sliding_window_size=W, moe_intermediate_size=64, router_outputs=8, n_routed_experts=4,
        experts_held_first=4, num_experts_per_tok=2, vocab_size=512, torch_dtype="float32",
        reference_tolerance=dict(TOL),
    )
    cfg.update(kw)
    return cfg


def build(cfg=None, **kw):
    cfg = cfg or file_cfg()
    opts = dict(num_blocks=96, block_size=PAGE, max_batch_size=4, max_context=256,
                prefill_buckets=(16, 32), decode_steps=4, decode_pipeline=2, seed=3)
    opts.update(kw)
    return TpuEngine(TpuEngineConfig(model=adapter.model_config(cfg), **opts))


def run(coro):
    if "loop" not in run.__dict__:
        run.loop = asyncio.new_event_loop()
    return run.loop.run_until_complete(coro)


def prompts(lengths, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).tolist() for n in lengths]


async def answer(eng, ps, n=12, prefix="r", resident=None):
    started, res = asyncio.Event(), None
    if resident is not None:
        res = asyncio.ensure_future(system.generate(
            eng, f"{prefix}-res", resident, 48, on_chunk=lambda *_: started.set()))
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


# ---------------------------------------------------------------------------
# the engine against the reference's one forward
# ---------------------------------------------------------------------------

SERVED = {
    # name: (engine options, file keys, a resident request beside the chunks)
    "bucket16": (dict(prefill_buckets=(16,)), {}, False),
    "buckets16-32": (dict(), {}, False),
    "mixed-steps": (dict(mixed_admission=True), {}, True),
    # each kind's layer alone: a dense and a sparse layer of one kind
    "full-layers-alone": (dict(), dict(num_hidden_layers=2, layer_types=[FULL, FULL]), False),
    "sliding-layers-alone": (dict(), dict(num_hidden_layers=2, layer_types=[SLIDING, SLIDING]), False),
}


@pytest.fixture(scope="module", params=sorted(SERVED))
def served(request):
    opts, keys, with_resident = SERVED[request.param]
    cfg = file_cfg(**keys)
    eng = build(cfg, **opts)
    ps = prompts((150, 77, 30))          # past index_topk and 16 windows; between; near both
    resident = prompts((20,), seed=9)[0] if with_resident else None
    phases = []
    eng.stats_hook = lambda s: phases.append(s)
    recs = run(answer(eng, ps, resident=resident))
    yield cfg, eng, ps, recs, phases
    eng.stop()


def test_engine_matches_the_reference(served):
    cfg, eng, ps, recs, phases = served
    res = ref.compare(cfg, adapter.reference_params(eng), samples_of(ps, recs), 256)
    assert res["ok"], res
    assert res["tokens_compared"] == 36
    if eng.mixed_enabled:
        assert any(s.phase == "mixed" for s in phases)


@pytest.fixture(scope="module")
def whole():
    """The whole tiny model served once: what the told-apart tests compare."""
    cfg = file_cfg()
    eng = build(cfg)
    ps = prompts((150, 77, 30), seed=4)
    phases = []
    eng.stats_hook = lambda s: phases.append(s)
    recs = run(answer(eng, ps))
    yield cfg, eng, ps, recs, phases
    eng.stop()


@pytest.mark.parametrize("name", sorted(ref.WRONG))
def test_each_named_wrong_computation_is_told_apart(whole, name):
    """The gate, the rescale, the window by one key (8 and 10 for 9 here:
    ``window_512`` / ``window_1024`` at the published 513), the sliding
    layers' rotary base, a sliding layer reading everything, the selection
    ignored or halved, an 8-bit cache, a skipped layer: each fails the
    tolerance the honest engine passes."""
    cfg, eng, ps, recs, _ = whole
    wrong = dict(ref.WRONG[name])
    if "window" in wrong:
        wrong["window"] = {512: W - 1, 1024: W + 1}[wrong["window"]]
    res = ref.compare(cfg, adapter.reference_params(eng), samples_of(ps, recs), 256, **wrong)
    assert not res["ok"], (name, res)


@pytest.mark.parametrize("keys", [TOPK - 1, TOPK, TOPK + 1, W - 1, W, W + 1])
def test_the_selections_and_the_windows_edge(keys):
    """A first generated token that sees exactly ``keys`` keys: at
    ``index_topk`` - 1 and exactly, every causal key is selected; one more and
    the indexer drops one; at the window - 1 and exactly a sliding layer sees
    its whole context; one more and the oldest key is out. Each against the
    reference, which is told nothing but the prompt."""
    cfg = file_cfg(num_hidden_layers=5, layer_types=PERIODS[:5])
    eng = build(cfg, prefill_buckets=(16,))
    try:
        ps = prompts((keys,), seed=keys)
        recs = run(answer(eng, ps, n=3))
        res = ref.compare(cfg, adapter.reference_params(eng), samples_of(ps, recs), 64)
        assert res["ok"], res
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of every share, with what every chip computes alike
    (the shared expert) counted once, are the uncut layer of the uncut
    reference: a sparse layer's feed-forward, 2 shares of 4 experts."""
    from benchmarks.reference.mla_dsa_decoder import _experts

    cfg = Dots3NoteConfig.tiny(dtype=jnp.float32, experts_held=None)
    kind = cfg.kind(1)
    p = dots3_note.init_params(jax.random.PRNGKey(5), cfg)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (24, cfg.hidden_size), jnp.float32)
    h = llama.rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    shared_only = dict(p, **{k: p[k][:0] for k in ("w_egate", "w_eup", "w_edown")})

    def share(first, count):
        held = dataclasses.replace(kind, experts_held=(first, count))
        sp = dict(p, **{k: p[k][first:first + count] for k in ("w_egate", "w_eup", "w_edown")})
        return moelib.routed_shared_ffn(sp, held, h)

    common = moelib.routed_shared_ffn(shared_only, dataclasses.replace(kind, experts_held=(0, 0)), h)
    total = common + sum(share(f, 4) - common for f in (0, 4))
    uncut = _experts({k: p[k] for k in ref._SPARSE_KEYS}, x, top_k=2, eps=cfg.rms_norm_eps,
                     renorm=True, scaling=1.0, first=0) - x
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)


# ---------------------------------------------------------------------------
# the page groups: shapes, a prefix hit over both, pages let go
# ---------------------------------------------------------------------------


def test_a_groups_arrays_take_the_shape_its_layers_need(whole):
    _, eng, *_ = whole
    mcfg = eng.mcfg
    assert registry.page_groups(mcfg) == (((0, 1, 5), None), ((2, 3, 4, 6, 7, 8), W))
    pool = eng._win_groups[0].allocator.num_blocks
    for i, (k, v) in enumerate(zip(eng.k_caches, eng.v_caches)):
        full = mcfg.layer_types[i] == FULL
        assert k.shape == ((96, PAGE, 2, 128) if full else (pool, PAGE, 4, 128))
        assert v.shape == ((96 if full else pool), PAGE, 2, 128)   # the one tile a step reads
    # every other family's pages are shaped as they were
    dense = llama.LlamaConfig.tiny()
    token = (dense.num_kv_heads, dense.head_dim)
    assert registry.page_shapes(dense) == ((token, token),) * dense.num_layers


def test_the_sliding_groups_pages_are_let_go_and_the_table_shifts(whole):
    _, eng, ps, _, phases = whole
    released = sum(s.page_groups_released[1] for s in phases if s.page_groups_released)
    # a 150-token prompt + 12 tokens leaves about 20 pages of 8 behind a window of 9
    assert released >= 15
    held = [s.page_groups_held for s in phases if s.page_groups_held]
    assert max(h[1] for h in held) <= 3 * eng._win_groups[0].pages < max(h[0] for h in held)
    assert all(a.active_blocks == 0 for a in [eng.allocator, eng._win_groups[0].allocator])
    # the counters of both kinds ride the step's readback
    steps = [s for s in phases if s.winlat_rows]
    assert steps and all(s.winlat_keys_read <= s.winlat_rows * W for s in steps)
    assert all(s.dsa_keys_selected <= s.dsa_keys_causal == s.dsa_keys_scored
               for s in phases if s.dsa_keys_causal)


def test_a_prefix_hit_restores_both_groups():
    """The same long prefix asked twice: the second request hits the document
    in the full group and the window's tail in the sliding one, and its
    logits are those of a request that hit nothing (both held to the
    reference, and to each other)."""
    cfg = file_cfg(num_hidden_layers=5, layer_types=PERIODS[:5])
    eng = build(cfg)
    try:
        doc = prompts((96,), seed=11)[0]
        first = run(answer(eng, [doc + [7]], n=1, prefix="doc"))[0]
        ask = doc + prompts((21,), seed=12)[0]
        hit = run(answer(eng, [ask], n=8, prefix="hit"))[0]
        assert hit["cached_tokens"] == 96 and first["cached_tokens"] in (None, 0)
        res = ref.compare(cfg, adapter.reference_params(eng), samples_of([ask], [hit]), 128)
        assert res["ok"], res
    finally:
        eng.stop()
    cold = build(cfg)
    try:
        miss = run(answer(cold, [ask], n=8, prefix="miss"))[0]
    finally:
        cold.stop()
    assert miss["cached_tokens"] in (None, 0) and miss["tokens"] == hit["tokens"]
    np.testing.assert_allclose(miss["logprobs"], hit["logprobs"], atol=2e-5)


# ---------------------------------------------------------------------------
# the launch: windowed_latent_attention interpreted against its jnp twin
# ---------------------------------------------------------------------------

H, RANK = 4, 512


def _paged(seed, nb=40, bs=8, mb=12):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    kc = jax.random.normal(k[0], (nb, bs, RANK // 128, 128), jnp.float32).astype(jnp.bfloat16)
    vc = jax.random.normal(k[1], (nb, bs, 2, 128), jnp.float32).astype(jnp.bfloat16)
    tables = jax.random.permutation(k[2], jnp.arange(1, nb))[: 3 * mb].reshape(3, mb)
    return kc, vc, tables.astype(jnp.int32)


LAUNCHES = {
    # name: (chunk queries, q_lens, seq_lens) over three tables of 96 tokens
    "decode-rows": (0, [1, 1, 0], [70, 9, 0]),
    "chunk": (16, [13], [90]),
    "mixed-step": (16, [16, 1, 1], [60, 10, 33]),
}


@pytest.mark.parametrize("name", sorted(LAUNCHES))
@pytest.mark.parametrize("window", [9, 10, 40])
def test_windowed_latent_attention_matches_its_twin(name, window):
    n_chunk, q_lens, seq_lens = LAUNCHES[name]
    kc, vc, tables = _paged(1)
    R = len(q_lens)
    tables = tables[:R]
    Tq = n_chunk + R - (1 if n_chunk else 0)
    q = jax.random.normal(jax.random.PRNGKey(2), (Tq, H, RANK + 128), jnp.float32).astype(jnp.bfloat16)
    q_lens, seq_lens = jnp.asarray(q_lens, jnp.int32), jnp.asarray(seq_lens, jnp.int32)
    first = 1 if n_chunk else 0
    q_starts = jnp.concatenate([jnp.zeros((first,), jnp.int32), n_chunk + jnp.arange(R - first)])
    twin = att.paged_latent_attention(q, kc, vc, tables, q_starts, q_lens, seq_lens, 0.07, window=window)
    got = plat.paged_latent_attention(
        q, kc, vc, tables, q_lens, seq_lens, scale=0.07, n_chunk=n_chunk, interpret=True,
        window=window, name=plat.WINDOWED_KERNEL_NAME)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(twin, np.float32),
                               atol=2e-2, rtol=2e-2)
    # and the window matters: every causal key is another answer
    if int(jnp.max(seq_lens)) > window + 1:
        dense = att.paged_latent_attention(q, kc, vc, tables, q_starts, q_lens, seq_lens, 0.07)
        assert float(jnp.max(jnp.abs(dense.astype(jnp.float32) - twin.astype(jnp.float32)))) > 0.05


def test_the_windowed_launch_starts_at_its_windows_chunk():
    """A decode row deep in a long table: poison (NaN) in every page that
    lies wholly behind the chunk its window starts in never reaches the
    output: those chunks are not walked."""
    kc, vc, _ = _paged(3, nb=310, bs=8, mb=100)
    tables = jnp.arange(1, 301, dtype=jnp.int32)[None]
    cp = plat._chunk_pages(kc, 300, 9)
    seq = 300 * 8 - 3
    lo_chunk = (seq - 1 - 9 + 1) // (cp * 8)
    assert lo_chunk >= 1, "the table is too short for a chunk to be skipped"
    kc = kc.at[1:1 + lo_chunk * cp].set(jnp.nan)
    q = jax.random.normal(jax.random.PRNGKey(4), (1, H, RANK + 128), jnp.float32).astype(jnp.bfloat16)
    got = plat.paged_latent_attention(
        q, kc, vc, tables, jnp.ones((1,), jnp.int32), jnp.full((1,), seq, jnp.int32),
        scale=0.07, interpret=True, window=9, name=plat.WINDOWED_KERNEL_NAME)
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# what the one-chip text path refuses a family that is both
# ---------------------------------------------------------------------------

REFUSALS = {
    # asked: (keywords, the rows' reason, the groups' reason)
    "tp": (dict(tp=2), "cannot shard on heads", "pools are not sharded"),
    "pp": (dict(pp=2), "carries the rows layout", "stacks ONE pool"),
    "sp": (dict(sp=2), "carries the rows layout", "attends one table"),
    "draft": (dict(spec=True), "no latent question", "shadow cache"),
    "lora": (dict(lora=True), "LoRA", "LoRA"),
    "int8": (dict(kv_quantized=True), "latent kernels read bf16 rows", "ONE pool's page count"),
    "vision": (dict(vision=True), "vision", "vision"),
    "transfer": (dict(transfer=True), None, "transfer plane"),
    "kvbm": (dict(kvbm=True), None, "KVBM"),
}


@pytest.mark.parametrize("asked", sorted(REFUSALS))
def test_each_refusal_raises_with_both_reasons(asked):
    """ONE list for a family that holds its latents as rows AND keeps pages
    by layer kind: whichever check is asked, both reasons are in the answer."""
    kw, rows_why, groups_why = REFUSALS[asked]
    cfg = Dots3NoteConfig.tiny()
    with pytest.raises(ValueError) as by_groups:
        registry.check_groups_supported(cfg, **kw)
    said = str(by_groups.value)
    assert "latent held as rows" in said and "pages kept by layer kind" in said
    assert groups_why in said and (rows_why is None or rows_why in said)
    if rows_why is not None:
        with pytest.raises(ValueError) as by_rows:
            registry.check_dsa_supported(cfg, **kw)
        assert str(by_rows.value) == said
    # asked nothing, refused nothing; a family of neither trait is refused nothing
    registry.check_groups_supported(cfg)
    registry.check_dsa_supported(cfg)
    registry.check_groups_supported(llama.LlamaConfig.tiny(), **kw)


def test_engine_construction_refuses_an_8_bit_cache_and_the_presets_answer():
    from dynamo_tpu.engine.__main__ import PRESETS

    with pytest.raises(ValueError, match="kv_dtype=int8"):
        build(kv_dtype="int8")
    assert isinstance(PRESETS["tiny-dots3-note"](), Dots3NoteConfig)
    big = PRESETS["dots3-note"]()
    assert big.layer_types.count(FULL) == 13 and big.layer_types.count(SLIDING) == 33
    assert (big.kind(0).num_kv_heads, big.kind(2).num_kv_heads) == (4, 8)
    assert round(big.kind(0).kv_latent_scale, 3) == 3.162 and big.kind(2).sliding_window == 513
