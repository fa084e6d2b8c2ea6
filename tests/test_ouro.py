"""Ouro (``ouro``: one stack run several times a token, a cache slot a (pass,
layer)) at test scale on the CPU (``tiny-ouro``: 3 passes over 3 layers of 4
heads x 64, so that passes != layers != anything else and a swapped index
shows): the engine's prefill in chunks of two sizes, mixed steps and decode
against the plain reference's full forwards
(benchmarks/reference/ouro_decoder.py), the pages it holds of the first and
the last slot, every named wrong computation told apart (a wrong slot index
both ways, the final norm once, no output norms, ...), one pass as the dense
stack, a prefix hit over every slot, the cache's bytes in slots, tp 2, the
refusals, the published config and a checkpoint under its names."""

import asyncio
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import system
from benchmarks.adapters import ouro as adapter
from benchmarks.reference import ouro_decoder as ref
from dynamo_tpu.engine import weights
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models import checkpoint, llama, ouro, registry
from dynamo_tpu.models.ouro import OuroConfig
from dynamo_tpu.parallel.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, L = 3, 3
# float32 engine against the float32 reference: 9 layer applications
TOL = {"worst_nat": 2e-4, "mean_nat": 2e-5, "first_slot_cache_rel": 1e-5,
       "last_slot_cache_rel": 1e-4}


def file_cfg(**kw):
    """The benchmark's configuration file cut to test scale (the reference
    reads the public keys)."""
    with open(os.path.join(ROOT, "benchmarks/configs/ouro-2.6b.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=128, num_hidden_layers=L, layer_types=["full_attention"] * L,
        intermediate_size=352, num_attention_heads=4, num_key_value_heads=4, head_dim=64,
        total_ut_steps=T, vocab_size=512, torch_dtype="float32",
        reference_tolerance=dict(TOL),
    )
    cfg.update(kw)
    return cfg


def build(cfg=None, mesh=None, **kw):
    cfg = cfg or file_cfg()
    opts = dict(num_blocks=64, block_size=16, max_batch_size=4, max_context=256,
                prefill_buckets=(16, 32), decode_steps=4, decode_pipeline=2, seed=3)
    opts.update(kw)
    return TpuEngine(TpuEngineConfig(model=adapter.model_config(cfg), **opts), mesh=mesh)


def run(coro):
    # ONE event loop for every drive of an engine: its loop task lives on the
    # loop that first drove it
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
# the engine against the reference's full forwards
# ---------------------------------------------------------------------------

SERVED = {
    # name: (engine options, a resident request beside the chunks)
    "bucket16": (dict(prefill_buckets=(16,)), False),
    "buckets16-32": (dict(), False),
    "mixed-steps": (dict(mixed_admission=True), True),
    "kernels-interpreted": (dict(use_pallas=True, mixed_admission=True), True),
}


@pytest.fixture(scope="module", params=sorted(SERVED))
def served(request):
    opts, with_resident = SERVED[request.param]
    # the kernels copy pages of 128 lanes: 2 heads x 128 there
    keys = dict(num_attention_heads=2, num_key_value_heads=2, head_dim=128) \
        if opts.get("use_pallas") else {}
    cfg = file_cfg(**keys)
    eng = build(cfg, **opts)
    ps = prompts((70, 41, 19))
    resident = prompts((20,), seed=9)[0] if with_resident else None
    phases = []
    eng.stats_hook = lambda s: phases.append(s)
    recs = run(answer(eng, ps, resident=resident))
    yield cfg, eng, ps, recs, phases
    eng.stop()


def test_engine_matches_the_reference(served):
    """Logprobs of what the engine emitted (prefill in chunks, then decode
    through the pages), and the pages it holds of slot (0, 0) and slot
    (T-1, L-1), found once by content and read at the same block ids."""
    cfg, eng, ps, recs, phases = served
    res = ref.compare(cfg, adapter.reference_params(eng), samples_of(ps, recs), 128)
    assert res["ok"], res
    assert res["tokens_compared"] == 36 and res["cache_pages_compared"] >= 6
    if eng.mixed_enabled:
        assert any(s.phase == "mixed" for s in phases)


def test_the_counters_count_passes_and_slots(served):
    _, eng, ps, _, phases = served
    steps = [s for s in phases if s.ouro_stack_tokens]
    assert steps and all(s.ouro_pass_tokens == T * s.ouro_stack_tokens for s in steps)
    # a decode row at position p reads p + 1 keys in each of the T x L slots
    decode = [s for s in steps if s.phase == "decode"]
    assert decode and all(s.ouro_slot_keys_read % (T * L) == 0 for s in decode)
    assert all(s.ouro_slot_keys_read >= T * L * s.ouro_stack_tokens for s in decode)
    assert all(s.ouro_stack_tokens is None for s in phases if s.phase == "prefill")
    if eng.use_pallas:
        # the decode-only kernel launches once a SLOT a step, and the host
        # counts its chunks of pages by slot
        assert len(eng._paged_layers) == T * L
        assert sum(s.paged_chunks_whole or 0 for s in phases) % (T * L) == 0


@pytest.fixture(scope="module")
def whole():
    """The tiny model served once: what the told-apart tests compare."""
    cfg = file_cfg()
    eng = build(cfg)
    ps = prompts((70, 41, 19), seed=4)
    recs = run(answer(eng, ps))
    yield cfg, eng, ps, recs
    eng.stop()


# the reading that tells each wrong computation from the honest engine
TOLD_BY = {
    "one_pass_fewer": "worst_logprob_difference_nat",
    "final_norm_once": "worst_logprob_difference_nat",
    "no_out_norms": "worst_logprob_difference_nat",
    "read_pass0_slot": "worst_logprob_difference_nat",
    "read_last_pass_slot": "worst_logprob_difference_nat",
    # rotary is relative inside a pass: no logit moves, the held keys do
    "positions_advanced": "last_slot_cache_difference",
    "skipped_layer": "worst_logprob_difference_nat",
    "cache_int8": "first_slot_cache_difference",
}
LIMIT_OF = {reading: limit for limit, _, readings in ref.LIMITS for reading in readings}


@pytest.mark.parametrize("name", sorted(TOLD_BY))
def test_each_named_wrong_computation_is_told_apart(whole, name):
    """A slot index both ways (every pass reading pass 0's slot; every pass
    in ONE slot, reading the last pass's keys of the tokens before), a pass
    fewer, the final norm once, no output norms, positions advanced a pass, a
    skipped layer, an 8-bit cache: each fails a limit the honest engine
    passes, by the reading named here."""
    cfg, eng, ps, recs = whole
    assert set(TOLD_BY) == set(ref.wrong_variants(cfg))
    res = ref.compare(cfg, adapter.reference_params(eng), samples_of(ps, recs), 128,
                      **ref.wrong_variants(cfg)[name])
    assert not res["ok"], (name, res)
    reading = TOLD_BY[name]
    assert res[reading] > 10 * TOL[LIMIT_OF[reading]], (name, res)


def test_positions_advanced_moves_no_logit(whole):
    """The one variant the logprobs cannot tell: named as such."""
    cfg, eng, ps, recs = whole
    res = ref.compare(cfg, adapter.reference_params(eng), samples_of(ps, recs), 128,
                      advance_positions=True)
    assert res["worst_logprob_difference_nat"] <= TOL["worst_nat"]
    assert res["first_slot_cache_difference"] <= TOL["first_slot_cache_rel"]


# ---------------------------------------------------------------------------
# the family against the dense one, and its own loop against itself
# ---------------------------------------------------------------------------


def test_one_pass_without_output_norms_is_the_dense_stack():
    """Ties the family to ``llama.layer_forward``: the same weights through
    one pass, the output norms off, are the dense family's hidden states."""
    cfg = OuroConfig.tiny(passes=1, out_norms=False, dtype=jnp.float32)
    params = ouro.init_params(jax.random.PRNGKey(2), cfg)
    assert "attn_out_norm" not in params["layers"][0]
    tokens = jnp.asarray(prompts((33,), seed=2)[0])
    pos = jnp.arange(33)
    mine = ouro.forward(params, cfg, tokens, pos, ouro.stateless_attend)
    dense_cfg = llama.LlamaConfig(**{
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(llama.LlamaConfig)})
    dense = llama.forward(params, dense_cfg, tokens, pos,
                          lambda q, k, v, i: ouro.stateless_attend(q, k, v, i))
    np.testing.assert_allclose(np.asarray(mine), np.asarray(dense), atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(ouro.lm_logits(params, cfg, mine)),
        np.asarray(llama.lm_logits(params, dense_cfg, mine)))


def test_the_traced_loop_is_the_unrolled_passes():
    """``forward`` under a ``loop`` that carries nothing (a stateless
    attend) against its own unrolled passes: the pass index a traced scalar
    or a Python number, the same states."""
    cfg = OuroConfig.tiny(dtype=jnp.float32)
    params = ouro.init_params(jax.random.PRNGKey(3), cfg)
    tokens, pos = jnp.asarray(prompts((21,), seed=3)[0]), jnp.arange(21)
    seen = []

    def attend(q, k, v, i, page_pass=None):
        seen.append(page_pass)
        return ouro.stateless_attend(q, k, v, i)

    unrolled = ouro.forward(params, cfg, tokens, pos, attend)
    assert seen == [t for t in range(T) for _ in range(L)]
    looped = jax.jit(lambda: ouro.forward(
        params, cfg, tokens, pos, attend,
        loop=lambda one_pass, x, n: jax.lax.fori_loop(0, n, lambda t, x: one_pass(x, t), x)))()
    np.testing.assert_allclose(np.asarray(looped), np.asarray(unrolled), atol=1e-5)


def test_the_exit_gate_is_a_distribution_and_part_of_no_logit(whole):
    cfg, eng, ps, _ = whole
    params = adapter.reference_params(eng)
    dist = ref.exit_distribution(cfg, params, ps[1])
    assert dist.shape == (41, T)
    np.testing.assert_allclose(dist.sum(axis=1), 1.0, atol=1e-6)
    # a gate that would send every token out at pass 0 moves no logit
    loud = {**params, "exit_gate_b": np.full((1,), 9.0, np.float32)}
    assert ref.exit_distribution(cfg, loud, ps[1])[:, 0].min() > 0.99
    a, _ = ref.logprobs(cfg, params, ps[1], [40])
    b, _ = ref.logprobs(cfg, loud, ps[1], [40])
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the cache: slots that outnumber the layers
# ---------------------------------------------------------------------------


def test_the_cache_is_counted_in_slots(whole):
    """``passes x num_layers`` slots under ONE table: a layer's arrays hold a
    pool a pass, a block's bytes are 3 x a same-sized dense model's, and the
    share of the cache in use reads blocks, which name a page in every slot."""
    _, eng, *_ = whole
    mcfg = eng.mcfg
    assert registry.page_passes(mcfg) == T and registry.page_slots(mcfg) == T * L
    assert registry.page_layers(mcfg) == tuple(range(L))
    assert len(eng.k_caches) == L
    assert all(k.shape == (T * 64, 16, 4, 64) for k in eng.k_caches + eng.v_caches)
    dense = TpuEngine(TpuEngineConfig(
        model=llama.LlamaConfig(**{f.name: getattr(mcfg, f.name)
                                   for f in dataclasses.fields(llama.LlamaConfig)}),
        num_blocks=64, block_size=16, max_batch_size=2, max_context=128,
        prefill_buckets=(16,), decode_steps=4, seed=3))
    try:
        assert registry.page_passes(dense.mcfg) == 1
        assert registry.page_slots(dense.mcfg) == L
        assert eng.kv_bytes_per_block == T * dense.kv_bytes_per_block
        assert eng.kv_bytes_per_block == 16 * 2 * 4 * 64 * 4 * T * L
    finally:
        dense.stop()
    assert eng.allocator.num_blocks == 64 and eng.allocator.active_blocks == 0


def test_kv_active_share_reads_blocks_of_every_slot():
    """A request of 40 + 6 tokens holds 3 blocks of 64 (and one booked for
    the horizon ahead) whatever the slots: the share the benchmark reads
    (kv_active_blocks / kv_total_blocks) is of blocks, each a page in all
    ``passes x num_layers`` slots."""
    eng = build()
    seen = []
    eng.stats_hook = lambda s: seen.append(s)
    try:
        run(answer(eng, prompts((40,), seed=6), n=6))
    finally:
        eng.stop()
    assert max(s.kv_active_blocks for s in seen) in (3, 4)
    assert all(s.kv_total_blocks == 64 for s in seen)


def test_a_prefix_hit_restores_every_slot():
    """The same prefix asked twice: the second request hits its blocks, which
    name a page in every slot, and its logits are those the reference gives
    a request that hit nothing."""
    cfg = file_cfg()
    eng = build(cfg)
    try:
        doc = prompts((64,), seed=11)[0]
        first = run(answer(eng, [doc + [7]], n=1, prefix="doc"))[0]
        ask = doc + prompts((21,), seed=12)[0]
        hit = run(answer(eng, [ask], n=8, prefix="hit"))[0]
        assert hit["cached_tokens"] == 64 and first["cached_tokens"] in (None, 0)
        res = ref.compare(cfg, adapter.reference_params(eng), samples_of([ask], [hit]), 128)
        assert res["ok"], res
        fresh = build(cfg)
        try:
            miss = run(answer(fresh, [ask], n=8, prefix="miss"))[0]
        finally:
            fresh.stop()
        assert miss["cached_tokens"] in (None, 0) and miss["tokens"] == hit["tokens"]
        np.testing.assert_allclose(hit["logprobs"], miss["logprobs"], atol=1e-5)
    finally:
        eng.stop()


def test_a_mixed_step_is_its_chunk_and_its_rows_apart():
    """The same requests with chunks riding the resident decode rows (mixed
    steps) and with prefill and decode dispatched apart: the same tokens and
    logprobs."""
    ps = prompts((45, 30), seed=13)
    resident = prompts((20,), seed=14)[0]
    out = {}
    for mixed in (True, False):
        eng = build(mixed_admission=mixed)
        phases = []
        eng.stats_hook = lambda s: phases.append(s.phase)
        try:
            out[mixed] = run(answer(eng, ps, resident=resident))
        finally:
            eng.stop()
        assert ("mixed" in phases) == mixed
    for a, b in zip(out[True], out[False]):
        assert a["tokens"] == b["tokens"]
        np.testing.assert_allclose(a["logprobs"], b["logprobs"], atol=1e-5)


def test_tp2_is_tp1():
    """``tp`` stays allowed: plain head sharding, the slots' pools sharded on
    their kv heads."""
    ps = prompts((37, 18), seed=15)
    out = {}
    for tp in (1, 2):
        eng = build(mesh=make_mesh(tp=tp, devices=jax.devices()[:tp]), tp=tp)
        try:
            out[tp] = run(answer(eng, ps, n=8))
        finally:
            eng.stop()
    for a, b in zip(out[1], out[2]):
        assert a["tokens"] == b["tokens"]
        np.testing.assert_allclose(a["logprobs"], b["logprobs"], atol=1e-4)


# ---------------------------------------------------------------------------
# what it cannot do yet, the published config, a checkpoint
# ---------------------------------------------------------------------------

REFUSALS = {
    "pp": (dict(pp=2), "stacks num_layers pools"),
    "sp": (dict(sp=2), "no pass to offset it"),
    "draft": (dict(spec=True), "shadow cache"),
    "int8": (dict(kv_quantized=True), "sized by num_blocks"),
    "vision": (dict(vision=True), "vision"),
    "transfer": (dict(transfer=True), "transfer plane"),
    "kvbm": (dict(kvbm=True), "KVBM"),
    "lora": (dict(lora=True), "LoRA"),
}


@pytest.mark.parametrize("asked", sorted(REFUSALS))
def test_each_refusal_raises_with_its_reason(asked):
    kw, why = REFUSALS[asked]
    cfg = OuroConfig.tiny()
    with pytest.raises(ValueError) as refused:
        registry.check_supported(cfg, **kw)
    said = str(refused.value)
    assert why in said
    if asked != "lora":
        assert "page slots that outnumber the layers" in said and "OuroConfig" in said
    # asked nothing, or tp, refused nothing; a dense family is refused none of these
    registry.check_supported(cfg)
    registry.check_supported(cfg, tp=2)
    if asked != "lora":
        registry.check_supported(llama.LlamaConfig.tiny(), **kw)


def test_an_exit_before_the_last_pass_and_an_8_bit_cache_are_refused_at_construction():
    with pytest.raises(ValueError, match="different numbers of passes"):
        OuroConfig.tiny(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="different numbers of passes"):
        adapter.model_config(file_cfg(early_exit_threshold=0.5))
    with pytest.raises(ValueError, match="kv_dtype=int8"):
        build(kv_dtype="int8")
    with pytest.raises(ValueError, match="pp"):
        build(pp=2)


def test_config_from_hf_reads_the_catalogs_config_and_the_presets_answer(tmp_path):
    from dynamo_tpu.engine.__main__ import PRESETS

    with open(os.path.join(ROOT, "benchmarks/configs/ouro-2.6b.json")) as f:
        published = json.load(f)
    (tmp_path / "config.json").write_text(json.dumps(published))
    cfg = weights.config_from_hf(str(tmp_path))
    big = PRESETS["ouro-2.6b"]()
    assert type(cfg) is OuroConfig and cfg == big
    assert (cfg.passes, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        4, 48, 16, 16, 128)
    assert registry.page_slots(cfg) == 192 and not cfg.tie_embeddings
    # 1.5 MiB of cache a token over the slots, in the pages' two bytes
    assert 2 * cfg.num_kv_heads * cfg.head_dim * 2 * registry.page_slots(cfg) == 1572864
    assert adapter.model_config(published) == big
    tiny = PRESETS["tiny-ouro"]()
    assert (tiny.passes, tiny.num_layers) == (T, L)
    assert registry.family(tiny) is ouro and registry.prefix_reusable(tiny)


def test_a_checkpoint_under_the_published_names_loads(monkeypatch):
    """No checkpoint is here to hold the loader to account: this round-trips
    one written under the tensor names the loader states."""
    cfg = OuroConfig.tiny(dtype=jnp.float32)
    params = ouro.init_params(jax.random.PRNGKey(7), cfg)
    params["exit_gate_w"] = jnp.full((cfg.hidden_size, 1), 0.25, jnp.float32)
    tensors = {"model.embed_tokens.weight": params["embed"],
               "model.norm.weight": params["final_norm"],
               "lm_head.weight": np.asarray(params["lm_head"]).T,
               "model.early_exit_gate.weight": np.asarray(params["exit_gate_w"]).T,
               "model.early_exit_gate.bias": params["exit_gate_b"]}
    names = {
        "attn_norm": "input_layernorm", "attn_out_norm": "input_layernorm_2",
        "mlp_norm": "post_attention_layernorm", "mlp_out_norm": "post_attention_layernorm_2",
        "wq": "self_attn.q_proj", "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
        "wo": "self_attn.o_proj", "w_gate": "mlp.gate_proj", "w_up": "mlp.up_proj",
        "w_down": "mlp.down_proj",
    }
    for i, lp in enumerate(params["layers"]):
        assert set(lp) == set(names)
        for ours, theirs in names.items():
            w = np.asarray(lp[ours])
            tensors[f"model.layers.{i}.{theirs}.weight"] = w.T if w.ndim == 2 else w
    monkeypatch.setattr(checkpoint, "open_safetensors",
                        lambda path: ((k, np.asarray(v)) for k, v in tensors.items()))
    loaded = weights.load_params("unused", cfg)
    jax.tree_util.tree_map(np.testing.assert_array_equal, loaded, params)
    del tensors["model.layers.1.input_layernorm_2.weight"]
    with pytest.raises(ValueError, match="lack a tensor"):
        weights.load_params("unused", cfg)
