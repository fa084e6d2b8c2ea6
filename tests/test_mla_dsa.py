"""Learned sparse attention over a paged latent (models/mla.py with an
indexer; GLM-5.2's mechanism) and an expert layer that holds one chip's
share, at a test's size, against the benchmark's plain float32 reference
(benchmarks/reference/mla_dsa_decoder.py): the seam's new question (pure-JAX
twin and interpreted kernel), the engine (chunked prefill, mixed steps,
decode through the paged cache, a prefix hit), the share, the counters and
what the family refuses at construction.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import system
from benchmarks.adapters import mla_dsa as adapter
from benchmarks.reference import mla_dsa_decoder as ref
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models import mla, moe as moelib
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops.paged_attention import PagedAttention
from dynamo_tpu.parallel.mesh import make_mesh

TOPK = 16


def file_cfg(dtype="float32", **kw):
    """A configuration file's dict (the public keys) at a test's size: layer
    kinds dense-full, sparse-shared, sparse-full; 8 experts, 4 held."""
    cfg = {
        "vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 3, "layer_offset": 2,
        "num_attention_heads": 4, "intermediate_size": 256, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 4096, "tie_word_embeddings": False, "torch_dtype": dtype,
        "q_lora_rank": 96, "kv_lora_rank": 256, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
        "v_head_dim": 32, "router_outputs": 8, "n_routed_experts": 4, "experts_held_first": 4,
        "num_experts_per_tok": 2, "moe_intermediate_size": 64, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "n_shared_experts": 1, "n_group": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "rope_interleave": True,
        "indexer_rope_interleave": True,
        "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
        "index_topk": TOPK, "index_n_heads": 4, "index_head_dim": 32,
        "indexer_types": ["full", "full", "full", "shared", "full"],
        "mlp_layer_types": ["dense", "dense", "dense", "sparse", "sparse"],
        "reference_tolerance": {"worst_nat": 2e-3, "mean_nat": 5e-4},
    }
    cfg.update(kw)
    return cfg


def engine_of(cfg, use_pallas=None, **kw):
    opts = dict(num_blocks=64, block_size=16, max_batch_size=4, max_context=128,
                prefill_buckets=(16, 32), seed=3, use_pallas=use_pallas,
                mixed_admission=True if use_pallas else None)
    opts.update(kw)
    return TpuEngine(TpuEngineConfig(model=adapter.model_config(cfg), **opts))


async def generate(engine, prompts, n_out=20):
    recs = await asyncio.gather(*[
        system.generate(engine, f"r{i}", p, n_out) for i, p in enumerate(prompts)
    ])
    return recs, [
        {"prompt": p, "tokens": r["tokens"], "logprobs": r["logprobs"]}
        for p, r in zip(prompts, recs)
    ]


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, n).tolist() for n in lengths]


# ----------------------------------------------------------- the engine
async def test_chunked_prefill_and_decode_match_the_reference_in_float32():
    """Pure-JAX twin, float32: prompts of 72, 40 and 20 tokens (contexts to
    92, above and below index_topk) in chunks of 32, then 20 decoded tokens
    through the paged cache, against the reference's one full forward."""
    cfg = file_cfg()
    engine = engine_of(cfg)
    steps = []
    engine.stats_hook = steps.append
    try:
        _, samples = await generate(engine, prompts_of(72, 40, 20))
        params = adapter.reference_params(engine)
        res = ref.compare(cfg, params, samples, 128)
        assert res["ok"], res
        assert res["tokens_compared"] == 60
        # each of the family's own mistakes is far outside those bounds
        for wrong in ({"dense_attention": True}, {"shared": "none"}, {"shared": "own"},
                      {"topk_scale": 0.5}, {"no_router_bias": True},
                      {"no_routed_scale": True}, {"no_shared_expert": True},
                      {"skip_layer": 1}):
            bad = ref.compare(cfg, params, samples, 128, **wrong)
            assert not bad["ok"] and bad["worst_logprob_difference_nat"] > 0.02, (wrong, bad)
    finally:
        engine.stop()
    counted = [s for s in steps if s.dsa_keys_causal is not None]
    assert counted and all(s.phase in ("decode", "mixed") for s in counted)
    for s in counted:
        # 3 layers of which 2 select; a row attends over min(context, 16) keys
        assert s.dsa_keys_scored * 3 == s.dsa_keys_causal * 2
        assert 0 < s.dsa_keys_selected <= s.dsa_keys_causal
        assert s.moe_held_experts_touched == s.moe_experts_touched <= 2 * 4 * 8
        # a table of 8 pages is one chunk of the index keys' read: a table
        # of each of the step's rows, in each of the 2 selecting layers, in
        # each of a horizon's steps
        rows = 4 + (s.phase == "mixed")
        assert s.dsa_index_chunks_whole in (2 * rows, 2 * rows * 8), s
        # ... of which none is a run: a context of 92 ends inside its table
        assert s.dsa_index_chunks_run == 0
    assert all(s.dsa_keys_causal is None for s in steps if s.phase == "prefill")


async def test_the_interpreted_kernel_serves_mixed_steps_in_bfloat16():
    """use_pallas forced on the CPU: the launch sparse_latent_attention and
    the Pallas expert multiplication run interpreted, chunks ride fused
    mixed steps. bf16 against the float32 reference moves a tiny model's
    logprobs by a few hundredths; the selection ignored moves them by a nat."""
    cfg = file_cfg("bfloat16", reference_tolerance={"worst_nat": 0.7, "mean_nat": 0.07})
    engine = engine_of(cfg, use_pallas=True)
    steps = []
    engine.stats_hook = steps.append
    try:
        assert engine.use_pallas and engine.mixed_enabled and engine.kernels_interpreted
        _, samples = await generate(engine, prompts_of(72, 40, 20))
        params = adapter.reference_params(engine)
        res = ref.compare(cfg, params, samples, 128)
        assert res["ok"], res
        bad = ref.compare(cfg, params, samples, 128, dense_attention=True)
        assert not bad["ok"], bad
    finally:
        engine.stop()
    assert {"mixed", "decode"} <= {s.phase for s in steps}


async def test_a_prefix_hit_restores_the_index_keys():
    """The second request's prefix comes from the cache: latent rows and
    index keys under the same block ids, so it selects what a cold prefill
    selects and emits the same tokens at the same logprobs."""
    cfg = file_cfg()
    engine = engine_of(cfg)
    try:
        (prompt,) = prompts_of(80, seed=5)
        (cold,), _ = await generate(engine, [prompt], 12)
        (warm,), _ = await generate(engine, [prompt], 12)
        assert (cold["cached_tokens"] or 0) == 0 and warm["cached_tokens"] >= 64
        assert warm["tokens"] == cold["tokens"]
        np.testing.assert_allclose(warm["logprobs"], cold["logprobs"], atol=1e-5)
    finally:
        engine.stop()


@pytest.mark.parametrize("what,kw", [
    ("tp > 1", dict(tp=2)),
    ("kv_dtype=int8", dict(kv_dtype="int8")),
    ("a speculative draft", dict(spec_draft=mla.MlaConfig.tiny_mla(vocab_size=512))),
    ("LoRA", dict(lora_max_adapters=2)),
    ("pp / sp > 1", dict(sp=2)),
])
def test_what_the_family_cannot_do_yet_is_refused_at_construction(what, kw):
    mesh = None
    if "tp" in kw or "sp" in kw:
        mesh = make_mesh(tp=kw.get("tp", 1), sp=kw.get("sp", 1), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=what.replace(">", ".")):
        TpuEngine(TpuEngineConfig(
            model=adapter.model_config(file_cfg()), num_blocks=32, block_size=16,
            max_batch_size=2, max_context=64, prefill_buckets=(16,), **kw,
        ), mesh=mesh)


def test_a_configuration_without_an_indexer_keeps_the_576_lane_contract():
    cfg = mla.MlaConfig.deepseek_v3()
    assert (cfg.num_kv_heads, cfg.head_dim, cfg.index_topk) == (1, 576, 0)
    dsa = adapter.model_config(file_cfg())
    assert (dsa.num_kv_heads, dsa.head_dim) == (2, 128)
    with pytest.raises(ValueError, match="multiple of 256"):
        mla.MlaConfig.tiny_mla_dsa(kv_lora_rank=192)
    with pytest.raises(ValueError, match="first layer selects"):
        mla.MlaConfig.tiny_mla_dsa(indexer_types=("shared", "full", "full"))


# ------------------------------------------------------------ the share
def test_the_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: the routed parts both shares of 4
    give, plus the shared expert once, are the uncut reference's whole
    expert layer; and the router's weights are the uncut layer's."""
    cfg = adapter.model_config(file_cfg(n_routed_experts=8, experts_held_first=0))
    assert cfg.experts_held is None
    lp = mla.init_layer_params(jax.random.PRNGKey(7), cfg, 1)
    lp["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (8,))
    x = jax.random.normal(jax.random.PRNGKey(9), (24, 128), jnp.float32)
    h = mla.rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    topw, topi = mla.route(lp, cfg, h)
    weights = np.zeros((24, 8), np.float32)
    np.put_along_axis(weights, np.asarray(topi), np.asarray(topw), axis=1)
    with jax.default_matmul_precision("highest"):
        want = ref._route(h, lp["w_router"], lp["router_bias"], top_k=2, renorm=True, scaling=2.5)
    np.testing.assert_allclose(weights, np.asarray(want), atol=1e-6)

    whole = ref._experts(lp, x, top_k=2, eps=cfg.rms_norm_eps, renorm=True, scaling=2.5, first=0)
    routed = jnp.zeros_like(x)
    rows = 0
    for first in (0, 4):
        share = {k: v[first:first + 4] for k, v in mla.expert_params(lp).items()}
        stats = moelib.RoutingStats()
        routed += moelib.moe_ffn_grouped(
            share, cfg, h, routed=(topw, topi), stats=stats, held=(first, 4)
        )
        rows += int(stats.reduce()[0])
    assert rows == 24 * 2                      # every assignment lands on one share
    sg = jax.nn.silu(h @ lp["w_shared_gate"])
    summed = x + routed + (sg * (h @ lp["w_shared_up"])) @ lp["w_shared_down"]
    np.testing.assert_allclose(np.asarray(summed), np.asarray(whole), atol=2e-5)
    # and the program's own layer with a share is the reference's with that share
    held = adapter.model_config(file_cfg())
    lp_held = dict(lp, **{k: lp[k][4:8] for k in ("w_egate", "w_eup", "w_edown")})
    got = x + mla._moe_ffn(lp_held, held, h)
    part = ref._experts(lp_held, x, top_k=2, eps=cfg.rms_norm_eps, renorm=True, scaling=2.5, first=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(part), atol=2e-5)


def test_a_share_without_rows_adds_nothing_and_counts_nothing():
    cfg = adapter.model_config(file_cfg())
    lp = mla.init_layer_params(jax.random.PRNGKey(1), cfg, 1)
    assert lp["w_egate"].shape[0] == 4 and lp["w_router"].shape[1] == 8
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 128), jnp.float32)
    routed = (jnp.full((6, 2), 0.5), jnp.tile(jnp.array([[0, 3]]), (6, 1)))
    stats = moelib.RoutingStats()
    y = moelib.moe_ffn_grouped(mla.expert_params(lp), cfg, x, routed=routed, stats=stats, held=(4, 4))
    assert float(jnp.abs(y).max()) == 0.0
    assert stats.reduce().tolist() == [0.0, 0.0, 0.0]


# -------------------------------------------------- the seam's new question
NB, BS, ROWS, H, RANK, MB = 24, 16, 2, 4, 256, 6


def _paged(seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    kc = jnp.asarray(rng.normal(size=(NB, BS, ROWS, 128)), dtype)
    vc = jnp.asarray(rng.normal(size=(NB, BS, ROWS, 128)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, NB))[:3 * MB].reshape(3, MB), jnp.int32)
    return rng, kc, vc, tables


def _dsa(rng, lead, selected=None, dtype=jnp.bfloat16):
    return att.DsaQuery(
        scale=0.125, topk=TOPK,
        index_q=None if selected is not None else jnp.asarray(rng.normal(size=(*lead, 4, 32)), dtype),
        index_w=None if selected is not None else jnp.asarray(rng.normal(size=(*lead, 4)), jnp.float32),
        selected=selected,
    )


@pytest.fixture(scope="module")
def seams():
    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    return PagedAttention(mesh, False), PagedAttention(mesh, True, interpret=True)


def _both(seams, ask):
    """Ask twin and interpreted kernel the same question with the same
    indexer inputs; they select alike (the scoring is shared XLA)."""
    outs, sels = [], []
    for seam in seams:
        dsa, *args = ask()
        outs.append(np.asarray(seam_call(seam, dsa, *args), np.float32))
        sels.append(np.asarray(dsa.selected))
    np.testing.assert_array_equal(sels[0], sels[1])
    np.testing.assert_allclose(outs[1], outs[0], atol=2e-2, rtol=2e-2)
    return outs[0], sels[0]


def seam_call(seam, dsa, kind, *args):
    return getattr(seam, kind)(*args, dsa=dsa)


def test_decode_rows_select_and_attend(seams):
    """Contexts below and above index_topk, one of exactly a page, an empty
    row; selections cross page edges (positions 0..89 over 16-token pages)."""
    seq_lens = jnp.asarray([90, 5, 0], jnp.int32)

    def ask():
        rng, kc, vc, tables = _paged(1)
        q = jnp.asarray(rng.normal(size=(3, H, RANK + 128)), jnp.bfloat16)
        return _dsa(rng, (3,)), "decode", q, kc, vc, tables, seq_lens

    out, sel = _both(seams, ask)
    assert (sel[0] >= 0).sum() == TOPK and len({p // BS for p in sel[0]}) > 1
    assert sorted(sel[1][sel[1] >= 0]) == list(range(5))      # every causal key
    assert (sel[2] == att.SEL_NONE).all() and not out[2].any()


def test_a_chunk_selects_query_by_query(seams):
    """A chunk of 32 at positions 40..65 (26 real, 6 padding rows at the
    context's far end): each real query its own top 16 of its causal keys."""
    positions = jnp.asarray(list(range(40, 66)) + [95] * 6, jnp.int32)

    def ask():
        rng, kc, vc, tables = _paged(2)
        q = jnp.asarray(rng.normal(size=(32, H, RANK + 128)), jnp.bfloat16)
        return (_dsa(rng, (32,)), "chunk", q, kc, vc, tables[0], jnp.int32(40),
                jnp.int32(66), positions)

    out, sel = _both(seams, ask)
    for i in range(26):
        assert (sel[i] >= 0).sum() == TOPK and sel[i].max() <= 40 + i
    assert (sel[26:] == att.SEL_NONE).all() and not out[26:].any()


def test_a_mixed_step_and_an_inherited_selection(seams):
    """Row 0 a chunk of 16 (12 real) behind which two decode rows ride; then
    the same rows with the selection inherited, as a shared layer asks."""
    q_starts = jnp.asarray([0, 16, 17], jnp.int32)
    q_lens = jnp.asarray([12, 1, 0], jnp.int32)
    seq_lens = jnp.asarray([60, 33, 0], jnp.int32)

    def ask(selected=None):
        rng, kc, vc, tables = _paged(3)
        q = jnp.asarray(rng.normal(size=(18, H, RANK + 128)), jnp.bfloat16)
        return (_dsa(rng, (18,), selected), "ragged", q, kc, vc, tables, q_starts,
                q_lens, seq_lens)

    out, sel = _both(seams, ask)
    assert all(sel[i].max() == 48 + i or sel[i].max() <= 48 + i for i in range(12))
    assert (sel[12:16] == att.SEL_NONE).all() and (sel[17] == att.SEL_NONE).all()
    assert (sel[16] >= 0).sum() == TOPK and sel[16].max() <= 32
    again, _ = _both(seams, lambda: ask(jnp.asarray(sel)))
    np.testing.assert_array_equal(again, out)


def _index_pool(nb, rows=4, bad_k_pe=False, seed=0):
    """A second array whose row 1 holds index keys and whose other rows hold
    something else; ``bad_k_pe``: row 0 (the low halves of the words the
    keys share) holds NaNs and infinities."""
    rng = np.random.default_rng(seed)
    vc = rng.normal(size=(nb, BS, rows, 128)).astype(np.float32)
    if bad_k_pe:
        vc[:, :, 0, 0::3], vc[:, :, 0, 1::3], vc[:, :, 0, 2::3] = np.nan, np.inf, -np.inf
    return rng, jnp.asarray(vc, jnp.bfloat16)


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16))


@pytest.mark.parametrize("case,n_tables,mb,dim,shuffled,bad_k_pe", [
    ("runs", 2, 8, 128, False, False),
    ("shuffled-page-by-page", 2, 8, 128, True, False),
    ("a-tail-of-2-pages", 3, 10, 128, False, False),
    ("a-tail-shuffled", 2, 11, 128, True, False),
    ("one-table", 1, 12, 128, False, False),
    ("nine-tables", 9, 6, 128, False, False),
    ("narrower-than-a-chunk", 2, 3, 128, True, False),
    ("dim-32", 2, 8, 32, False, False),
    ("dim-64-shuffled-tail", 2, 9, 64, True, False),
    ("k_pe-holds-nan-and-inf", 2, 9, 128, False, True),
])
def test_the_index_keys_launch_is_bitwise_the_twin(
        monkeypatch, case, n_tables, mb, dim, shuffled, bad_k_pe):
    """``pallas_sparse.paged_index_keys`` interpreted, chunks of 4 pages,
    against ``att.paged_index_keys``: whole chunks read as runs (one strided
    descriptor) and page by page, a table's tail chunk, a table narrower than
    a chunk; the keys are the high halves of the words they share with
    ``k_pe``, cut out by a mask, so a NaN beside a key stays beside it."""
    from dynamo_tpu.ops import pallas_sparse as ps

    monkeypatch.setattr(ps, "INDEX_CHUNK_PAGES", 4)
    nb = 2 + n_tables * mb
    rng, vc = _index_pool(nb, bad_k_pe=bad_k_pe, seed=mb)
    ids = np.arange(1, 1 + n_tables * mb)
    tables = jnp.asarray(
        (rng.permutation(ids) if shuffled else ids).reshape(n_tables, mb), jnp.int32)
    want = att.paged_index_keys(vc, tables, dim)
    got = ps.paged_index_keys(vc, tables, dim, interpret=True)
    assert got.shape == want.shape == (n_tables, mb * BS, dim) and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert not np.isnan(np.asarray(got, np.float32)).any()
    whole, run = ps.index_chunk_reads(tables)
    assert int(whole) == n_tables * (mb // min(4, mb))
    assert int(run) == (0 if shuffled else int(whole))


def test_a_pool_the_index_keys_launch_cannot_read_is_refused():
    from dynamo_tpu.ops import pallas_sparse as ps

    tables = jnp.ones((1, 4), jnp.int32)
    for shape, dtype in (((8, BS, 4, 128), jnp.float32), ((8, BS, 3, 128), jnp.bfloat16),
                         ((8, BS, 4, 64), jnp.bfloat16)):
        with pytest.raises(ValueError, match="bf16 pages of 128 lanes"):
            ps.paged_index_keys(jnp.zeros(shape, dtype), tables, 64, interpret=True)


@pytest.mark.parametrize("kind", ["decode", "ragged"])
def test_the_seam_selects_and_attends_alike_with_the_launch_and_the_twin(seams, kind):
    """Through the seam, Pallas on: the index keys come from the launch
    ``paged_index_keys`` (the twin's slice elsewhere); the same bits, so
    ``dsa.selected`` is the twin's exactly, with a pool of 4 rows a token and
    NaNs in ``k_pe``'s lanes past its 64; both leave the same chunk counts."""
    rng, vc = _index_pool(NB, seed=11)
    kc = jnp.asarray(rng.normal(size=(NB, BS, 4, 128)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, NB))[:3 * MB].reshape(3, MB), jnp.int32)
    if kind == "decode":
        n, args = 3, (tables, jnp.asarray([90, 17, 0], jnp.int32))
    else:
        n, args = 18, (tables, jnp.asarray([0, 16, 17], jnp.int32),
                       jnp.asarray([12, 1, 1], jnp.int32), jnp.asarray([60, 33, 96], jnp.int32))
    q = jnp.asarray(rng.normal(size=(n, H, 2 * RANK + 128)), jnp.bfloat16)
    iq = jnp.asarray(rng.normal(size=(n, 4, 32)), jnp.bfloat16)
    iw = jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)
    outs, asked = [], []
    for seam in seams:
        dsa = att.DsaQuery(scale=0.125, topk=TOPK, index_q=iq, index_w=iw)
        fn = lambda q, kc, vc: getattr(seam, kind)(q, kc, vc, *args, dsa=dsa)  # noqa: E731
        names = [e.params.get("name") for e in jax.make_jaxpr(fn)(q, kc, vc).jaxpr.eqns
                 if e.primitive.name == "jit"]
        assert ("paged_index_keys" in names) == seam.use_pallas
        dsa.selected = None
        outs.append(np.asarray(fn(q, kc, vc), np.float32))
        asked.append(dsa)
    np.testing.assert_array_equal(*(np.asarray(d.selected) for d in asked))
    assert (np.asarray(asked[0].selected) >= 0).any()
    np.testing.assert_allclose(outs[1], outs[0], atol=2e-2, rtol=2e-2)
    assert [int(x) for x in asked[0].index_chunk_reads] == [
        int(x) for x in asked[1].index_chunk_reads] == [3, 0]


def _scratch_operands(fn, *args):
    """How many scratch buffers the launch's pallas_call asks for: 3 where
    every chunk buffer is gathered, 6 where a row's pages are staged too."""
    (call,) = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    return call.params["grid_mapping"].num_scratch_operands


@pytest.mark.parametrize("filling,chunk", [
    ("gathered", 32), ("gathered", 256), ("staged", 32), ("staged", 256), ("too-wide", 32),
])
def test_the_kernel_across_chunks_tails_and_both_fillings(monkeypatch, filling, chunk):
    """``pallas_sparse.sparse_latent_attention`` interpreted, against the twin,
    with ``K`` of 3 chunks: per-query counts 0, 1, a group's edge, chunk - 1,
    chunk, chunk + 1, two chunks and a tail, ``K``; selections cross page
    edges and rows. ``staged``: 8 chunk queries of row 0 whose pages are
    copied into VMEM once, then 4 decode rows of other tables that gather,
    in one launch, bitwise what the gather alone gives. ``too-wide``: the same
    launch over tables of 6 200 pages, more than ``STAGED_VMEM_BYTES`` holds,
    which takes the gather. The interpreter's semaphore is an int16 that
    saturates at 32 767 elements (jax/_src/pallas/core.py) where a whole
    chunk's wait asks for 131 072: tier-1 holds the answers, not the wait
    count; chip_smoke.py holds that on the chip."""
    from dynamo_tpu.ops import pallas_sparse as ps

    monkeypatch.setattr(ps, "CHUNK", chunk)
    K = 3 * chunk
    nb = 2 + 3 * K // BS
    rng = np.random.default_rng(chunk)
    kc = jnp.asarray(rng.normal(size=(nb, BS, ROWS, 128)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(nb, BS, ROWS, 128)), jnp.bfloat16)
    tables = rng.permutation(np.arange(1, nb))[:3 * K // BS].reshape(3, K // BS)
    counts = [0, 1, 17, chunk - 1, chunk, chunk + 1, 2 * chunk + 5, K]
    rows = [0, 1, 2, 0, 1, 2, 0, 1]
    n_chunk = 0
    if filling != "gathered":
        counts, rows, n_chunk = counts + [K, chunk + 1, 0, chunk - 1], [0] * 8 + [1, 2, 1, 2], 8
    if filling == "too-wide":
        tables = np.pad(tables, ((0, 0), (0, 6200 - tables.shape[1])))
    sel = np.full((len(counts), K), att.SEL_NONE, np.int32)
    for i, n in enumerate(counts):
        sel[i, :n] = rng.permutation(K)[:n]
    q = jnp.asarray(rng.normal(size=(len(counts), H, RANK + 128)), jnp.bfloat16)
    args = (q, kc, vc, jnp.asarray(tables, jnp.int32), jnp.asarray(rows, jnp.int32), jnp.asarray(sel))

    def kernel(n_chunk):
        return lambda *a: ps.sparse_latent_attention(*a, scale=0.125, n_chunk=n_chunk, interpret=True)

    assert _scratch_operands(kernel(n_chunk), *args) == (6 if filling == "staged" else 3)
    got = np.asarray(kernel(n_chunk)(*args), np.float32)
    want = np.asarray(att.sparse_latent_attention(*args, 0.125), np.float32)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert not got[[i for i, n in enumerate(counts) if n == 0]].any()
    if filling == "staged":
        np.testing.assert_array_equal(got, np.asarray(kernel(0)(*args), np.float32))


def _select_case(name):
    """(scores [Q, T], q_pos, q_valid, topk) of one case of the selection."""
    rng = np.random.default_rng(sum(map(ord, name)))
    normal = lambda Q, T: rng.normal(size=(Q, T)).astype(np.float32)   # noqa: E731
    if name == "random":
        return normal(3, 40), [39, 9, 20], [True, True, False], 16
    if name == "cut-inside-a-run-of-equal-values":
        # eighths: every cut falls in a run of ties, the lower positions win
        return np.round(normal(16, 1280) * 8) / 8, rng.integers(300, 1280, 16), [True] * 16, 256
    if name == "two-values-only":
        return (rng.random((4, 640)) < 0.5).astype(np.float32), [639, 500, 77, 300], [True] * 4, 128
    if name == "signed-zeros":
        # +0.0 beside -0.0 (equal to lax.top_k), a few scores on either side
        s = np.where(rng.random((6, 512)) < 0.5, 0.0, -0.0).astype(np.float32)
        far = rng.random((6, 512)) < 0.1
        return np.where(far, normal(6, 512), s), [511, 400, 300, 200, 511, 64], [True] * 6, 64
    if name == "fewer-causal-keys-than-topk":
        return normal(5, 384), [0, 5, 63, 64, 200], [True] * 5, 128
    if name == "context-no-longer-than-topk":
        return normal(4, 100), [99, 50, 0, 99], [True, True, True, False], 128
    if name == "context-of-exactly-topk":
        return normal(3, 256), [255, 100, 255], [True] * 3, 256
    if name == "invalid-rows":
        return normal(4, 300), [299, 10, 200, 150], [False, True, False, False], 32
    if name == "context-not-a-multiple-of-128":
        return normal(7, 1000), rng.integers(0, 1000, 7), [True] * 7, 200
    if name == "infinite-scores":
        s = normal(4, 256)
        s[:, ::5], s[:, 1::7] = -np.inf, np.inf
        return s, [255, 200, 30, 255], [True] * 4, 64
    if name == "glm52-at-a-reduced-context":
        # (T, topk) in the long-document cell's proportion (25 600, 2 048): a
        # 24-token chunk behind its history and 8 decode rows
        return (normal(32, 3200), np.r_[3000 + np.arange(24), rng.integers(2500, 3200, 8)],
                np.r_[[True] * 20, [False] * 4, [True] * 8], 256)
    if name == "dots3-at-a-reduced-context":
        # ... and the agent cell's (37 376, 2 048)
        return (normal(48, 4672), np.r_[4400 + np.arange(32), rng.integers(4000, 4672, 16)],
                np.r_[[True] * 29, [False] * 3, [True] * 16], 256)
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "random", "cut-inside-a-run-of-equal-values", "two-values-only", "signed-zeros",
    "fewer-causal-keys-than-topk", "context-no-longer-than-topk", "context-of-exactly-topk",
    "invalid-rows", "context-not-a-multiple-of-128", "infinite-scores",
    "glm52-at-a-reduced-context", "dots3-at-a-reduced-context",
])
def test_exact_top_k_of_the_causal_scores(name):
    """``dsa_select`` against ``jax.lax.top_k`` (the oracle: the sort stays
    here and nowhere in the selection), set by set, and the list's form: the
    selected first and ascending, ``SEL_NONE`` after them."""
    scores, q_pos, q_valid, topk = _select_case(name)
    scores, q_pos, q_valid = jnp.asarray(scores), jnp.asarray(q_pos, jnp.int32), jnp.asarray(q_valid)
    T = scores.shape[1]
    sel = np.asarray(jax.jit(att.dsa_select, static_argnums=3)(scores, q_pos, q_valid, topk))
    assert sel.shape == (scores.shape[0], min(topk, T)) and sel.dtype == np.int32
    seen = (np.arange(T)[None, :] <= np.asarray(q_pos)[:, None]) & np.asarray(q_valid)[:, None]
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), min(topk, T))
    for got, want, pos, ok in zip(sel, np.asarray(idx), np.asarray(q_pos), np.asarray(q_valid)):
        want = sorted(int(i) for i in want if ok and i <= pos)
        assert len(want) == (min(topk, pos + 1) if ok else 0)
        assert got[:len(want)].tolist() == want          # the same set, ascending
        assert (got[len(want):] == att.SEL_NONE).all()


@pytest.mark.parametrize("Q,n,d,T,rows,slabs", [
    (5, 4, 32, 70, None, 0),            # all heads at once (the decode rows' branch)
    (48, 64, 16, 22016, 64, 1),         # under one slab: head by head, no outer loop
    (48, 64, 16, 22016, 16, 3),         # a multiple of the slab
    (40, 64, 16, 27008, 16, 3),         # not a multiple: a last slab padded
    (24, 128, 16, 22016, 16, 2),        # ... and the slab the largest power of two under Q
    (48, 32, 16, 44032, 64, 1),         # GLM's head count
    (48, 32, 16, 44032, 16, 3),
    (36, 32, 16, 60032, 8, 5),
], ids=lambda v: str(v))
def test_the_index_scores_head_by_head_are_the_scores_of_all_heads_at_once(
        monkeypatch, Q, n, d, T, rows, slabs):
    """``dsa_index_scores`` against the float64 sum over all heads at once,
    whichever way it goes: all heads at once, head by head over the whole
    chunk, head by head a slab of queries at a time (``INDEX_SLAB_BYTES``
    patched so that a CPU shape slabs). Rows are independent: the slabbed
    scores are the one-slab scores BIT FOR BIT."""
    rng = np.random.default_rng(1)
    iq, iw, keys = rng.normal(size=(Q, n, d)), rng.normal(size=(Q, n)), rng.normal(size=(T, d))
    want = np.zeros((Q, T))
    for j in range(n):
        want += np.maximum(iq[:, j] @ keys.T, 0) * iw[:, j, None]
    args = [jnp.asarray(a, jnp.float32) for a in (iq, iw, keys)]
    if rows is not None:
        assert Q * n * T * 4 > 2 ** 28               # past the all-heads branch
        monkeypatch.setattr(att, "INDEX_SLAB_BYTES", rows * T * 4)
        assert att.index_slab_rows(Q, T) == (Q if slabs == 1 else rows)

    def scores():
        # a function of its own a trace: JAX keeps a trace by the function
        # and the shapes, and would not see the constant move
        return lambda *a: att.dsa_index_scores(*a)

    # a head scan, and around it the slab loop where there are slabs
    assert str(jax.make_jaxpr(scores())(*args)).count("scan[") == min(slabs, 2)
    got = np.asarray(jax.jit(scores())(*args))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    if slabs > 1:
        monkeypatch.setattr(att, "INDEX_SLAB_BYTES", Q * T * 4)
        np.testing.assert_array_equal(got, np.asarray(jax.jit(scores())(*args)))


@pytest.mark.parametrize("kind,n_chunk,rows", [
    ("ragged", 96, 32),       # a mixed step: three slabs, then two decode rows
    ("ragged", 80, 32),       # ... the last slab short
    ("chunk", 96, 64),        # a lone chunk: a slab and a short one
])
def test_a_chunk_wider_than_a_slab_selects_the_whole_chunks_lists(monkeypatch, kind, n_chunk, rows):
    """Through the seam, a chunk whose index scores are over
    ``INDEX_SLAB_BYTES`` is scored AND selected a slab at a time (the chunk's
    scores are never one array), the decode rows beside it apart: the lists
    are ``dsa_select``'s over the whole chunk's scores row by row, and the
    attend's output with them bit for bit."""
    seam = PagedAttention(make_mesh(tp=1, devices=jax.devices()[:1]), False)
    mb, nb, n = 2064, 2100, 64                    # 33 024 keys: a slab too is past all heads at once
    rng, vc = _index_pool(nb, seed=5)
    kc = jnp.asarray(rng.normal(size=(nb, BS, 4, 128)), jnp.bfloat16)
    tables = jnp.asarray(np.stack([rng.permutation(np.arange(1, nb))[:mb] for _ in range(3)]), jnp.int32)
    Tq = n_chunk + (2 if kind == "ragged" else 0)
    q = jnp.asarray(rng.normal(size=(Tq, H, 2 * RANK + 128)), jnp.bfloat16)
    iq = jnp.asarray(rng.normal(size=(Tq, n, 32)), jnp.bfloat16)
    iw = jnp.asarray(rng.normal(size=(Tq, n)), jnp.float32)
    start = mb * BS - 300
    if kind == "ragged":
        args = (tables, jnp.asarray([0, n_chunk, n_chunk + 1], jnp.int32),
                jnp.asarray([n_chunk - 3, 1, 1], jnp.int32),
                jnp.asarray([start + n_chunk - 3, 9000, 17], jnp.int32))
    else:
        args = (tables[0], jnp.int32(start), jnp.int32(start + n_chunk - 3),
                start + jnp.arange(n_chunk, dtype=jnp.int32))
    assert rows * n * mb * BS * 4 > 2 ** 28

    def ask(slab_rows):
        monkeypatch.setattr(att, "INDEX_SLAB_BYTES", slab_rows * mb * BS * 4)
        dsa = att.DsaQuery(scale=0.125, topk=TOPK, index_q=iq, index_w=iw)
        fn = lambda q, kc, vc: getattr(seam, kind)(q, kc, vc, *args, dsa=dsa)  # noqa: E731
        scans = str(jax.make_jaxpr(fn)(q, kc, vc)).count("scan[")
        dsa.selected = None
        return np.asarray(fn(q, kc, vc), np.float32), np.asarray(dsa.selected), scans

    whole, slabbed = ask(n_chunk), ask(rows)
    assert slabbed[2] > whole[2]                          # the slab loop is there
    assert (whole[1][:n_chunk - 3] >= 0).sum() == (n_chunk - 3) * TOPK
    assert (whole[1][n_chunk - 3:n_chunk] == att.SEL_NONE).all()      # the chunk's padding
    np.testing.assert_array_equal(slabbed[1], whole[1])
    np.testing.assert_array_equal(slabbed[0], whole[0])


def test_bf16_index_scores_move_a_few_keys_across_the_cut():
    """What is honest and new in the tolerance: rounding the indexer's
    inputs to bf16 changes which keys rank just inside index_topk."""
    cfg = file_cfg()
    engine = engine_of(cfg)
    try:
        params = adapter.reference_params(engine)
    finally:
        engine.stop()
    (tokens,) = prompts_of(96, seed=3)
    flips = ref.selection_flips(cfg, params, tokens)
    assert flips["queries"] == 96 - TOPK
    assert 0 <= flips["mean"] <= 2.0 and flips["worst"] <= TOPK / 2
