"""Latent attention over EVERY causal key of a paged latent (models/mla.py
WITHOUT an indexer, held as rows of 128 lanes; A.X-K1's and DeepSeek-V3's
mechanism), YaRN positions with their softmax factor, group-limited routing
over a held share, at a test's size, against the benchmark's plain float32
reference (benchmarks/reference/mla_decoder.py): the seam's question (pure-JAX
twin and interpreted kernel), the engine (chunked prefill, a mixed step,
decode through the paged cache), the shares, the counters, the reference's
hand switches and what the layout refuses at construction.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import system
from benchmarks.adapters import mla as adapter
from benchmarks.reference import mla_decoder as ref
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.engine.telemetry import run_chunk_share
from dynamo_tpu.models import mla, moe as moelib, registry
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import pallas_latent as plat
from dynamo_tpu.ops import pallas_paged as paged
from dynamo_tpu.ops.paged_attention import PagedAttention
from dynamo_tpu.parallel.mesh import make_mesh


def file_cfg(dtype="float32", **kw):
    """A configuration file's dict (the public keys) at a test's size: one
    dense layer and two sparse; 16 experts in 4 groups of which 2 stay, 4
    held; YaRN x8 from 32 positions, so the tests' positions lie past the
    original maximum."""
    cfg = {
        "vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 3,
        "num_attention_heads": 4, "intermediate_size": 256, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 4096, "tie_word_embeddings": False, "torch_dtype": dtype,
        "q_lora_rank": 96, "kv_lora_rank": 256, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
        "v_head_dim": 32, "router_outputs": 16, "n_routed_experts": 4, "experts_held_first": 4,
        "num_experts_per_tok": 2, "moe_intermediate_size": 64, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "n_shared_experts": 1, "n_group": 4, "topk_group": 2,
        "first_k_dense_replace": 1, "scoring_func": "sigmoid", "topk_method": "none",
        "rope_interleave": True, "rope_theta": 10000.0,
        "rope_scaling": {"type": "yarn", "factor": 8, "original_max_position_embeddings": 32,
                         "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
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


WRONG = [
    {"no_mscale": True}, {"plain_rope": True}, {"no_group_limit": True},
    {"no_router_bias": True}, {"no_routed_scale": True}, {"no_shared_expert": True},
    {"kv_bits": 8}, {"skip_layer": 1},
]


# ----------------------------------------------------------- the layout
ONE_CHIP = dict(tp=1, pp=1, sp=1)
NOT_ONE_CHIP_TEXT = [
    dict(tp=8), dict(tp=2, sp=2), dict(pp=2), dict(spec=True), dict(lora=True),
    dict(kv_quantized=True), dict(vision=True),
]


def test_the_engine_takes_the_rows_layout_on_the_one_chip_text_path():
    """A configuration states no layout: one head of rank + rope lanes. The
    layout is chosen where the parallelism is known (registry.place_latent,
    at engine construction)."""
    cfg = adapter.model_config(file_cfg())
    assert cfg.rows_capable and not cfg.latent_rows and (cfg.num_kv_heads, cfg.head_dim) == (1, 272)
    placed = registry.place_latent(cfg, **ONE_CHIP)
    assert placed.latent_rows and (placed.num_kv_heads, placed.head_dim, placed.index_topk) == (2, 128, 0)
    assert registry.read_counters(placed) == (
        "mla_keys_attended", "mla_decode_rows", "mla_chunks_whole", "mla_chunks_run")
    assert registry.read_counters(cfg) == ()
    pub = mla.MlaConfig.axk1()
    assert (pub.num_kv_heads, pub.head_dim) == (1, 576)
    assert (registry.place_latent(pub).num_kv_heads, registry.place_latent(pub).head_dim) == (4, 128)
    assert abs(pub.softmax_scale - 0.13086) < 1e-5 and pub.n_group == 8 and pub.num_experts == 192
    # a narrower latent cannot be held as rows, and no YaRN is no factor
    small = mla.MlaConfig.tiny_mla()
    assert registry.place_latent(small, **ONE_CHIP) is small and (small.num_kv_heads, small.head_dim) == (1, 80)
    assert small.yarn_scale_factor == 1.0 and small.softmax_scale == 1.0 / 48 ** 0.5
    with pytest.raises(ValueError, match="multiple of 256"):
        mla.MlaConfig.tiny_mla(rows_layout=True)
    # the engine does the placing, and says so in its own config
    engine = engine_of(file_cfg())
    try:
        assert engine.mcfg.rows_layout and engine.cfg.model is engine.mcfg
    finally:
        engine.stop()


@pytest.mark.parametrize("preset", ["deepseek_v3", "axk1"])
@pytest.mark.parametrize("asked", NOT_ONE_CHIP_TEXT, ids=lambda a: "+".join(a))
def test_a_published_latent_keeps_one_head_off_the_one_chip_text_path(preset, asked):
    """What ran on the pure-JAX path before the rows layout existed still
    does: sharded over chips, behind a draft, with an 8-bit cache, the 512 +
    64 latent is one 576-lane head and nothing is refused."""
    cfg = getattr(mla.MlaConfig, preset)()
    asked = {**ONE_CHIP, **asked}
    placed = registry.place_latent(cfg, **asked)
    assert placed is cfg and (cfg.num_kv_heads, cfg.head_dim, cfg.latent_rows) == (1, 576, False)
    registry.check_dsa_supported(placed, **asked)            # refuses nothing
    assert registry.read_counters(placed) == ()


def test_the_deepseek_v3_preset_runs_a_layer_on_the_one_head_layout_in_abstract_shapes():
    """The 671 B preset as a deployment shards it (tp 8): a dense and a sparse
    layer traced over abstract parameters hand the attention seam one
    576-lane head and no latent question; the cache spec replicates it."""
    from jax.sharding import PartitionSpec as P

    cfg = registry.place_latent(mla.MlaConfig.deepseek_v3(), tp=8)
    assert registry.kv_cache_spec(cfg, tp=8) == P(None, None, None, None)
    seen = []

    def attend(q, k, v, layer_idx, **extra):
        seen.append((q.shape, k.shape, v.shape, sorted(extra)))
        return jnp.zeros_like(q)

    def one(layer_idx):
        lp = jax.eval_shape(lambda: mla.init_layer_params(jax.random.PRNGKey(0), cfg, layer_idx))
        x = jax.ShapeDtypeStruct((8, cfg.hidden_size), cfg.dtype)
        cs = jax.ShapeDtypeStruct((8, 1, cfg.qk_rope_head_dim // 2), jnp.float32)
        return jax.eval_shape(lambda lp, x, c, s: mla.layer_forward(lp, cfg, x, c, s, attend, layer_idx), lp, x, cs, cs)

    assert one(0).shape == one(3).shape == (8, 7168)
    assert seen == [((8, 128, 576), (8, 1, 576), (8, 1, 576), [])] * 2


def test_the_deploy_example_builds_its_workers_engine_configs(monkeypatch):
    """deploy/examples/deepseek-v3-disagg.yaml: each worker's rendered command,
    through the worker's own argument parser and config builder, is a
    configuration the engine's construction checks admit (tp 8: one head)."""
    import sys

    from dynamo_tpu.deploy import GraphSpec, render
    from dynamo_tpu.engine import __main__ as worker

    sets = [o for o in render(GraphSpec.load("deploy/examples/deepseek-v3-disagg.yaml"))
            if o["kind"] == "StatefulSet" and "--preset" in o["spec"]["template"]["spec"]["containers"][0]["command"]]
    assert len(sets) == 2
    for o in sets:
        command = o["spec"]["template"]["spec"]["containers"][0]["command"]
        monkeypatch.setattr(sys, "argv", command[2:])
        args = worker.parse_args()
        assert (args.preset, args.tp) == ("deepseek-v3", 8)
        ecfg = worker.make_engine_config(args, worker.PRESETS[args.preset]())
        asked = dict(tp=ecfg.tp, pp=ecfg.pp, sp=ecfg.sp, spec=ecfg.spec_draft is not None,
                     lora=ecfg.lora_max_adapters > 0, kv_quantized=ecfg.kv_quantized,
                     vision=ecfg.vision is not None)
        placed = registry.place_latent(ecfg.model, **asked)
        registry.check_dsa_supported(placed, **asked)
        assert (placed.num_kv_heads, placed.head_dim) == (1, 576)


async def test_the_published_latent_at_tp_2_answers_as_the_rows_layout_does_at_tp_1():
    """The two layouts of one latent (512 + 64, the published widths, in a
    model cut small) are one computation: tp 1 holds it as rows and asks the
    latent question (the twin), tp 2 holds one replicated 576-lane head on
    the pure-JAX paged path; chunked prefill and decode give the same tokens."""
    cfg = mla.MlaConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4, q_lora_rank=48,
        kv_lora_rank=512, qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16,
        intermediate_size=128, dtype=jnp.float32, max_position=512,
        rope_scaling_factor=8.0, rope_original_max_position=32, rope_mscale_all_dim=1.0,
    )
    prompts = prompts_of(40, 23)
    got = {}
    for tp in (1, 2):
        engine = TpuEngine(TpuEngineConfig(
            model=cfg, num_blocks=32, block_size=16, max_batch_size=2, max_context=128,
            prefill_buckets=(16, 32), seed=5, tp=tp,
        ), mesh=make_mesh(tp=tp, devices=jax.devices()[:tp]))
        try:
            assert engine.mcfg.rows_layout == (tp == 1)
            assert (engine.mcfg.num_kv_heads, engine.mcfg.head_dim) == ((4, 128) if tp == 1 else (1, 576))
            recs, _ = await generate(engine, prompts, n_out=12)
            got[tp] = [r["tokens"] for r in recs]
        finally:
            engine.stop()
    assert got[1] == got[2] and all(len(t) == 12 for t in got[1])


def test_yarn_tables_are_the_references_and_plain_positions_are_untouched():
    cfg = file_cfg()
    mcfg = adapter.model_config(cfg)
    pos = jnp.arange(100)
    cos, sin = mla.rope_tables(mcfg, pos)
    freq, on_tables, on_scale = ref.yarn(cfg)
    ang = np.arange(100)[:, None] * freq[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang) * on_tables, atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang) * on_tables, atol=2e-5)
    assert abs(mcfg.softmax_scale - 48 ** -0.5 * on_scale) < 1e-9 and on_scale > 1.4
    # the blend moved some frequencies and left the fastest alone
    plain, _, _ = ref.yarn(cfg, plain=True)
    assert freq[0] == plain[0] and freq[-1] == plain[-1] / 8 and (freq <= plain).all()
    # factor <= 1: today's tables, bit for bit
    small = mla.MlaConfig.tiny_mla()
    want = mla.rope_cos_sin(pos, small.qk_rope_head_dim, small.rope_theta)
    got = mla.rope_tables(small, pos)
    assert all(bool(jnp.all(a == b)) for a, b in zip(got, want))


# ----------------------------------------------------------- the engine
async def test_chunked_prefill_and_decode_match_the_reference_in_float32():
    """Pure-JAX twin, float32: prompts of 72, 40 and 20 tokens in chunks of
    32 (positions past YaRN's original 32), then 20 decoded tokens through
    the paged cache, against the reference's one full forward; and each of
    the reference's hand switches moves the comparison out of bounds."""
    cfg = file_cfg()
    engine = engine_of(cfg)
    steps = []
    engine.stats_hook = steps.append
    try:
        assert not engine.use_pallas
        _, samples = await generate(engine, prompts_of(72, 40, 20))
        params = adapter.reference_params(engine)
        res = ref.compare(cfg, params, samples, 128)
        assert res["ok"], res
        assert res["tokens_compared"] == 60
        for wrong in WRONG:
            bad = ref.compare(cfg, params, samples, 128, **wrong)
            assert not bad["ok"], (wrong, bad)
            assert bad["mean_logprob_difference_nat"] > 4 * res["mean_logprob_difference_nat"], (wrong, bad)
    finally:
        engine.stop()
    counted = [s for s in steps if s.mla_decode_rows is not None]
    assert counted and all(s.phase in ("decode", "mixed") for s in counted)
    for s in counted:
        # 3 layers; a row attends over its whole context, 21..92 keys here
        # (a horizon's last steps may run a finished row a few tokens on)
        assert s.mla_decode_rows % 3 == 0 and s.dsa_keys_causal is None
        assert 21 * s.mla_decode_rows <= s.mla_keys_attended <= 100 * s.mla_decode_rows
        assert s.moe_held_experts_touched == s.moe_experts_touched <= 2 * 4 * 8
    assert all(s.mla_decode_rows is None for s in steps if s.phase == "prefill")


async def test_the_interpreted_kernel_serves_mixed_steps_in_bfloat16(monkeypatch):
    """use_pallas forced on the CPU: the launch paged_latent_attention and
    the Pallas expert multiplication run interpreted, chunks ride fused
    mixed steps. bf16 against the float32 reference moves a tiny model's
    logprobs by a few hundredths; a skipped layer moves them by far more.
    At 2 pages a chunk the contexts hold whole chunks: those of a prompt,
    admitted in one go into a fresh pool, are runs of consecutive pages and
    come in as one copy an array; a page a row took while decoding beside
    the others breaks its chunk's run, and the step's counters say so."""
    from dynamo_tpu.ops import pallas_paged as paged

    monkeypatch.setattr(paged, "chunk_pages", lambda *a: 2)
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
        bad = ref.compare(cfg, params, samples, 128, skip_layer=1)
        assert not bad["ok"], bad
    finally:
        engine.stop()
    assert {"mixed", "decode"} <= {s.phase for s in steps}
    assert any(s.mla_keys_attended for s in steps if s.phase == "mixed")
    counted = [s for s in steps if s.mla_chunks_whole is not None]
    assert counted and all(s.phase in ("decode", "mixed") for s in counted)
    assert all(0 <= s.mla_chunks_run <= s.mla_chunks_whole and s.mla_chunks_whole % 3 == 0 for s in counted)
    assert 0.5 < run_chunk_share(steps) < 1.0 and run_chunk_share([s for s in steps if s.phase == "prefill"]) is None


@pytest.mark.parametrize("what,kw", [
    ("tp > 1", dict(tp=2)),
    ("kv_dtype=int8", dict(kv_dtype="int8")),
    ("a speculative draft", dict(spec_draft=mla.MlaConfig.tiny_mla(vocab_size=512))),
    ("LoRA", dict(lora_max_adapters=2)),
    ("pp / sp > 1", dict(sp=2)),
])
def test_what_a_dense_latent_in_rows_cannot_do_yet_is_refused_at_construction(what, kw):
    mesh = None
    if "tp" in kw or "sp" in kw:
        mesh = make_mesh(tp=kw.get("tp", 1), sp=kw.get("sp", 1), devices=jax.devices()[:2])
    # no held share: the latent's layout alone is what refuses, and only a
    # configuration that STATES the rows layout (the engine would not pick it)
    import dataclasses

    cfg = adapter.model_config(file_cfg(n_routed_experts=16, experts_held_first=0))
    assert cfg.experts_held is None
    with pytest.raises(ValueError, match=what.replace(">", ".")):
        TpuEngine(TpuEngineConfig(
            model=dataclasses.replace(cfg, rows_layout=True), num_blocks=32, block_size=16,
            max_batch_size=2, max_context=64, prefill_buckets=(16,), **kw,
        ), mesh=mesh)


def test_a_program_without_the_yarn_fields_is_refused_before_any_device(monkeypatch):
    """What the parent commit does on the new cell: its MlaConfig knows no
    ``rope_scaling_factor`` and the constructor raises TypeError."""
    import dataclasses

    old = dataclasses.make_dataclass("MlaConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(mla.MlaConfig)
        if not f.name.startswith("rope_") or f.name == "rope_theta" or f.name == "rope_interleave"
    ])
    monkeypatch.setattr(mla, "MlaConfig", old)
    with pytest.raises(TypeError, match="rope_scaling_factor|rope_"):
        adapter.model_config(file_cfg())


# ------------------------------------------------------------ the share
def test_the_shares_add_up_to_the_uncut_layer_under_the_group_limit():
    """model-configs guide, section 4: the routed parts all 4 shares of 4
    give, plus the shared expert once, are the uncut reference's whole
    expert layer, under the group limit (4 groups of 4, 2 stay); and the
    router's weights are the uncut layer's."""
    cfg = adapter.model_config(file_cfg(n_routed_experts=16, experts_held_first=0))
    assert cfg.experts_held is None and (cfg.n_group, cfg.topk_group) == (4, 2)
    lp = mla.init_layer_params(jax.random.PRNGKey(7), cfg, 1)
    lp["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (16,))
    x = jax.random.normal(jax.random.PRNGKey(9), (24, 128), jnp.float32)
    h = mla.rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    topw, topi = mla.route(lp, cfg, h)
    weights = np.zeros((24, 16), np.float32)
    np.put_along_axis(weights, np.asarray(topi), np.asarray(topw), axis=1)
    kw = dict(top_k=2, renorm=True, scaling=2.5, n_group=4, topk_group=2)
    with jax.default_matmul_precision("highest"):
        want = ref._route(h, lp["w_router"], lp["router_bias"], **kw)
        free = ref._route(h, lp["w_router"], lp["router_bias"], **dict(kw, n_group=1))
    np.testing.assert_allclose(weights, np.asarray(want), atol=1e-6)
    assert (np.asarray(want) != np.asarray(free)).any()      # the limit binds for some token
    # a token's chosen experts lie in at most topk_group groups
    assert all(len({e // 4 for e in row}) <= 2 for row in np.asarray(topi))

    whole = ref._experts(lp, x, eps=cfg.rms_norm_eps, first=0, **kw)
    routed = jnp.zeros_like(x)
    rows = 0
    for first in (0, 4, 8, 12):
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
    part = ref._experts(lp_held, x, eps=cfg.rms_norm_eps, first=4, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(part), atol=2e-5)


# ------------------------------------------------------ the seam's question
NB, BS, ROWS, H, RANK, MB = 40, 16, 2, 4, 256, 12


def _paged(seed=0):
    rng = np.random.default_rng(seed)
    kc = jnp.asarray(rng.normal(size=(NB, BS, ROWS, 128)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(NB, BS, ROWS, 128)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, NB))[:3 * MB].reshape(3, MB), jnp.int32)
    return rng, kc, vc, tables


@pytest.fixture(scope="module")
def seams():
    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    return PagedAttention(mesh, False), PagedAttention(mesh, True, interpret=True)


def _both(seams, kind, *args):
    ask = att.LatentQuery(scale=0.125)
    twin, kernel = (np.asarray(getattr(s, kind)(*args, latent=ask), np.float32) for s in seams)
    np.testing.assert_allclose(kernel, twin, atol=2e-2, rtol=2e-2)
    return twin


def test_decode_rows_attend_over_their_whole_contexts(seams):
    """Contexts of 1, 15 and 17 tokens past a page's edge, one of exactly
    the table's width, an empty row: zeros back for it, and a row's answer
    is the softmax over all its keys (a hand check against the cache)."""
    rng, kc, vc, tables = _paged(1)
    tables = jnp.concatenate([tables, tables[:2]])
    seq_lens = jnp.asarray([177, 175, 0, 161, 192], jnp.int32)
    q = jnp.asarray(rng.normal(size=(5, H, RANK + 128)), jnp.bfloat16)
    out = _both(seams, "decode", q, kc, vc, tables, seq_lens)
    assert not out[2].any() and out[[0, 1, 3, 4]].any(axis=(1, 2)).all()
    keys = np.concatenate([np.asarray(kc[tables[1]], np.float32).reshape(-1, RANK),
                           np.asarray(vc[tables[1]], np.float32)[:, :, 0].reshape(-1, 128)], axis=1)[:175]
    s = np.einsum("hd,td->ht", np.asarray(q[1], np.float32), keys) * 0.125
    p = np.exp(s - s.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ keys[:, :RANK]
    np.testing.assert_allclose(out[1], want, atol=2e-2, rtol=2e-2)


def test_a_chunk_attends_causally_at_its_contexts_tail(seams):
    """A chunk of 32 at positions 120..145 (26 real, 6 padding rows): each
    real query over the keys up to its own position, padding zeros."""
    rng, kc, vc, tables = _paged(2)
    positions = jnp.asarray(list(range(120, 146)) + [191] * 6, jnp.int32)
    q = jnp.asarray(rng.normal(size=(32, H, RANK + 128)), jnp.bfloat16)
    out = _both(seams, "chunk", q, kc, vc, tables[0], jnp.int32(120), jnp.int32(146), positions)
    assert not out[26:].any() and out[:26].any(axis=(1, 2)).all()
    # causal: the queries at positions 120..127 see nothing of the pages from
    # position 128 on, so moving those pages leaves their answers alone
    kc2 = kc.at[tables[0, 128 // BS:]].add(1.0)
    again = np.asarray(seams[0].chunk(
        q, kc2, vc, tables[0], jnp.int32(120), jnp.int32(146), positions,
        latent=att.LatentQuery(scale=0.125)), np.float32)
    np.testing.assert_allclose(again[:8], out[:8], atol=1e-6)
    assert np.abs(again[8:26] - out[8:26]).max() > 0.1


def test_a_mixed_step_is_one_question(seams):
    """Row 0 a chunk of 20 (15 real) behind which two decode rows ride, one
    of them empty."""
    rng, kc, vc, tables = _paged(3)
    q = jnp.asarray(rng.normal(size=(22, H, RANK + 128)), jnp.bfloat16)
    out = _both(seams, "ragged", q, kc, vc, tables, jnp.asarray([0, 20, 21], jnp.int32),
                jnp.asarray([15, 1, 0], jnp.int32), jnp.asarray([81, 33, 0], jnp.int32))
    assert not out[15:20].any() and not out[21].any() and out[20].any()
    # ONE launch, and it carries the name the trace is read by
    kernel = seams[1]
    jaxpr = jax.make_jaxpr(lambda *a: kernel.ragged(*a, latent=att.LatentQuery(scale=0.125)))(
        q, kc, vc, tables, jnp.asarray([0, 20, 21]), jnp.asarray([15, 1, 0]), jnp.asarray([81, 33, 0]))
    text = str(jaxpr)
    assert text.count("pallas_call") == 1 and plat.KERNEL_NAME in text


def _parents_chunk_matrix(k_words, v_words, kcat, slot, T, lat_rows):
    """The unpack as PR 33 wrote it and PR 47 found it (45 000 vector
    instructions a visit on the chip: a word-row of EVERY token out of the 4-D
    buffer, one token a register, a float32 round trip a half), kept as the
    twin of ``pallas_latent._chunk_matrix``: the same matrix, bit for bit."""
    from jax.experimental.pallas import tpu as pltpu

    from dynamo_tpu.ops.pallas_sparse import _halves

    nw = lat_rows // 2
    # of the second array a slot holds one tile a token since PR 55: one word-row
    k4, v4 = k_words.reshape(2, T, nw, 128), v_words.reshape(2, T, 1, 128)
    for w in range(nw):
        for half, x in enumerate(_halves(k4[slot, :, w, :])):
            lane0 = (2 * w + half) * 128
            kcat[:, lane0:lane0 + 128] = pltpu.bitcast(x, jnp.uint32)
    pe, _ = _halves(v4[slot, :, 0, :])
    kcat[:, lat_rows * 128:] = pltpu.bitcast(pe, jnp.uint32)


def _launch_with_the_parents_unpack(monkeypatch, *args, **kw):
    """The launch traced anew (no jit cache) around the parent's unpack."""
    with monkeypatch.context() as m:
        m.setattr(plat, "_chunk_matrix", _parents_chunk_matrix)
        return jax.jit(plat.paged_latent_attention.__wrapped__,
                       static_argnames=("scale", "n_chunk", "interpret"))(*args, **kw)


@pytest.mark.parametrize("lat_rows", [2, 4])
@pytest.mark.parametrize("chunk_pages", [2, 5, 8])
def test_the_kernel_across_chunks_and_tails(monkeypatch, chunk_pages, lat_rows):
    """``pallas_latent.paged_latent_attention`` interpreted, against the
    twin, with chunks of 2, 5 and 8 pages (8: a whole chunk's copies start
    ``UNROLL`` pages a pass) over tables of three chunks: contexts that end 1,
    15 and 17 tokens into a chunk, exactly on a chunk's edge, inside the
    first chunk and at the table's end; an empty row; a chunk of 13 queries
    (not a whole tile) whose last tile is all padding. The interpreter's
    semaphore saturates where a whole chunk's wait is large (tests/
    test_mla_dsa.py has the note): tier-1 holds the answers, chip_smoke.py
    the waits. At 2 rows a token (one word-row: the strided loads take every
    second word) and at the cell's 4 (two); both launches bitwise what the
    parent's unpack gives on the same buffers."""
    from dynamo_tpu.ops import pallas_paged as paged

    monkeypatch.setattr(paged, "chunk_pages", lambda *a: chunk_pages)
    T, mb = chunk_pages * BS, 3 * chunk_pages
    RANK = lat_rows * 128
    rng = np.random.default_rng(chunk_pages)
    kc = jnp.asarray(rng.normal(size=(3 * mb + 1, BS, lat_rows, 128)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(3 * mb + 1, BS, lat_rows, 128)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, 3 * mb + 1)).reshape(3, mb), jnp.int32)
    lens = [T + 1, 2 * T + 15, T + 17, 2 * T, 3, mb * BS, 0]
    tb = jnp.concatenate([tables, tables, tables[:1]])
    q = jnp.asarray(rng.normal(size=(len(lens), H, RANK + 128)), jnp.bfloat16)
    q_lens = jnp.asarray([int(n > 0) for n in lens], jnp.int32)
    seq = jnp.asarray(lens, jnp.int32)
    args = (q, kc, vc, tb, q_lens, seq)
    got = plat.paged_latent_attention(*args, scale=0.125, interpret=True)
    assert bool(jnp.all(got == _launch_with_the_parents_unpack(
        monkeypatch, *args, scale=0.125, interpret=True)))
    got = np.asarray(got, np.float32)
    want = np.asarray(att.paged_latent_attention(
        q, kc, vc, tb, jnp.arange(len(lens)), q_lens, seq, 0.125), np.float32)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert not got[-1].any()
    # a chunk that is not whole tiles, at a context that crosses chunks, + rows
    qc = jnp.asarray(rng.normal(size=(24 + 2, H, RANK + 128)), jnp.bfloat16)
    q_lens = jnp.asarray([13, 1, 1], jnp.int32)
    seq = jnp.asarray([2 * T + 7, T, 1], jnp.int32)
    args = (qc, kc, vc, tables, q_lens, seq)
    got = plat.paged_latent_attention(*args, scale=0.125, n_chunk=24, interpret=True)
    assert bool(jnp.all(got == _launch_with_the_parents_unpack(
        monkeypatch, *args, scale=0.125, n_chunk=24, interpret=True)))
    got = np.asarray(got, np.float32)
    want = np.asarray(att.paged_latent_attention(
        qc, kc, vc, tables, jnp.asarray([0, 24, 25]), q_lens, seq, 0.125), np.float32)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert not got[13:24].any()


# a whole chunk of consecutive pages is ONE descriptor an array -------------
def _run_tables(cp):
    """Three rows of three chunks, each row consecutive ids, and ids to
    spare behind them."""
    mb = 3 * cp
    return np.arange(1, 3 * mb + 1).reshape(3, mb), 3 * mb + 1


def _spoil(cp, *places):
    tables, spare = _run_tables(cp)
    for i, place in enumerate(places):
        tables[0, place] = spare + i
    return tables


def _swapped(cp):
    # [f, f + 2, f + 1, f + 3, ...]: first and last id are a run's, the middle
    # is not (at 2 pages a chunk the two sit either side of an edge: [5, 100 |
    # 7, 8] in small)
    tables, _ = _run_tables(cp)
    tables[0, [1, 2]] = tables[0, [2, 1]]
    return tables


RUN_TABLES = {
    "all_runs": lambda cp: _run_tables(cp)[0],
    "no_runs": lambda cp: np.random.default_rng(cp).permutation(
        np.arange(1, 9 * cp + 1)).reshape(3, 3 * cp),
    "broken_at_first_page": lambda cp: _spoil(cp, 0),
    "broken_at_middle_page": lambda cp: _spoil(cp, cp + cp // 2),
    "broken_at_last_page": lambda cp: _spoil(cp, 2 * cp - 1),
    "descending": lambda cp: _run_tables(cp)[0][:, ::-1].copy(),
    "first_and_last_a_runs_middle_not": _swapped,
    # ids 2 .. 2 cp - 1 lie one after the other across the edge of chunks 0
    # and 1, and neither chunk is a run
    "run_across_a_chunks_edge": lambda cp: _spoil(cp, 0, 2 * cp - 1),
}
# the launch's shape on tables that are all runs: (n_chunk, q_lens, contexts
# in tokens as a function of a chunk's T)
RUN_SHAPES = {
    "only_chunk_a_tail": (0, [1, 1, 1], lambda T: [T - BS, 3, T // 2]),
    "an_empty_row": (0, [1, 0, 1], lambda T: [2 * T + 5, 0, 3 * T]),
    "lone_chunk": (24, [13], lambda T: [2 * T + 7]),
    "decode_rows": (0, [1, 1, 1], lambda T: [T + 1, 3 * T, 2 * T]),
    "mixed_launch": (24, [13, 1, 1], lambda T: [3 * T, 2 * T + 15, T]),
}
# PR 47: what the unpack has to hold, at the cell's 4 rows a token (two
# word-rows, so a strided load skips a word): each bitwise the parent's unpack.
# A tail chunk behind a longer row (its buffer rows past the tail hold the
# longer row's tokens, or the zeros of the first program), a one-token
# context, an empty row, tails of odd and of even token counts, and last pages
# whose tokens past the context's end are another request's (planted: large
# and finite; their weight is an exact 0)
UNPACK_SHAPES = {
    "a_tail_chunk_behind_a_longer_row": (0, [1, 1, 1], lambda T: [3 * T, BS + 3, 2 * T + BS]),
    "a_one_token_context": (0, [1, 1, 1], lambda T: [1, T + 1, 2]),
    "an_empty_row_between_tails": (24, [13, 0, 1], lambda T: [T + 9, 0, 5]),
    "an_odd_tail": (0, [1, 1, 1], lambda T: [T + 7, 2 * T + BS + 1, 5]),
    "an_even_tail": (0, [1, 1, 1], lambda T: [T + 6, 2 * T + BS + 2, 4]),
    "a_partly_stale_last_page": (24, [13, 1, 1], lambda T: [2 * T + 3, T + BS + 5, 3 * T - 1]),
}
RUN_SHAPES.update(UNPACK_SHAPES)


def _row_starts(n_chunk, n_rows):
    """Where each row's queries start in the packed buffer the twin takes: the
    chunk's at 0, then one query a row."""
    if not n_chunk:
        return jnp.arange(n_rows)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), n_chunk + jnp.arange(n_rows - 1)])


def _numpy_runs(tables, cp):
    """chunk_runs the slow way: a chunk's ids are first, first + 1, ..."""
    R, mb = tables.shape
    return np.asarray([[
        list(tables[r, c * cp:(c + 1) * cp]) == list(range(tables[r, c * cp], tables[r, c * cp] + cp))
        for c in range(mb // cp)] for r in range(R)])


@pytest.mark.parametrize("chunk_pages", [2, 5, 8])
@pytest.mark.parametrize("case", [*RUN_TABLES, *RUN_SHAPES])
def test_a_run_of_pages_is_read_as_one_copy_and_changes_no_bit(monkeypatch, case, chunk_pages):
    """The launch against itself with no chunk a run: the same pages at
    shuffled places of a second pool, the tables following them, so that every
    whole chunk goes page by page there. Bitwise the same, for tables that
    are runs, that are not, that break a run at a chunk's first, middle or
    last page or only in its middle, that descend, that run across a chunk's
    edge; for a row whose only chunk is a tail, an empty row, a lone chunk,
    decode rows and the mixed launch; the counter reads what the tables hold.
    ``UNPACK_SHAPES`` at 4 rows a token; every case bitwise the parent's unpack."""
    cp = chunk_pages
    monkeypatch.setattr(paged, "chunk_pages", lambda *a: cp)
    T = cp * BS
    ROWS = 4 if case in UNPACK_SHAPES else 2
    RANK = ROWS * 128
    n_chunk, q_lens, lens = RUN_SHAPES.get(case, RUN_SHAPES["mixed_launch"])
    lens = lens(T)
    tables = RUN_TABLES.get(case, RUN_TABLES["all_runs"])(cp)[:len(lens)]
    nb = 9 * cp + 3
    rng = np.random.default_rng(cp)
    kc, vc = (jnp.asarray(rng.normal(size=(nb, BS, ROWS, 128)), jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(n_chunk + len(lens) - bool(n_chunk), H, RANK + 128)), jnp.bfloat16)
    if case == "a_partly_stale_last_page":
        stale = np.zeros((nb, BS), bool)
        for row, n in zip(tables, lens):
            stale[row[(n - 1) // BS], n % BS:] = bool(n % BS)
        kc, vc = (jnp.where(stale[:, :, None, None], 1e4, x).astype(x.dtype) for x in (kc, vc))
        assert stale.sum() == sum(BS - n % BS for n in lens if n % BS)
    q_lens, seq = jnp.asarray(q_lens, jnp.int32), jnp.asarray(lens, jnp.int32)

    def launch(kc, vc, tables):
        return plat.paged_latent_attention(
            q, kc, vc, jnp.asarray(tables, jnp.int32), q_lens, seq, scale=0.125,
            n_chunk=n_chunk, interpret=True)

    # the same pages somewhere else: page i of the pool at place[i]
    while True:
        place = rng.permutation(nb)
        if not _numpy_runs(place[tables], cp).any():
            break
    back = np.argsort(place)
    got, want = launch(kc, vc, tables), launch(kc[back], vc[back], place[tables])
    assert bool(jnp.all(got == want))
    assert bool(jnp.all(got == _launch_with_the_parents_unpack(
        monkeypatch, q, kc, vc, jnp.asarray(tables, jnp.int32), q_lens, seq, scale=0.125,
        n_chunk=n_chunk, interpret=True)))
    twin = att.paged_latent_attention(
        q, kc, vc, jnp.asarray(tables), _row_starts(n_chunk, len(lens)), q_lens, seq, 0.125)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(twin, np.float32), atol=2e-2, rtol=2e-2)
    # what the kernel was told and what the step counts: every neighbour compared
    runs = _numpy_runs(tables, cp)
    assert (np.asarray(paged.chunk_runs(jnp.asarray(tables), cp)) == runs).all()
    whole = [(-(-n // BS)) // cp if ql else 0 for n, ql in zip(lens, np.asarray(q_lens))]
    n_whole = sum(whole)
    n_run = sum(int(runs[r, :w].sum()) for r, w in enumerate(whole))
    counted = tuple(int(x) for x in plat.chunk_reads(kc, jnp.asarray(tables), q_lens, seq))
    assert counted == (n_whole, n_run)
    assert tuple(int(x) for x in plat.chunk_reads(kc, jnp.asarray(place[tables]), q_lens, seq)) == (n_whole, 0)
    share = {"all_runs": 1.0, "no_runs": 0.0, "descending": 0.0, "decode_rows": 1.0, "mixed_launch": 1.0,
             "lone_chunk": 1.0, "broken_at_first_page": 5 / 6, "broken_at_middle_page": 5 / 6,
             "broken_at_last_page": 5 / 6, "run_across_a_chunks_edge": 4 / 6}
    if case in share:
        assert n_whole and n_run / n_whole == share[case]
    if case == "only_chunk_a_tail":
        assert counted == (0, 0)


def _spoiled(vc, fill, rng):
    """The second array with rows 1 and up of every token, of every page,
    filled: NaN, or random bits (NaNs, infinities and huge values among them)."""
    nb, bs, rows, lanes = vc.shape
    if fill == "nan":
        junk = jnp.full((nb, bs, rows - 1, lanes), jnp.nan, vc.dtype)
    else:
        junk = jax.lax.bitcast_convert_type(jnp.asarray(
            rng.integers(0, 2 ** 16, (nb, bs, rows - 1, lanes)), jnp.uint16), vc.dtype)
    return vc.at[:, :, 1:].set(junk)


@pytest.mark.parametrize("fill", ["nan", "random_bits"])
@pytest.mark.parametrize("pages", ["runs", "shuffled"])
@pytest.mark.parametrize("case", [
    "decode_rows", "lone_chunk", "mixed_launch", "only_chunk_a_tail", "an_empty_row"])
def test_only_row_0_of_the_second_array_reaches_a_score(monkeypatch, case, pages, fill):
    """PR 55: the launch copies of the second array a token's first tile alone
    (rows 0 and 1) and keeps the low halves of its words (row 0, ``k_pe``).
    With rows 1-3 of every token NaN, or random bits, the output is BITWISE
    what it is over zeros there, and the twin's within the file's tolerance:
    neither the rows it no longer copies nor the index-key half of the tile it
    does copy reach a score. Decode rows (one with a tail chunk), a lone
    chunk, the mixed launch, a row whose only chunk is a tail, an empty row;
    tables that are runs and the same pages at shuffled places."""
    cp, ROWS = 2, 4
    monkeypatch.setattr(paged, "chunk_pages", lambda *a: cp)
    T, RANK = cp * BS, ROWS * 128
    n_chunk, q_lens, lens = RUN_SHAPES[case]
    lens = lens(T)
    tables, nb = _run_tables(cp)
    tables = tables[:len(lens)]
    rng = np.random.default_rng(len(case))
    kc, vc = (jnp.asarray(rng.normal(size=(nb, BS, ROWS, 128)), jnp.bfloat16) for _ in range(2))
    if pages == "shuffled":
        place = np.concatenate([[0], 1 + rng.permutation(nb - 1)])
        back = np.argsort(place)
        kc, vc, tables = kc[back], vc[back], place[tables]
    tables = jnp.asarray(tables, jnp.int32)
    assert bool(paged.chunk_runs(tables, cp).all()) == (pages == "runs")
    q = jnp.asarray(rng.normal(size=(n_chunk + len(lens) - bool(n_chunk), H, RANK + 128)), jnp.bfloat16)
    q_lens, seq = jnp.asarray(q_lens, jnp.int32), jnp.asarray(lens, jnp.int32)

    def launch(vc):
        return plat.paged_latent_attention(
            q, kc, vc, tables, q_lens, seq, scale=0.125, n_chunk=n_chunk, interpret=True)

    spoiled = _spoiled(vc, fill, rng)
    assert not bool(jnp.isfinite(spoiled[:, :, 1:].astype(jnp.float32)).all())
    got = launch(spoiled)
    assert bool(jnp.all(got == launch(vc.at[:, :, 1:].set(0))))
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all() and got.any()
    twin = att.paged_latent_attention(
        q, kc, spoiled, tables, _row_starts(n_chunk, len(lens)), q_lens, seq, 0.125)
    np.testing.assert_allclose(got, np.asarray(twin, np.float32), atol=2e-2, rtol=2e-2)


def test_the_launch_refuses_what_it_cannot_read():
    _, kc, vc, tables = _paged(0)
    q = jnp.zeros((3, H, RANK + 128), jnp.bfloat16)
    ones = jnp.ones((3,), jnp.int32)
    with pytest.raises(ValueError, match="bf16 pages"):
        plat.paged_latent_attention(q, kc.astype(jnp.float32), vc, tables, ones, ones, scale=1.0)
    with pytest.raises(ValueError, match="even number of tokens"):
        plat.paged_latent_attention(q, kc[:, :15], vc[:, :15], tables, ones, ones, scale=1.0)
    with pytest.raises(ValueError, match="do not make"):
        plat.paged_latent_attention(q, kc, vc, tables, ones, ones, scale=1.0, n_chunk=2)
