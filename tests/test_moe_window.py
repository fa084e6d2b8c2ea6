"""The windowed sparse-expert family (models/moe.py with ``layer_types``):
the engine against the benchmark's plain float32 reference, the grouped
expert layer against the dense oracle, the routing counters against counts
done in numpy, and the benchmark's configuration file through its adapter.

The tiny preset has the real structure: ``[sliding x3, full] x 2``, window
32, 8 experts top 2, YaRN on the full layers, per-head q/k norm. Contexts
run to 3-4 windows, so the window bites, and prompts are longer than the
one prefill bucket, so prefill is chunked.
"""

import asyncio
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.adapters import moe_window as adapter
from benchmarks.reference import moe_window_decoder as reference
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import moe
from dynamo_tpu.ops import pallas_moe
from dynamo_tpu.parallel.mesh import make_mesh
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, EXPERTS, TOP_K, WINDOW = 8, 8, 2, 32

TINY = {
    "attention_bias": False, "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * LAYERS,
    "max_position_embeddings": 1024, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts": EXPERTS, "num_experts_per_tok": TOP_K,
    "num_hidden_layers": LAYERS, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386294361119891,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
    },
    "sliding_window": WINDOW, "tie_word_embeddings": False, "vocab_size": 512,
    "torch_dtype": "float32", "qk_norm": True,
    # float32 engine against the float32 reference: what is left is the
    # order of summation (chunked online softmax against one softmax, sorted
    # rows against a loop over experts): worst 4.8e-7 nat, mean 1.6e-7 seen
    # on the CPU, both paths. 200x that; the mildest wrong computation below
    # (YaRN left off) moves the worst to 0.12 and the mean to 0.035
    "reference_tolerance": {"worst_nat": 1e-4, "mean_nat": 3e-5},
}


def tiny_engine(use_pallas, **kw):
    opts = dict(
        num_blocks=96, block_size=4, max_batch_size=3, max_context=256,
        prefill_buckets=(32,), decode_steps=4, decode_pipeline=1,
        use_pallas=use_pallas, mixed_admission=True, seed=7,
    )
    opts.update(kw)
    return TpuEngine(
        TpuEngineConfig(model=adapter.model_config(TINY), **opts),
        mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
    )


async def generate(engine, rid, prompt, n, started=None):
    req = PreprocessedRequest(
        request_id=rid, model="m", token_ids=prompt,
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        sampling=SamplingOptions(temperature=0.0),
    )
    toks, lps = [], []
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
        lps.extend(out.logprobs or [])
        if started is not None and toks:
            started.set()
    return {"prompt": prompt, "tokens": toks, "logprobs": lps}


def prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(5, 500, n).tolist() for n in lengths]


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas-interpreted", "pure-jax"])
async def test_engine_matches_plain_reference(use_pallas):
    """Logprobs of the engine's own greedy tokens, through ``prefill`` in
    chunks (prompts of 100-121 tokens, bucket 32), ``decode_multi`` (a lone
    request), and ``mixed_step`` (a second prompt arriving beside a resident
    decode), against one full float32 forward of the reference. Logits, not
    tokens; the bounds are TINY's, with their reason there."""
    engine = tiny_engine(use_pallas)
    steps = []
    engine.stats_hook = steps.append
    try:
        lone, resident, arriving = prompts(1, (100, 109, 121))
        samples = [await generate(engine, "lone", lone, 12)]
        started = asyncio.Event()
        first = asyncio.ensure_future(generate(engine, "res", resident, 16, started))
        await started.wait()
        samples.append(await generate(engine, "arr", arriving, 8))
        samples.append(await first)
    finally:
        engine.stop()
    phases = {s.phase for s in steps}
    assert {"prefill", "decode", "mixed"} <= phases
    res = reference.compare(TINY, adapter.reference_params(engine), samples, pad_to=256)
    assert res["ok"], res
    assert res["tokens_compared"] == 36
    # and the bounds do tell this family's own mistakes apart
    for wrong in ({"ignore_window": True}, {"no_yarn": True}, {"no_renorm": True},
                  {"skip_layer": 3}):
        bad = reference.compare(TINY, adapter.reference_params(engine), samples[:1],
                                pad_to=256, **wrong)
        assert not bad["ok"], (wrong, bad)


async def test_step_counters_match_counts_done_by_hand():
    """StepStats.moe_*: T x K summed over layers is arithmetic on what the
    step processed; touched experts and the largest load are bounded by it.
    A horizon sums its steps; a prefill-only step carries none."""
    engine = tiny_engine(False)
    steps = []
    engine.stats_hook = steps.append
    try:
        lone, resident, arriving = prompts(2, (40, 36, 50))
        await generate(engine, "lone", lone, 9)
        started = asyncio.Event()
        first = asyncio.ensure_future(generate(engine, "res", resident, 24, started))
        await started.wait()
        await generate(engine, "arr", arriving, 2)
        await first
    finally:
        engine.stop()
    by_phase = {}
    for s in steps:
        by_phase.setdefault(s.phase, []).append(s)
    assert all(s.moe_tokens_routed is None for s in by_phase["prefill"])
    # the lone request's horizons: 4 steps x 1 row x top 2 x 8 layers
    horizon = by_phase["decode"][0]
    assert horizon.moe_tokens_routed == 4 * 1 * TOP_K * LAYERS
    assert 4 * LAYERS * 1 <= horizon.moe_experts_touched <= horizon.moe_tokens_routed
    assert horizon.moe_load_max == 1  # one row: no expert gets two
    # a fused step: the chunk's real tokens (not its bucket padding) plus
    # the resident decode row
    chunk_lens = [32, 18]  # 50 tokens through a 32-token bucket
    mixed = by_phase["mixed"]
    assert [s.moe_tokens_routed for s in mixed[:2]] == [
        (n + 1) * TOP_K * LAYERS for n in chunk_lens
    ]
    for s, n in zip(mixed, chunk_lens):
        assert LAYERS <= s.moe_experts_touched <= LAYERS * EXPERTS
        assert -(-(n + 1) * TOP_K // EXPERTS) <= s.moe_load_max <= n + 1


def test_routing_stats_against_numpy():
    cfg = moe.MoeConfig.tiny_moe(num_experts=EXPERTS, num_experts_per_tok=TOP_K)
    rng = np.random.default_rng(3)
    valid = np.array([1, 1, 0, 1, 1, 1, 0, 1, 1, 1], bool)
    stats = moe.RoutingStats(jnp.asarray(valid))
    want = []
    for layer in range(3):
        p = moe.init_layer_params(jax.random.PRNGKey(layer), cfg)
        x = jnp.asarray(rng.standard_normal((10, cfg.hidden_size)), jnp.float32)
        moe.moe_ffn_grouped(p, cfg, x, stats=stats)
        _, topi = moe.route(p, cfg, x)
        want.append(np.bincount(np.asarray(topi)[valid].reshape(-1), minlength=EXPERTS))
    want = np.stack(want)
    got = np.asarray(stats.reduce())
    assert got.tolist() == [want.sum(), (want > 0).sum(), want.max()]
    assert want.sum() == 8 * TOP_K * 3


def _kernel(interpret=True):
    return lambda lhs, rhs, sizes: pallas_moe.grouped_matmul(lhs, rhs, sizes, interpret=interpret)


@pytest.mark.parametrize("matmul", [pallas_moe.grouped_matmul_reference, _kernel()],
                         ids=["ragged-dot", "pallas-interpreted"])
@pytest.mark.parametrize("T", [1, 7, 32, 130])
def test_grouped_matches_dense(T, matmul):
    """One path for every token count, against the dense oracle; at T=1 six
    of the eight experts get no row."""
    cfg = moe.MoeConfig.tiny_moe(num_experts=EXPERTS, num_experts_per_tok=TOP_K,
                                 moe_intermediate_size=32)
    p = moe.init_layer_params(jax.random.PRNGKey(11), cfg)
    x = jnp.asarray(np.random.default_rng(T).standard_normal((T, cfg.hidden_size)), jnp.float32)
    if T == 1:
        assert (np.asarray(moe.expert_load(cfg, moe.route(p, cfg, x)[1])) == 0).sum() == 6
    got = moe.moe_ffn_grouped(p, cfg, x, matmul=matmul)
    np.testing.assert_allclose(np.asarray(got), np.asarray(moe.moe_ffn(p, cfg, x)),
                               rtol=2e-5, atol=2e-5)


def _sizes(E, at):
    sizes = np.zeros(E, np.int32)
    for e, n in at.items():
        sizes[e] = n
    return sizes


# (E, rows, k, n, dtype, right-hand sides, group sizes): shapes at which
# ``pick_tiles`` cuts k, so a visit is ``tiles_k`` grid steps
LAUNCHES = {
    # the long-document cell's decode step: 8 rows x 8 experts sorted, 2 of
    # the 16 held experts get a row, 14 visits of 16 are dead
    "decode-2-of-16": (16, 64, 3072, 512, jnp.bfloat16, 2, _sizes(16, {3: 5, 11: 2})),
    # every row went to another chip's experts
    "nobody": (16, 64, 3072, 512, jnp.bfloat16, 2, _sizes(16, {})),
    # every group has rows and most start inside a row tile of 128
    "all-straddle": (8, 512, 3072, 512, jnp.bfloat16, 2,
                     np.array([100, 60, 130, 20, 70, 90, 30, 12], np.int32)),
    # a chunk under a held share: 200 of 512 sorted rows are held experts',
    # the last two row tiles are nobody's
    "held-chunk-tail": (8, 512, 3072, 512, jnp.bfloat16, 2,
                        _sizes(8, {1: 90, 2: 60, 6: 50})),
    # the down projection's kind: one right-hand side, n cut as well
    "tiles-n": (4, 64, 2048, 3072, jnp.float32, 1, _sizes(4, {0: 9, 2: 30})),
}


@pytest.mark.parametrize("case", LAUNCHES)
def test_a_dead_visit_fetches_nothing(case):
    """Walk the grid ``(tiles_n, visits, tiles_k)`` in the pipeline's order
    through the kernel's own index maps and tables, and count a copy wherever
    an operand's block index differs from the step before: each right-hand
    side is read once a real visit a pass, and a dead step moves nothing."""
    E, m, k, n, dtype, n_rhs, sizes = LAUNCHES[case]
    tm = min(pallas_moe.ROW_TILE, m)  # as grouped_matmul cuts rows that need no padding
    tk, tn = pallas_moe.pick_tiles(k, n, jnp.dtype(dtype).itemsize, n_rhs)
    tiles_k, tiles_n = k // tk, n // tn
    assert tiles_k > 1 and (case != "tiles-n" or tiles_n > 1)
    tables = pallas_moe.visit_tables(jnp.asarray(sizes), m, tm)
    visits, n_visits = tables[1].shape[0], int(tables[3][0])
    assert visits == m // tm + E - 1
    ends = np.cumsum(sizes)
    assert n_visits == sum(-(-end // tm) - (end - size) // tm for end, size in zip(ends, sizes) if size)
    n_i, v, k_i = (a.reshape(-1) for a in np.meshgrid(
        np.arange(tiles_n), np.arange(visits), np.arange(tiles_k), indexing="ij"))
    walked = [
        np.stack(jax.vmap(lambda *step: index_map(*step, *tables))(n_i, v, k_i), axis=1)
        for index_map in pallas_moe.index_maps(tiles_k)
    ]
    moved = [np.concatenate([[True], (w[1:] != w[:-1]).any(axis=1)]) for w in walked]
    lhs_moved, rhs_moved, out_moved = moved
    # a real visit walks k, so every one of its steps brings a block of the
    # rows and of each right-hand side; a launch with no real visit holds
    # visit 0's blocks and reads the one a pass over n that n_i moves
    copies = tiles_n * n_visits * tiles_k
    assert rhs_moved.sum() == max(copies, tiles_n)
    assert lhs_moved.sum() == max(copies, 1)
    # a dead step moves no operand (the output among them: no write-back),
    # but where an empty launch starts its next pass
    dead = (v >= n_visits) & ~((v == 0) & (k_i == 0))
    for operand in moved:
        assert not operand[dead].any()


@pytest.mark.parametrize("case", LAUNCHES)
def test_grouped_kernel_is_bitwise_the_ragged_dot(case):
    """The interpreted kernel at the launches above against its twin, on the
    rows a group owns (the others are unspecified). Small whole numbers over
    a power of two: every partial sum is exact in float32, so the order in
    which k is cut cannot show, and a wrong or a missing block does."""
    E, m, k, n, dtype, n_rhs, sizes = LAUNCHES[case]
    rng = np.random.default_rng(43)
    lhs = jnp.asarray(rng.integers(-3, 4, (m, k), dtype=np.int8), dtype)
    rhs = [jnp.asarray(rng.integers(-2, 3, (E, k, n), dtype=np.int8), dtype) / 64
           for _ in range(n_rhs)]
    got = pallas_moe.grouped_matmul(lhs, rhs, jnp.asarray(sizes), interpret=True)
    want = pallas_moe.grouped_matmul_reference(lhs, rhs, jnp.asarray(sizes))
    assert got.shape == want.shape == (m, n) and got.dtype == want.dtype
    owned = int(sizes.sum())
    got, want = (np.asarray(a[:owned].astype(jnp.float32)) for a in (got, want))
    np.testing.assert_array_equal(got, want)
    assert want.any() or not owned


def test_grouped_takes_routing_from_the_caller():
    """``routed=`` as mla.py passes it: weights that do not sum to 1 and an
    expert table under the kernel's names."""
    cfg = moe.MoeConfig.tiny_moe(num_experts=EXPERTS, num_experts_per_tok=3,
                                 moe_intermediate_size=32)
    p = moe.init_layer_params(jax.random.PRNGKey(5), cfg)
    experts = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((9, cfg.hidden_size)), jnp.float32)
    topi = jnp.asarray(np.stack([rng.permutation(EXPERTS)[:3] for _ in range(9)]), jnp.int32)
    topw = jnp.asarray(rng.uniform(0.1, 2.5, (9, 3)), jnp.float32)
    got = moe.moe_ffn_grouped(experts, cfg, x, routed=(topw, topi))
    want = np.zeros((9, cfg.hidden_size), np.float32)
    for t in range(9):
        for w, e in zip(np.asarray(topw[t]), np.asarray(topi[t])):
            h = np.asarray(x[t])
            gate, up = h @ np.asarray(p["w_gate"][e]), h @ np.asarray(p["w_up"][e])
            want[t] += w * ((gate / (1 + np.exp(-gate)) * up) @ np.asarray(p["w_down"][e]))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_config_file_gives_the_published_widths():
    with open(os.path.join(ROOT, "benchmarks", "configs", "mellum2-12b-a2.5b-d12.json")) as f:
        cfg = json.load(f)
    m = adapter.model_config(cfg)
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim) == (2304, 32, 4, 128)
    assert (m.num_experts, m.moe_intermediate_size, m.num_experts_per_tok) == (64, 896, 8)
    assert (m.sliding_window, m.vocab_size, m.num_layers) == (1024, 98304, 12)
    assert m.norm_topk_prob and m.qk_norm and not m.tie_embeddings and not m.qkv_bias
    assert m.layer_types == ("sliding_attention",) * 3 + ("full_attention",) \
        + ("sliding_attention",) * 3 + ("full_attention",) \
        + ("sliding_attention",) * 3 + ("full_attention",)
    assert [m.window_for_layer(i) for i in range(4)] == [1024, 1024, 1024, None]
    assert (m.rope_theta, m.rope_scaling_factor, m.rope_original_max_position) == (500000.0, 16.0, 8192)
    assert m.rope_attention_factor == 1.2772588722239782 and m.rope_truncate
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["reduced_from"] == {"num_hidden_layers": 28}
    assert len(cfg["layer_types"]) == 28  # the public list, kept whole


def test_rotary_tables_match_the_reference():
    """The program's two tables (YaRN for full layers, plain for sliding)
    against the reference's, written separately from the config keys."""
    m = adapter.model_config(TINY)
    pos = jnp.arange(200)
    for kind, yarn in (("full_attention", True), ("sliding_attention", False)):
        inv, att = reference.rope_inv_freq(TINY["rope_parameters"][kind], 16)
        cos, sin = moe.rope_tables(m, pos, yarn)
        ang = np.arange(200, dtype=np.float32)[:, None] * inv[None]
        np.testing.assert_allclose(np.asarray(cos[:, 0]), np.cos(ang) * att, atol=2e-5)
        np.testing.assert_allclose(np.asarray(sin[:, 0]), np.sin(ang) * att, atol=2e-5)
    assert att == 1.0 and reference.rope_inv_freq(TINY["rope_parameters"]["full_attention"], 16)[1] > 1.1
