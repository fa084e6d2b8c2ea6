"""Solar Open 2's hybrid of layer kinds (models/solar_open2.py: Kimi Delta
Attention in three layers of four, gated softmax attention without positions
in the fourth, a routed feed-forward of which the chip holds a share) at a
test's size that keeps the shape's oddities (two periods of 4 that start on a
GQA layer, 4 query heads a kv head, a held share, beta in (0, 2), channels
that forget fast and slow): the recurrence's two forms, the kernel against
its twin, the engine with pages for its attention layers and slot state for
the others against the benchmark's plain float32 reference
(benchmarks/reference/solar_open2_decoder.py), the shares adding up, the
counters, the refusals, and what every other family allocates.
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import system
from benchmarks.adapters import solar_open2 as adapter
from benchmarks.reference import solar_open2_decoder as ref
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig, _model_param_bytes
from dynamo_tpu.models import falcon_h1 as fh1
from dynamo_tpu.models import moe as moelib
from dynamo_tpu.models import registry
from dynamo_tpu.models import solar_open2 as so2
from dynamo_tpu.models.gptoss import GptOssConfig
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.mla import MlaConfig
from dynamo_tpu.models.moe import MoeConfig
from dynamo_tpu.ops import pallas_kda as pk

L = 8  # layers of the tests' model: GQA, KDA x 3, twice


def file_cfg(dtype="float32", **kw):
    """A configuration file's dict (the public keys) at a test's size."""
    cfg = {
        "model_type": "solar_open2", "vocab_size": 512, "hidden_size": 128,
        "num_hidden_layers": L, "num_attention_heads": 8, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 256, "moe_intermediate_size": 32,
        "rope_theta": 10000, "partial_rotary_factor": 1, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 4096, "tie_word_embeddings": False, "torch_dtype": dtype,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                               "num_kv_heads": None},
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": [0, 4, 8, 12], "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_routed_experts": 4, "router_outputs": 16,
        "experts_held_first": 4, "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 4,
        "assumed_sizes": {"kda_low_rank": 16},
        "reference_tolerance": {"worst_nat": 2e-4, "mean_nat": 2e-5, "median_nat": 2e-5,
                                "slow_state_rel": 2e-4, "first_cache_rel": 1e-5,
                                "state_precision_gap": 0.5},
    }
    cfg.update(kw)
    return cfg


def engine_of(cfg=None, **kw):
    opts = dict(num_blocks=64, block_size=8, max_batch_size=2, max_context=128,
                prefill_buckets=(16,), seed=3, use_pallas=False, decode_steps=8,
                decode_pipeline=1, mixed_admission=True)
    opts.update(kw)
    model = adapter.model_config(cfg or file_cfg())
    return TpuEngine(TpuEngineConfig(model=model, **opts))


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, n).tolist() for n in lengths]


def sample(prompt, rec):
    return {"prompt": prompt, "tokens": rec["tokens"], "logprobs": rec["logprobs"]}


def test_the_adapter_builds_the_tiny_preset():
    assert adapter.model_config(file_cfg()) == so2.SolarOpen2Config.tiny(
        dtype=jnp.float32, intermediate_size=256, max_position=4096)


# ---------------------------------------------------------------------------
# the recurrence: the chunked form and the kernel against the definition
# ---------------------------------------------------------------------------


def _operands(key, lead, H=4, d=16, fast=False):
    k = jax.random.split(key, 7)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = l2(jax.random.normal(k[0], (*lead, H, d))) * d ** -0.5
    kk = l2(jax.random.normal(k[1], (*lead, H, d)))
    v = jax.random.normal(k[2], (*lead, H, d))
    # log-decays a channel from -1e-3 (remembers a thousand tokens) to -1.6
    # (rate 16, step 0.1: cumulated past -88 inside 64 tokens); ``fast``:
    # every fourth channel at -4 a token
    g = -jnp.exp(jax.random.uniform(k[3], (*lead, H, d), minval=np.log(1e-3), maxval=np.log(1.6)))
    if fast:
        g = jnp.where(jnp.arange(d) % 4 == 0, -4.0, g)
    beta = jax.random.uniform(k[4], (*lead, H), minval=0.0, maxval=2.0)
    S = jax.random.normal(k[5], (H, d, d), jnp.float32)
    return S, q, kk, v, g, beta


def _token_by_token(S, q, k, v, g, beta):
    def token(s, inp):
        q_t, k_t, v_t, g_t, b_t = (x[None] for x in inp)
        s, y = pk.kda_state_update_reference(s, q_t, k_t, v_t, jnp.exp(g_t), b_t, jnp.ones((1,), bool))
        return s, (y[0], s[0])

    _, (ys, states) = jax.lax.scan(token, S[None], (q, k, v, g, beta))
    return ys, states


@pytest.mark.parametrize("T,chunk,sub,identity_from", [
    (150, 64, 16, 150), (21, 8, 4, 17), (16, 8, 4, 16), (8, 8, 8, 3), (5, 16, 4, 5),
])
def test_the_chunked_scan_is_the_token_by_token_recurrence(T, chunk, sub, identity_from):
    """With channels that forget fast (the cumulated log-decay passes -88
    inside a chunk: a product form would overflow float32) and beta above 1;
    also past the run's real tokens, where beta 0 and a decay of 1 have to be
    the identity, and over a run that is not whole chunks."""
    S, q, k, v, g, beta = _operands(jax.random.PRNGKey(T), (T,), fast=True)
    assert float(jnp.cumsum(g[:chunk], axis=0).min()) < -88 or T < 32
    assert float(beta.max()) > 1.0
    real = jnp.arange(T) < identity_from
    g, beta = jnp.where(real[:, None, None], g, 0.0), jnp.where(real[:, None], beta, 0.0)
    y, S_end = pk.kda_scan(S, q, k, v, g, beta, chunk=chunk, sub=sub)
    ys, states = _token_by_token(S, q, k, v, g, beta)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(S_end), np.asarray(states[-1]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ys), atol=2e-5)
    if identity_from < T:  # the padding changed nothing
        np.testing.assert_allclose(np.asarray(S_end), np.asarray(states[identity_from - 1]),
                                   atol=2e-5)


def test_the_scan_refuses_a_chunk_that_is_not_whole_sub_blocks():
    S, q, k, v, g, beta = _operands(jax.random.PRNGKey(0), (8,))
    with pytest.raises(ValueError, match="sub-blocks"):
        pk.kda_scan(S, q, k, v, g, beta, chunk=8, sub=3)


@pytest.mark.parametrize("live", [
    [1, 0, 1, 1, 0, 1], [0] * 6, [1] * 6, [0, 0, 0, 0, 1, 0], [0, 1, 1, 0, 0, 0],
])
@pytest.mark.parametrize("head_block", [2, 4])
def test_the_interpreted_kernel_is_its_twin_and_dead_rows_keep_their_state(live, head_block):
    R = len(live)
    S0, q, k, v, g, beta = _operands(jax.random.PRNGKey(7), (R,))
    S = jnp.stack([S0 * (i + 1) for i in range(R)])
    live = jnp.asarray(live, bool)
    v = v.astype(jnp.bfloat16)
    S_ref, y_ref = pk.kda_state_update_reference(S, q, k, v, jnp.exp(g), beta, live)
    S_k, y_k = pk.kda_state_update(S + 0, q, k, v, jnp.exp(g), beta, live, interpret=True,
                                   head_block=head_block)
    np.testing.assert_allclose(np.asarray(S_k), np.asarray(S_ref), atol=1e-5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=1e-5, rtol=1e-5)
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(S_k)[dead], np.asarray(S)[dead])
    assert not np.asarray(y_k)[dead].any()


def test_the_kernel_refuses_heads_that_do_not_cut_into_blocks():
    S0, q, k, v, g, beta = _operands(jax.random.PRNGKey(1), (2,), H=6)
    with pytest.raises(ValueError, match="do not cut into blocks"):
        pk.kda_state_update(jnp.stack([S0, S0]), q, k, v, jnp.exp(g), beta, jnp.ones((2,), bool),
                            interpret=True, head_block=4)


# ---------------------------------------------------------------------------
# the engine: one run of every scenario, shared by the tests below
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """The float32 engine (mixed steps, horizons of 8, 2 slots, one bucket of
    16) through ``generate``: B alone in a fresh slot; A alone; B again in
    the slot A left; A and B at once (B's chunks ride A's decode steps)."""
    cfg = file_cfg()
    engine = engine_of(cfg)
    steps = []
    engine.stats_hook = steps.append
    a, b = prompts_of(20, 33)  # b: three chunks, the last 1 real token + 15 of padding

    async def run():
        out = {}
        out["b1"] = await system.generate(engine, "b1", b, 12)
        out["a"] = await system.generate(engine, "a", a, 12)
        out["b2"] = await system.generate(engine, "b2", b, 12)
        started = asyncio.Event()
        ta = asyncio.ensure_future(system.generate(
            engine, "a-c", a, 24, on_chunk=lambda *_: started.set()))
        await started.wait()
        out["b-c"] = await system.generate(engine, "b-c", b, 12)
        out["a-c"] = await ta
        return out

    try:
        recs = asyncio.run(run())
    finally:
        engine.stop()
    return {"cfg": cfg, "engine": engine, "recs": recs, "steps": steps, "a": a, "b": b}


def test_chunked_prefill_then_decode_through_pages_and_state_is_the_references_forward(served):
    """Three chunks (state and tail carried across chunk boundaries, the last
    chunk's padding the identity), then a horizon of decode steps, against
    one plain forward whose KDA layers are the recurrence: logits, not tokens."""
    recs = served["recs"]
    samples = [sample(served["b"], recs["b1"]), sample(served["a"], recs["a"])]
    res = ref.compare(served["cfg"], adapter.reference_params(served["engine"]), samples, 64)
    assert res["tokens_compared"] == 24 and res["worst_argmax_gap_nat"] == 0.0, res
    assert max(res["worst_logprob_difference_nat"], res["mean_logprob_difference_nat"]) < 2e-4, res


def test_a_mixed_step_is_its_two_halves(served):
    """B's chunks rode A's decode steps, A decoded beside them: both still
    the reference's forward (the split programs gave ``b1`` and ``a``), and
    what the slots and the pages hold when they end is what the reference's
    recurrence and its keys would hold."""
    recs = served["recs"]
    assert any(s.phase == "mixed" and s.kda_rows_updated and s.kda_tokens_scanned
               for s in served["steps"])
    samples = [sample(served["b"], recs["b-c"]), sample(served["a"], recs["a-c"])]
    res = ref.compare(served["cfg"], adapter.reference_params(served["engine"]), samples, 64)
    assert res["ok"], res
    assert len(res["slowest_rows_state_difference_by_layer"]) == 6
    assert len(res["cache_difference_by_layer"]) == 2
    assert recs["b-c"]["tokens"] == recs["b1"]["tokens"]
    assert recs["a-c"]["tokens"][:12] == recs["a"]["tokens"]


def test_a_state_kept_at_sixteen_bits_is_told_by_its_precision(served):
    """The delta rule forgets, so a state rounded to bf16 a token reads close
    to the honest one by norm at the published size; the comparison also
    reads the PRECISION each side keeps its state at: both float32 here (gap
    0), the reference's rounded to bf16 a token (gap 1, over the limit)."""
    recs = served["recs"]
    samples = [sample(served["b"], recs["b-c"]), sample(served["a"], recs["a-c"])]
    params = adapter.reference_params(served["engine"])
    honest = ref.compare(served["cfg"], params, samples, 64)
    assert honest["held_state_precision_gap"] < 0.01 and honest["ok"], honest
    only = dict(served["cfg"], reference_tolerance={"state_precision_gap": 0.5})
    assert ref.compare(only, params, samples, 64)["ok"]
    rounded = ref.compare(only, params, samples, 64, state_bits=16)
    assert rounded["held_state_precision_gap"] > 0.99 and not rounded["ok"], rounded


def test_a_reused_slot_does_not_remember_who_held_it(served):
    recs = served["recs"]
    assert recs["b2"]["tokens"] == recs["b1"]["tokens"]
    assert recs["b2"]["logprobs"] == recs["b1"]["logprobs"]


def test_a_repeated_prompt_takes_no_prefix_hit(served):
    """A block hash restores pages and no state: the family declines."""
    assert not registry.prefix_reusable(served["engine"].mcfg)
    assert served["recs"]["b2"]["cached_tokens"] == 0
    assert served["engine"].allocator.cached_blocks == 0


def test_the_steps_count_the_recurrence_over_the_state_layers_and_the_routing(served):
    """``kda_*`` over the 6 layers that keep state (not the 8 run); the
    routing's three and the held experts touched on every step with a
    readback; the slot store's bytes on the gauge's field; no ``ssm_*``."""
    steps = served["steps"]
    per_slot = served["engine"].state.bytes_per_slot
    assert per_slot == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    SL = 6  # the layers that keep state
    assert sum(s.kda_tokens_scanned for s in steps) == SL * (3 * 33 + 2 * 20)
    for s in steps:
        assert s.ssm_rows_updated is None and s.ssm_state_bytes == s.batch_occupancy * per_slot
        if s.phase == "prefill":
            assert (s.kda_rows_updated, s.kda_decode_steps) == (0, 0)
            assert s.kda_tokens_scanned == SL * s.tokens
        elif s.phase == "mixed":
            assert s.kda_decode_steps == 1 and s.kda_rows_updated == SL  # one resident row
        else:
            assert s.kda_decode_steps in (1, 8)
            # a row of a horizon stops at what its request asked for
            assert s.kda_rows_updated == SL * s.tokens <= SL * 2 * s.kda_decode_steps
    assert any(s.kda_decode_steps == 8 for s in steps)
    # every emitted token but a request's first came from a decode row
    emitted = sum(len(r["tokens"]) - 1 for r in served["recs"].values())
    assert sum(s.kda_rows_updated for s in steps) == SL * emitted
    counted = [s for s in steps if s.phase != "prefill"]
    assert all(s.moe_tokens_routed is not None and s.moe_held_experts_touched is not None
               and s.moe_held_experts_touched <= 8 * 4 * 8 for s in counted)


def test_debug_worker_shows_the_state_under_the_familys_prefix(served):
    from dynamo_tpu.engine.telemetry import EngineTelemetry
    from dynamo_tpu.runtime import metrics as M

    tele = EngineTelemetry(M.MetricsScope())
    for s in served["steps"]:
        tele.on_step(s)
    snap = tele.snapshot()
    assert "ssm" not in snap
    assert snap["kda"]["rows_updated"] > 0 and snap["kda"]["tokens_scanned"] > 0
    assert snap["kda"]["state_bytes"] == served["steps"][-1].ssm_state_bytes
    assert "held_experts_touched" in snap["moe"]


@pytest.mark.parametrize("kernels", ["pure JAX", "interpreted"])
def test_single_steps_and_interpreted_kernels_give_the_same_logprobs(served, kernels):
    """More requests than slots: one waits, the loop falls back to the
    single-step ``decode``; with ``use_pallas`` the recurrence, both
    attention launches and the grouped multiplication run interpreted."""
    a, b = served["a"], served["b"]
    engine = engine_of(served["cfg"], use_pallas=kernels == "interpreted")
    steps = []
    engine.stats_hook = steps.append

    async def run():
        return await asyncio.gather(
            system.generate(engine, "b", b, 12), system.generate(engine, "a", a, 12),
            system.generate(engine, "b'", b, 12))

    try:
        recs = asyncio.run(run())
    finally:
        engine.stop()
    assert any(s.phase == "decode" for s in steps)
    for got, want in zip(recs, ("b1", "a", "b1")):
        assert got["tokens"] == served["recs"][want]["tokens"]
        np.testing.assert_allclose(got["logprobs"], served["recs"][want]["logprobs"], atol=2e-4)


def test_a_prefill_chunk_longer_than_the_scans_chunk_is_the_references_forward():
    """One prefill chunk of 45 tokens in a bucket of 64 crosses the scan's
    own chunk (``pallas_kda.SCAN_CHUNK``) inside one program."""
    assert pk.SCAN_CHUNK < 45
    cfg = file_cfg()
    engine = engine_of(cfg, prefill_buckets=(64,))
    (c,) = prompts_of(45, seed=1)
    try:
        rec = asyncio.run(system.generate(engine, "c", c, 12))
    finally:
        engine.stop()
    res = ref.compare(cfg, adapter.reference_params(engine), [sample(c, rec)], 64)
    assert res["ok"] and res["worst_argmax_gap_nat"] == 0.0, res


def test_bucket_padding_leaves_state_and_tail_alone():
    """A chunk of 5 real tokens in a bucket of 16: the state and the tail
    after it are those after the 5 tokens alone."""
    cfg = so2.SolarOpen2Config.tiny(dtype=jnp.float32)
    p = so2.init_layer_params(jax.random.PRNGKey(0), cfg, 1)
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    n, H = cfg.kda_size, cfg.kda_num_heads
    qkv, f, b = (jax.random.normal(k[0], (16, 3 * n)), jax.random.normal(k[1], (16, n)),
                 jax.random.normal(k[2], (16, H)))
    S = jax.random.normal(k[3], (H, 16, 16))
    tail = jax.random.normal(k[4], (3, 3 * n))
    y_pad, S_pad, t_pad = so2.mix_chunk(p, cfg, qkv, f, b, S, tail, 5)
    y, S_5, t_5 = so2.mix_chunk(p, cfg, qkv[:5], f[:5], b[:5], S, tail, 5)
    np.testing.assert_allclose(np.asarray(S_pad), np.asarray(S_5), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(t_pad), np.asarray(t_5))
    np.testing.assert_allclose(np.asarray(y_pad[:5]), np.asarray(y), atol=1e-6)
    # and one token a row is the chunk's first token
    y_row, S_row, t_row = so2.mix_rows(p, cfg, qkv[:1], f[:1], b[:1], S[None], tail[None],
                                       jnp.ones((1,), bool))
    y_1, S_1, t_1 = so2.mix_chunk(p, cfg, qkv[:1], f[:1], b[:1], S, tail, 1)
    np.testing.assert_allclose(np.asarray(S_row[0]), np.asarray(S_1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y_row[0]), np.asarray(y_1[0]), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(t_row[0]), np.asarray(t_1))


def test_the_tiny_preset_draws_beta_above_one_and_fast_and_slow_channels():
    cfg = so2.SolarOpen2Config.tiny(dtype=jnp.float32)
    assert registry.page_layers(cfg) == (0, 4) and registry.state_layers(cfg) == (1, 2, 3, 5, 6, 7)
    p = so2.init_layer_params(jax.random.PRNGKey(0), cfg, 1)
    u = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))
    g, beta = so2._gates(p, cfg, (u @ p["w_f1"]) @ p["w_f2"], u @ p["w_b"])
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    rate = np.asarray(-g.mean(axis=0))
    assert rate.max() / rate.min() > 50


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------


def test_the_drawn_selection_bias_moves_the_selection_and_leaves_the_load_even():
    """At the published router's size (4 096 -> 320, top 8): the bias as drawn
    changes most tokens' top 8 (ignoring it is a wrong computation) and does
    not decide it (the top of a sigmoid is flat: at 0.1 one expert takes 10 x
    the mean load and half of a held share of 20 goes untouched a step)."""
    h, E, K = 4096, 320, 8
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (1024, h))
    s = jax.nn.sigmoid(x @ (jax.random.normal(kw, (h, E)) / np.sqrt(h)))
    unit = jax.random.normal(kb, (E,))

    def chosen(std):
        return np.sort(np.asarray(jax.lax.top_k(s + std * unit, K)[1]), axis=-1)

    plain, drawn, heavy = chosen(0.0), chosen(so2.ROUTER_BIAS_STD), chosen(0.1)
    assert (drawn != plain).any(axis=-1).mean() > 0.5
    load = lambda idx: np.bincount(idx.ravel(), minlength=E)  # noqa: E731
    assert load(drawn).max() < 2.5 * load(drawn).mean() < load(heavy).max() / 2
    touched = lambda idx: np.mean([  # noqa: E731
        len(set(r[(r >= 160) & (r < 180)])) for r in idx.reshape(8, -1)])
    assert touched(drawn) > 18 and touched(heavy) < 14


def test_the_sixteen_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The routed parts of all 4 shares of the tiny layer (16 experts, 4 a
    share; the published layer's 16 shares of 20) plus the shared expert
    counted once equal the layer that holds every expert."""
    whole = so2.SolarOpen2Config.tiny(dtype=jnp.float32, experts_held=None)
    p = so2.init_layer_params(jax.random.PRNGKey(0), whole, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, whole.hidden_size))
    want = moelib.routed_shared_ffn(p, whole, x)
    no_shared = dict(whole.__dict__, num_shared_experts=0)
    routed = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        share = so2.SolarOpen2Config(**dict(no_shared, experts_held=(first, 4)))
        ps = dict(p, **{k: p[k][first:first + 4] for k in ("w_egate", "w_eup", "w_edown")})
        routed = routed + moelib.routed_shared_ffn(ps, share, x)
    only_shared = so2.SolarOpen2Config(**dict(whole.__dict__, experts_held=(0, 4)))
    none = dict(p, **{k: jnp.zeros_like(p[k][:4]) for k in ("w_egate", "w_eup", "w_edown")})
    shared = moelib.routed_shared_ffn(none, only_shared, x)
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want), atol=2e-5)
    # and the reference's share is the program's
    cfg = file_cfg()
    held = so2.SolarOpen2Config.tiny(dtype=jnp.float32)
    ph = dict(p, **{k: p[k][4:8] for k in ("w_egate", "w_eup", "w_edown")})
    got = ref._ffn({k: ph[k] for k in ref._FFN_KEYS}, x, eps=1e-5, top_k=4, first=4, scale=1.0,
                   norm_topk=True)
    v = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(moelib.routed_shared_ffn(ph, held, v)),
                               atol=2e-5)
    assert cfg["n_routed_experts"] * 4 == cfg["router_outputs"]


# ---------------------------------------------------------------------------
# who allocates what
# ---------------------------------------------------------------------------


def test_pages_for_page_layers_only_and_state_for_state_layers_only(served):
    e = served["engine"]
    assert len(e.k_caches) == len(e.v_caches) == 2
    assert {k: len(v) for k, v in e.state.arrays.items()} == {"kda": 6, "conv": 6}
    assert e.state.arrays["kda"][0].shape == (2, 4, 16, 16)
    assert e.state.arrays["kda"][0].dtype == jnp.float32
    assert e.state.arrays["conv"][0].shape == (2, 3, 192)
    # a block's bytes are two layers' pages, not eight
    assert e.kv_bytes_per_block == 2 * 2 * 8 * 2 * 16 * 4
    assert e.snapshot()["slot_state"]["bytes"] == 2 * e.state.bytes_per_slot


@pytest.mark.parametrize("name,make,pages,state", [
    ("llama", lambda: LlamaConfig.tiny(), 4, None),
    ("moe", lambda: MoeConfig.tiny_moe(), 2, None),
    ("mla", lambda: MlaConfig.tiny_mla(), 2, None),
    ("falcon_h1", lambda: fh1.FalconH1Config.tiny(dtype=jnp.float32), 2, {"ssm": 2, "conv": 2}),
])
def test_every_other_family_allocates_what_it_did(name, make, pages, state):
    cfg = make()
    assert registry.page_layers(cfg) == tuple(range(cfg.num_layers))
    assert registry.state_layers(cfg) == (tuple(range(cfg.num_layers)) if state else ())
    assert registry.layer_index(registry.page_layers(cfg), cfg.num_layers) is None
    e = TpuEngine(TpuEngineConfig(model=cfg, num_blocks=16, block_size=8, max_batch_size=2,
                                  max_context=64, prefill_buckets=(16,), decode_steps=4,
                                  decode_pipeline=1, use_pallas=False))
    try:
        assert len(e.k_caches) == len(e.v_caches) == pages == cfg.num_layers
        if state is None:
            assert e.state is None
        else:
            assert {k: len(v) for k, v in e.state.arrays.items()} == state
    finally:
        e.stop()


@pytest.mark.parametrize("make", [
    lambda: LlamaConfig.tiny(), lambda: MoeConfig.tiny_moe(), lambda: MlaConfig.tiny_mla_moe(),
    lambda: fh1.FalconH1Config.tiny(), lambda: so2.SolarOpen2Config.tiny(),
    lambda: GptOssConfig.tiny_gptoss(),
])
def test_parameter_bytes_are_counted_from_the_familys_own_shapes(make):
    """Every leaf once at its own width; a leaf the family names as stacked
    over its experts (every leaf with the experts in front, and no other) at
    its top-k."""
    cfg = make()
    shapes = jax.eval_shape(lambda k: registry.init_params(k, cfg), jax.random.PRNGKey(0))
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(shapes))
    named = registry.expert_stack_leaves(cfg)
    held = getattr(cfg, "experts_held", None)
    n = held[1] if held else getattr(cfg, "num_experts", 0)
    layers = [layer for layer in shapes["layers"] if any(name in layer for name in named)]
    assert bool(layers) == bool(n)
    for layer in layers:
        assert all(layer[name].shape[0] == n for name in named)
        assert not [name for name, x in layer.items()
                    if name not in named + ("w_uk", "w_uv") and x.ndim == 3]  # MLA's head stacks
    stacks = sum(int(np.prod(layer[name].shape)) * layer[name].dtype.itemsize
                 for layer in layers for name in named)
    got = _model_param_bytes(cfg)
    k = min(getattr(cfg, "num_experts_per_tok", 0), n)
    assert got == total - (stacks - stacks * k // n if stacks else 0)


def test_the_published_config_counts_its_parameters_state_and_pages():
    """ISSUE 41's arithmetic from the program's own shapes, in the abstract."""
    cfg = so2.SolarOpen2Config.solar_open2_250b(num_layers=8, vocab_size=24576,
                                                experts_held=(160, 20))
    shapes = jax.eval_shape(lambda k: so2.init_params(k, cfg), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    kda = sum(int(np.prod(x.shape)) for x in shapes["layers"][1].values())
    gqa = sum(int(np.prod(x.shape)) for x in shapes["layers"][0].values())
    assert abs(kda / 469.4e6 - 1) < 2e-3 and abs(gqa / 440.7e6 - 1) < 2e-3
    assert abs(n / 3.90e9 - 1) < 3e-3
    per_slot = sum(int(np.prod(s)) * np.dtype(d).itemsize for _, s, d in so2.state_spec(cfg))
    assert per_slot == 64 * 128 * 128 * 4 + 3 * 24576 * 2
    assert registry.page_layers(cfg) == (0, 4) and len(registry.state_layers(cfg)) == 6
    # the uncut model: 250.3 B parameters, 14.7 B active a token
    full = so2.SolarOpen2Config.solar_open2_250b()
    fs = jax.eval_shape(lambda k: so2.init_params(k, full), jax.random.PRNGKey(0))
    total = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(fs))
    assert abs(total / 250.3e9 - 1) < 2e-3
    stacks = sum(int(np.prod(x.shape)) for l in fs["layers"] for k, x in l.items() if x.ndim == 3)
    assert abs((total - stacks + stacks * 8 // 320) / 14.7e9 - 1) < 5e-3


# ---------------------------------------------------------------------------
# the registry and the refusals
# ---------------------------------------------------------------------------


def test_the_registry_knows_the_family():
    cfg = so2.SolarOpen2Config.tiny()
    assert registry.is_solar_open2(cfg) and registry.family(cfg) is so2
    assert registry.counts_routing(cfg) and not registry.supports_pp(cfg)
    assert not registry.prefix_reusable(cfg) and registry.read_counters(cfg) == ()
    assert registry.state_prefix(cfg) == "kda"
    assert registry.state_prefix(fh1.FalconH1Config.tiny()) == "ssm"
    assert [n for n, _, _ in registry.state_spec(cfg)] == ["kda", "conv"]
    assert registry.layer_index(registry.page_layers(cfg), 8) == {0: 0, 4: 1}
    assert registry.layer_index(registry.state_layers(cfg), 8) == {1: 0, 2: 1, 3: 2, 5: 3, 6: 4, 7: 5}
    with pytest.raises(ValueError, match="pp serving supports dense"):
        registry.check_pp_supported(cfg)
    with pytest.raises(ValueError, match="both kinds"):
        so2.SolarOpen2Config.tiny(gqa_layers=())


@pytest.mark.parametrize("asked,match", [
    (dict(tp=2), "tp > 1"), (dict(pp=2), "pp / sp > 1"), (dict(sp=2), "pp / sp > 1"),
    (dict(spec=True), "speculative draft"), (dict(lora=True), "LoRA"),
    (dict(kv_quantized=True), "kv_dtype=int8"), (dict(vision=True), "vision"),
    (dict(transfer=True), "transfer plane"), (dict(kvbm=True), "KVBM"),
])
def test_each_refusal_says_slot_state_and_its_reason(asked, match):
    cfg = so2.SolarOpen2Config.tiny()
    with pytest.raises(ValueError, match=match) as e:
        registry.check_state_supported(cfg, **asked)
    assert "slot state (SolarOpen2Config)" in str(e.value)


def test_a_held_share_is_refused_under_tp_whatever_the_family():
    cfg = so2.SolarOpen2Config.tiny()
    with pytest.raises(ValueError, match="held share of the experts .SolarOpen2Config. does not run with tp > 1"):
        registry.check_dsa_supported(cfg, tp=2)
    registry.check_dsa_supported(cfg)
    registry.check_dsa_supported(so2.SolarOpen2Config.tiny(experts_held=None), tp=2)


def test_the_engine_refuses_at_construction():
    with pytest.raises(ValueError, match="kv_dtype=int8"):
        engine_of(kv_dtype="int8")


def test_a_published_config_json_is_read_as_this_family_and_its_checkpoint_refused(tmp_path):
    from dynamo_tpu.engine import weights

    hf = {k: v for k, v in file_cfg().items()
          if k not in ("reference_tolerance", "torch_dtype", "router_outputs", "experts_held_first",
                       "assumed_sizes")}
    hf["n_routed_experts"] = 16
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = weights.config_from_hf(str(tmp_path))
    assert registry.is_solar_open2(cfg)
    want = so2.SolarOpen2Config.tiny(experts_held=None, intermediate_size=256,
                                     max_position=4096, rope_theta=10000.0)
    assert cfg == want
    with pytest.raises(NotImplementedError, match="no checkpoint loader for solar_open2"):
        weights.load_params(str(tmp_path), cfg)
