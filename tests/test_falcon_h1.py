"""Falcon-H1's parallel hybrid layer (models/falcon_h1.py: a Mamba-2
state-space mixer beside grouped-query attention) at a test's size that
keeps the shape's oddities (5 query heads a kv head, 2 groups, a state wider
than the head, every multiplier away from 1): the recurrence's two forms,
the kernel against its twin, the engine with its second kind of state
(engine/state_cache.py) against the benchmark's plain float32 reference
(benchmarks/reference/falcon_h1_decoder.py), the counters, the refusals, and
the other families' programs left as they were.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import system
from benchmarks.adapters import falcon_h1 as adapter
from benchmarks.reference import falcon_h1_decoder as ref
from dynamo_tpu.engine import step_args
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models import falcon_h1 as fh1, registry
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.mla import MlaConfig
from dynamo_tpu.ops import pallas_ssm as ps

L = 2  # layers of the tests' model


def file_cfg(dtype="float32", **kw):
    """A configuration file's dict (the public keys) at a test's size."""
    cfg = {
        "model_type": "falcon_h1", "vocab_size": 512, "hidden_size": 128,
        "num_hidden_layers": L, "num_attention_heads": 10, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 256, "rope_theta": 1e11, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 4096, "tie_word_embeddings": False, "torch_dtype": dtype,
        "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 32,
        "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_conv_bias": True,
        "mamba_norm_before_gate": False, "mamba_rms_norm": True, "mamba_proj_bias": False,
        "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
        "rope_scaling": None, "attn_layer_indices": None,
        "embedding_multiplier": 5.66, "lm_head_multiplier": 0.05, "attention_in_multiplier": 0.9,
        "attention_out_multiplier": 0.3, "key_multiplier": 0.2, "ssm_in_multiplier": 0.25,
        "ssm_out_multiplier": 0.4, "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.6],
        "mlp_multipliers": [0.18, 0.11],
        "reference_tolerance": {"worst_nat": 2e-4, "mean_nat": 2e-5, "median_nat": 2e-5},
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


# ---------------------------------------------------------------------------
# the recurrence: the chunked dual form and the kernel against the definition
# ---------------------------------------------------------------------------


def _operands(key, lead, H=4, G=2, N=32, P=16, dtype=jnp.bfloat16):
    k = jax.random.split(key, 6)
    x = jax.random.normal(k[0], (*lead, H, P)).astype(dtype)
    B = jax.random.normal(k[1], (*lead, G, N)).astype(dtype)
    C = jax.random.normal(k[2], (*lead, G, N)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[3], (*lead, H)))
    A = -jnp.exp(jax.random.normal(k[4], (H,)))
    S = jax.random.normal(k[5], (H, N, P), jnp.float32)
    return S, x, B, C, dt, A, jnp.ones((H,))


@pytest.mark.parametrize("T,chunk,identity_from", [(21, 8, 17), (16, 8, 16), (8, 8, 3), (5, 16, 5)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(T, chunk, identity_from):
    """Also past the run's real tokens, where a step size of 0 has to be the
    identity, and over a run that is not whole chunks."""
    S, x, B, C, dt, A, D = _operands(jax.random.PRNGKey(T), (T,))
    dt = dt.at[identity_from:].set(0.0)
    y, S_end = ps.ssm_scan(S, x, B, C, dt, A, D, chunk=chunk)

    def token(s, inp):
        s, y_t = ps.ssm_state_update_reference(
            s, *(v[None] for v in inp), A, D, jnp.ones((1,), bool))
        return s, (y_t[0], s[0])

    _, (ys, states) = jax.lax.scan(token, S[None], (x, B, C, dt))
    np.testing.assert_allclose(np.asarray(S_end), np.asarray(states[-1]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ys, np.float32),
                               atol=2e-2, rtol=2e-2)
    if identity_from < T:  # the padding changed nothing
        np.testing.assert_array_equal(np.asarray(states[-1]), np.asarray(states[identity_from - 1]))


@pytest.mark.parametrize("live", [
    [1, 0, 1, 1, 0, 1], [0] * 6, [1] * 6, [0, 0, 0, 0, 1, 0], [0, 1, 1, 0, 0, 0],
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_interpreted_kernel_is_its_twin_and_dead_rows_keep_their_state(live, dtype):
    R = len(live)
    S0, x, B, C, dt, A, D = _operands(jax.random.PRNGKey(7), (R,), dtype=dtype)
    S = jnp.stack([S0 * (i + 1) for i in range(R)])
    live = jnp.asarray(live, bool)
    S_ref, y_ref = ps.ssm_state_update_reference(S, x, B, C, dt, A, D, live)
    S_k, y_k = ps.ssm_state_update(S + 0, x, B, C, dt, A, D, live, interpret=True, head_block=2)
    np.testing.assert_allclose(np.asarray(S_k), np.asarray(S_ref), atol=1e-5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(y_k, np.float32), np.asarray(y_ref, np.float32),
                               atol=1e-5, rtol=1e-2)
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(S_k)[dead], np.asarray(S)[dead])
    assert not np.asarray(y_k, np.float32)[dead].any()


def test_the_kernel_refuses_a_head_block_that_straddles_groups():
    S0, x, B, C, dt, A, D = _operands(jax.random.PRNGKey(1), (2,), H=6, G=2)
    with pytest.raises(ValueError, match="do not cut into blocks"):
        ps.ssm_state_update(jnp.stack([S0, S0]), x, B, C, dt, A, D, jnp.ones((2,), bool),
                            interpret=True, head_block=2)


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
    """Three chunks (state and tail carried, the last chunk's padding the
    identity), then a horizon of decode steps, against one plain forward."""
    recs = served["recs"]
    samples = [sample(served["b"], recs["b1"]), sample(served["a"], recs["a"])]
    res = ref.compare(served["cfg"], adapter.reference_params(served["engine"]), samples, 64)
    assert res["ok"], res
    assert res["tokens_compared"] == 24 and res["worst_argmax_gap_nat"] == 0.0


def test_a_mixed_step_is_its_two_halves(served):
    """B's chunks rode A's decode steps, A decoded beside them: both still
    the reference's forward (the split programs gave ``b1`` and ``a``)."""
    recs = served["recs"]
    assert any(s.phase == "mixed" and s.ssm_rows_updated and s.ssm_tokens_scanned
               for s in served["steps"])
    samples = [sample(served["b"], recs["b-c"]), sample(served["a"], recs["a-c"])]
    res = ref.compare(served["cfg"], adapter.reference_params(served["engine"]), samples, 64)
    assert res["ok"], res
    assert recs["b-c"]["tokens"] == recs["b1"]["tokens"]
    assert recs["a-c"]["tokens"][:12] == recs["a"]["tokens"]


def test_a_reused_slot_does_not_remember_who_held_it(served):
    recs = served["recs"]
    assert recs["b2"]["tokens"] == recs["b1"]["tokens"]
    assert recs["b2"]["logprobs"] == recs["b1"]["logprobs"]


def test_a_repeated_prompt_takes_no_prefix_hit(served):
    """A block hash restores pages and no state: the family declines."""
    assert not registry.prefix_reusable(served["engine"].mcfg)
    assert registry.prefix_reusable(LlamaConfig.tiny())
    assert served["recs"]["b2"]["cached_tokens"] == 0
    assert served["engine"].allocator.cached_blocks == 0


def test_the_step_counters_are_what_the_batch_implies(served):
    steps, engine = served["steps"], served["engine"]
    per_slot = engine.state.bytes_per_slot
    # 4 x 32 x 16 float32 + 3 x 128 float32 lanes, two layers
    assert per_slot == L * (4 * 32 * 16 * 4 + 3 * (64 + 2 * 64) * 4)
    prompt_tokens = 20 + 33 + 33 + 20 + 33
    assert sum(s.ssm_tokens_scanned for s in steps) == L * prompt_tokens
    for s in steps:
        assert s.ssm_state_bytes == s.batch_occupancy * per_slot
        if s.phase == "prefill":
            assert (s.ssm_rows_updated, s.ssm_decode_steps) == (0, 0)
            assert s.ssm_tokens_scanned == L * s.tokens
        elif s.phase == "mixed":
            assert s.ssm_decode_steps == 1 and s.ssm_rows_updated == L  # one resident row
        else:
            assert s.ssm_decode_steps in (1, 8)
            # a row of a horizon stops at what its request asked for
            assert s.ssm_rows_updated == L * s.tokens <= L * 2 * s.ssm_decode_steps
    # every emitted token but a request's first came from a decode row, and
    # no row advanced its slot past its request's last token
    emitted = sum(len(r["tokens"]) - 1 for r in served["recs"].values())
    assert sum(s.ssm_rows_updated for s in steps) == L * emitted


def test_debug_worker_shows_the_second_kind_of_state(served):
    from dynamo_tpu.engine.telemetry import EngineTelemetry
    from dynamo_tpu.runtime import metrics as M

    tele = EngineTelemetry(M.MetricsScope())
    for s in served["steps"]:
        tele.on_step(s)
    ssm = tele.snapshot()["ssm"]
    assert ssm["rows_updated"] > 0 and ssm["tokens_scanned"] > 0
    assert ssm["state_bytes"] == served["steps"][-1].ssm_state_bytes
    snap = served["engine"].snapshot()["slot_state"]
    assert snap == {"bytes_per_slot": served["engine"].state.bytes_per_slot,
                    "bytes": 2 * served["engine"].state.bytes_per_slot, "slots": 2}


async def test_a_horizon_of_8_is_8_single_steps_and_the_interpreted_kernel_serves(served):
    """The same weights, stepped one token a dispatch (``decode_steps`` 1)
    with the Pallas side on (interpreted: both attention kernels at 5 query
    heads a kv head, and ``ssm_state_update``), no mixed steps."""
    engine = engine_of(served["cfg"], decode_steps=1, use_pallas=True, mixed_admission=False)
    try:
        assert engine.kernels_interpreted
        rec = await system.generate(engine, "b", served["b"], 12)
    finally:
        engine.stop()
    assert rec["tokens"] == served["recs"]["b1"]["tokens"]
    np.testing.assert_allclose(rec["logprobs"], served["recs"]["b1"]["logprobs"], atol=2e-5)


# ---------------------------------------------------------------------------
# what the engine HOLDS when a request ends, against what the reference would
# ---------------------------------------------------------------------------

HELD_LIMITS = {"worst_nat": 2e-4, "mean_nat": 2e-5, "median_nat": 2e-5,
               "slow_state_rel": 1e-4, "first_cache_rel": 1e-4}


@pytest.fixture(scope="module")
def held():
    """Horizons of 8, two in flight, four slots: four requests at once that
    ask for 12, 19, 30 and 9 tokens (so each ends INSIDE a horizon, and the
    host learns it up to two horizons late), compared as they stand; then
    one request alone, whose pages nobody can have taken since."""
    cfg = file_cfg(reference_tolerance=HELD_LIMITS)
    engine = engine_of(cfg, max_batch_size=4, decode_pipeline=2, num_blocks=128)
    prompts, asks = prompts_of(20, 33, 41, 17, seed=5), (12, 19, 30, 9)
    out = {"cfg": cfg, "engine": engine}

    async def run():
        recs = await asyncio.gather(*[
            system.generate(engine, f"r{i}", p, n) for i, (p, n) in enumerate(zip(prompts, asks))])
        out["together"] = [sample(p, r) for p, r in zip(prompts, recs)]
        out["together_res"] = ref.compare(cfg, adapter.reference_params(engine), out["together"], 128)
        out["alone"] = [sample(prompts[2], await system.generate(engine, "alone", prompts[2], 30))]
        out["params"] = adapter.reference_params(engine)
        out["alone_res"] = ref.compare(cfg, out["params"], out["alone"], 128)

    try:
        asyncio.run(run())
    finally:
        engine.stop()
    return out


def test_a_finished_slot_holds_the_state_after_its_last_fed_token(held):
    """A horizon runs on after a row has sampled what its request asked for;
    the row's recurrence does not (``decode_multi``'s ``max_new``): every
    slot's state is the reference scan's after prompt + emitted - 1 tokens,
    for every layer and head."""
    res = held["together_res"]
    assert res["tokens_compared"] == 12 + 19 + 30 + 9
    assert res["worst_state_difference"] < 1e-5, res
    assert len(res["slowest_head_state_difference_by_layer"]) == L
    assert res["slowest_head_state_difference"] <= res["worst_state_difference"]


def test_the_pages_of_a_request_that_ended_are_what_the_reference_would_cache(held):
    res = held["alone_res"]
    assert res["ok"], res
    assert max(res["cache_difference_by_layer"]) < 1e-5 and res["worst_state_difference"] < 1e-5


@pytest.mark.parametrize("switch,reading,limit", [
    ({"state_bits": 16}, "slowest_head_state_difference", "slow_state_rel"),
    ({"cache_bits": 8}, "first_layer_cache_difference", "first_cache_rel"),
])
def test_a_lower_precision_of_what_is_held_fails_its_own_limit(held, switch, reading, limit):
    """The state rounded to bf16 a token, the pages as 8 bits would return
    them: each moves the number that reads what is HELD far over its limit,
    whatever it does to a logprob."""
    res = ref.compare(held["cfg"], held["params"], held["alone"], 128, **switch)
    assert not res["ok"] and res[reading] > 10 * HELD_LIMITS[limit], res


def test_a_limit_on_what_is_held_fails_when_nothing_held_is_handed_over(held):
    params = {k: v for k, v in held["params"].items() if k != "held"}
    res = ref.compare(held["cfg"], params, held["alone"], 128)
    assert not res["ok"] and "worst_state_difference" not in res
    assert res["mean_logprob_difference_nat"] <= HELD_LIMITS["mean_nat"]


# ---------------------------------------------------------------------------
# the reference's switches, the refusals, the other families
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def scored(served):
    params = adapter.reference_params(served["engine"])
    seq = served["a"] + served["recs"]["a"]["tokens"]
    rows = list(range(19, len(seq) - 1))
    return params, seq, rows, ref.logprobs(served["cfg"], params, seq, rows)


@pytest.mark.parametrize("name", ref.MULTIPLIERS)
def test_each_multiplier_dropped_moves_the_reference(served, scored, name):
    """The init makes every multiplier visible (models/falcon_h1.py)."""
    params, seq, rows, honest = scored
    wrong = ref.logprobs(served["cfg"], params, seq, rows, drop=name)
    assert np.abs(wrong - honest).mean() > 0.02


@pytest.mark.parametrize("switch", [
    {"state_bits": 16}, {"state_bits": 8}, {"cache_bits": 8}, {"skip_layer": 1},
    {"norm_before_gate": True}, {"no_D": True}, {"no_conv_bias": True}, {"groups_as_one": True},
])
def test_each_switch_computes_another_result(served, scored, switch):
    params, seq, rows, honest = scored
    wrong = ref.logprobs(served["cfg"], params, seq, rows, **switch)
    least = {16: 1e-5, 8: 1e-3}.get(switch.get("state_bits"), 1e-3 if "cache_bits" in switch else 0.02)
    assert np.abs(wrong - honest).mean() > least


def test_calibrate_runs_every_switch_by_name(served):
    assert set(ref.wrong_variants(served["cfg"])) == (
        {"cache_int8", "state_bf16", "state_fp8", "cache_int8_state_fp8", "norm_before_gate",
         "no_D", "no_conv_bias", "groups_as_one"}
        | {f"drop_{m}" for m in ref.MULTIPLIERS})


REFUSED = {
    "tp": (dict(tp=2), {}, "tp > 1"),
    "pp": (dict(pp=2), {}, "falcon-h1"),
    "sp": (dict(sp=2), {}, "pp / sp > 1"),
    "draft": (dict(spec_draft=LlamaConfig.tiny(vocab_size=512)), {}, "speculative draft"),
    "lora": (dict(lora_max_adapters=2), {}, "LoRA"),
    "int8": (dict(kv_dtype="int8"), {}, "kv_dtype=int8"),
    "vision": (dict(vision=object()), {}, "vision"),
    "kvbm": ({}, dict(kvbm=object()), "KVBM offload"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_slot_state_cannot_do_yet_is_refused_at_construction(what):
    cfg_kw, ctor_kw, why = REFUSED[what]
    ecfg = TpuEngineConfig(
        model=adapter.model_config(file_cfg()), num_blocks=16, block_size=8, max_batch_size=2,
        max_context=64, prefill_buckets=(16,), decode_steps=8, decode_pipeline=1, **cfg_kw)
    with pytest.raises(ValueError, match=why):
        TpuEngine(ecfg, **ctor_kw)


async def test_the_transfer_plane_is_refused_where_it_is_asked_for(served):
    with pytest.raises(ValueError, match="KV transfer plane"):
        await served["engine"].serve_transfer()
    with pytest.raises(ValueError, match="KV transfer plane"):
        served["engine"]._get_transfer_client()


def _program_leaves(engine):
    """(inputs, outputs) of ``decode_multi`` and ``mixed_step`` as the loop
    calls them, in leaves, traced and not compiled."""
    c, e = engine.cfg, engine
    B, S = c.max_batch_size, c.prefill_buckets[0]
    sampling = (e._seeds, e._temps, e._top_ks, e._top_ps, e._min_ps, e._pres, e._freqs, e._reps)
    tail = (e.prompt_masks, {}, e._lora_slots, e._lp_masks)
    head = (e.params, e.k_caches, e.v_caches, e.output_counts)
    state = () if e.state is None else (e.state.arrays,)
    multi = head + state + (
        e._tokens, e._seq_lens, e._block_tables, np.zeros(B, bool), sampling[0],
        np.zeros(B, np.int32), *sampling[1:], tail[0], np.bool_(False), *tail[1:])
    # a family with slot state also hands a horizon what each row was asked for
    quota = {} if e.state is None else {"max_new": e._max_new}
    step = step_args.pack(
        B, c.max_blocks_per_seq, table_row=e._block_tables[0], total_len=S, chunk_start=0, slot=0,
        is_final=True, c_lp_need=False, lp_need=False, c_g_state=0, tokens=e._tokens,
        positions=e._seq_lens, seq_lens=e._seq_lens, write_blocks=e._seq_lens,
        write_offsets=e._seq_lens, steps=e._seq_lens)
    mixed = head + state + (
        np.zeros(S, np.int32), np.zeros(S, np.int32), np.zeros(S // c.block_size, np.int32),
        step, np.zeros(B, np.int32), e._block_tables, *sampling, *tail)
    out = {}
    for name, args, kw in (("_decode_multi_fn", multi, quota), ("_mixed_fn", mixed, {})):
        fn = getattr(e, name)
        fn = getattr(fn, "jitted", fn)  # this family: the program under the hand-over
        shapes = jax.eval_shape(fn, *args, **kw)
        out[name] = (len(jax.tree_util.tree_leaves((args, kw))), len(jax.tree_util.tree_leaves(shapes)))
    return out


@pytest.mark.parametrize("family", ["llama", "mla"])
def test_the_other_families_programs_take_and_return_what_they_did(family):
    """A family without slot state gets the jitted program itself, with the
    arguments and results it had: 2 x layers of pages + counts in front, no
    state behind them."""
    model = LlamaConfig.tiny() if family == "llama" else MlaConfig.tiny_mla()
    engine = TpuEngine(TpuEngineConfig(
        model=model, num_blocks=16, block_size=16, max_batch_size=2, max_context=64,
        prefill_buckets=(16,), decode_steps=8, decode_pipeline=1, use_pallas=False,
        mixed_admission=True))
    try:
        assert engine.state is None and registry.state_spec(model) == ()
        assert hasattr(engine._decode_multi_fn, "lower") and hasattr(engine._mixed_fn, "lower")
        n_params = len(jax.tree_util.tree_leaves(engine.params))
        pages = 2 * model.num_layers
        got = _program_leaves(engine)
        assert got["_decode_multi_fn"] == (n_params + pages + 1 + 17, pages + 1 + 4)
        assert got["_mixed_fn"] == (n_params + pages + 1 + 17, pages + 1 + 10)
    finally:
        engine.stop()


def test_this_familys_programs_carry_the_state_behind_the_pages(served):
    engine = served["engine"]
    n_params = len(jax.tree_util.tree_leaves(engine.params))
    pages = state = 2 * L
    got = _program_leaves(engine)
    assert got["_decode_multi_fn"] == (n_params + pages + 1 + state + 17 + 1, pages + 1 + state + 4)
    assert got["_mixed_fn"] == (n_params + pages + 1 + state + 17, pages + 1 + state + 10)


def test_the_registry_knows_the_family():
    cfg = fh1.FalconH1Config.tiny()
    assert registry.is_falcon_h1(cfg) and registry.family(cfg) is fh1
    assert not registry.supports_pp(cfg)
    names = [n for n, _, _ in registry.state_spec(cfg)]
    assert names == ["ssm", "conv"]
    (_, s_shape, s_dtype), (_, c_shape, c_dtype) = registry.state_spec(cfg)
    assert s_shape == (4, 32, 16) and s_dtype == jnp.float32   # float32 whatever the model's
    assert c_shape == (3, 64 + 2 * 64) and c_dtype == cfg.dtype


def test_the_published_config_counts_its_parameters_and_state():
    """ISSUE 39's arithmetic from the program's own shapes, in the abstract."""
    from dynamo_tpu.engine.engine import _model_param_bytes

    cfg = fh1.FalconH1Config.falcon_h1_34b(num_layers=6, vocab_size=32640)
    shapes = jax.eval_shape(lambda k: fh1.init_params(k, cfg), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 2_914_958_912
    assert cfg.in_proj_size == 9248 and cfg.conv_dim == 5120
    per_slot = sum(int(np.prod(s)) * np.dtype(d).itemsize for _, s, d in fh1.state_spec(cfg))
    assert per_slot == 32 * 128 * 256 * 4 + 3 * 5120 * 2
    assert abs(_model_param_bytes(cfg) / (2 * n) - 1) < 0.001


def test_a_published_config_json_is_read_as_this_family_and_its_checkpoint_refused(tmp_path):
    import json

    from dynamo_tpu.engine import weights

    hf = {k: v for k, v in file_cfg().items() if k not in ("reference_tolerance", "torch_dtype")}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = weights.config_from_hf(str(tmp_path))
    assert registry.is_falcon_h1(cfg)
    assert cfg == adapter.model_config(file_cfg(torch_dtype="bfloat16"))
    with pytest.raises(NotImplementedError, match="no checkpoint loader for falcon_h1"):
        weights.load_params(str(tmp_path), cfg)
