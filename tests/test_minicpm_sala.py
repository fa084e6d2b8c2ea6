"""MiniCPM-SALA's hybrid of layer kinds (models/minicpm_sala.py: block-sparse
grouped-query attention over a cache of pooled keys in one layer of four,
lightning attention with a matrix state a slot in the other three) at a test's
size that keeps the shape's oddities (two periods of 4, 16 query heads a kv
head, 2 kv heads that choose differently, contexts on both sides of
``dense_len``): the recurrence's two forms, both launches against their
twins, the pooled keys whatever split wrote them, the engine against the
benchmark's plain float32 reference
(benchmarks/reference/minicpm_sala_decoder.py), the counters, the refusals.
"""

import asyncio
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import system
from benchmarks.adapters import minicpm_sala as adapter
from benchmarks.reference import minicpm_sala_decoder as ref
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models import minicpm_sala as sala
from dynamo_tpu.models import registry
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import pallas_attention as pa
from dynamo_tpu.ops import pallas_lightning as plight
from dynamo_tpu.ops.paged_attention import INFLLM_KERNEL_NAME, PagedAttention

L = 8  # layers of the tests' model: sparse, lightning x 3, twice
SIZES = {"kernel_size": 32, "kernel_stride": 16, "block_size": 32, "topk": 2,
         "init_blocks": 1, "window_size": 64, "dense_len": 128}
SPEC = att.InfLlmQuery(32, 16, 32, 2, 1, 64, 128)


def file_cfg(dtype="float32", **kw):
    """A configuration file's dict (the public keys) at a test's size."""
    cfg = {
        "model_type": "minicpm_sala", "vocab_size": 512, "hidden_size": 128,
        "num_hidden_layers": L, "num_attention_heads": 32, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 256, "hidden_act": "silu",
        "attention_bias": False, "attn_use_rope": False, "qk_norm": True,
        "lightning_head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "rope_theta": 10000, "rms_norm_eps": 1e-6, "max_position_embeddings": 2048,
        "tie_word_embeddings": False, "torch_dtype": dtype,
        "mixer_types": ["minicpm4"] + ["lightning-attn"] * 3 + ["minicpm4"] + ["lightning-attn"] * 3,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 32,
        "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True,
        "assumed_sizes": dict(SIZES),
        "reference_tolerance": {"worst_nat": 5e-4, "mean_nat": 5e-5, "median_nat": 5e-5,
                                "state_rel": 2e-4, "state_precision_gap": 0.5,
                                "first_cache_rel": 1e-5, "pooled_key_rel": 1e-5,
                                "block_overlap_min": 0.99},
    }
    cfg.update(kw)
    return cfg


def engine_of(cfg=None, **kw):
    opts = dict(num_blocks=96, block_size=16, max_batch_size=2, max_context=512,
                prefill_buckets=(64,), seed=3, use_pallas=False, decode_steps=8,
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
    assert adapter.model_config(file_cfg()) == sala.MiniCpmSalaConfig.tiny(
        dtype=jnp.float32, dim_model_base=32)
    run_from_9 = file_cfg(first_layer_run=1, mixer_types=["lightning-attn"] + file_cfg()["mixer_types"])
    assert adapter.model_config(run_from_9).sparse_layers == (0, 4)


# ---------------------------------------------------------------------------
# lightning attention: the scan, the launch
# ---------------------------------------------------------------------------


def _operands(key, lead, H=4, d=16):
    ks = jax.random.split(key, 4)
    q, k, v = (jax.random.normal(ks[i], (*lead, H, d)) for i in range(3))
    S = jax.random.normal(ks[3], (*lead[:-1], H, d, d)) if len(lead) > 1 else jax.random.normal(ks[3], (H, d, d))
    decay = jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(H) + 1.0) / H)))
    return S, q, k, v, decay


def _token_by_token(S, q, k, v, decay):
    ys = []
    for t in range(q.shape[0]):
        S = decay[:, None, None] * S + k[t][:, :, None] * v[t][:, None, :]
        ys.append(jnp.einsum("hk,hkv->hv", q[t], S))
    return jnp.stack(ys), S


@pytest.mark.parametrize("T,block,identity_from", [(70, 16, 70), (64, 32, 41), (20, 128, 20)])
def test_the_blocked_scan_is_the_token_by_token_recurrence(T, block, identity_from):
    S, q, k, v, decay = _operands(jax.random.PRNGKey(T), (T,))
    y, S1 = plight.lightning_scan(S, q, k, v, jnp.log(decay), identity_from, block=block)
    want_y, want_S = _token_by_token(S, q[:identity_from], k[:identity_from],
                                     v[:identity_from], decay)
    np.testing.assert_allclose(y[:identity_from], want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S1, want_S, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("live", [[True, False, True, True], [False] * 4, [True] * 4])
def test_the_interpreted_launch_is_its_twin_and_dead_rows_keep_their_state(live):
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(kk, (4, 4, 16)) for kk in jax.random.split(key, 3))
    S = jax.random.normal(jax.random.PRNGKey(8), (4, 4, 16, 16))
    decay = sala.decays(sala.MiniCpmSalaConfig.tiny())
    live = jnp.asarray(live)
    want_S, want_y = plight.lightning_state_update_reference(S, q, k, v, decay, live)
    got_S, got_y = plight.lightning_state_update(S + 0.0, q, k, v, decay, live, interpret=True)
    np.testing.assert_allclose(got_S, want_S, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got_S[~live], S[~live])


def test_the_launch_carries_its_own_name_and_the_delta_rules_keeps_its():
    from dynamo_tpu.ops import pallas_kda as pk

    assert plight.KERNEL_NAME == "lightning_state_update" != pk.KERNEL_NAME == "kda_state_update"
    # the delta rule's launch is the same skeleton WITH its correction
    _, q, k, v, decay = _operands(jax.random.PRNGKey(1), (2,))
    S = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 16, 16))
    alpha = jnp.broadcast_to(decay[None, :, None], q.shape)
    live = jnp.ones(2, bool)
    want = pk.kda_state_update_reference(S, q, k, v, alpha, jnp.ones((2, 4)), live)
    got = pk.kda_state_update(S + 0.0, q, k, v, alpha, jnp.ones((2, 4)), live, interpret=True)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    plain = plight.lightning_state_update_reference(S, q, k, v, decay, live)
    assert float(jnp.abs(plain[0] - want[0]).max()) > 0.1


# ---------------------------------------------------------------------------
# the pooled keys and the selection
# ---------------------------------------------------------------------------


def _pool(pages=24, bs=16, kvh=2, d=16, seed=0):
    """A K pool of ``pages`` request pages + the pooled keys' rows."""
    total = pages + att.infllm_pool_pages(pages, bs)
    return jnp.zeros((total, bs, kvh, d), jnp.float32), pages


def _pooled_truth(k, n):
    J = (n - 32) // 16 + 1
    return np.stack([np.asarray(k[16 * j:16 * j + 32]).mean(0) for j in range(J)])


@pytest.mark.parametrize("split", [(200,), (64, 136), (64, 64, 72), (128, 16, 56)])
def test_pooled_keys_are_the_same_whatever_chunks_wrote_them(split):
    """A prompt of 200 keys written in one chunk or several (every chunk but
    the last a whole number of pages): key j = mean(k[16j .. 16j+31]) under
    page j's block id, the key that straddles two chunks included."""
    n = sum(split)
    k = jax.random.normal(jax.random.PRNGKey(0), (n, 2, 16))
    kc, base = _pool()
    table = jnp.asarray([5, 9, 2, 7, 11, 3, 14, 1, 20, 17, 8, 6, 13, 0, 0, 0], jnp.int32)
    start = 0
    for m in split:
        pad = -m % 16
        k_new = jnp.pad(k[start:start + m], ((0, pad), (0, 0), (0, 0)), constant_values=9.0)
        ids = jax.lax.dynamic_slice(table, (start // 16,), ((m + pad) // 16,))
        kc, _ = att.write_prefill_kv(kc, kc, k_new, k_new, ids)
        kc = att.infllm_pool_chunk(kc, k_new, table, start, start + m, SPEC, base)
        start += m
    got = att.infllm_pooled_keys(kc, table[None], base)[0]
    want = _pooled_truth(k, n)
    np.testing.assert_allclose(got[:len(want)], want, rtol=1e-6, atol=1e-6)
    assert len(want) == 11 and not np.any(np.asarray(got[len(want):12]))   # not final: not written


def test_decode_writes_the_pooled_key_when_the_next_page_fills():
    """Tokens written one a step: the key of page j is written by the step
    that fills page j + 1, never before, and is the prefill's."""
    k = jax.random.normal(jax.random.PRNGKey(1), (80, 2, 16))
    kc, base = _pool()
    tables = jnp.asarray([[4, 10, 6, 12, 3, 0], [0] * 6], jnp.int32)
    written = []
    for p in range(80):
        blocks = jnp.asarray([tables[0, p // 16], 0])
        offs = jnp.asarray([p % 16, 0])
        kc, _ = att.write_decode_kv(kc, kc, jnp.stack([k[p], k[p]]), jnp.stack([k[p], k[p]]), blocks, offs)
        kc = att.infllm_pool_rows(kc, tables, jnp.asarray([p + 1, 0]), blocks, offs, SPEC, base)
        written.append(int(np.count_nonzero(np.abs(np.asarray(
            att.infllm_pooled_keys(kc, tables[:1], base)[0, :5])).sum((1, 2)))))
    assert written[30] == 0 and written[31] == 1 and written[46] == 1 and written[47] == 2
    got = att.infllm_pooled_keys(kc, tables[:1], base)[0]
    np.testing.assert_allclose(got[:4], _pooled_truth(k, 80), rtol=1e-6, atol=1e-6)


def _served_rows(n_keys, seed=0, kvh=2, h=32, d=16, mb=20):
    """Decode rows over a pool filled by one prefill each."""
    rng = jax.random.PRNGKey(seed)
    kc, base = _pool(pages=64)
    vc = kc
    R = len(n_keys)
    tables = np.zeros((R, mb), np.int32)
    nxt = 1
    ks, vs = [], []
    for r, n in enumerate(n_keys):
        rng, a, b = jax.random.split(rng, 3)
        pages = -(-n // 16)
        k = 1.5 * jax.random.normal(a, (pages * 16, kvh, d))
        v = jax.random.normal(b, (pages * 16, kvh, d))
        tables[r, :pages] = np.arange(nxt, nxt + pages)[::-1] if r % 2 else np.arange(nxt, nxt + pages)
        nxt += pages
        kc, vc = att.write_prefill_kv(kc, vc, k, v, jnp.asarray(tables[r, :pages]))
        kc = att.infllm_pool_chunk(kc, k, jnp.asarray(tables[r]), 0, n, SPEC, base)
        ks.append(k[:n]), vs.append(v[:n])
    q = 2.0 * jax.random.normal(rng, (R, h, d))
    return q, kc, vc, jnp.asarray(tables), jnp.asarray(n_keys, jnp.int32), base, ks, vs


def test_decode_rows_attend_their_chosen_blocks_and_two_kv_heads_choose_differently():
    """Rows on both sides of dense_len (and an empty one) against a direct
    computation from the row's own keys: the stateless twin's last query."""
    n_keys = [300, 128, 0, 129, 257]
    q, kc, vc, tables, lens, base, ks, vs = _served_rows(n_keys)
    got = att.infllm_paged_decode_attention(q, kc, vc, tables, lens, SPEC, base)
    differ = 0
    for r, n in enumerate(n_keys):
        if not n:
            assert not np.any(np.asarray(got[r]))
            continue
        qs = jnp.zeros((n, 32, 16)).at[-1].set(q[r])
        want = att.infllm_attention(qs, ks[r], vs[r], SPEC)[-1]
        np.testing.assert_allclose(got[r], want, rtol=2e-5, atol=2e-5)
        if n > SPEC.dense_len:
            blocks, count = att.infllm_select(
                q[r:r + 1], att.infllm_pooled_keys(kc, tables[r:r + 1], base), lens[r:r + 1], SPEC)
            assert int(count[0, 0]) == min(-(-n // 32), 6)
            differ += int(np.any(np.asarray(blocks[0, 0]) != np.asarray(blocks[0, 1])))
            # ascending, the query's own block last, forced blocks in
            chosen = np.asarray(blocks[0, 0][: int(count[0, 0])])
            assert np.all(np.diff(chosen) > 0) and chosen[0] == 0 and chosen[-1] == (n - 1) // 32
    assert differ >= 1


def test_a_row_past_dense_len_does_not_read_every_key():
    """What the launch is handed: views of the chosen pages only."""
    q, kc, vc, tables, lens, base, _, _ = _served_rows([300, 100])
    qv, view, vlens = att.infllm_decode_rows(q, kc, tables, lens, SPEC, base)
    assert qv.shape[0] == 4 and view.shape == (4, att.infllm_view_width(SPEC, 16, 20))
    assert list(np.asarray(vlens)) == [5 * 32 + 12, 5 * 32 + 12, 100, 100]


@pytest.mark.parametrize("topk, per_kv_head", [(2, [5 * 32 + 12, 100, 0]), (64, [300, 100, 0])])
def test_what_is_counted_as_selected_is_what_the_launch_was_handed(topk, per_kv_head):
    """``infllm_keys_selected`` is the handed views' lengths over the step's
    real decode rows, not a formula of the contexts: a selection that hands
    the launch every block reads every causal key (a share of 100)."""
    from dynamo_tpu.models.moe import RoutingStats

    n_keys = [300, 100, 0]
    q, kc, vc, tables, lens, base, _, _ = _served_rows(n_keys)
    spec = dataclasses.replace(SPEC, topk=topk, handed=[])
    att.infllm_decode_rows(q, kc, tables, lens, spec, base)
    (handed,) = spec.handed
    assert np.asarray(handed).tolist() == [[n, n] for n in per_kv_head]
    cfg = adapter.model_config(file_cfg())
    stats = RoutingStats(valid=lens > 0)
    sala._count_selection(cfg, stats, lens - 1, [handed, handed])
    reads = dict(zip(stats.read_names, np.asarray(stats.reads).tolist()))
    assert reads["infllm_keys_selected"] == 2 * sum(per_kv_head)
    assert reads["infllm_keys_causal"] == 2 * 400 and reads["infllm_rows_sparse"] == 2
    # a row that is no real decode row (a horizon's tail) is not counted
    stats = RoutingStats(valid=lens > 0, decode_rows=jnp.asarray([False, True, False]))
    sala._count_selection(cfg, stats, lens - 1, [handed])
    assert int(stats.reads[0]) == 100


@pytest.mark.parametrize("use_pallas", [False, True])
def test_the_seams_launch_is_its_twin_and_carries_its_own_name(use_pallas):
    from dynamo_tpu.parallel import mesh as meshlib

    q, kc, vc, tables, lens, base, _, _ = _served_rows([300, 128, 0, 257], seed=2)
    mesh = meshlib.make_mesh(tp=1) if hasattr(meshlib, "make_mesh") else None
    seam = PagedAttention(mesh, use_pallas, interpret=True, summary_base=base)
    got = seam.decode(q, kc, vc, tables, lens, infllm=SPEC)
    want = att.infllm_paged_decode_attention(q, kc, vc, tables, lens, SPEC, base)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert INFLLM_KERNEL_NAME == "infllm_decode_attention" != pa.KERNEL_NAME


def test_a_chunk_under_its_mask_is_the_stateless_twin():
    """A chunk at the tail of a context past dense_len, its queries on both
    sides of it, over a table in another order."""
    n, start = 200, 64
    q, kc, vc, tables, lens, base, ks, vs = _served_rows([n], seed=3)
    qs = 2.0 * jax.random.normal(jax.random.PRNGKey(9), (n, 32, 16))
    want = att.infllm_attention(qs, ks[0], vs[0], SPEC)
    S_pad = 192
    qc = jnp.pad(qs[start:], ((0, S_pad - (n - start)), (0, 0), (0, 0)))
    got = att.infllm_chunk_attention(
        qc, kc, vc, tables[0], start + jnp.arange(S_pad), jnp.asarray(n), SPEC, base)
    np.testing.assert_allclose(got[: n - start], want[start:], rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the engine against the plain reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """The float32 engine (mixed steps, horizons of 8, 2 slots, one bucket of
    64) through ``generate``: B (past dense_len from its prompt on) alone in
    a fresh slot; A (crosses dense_len while decoding) alone; B again in the
    slot A left; A and B at once (B's chunks ride A's decode steps)."""
    cfg = file_cfg()
    engine = engine_of(cfg)
    steps = []
    engine.stats_hook = steps.append
    a, b = prompts_of(118, 309)   # b: five chunks, the last 53 real tokens

    async def run():
        out = {}
        out["b1"] = await system.generate(engine, "b1", b, 24)
        out["a"] = await system.generate(engine, "a", a, 24)
        out["b2"] = await system.generate(engine, "b2", b, 24)
        started = asyncio.Event()
        ta = asyncio.ensure_future(system.generate(
            engine, "a-c", a, 40, on_chunk=lambda *_: started.set()))
        await started.wait()
        out["b-c"] = await system.generate(engine, "b-c", b, 24)
        out["a-c"] = await ta
        return out

    try:
        recs = asyncio.run(run())
    finally:
        engine.stop()
    return {"cfg": cfg, "engine": engine, "recs": recs, "steps": steps, "a": a, "b": b}


def test_chunked_prefill_then_decode_through_pages_pool_and_state_is_the_references_forward(served):
    """Five chunks (the state carried across chunk boundaries, pooled keys
    across them, the last chunk's queries under their mask), then horizons
    of decode steps over chosen pages, against one plain forward whose
    lightning layers are the recurrence: logits, not tokens; contexts on
    both sides of dense_len (A starts at 118 and crosses 128 while decoding)."""
    recs = served["recs"]
    samples = [sample(served["b"], recs["b1"]), sample(served["a"], recs["a"])]
    res = ref.compare(served["cfg"], adapter.reference_params(served["engine"]), samples, 256)
    assert res["tokens_compared"] == 48 and res["worst_argmax_gap_nat"] == 0.0, res
    assert max(res["worst_logprob_difference_nat"], res["mean_logprob_difference_nat"]) < 5e-4, res


def test_a_mixed_step_is_its_two_halves_and_what_is_held_is_the_references(served):
    recs = served["recs"]
    assert any(s.phase == "mixed" and s.lightning_rows_updated and s.lightning_tokens_scanned
               for s in served["steps"])
    samples = [sample(served["b"], recs["b-c"]), sample(served["a"], recs["a-c"])]
    res = ref.compare(served["cfg"], adapter.reference_params(served["engine"]), samples, 256)
    assert res["ok"], res
    assert len(res["state_difference_by_layer"]) == 6
    assert res["block_overlap_worst"] == 1.0 and res["kv_heads_choose_differently"], res
    assert res["pooled_keys_compared"] > 20
    assert recs["b-c"]["tokens"] == recs["b1"]["tokens"]
    assert recs["a-c"]["tokens"][:24] == recs["a"]["tokens"]


@pytest.mark.parametrize("wrong,fails", [
    ({"dense_attention": True}, "block_overlap_worst"),
    ({"pool_kernel": 16}, "pooled_key_difference"),
    ({"forced_inside_topk": True}, "block_overlap_worst"),
    ({"depth_held": True}, "mean_logprob_difference_nat"),
    ({"state_bits": 16}, "held_state_precision_gap"),
    ({"no_decay": True}, "first_state_difference"),
    ({"cache_bits": 8}, "first_layer_cache_difference"),
])
def test_each_named_wrong_computation_fails_a_limit(served, wrong, fails):
    recs = served["recs"]
    samples = [sample(served["b"], recs["b-c"]), sample(served["a"], recs["a-c"])]
    params = adapter.reference_params(served["engine"])
    res = ref.compare(served["cfg"], params, samples, 256, **wrong)
    assert not res["ok"], res
    limit = next(l for l, _, names, _ in ref.LIMITS if fails in names)
    only = dict(served["cfg"], reference_tolerance={limit: served["cfg"]["reference_tolerance"][limit]})
    assert not ref.compare(only, params, samples, 256, **wrong)["ok"]
    assert ref.compare(only, params, samples, 256)["ok"]


def test_a_reused_slot_does_not_remember_who_held_it(served):
    recs = served["recs"]
    assert recs["b2"]["tokens"] == recs["b1"]["tokens"]
    assert recs["b2"]["logprobs"] == recs["b1"]["logprobs"]


def test_a_repeated_prompt_takes_no_prefix_hit(served):
    assert not registry.prefix_reusable(served["engine"].mcfg)
    assert served["recs"]["b2"]["cached_tokens"] == 0
    assert served["engine"].allocator.cached_blocks == 0


def test_the_steps_count_the_recurrence_and_the_selection(served):
    """``lightning_*`` over the 6 layers that keep state; ``infllm_*`` over
    the 2 sparse layers, on every step with a readback."""
    steps = served["steps"]
    per_slot = served["engine"].state.bytes_per_slot
    assert per_slot == 6 * 4 * 16 * 16 * 4
    SL, PL = 6, 2
    assert sum(s.lightning_tokens_scanned for s in steps) == SL * (3 * 309 + 2 * 118)
    emitted = sum(len(r["tokens"]) - 1 for r in served["recs"].values())
    assert sum(s.lightning_rows_updated for s in steps) == SL * emitted
    assert all(s.state_prefix == "lightning" and s.kda_rows_updated is None
               and s.ssm_rows_updated is None for s in steps)
    counted = [s for s in steps if s.infllm_keys_causal is not None]
    assert counted and all(s.phase != "prefill" for s in counted)
    assert all(s.infllm_keys_selected <= s.infllm_keys_causal for s in counted)
    # b alone, a whole horizon of 8 steps: consecutive contexts, 6 blocks
    # chosen (block 0, the window's 3, the best 2) of 10 or 11
    lone = [s for s in counted if s.phase == "decode" and s.lightning_rows_updated == SL * 8
            and s.infllm_rows_sparse == PL * 8]
    assert any(s.infllm_keys_selected < s.infllm_keys_causal for s in lone)
    for s in lone:
        n0 = (s.infllm_keys_causal // PL - 28) // 8
        assert s.infllm_keys_causal == PL * sum(range(n0, n0 + 8))
        if n0 < 300:     # a: five blocks in all, every one chosen
            assert s.infllm_keys_selected == s.infllm_keys_causal
            continue
        assert s.infllm_keys_selected == PL * sum(
            5 * 32 + (n - 1) % 32 + 1 for n in range(n0, n0 + 8))
    # every pooled key a token of a counted step made final, a kv head
    assert sum(s.infllm_pooled_keys_written for s in counted) % (PL * 2) == 0
    assert sum(s.infllm_pooled_keys_written for s in counted) > 0


def test_debug_worker_shows_the_state_under_the_familys_prefix_and_the_selection(served):
    from dynamo_tpu.engine.telemetry import EngineTelemetry
    from dynamo_tpu.runtime import metrics as M

    tele = EngineTelemetry(M.MetricsScope())
    for s in served["steps"]:
        tele.on_step(s)
    snap = tele.snapshot()
    assert "ssm" not in snap and "kda" not in snap
    assert snap["lightning"]["rows_updated"] > 0 and snap["lightning"]["tokens_scanned"] > 0
    assert snap["lightning"]["state_bytes"] == served["steps"][-1].ssm_state_bytes
    assert 0 < snap["infllm"]["keys_selected"] < snap["infllm"]["keys_causal"]


@pytest.mark.parametrize("kernels", ["pure JAX", "interpreted"])
def test_single_steps_and_interpreted_kernels_give_the_same_logprobs(served, kernels):
    """More requests than slots: one waits, the loop falls back to the
    single-step ``decode``; with ``use_pallas`` the recurrence, the launch
    over the chosen pages and the dense chunk launch run interpreted."""
    a, b = served["a"], served["b"]
    engine = engine_of(served["cfg"], use_pallas=kernels == "interpreted")
    steps = []
    engine.stats_hook = steps.append

    async def run():
        return await asyncio.gather(
            system.generate(engine, "b", b, 24), system.generate(engine, "a", a, 24),
            system.generate(engine, "b'", b, 24))

    try:
        recs = asyncio.run(run())
    finally:
        engine.stop()
    assert any(s.phase == "decode" for s in steps)
    for got, want in zip(recs, ("b1", "a", "b1")):
        assert got["tokens"] == served["recs"][want]["tokens"]
        np.testing.assert_allclose(got["logprobs"], served["recs"][want]["logprobs"], atol=5e-4)


@pytest.mark.parametrize("ready", [False, True])
def test_the_single_step_is_readied_at_construction_only_when_asked(served, ready):
    """``TpuEngineConfig.ready_single_step``: ``decode`` has run over no row
    before the first request (no token, no state, no counter), and the first
    tick that finds a request waiting compiles nothing; left out, the engine
    is built without a step."""
    a, b = served["a"], served["b"]
    engine = engine_of(served["cfg"], ready_single_step=ready)
    steps = []
    engine.stats_hook = steps.append
    program = getattr(engine._decode_fn, "jitted", engine._decode_fn)
    built = program._cache_size()

    async def run():
        return await asyncio.gather(
            system.generate(engine, "b", b, 24), system.generate(engine, "a", a, 24),
            system.generate(engine, "b'", b, 24))

    try:
        assert (built > 0) == ready
        assert engine._moe_last is None and engine._state_counts == [0, 0, 0]
        assert not any(np.any(np.asarray(x)) for xs in engine.state.arrays.values() for x in xs)
        recs = asyncio.run(run())
    finally:
        engine.stop()
    assert any(s.phase == "decode" for s in steps)
    assert (program._cache_size() == built) == ready
    for got, want in zip(recs, ("b1", "a", "b1")):
        assert got["tokens"] == served["recs"][want]["tokens"]


@pytest.mark.parametrize("buckets", [(16,), (32, 128), (256,)])
def test_another_split_of_the_prompt_gives_the_same_tokens_and_pooled_keys(served, buckets):
    engine = engine_of(served["cfg"], prefill_buckets=buckets)
    b = served["b"]
    try:
        rec = asyncio.run(system.generate(engine, "b", b, 24))
    finally:
        engine.stop()
    assert rec["tokens"] == served["recs"]["b1"]["tokens"]
    np.testing.assert_allclose(rec["logprobs"], served["recs"]["b1"]["logprobs"], atol=5e-4)
    res = ref.compare(served["cfg"], adapter.reference_params(engine), [sample(b, rec)], 256)
    assert res["ok"] and res["pooled_key_difference"] < 1e-5, res


def test_pages_and_pooled_rows_for_sparse_layers_and_state_for_the_others(served):
    engine = served["engine"]
    assert registry.page_layers(engine.mcfg) == (0, 4)
    assert registry.state_layers(engine.mcfg) == (1, 2, 3, 5, 6, 7)
    assert registry.pooled_keys(engine.mcfg) and not registry.pooled_keys(
        __import__("dynamo_tpu.models.llama", fromlist=["x"]).LlamaConfig())
    assert len(engine.k_caches) == 2 and engine.k_caches[0].shape == (96 + 6, 16, 2, 16)
    assert [a.shape for a in engine.state.arrays["lightning"]] == [(2, 4, 16, 16)] * 6


def test_the_published_config_counts_its_parameters_state_and_pages():
    with open("benchmarks/configs/minicpm-sala-9b-d8.json") as f:
        cfg = json.load(f)
    m = adapter.model_config(cfg)
    assert m.sparse_layers == (0, 7) and m.num_layers == 8 and m.mup_denominator == 32
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim, m.intermediate_size,
            m.vocab_size, m.lightning_heads, m.lightning_head_dim) == (
                4096, 32, 2, 128, 16384, 73448, 32, 128)
    shapes = jax.eval_shape(lambda: registry.init_params(jax.random.PRNGKey(0), m))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 2.82e9) < 0.01e9
    (_, shape, dt), = registry.state_spec(m)
    assert shape == (32, 128, 128) and dt == jnp.float32
    assert m.selection.max_chosen * m.block_size == 6272
    assert sala.MiniCpmSalaConfig.minicpm_sala_9b().sparse_layers == (0, 9, 16, 17, 22, 29, 30, 31)


def test_the_registry_knows_the_family():
    m = sala.MiniCpmSalaConfig.tiny()
    assert registry.is_minicpm_sala(m) and registry.family(m) is sala
    assert registry.state_prefix(m) == "lightning"
    assert registry.read_counters(m)[0] == "infllm_keys_selected"
    assert not registry.supports_pp(m) and not registry.counts_routing(m)
    assert registry.page_groups(m) == (((0, 4), None),)
    with pytest.raises(ValueError, match="both kinds"):
        sala.MiniCpmSalaConfig.tiny(sparse_layers=())
    with pytest.raises(ValueError, match="two pages"):
        sala.MiniCpmSalaConfig.tiny(kernel_size=16)


@pytest.mark.parametrize("asked,match", [
    ({"tp": 2}, "tp > 1"), ({"pp": 2}, "pp / sp"), ({"sp": 2}, "pp / sp"),
    ({"spec": True}, "speculative"), ({"lora": True}, "LoRA"),
    ({"kv_quantized": True}, "int8"), ({"transfer": True}, "transfer plane"),
    ({"kvbm": True}, "offload"),
])
def test_each_refusal_says_slot_state_and_its_reason(asked, match):
    with pytest.raises(ValueError, match="slot state") as e:
        registry.check_state_supported(sala.MiniCpmSalaConfig.tiny(), **asked)
    assert match in str(e.value)


def test_the_engine_refuses_at_construction():
    with pytest.raises(ValueError, match="slot state"):
        engine_of(kv_dtype="int8")
    with pytest.raises(ValueError, match="stride is the page"):
        engine_of(block_size=8)


def test_a_published_config_json_is_read_as_this_family_and_its_checkpoint_refused(tmp_path):
    from dynamo_tpu.engine import weights

    with open("benchmarks/configs/minicpm-sala-9b-d8.json") as f:
        public = {k: v for k, v in json.load(f).items()
                  if k not in ("reduced", "assumed", "engine", "first_layer_run")}
    public["num_hidden_layers"] = 32
    (tmp_path / "config.json").write_text(json.dumps(public))
    cfg = weights.config_from_hf(str(tmp_path))
    assert cfg == sala.MiniCpmSalaConfig.minicpm_sala_9b()
    with pytest.raises(NotImplementedError, match="minicpm_sala"):
        weights.load_params(str(tmp_path), cfg)
