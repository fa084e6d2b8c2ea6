"""By hand (``python -m pytest benchmarks/tests/test_dots3_note.py -q``):
``costs_dots3.py`` against ISSUE 56's arithmetic, the configuration file
through its adapter and against the catalog's keys, the adapter's parameter
names against the reference's, the reference's switches, the traffic file's
parameters, the accepted readers' counts against this model, and the five new
readers on made-up records (a program without the counters gives ``None``,
as the parent has to). The reference against the engine at a test's size is
tier-1's (``tests/test_dots3_note.py``)."""

import importlib.util
import inspect
import json
import os
import re
import types

import pytest

from benchmarks import costs_dots3, costs_moe

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
READERS = ("windowed_latent_attention_roofline", "dots3_attention_share.tput",
           "dots3_window_keys_per_decode_row.tput", "dots3_held_experts_touched.tput",
           "dots3_chunk_mfu")
CELL = "dots3-agent-toolturns"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "dots3-note-ep8-d5.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_costs_are_the_issues_arithmetic(cfg):
    c = costs_dots3
    # a layer's attention: 144.0 M full (134.7 + the indexer's 9.37), 90.8 M sliding
    assert c.attention_params(cfg, c.FULL) == 144_048_128
    assert c.attention_params(cfg, c.SLIDING) == 90_832_896
    # the dense feed-forward 212.3 M; a sparse one HERE: router 1.31 M, the shared
    # expert 23.59 M and one routed expert in 8 of a token's 8 (32 of 256 held)
    assert c.ffn_params(cfg, 0) == 3 * 5120 * 13824
    assert c.ffn_params(cfg, 1) == 5120 * 256 + 2 * 3 * 5120 * 1536
    assert c.matrix_flops_per_token(cfg) == pytest.approx(1.934e9, rel=1e-3)
    # a key: 2 176 B in a sliding layer, 1 152 B in a full one
    assert (c.latent_row_bytes(cfg, c.SLIDING), c.latent_row_bytes(cfg, c.FULL)) == (2176, 1152)
    # a pair: 64 heads x (1088 + 1024) x 2 sliding, 128 x (576 + 512) x 2 full, 64 x 128 x 2 indexed
    assert (c.pair_flops(cfg, c.SLIDING), c.pair_flops(cfg, c.FULL), c.index_pair_flops(cfg)) == (
        270_336, 278_528, 16_384)
    # a token at 35k keys: 573 MFLOP of index scores and 570 of selected products a
    # full layer, 139 a sliding layer: 2.70 GFLOP; a 3 072-token turn 14 TFLOP
    assert c.causal_pairs(1, 34999) * c.index_pair_flops(cfg) == pytest.approx(573e6, rel=2e-3)
    assert c.selected_pairs(cfg, 1, 34999) * c.pair_flops(cfg, c.FULL) == pytest.approx(570e6, rel=2e-3)
    assert c.window_pairs(cfg, 1, 34999) * c.pair_flops(cfg, c.SLIDING) == pytest.approx(139e6, rel=3e-3)
    assert c.attention_flops(cfg, 1, 34999) == pytest.approx(2.70e9, rel=2e-3)
    assert c.chunk_flops(cfg, 3072, 32768) == pytest.approx(14.2e12, rel=1e-2)
    # the edges: a query sees min(position + 1, 513) keys, selects min(position + 1, 2048)
    assert c.window_pairs(cfg, 3, 511) == 512 + 513 + 513
    assert c.selected_pairs(cfg, 2, 2047) == 2048 + 2048
    assert c.window_row_keys(cfg, 100) == 100 and c.window_row_keys(cfg, 35000) == 513
    assert (c.layers_of_kind(cfg, c.FULL), c.layers_of_kind(cfg, c.SLIDING), c.routed_layers(cfg)) == (2, 3, 4)
    # 16 decode rows x 3 layers at full windows: 53.6 MB, 65 us of reads; a 2 048-token
    # chunk's 1.05 M pairs a layer: 284 GFLOP, 1.44 ms of products
    assert c.windowed_least_s(cfg, 16 * 3 * 513, 0, 0, PEAKS) == pytest.approx(65.4e-6, rel=1e-2)
    pairs = c.window_pairs(cfg, 2048, 32768)
    assert pairs == 2048 * 513
    assert c.windowed_least_s(cfg, 0, pairs, 2560, PEAKS) == pytest.approx(1.442e-3, rel=1e-2)
    # the accepted readers' counts hold for this model as they stand: a held expert is
    # 47.2 MB over its three matrices, a routed row 47.2 MFLOP
    assert costs_moe.expert_bytes(cfg) == 47_185_920
    assert costs_moe.expert_flops_per_row(cfg) == 47_185_920


def test_the_file_is_the_catalog_row_but_for_what_it_lists(cfg):
    rows = [json.loads(x) for x in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    row = next(r for r in rows if r["name"] == "dots3-note-prev")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 46, "n_routed_experts": 256, "vocab_size": 152064}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 32, 19008)
    assert (cfg["router_outputs"], cfg["experts_held_first"]) == (256, 96)
    assert len(cfg["layer_types"]) == 46                      # the published list kept whole
    assert cfg["layer_types"][:5] == ["full_attention"] * 2 + ["sliding_attention"] * 3
    assert cfg["reference_sample"] == {"n": 8, "lo": 6144, "hi": 8192, "tokens": 64}
    assert {"worst_nat", "mean_nat", "set_from"} <= set(cfg["reference_tolerance"])
    assert set(cfg["assumed"]) >= {"attention_gate", "lora_rescale", "indexer_parameters",
                                   "indexer_inputs", "indexer_rope", "e_score_correction_bias",
                                   "torch_dtype", "weights"}
    assert "left_out" in cfg and "deployment" in cfg and "memory_layout" in cfg


def test_the_adapter_builds_the_published_widths(cfg):
    from benchmarks.adapters import dots3_note
    from dynamo_tpu.models import registry

    m = dots3_note.model_config(cfg)
    full, win = m.kind(0), m.kind(2)
    assert (m.hidden_size, m.num_layers, m.vocab_size, m.experts_held) == (5120, 5, 19008, (96, 32))
    assert (full.num_heads, full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim,
            full.q_lora_rank, full.kv_lora_rank, full.rope_theta) == (128, 128, 64, 128, 1024, 512, 8e7)
    assert (win.num_heads, win.qk_nope_head_dim, win.qk_rope_head_dim, win.v_head_dim,
            win.q_lora_rank, win.kv_lora_rank, win.rope_theta) == (64, 192, 64, 128, 1024, 1024, 5e4)
    assert (full.index_topk, full.index_n_heads, full.index_head_dim, win.index_topk) == (2048, 64, 128, 0)
    assert win.sliding_window == 513 and full.sliding_window is None
    assert full.q_latent_scale == pytest.approx(5 ** 0.5) and full.kv_latent_scale == pytest.approx(10 ** 0.5)
    assert win.q_latent_scale == win.kv_latent_scale == pytest.approx(5 ** 0.5)
    assert (m.num_experts, m.num_experts_per_tok, m.moe_intermediate_size, m.num_shared_experts) == (256, 8, 1536, 1)
    assert registry.page_groups(m) == (((0, 1), None), ((2, 3, 4), 513))
    assert registry.page_shapes(m) == ((((4, 128), (2, 128)),) * 2 + (((8, 128), (2, 128)),) * 3)
    with pytest.raises(ValueError, match="headwise"):
        dots3_note.model_config({**cfg, "swa_attention_gate_type": "elementwise"})
    with pytest.raises(ValueError, match="plain rotary"):
        dots3_note.model_config({**cfg, "rope_scaling": {"factor": 4}})
    with pytest.raises(ValueError, match="sigmoid router"):
        dots3_note.model_config({**cfg, "scoring_func": "softmax"})


def test_the_adapters_names_are_the_references_and_the_switches_are_named():
    from benchmarks.reference import dots3_note_decoder as ref

    names = set(ref._INDEXER_KEYS) | set(ref._ATTN_KEYS) | set(ref._DENSE_KEYS) | set(ref._SPARSE_KEYS)
    import jax
    import jax.numpy as jnp

    from benchmarks.adapters import dots3_note
    from dynamo_tpu.models.dots3_note import Dots3NoteConfig, init_params

    mcfg = Dots3NoteConfig.tiny(dtype=jnp.float32)
    eng = types.SimpleNamespace(mcfg=mcfg, params=init_params(jax.random.PRNGKey(0), mcfg))
    out = dots3_note.reference_params(eng)
    assert set(out) == {"embed", "final_norm", "lm_head", "layers"}
    for i, lp in enumerate(out["layers"]):
        full = mcfg.layer_types[i] == "full_attention"
        want = set(ref._ATTN_KEYS) | (set(ref._INDEXER_KEYS) if full else set())
        want |= set(ref._DENSE_KEYS if i == 0 else ref._SPARSE_KEYS)
        assert set(lp) == want <= names, (i, set(lp) ^ want)
        k = mcfg.kind(i)
        assert lp["w_uk"].shape == (k.num_heads, k.kv_lora_rank, k.qk_nope_head_dim)
        assert lp["w_uq"].shape == (k.q_lora_rank, k.num_heads, k.qk_head_dim)
        assert lp["w_g"].shape == (mcfg.hidden_size, k.num_heads)
    # every named wrong computation is a switch of logprobs
    switches = set(inspect.signature(ref.logprobs).parameters)
    assert set(ref.WRONG) == {"window_512", "window_1024", "no_gate", "no_rescale",
                              "swa_theta_from_full", "sliding_dense", "selection_ignored",
                              "index_topk_halved", "cache_8_bits", "skipped_layer"}
    assert all(set(kw) <= switches for kw in ref.WRONG.values())


def test_the_traffic_is_the_issues(cfg):
    with open(os.path.join(BENCH, "traffic", "agent-toolturns.json")) as f:
        t = json.load(f)
    assert (t["loop"], t["clients"], t["pool"], t["pool_seed"], t["order"]) == (
        "closed", 16, 64, 0, "fixed")
    assert t["prompt"] == {"dist": "uniform", "min": 2048, "max": 4096}
    assert t["output"] == {"dist": "uniform", "min": 128, "max": 256}
    assert t["shared_prefix"] == {"groups": 16, "tokens": 32768, "assign": "client",
                                  "prefill_in_setup": True}
    assert t["drain_s"] == 20
    assert t["engine"]["max_batch_size"] == 16 and t["engine"]["max_context"] == 37376
    # every row's whole table fits the full group's pool
    assert cfg["engine"]["num_blocks"] >= 16 * (37376 // 16) + 1


def step(phase, rows=None, keys=None, chunk=0, touched=None, queue=0, tokens=0, causal=None):
    return types.SimpleNamespace(
        phase=phase, queue_depth=queue, tokens=tokens, winlat_rows=rows, winlat_keys_read=keys,
        page_groups_held=(50000, 3000),
        winlat_chunk_tokens=chunk, moe_held_experts_touched=touched, dsa_keys_causal=causal)


class Trace:
    busy_s = 4.0

    def __init__(self, modules=(), **by):
        self.by, self.modules = by, list(modules)

    def op_seconds(self, pattern):
        return sum(s for name, s in self.by.items() if re.search(pattern, name))

    def module_durations_s(self, pattern):
        return [d for name, d in self.modules if re.search(pattern, name)]


def made_up(cfg):
    trace = Trace(modules=[("jit_mixed_step", 0.5), ("jit_prefill", 0.5), ("jit_decode_multi", 0.02)],
                  windowed_latent_attention=0.02, sparse_latent_attention=0.9, paged_index_keys=0.08,
                  moe_grouped_matmul=1.5, fusion=1.0)
    ctx = types.SimpleNamespace(cfg=cfg, trace=trace, trace_host=(10.0, 15.0), peaks=PEAKS,
                                engine={"decode_steps": 8})
    n = 16 * 8                                   # a horizon: 16 rows x 8 steps, 3 sliding layers
    ctx.steps = ctx.steps_all = [
        (11.0, step("decode", 3 * n, 3 * n * 513, touched=4 * 8 * 10)),
        (12.0, step("decode", 3 * n, 3 * n * 513, touched=4 * 8 * 12)),
        (13.0, step("mixed", 3 * 15, 3 * 15 * 400, chunk=3 * 2048, touched=100, tokens=2063)),
        (14.0, step("prefill", tokens=1024)),
        (14.5, step("decode", 3 * n, 3 * n * 513, touched=4 * 8 * 9, queue=1)),
    ]
    req = dict(cached_tokens=32768, prompt_tokens=32768 + 3072, t_first=13.5, t_ref=12.5)
    ctx.requests = ctx.requests_all = [req]
    return ctx


def test_the_five_readers_on_made_up_records(cfg):
    ctx = made_up(cfg)
    rows = 3 * (3 * 128) + 45
    keys = 3 * (3 * 128 * 513) + 45 * 400
    assert reader("dots3_window_keys_per_decode_row.tput")(ctx) == pytest.approx(keys / rows)
    assert reader("dots3_attention_share.tput")(ctx) == pytest.approx(25.0)
    assert reader("dots3_held_experts_touched.tput")(ctx) == pytest.approx(11.0)
    # the launch: the steps' row keys x 2 176 B, and the one request's 3 072 tokens behind
    # 32 768: 3 layers x 3 072 x 513 pairs x 270 336 FLOP
    least = keys * 2176 / 819e9 + 3 * 3072 * 513 * 270_336 / 197e12
    assert reader("windowed_latent_attention_roofline")(ctx) == pytest.approx(100 * least / 0.02)
    # the step: mean tokens of the chunk-carrying steps x the request's FLOPs a token
    per_token = costs_dots3.chunk_flops(cfg, 3072, 32768) / 3072
    assert reader("dots3_chunk_mfu")(ctx) == pytest.approx(
        100 * (2063 + 1024) / 2 * per_token / (0.5 * 197e12))


def test_a_program_without_the_counters_gives_none(cfg):
    """The parent under this PR's benchmark files: nothing to read, no raise."""
    ctx = made_up(cfg)
    bare = types.SimpleNamespace(phase="decode", queue_depth=0, tokens=16)
    ctx.steps = ctx.steps_all = [(11.0, bare)]
    for name in READERS:
        assert reader(name)(ctx) is None
    ctx = made_up(cfg)
    ctx.trace = None
    for name in ("windowed_latent_attention_roofline", "dots3_attention_share.tput", "dots3_chunk_mfu"):
        assert reader(name)(ctx) is None


def test_the_manifest_lists_the_cell_where_the_issue_said():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots3-note-ep8-d5", "agent-toolturns", 1)
    assert m["workloads"][-1] is cell and len(m["workloads"]) == 12 and len(m["configs"]) == 11
    assert len(cell["why"]) <= 200
    conf = m["configs"][-1]
    assert conf["name"] == "dots3-note-ep8-d5"
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert conf["source"] == "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json"
    listed = {e["name"] for e in m["per_layer"] if CELL in e.get("workloads", [])}
    assert set(READERS) <= listed
    assert {"moe_grouped_matmul_roofline", "dsa_selected_share.tput", "dsa_index_run_chunk_share.tput",
            "win_pages_held_share.tput", "prefix_hit_share.tput",
            "programs_compiled_in_window.tput"} <= listed
    # sparse_latent_attention_roofline's reader counts a chunk's pairs over num_hidden_layers
    # (5) where 2 layers select: it does not reckon this configuration, and does not list it
    assert not {"sparse_latent_attention_roofline", "paged_latent_attention_roofline",
                "moe_held_experts_touched.tput", "prefill_mfu"} & listed
    for e in m["per_layer"][-5:]:
        assert e["moves"] == "output_tokens_per_s" and e["workloads"] == [CELL]
    assert [e["name"] for e in m["per_layer"][-5:]] == list(READERS)
