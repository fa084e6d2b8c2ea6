"""By hand (``python -m pytest benchmarks/tests/test_minicpm_sala.py -q``):
``costs_sala.py`` against ISSUE 54's arithmetic, the configuration file
through its adapter and against the catalog's keys, the benchmark's plain
reference against the program's own plain forward at a test's size (the
recurrence token by token against the blocked scan, a selection from a
strided window against the one from pairs of pages), the traffic file's
numbers, and the seven readers on made-up records (a program without the
counters gives ``None``, as the parent has to)."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

from benchmarks import costs_sala

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "minicpmsala-longdoc-reason"
READERS = ("lightning_state_update_roofline", "lightning_update_share.tput",
           "lightning_rows_per_decode_step.tput", "infllm_decode_attention_roofline",
           "infllm_attention_share.tput", "infllm_selected_share.tput",
           "chunk_run_ms_per_token.tput")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "minicpm-sala-9b-d8.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_costs_are_the_issues_arithmetic(cfg):
    assert (costs_sala.sparse_layers(cfg), costs_sala.lightning_layers(cfg)) == (2, 6)
    # 2 x 2 MiB of state traffic a row a layer, and the row's own operands
    assert 2 * costs_sala.state_elements(cfg) * 4 == 2 * 2 * 1024 * 1024
    assert costs_sala.state_row_vector_bytes(cfg) == 4096 * 4 + 2 * 4096 * 2 + 4096 * 4
    assert costs_sala.state_update_bytes(cfg, 32 * 6) == 32 * 6 * (4_194_304 + 49_152)
    # the chosen keys of the head's OWN kv head: 512 B a key a kv head
    assert costs_sala.own_head_key_value_bytes(cfg) == 512
    assert costs_sala.decode_attention_bytes(cfg, 6272, 1) == 6272 * 2 * 512 + 2 * 4096 * 2
    # FFN 201.3 M; a sparse layer 253.7 M, a lightning layer 285.2 M
    assert costs_sala.ffn_params(cfg) == 201_326_592
    assert round(costs_sala.sparse_layer_params(cfg) / 1e6, 1) == 253.8
    assert round(costs_sala.lightning_layer_params(cfg) / 1e6, 1) == 285.2
    layers = 2 * costs_sala.sparse_layer_params(cfg) + 6 * costs_sala.lightning_layer_params(cfg)
    assert round((layers + 2 * 73448 * 4096) / 1e9, 2) == 2.82
    # a query's keys: every causal one up to dense_len, 6 272 at most past it
    assert costs_sala.keys_attended(cfg, 8191) == 8192
    assert costs_sala.keys_attended(cfg, 8192) == 97 * 64 + 1
    assert costs_sala.keys_attended(cfg, 12000) == 97 * 64 + 12000 % 64 + 1
    assert max(costs_sala.keys_attended(cfg, t) for t in range(8192, 18432)) == 6272
    assert costs_sala.pooled_keys_scored(cfg, 8191) == 0
    assert costs_sala.pooled_keys_scored(cfg, 10239) == (10240 - 32) // 16 + 1
    one = costs_sala.prompt_flops(cfg, 1)
    assert one == pytest.approx(costs_sala.matrix_flops_per_token(cfg) + 2 * 4 * 4096
                                + 6 * 4 * costs_sala.state_elements(cfg))


def test_the_file_is_the_catalog_row_but_for_depth(cfg):
    rows = [json.loads(x) for x in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    row = next(r for r in rows if r["name"] == "MiniCPM-SALA")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 32} and cfg["first_layer_run"] == 9
    assert costs_sala.layer_kinds(cfg) == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert set(cfg["assumed_sizes"]) == {"kernel_size", "kernel_stride", "block_size", "topk",
                                         "init_blocks", "window_size", "dense_len"}
    for name in ("sparse_config", "forced_blocks", "topk_counts", "rule_by_query", "sparse_gate",
                 "lightning_decay", "lightning_activation", "state_dtype", "weights"):
        assert "lternative" in cfg["assumed"][name] or name in ("state_dtype", "weights"), name
    tol = cfg["reference_tolerance"]
    assert {"worst_nat", "mean_nat", "state_rel", "state_precision_gap", "pooled_key_rel",
            "block_overlap_min", "first_cache_rel", "set_from"} <= set(tol)


def test_the_adapter_builds_the_published_widths(cfg):
    from benchmarks.adapters import minicpm_sala

    m = minicpm_sala.model_config(cfg)
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim) == (4096, 32, 2, 128)
    assert (m.lightning_heads, m.lightning_head_dim, m.intermediate_size) == (32, 128, 16384)
    assert m.vocab_size == 73448 and m.num_layers == 8 and m.sparse_layers == (0, 7)
    assert (m.scale_emb, m.scale_depth, m.mup_denominator, m.dim_model_base) == (12, 1.4, 32, 256)
    sel = m.selection
    assert (sel.kernel, sel.stride, sel.block, sel.topk, sel.init_blocks, sel.window,
            sel.dense_len, sel.max_chosen) == (32, 16, 64, 64, 1, 2048, 8192, 98)


def test_the_traffic_is_the_issues(cfg):
    with open(os.path.join(BENCH, "traffic", "longdoc-reason.json")) as f:
        t = json.load(f)
    assert (t["loop"], t["clients"], t["pool"], t["order"]) == ("closed", 32, 128, "fixed")
    assert t["prompt"] == {"dist": "lognormal", "median": 10240, "sigma": 0.25,
                           "min": 8192, "max": 16384}
    assert t["output"] == {"dist": "lognormal", "median": 1536, "sigma": 0.25,
                           "min": 1024, "max": 2048}
    assert t["engine"] == {"max_batch_size": 32, "prefill_buckets": [128, 256, 512],
                           "max_context": 18560}
    # every row's pages fit, a page a 16 tokens, whatever the draw
    assert cfg["engine"]["num_blocks"] >= 32 * 18560 // 16 and cfg["engine"]["block_size"] == 16
    assert t["prompt"]["min"] >= cfg["assumed_sizes"]["dense_len"]


def test_the_reference_is_the_programs_plain_forward_at_a_tests_size():
    """One forward each, float32, contexts on both sides of dense_len: the
    reference's lightning layers are the recurrence token by token, the
    program's the blocked scan from zeros; the reference's pooled keys a
    strided window and its top-k a rank, the program's pairs of pages and
    ``lax.top_k``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.adapters import minicpm_sala as adapter
    from benchmarks.reference import minicpm_sala_decoder as ref
    from dynamo_tpu.models import minicpm_sala as sala

    small = {
        "model_type": "minicpm_sala", "vocab_size": 512, "hidden_size": 128,
        "num_hidden_layers": 8, "num_attention_heads": 32, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 256, "hidden_act": "silu",
        "attention_bias": False, "attn_use_rope": False, "qk_norm": True,
        "lightning_head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True, "rope_theta": 10000,
        "rms_norm_eps": 1e-6, "max_position_embeddings": 2048, "tie_word_embeddings": False,
        "torch_dtype": "float32",
        "mixer_types": (["minicpm4"] + ["lightning-attn"] * 3) * 2,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 32,
        "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True,
        "assumed_sizes": {"kernel_size": 32, "kernel_stride": 16, "block_size": 32, "topk": 2,
                          "init_blocks": 1, "window_size": 64, "dense_len": 128},
    }
    m = adapter.model_config(small)
    params = sala.init_params(jax.random.PRNGKey(5), m)
    n = 300
    ids = np.random.default_rng(0).integers(0, 512, n)
    with jax.default_matmul_precision("highest"):
        hidden = sala.forward(params, m, jnp.asarray(ids), jnp.arange(n), sala.stateless_attend(m))
        want = jax.nn.log_softmax(sala.lm_logits(params, m, hidden), axis=-1)
    got, held = ref.logprobs(small, params, ids.tolist(), list(range(n)), pad_to=320, held_after=n)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-4)
    assert [h is None for h in held["state"]] == [True, False, False, False] * 2
    assert [h is None for h in held["sparse"]] == [False, True, True, True] * 2
    chosen = np.asarray(held["sparse"][0]["chosen"])
    assert chosen.shape == (2, 10) and list(chosen.sum(-1)) == [6, 6]
    # every switch computes something else
    for name, sw in ref.wrong_variants(small).items():
        if name.startswith(("cache", "state")):
            continue
        other = ref.logprobs(small, params, ids.tolist(), list(range(n)), **sw)
        assert np.abs(other - got).max() > 1e-3, name


SEQ = [0]


def step(phase, tokens=0, rows=None, steps=0, selected=None, causal=None, t0=0, landed=None):
    """One StepStats with ITS launch (``t0`` ms) and that launch's arrival
    (``landed`` ms, or none)."""
    SEQ[0] += 1
    program = {"prefill": "prefill", "mixed": "mixed_step"}.get(phase, "decode_multi")
    return types.SimpleNamespace(
        phase=phase, tokens=tokens, lightning_rows_updated=rows, lightning_decode_steps=steps,
        lightning_tokens_scanned=0, infllm_keys_selected=selected, infllm_keys_causal=causal,
        infllm_rows_sparse=None, infllm_pooled_keys_written=None,
        launches=(SEQ[0], program, 512, int(t0 * 1e6), int(t0 * 1e6) + 1000, 0, -1),
        arrivals=() if landed is None else (SEQ[0], int(landed * 1e6)))


class Trace:
    busy_s = 4.0

    def __init__(self, seconds):
        self.seconds = seconds

    def op_seconds(self, pattern):
        return self.seconds


def made_up(cfg):
    ctx = types.SimpleNamespace(
        cfg=cfg, trace=Trace(2.0), trace_host=(10.0, 15.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        requests=[{"prompt_tokens": 9000, "cached_tokens": 0},
                  {"prompt_tokens": 8500, "cached_tokens": 0}])
    # two horizons of 30 rows (8 steps; x 6 lightning layers, x 2 sparse), a
    # run of three chunk steps (a mixed step of 20 rows, a lone chunk that
    # returns nothing, a prompt's last chunk), a horizon outside the trace,
    # and a lone chunk whose results nobody read: left out, tokens and time
    ctx.steps = ctx.steps_all = [
        (11.0, step("decode", 240, 30 * 48, 8, 30 * 16 * 6272, 30 * 16 * 12000, 1000, 1090)),
        (12.0, step("decode", 240, 30 * 48, 8, 30 * 16 * 6200, 30 * 16 * 11000, 1100, 1190)),
        (13.0, step("mixed", 532, 20 * 6, 1, 20 * 2 * 6272, 20 * 2 * 10000, 1200, 1260)),
        (13.5, step("prefill", 512, 0, 0, t0=1210)),
        (14.0, step("prefill", 300, 0, 0, t0=1220, landed=1340)),
        (20.0, step("decode", 256, 32 * 48, 8, 32 * 16 * 6272, 32 * 16 * 13000, 1350, 1440)),
        (21.0, step("prefill", 512, 0, 0, t0=1450)),
    ]
    return ctx


def test_the_seven_readers_on_made_up_records(cfg):
    ctx = made_up(cfg)
    rows = 2 * 30 * 48 + 20 * 6
    assert reader("lightning_state_update_roofline")(ctx) == pytest.approx(
        100 * rows * (4_194_304 + 49_152) / 819e9 / 2.0)
    assert reader("lightning_update_share.tput")(ctx) == pytest.approx(50.0)
    assert reader("infllm_attention_share.tput")(ctx) == pytest.approx(50.0)
    assert reader("lightning_rows_per_decode_step.tput")(ctx) == pytest.approx(
        (2 * 30 * 48 + 20 * 6 + 32 * 48) / 6 / (8 + 8 + 1 + 8))
    keys = 30 * 16 * (6272 + 6200) + 20 * 2 * 6272
    need = keys * 2 * 512 + rows / 6 * 2 * (2 * 4096 * 2)
    assert reader("infllm_decode_attention_roofline")(ctx) == pytest.approx(100 * need / 819e9 / 2.0)
    sel = 30 * 16 * (6272 + 6200) + 20 * 2 * 6272 + 32 * 16 * 6272
    cau = 30 * 16 * (12000 + 11000) + 20 * 2 * 10000 + 32 * 16 * 13000
    assert reader("infllm_selected_share.tput")(ctx) == pytest.approx(100 * sel / cau)
    # the run of three chunk steps, first call (1 200 ms) to last arrival (1 340)
    assert reader("chunk_run_ms_per_token.tput")(ctx) == pytest.approx(140.0 / (532 + 512 + 300))


def test_a_program_without_the_counters_gives_none(cfg):
    """The parent under this PR's benchmark files: nothing to read, no raise."""
    bare = types.SimpleNamespace(phase="decode", tokens=8)
    ctx = types.SimpleNamespace(cfg=cfg, trace=Trace(0.0), trace_host=(10.0, 15.0),
                                peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
                                requests=[], steps=[(11.0, bare)], steps_all=[(11.0, bare)])
    for name in READERS:
        assert reader(name)(ctx) is None, name
    ctx.trace = None
    for name in READERS:
        assert reader(name)(ctx) is None, name


def test_the_manifest_lists_the_cell_where_the_issue_said():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        m = json.load(f)
    listed = {e["name"] for e in m["per_layer"] if CELL in e.get("workloads", [])}
    solar = {e["name"] for e in m["per_layer"] if "solaropen2-reason-wide" in e.get("workloads", [])}
    assert set(READERS) <= listed
    # the traced sub-window of this cell holds decode horizons only (its 32
    # callers send at once): what reads a chunk program's device time is not
    # listed (PERF.md section 6, PR 54)
    no_chunk_in_trace = {"prefill_chunk_ms.tput", "device_wait_before_mixed_ms.tput",
                         "chunk_step_ms_per_token.tput"}
    assert {n for n in solar if n.endswith((".tput", ".closed")) and not n.startswith(("kda_", "moe_"))
            and n != "paged_run_chunk_share.tput"} - no_chunk_in_trace <= listed
    assert not no_chunk_in_trace & listed
    assert not {n for n in listed if n.startswith(("ssm_", "kda_", "moe_", "dsa_", "mla_", "paged_"))
                or n == "prefill_mfu"}
    for name in READERS:
        e = next(e for e in m["per_layer"] if e["name"] == name)
        assert e["workloads"] == [CELL] and e["moves"] == "output_tokens_per_s"
        assert e["source"] in ("device_trace", "program_counter", "host_clock")
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("minicpm-sala-9b-d8", "longdoc-reason", 1)
    assert m["workloads"][-1] is cell and m["configs"][-1]["name"] == "minicpm-sala-9b-d8"
    assert CELL in next(e for e in m["end_to_end"] if e["name"] == "output_tokens_per_s")["workloads"]
