"""By hand (``python -m pytest benchmarks/tests/test_solar_open2.py -q``):
``costs_kda.py`` against ISSUE 41's arithmetic, the configuration file
through its adapter and against the catalog's keys, the benchmark's plain
reference against the program's own plain forward at a test's size (the
recurrence token by token against the chunked scan, blocks of queries
against dense causal attention, experts one at a time against the grouped
path), and the three KDA readers on made-up records (a program without the
counters gives ``None``, as the parent has to)."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

from benchmarks import costs_kda

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
READERS = ("kda_state_update_roofline", "kda_update_share.tput", "kda_rows_per_decode_step.tput",
           "kda_held_experts_touched.tput", "kda_held_load_max_over_mean.tput")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "solar-open2-ep16-d8.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_costs_are_the_issues_arithmetic(cfg):
    assert costs_kda.kda_layers(cfg) == 6
    assert costs_kda.state_elements(cfg) == 64 * 128 * 128
    # 8.39 MB of state traffic a row a layer, and the row's own operands:
    # q, k, alpha float32, v bf16, beta float32 a head, y float32
    assert 2 * costs_kda.state_elements(cfg) * 4 == 8_388_608
    assert costs_kda.row_vector_bytes(cfg) == 3 * 8192 * 4 + 8192 * 2 + 64 * 4 + 8192 * 4
    assert costs_kda.state_update_bytes(cfg, 1) == 8_388_608 + 147_712
    assert costs_kda.state_update_bytes(cfg, 128, 6) == 128 * 6 * (8_388_608 + 147_712)


def test_the_file_is_the_catalog_row_but_for_depth_experts_and_vocabulary(cfg):
    rows = [json.loads(x) for x in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    row = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 48, "n_routed_experts": 320,
                                   "vocab_size": 196608}
    assert cfg["vocab_size"] * 8 == 196608 and cfg["n_routed_experts"] * 16 == cfg["router_outputs"] == 320
    assert cfg["experts_held_first"] == 160


def test_the_adapter_builds_the_published_widths(cfg):
    from benchmarks.adapters import solar_open2

    m = solar_open2.model_config(cfg)
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim) == (4096, 64, 8, 128)
    assert (m.kda_num_heads, m.kda_head_dim, m.kda_conv_kernel, m.kda_low_rank) == (64, 128, 4, 128)
    assert (m.num_experts, m.num_experts_per_tok, m.moe_intermediate_size) == (320, 8, 1280)
    assert m.experts_held == (160, 20) and m.num_layers == 8 and m.gqa_layers == (0, 4)
    assert m.vocab_size == 24576 and m.conv_dim == 24576 and m.kda_allow_neg_eigval


def test_the_reference_is_the_programs_plain_forward_at_a_tests_size():
    """One forward each, float32: the reference's KDA layers are the
    recurrence token by token, the program's the chunked scan from zeros."""
    import jax
    import jax.numpy as jnp

    from benchmarks.adapters import solar_open2 as adapter
    from benchmarks.reference import solar_open2_decoder as ref
    from dynamo_tpu.models import solar_open2 as so2
    from dynamo_tpu.ops import attention as att

    small = {
        "model_type": "solar_open2", "vocab_size": 512, "hidden_size": 128,
        "num_hidden_layers": 8, "num_attention_heads": 8, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 256, "moe_intermediate_size": 32,
        "rope_theta": 10000, "rms_norm_eps": 1e-5, "max_position_embeddings": 4096,
        "tie_word_embeddings": False, "torch_dtype": "float32",
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                               "num_kv_heads": None},
        "first_k_dense_replace": 0, "use_rope": False, "gqa_layers": [0, 4, 8, 12],
        "use_gqa_gate": True, "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_routed_experts": 4, "router_outputs": 16, "experts_held_first": 4,
        "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
        "num_experts_per_tok": 4, "assumed_sizes": {"kda_low_rank": 16},
    }
    m = adapter.model_config(small)
    params = so2.init_params(jax.random.PRNGKey(5), m)
    ids = np.random.default_rng(0).integers(0, 512, 45)

    def attend(q, k, v, layer_idx):
        return att.causal_attention(q, k, v)

    with jax.default_matmul_precision("highest"):
        hidden = so2.forward(params, m, jnp.asarray(ids), jnp.arange(45), attend)
        want = jax.nn.log_softmax(so2.lm_logits(params, m, hidden), axis=-1)
    got, held = ref.logprobs(small, params, ids.tolist(), list(range(45)), pad_to=64, held_after=45)
    np.testing.assert_allclose(got, np.asarray(want), atol=5e-5)
    assert [h is None for h in held["kda"]] == [True, False, False, False] * 2
    assert [h is None for h in held["k"]] == [False, True, True, True] * 2
    # every switch computes something else
    for name, sw in ref.wrong_variants(small).items():
        if name.startswith(("cache", "state")):
            continue
        other = ref.logprobs(small, params, ids.tolist(), list(range(45)), **sw)
        assert np.abs(other - got).max() > 1e-3, name


def step(phase, rows=None, steps=0, scanned=0):
    return types.SimpleNamespace(phase=phase, kda_rows_updated=rows, kda_decode_steps=steps,
                                 kda_tokens_scanned=scanned)


class Trace:
    busy_s = 4.0

    def __init__(self, seconds):
        self.seconds = seconds

    def op_seconds(self, pattern):
        return self.seconds


def test_the_three_readers_on_made_up_records(cfg):
    ctx = types.SimpleNamespace(cfg=cfg, trace=Trace(2.0), trace_host=(10.0, 15.0),
                                peaks={"hbm_bytes_per_s": 819e9})
    # two horizons of 100 rows (8 steps x 6 KDA layers), a mixed step of 90, a prefill, one outside
    ctx.steps = ctx.steps_all = [
        (11.0, step("decode", 100 * 48, 8)), (12.0, step("decode", 100 * 48, 8)),
        (13.0, step("mixed", 90 * 6, 1, scanned=6 * 128)), (14.0, step("prefill", 0, 0, 6 * 128)),
        (20.0, step("decode", 128 * 48, 8)),
    ]
    rows = 2 * 100 * 48 + 90 * 6
    need_s = rows * (8_388_608 + 147_712) / 819e9
    assert reader("kda_state_update_roofline")(ctx) == pytest.approx(100 * need_s / 2.0)
    assert reader("kda_update_share.tput")(ctx) == pytest.approx(50.0)
    per_step = (2 * 100 * 48 + 90 * 6 + 128 * 48) / 6 / (8 + 8 + 1 + 8)
    assert reader("kda_rows_per_decode_step.tput")(ctx) == pytest.approx(per_step)


def test_the_held_shares_two_readers_on_made_up_records(cfg):
    """Decode horizons only (8 steps x 8 routing layers = 64 cells of 20
    held experts): a horizon that touched 19 of 20 everywhere and routed 64
    held rows a cell with 8 on the busiest expert, one at 17 and 7; a mixed
    step, a single step beside a waiting request and a step of a family
    without a held share are left out."""
    def horizon(touched, routed, load_max, phase="decode", queue=0, held=True):
        s = step(phase, 100 * 48, 8)
        s.queue_depth, s.moe_tokens_routed, s.moe_load_max = queue, routed, load_max
        s.moe_held_experts_touched = touched if held else None
        return s

    ctx = types.SimpleNamespace(cfg=cfg, engine={"decode_steps": 8}, steps=[
        (11.0, horizon(19 * 64, 64 * 64, 8)), (12.0, horizon(17 * 64, 64 * 64, 7)),
        (13.0, horizon(20, 64, 20, phase="mixed")), (14.0, horizon(20, 64, 20, queue=1)),
        (15.0, horizon(20 * 64, 64 * 64, 20, held=False)),
    ])
    assert cfg["n_routed_experts"] == 20 and cfg["num_hidden_layers"] == 8
    assert reader("kda_held_experts_touched.tput")(ctx) == pytest.approx(18.0)
    # mean load a held expert a cell: 64 / 20 = 3.2 rows
    assert reader("kda_held_load_max_over_mean.tput")(ctx) == pytest.approx((8 + 7) / 2 / 3.2)


def test_a_program_without_the_counters_gives_none(cfg):
    """The parent under this PR's benchmark files: nothing to read, no raise."""
    bare = types.SimpleNamespace(phase="decode")
    ctx = types.SimpleNamespace(cfg=cfg, trace=Trace(0.0), trace_host=(10.0, 15.0),
                                peaks={"hbm_bytes_per_s": 819e9}, engine={"decode_steps": 8},
                                steps=[(11.0, bare)], steps_all=[(11.0, bare)])
    for name in READERS:
        assert reader(name)(ctx) is None
    ctx.trace = None
    assert reader("kda_state_update_roofline")(ctx) is None


def test_the_manifest_lists_the_cell_where_the_issue_said():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = "solaropen2-reason-wide"
    listed = {e["name"] for e in m["per_layer"] if cell in e.get("workloads", [])}
    wide = {e["name"] for e in m["per_layer"] if "falconh1-chat-wide" in e.get("workloads", [])}
    assert set(READERS) <= listed
    assert {"paged_decode_attention_roofline", "moe_grouped_matmul_roofline"} <= listed
    assert {n for n in wide if n.endswith((".tput", ".closed")) and not n.startswith("ssm_")} <= listed
    assert not {n for n in listed if n.startswith(("ssm_", "dsa_", "mla_")) or n == "prefill_mfu"}
    for name in READERS:
        assert next(e for e in m["per_layer"] if e["name"] == name)["workloads"] == [cell]
