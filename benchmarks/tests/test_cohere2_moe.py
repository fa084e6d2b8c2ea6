"""By hand (``python -m pytest benchmarks/tests/test_cohere2_moe.py -q``):
``costs_cmda.py`` against ISSUE 49's arithmetic, the configuration file through
its adapter and against the catalog's keys, the traffic file's parameters,
the accepted readers' counts of bytes against this model, and the five new
readers on made-up records (a program without the counters gives ``None``,
as the parent has to). The reference against the engine at a test's size is
tier-1's (``tests/test_cohere2_moe.py``)."""

import importlib.util
import json
import os
import re
import types

import pytest

from benchmarks import costs, costs_cmda, costs_moe

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
READERS = ("win_pages_held_share.tput", "win_keys_per_decode_row.tput",
           "full_keys_per_decode_row.tput", "cmda_attention_share.tput",
           "cmda_held_experts_touched.tput")
CELL = "cmdaplus-contract-sessions"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "command-a-plus-ep8-d4.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_costs_are_the_issues_arithmetic(cfg):
    # 4 KiB a token a layer: 2 x 8 kv heads x 128 x 2 bytes
    assert costs.kv_bytes_per_token_per_layer(cfg) == 4096
    assert costs_cmda.layers_of_kind(cfg, "sliding_attention") == 3
    assert costs_cmda.layers_of_kind(cfg, "full_attention") == 1
    assert costs_cmda.routed_layers(cfg) == 4
    # the four shared experts 4 x 3 x 16 777 216 parameters, the projections 142 606 336
    assert costs_cmda.shared_expert_bytes(cfg) == 2 * 201_326_592
    assert costs_cmda.attention_weight_bytes(cfg) == 2 * 142_606_336
    # one table holds 2 112 pages of a 33 792-token context; a sliding layer
    # can still read 257 (a window that does not start on a page's edge)
    assert costs_cmda.one_table_pages(33792, 16) == 2112
    assert costs_cmda.windowed_pages(cfg, 33792, 16) == 257
    assert costs_cmda.windowed_pages(cfg, 1000, 16) == 63
    # the accepted readers' counts hold for this model as they stand: a held
    # expert is 100.7 MB over its three matrices, a windowed row reads 4 096
    # keys whatever its context, a full row every key
    assert costs_moe.expert_bytes(cfg) == 100_663_296
    assert costs_moe.sliding_layers(cfg) == 3
    assert costs_moe.windowed_row_tokens(cfg, 33000) == 4096
    assert costs_moe.windowed_row_tokens(cfg, 1000) == 1000
    assert costs.decode_attention_bytes(cfg, 33000) == 33000 * 4096


def test_the_file_is_the_catalog_row_but_for_what_it_lists(cfg):
    rows = [json.loads(x) for x in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    row = next(r for r in rows if r["name"] == "command-a-plus-05-2026")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 32768)
    assert (cfg["router_outputs"], cfg["experts_held_first"]) == (128, 0)
    assert len(cfg["layer_types"]) == 32                      # the published list kept whole
    assert cfg["reference_sample"] == {"n": 4, "lo": 8192, "hi": 12288, "tokens": 512}
    assert set(cfg["reference_tolerance"]) == {
        "worst_nat", "mean_nat", "median_nat", "first_cache_rel", "window_edge_lean", "set_from"}
    assert set(cfg["assumed"]) >= {"intermediate_size", "shared_expert_combination_strategy",
                                   "sliding_window", "full_layers", "rope_layout", "weights"}


def test_the_adapter_builds_the_published_widths(cfg):
    from benchmarks.adapters import cohere2_moe
    from dynamo_tpu.models import registry

    m = cohere2_moe.model_config(cfg)
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim) == (4096, 128, 8, 128)
    assert (m.num_layers, m.sliding_window, m.rope_theta, m.vocab_size) == (4, 4096, 50000.0, 32768)
    assert (m.num_experts, m.num_experts_per_tok, m.moe_intermediate_size) == (128, 8, 4096)
    assert (m.num_shared_experts, m.shared_expert_scale, m.experts_held) == (4, 0.25, (0, 16))
    assert registry.page_groups(m) == (((3,), None), ((0, 1, 2), 4096))
    with pytest.raises(ValueError, match="parallel block"):
        cohere2_moe.model_config({**cfg, "use_parallel_block": False})
    with pytest.raises(ValueError, match="interleaved rotary"):
        cohere2_moe.model_config({**cfg, "use_qk_norm": True})
    with pytest.raises(ValueError, match="sigmoid-routed"):
        cohere2_moe.model_config({**cfg, "expert_selection_fn": "softmax"})


def test_the_traffic_is_the_issues(cfg):
    with open(os.path.join(BENCH, "traffic", "contract-sessions.json")) as f:
        t = json.load(f)
    assert (t["loop"], t["clients"], t["pool"], t["pool_seed"], t["order"]) == (
        "closed", 24, 96, 0, "fixed")
    assert t["prompt"] == {"dist": "uniform", "min": 128, "max": 512}
    assert t["output"] == {"dist": "uniform", "min": 128, "max": 384}
    assert t["shared_prefix"] == {"groups": 24, "tokens": 32768, "assign": "client",
                                  "prefill_in_setup": True}
    assert t["drain_s"] == 20
    assert t["engine"] == {"max_batch_size": 24, "prefill_buckets": [128, 512],
                           "max_context": 33792}
    # every row's whole table fits the full group's pool
    assert cfg["engine"]["num_blocks"] >= 24 * costs_cmda.one_table_pages(33792, 16) + 1
    assert cfg["engine"]["options"] == {"window_blocks": 10240}


def step(phase, held=None, released=None, win=None, full=None, rows=None, touched=None,
         queue=0):
    return types.SimpleNamespace(
        phase=phase, queue_depth=queue, page_groups_held=held, page_groups_released=released,
        win_keys_read=win, full_keys_read=full, win_decode_rows=rows,
        full_decode_rows=None if rows is None else rows // 3,
        moe_held_experts_touched=touched)


class Trace:
    busy_s = 4.0

    def __init__(self, **by):
        self.by = by

    def op_seconds(self, pattern):
        return sum(s for name, s in self.by.items() if re.search(pattern, name))


def test_the_five_readers_on_made_up_records(cfg):
    trace = Trace(paged_decode_attention=0.6, ragged_paged_attention_windowed=0.3,
                  ragged_paged_attention=0.1, moe_grouped_matmul=1.5)
    ctx = types.SimpleNamespace(cfg=cfg, trace=trace, trace_host=(10.0, 15.0),
                                engine={"decode_steps": 8})
    # two horizons of 24 rows x 8 steps: 3 sliding layers at 4 096 keys, the
    # full layer at 33 000; a mixed step whose rows' windows are not full; a
    # prefill (no readback); each with what the live rows hold
    n = 24 * 8
    ctx.steps = ctx.steps_all = [
        (11.0, step("decode", (50000, 6900), (0, 12), 3 * n * 4096, n * 33000, 3 * n, 4 * 8 * 12)),
        (12.0, step("decode", (50400, 6960), (0, 24), 3 * n * 4096, n * 33000, 3 * n, 4 * 8 * 13)),
        (13.0, step("mixed", (50400, 6960), (0, 0), 3 * 20 * 1000, 20 * 1000, 60, 40)),
        (14.0, step("prefill", (50400, 6990), (0, 30))),
        (14.5, step("decode", (50400, 6990), (0, 0), 3 * n * 4096, n * 33000, 3 * n, 4 * 8 * 9,
                    queue=1)),
    ]
    held = [(50000, 6900), (50400, 6960), (50400, 6960), (50400, 6990), (50400, 6990)]
    assert reader("win_pages_held_share.tput")(ctx) == pytest.approx(
        100 * sum(h[1] for h in held) / sum(h[0] for h in held))
    rows = 3 * (3 * n) + 60
    assert reader("win_keys_per_decode_row.tput")(ctx) == pytest.approx(
        (3 * 3 * n * 4096 + 60 * 1000) / rows)
    assert reader("full_keys_per_decode_row.tput")(ctx) == pytest.approx(
        (3 * n * 33000 + 20 * 1000) / (3 * n + 20))
    assert reader("cmda_attention_share.tput")(ctx) == pytest.approx(25.0)
    # horizons with nobody waiting: 12 and 13 touched a layer a step
    assert reader("cmda_held_experts_touched.tput")(ctx) == pytest.approx(12.5)


def test_a_program_without_the_counters_gives_none(cfg):
    """The parent under this PR's benchmark files: nothing to read, no raise."""
    bare = types.SimpleNamespace(phase="decode", queue_depth=0)
    ctx = types.SimpleNamespace(cfg=cfg, trace=Trace(paged_decode_attention=1.0),
                                trace_host=(10.0, 15.0), engine={"decode_steps": 8},
                                steps=[(11.0, bare)], steps_all=[(11.0, bare)])
    for name in READERS:
        assert reader(name)(ctx) is None
    ctx.trace = None
    assert reader("cmda_attention_share.tput")(ctx) is None


def test_the_manifest_lists_the_cell_where_the_issue_said():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command-a-plus-ep8-d4", "contract-sessions", 1)
    assert m["workloads"][-1] is cell and len(m["workloads"]) == 10 and len(m["configs"]) == 9
    assert len(cell["why"]) <= 200
    conf = m["configs"][-1]
    assert conf["name"] == "command-a-plus-ep8-d4"
    assert conf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert conf["source"] == (
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json")
    listed = {e["name"] for e in m["per_layer"] if CELL in e.get("workloads", [])}
    assert set(READERS) <= listed
    assert {"moe_grouped_matmul_roofline", "paged_decode_attention_roofline",
            "windowed_attention_roofline", "prefix_hit_share.tput"} <= listed
    # readers that find nothing to read in this cell do not list it
    assert not {"moe_held_experts_touched.tput", "prefill_mfu"} & listed
    for e in m["per_layer"][-5:]:
        assert e["moves"] == "output_tokens_per_s" and e["workloads"] == [CELL]
    assert [e["name"] for e in m["per_layer"][-5:]] == list(READERS)
