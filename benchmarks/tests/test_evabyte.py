"""By hand (``python -m pytest benchmarks/tests/test_evabyte.py -q``):
``costs_eva.py`` against ISSUE 46's arithmetic, the configuration file through
its adapter and against the catalog's keys, the traffic file's parameters,
the benchmark's plain reference against the program's own whole-sequence twin
at a test's size (two independent writings of the equations), and the four
EVA readers on made-up records (a program without the counters gives
``None``, as the parent has to)."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

from benchmarks import costs_eva

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
READERS = ("eva_decode_attention_roofline", "eva_attention_share.tput",
           "eva_summaries_per_decode_row.tput", "eva_window_keys_per_decode_row.tput")
CELL = "evabyte-answer-long"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "evabyte-6.5b-d8.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_costs_are_the_issues_arithmetic(cfg):
    # 16 KiB a token a layer: 32 heads x 128 x 2 (key and value) x 2 bytes
    assert costs_eva.key_value_bytes(cfg) == 16384
    assert costs_eva.summaries_per_window(cfg) == 128
    # a ring is 32 MiB a layer, a closed window's summaries 2 MiB
    assert 2048 * costs_eva.key_value_bytes(cfg) == 32 * 2 ** 20
    assert 128 * costs_eva.key_value_bytes(cfg) == 2 * 2 ** 20
    # the issue's mean row: ring half full, two closed windows = 20 MiB a layer
    assert costs_eva.row_bytes_at(cfg, 2 * 2048 + 1023) == 20 * 2 ** 20 + 16384
    # a row at a window's first position reads one key and its closed windows
    assert costs_eva.row_bytes_at(cfg, 2048) == (1 + 128) * 16384 + 16384
    assert costs_eva.decode_attention_bytes(cfg, 10, 5, 2) == 15 * 16384 + 2 * 16384


def test_the_file_is_the_catalog_row_but_for_depth(cfg):
    rows = [json.loads(x) for x in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    row = next(r for r in rows if r["name"] == "EvaByte")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 32} and cfg["num_hidden_layers"] == 8
    assert cfg["reference_sample"] == {"n": 4, "lo": 3968, "hi": 4090, "tokens": 128}
    assert set(cfg["reference_tolerance"]) == {
        "worst_nat", "mean_nat", "median_nat", "first_summary_rel", "set_from"}


def test_the_adapter_builds_the_published_widths(cfg):
    from benchmarks.adapters import evabyte

    m = evabyte.model_config(cfg)
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim) == (4096, 32, 32, 128)
    assert (m.window_size, m.chunk_size, m.num_pred_heads, m.vocab_size) == (2048, 16, 8, 320)
    assert (m.intermediate_size, m.num_layers, m.rope_theta) == (11008, 8, 100000.0)
    with pytest.raises(ValueError, match="EVA attention"):
        evabyte.model_config({**cfg, "attention_class": "softmax"})
    with pytest.raises(ValueError, match="published switches"):
        evabyte.model_config({**cfg, "norm_add_unit_offset": False})


def test_the_traffic_is_the_issues(cfg):
    with open(os.path.join(BENCH, "traffic", "answer-long.json")) as f:
        t = json.load(f)
    assert (t["loop"], t["clients"], t["pool"], t["pool_seed"], t["order"]) == (
        "closed", 24, 256, 0, "fixed")
    assert t["prompt"] == {"dist": "lognormal", "median": 4096, "sigma": 0.5,
                           "min": 2048, "max": 8192}
    assert t["output"] == {"dist": "lognormal", "median": 1024, "sigma": 0.25,
                           "min": 768, "max": 1536}
    assert t["drain_s"] == 40 and "shared_prefix" not in t
    assert t["engine"] == {"max_batch_size": 24, "prefill_buckets": [128, 256, 512],
                           "max_context": 10240}
    # 24 whole rings and the scratch page; five whole windows of context
    assert cfg["engine"]["num_blocks"] == 1 + 24 * 2048 // 16
    assert t["engine"]["max_context"] == 5 * cfg["window_size"]


def test_the_reference_is_the_programs_whole_sequence_twin_at_a_tests_size():
    """Two writings of the equations (the reference a window and a block of
    queries at a time with its own softmax; the twin one masked softmax over
    keys and summaries together), float32, over five windows."""
    import jax
    import jax.numpy as jnp

    from benchmarks.adapters import evabyte as adapter
    from benchmarks.reference import evabyte_decoder as ref
    from dynamo_tpu.models import evabyte

    small = {
        "model_type": "evabyte", "attention_class": "eva", "vocab_size": 320, "hidden_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
        "head_dim": 16, "intermediate_size": 128, "rope_theta": 100000, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 32768, "tie_word_embeddings": False, "torch_dtype": "float32",
        "window_size": 64, "chunk_size": 16, "num_pred_heads": 8, "norm_add_unit_offset": True,
        "fp32_skip_add": True, "fp32_logits": True, "mixedp_attn": True, "fp32_ln": False,
        "attention_bias": False, "rope_scaling": None,
    }
    m = adapter.model_config(small)
    params = evabyte.init_params(jax.random.PRNGKey(5), m)
    ids = np.random.default_rng(0).integers(0, 320, 5 * 64 - 7)
    padded = np.concatenate([ids, np.zeros(7, np.int64)])
    with jax.default_matmul_precision("highest"):
        hidden = evabyte.forward(params, m, jnp.asarray(padded), jnp.arange(len(padded)),
                                 evabyte.stateless_attend(m))
        want = jax.nn.log_softmax(evabyte.lm_logits(params, m, hidden), axis=-1)[: len(ids)]
    rows = list(range(len(ids)))
    got, held = ref.logprobs(small, params, ids.tolist(), rows, held_after=len(ids))
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    assert held["ks"].shape == (4 * 4, 4, 16)      # four closed windows of four chunks
    # every switch computes something else
    for name, sw in {**ref.wrong_variants(small), "skip_layer": {"skip_layer": 1}}.items():
        other, _ = ref.logprobs(small, params, ids.tolist(), rows, **sw)
        assert np.abs(other - got).max() > (1e-4 if "int8" in name or "bf16" in name else 1e-3), name


def step(phase, rows=None, keys=0, summaries=0, closed=0, steps=0):
    return types.SimpleNamespace(phase=phase, eva_rows_attended=rows, eva_window_keys=keys,
                                 eva_summaries_read=summaries, eva_windows_closed=closed,
                                 eva_decode_steps=steps)


class Trace:
    busy_s = 4.0

    def __init__(self, kernel_s, ragged_s=0.0):
        self.by = {"eva_decode_attention": kernel_s, "ragged_paged_attention": ragged_s}

    def op_seconds(self, pattern):
        import re

        return sum(s for name, s in self.by.items() if re.search(pattern, name))


def test_the_four_readers_on_made_up_records(cfg):
    ctx = types.SimpleNamespace(cfg=cfg, trace=Trace(1.5, 0.5), trace_host=(10.0, 15.0),
                                peaks={"hbm_bytes_per_s": 819e9})
    # two horizons of 24 rows x 8 steps x 8 layers, each row 1 000 keys and
    # 256 summaries; a mixed step (not the kernel's); a prefill; one outside
    n = 24 * 8 * 8
    ctx.steps = ctx.steps_all = [
        (11.0, step("decode", n, n * 1000, n * 256, 1, 8)),
        (12.0, step("decode", n, n * 1000, n * 256, 0, 8)),
        (13.0, step("mixed", 20 * 8, 20 * 8 * 500, 20 * 8 * 128, 0, 1)),
        (14.0, step("prefill")),
        (20.0, step("decode", n, n * 2000, 0, 0, 8)),
    ]
    need = 2 * n * (1256 * 16384 + 16384)
    assert reader("eva_decode_attention_roofline")(ctx) == pytest.approx(
        100 * need / 819e9 / 1.5)
    assert reader("eva_attention_share.tput")(ctx) == pytest.approx(50.0)
    rows = 3 * n + 160
    assert reader("eva_summaries_per_decode_row.tput")(ctx) == pytest.approx(
        (2 * n * 256 + 160 * 128) / rows)
    assert reader("eva_window_keys_per_decode_row.tput")(ctx) == pytest.approx(
        (2 * n * 1000 + 160 * 500 + n * 2000) / rows)


def test_a_program_without_the_counters_gives_none(cfg):
    """The parent under this PR's benchmark files: nothing to read, no raise."""
    bare = types.SimpleNamespace(phase="decode")
    ctx = types.SimpleNamespace(cfg=cfg, trace=Trace(0.0), trace_host=(10.0, 15.0),
                                peaks={"hbm_bytes_per_s": 819e9}, engine={"decode_steps": 8},
                                steps=[(11.0, bare)], steps_all=[(11.0, bare)])
    for name in READERS:
        assert reader(name)(ctx) is None
    ctx.trace = None
    assert reader("eva_decode_attention_roofline")(ctx) is None
    assert reader("eva_attention_share.tput")(ctx) is None


def test_the_manifest_lists_the_cell_where_the_issue_said():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("evabyte-6.5b-d8", "answer-long", 1)
    assert m["workloads"][-1] is cell and len(m["workloads"]) == 9 and len(m["configs"]) == 8
    conf = m["configs"][-1]
    assert conf["name"] == "evabyte-6.5b-d8" and conf["reduced"] == ["num_hidden_layers"]
    assert conf["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    listed = {e["name"] for e in m["per_layer"] if CELL in e.get("workloads", [])}
    solar = {e["name"] for e in m["per_layer"] if "solaropen2-reason-wide" in e.get("workloads", [])}
    assert set(READERS) <= listed
    # what the long-answer cell lists, less what reads another family's launch
    assert listed - set(READERS) == {
        n for n in solar if not n.startswith("kda_")
        and n not in ("paged_decode_attention_roofline", "moe_grouped_matmul_roofline")}
    for e in m["end_to_end"]:
        if e["name"] == "output_tokens_per_s":
            assert e["workloads"][-1] == CELL
        if e["name"] == "tpot_p95_ms":
            assert CELL not in e["workloads"]
    for name in READERS:
        e = next(e for e in m["per_layer"] if e["name"] == name)
        assert e["workloads"] == [CELL] and e["moves"] == "output_tokens_per_s"
