"""By hand (``python -m pytest benchmarks/tests/test_falcon_h1.py -q``):
``costs_ssm.py`` against ISSUE 39's arithmetic, the configuration file
through its adapter and against the catalog's keys, and the three
state-space readers on made-up records (a program without the counters
gives ``None``, as the parent has to)."""

import importlib.util
import json
import os
import types

import pytest

from benchmarks import costs_ssm

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "falcon-h1-34b-d6-v8.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_costs_are_the_issues_arithmetic(cfg):
    assert costs_ssm.state_elements(cfg) == 32 * 128 * 256
    # 8.39 MB of state traffic a row a layer, and the row's own operands
    assert 2 * costs_ssm.state_elements(cfg) * 4 == 8_388_608
    assert costs_ssm.row_vector_bytes(cfg) == (2 * 4096 + 2 * 512) * 2 + 32 * 4
    assert costs_ssm.state_update_bytes(cfg, 1) == 8_388_608 + 18_560
    assert costs_ssm.state_update_bytes(cfg, 128, 6) == 128 * 6 * (8_388_608 + 18_560)


def test_the_file_is_the_catalog_row_but_for_depth_and_vocabulary(cfg):
    rows = [json.loads(x) for x in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    row = next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 72, "vocab_size": 261120}
    assert cfg["vocab_size"] * 8 == 261120


def test_the_adapter_builds_the_published_widths(cfg):
    from benchmarks.adapters import falcon_h1

    m = falcon_h1.model_config(cfg)
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim) == (5120, 20, 4, 128)
    assert (m.mamba_n_heads, m.mamba_d_head, m.mamba_d_state, m.mamba_n_groups) == (32, 128, 256, 2)
    assert (m.in_proj_size, m.conv_dim, m.intermediate_size, m.num_layers) == (9248, 5120, 21504, 6)
    assert m.rope_theta == 1e11 and m.mamba_chunk_size == 128 and m.vocab_size == 32640


def step(phase, rows=None, steps=0, scanned=0):
    return types.SimpleNamespace(phase=phase, ssm_rows_updated=rows, ssm_decode_steps=steps,
                                 ssm_tokens_scanned=scanned)


class Trace:
    busy_s = 4.0

    def __init__(self, seconds):
        self.seconds = seconds

    def op_seconds(self, pattern):
        return self.seconds


def test_the_three_readers_on_made_up_records(cfg):
    ctx = types.SimpleNamespace(cfg=cfg, trace=Trace(2.0), trace_host=(10.0, 15.0),
                                peaks={"hbm_bytes_per_s": 819e9})
    # two horizons of 100 rows (8 steps x 6 layers), a mixed step of 90, a prefill, one outside
    ctx.steps = ctx.steps_all = [
        (11.0, step("decode", 100 * 48, 8)), (12.0, step("decode", 100 * 48, 8)),
        (13.0, step("mixed", 90 * 6, 1, scanned=6 * 128)), (14.0, step("prefill", 0, 0, 6 * 128)),
        (20.0, step("decode", 128 * 48, 8)),
    ]
    rows = 2 * 100 * 48 + 90 * 6
    need_s = rows * (8_388_608 + 18_560) / 819e9
    assert reader("ssm_state_update_roofline")(ctx) == pytest.approx(100 * need_s / 2.0)
    assert reader("ssm_update_share.tput")(ctx) == pytest.approx(50.0)
    per_step = (2 * 100 * 48 + 90 * 6 + 128 * 48) / 6 / (8 + 8 + 1 + 8)
    assert reader("ssm_rows_per_decode_step.tput")(ctx) == pytest.approx(per_step)


def test_a_program_without_the_counters_gives_none(cfg):
    """The parent under this PR's benchmark files: nothing to read, no raise."""
    bare = types.SimpleNamespace(phase="decode")
    ctx = types.SimpleNamespace(cfg=cfg, trace=Trace(0.0), trace_host=(10.0, 15.0),
                                peaks={"hbm_bytes_per_s": 819e9},
                                steps=[(11.0, bare)], steps_all=[(11.0, bare)])
    for name in ("ssm_state_update_roofline", "ssm_update_share.tput", "ssm_rows_per_decode_step.tput"):
        assert reader(name)(ctx) is None
    ctx.trace = None
    assert reader("ssm_state_update_roofline")(ctx) is None
