"""By hand (``python -m pytest benchmarks/tests/test_ouro.py -q``):
``costs_ouro.py`` against ISSUE 60's arithmetic (19.9 GB of weights a decode
step, 1 572 864 B of cache a token), the configuration file through its
adapter and against the catalog's keys, the adapter's parameter names against
the reference's, the reference's switches, the traffic file's parameters,
and the four new readers on made-up records (a program without the counters
gives ``None``, as the parent has to). The reference against the engine at a
test's size is tier-1's (``tests/test_ouro.py``)."""

import importlib.util
import inspect
import json
import os
import re
import types

import pytest

from benchmarks import costs, costs_ouro

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
READERS = ("ouro_step_mfu", "ouro_decode_step_roofline", "ouro_attention_share.tput",
           "ouro_passes_per_token.tput")
CELL = "ouro26b-reason-steps"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_costs_are_the_issues_arithmetic(cfg):
    c = costs_ouro
    assert (c.passes(cfg), c.page_slots(cfg)) == (4, 192)
    # a layer: 4 x 2048^2 + 3 x 2048 x 5632 + 4 x 2048
    assert c.layer_params(cfg) == 51_388_416
    # the model: 48 layers, embedding + head 201.3 M, the final norm, the gate
    model = 48 * c.layer_params(cfg) + 2 * c.head_params(cfg) + 2048 + 2049
    assert model == 2_667_974_657
    # the cache: 8 KiB a token a slot, 1.5 MiB a token over 192 slots
    assert costs.kv_bytes_per_token_per_layer(cfg) == 8192
    assert c.kv_bytes_per_token(cfg) == 1_572_864
    # a 16-token page over all slots 25.2 MB; 344 of them 8.66 GB
    assert 16 * c.kv_bytes_per_token(cfg) == 25_165_824
    assert 344 * 16 * c.kv_bytes_per_token(cfg) == pytest.approx(8.66e9, rel=1e-3)
    # a decode step: FOUR reads of the layers' 4.93 GB and the head's 0.2 GB: 19.9 GB,
    # 24.3 ms at 819 GB/s before a key is read
    assert c.weight_bytes_per_step(cfg) == 2 * (4 * (48 * 51_388_416 + 2048) + 2048 * 49152)
    assert c.weight_bytes_per_step(cfg) == pytest.approx(19.93e9, rel=1e-3)
    assert c.weight_bytes_per_step(cfg) / PEAKS["hbm_bytes_per_s"] == pytest.approx(24.3e-3, rel=5e-3)
    # 8 rows with 2 300 tokens in flight: 3.6 GB of keys more, 4.4 ms
    step = c.decode_step_bytes(cfg, 8, 2300)
    assert step - c.weight_bytes_per_step(cfg) == (2300 + 8) * 1_572_864
    assert (2300 * 1_572_864) / PEAKS["hbm_bytes_per_s"] == pytest.approx(4.4e-3, rel=1e-2)
    assert step / PEAKS["hbm_bytes_per_s"] == pytest.approx(28.8e-3, rel=1e-2)
    # FLOPs: 2 a matrix weight a token a pass; a key position in a slot 8 192
    assert c.attention_flops_per_key(cfg) == 8192
    mm = 2 * 4 * 48 * costs.matmul_params_per_layer(cfg)
    assert c.step_flops(cfg, 1, 0, 0) == mm == pytest.approx(19.73e9, rel=1e-3)
    assert c.step_flops(cfg, 8, 8 * 300 * 192, 8) == pytest.approx(
        8 * mm + 8192 * 8 * 300 * 192 + 2 * 8 * 2048 * 49152)
    # a 128-token prompt's chunk: 128 x 129 / 2 key positions a slot
    assert c.chunk_slot_keys(cfg, 128, 0) == 8256 * 192


def test_the_file_is_the_catalog_row_uncut(cfg):
    rows = [json.loads(x) for x in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    row = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert cfg["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if cfg.get(k, "absent") != v] == []
    assert cfg["reduced"] == []
    assert (cfg["total_ut_steps"], cfg["early_exit_threshold"], len(cfg["layer_types"])) == (4, 1, 48)
    assert cfg["reference_sample"] == {"n": 8, "lo": 192, "hi": 256, "tokens": 128}
    assert {"worst_nat", "mean_nat", "first_slot_cache_rel", "last_slot_cache_rel",
            "set_from"} <= set(cfg["reference_tolerance"])
    assert set(cfg["assumed"]) >= {"output_norms", "final_norm_every_pass", "cache_slot_per_pass",
                                   "attention_bias", "positions", "exit_gate", "torch_dtype",
                                   "weights"}
    assert "deployment" in cfg and "memory_layout" in cfg
    assert cfg["engine"]["block_size"] == 16 and cfg["engine"]["tp"] == 1


def test_the_adapter_builds_the_published_widths(cfg):
    from benchmarks.adapters import ouro
    from dynamo_tpu.models import registry

    m = ouro.model_config(cfg)
    assert (m.hidden_size, m.num_layers, m.vocab_size, m.passes) == (2048, 48, 49152, 4)
    assert (m.num_heads, m.num_kv_heads, m.head_dim, m.intermediate_size) == (16, 16, 128, 5632)
    assert (m.rope_theta, m.rms_norm_eps, m.max_position) == (1e6, 1e-6, 65536)
    assert not m.tie_embeddings and m.out_norms and m.early_exit_threshold == 1.0
    assert registry.page_passes(m) == 4 and registry.page_slots(m) == 192
    assert len(registry.page_layers(m)) == 48
    with pytest.raises(ValueError, match="different numbers of passes"):
        ouro.model_config({**cfg, "early_exit_threshold": 0.9})
    with pytest.raises(ValueError, match="plain rotary"):
        ouro.model_config({**cfg, "rope_scaling": {"factor": 4}})
    with pytest.raises(ValueError, match="full attention"):
        ouro.model_config({**cfg, "use_sliding_window": True})


def test_the_adapters_names_are_the_references_and_the_switches_are_named(cfg):
    import jax
    import jax.numpy as jnp

    from benchmarks.adapters import ouro as adapter
    from benchmarks.reference import ouro_decoder as ref
    from dynamo_tpu.models.ouro import OuroConfig, init_params

    mcfg = OuroConfig.tiny(dtype=jnp.float32)
    n = 8
    pools = [jnp.zeros((mcfg.passes * n, 16, 4, 64)) + l for l in range(mcfg.num_layers)]
    eng = types.SimpleNamespace(
        mcfg=mcfg, params=init_params(jax.random.PRNGKey(0), mcfg),
        cfg=types.SimpleNamespace(num_blocks=n), k_caches=pools, v_caches=pools)
    out = adapter.reference_params(eng)
    assert set(out) == {"embed", "final_norm", "lm_head", "layers", "exit_gate_w",
                        "exit_gate_b", "held"}
    named = set(re.findall(r"``(\w+)``", ref.__doc__.split("Parameters (")[1].split("WRONG")[0]))
    for lp in out["layers"]:
        assert set(lp) <= named and len(lp) == 11
    # the held pools: slot (0, 0) the first layer's first pool, slot (T-1, L-1) the
    # last layer's last, each num_blocks pages
    first, last = out["held"]["first"], out["held"]["last"]
    assert first[0].shape == last[1].shape == (n, 16, 4, 64)
    assert float(first[0][0, 0, 0, 0]) == 0 and float(last[0][0, 0, 0, 0]) == mcfg.num_layers - 1
    # every named wrong computation is a switch of logprobs (or compare's own)
    switches = set(inspect.signature(ref.logprobs).parameters) | {"shared_slot"}
    wrong = ref.wrong_variants(cfg)
    assert set(wrong) == {"one_pass_fewer", "final_norm_once", "no_out_norms", "read_pass0_slot",
                          "read_last_pass_slot", "positions_advanced", "skipped_layer", "cache_int8"}
    assert all(set(kw) <= switches for kw in wrong.values())
    assert wrong["one_pass_fewer"] == {"passes": 3} and wrong["skipped_layer"] == {"skip_layer": 24}
    # the harness's own two switches (run.py --calibrate) are logprobs' too
    assert {"skip_layer", "kv_bits"} <= switches


def test_the_traffic_is_the_issues(cfg):
    with open(os.path.join(BENCH, "traffic", "reason-steps.json")) as f:
        t = json.load(f)
    assert (t["loop"], t["clients"], t["pool"], t["pool_seed"], t["order"]) == (
        "closed", 8, 64, 0, "fixed")
    assert t["prompt"] == {"dist": "lognormal", "median": 128, "sigma": 0.5, "min": 64, "max": 256}
    assert t["output"] == {"dist": "lognormal", "median": 320, "sigma": 0.15, "min": 256, "max": 384}
    assert "shared_prefix" not in t and t["drain_s"] == 30
    assert t["engine"] == {"max_batch_size": 8, "prefill_buckets": [128, 256], "max_context": 672}
    # every row's whole table fits the pool beside the scratch page: 8 x 42 + 1
    assert cfg["engine"]["num_blocks"] >= 8 * (672 // 16) + 1
    # the harness asks longest prompt + output + two horizons below max_context
    assert 256 + 384 + 2 * 8 <= 672


def step(phase, tokens=None, keys=None, occupancy=8):
    return types.SimpleNamespace(
        phase=phase, batch_occupancy=occupancy, tokens=tokens or 0, ouro_stack_tokens=tokens,
        ouro_pass_tokens=None if tokens is None else 4 * tokens, ouro_slot_keys_read=keys)


class Trace:
    busy_s = 4.0

    def __init__(self, modules=(), **by):
        self.by, self.modules = by, list(modules)

    def op_seconds(self, pattern):
        return sum(s for name, s in self.by.items() if re.search(pattern, name))

    def module_durations_s(self, pattern):
        return [d for name, d in self.modules if re.search(pattern, name)]


def made_up(cfg):
    trace = Trace(modules=[("jit_decode_multi", 0.30), ("jit_decode_multi", 0.34),
                           ("jit_mixed_step", 0.05), ("jit_prefill", 0.04)],
                  paged_decode_attention=0.5, ragged_paged_attention=0.1, fusion=3.0)
    ctx = types.SimpleNamespace(cfg=cfg, trace=trace, trace_host=(10.0, 15.0), peaks=PEAKS,
                                engine={"decode_steps": 8, "max_batch_size": 8})
    horizon = 8 * 8                              # 8 rows x 8 steps
    ctx.steps = ctx.steps_all = [
        (9.0, step("decode", horizon, horizon * 300 * 192)),        # before the sub-window
        (11.0, step("decode", horizon, horizon * 300 * 192)),
        (12.0, step("decode", horizon, horizon * 340 * 192)),
        (13.0, step("mixed", 128 + 7, 7 * 320 * 192)),
        (14.0, step("prefill")),
    ]
    return ctx


def test_the_four_readers_on_made_up_records(cfg):
    ctx = made_up(cfg)
    c = costs_ouro
    assert reader("ouro_passes_per_token.tput")(ctx) == 4.0
    assert reader("ouro_attention_share.tput")(ctx) == pytest.approx(15.0)
    # a horizon: 8 steps' weights, the rows' keys once a slot, the fed tokens' written
    needed = 8 * c.weight_bytes_per_step(cfg) + 64 * 320 * 192 * 8192 + 64 * c.kv_bytes_per_token(cfg)
    assert reader("ouro_decode_step_roofline")(ctx) == pytest.approx(
        100 * needed / (0.32 * 819e9))
    # the whole step: two horizons and one mixed step ran whole in the sub-window
    horizon = c.step_flops(cfg, 64, 64 * 320 * 192, 64)
    mixed = c.step_flops(cfg, 135, 7 * 320 * 192 + c.chunk_slot_keys(cfg, 128, 0), 8)
    assert reader("ouro_step_mfu")(ctx) == pytest.approx(
        100 * (2 * horizon + mixed) / ((2 * 0.32 + 0.05) * 197e12))
    assert 0 < reader("ouro_step_mfu")(ctx) < 10


def test_a_program_without_the_counters_gives_none(cfg):
    """The parent under this PR's benchmark files: nothing to read, no raise."""
    ctx = made_up(cfg)
    bare = types.SimpleNamespace(phase="decode", queue_depth=0, tokens=16, batch_occupancy=8)
    ctx.steps = ctx.steps_all = [(11.0, bare)]
    for name in READERS:
        assert reader(name)(ctx) is None
    ctx = made_up(cfg)
    ctx.trace = None
    for name in READERS[:3]:
        assert reader(name)(ctx) is None


def test_the_manifest_lists_the_cell_where_the_issue_said():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro-2.6b", "reason-steps", 1)
    assert len(cell["why"]) <= 200
    conf = next(c for c in m["configs"] if c["name"] == "ouro-2.6b")
    assert conf["reduced"] == [] and conf["file"] == "benchmarks/configs/ouro-2.6b.json"
    assert conf["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    listed = {e["name"] for e in m["per_layer"] if CELL in e.get("workloads", [])}
    assert set(READERS) <= listed
    assert {"ttft_p50_ms.closed", "occupancy_mean.tput", "kv_active_share", "decode_step_ms.tput",
            "prefill_chunk_ms.tput", "chunk_step_ms_per_token.tput", "device_idle.tput",
            "programs_compiled_in_window.tput", "h2d_placements_per_step.tput",
            "admit_wait_p50_ms.closed", "submit_p50_ms.closed", "mixed_step_gap_ms.tput",
            "horizon_gap_ms.tput", "mixed_chained_share.tput", "paged_decode_attention_roofline",
            "paged_run_chunk_share.tput"} <= listed
    # prefill_mfu counts num_hidden_layers matrices a token: a quarter of this model's
    assert "prefill_mfu" not in listed
    assert CELL in next(e for e in m["end_to_end"] if e["name"] == "output_tokens_per_s")["workloads"]
    mine = [e for e in m["per_layer"] if e["name"] in READERS]
    assert [e["name"] for e in mine] == list(READERS)
    for e in mine:
        assert e["moves"] == "output_tokens_per_s" and e["workloads"] == [CELL]
