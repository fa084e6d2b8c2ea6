"""A family is one module (models/registry.py ``FAMILIES``): a family defined
HERE is served with no edit outside this file; every preset's ``param_specs``
and what it is refused, thing by thing, are the parent's (recorded from a
checkout of PR 57 by tools/record_family_table.py into
tests/data/family_table_pr57.json); the presets keep their names."""

import asyncio
import dataclasses
import functools
import json
import os
import types

import pytest

from benchmarks import system
from dynamo_tpu.engine.__main__ import PRESETS
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models import falcon_h1, llama, mla, registry, solar_open2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLE = os.path.join(ROOT, "tests/data/family_table_pr57.json")

# presets of families added since the table was recorded: the parent has no
# answer to hold them to (tests/test_ouro.py holds what they are refused)
SINCE = {"tiny-ouro", "ouro-2.6b"}
# every worker preset the parent had, and the published and tiny
# configurations that have none (the benchmark's adapters build those)
PRESET_CASES = {
    **{name: make for name, make in PRESETS.items() if name not in SINCE},
    "tiny-mla-dsa": mla.MlaConfig.tiny_mla_dsa,
    "axk1": mla.MlaConfig.axk1,
    "tiny-falcon-h1": falcon_h1.FalconH1Config.tiny,
    "falcon-h1-34b": falcon_h1.FalconH1Config.falcon_h1_34b,
    "tiny-solar-open2": solar_open2.SolarOpen2Config.tiny,
    "solar-open2-250b": solar_open2.SolarOpen2Config.solar_open2_250b,
}
# one thing asked at a time, as engine construction (and the transfer
# wiring) asks it
ASKED = {
    "tp": dict(tp=2), "pp": dict(pp=2), "sp": dict(sp=2), "spec": dict(spec=True),
    "lora": dict(lora=True), "kv_quantized": dict(kv_quantized=True),
    "vision": dict(vision=True), "transfer": dict(transfer=True), "kvbm": dict(kvbm=True),
}


def specs_as_text(specs):
    return {"default": str(specs["default"]),
            **{part: {k: str(v) for k, v in specs[part].items()} for part in ("top", "layer")}}


def refusal(registry, check, cfg, asked):
    """What ``check`` says of ``cfg`` once the engine has placed its latent
    (``place_latent`` is not asked about the transfer plane or KVBM), or None."""
    placing = {k: v for k, v in asked.items() if k not in ("transfer", "kvbm")}
    try:
        check(registry.place_latent(cfg, **placing), **asked)
    except ValueError as e:
        return str(e)
    return None


@functools.lru_cache(maxsize=None)
def parents_table():
    with open(TABLE) as f:
        return json.load(f)


def parents(name):
    return parents_table()[name]


@pytest.mark.parametrize("preset", sorted(PRESET_CASES))
def test_param_specs_are_the_parents_key_by_key(preset):
    got = specs_as_text(registry.param_specs(PRESET_CASES[preset]()))
    assert got == parents(preset)["param_specs"]


@pytest.mark.parametrize("asked", sorted(ASKED))
@pytest.mark.parametrize("preset", sorted(PRESET_CASES))
def test_what_is_refused_is_what_the_parent_refused(preset, asked):
    """Refused or not as the parent's four functions and the engine's three
    predicates answered, in the engine's order; where it is refused, what the
    parent named (the trait) and why are both in the answer, which may name
    the family's other traits beside them."""
    want = parents(preset)["refused"][asked]
    got = refusal(registry, registry.check_supported, PRESET_CASES[preset](), ASKED[asked])
    assert (got is None) == (want is None), (got, want)
    for part in (want or "").split(" does not run with "):
        assert part in (got or "")


def test_the_presets_keep_their_names():
    assert len(PRESETS) == 26 + len(SINCE) and set(PRESETS) - SINCE == {
        "tiny", "qwen3-0.6b", "llama3-8b", "llama3-70b", "tiny-moe", "qwen3-30b-a3b",
        "tiny-gptoss", "gpt-oss-20b", "gpt-oss-120b", "tiny-gemma2", "tiny-gemma3",
        "gemma2-2b", "gemma3-4b", "tiny-mla", "tiny-mla-moe", "deepseek-v2-lite",
        "deepseek-v3", "tiny-vl", "tiny-evabyte", "evabyte-6.5b", "tiny-cohere2-moe",
        "command-a-plus", "tiny-dots3-note", "dots3-note", "tiny-minicpm-sala", "minicpm-sala",
    }
    for name, make in PRESETS.items():
        assert type(make()) is registry.family(make()).CONFIG, name


def test_a_subclass_finds_its_own_module_and_a_strangers_class_none():
    assert registry.family(mla.MlaConfig.tiny_mla()) is mla
    assert registry.family(type("Mine", (mla.MlaConfig,), {}).tiny_mla()) is mla
    assert registry.family(type("Dense", (llama.LlamaConfig,), {})()) is llama
    with pytest.raises(TypeError, match="no family's configuration"):
        registry.family(object())


# ---------------------------------------------------------------------------
# a twelfth family, defined here: its own configuration class, llama's layers
# ---------------------------------------------------------------------------

TwelfthConfig = dataclasses.make_dataclass(
    "TwelfthConfig",
    [(f.name, f.type, dataclasses.field(default=f.default))
     for f in dataclasses.fields(llama.LlamaConfig)],
    namespace={"q_size": llama.LlamaConfig.q_size, "kv_size": llama.LlamaConfig.kv_size},
    frozen=True,
)


def twelfth_family():
    module = types.ModuleType("twelfth")
    module.CONFIG = TwelfthConfig
    module.init_params, module.forward, module.lm_logits = (
        llama.init_params, llama.forward, llama.lm_logits)
    module.layer_specs = llama.layer_specs
    return module


def tokens_of(mcfg):
    eng = TpuEngine(TpuEngineConfig(
        model=mcfg, num_blocks=32, block_size=16, max_batch_size=2, max_context=128,
        prefill_buckets=(32,), decode_steps=4, decode_pipeline=1, seed=5))
    try:
        return asyncio.run(system.generate(eng, "r", list(range(3, 23)), 8))["tokens"]
    finally:
        eng.stop()


def test_a_family_defined_in_a_test_is_served_with_no_other_edit(monkeypatch):
    with pytest.raises(TypeError, match="no family's configuration"):
        registry.family(TwelfthConfig())
    twelfth = twelfth_family()
    monkeypatch.setattr(registry, "FAMILIES", registry.FAMILIES + (twelfth,))
    cfg = TwelfthConfig()
    assert registry.family(cfg) is twelfth and registry.family(llama.LlamaConfig()) is llama
    assert not registry.supports_pp(cfg) and registry.page_groups(cfg) == (((0, 1, 2, 3), None),)
    # what it states nothing of is refused: it has no adapter path, and no stages
    with pytest.raises(ValueError, match="LoRA serving covers"):
        registry.check_supported(cfg, lora=True)
    with pytest.raises(ValueError, match="TwelfthConfig is not stacked"):
        registry.check_pp_supported(cfg)
    registry.check_supported(cfg, tp=2, sp=2, spec=True, kv_quantized=True, transfer=True)
    got = tokens_of(cfg)
    assert len(got) == 8 and got == tokens_of(llama.LlamaConfig())
