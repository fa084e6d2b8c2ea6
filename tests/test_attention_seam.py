"""The attention seam end to end (ops/paged_attention.py behind the engine's
step programs): what a Pallas engine's lone ``prefill`` program is made of,
and that it serves every bucket.

Per-question parity with the pure-JAX twins is in test_pallas_ops.py (chunk,
decode), test_unified_attention.py (ragged) and test_kv_quant.py (int8).
"""

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.gptoss import GptOssConfig
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.parallel.mesh import make_mesh
from dynamo_tpu.runtime.engine import Context

BS, CONTEXT = 16, 256  # 16 pages a table
MODELS = {
    "dense": lambda: LlamaConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=128, dtype=jnp.float32,
    ),
    "windowed": lambda: GptOssConfig.tiny_gptoss(vocab_size=512),
}


def _engine(model, use_pallas):
    """Buckets 64 and 192 at 16-token pages: multiples of neither 128
    queries nor 512 keys, so no tile grid a kernel might want holds them.
    DTPU_MIXED is off suite-wide: every prefill is a lone one."""
    cfg = TpuEngineConfig(
        model=MODELS[model](), num_blocks=40, block_size=BS, max_batch_size=2,
        max_context=CONTEXT, prefill_buckets=(64, 192), decode_steps=1,
        decode_pipeline=1, use_pallas=use_pallas,
    )
    return TpuEngine(cfg, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))


async def _generate(engine, rid, prompt, n):
    req = PreprocessedRequest(
        request_id=rid, model="m", token_ids=prompt,
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        sampling=SamplingOptions(temperature=0.0),
    )
    toks = []
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
    return toks


def _context_gathers(jaxpr, pages):
    """Gathers of ``pages`` whole pages ([pages, BS, kv_heads, d] out of a
    cache) anywhere under ``jaxpr``: what ``att.gather_kv`` over one padded
    block table traces to."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            shape = eqn.outvars[0].aval.shape
            n += len(shape) == 4 and shape[:2] == (pages, BS)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _context_gathers(sub, pages)
    return n


@pytest.mark.parametrize("model", sorted(MODELS))
async def test_pallas_prefill_program_gathers_no_padded_context(model):
    """The ``prefill`` program of a Pallas (interpreted) engine streams a
    chunk's pages inside the ragged kernel: no gather of the table's
    ``max_blocks_per_seq`` pages is left in it, windowed layers or not. The
    pure-JAX engine's program has one for K and one for V a layer (the walk
    finds what it looks for)."""
    found = {}
    for use_pallas in (False, True):
        engine = _engine(model, use_pallas)
        seen = []
        prefill = engine._prefill_fn

        def record(*args, prefill=prefill, seen=seen):
            seen.append(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
                args,
            ))
            return prefill(*args)

        engine._prefill_fn = record
        try:
            # max_tokens 1: the prefill samples it, no decode program is built
            await _generate(engine, "p", list(range(5, 55)), 1)
        finally:
            engine.stop()
        jaxpr = jax.make_jaxpr(prefill)(*seen[0]).jaxpr
        found[use_pallas] = _context_gathers(jaxpr, CONTEXT // BS)
    layers = MODELS[model]().num_layers
    assert found == {False: 2 * layers, True: 0}


async def test_lone_prefill_off_the_old_tile_grid_is_token_identical():
    """Prompts of 50 and 150 tokens (buckets 64 and 192) and a few decode
    steps: the interpreted-Pallas engine's greedy tokens equal the pure-JAX
    engine's, whatever the bucket."""
    prompts = [list(range(7, 57)), [(11 * i) % 500 + 3 for i in range(150)]]
    streams = {}
    for use_pallas in (False, True):
        engine = _engine("dense", use_pallas)
        try:
            streams[use_pallas] = [
                await _generate(engine, f"r{i}", p, 4)
                for i, p in enumerate(prompts)
            ]
        finally:
            engine.stop()
    assert all(len(s) == 4 for s in streams[True])
    assert streams[True] == streams[False]
