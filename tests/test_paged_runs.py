"""The host's count of what the decode-only kernel reads by the chunk
(``StepStats.paged_chunks_whole`` / ``.paged_chunks_run``, engine
``_count_paged``) against the rule the kernel itself is handed
(``ops/pallas_paged.chunk_runs`` of the same tables, ``chunk_pages``), and
through a served engine with the kernels interpreted."""

import asyncio
import types

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.telemetry import run_chunk_share
from dynamo_tpu.ops import pallas_paged as paged

from test_engine import greedy_req, run_req, tiny_engine

BS, CP, LAYERS = 4, 4, 3


def _by_the_kernels_rule(tables, held, contexts, steps):
    """(whole, run) a layer: each row's each step the slow way, from the
    flags the launch would be handed for these tables."""
    flags = np.asarray(paged.chunk_runs(jnp.asarray(tables), CP))
    whole = run = 0
    for r, ctx in enumerate(contexts):
        for k in range(steps if ctx else 0):
            w = min(-(-(ctx + k) // BS) // CP, held[r] // CP)
            whole += w
            run += int(flags[r, :w].sum())
    return whole, run


@pytest.mark.parametrize("steps", [1, 5, 8, 40])
@pytest.mark.parametrize("tables", ["runs", "shuffled", "mixed"])
def test_the_hosts_count_is_the_kernels_chunk_rule(tables, steps):
    """Rows of several chunks, runs or not, a row that is empty, one under a
    chunk, one whose chunk fills inside the horizon, a horizon longer than a
    chunk; counted twice (a request's runs are looked at once a chunk)."""
    rng = np.random.default_rng(steps)
    mb = 6 * CP
    ids = 1 + np.arange(5 * mb).reshape(5, mb)
    if tables == "shuffled":
        ids = rng.permutation(ids.reshape(-1)).reshape(5, mb)
    elif tables == "mixed":
        ids[:, CP : 2 * CP] = ids[:, CP : 2 * CP][:, ::-1]
        ids[2, 4 * CP + 1] += 500
    contexts = [3 * CP * BS + 2, 0, CP * BS - 5, 2 * CP * BS - 2, 5 * CP * BS]
    # what a request holds: the pages of its context and of the horizon
    held = [min(mb, -(-(c + steps) // BS)) if c else 0 for c in contexts]
    seqs = [
        None if not c else types.SimpleNamespace(
            block_ids=[int(x) for x in ids[r, : held[r]]], run_chunks=[0])
        for r, c in enumerate(contexts)
    ]
    eng = types.SimpleNamespace(
        _paged_layers={i: CP for i in range(LAYERS)}, _paged_counts=[0, 0],
        cfg=types.SimpleNamespace(block_size=BS),
    )
    want = _by_the_kernels_rule(ids, held, contexts, steps)
    assert want[0] > 0
    for n in (1, 2):
        TpuEngine._count_paged(eng, seqs, contexts, steps)
        assert eng._paged_counts == [n * LAYERS * want[0], n * LAYERS * want[1]]
    if tables == "runs":
        assert want[0] == want[1]
    if tables == "shuffled":
        assert want[1] == 0


def test_no_layer_of_the_decode_kernels_counts_nothing():
    eng = types.SimpleNamespace(_paged_layers={}, _paged_counts=[0, 0])
    TpuEngine._count_paged(eng, [object()], [100], 8)
    assert eng._paged_counts == [0, 0]


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_a_served_engine_counts_its_decode_rows_whole_chunks(decode_steps):
    """A tiny engine with the kernels interpreted: tables of 16 pages are one
    chunk, whole once a context passes 60 of 64 tokens; a fresh pool hands
    out consecutive ids, so it is a run. Single steps count exactly the
    contexts 61-63 in both layers; the pure-JAX engine counts nothing."""

    async def drive(use_pallas):
        eng = tiny_engine(
            use_pallas=use_pallas, max_context=64, decode_steps=decode_steps,
            prefill_buckets=(64,), mixed_admission=False)
        steps = []
        eng.stats_hook = steps.append
        try:
            toks, _ = await run_req(
                eng, greedy_req("a", list(range(40, 97)), max_tokens=7))
        finally:
            eng.stop()
        return toks, steps, dict(eng._paged_layers)

    toks, steps, layers = asyncio.run(asyncio.wait_for(drive(True), 420))
    assert len(toks) == 7 and layers == {0: 16, 1: 16}
    whole = sum(s.paged_chunks_whole or 0 for s in steps)
    run = sum(s.paged_chunks_run or 0 for s in steps)
    assert whole == run > 0 and run_chunk_share(steps, "paged") == 1.0
    if decode_steps == 1:
        assert whole == 3 * 2
    toks2, steps2, layers2 = asyncio.run(asyncio.wait_for(drive(False), 420))
    assert toks2 == toks and layers2 == {}
    assert all(s.paged_chunks_whole is None for s in steps2)
