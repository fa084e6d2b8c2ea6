"""How a synchronous step hands its arguments to the device (PR 29).

The three synchronous runners (``_run_prefill_chunk``, ``_run_mixed_step``,
``_run_decode``) used to place every host array and scalar of a dispatch
with a ``jnp.asarray`` of its own: about 27 placements for a mixed step.
Now per-slot state travels through ``_dev`` (a compare on a steady step),
what changes every step is ONE packed int32 buffer (engine/step_args.py),
and ``StepStats.h2d_placements`` counts what was placed.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import step_args
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.engine.telemetry import EngineTelemetry
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.runtime import Context
from dynamo_tpu.runtime import metrics as M
from dynamo_tpu.runtime.multihost import MultihostRouter

MODEL = LlamaConfig(
    vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, intermediate_size=128, dtype=jnp.float32,
)
P_RESIDENT = [(i * 37 + 11) % 500 for i in range(30)]
P_SHORT = [(i * 29 + 5) % 500 for i in range(20)]
P_LONG = [(i * 53 + 7) % 500 for i in range(200)]   # 7 chunks of 32

# the settled numbers: a steady synchronous step places its packed buffer,
# and a chunk's three arrays where the prep thread had not placed them
PACKED = 1
CHUNK_ARRAYS = 3


def make_engine(mixed, **kw):
    kw.setdefault("decode_steps", 4)
    kw.setdefault("max_batch_size", 2)
    return TpuEngine(TpuEngineConfig(
        model=MODEL, num_blocks=128, block_size=16, max_context=512,
        prefill_buckets=(16, 32), decode_pipeline=2, mixed_admission=mixed,
        **kw,
    ))


def preq(rid, tokens, n, **sampling):
    sampling.setdefault("temperature", 0.0)
    return PreprocessedRequest(
        request_id=rid, model="m", token_ids=tokens,
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        sampling=SamplingOptions(**sampling),
    )


async def run_one(eng, req, decoding=None):
    toks = []
    async for out in eng.generate(req, Context()):
        toks.extend(out.token_ids)
        if decoding is not None and len(toks) > 1:
            decoding.set()      # past the prefill's token: a decode step ran
    return toks


async def arrive_while_decoding(eng, resident, *later):
    """``resident`` decodes; each of ``later`` is sent once the one before
    it has finished (the first once ``resident`` has decoded a token), so a
    later request's chunks meet the resident decode. Returns every stream."""
    first = asyncio.Event()
    t1 = asyncio.create_task(run_one(eng, resident, first))
    await asyncio.wait_for(first.wait(), 90)
    rest = [await run_one(eng, req) for req in later]
    return [await t1, *rest]


def steady(stats, phase):
    """The steps of ``phase`` that no admission, finish or new page came
    before: the step before ran the same program, it and the one before it
    held as many pages and rows, and nothing was admitted."""
    return [
        s for before, last, s in zip(stats, stats[1:], stats[2:])
        if last.phase == s.phase == phase and not s.admit_wait_s
        and before.kv_active_blocks == last.kv_active_blocks
        == s.kv_active_blocks
        and before.batch_occupancy == last.batch_occupancy
        == s.batch_occupancy
    ]


@pytest.mark.parametrize("runner", ["prefill", "mixed", "decode"])
async def test_a_steady_step_places_its_packed_buffer_and_nothing_else(runner):
    """Per-slot state costs a compare on a steady step: the dispatch places
    the step's one buffer (and a chunk's three arrays on a prep miss). The
    parent placed about 27 values a mixed step, 22 a chunk, 20 a decode."""
    # decode_steps 1: no horizon can be booked, so decode runs _run_decode
    eng = make_engine(runner == "mixed",
                      decode_steps=1 if runner == "decode" else 4)
    stats = []
    eng.stats_hook = stats.append
    try:
        if runner == "mixed":
            await arrive_while_decoding(
                eng, preq("r1", P_RESIDENT, 60), preq("r2", P_LONG, 2)
            )
        elif runner == "prefill":
            await run_one(eng, preq("r", P_LONG, 2))
        else:
            await run_one(eng, preq("r", P_RESIDENT, 40))
    finally:
        eng.stop()
    quiet = steady(stats, runner)
    assert len(quiet) >= 3, [(s.phase, s.kv_active_blocks) for s in stats]
    for s in quiet:
        allowed = PACKED + (
            CHUNK_ARRAYS if runner != "decode" and not s.prep_hit else 0
        )
        assert s.h2d_placements <= allowed, (runner, s)
    # and the least is reached: with the chunk's arrays prebuilt, ONE
    assert min(s.h2d_placements for s in quiet) == PACKED
    # /debug/worker carries the mean over its window beside loop_phases
    tele = EngineTelemetry(M.MetricsScope())
    for s in stats:
        tele.on_step(s)
    snap = tele.snapshot()
    assert snap["h2d_placements"] == round(
        sum(s.h2d_placements for s in stats[-128:]) / len(stats[-128:]), 3
    )


async def test_a_chained_mixed_step_places_its_packed_buffer_and_no_carry():
    """A mixed step launched on the carry of the one before it (ISSUE 42)
    takes that carry as it is, on the device: no placement. Its StepStats
    counts its own dispatch's placements, though it is made a tick later,
    when the step is read and the next one's are already being counted."""
    eng = make_engine(True)
    stats = []
    eng.stats_hook = stats.append
    try:
        await arrive_while_decoding(
            eng, preq("r1", P_RESIDENT, 60), preq("r2", P_LONG, 2)
        )
    finally:
        eng.stop()
    chained = [s for s in steady(stats, "mixed") if s.mixed_chained]
    assert len(chained) >= 3, [(s.phase, s.mixed_chained) for s in stats]
    for s in chained:
        assert s.h2d_placements == PACKED + (0 if s.prep_hit else CHUNK_ARRAYS), s
    assert any(s.prep_hit for s in chained)
    # and the mixed step that was NOT chained places no more for that
    first = next(s for s in stats if s.phase == "mixed")
    assert first.mixed_chained is False
    assert all(s.mixed_chained is None for s in stats if s.phase != "mixed")


async def _recycled_slot(eng, phases=None):
    """r1 stays resident; A (sampled) comes and goes; B, with another
    temperature, seed and top-k, is admitted into A's slot."""
    if phases is not None:
        eng.stats_hook = lambda s: phases.append(s.phase)
    return await arrive_while_decoding(
        eng,
        preq("r1", P_RESIDENT, 150),
        preq("A", P_SHORT, 4, temperature=0.9, seed=7),
        preq("B", P_LONG[:90], 8, temperature=1.3, seed=99, top_k=5),
    )


async def test_a_recycled_slot_is_sampled_with_the_new_requests_parameters():
    """``_dev`` compares by content, so what admission wrote into a
    recycled slot reaches the very next mixed step: B's stream equals B's
    stream alone on a fresh engine (slot 0, nothing cached) and under the
    split schedule."""
    split = make_engine(False)
    try:
        alone = await run_one(
            split, preq("B", P_LONG[:90], 8, temperature=1.3, seed=99, top_k=5)
        )
        s_r1, s_a, s_b = await _recycled_slot(split)
    finally:
        split.stop()
    mixed = make_engine(True)
    phases = []
    try:
        m_r1, m_a, m_b = await _recycled_slot(mixed, phases)
    finally:
        mixed.stop()
    assert "mixed" in phases        # B's last chunk, which samples, rode one
    assert m_b == alone == s_b
    assert (m_r1, m_a) == (s_r1, s_a)
    # A and B did not sample alike: the parameters mattered
    greedy = make_engine(False)
    try:
        g_b = await run_one(greedy, preq("B", P_LONG[:90], 8))
    finally:
        greedy.stop()
    assert g_b != alone


async def test_mixed_steps_and_horizons_share_the_cached_slot_arrays():
    """A mixed step right after a horizon, and a horizon right after a
    mixed step, find the per-slot arrays placed: the same device copies
    serve both programs, and the streams equal the split schedule's."""
    names = ("seeds", "temps", "top_ks", "top_ps", "min_ps", "pres", "freqs",
             "reps", "lora_slots", "proc_masks", "tables")
    mixed = make_engine(True)
    seen = []

    def hook(s):
        cache = mixed._dev_cache
        seen.append((s.phase, s.h2d_placements, {
            n: (id(cache[n][0]), cache[n][1].tobytes())
            for n in names if n in cache
        }))

    mixed.stats_hook = hook
    try:
        m = await arrive_while_decoding(
            mixed, preq("r1", P_RESIDENT, 60), preq("r2", P_LONG[:90], 12)
        )
    finally:
        mixed.stop()
    phases = [p for p, _, _ in seen]
    first_mixed = phases.index("mixed")
    last_mixed = len(phases) - 1 - phases[::-1].index("mixed")
    assert phases[first_mixed - 1] == "decode"      # a horizon before it
    assert phases[last_mixed + 1] == "decode"       # and one after
    # from step to step, whichever program ran: a device copy is placed
    # again when its content changed, and only then
    kept = 0
    for (_, _, was), (_, _, now) in zip(seen, seen[1:]):
        for n in set(was) & set(now):
            assert (was[n][0] == now[n][0]) == (was[n][1] == now[n][1]), n
            kept += was[n][0] == now[n][0]
    assert kept > 5 * len(seen)
    # into the first mixed step r2's admission changed the seeds, top-k and
    # tables, and the chunk was not prebuilt; the rest was found placed
    assert seen[first_mixed][1] <= PACKED + CHUNK_ARRAYS + 3
    # the horizon after the last: the active mask, new pages, the carry
    assert seen[last_mixed + 1][1] <= 6
    split = make_engine(False)
    try:
        s = await arrive_while_decoding(
            split, preq("r1", P_RESIDENT, 60), preq("r2", P_LONG[:90], 12)
        )
    finally:
        split.stop()
    assert m == s


class _Leader:
    """A one-process multihost group: the leader with no follower."""

    is_leader = True
    num_processes = 1

    def __init__(self):
        self.router = MultihostRouter(self)
        self.frames = []

    def broadcast(self, name, send):
        self.frames.append(name)

    def close(self):
        pass


async def test_the_multihost_leader_is_handed_host_values():
    """Multihost: every argument that is not replay state reaches the
    leader wrapper as host numpy (never a device array it would have to
    pull back for the broadcast), the packed buffer among them."""
    mh = _Leader()
    eng = TpuEngine(
        TpuEngineConfig(
            model=MODEL, num_blocks=128, block_size=16, max_batch_size=2,
            max_context=512, prefill_buckets=(16, 32), decode_steps=1,
        ),
        multihost=mh,
    )
    handed = {}
    for name, state in (("_prefill_fn", (0, 1, 2, 3, 16, 17)),
                        ("_decode_fn", (0, 1, 2, 3, 14, 15))):
        fn = getattr(eng, name)

        def record(*args, fn=fn, name=name, state=state):
            handed[name] = [
                type(a) for i, a in enumerate(args) if i not in state
            ]
            return fn(*args)

        setattr(eng, name, record)
    try:
        toks = await run_one(eng, preq("r", P_LONG[:40], 4))
    finally:
        eng.stop()
    assert len(toks) == 4
    assert {"prefill", "decode"} <= {f.rpartition(":")[2] for f in mh.frames}
    for name, types in handed.items():
        assert types and all(
            issubclass(t, (np.ndarray, np.generic)) for t in types
        ), (name, types)


def test_the_packed_buffer_round_trips_and_has_one_static_shape():
    """``unpack`` reads back what ``pack`` laid out (inside a jitted
    program, where it runs), and the length depends on the batch and the
    table width alone: one shape per engine, so warm-up covers it."""
    B, NB = 4, 9
    rows = {name: np.arange(B, dtype=np.int32) + 10 * k
            for k, name in enumerate(step_args.ROWS)}
    row = np.arange(NB, dtype=np.int32) + 100
    buf = step_args.pack(
        B, NB, table_row=row, total_len=77, chunk_start=64, slot=3,
        is_final=True, c_lp_need=False, lp_need=np.bool_(True), c_g_state=5,
        **rows,
    )
    assert buf.dtype == np.int32
    assert buf.shape == step_args.pack(B, NB).shape == (
        len(step_args.ROWS) * B + len(step_args.SCALARS) + NB,
    )
    got = jax.jit(lambda b: vars(step_args.unpack(b, B)))(buf)
    for name, want in rows.items():
        np.testing.assert_array_equal(got[name], want)
    np.testing.assert_array_equal(got["table_row"], row)
    assert (int(got["total_len"]), int(got["chunk_start"]), int(got["slot"]),
            int(got["c_g_state"])) == (77, 64, 3, 5)
    assert (bool(got["is_final"]), bool(got["c_lp_need"]),
            bool(got["lp_need"])) == (True, False, True)
    assert got["is_final"].dtype == jnp.bool_
