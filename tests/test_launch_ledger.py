"""The launch ledger (engine/telemetry.py ``launch`` / ``record_arrival``).

One tiny engine serves, once for the module: a wave with no ``stats_hook``
(nothing may accumulate; every step program compiles), the same wave with a
hook (a lone prefill, fused mixed steps beside a resident decode, horizons,
single-step decodes while two requests wait for a slot), a lone prompt of
three chunks, a request with a penalty (``reset_slot``) and four embeddings
(``embed`` twice at one bucket, ``embed_chunk``). A second engine with
``decode_pipeline`` 2 and no fused step gives pipelined horizons. The cases
below read the ``launches`` and ``arrivals`` of every ``StepStats`` that left.
"""

import asyncio
import gc
import logging

import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import telemetry as T
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.runtime import Context
from dynamo_tpu.runtime import metrics as M

MODEL = LlamaConfig(
    vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, intermediate_size=128, dtype=jnp.float32,
)
BUCKETS = (16, 32)
DECODE_STEPS = 4


def _req(rid, tokens, n, **sampling):
    return PreprocessedRequest(
        request_id=rid, model="m", token_ids=tokens,
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        sampling=SamplingOptions(temperature=0.0, **sampling),
    )


async def _one(engine, req, started=None):
    async for out in engine.generate(req, Context()):
        if started is not None and out.token_ids:
            started.set()


async def _wave(engine, tag):
    """A resident decode that outlasts the wave, then (once it has its first
    token: a tiny engine's launches return at once on the CPU) five prompts
    of three chunks beside it: two more requests than slots, so the loop also
    decodes step by step."""
    salt = ord(tag)  # no wave finds the other's prompts in the prefix cache
    started = asyncio.Event()
    first = asyncio.create_task(_one(
        engine, _req(f"{tag}0", [(i * 37 + salt) % 500 for i in range(30)], 160),
        started))
    await started.wait()
    rest = [
        asyncio.create_task(_one(
            engine, _req(f"{tag}{k}", [(i * 53 + 7 * k + salt) % 500 for i in range(70)], 12)))
        for k in range(1, 6)
    ]
    await asyncio.gather(first, *rest)


def _engine(**kw):
    return TpuEngine(TpuEngineConfig(
        model=MODEL, num_blocks=256, block_size=4, max_batch_size=4,
        max_context=512, prefill_buckets=BUCKETS, decode_steps=DECODE_STEPS, **kw,
    ))


async def _serve():
    out = {}
    engine = _engine(decode_pipeline=1, mixed_admission=True)
    try:
        await _wave(engine, "w")  # no hook (and every step program compiled)
        out["pending_without_hook"] = (len(engine._launches), len(engine._arrivals))
        steps = []
        engine.stats_hook = steps.append
        await _wave(engine, "p")
        mark = len(steps)
        await _one(engine, _req("lone", [(i * 29 + 3) % 500 for i in range(70)], 2))
        out["lone"] = steps[mark:]
        await _one(engine, _req("pen", list(range(40, 52)), 6, presence_penalty=0.5))
        for rid, n in (("e1", 20), ("e2", 22), ("e3", 12), ("long", 84)):
            await _one(engine, PreprocessedRequest(
                request_id=rid, model="m", token_ids=list(range(3, 3 + n)),
                annotations={"op": "embed"},
            ))
        # what no StepStats has carried away yet (an embedding makes none)
        await _one(engine, _req("flush", list(range(60, 70)), 2))
        engine.stats_hook = None
        out["steps"] = steps
    finally:
        engine.stop()
    piped = _engine(decode_pipeline=2, mixed_admission=False)
    try:
        steps = []
        piped.stats_hook = steps.append
        await asyncio.gather(*[
            _one(piped, _req(f"h{k}", [(i * 41 + k) % 500 for i in range(20)], 40))
            for k in range(2)
        ])
        out["piped"] = steps
    finally:
        piped.stop()
    return out


@pytest.fixture(scope="module")
def served():
    return asyncio.run(asyncio.wait_for(_serve(), timeout=600))


def _launches(steps):
    return [rec for s in steps for rec in T.launch_records(s.launches)]


def _arrivals(steps):
    return [rec for s in steps for rec in T.arrival_records(s.arrivals)]


# -- every launch site leaves one record --------------------------------------
@pytest.mark.parametrize("program, keys", [
    ("prefill", set(BUCKETS)), ("mixed_step", set(BUCKETS)),
    ("decode_multi", {DECODE_STEPS}), ("decode", {1}), ("reset_slot", {0}),
    ("embed", {16, 32}), ("embed_chunk", set(BUCKETS)),
])
def test_every_launch_site_leaves_a_record_with_its_program_and_key(served, program, keys):
    mine = [rec for rec in _launches(served["steps"]) if rec[1] == program]
    assert mine, f"no launch of {program} in the ledger"
    assert {rec[2] for rec in mine} <= keys
    for _, _, key, t0, t1, compiled, after in mine:
        assert isinstance(key, int) and t0 <= t1 and isinstance(compiled, bool)
        assert isinstance(after, int)


def test_no_other_program_is_in_the_ledger(served):
    assert {rec[1] for rec in _launches(served["steps"])} == {
        "prefill", "mixed_step", "decode_multi", "decode", "reset_slot",
        "embed", "embed_chunk",
    }


@pytest.mark.parametrize("which", ["steps", "piped"])
def test_seq_rises_by_one_in_launch_order(served, which):
    recs = _launches(served[which])
    seqs = [rec[0] for rec in recs]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    starts = [rec[3] for rec in recs]
    assert starts == sorted(starts)


@pytest.mark.parametrize("which", ["steps", "piped"])
def test_each_arrival_names_a_launch_once_and_lands_after_its_call_began(served, which):
    by_seq = {rec[0]: rec for rec in _launches(served[which])}
    arrivals = _arrivals(served[which])
    seqs = [seq for seq, _ in arrivals]
    assert len(seqs) == len(set(seqs)) > 0
    for seq, t in arrivals:
        assert seq in by_seq and t >= by_seq[seq][3]
    # every program that is read leaves one; a chunk that is not final none
    # (a horizon launched ahead of a request's end lands behind the last
    # StepStats: nothing carries its arrival away)
    unread = {rec[1] for seq, rec in by_seq.items()
              if seq not in set(seqs) and seq < max(seqs)}
    assert unread <= {"prefill", "reset_slot", "embed_chunk"}


def test_a_chunk_that_is_not_final_has_no_arrival(served):
    """A lone prompt of 70 tokens is three chunks (32, 32, 6 in the bucket of
    16): three ``prefill`` launches, and only the last is read."""
    chunks = [rec for rec in _launches(served["lone"]) if rec[1] == "prefill"]
    assert [rec[2] for rec in chunks] == [32, 32, 16]
    landed = {seq for seq, _ in _arrivals(served["lone"])}
    assert [rec[0] in landed for rec in chunks] == [False, False, True]


def test_compiled_is_true_on_a_first_call_at_a_key_and_false_on_the_second(served):
    embeds = [rec for rec in _launches(served["steps"]) if rec[1] == "embed"]
    assert [(rec[2], rec[5]) for rec in embeds] == [(32, True), (32, False), (16, True)]
    # the step programs compiled in the wave before the hook was set, and a
    # chunk's arrays prebuilt on the device where they were host arrays (both
    # were served) are a new form of the arguments, not a compile
    assert {s.prep_hit for s in served["steps"] if s.phase == "mixed"} >= {True, None}
    for program in ("mixed_step", "decode_multi", "decode"):
        assert not any(rec[5] for rec in _launches(served["steps"]) if rec[1] == program)
    # the one bucket no wave ran alone: the lone prompt's last chunk
    assert [rec[2] for rec in _launches(served["steps"])
            if rec[1] == "prefill" and rec[5]] == [16]


@pytest.mark.parametrize("second, grows", [("host", False), ("device", True)])
def test_compiled_is_a_compile_and_not_a_new_form_of_the_arguments(second, grows):
    """A jitted function's fast path misses on an argument form it has not
    seen (its cache grows) without compiling anything: milliseconds, and no
    ``compiled``."""
    import jax
    import numpy as np

    engine = _FakeEngine(print)
    program = jax.jit(lambda x: x + 1)
    x = np.arange(8, dtype=np.int32)
    T.launch(engine, program, 8, x)
    size = program._cache_size()
    T.launch(engine, program, 8, x if second == "host" else jnp.asarray(x))
    assert (program._cache_size() > size) == grows
    assert [rec[5] for rec in T.launch_records(tuple(engine._launches))] == [True, False]


# -- order along the chain ------------------------------------------------------
def test_after_names_results_that_had_landed_before_the_call_began(served):
    landed = dict(_arrivals(served["steps"]))
    recs = _launches(served["steps"])
    assert any(rec[6] >= 0 for rec in recs)
    for seq, _, _, t0, _, _, after in recs:
        assert -1 <= after < seq
        if after in landed:
            assert landed[after] <= t0


def test_a_chained_mixed_link_is_launched_before_the_one_before_it_is_read(served):
    """Launched on the device carry of mixed step N, link N + 1 comes
    ``after`` an OLDER launch's results than N's, and a link that was not
    chained after N's or a later one's; the records stay in launch order
    whatever the order of the reads. The k-th mixed ``StepStats`` is the k-th
    ``mixed_step`` launch."""
    mixed = [rec for rec in _launches(served["steps"]) if rec[1] == "mixed_step"]
    flags = [s.mixed_chained for s in served["steps"] if s.phase == "mixed"]
    assert len(mixed) == len(flags) and any(flags)
    for prev, rec, chained in zip(mixed, mixed[1:], flags[1:]):
        assert (rec[6] < prev[0]) == chained, (prev, rec, chained)


def test_pipelined_horizons_keep_launch_order(served):
    """At ``decode_pipeline`` 2 horizon k + 1 is launched before horizon k is
    read: its record comes ``after`` an older launch than the one before it."""
    horizons = [rec for rec in _launches(served["piped"]) if rec[1] == "decode_multi"]
    ahead = sum(1 for prev, rec in zip(horizons, horizons[1:])
                if rec[0] == prev[0] + 1 and rec[6] < prev[0])
    assert ahead >= len(horizons) // 2 > 0
    # all but a last one, launched ahead of the requests' end, are read (two
    # fetch threads may stamp two ready results in either order on the CPU)
    landed = dict(_arrivals(served["piped"]))
    assert sum(rec[0] in landed for rec in horizons) >= len(horizons) - 1


def test_a_mixed_steps_duration_runs_from_its_launch_records_t0(served):
    """``_Chain.t0_ns`` is taken as the record's call begins, on its clock:
    the k-th mixed ``StepStats`` is the k-th ``mixed_step`` launch, made once
    its results were taken."""
    mixed = [rec for rec in _launches(served["steps"]) if rec[1] == "mixed_step"]
    stats = [s for s in served["steps"] if s.phase == "mixed"]
    landed = dict(_arrivals(served["steps"]))
    assert len(mixed) == len(stats) > 0
    for rec, s in zip(mixed, stats):
        assert s.duration_s * 1e9 >= landed[rec[0]] - rec[3] > 0


# -- no reader, no growth; a reader that falls behind is bounded -----------------
def test_nothing_is_recorded_without_a_hook(served):
    assert served["pending_without_hook"] == (0, 0)


class _FakeEngine:
    def __init__(self, hook):
        import itertools

        self.stats_hook = hook
        self._launches, self._arrivals = T.pending_launches(), T.pending_arrivals()
        self._launch_seq, self._read_seq = itertools.count(), -1


@pytest.mark.parametrize("hook, kept", [(None, 0), (print, T.PENDING_SPANS_MAX)])
def test_the_pending_lists_are_bounded(hook, kept):
    engine = _FakeEngine(hook)

    def program(x):
        return x + 1

    for i in range(T.PENDING_SPANS_MAX + 100):
        seq, out = T.launch(engine, program, 7, i)
        assert (seq, out) == (i, i + 1)
        T.record_arrival(engine, seq)
    recs = list(T.launch_records(tuple(engine._launches)))
    assert len(recs) == kept and len(engine._launches) == T.LAUNCH_VALUES * kept
    assert len(engine._arrivals) == 2 * kept
    if kept:  # the oldest went whole: what is left still reads as records
        assert [rec[0] for rec in recs] == list(range(100, 100 + kept))
        assert all(rec[1:3] == ("program", 7) and rec[5] is False for rec in recs)
        T.record_arrival(engine, -1)  # a launch nobody reads stamps nothing
        assert len(engine._arrivals) == 2 * kept


def test_the_record_is_nothing_the_cyclic_collector_keeps(served):
    steps = served["steps"]
    assert all(type(v) in (str, int, bool) for s in steps for v in s.launches)
    assert all(type(v) is int for s in steps for v in s.arrivals)
    assert all(len(s.launches) % T.LAUNCH_VALUES == 0 and len(s.arrivals) % 2 == 0
               for s in steps)
    gc.collect()
    assert not any(gc.is_tracked(s.launches) or gc.is_tracked(s.arrivals) for s in steps)


def test_step_stats_defaults_are_empty():
    s = T.StepStats(
        phase="decode", duration_s=0.0, batch_occupancy=0, batch_size=1,
        tokens=0, queue_depth=0, kv_active_blocks=0, kv_free_blocks=0,
        kv_total_blocks=0,
    )
    assert s.launches == () and s.arrivals == ()


# -- the operator's view ----------------------------------------------------------
def _step(launches, duration_s=0.01):
    return T.StepStats(
        phase="mixed", duration_s=duration_s, batch_occupancy=8, batch_size=8,
        tokens=264, queue_depth=0, kv_active_blocks=1, kv_free_blocks=1,
        kv_total_blocks=2, launches=launches,
    )


def _logged(fn):
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    T.log.addHandler(handler)
    try:
        fn()
    finally:
        T.log.removeHandler(handler)
    return seen


async def test_debug_worker_shows_the_programs(served):
    import aiohttp

    from dynamo_tpu.runtime.health import HealthState, StatusServer

    tele = T.EngineTelemetry(M.MetricsScope())
    for s in served["steps"]:
        tele.on_step(s)
    server = StatusServer(
        HealthState(), host="127.0.0.1", port=0,
        worker_snapshot_fn=lambda: {"telemetry": [tele.snapshot()]},
    )
    addr = await server.start()
    try:
        async with aiohttp.ClientSession() as http:
            async with http.get(f"http://{addr}/debug/worker") as r:
                doc = await r.json()
    finally:
        await server.stop()
    programs = doc["telemetry"][0]["programs"]
    recs = _launches(served["steps"])
    assert set(programs) == {rec[1] for rec in recs}
    for program, by_key in programs.items():
        for key, counts in by_key.items():
            mine = [rec for rec in recs if rec[1] == program and str(rec[2]) == key]
            assert counts == {"launches": len(mine),
                              "compiled": sum(rec[5] for rec in mine)}
    assert programs["embed"]["32"] == {"launches": 2, "compiled": 1}


@pytest.mark.parametrize("compiled", [True, False])
def test_a_launch_that_compiles_while_serving_is_warned_of_once(compiled):
    ms = 1_000_000
    step = _step((7, "mixed_step", 256, 10 * ms, 4110 * ms, compiled, 5,
                  8, "decode_multi", 8, 4200 * ms, 4201 * ms, False, 6))
    tele = T.EngineTelemetry(M.MetricsScope(), slow_step_s=60.0)
    seen = _logged(lambda: tele.on_step(step))
    assert seen == (["program compiled while serving: mixed_step[256], 4.1 s"]
                    if compiled else [])


@pytest.mark.parametrize("compiled, said", [
    (True, "; launched mixed_step[256] in 4100 ms, compiled (threshold"),
    (False, "; launched mixed_step[256] in 4100 ms (threshold"),
])
def test_the_slow_step_line_names_the_program_and_says_compiled(compiled, said):
    ms = 1_000_000
    step = _step((7, "mixed_step", 256, 10 * ms, 4110 * ms, compiled, 5,
                  8, "decode_multi", 8, 4200 * ms, 4201 * ms, False, 6),
                 duration_s=4.3)
    tele = T.EngineTelemetry(M.MetricsScope(), slow_step_s=1.0)
    seen = _logged(lambda: tele.on_step(step))
    assert seen[-1].startswith("slow mixed step: 4300 ms of which no span 0 ms" + said)
