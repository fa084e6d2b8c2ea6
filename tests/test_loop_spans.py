"""The engine loop's phase spans (engine/telemetry.py ``loop_span``).

One tiny engine serves two waves of requests, once for the whole module:
the first with no ``stats_hook`` (nothing may accumulate), the second with a
hook and a ``jax.profiler`` trace around it, marked as ``benchmarks/run.py``
marks its traced sub-window. The cases below read what that left: the
``host_spans``, ``request_spans`` and ``admit_wait_s`` of every ``StepStats``,
and the trace's ``dtpu.loop.*`` and ``dtpu.req.submit`` events. The waves reach all four executor paths: a lone
prefill, fused mixed steps (a prompt arriving beside a resident decode),
horizons, and single-step decodes (more requests than slots).
"""

import asyncio
import bisect
import glob
import os
import statistics
import time

import jax
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
MARKER = "test_loop_spans_window"
N_REQUESTS = 6  # two more than slots: the loop also decodes step by step


def _req(rid, tokens, n):
    return PreprocessedRequest(
        request_id=rid, model="m", token_ids=tokens,
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        sampling=SamplingOptions(temperature=0.0),
    )


async def _one(engine, req, rec):
    rec["t_call"] = time.monotonic_ns()
    async for out in engine.generate(req, Context()):
        if out.token_ids and "t_first" not in rec:
            rec["t_first"] = time.monotonic_ns()


async def _wave(engine, tag):
    """A resident decode, then five prompts of three chunks beside it.
    Returns one record per request, in the order they were queued. No wave
    finds the other's prompts in the prefix cache (``salt``): each prompt is
    three chunks' worth of mixed steps every time."""
    recs = [{} for _ in range(N_REQUESTS)]
    salt = ord(tag)
    first = asyncio.create_task(_one(
        engine, _req(f"{tag}0", [(i * 37 + 11 + salt) % 500 for i in range(30)], 160),
        recs[0],
    ))
    # the resident outlasts the wave, and the rest arrive once it decodes (a
    # tiny engine's launches return at once on the CPU: sent together, or
    # behind a resident that has already finished, prompts only prefill)
    while "t_first" not in recs[0]:
        await asyncio.sleep(0.001)
    rest = [
        asyncio.create_task(_one(
            engine,
            _req(f"{tag}{k}", [(i * 53 + 7 * k + salt) % 500 for i in range(70)], 12),
            recs[k],
        ))
        for k in range(1, N_REQUESTS)
    ]
    await asyncio.gather(first, *rest)
    return recs


async def _serve(trace_dir):
    engine = TpuEngine(TpuEngineConfig(
        model=MODEL, num_blocks=256, block_size=4, max_batch_size=4,
        max_context=512, prefill_buckets=(16, 32), decode_steps=4,
        decode_pipeline=1, mixed_admission=True,
    ))
    out = {}
    try:
        await _wave(engine, "w")  # no hook (and every program compiled)
        out["pending_without_hook"] = (
            len(engine._host_spans), len(engine._admit_waits)
        )
        out["request_spans_without_hook"] = len(engine._request_spans)
        steps = []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            engine.stats_hook = steps.append
            marker = jax.profiler.TraceAnnotation(MARKER)
            out["h_lo"] = time.monotonic_ns()
            marker.__enter__()
            out["requests"] = await _wave(engine, "p")
            marker.__exit__(None, None, None)
            engine.stats_hook = None
        finally:
            jax.profiler.stop_trace()
        out["steps"] = steps
    finally:
        engine.stop()
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("loop_spans_trace"))
    out = asyncio.run(asyncio.wait_for(_serve(trace_dir), timeout=300))
    (xplane,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    events, marker = {}, None
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dtpu.loop."):
                    events.setdefault(ev.name[len("dtpu.loop."):], []).append(
                        (int(ev.start_ns), plane.name)
                    )
                elif ev.name.startswith("dtpu.req."):
                    out.setdefault("request_events", []).append(ev.name)
                elif ev.name == MARKER:
                    marker = int(ev.start_ns)
    out["trace_events"], out["marker_start"] = events, marker
    return out


def _spans(step):
    """``host_spans`` is flat, three values a span: read it as triples."""
    return list(T.span_triples(step.host_spans))


def _loop_spans(step):
    return [s for s in _spans(step) if s[0] in T.LOOP_PHASES]


# -- (a) the loop thread's spans: ordered, disjoint, gap-free ----------------
@pytest.mark.parametrize("phase", ["prefill", "mixed", "decode"])
def test_loop_thread_spans_are_ordered_disjoint_and_cover_the_tick(served, phase):
    steps = [s for s in served["steps"] if s.phase == phase]
    assert steps, f"the waves never made a {phase} step"
    for step in steps:
        spans = _loop_spans(step)
        assert spans, "a StepStats with a hook set carries its loop spans"
        for (_, a0, a1), (_, b0, _b1) in zip(spans, spans[1:]):
            assert a0 <= a1 <= b0, f"{phase}: spans overlap or are out of order"
        covered = sum(t1 - t0 for _, t0, t1 in spans)
        extent = spans[-1][2] - spans[0][1]
        assert covered >= 0.95 * extent, (
            f"{phase}: spans cover {covered / extent:.3f} of the tick"
        )
        assert len(step.host_spans) % 3 == 0
        assert {n for n, _, _ in _spans(step)} <= set(
            T.LOOP_PHASES + T.EXECUTOR_PHASES
        )


# -- (b) executor spans lie inside a step span -------------------------------
@pytest.mark.parametrize("name", T.EXECUTOR_PHASES)
def test_executor_spans_lie_inside_a_step_span(served, name):
    spans = [s for st in served["steps"] for s in _spans(st)]
    step_spans = [(t0, t1) for n, t0, t1 in spans if n == "step"]
    mine = [(t0, t1) for n, t0, t1 in spans if n == name]
    assert mine, f"no {name} span in any step"
    for t0, t1 in mine:
        assert any(a <= t0 and t1 <= b for a, b in step_spans), (
            f"a {name} span lies outside every step span"
        )


def test_a_horizon_dispatch_does_not_wait(served):
    """``sync`` is where an executor function waits for device results: a
    lone decode step, and a mixed step that has to be read at once. A mixed
    step is a link of the chain like a horizon (ISSUE 42): its results are
    awaited by the loop (``fetch``), so the ``step`` span of either
    dispatch holds no ``sync``."""
    horizons = 0
    for step in served["steps"]:
        if step.phase == "mixed":
            names = [n for n, _, _ in _spans(step)]
            assert "fetch" in names and "sync" not in names
        loop = _loop_spans(step)
        syncs = [(t0, t1) for n, t0, t1 in _spans(step) if n == "sync"]
        for i in range(2, len(loop)):
            if [n for n, _, _ in loop[i - 2:i + 1]] == ["step", "book", "fetch"]:
                horizons += 1
                _, a, b = loop[i - 2]
                assert not any(a <= t0 and t1 <= b for t0, t1 in syncs)
    assert horizons > 0


def test_a_chained_mixed_step_is_launched_before_the_one_before_it_is_read(served):
    """Span order of the chain's mixed links (ISSUE 42): the StepStats of
    mixed step N is made when N is read, so it carries step N + 1's
    ``launch``; where N + 1 was launched on N's carry that ``launch`` ended
    before N's ``fetch`` began, and the tick held no ``sync``."""
    mixed = [s for s in served["steps"] if s.phase == "mixed"]
    ahead = 0
    for this, nxt in zip(mixed, mixed[1:]):
        if not nxt.mixed_chained:
            continue
        spans = _spans(this)
        launches = [t1 for n, _, t1 in spans if n == "launch"]
        fetches = [t0 for n, t0, _ in spans if n == "fetch"]
        assert launches and fetches, [n for n, _, _ in spans]
        assert launches[-1] <= fetches[-1]
        ahead += 1
    assert ahead >= len(mixed) // 2 > 0


@pytest.mark.parametrize("phase", ["prefill", "mixed", "decode"])
def test_mixed_chained_is_a_mixed_steps_field(served, phase):
    steps = [s for s in served["steps"] if s.phase == phase]
    assert steps
    if phase == "mixed":
        assert all(isinstance(s.mixed_chained, bool) for s in steps)
        assert any(s.mixed_chained for s in steps)
    else:
        assert all(s.mixed_chained is None for s in steps)
    # /debug/worker: the share of the window's mixed steps, beside h2d_placements
    tele = T.EngineTelemetry(M.MetricsScope())
    for s in steps:
        tele.on_step(s)
    snap = tele.snapshot()
    if phase == "mixed":
        assert snap["mixed_chained"] == round(
            sum(s.mixed_chained for s in steps[-128:]) / len(steps[-128:]), 3)
    else:
        assert "mixed_chained" not in snap and "h2d_placements" in snap


@pytest.mark.parametrize("wait", ["sync", "fetch"])
def test_the_slow_step_line_names_whichever_wait_it_was(wait):
    """A mixed link's wait is the loop's ``fetch``; one read at once waits
    under the executor's ``sync``. The worker's line says which."""
    import logging

    ms = 1_000_000
    spans = ("yield", 0, 14 * ms, "pack", 15 * ms, 17 * ms, "launch", 17 * ms, 18 * ms)
    spans += (
        ("sync", 18 * ms, 1888 * ms, "step", 14 * ms, 1890 * ms) if wait == "sync"
        else ("step", 14 * ms, 19 * ms, "fetch", 19 * ms, 1889 * ms)
    )
    step = T.StepStats(
        phase="mixed", duration_s=1.9, batch_occupancy=8, batch_size=8,
        tokens=520, queue_depth=0, kv_active_blocks=1, kv_free_blocks=1,
        kv_total_blocks=2, host_spans=spans, mixed_chained=wait == "fetch",
    )
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    T.log.addHandler(handler)
    try:
        T.EngineTelemetry(M.MetricsScope(), slow_step_s=1.0).on_step(step)
    finally:
        T.log.removeHandler(handler)
    assert seen[0].startswith(f"slow mixed step: 1900 ms of which {wait} 1870 ms")


# -- (c) one admission wait per admitted request -----------------------------
def test_admit_wait_one_value_per_request_within_its_ttft(served):
    waits = [w for s in served["steps"] for w in s.admit_wait_s]
    assert len(waits) == N_REQUESTS
    # admitted in the order queued, which is the order the tasks were made
    for wait, rec in zip(waits, served["requests"]):
        ttft_s = (rec["t_first"] - rec["t_call"]) / 1e9
        assert 0.0 <= wait <= ttft_s
    # the two requests beyond the four slots waited for a slot to free
    assert max(waits) > 0.0


# -- (d) no reader, no growth; and a reader that falls behind is bounded ------
def test_nothing_accumulates_without_a_hook(served):
    assert served["pending_without_hook"] == (0, 0)


class _FakeEngine:
    def __init__(self, hook):
        self.stats_hook = hook
        self._host_spans = T.pending_spans()


@pytest.mark.parametrize("hook, kept", [(None, 0), (print, T.PENDING_SPANS_MAX)])
def test_pending_spans_are_bounded(hook, kept):
    engine = _FakeEngine(hook)
    for _ in range(T.PENDING_SPANS_MAX + 100):
        with T.loop_span(engine, "yield"):
            pass
    spans = list(T.span_triples(tuple(engine._host_spans)))
    assert len(spans) == kept and len(engine._host_spans) == 3 * kept
    if kept:  # the oldest went whole: what is left still reads as triples
        assert all(n == "yield" and t0 <= t1 for n, t0, t1 in spans)


def test_the_span_record_is_nothing_the_cyclic_collector_keeps(served):
    """A hook that keeps its ``StepStats`` keeps the spans. A tuple per span
    was a dozen tracked objects a tick (twice the youngest-generation passes
    in a serving window on the chip, PERF.md section 6, PR 24). Flat, a
    step's record is ONE object, holding strings and integers only, which
    the collector lets go of at its first pass."""
    import gc

    steps = served["steps"]
    assert all(
        type(v) in (str, int) for s in steps for v in s.host_spans
    )
    gc.collect()
    assert not any(gc.is_tracked(s.host_spans) for s in steps)
    assert not any(gc.is_tracked(s.admit_wait_s) for s in steps)


def test_step_stats_defaults_are_empty():
    s = T.StepStats(
        phase="decode", duration_s=0.0, batch_occupancy=0, batch_size=1,
        tokens=0, queue_depth=0, kv_active_blocks=0, kv_free_blocks=0,
        kv_total_blocks=0,
    )
    assert s.host_spans == () and s.admit_wait_s == ()
    assert s.request_spans == ()


# -- (d2) spans with a request for a subject: a field of their own ------------
def _quads(served):
    return [q for s in served["steps"] for q in T.span_quads(s.request_spans)]


def test_host_spans_keep_their_thirteen_names_as_triples(served):
    """``request_spans`` is a NEW field: what ``host_spans`` holds, and what
    ``dtpu_engine_loop_phase_seconds_total`` and ``/debug/worker`` read from
    it, is what it was."""
    assert T.LOOP_PHASES + T.EXECUTOR_PHASES == (
        "idle", "admit", "book", "step", "fetch", "emit", "reap", "publish",
        "yield", "pack", "upload", "launch", "sync",
    )
    assert T.REQUEST_PHASES == ("submit", "deliver")
    for step in served["steps"]:
        assert len(step.host_spans) % 3 == 0
        names = set(step.host_spans[0::3])
        assert names <= set(T.LOOP_PHASES + T.EXECUTOR_PHASES)
        assert not names & set(T.REQUEST_PHASES)
        assert all(type(v) is int for v in step.host_spans[1::3] + step.host_spans[2::3])


def test_request_spans_are_flat_quads_of_strings_and_integers(served):
    import gc

    steps = served["steps"]
    assert any(s.request_spans for s in steps)
    for s in steps:
        flat = s.request_spans
        assert len(flat) % 4 == 0
        assert set(flat[0::4]) <= set(T.REQUEST_PHASES)
        assert all(type(v) is int for v in flat[1::4] + flat[2::4])
        assert all(type(v) is str for v in flat[0::4] + flat[3::4])
        assert all(t0 <= t1 for _, t0, t1, _ in T.span_quads(flat))
    gc.collect()
    assert not any(gc.is_tracked(s.request_spans) for s in steps)


def test_no_request_span_accumulates_without_a_hook(served):
    assert served["request_spans_without_hook"] == 0


@pytest.mark.parametrize("hook, kept", [(None, 0), (print, T.PENDING_SPANS_MAX)])
def test_pending_request_spans_are_bounded(hook, kept):
    """A hook set on a loop that turns without stepping: requests come, are
    refused or cancelled, and no ``StepStats`` carries their spans away."""
    engine = _FakeEngine(hook)
    engine._request_spans = T.pending_request_spans()
    for k in range(T.PENDING_SPANS_MAX + 50):
        with T.loop_span(engine, "submit", f"r{k}"):
            pass
        T.record_request_span(engine, "deliver", T.now_ns(), f"r{k}")
    quads = list(T.span_quads(tuple(engine._request_spans)))
    assert len(quads) == kept and len(engine._request_spans) == 4 * kept
    assert len(engine._host_spans) == 0  # a span with a subject is not a loop phase
    if kept:  # the oldest went whole: what is left still reads as quads
        assert all(n in T.REQUEST_PHASES and t0 <= t1 and rid.startswith("r")
                   for n, t0, t1, rid in quads)


def test_every_request_has_one_submit_span_inside_a_span_of_the_loop_thread(served):
    """``generate`` runs on the loop's thread: its synchronous work before a
    request is queued lies INSIDE the span in which the loop gave the thread
    away (parked, ``sleep(0)``, or awaiting the executor or a readback), on
    the same clock, and says whose work it was."""
    quads = _quads(served)
    submits = [q for q in quads if q[0] == "submit"]
    assert sorted(rid for _, _, _, rid in submits) == [f"p{k}" for k in range(N_REQUESTS)]
    away = [
        (t0, t1) for s in served["steps"] for n, t0, t1 in _spans(s)
        if n in ("yield", "idle", "step", "fetch")
    ]
    for _, t0, t1, rid in submits:
        assert t1 > t0
        assert any(a <= t0 and t1 <= b for a, b in away), (
            f"the submit span of {rid} lies in no yield / idle / step / fetch span"
        )
    # between the caller's call and its first token, on time.monotonic_ns()
    by_id = {rid: (t0, t1) for _, t0, t1, rid in submits}
    for k, rec in enumerate(served["requests"]):
        t0, t1 = by_id[f"p{k}"]
        assert rec["t_call"] <= t0 <= t1 <= rec["t_first"]


def test_deliver_spans_of_one_request_do_not_overlap(served):
    by_id = {}
    for name, t0, t1, rid in _quads(served):
        if name == "deliver":
            by_id.setdefault(rid, []).append((t0, t1))
    assert sorted(by_id) == [f"p{k}" for k in range(N_REQUESTS)]
    for rid, spans in by_id.items():
        spans.sort()
        for (_, a1), (b0, _) in zip(spans, spans[1:]):
            assert a1 <= b0, f"two deliver spans of {rid} overlap"
    # a span an item; the last one closes as the caller leaves at the finish frame
    submit_end = {rid: t1 for n, _, t1, rid in _quads(served) if n == "submit"}
    assert all(spans[0][0] >= submit_end[rid] for rid, spans in by_id.items())


def test_submit_opens_an_annotation_and_deliver_none(served):
    assert set(served["request_events"]) == {"dtpu.req.submit"}
    assert len(served["request_events"]) == N_REQUESTS


# -- (e) the two sinks are one clock -----------------------------------------
def test_trace_holds_the_spans_on_a_host_plane_on_the_same_clock(served):
    events = served["trace_events"]
    assert served["marker_start"] is not None
    assert {"admit", "book", "step", "emit", "pack", "launch"} <= set(events)
    assert all(
        not plane.startswith("/device:")
        for evs in events.values() for _, plane in evs
    )
    # host_spans moved onto the trace's clock by the marker, as run.py does it
    shift = served["marker_start"] - served["h_lo"]
    starts = {name: sorted(t for t, _ in evs) for name, evs in events.items()}
    residuals = []
    for step in served["steps"]:
        for name, t0, _t1 in _spans(step):
            if t0 < served["h_lo"]:
                continue  # opened before the profile: no twin in the trace
            twin = starts[name]
            i = bisect.bisect_left(twin, t0 + shift)
            residuals.append(min(
                abs(twin[j] - (t0 + shift))
                for j in (i - 1, i) if 0 <= j < len(twin)
            ))
    assert len(residuals) > 100
    assert statistics.median(residuals) < 5e6  # ns


# -- (f) the operator's view --------------------------------------------------
def test_engine_telemetry_folds_the_spans(served):
    scope = M.MetricsScope().child(dtpu_namespace="ns", dtpu_component="be")
    tele = T.EngineTelemetry(scope)
    for step in served["steps"]:
        tele.on_step(step)
    phases = tele.snapshot()["loop_phases"]
    assert {"admit", "book", "step", "emit", "launch"} <= set(phases)
    assert all(v >= 0 for v in phases.values())
    # the executor's phases lie inside "step"
    assert phases["step"] >= phases["launch"]
    text = scope.expose().decode()
    lines = [
        l for l in text.splitlines()
        if l.startswith(M.LOOP_PHASE_SECONDS_TOTAL + "{")
    ]
    assert {p for p in T.LOOP_PHASES + T.EXECUTOR_PHASES
            if any(f'phase="{p}"' in l for l in lines)} >= set(phases)
    total = sum(float(l.rsplit(" ", 1)[1]) for l in lines)
    spans_s = sum(
        (t1 - t0) / 1e9 for s in served["steps"] for _, t0, t1 in _spans(s)
    )
    assert total == pytest.approx(spans_s, rel=1e-6)


# -- the operator's switch: POST /debug/profile -------------------------------
async def test_profile_endpoint_writes_a_trace_with_the_loop_spans(monkeypatch):
    import aiohttp

    from dynamo_tpu.runtime import health
    from dynamo_tpu.runtime.health import PROFILE_MAX_S, HealthState, StatusServer

    assert PROFILE_MAX_S == 30.0
    engine = TpuEngine(TpuEngineConfig(
        model=MODEL, num_blocks=64, block_size=4, max_batch_size=4,
        max_context=256, prefill_buckets=(16, 32),
    ))
    server = StatusServer(HealthState(), host="127.0.0.1")
    await server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/debug/profile"
        await _one(engine, _req("warm", list(range(40, 52)), 4), {})
        async with aiohttp.ClientSession() as s:
            async def post(seconds):
                async with s.post(url, params={"seconds": seconds}) as r:
                    return r.status, await r.json()

            first = asyncio.create_task(post("1.5"))
            await asyncio.sleep(0.3)
            assert (await post("1"))[0] == 409          # one profile at a time
            assert (await post("nope"))[0] == 400
            k = 0
            while not first.done():  # the shapes the warm-up compiled
                await _one(engine, _req(f"traced{k}", list(range(40, 52)), 4), {})
                k += 1
            status, body = await first
            # a worker keeps its last PROFILE_KEEP directories and no more
            monkeypatch.setattr(health, "PROFILE_KEEP", 2)
            later = [(await post("0.05"))[1]["dir"]]
        assert status == 200 and body["seconds"] == 1.5
        assert os.path.isdir(body["dir"]) and os.path.isdir(later[0])
        (xplane,) = glob.glob(f"{body['dir']}/plugins/profile/*/*.xplane.pb")
        names = {
            ev.name
            for plane in jax.profiler.ProfileData.from_file(xplane).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("dtpu.loop.")
        }
        assert {"dtpu.loop.step", "dtpu.loop.launch", "dtpu.loop.emit"} <= names
        async with aiohttp.ClientSession() as s:
            async with s.post(url, params={"seconds": "0.05"}) as r:
                newest = (await r.json())["dir"]
        assert not os.path.exists(body["dir"])          # the oldest went as the third began
        assert os.path.isdir(later[0]) and os.path.isdir(newest)
    finally:
        await server.stop()
        engine.stop()
