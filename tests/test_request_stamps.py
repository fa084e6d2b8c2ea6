"""A request's stamps and spans on the loop's clock (engine/telemetry.py).

``_Seq.t_queued`` / ``t_admitted`` / ``t_prefill_start`` / ``t_first_token``
are taken with ``time.monotonic_ns()``, the clock of the loop's spans and of
the benchmark's marker; one offset, taken as the loop starts, moves them onto
the wall clock for the sinks that carry unix nanoseconds. One tiny engine,
more requests than slots, a hook set, and a wall clock that steps an hour
BACK between the first request's queueing and the last one's admission.
"""

import asyncio
import logging
import time

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
from dynamo_tpu.runtime.flight_recorder import get_flight_recorder

MODEL = LlamaConfig(
    vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, intermediate_size=128, dtype=jnp.float32,
)
N = 4          # two slots: two requests wait for one
HOUR_NS = 3600 * 10**9


def _req(rid, n_prompt, n_out):
    return PreprocessedRequest(
        request_id=rid, model="m",
        token_ids=[(i * 31 + len(rid)) % 500 for i in range(n_prompt)],
        stop=StopConditions(max_tokens=n_out, ignore_eos=True),
        sampling=SamplingOptions(temperature=0.0),
    )


async def _drain(engine, req):
    async for _ in engine.generate(req, Context()):
        pass


async def _serve(monkeypatch):
    engine = TpuEngine(TpuEngineConfig(
        model=MODEL, num_blocks=128, block_size=4, max_batch_size=2,
        max_context=256, prefill_buckets=(16, 32), decode_steps=4,
        decode_pipeline=1,
    ))
    seqs, steps = {}, []
    finished = engine._request_finished

    def keep(st, reason):
        seqs[st.req.request_id] = st
        return finished(st, reason)

    engine._request_finished = keep
    out = {"seqs": seqs, "steps": steps}
    try:
        await _drain(engine, _req("warm", 40, 6))   # the loop and its offset exist
        out["offset_ns"] = engine._wall_offset_ns
        out["unix_now"] = (engine._unix_ns(time.monotonic_ns()), time.time_ns())
        engine.stats_hook = steps.append
        # from here on the wall clock reads an hour less at every look
        real, looks = time.time_ns, [0]

        def stepped_back():
            looks[0] += 1
            return real() - looks[0] * HOUR_NS

        monkeypatch.setattr(time, "time_ns", stepped_back)
        out["t_lo"] = time.monotonic_ns()
        await asyncio.gather(*[
            _drain(engine, _req(f"s{k}", 40 + k, 12)) for k in range(N)
        ])
        out["t_hi"] = time.monotonic_ns()
        monkeypatch.setattr(time, "time_ns", real)
        engine.stats_hook = None
    finally:
        engine.stop()
    return out


@pytest.fixture(scope="module")
def served():
    mp = pytest.MonkeyPatch()
    try:
        return asyncio.run(asyncio.wait_for(_serve(mp), timeout=300))
    finally:
        mp.undo()


def test_the_stamps_are_ordered_and_lie_on_the_monotonic_clock(served):
    assert sorted(k for k in served["seqs"] if k != "warm") == [f"s{k}" for k in range(N)]
    for rid, st in served["seqs"].items():
        if rid == "warm":
            continue
        assert (served["t_lo"] <= st.t_queued <= st.t_admitted
                <= st.t_prefill_start <= st.t_first_token <= served["t_hi"]), rid


def test_a_wall_clock_stepped_backwards_leaves_admit_wait_right(served):
    """The third and fourth request wait for a slot while the wall clock
    loses hours: their wait is what the monotonic stamps say, not zero (a
    negative difference clipped) and not an hour."""
    waits = [w for s in served["steps"] for w in s.admit_wait_s]
    assert len(waits) == N
    want = sorted(
        (st.t_admitted - st.t_queued) / 1e9
        for rid, st in served["seqs"].items() if rid != "warm"
    )
    assert sorted(waits) == pytest.approx(want)
    assert all(0.0 <= w < 60.0 for w in waits)
    assert sorted(waits)[-2] > 0.0          # two requests waited for a slot


def test_the_offset_puts_a_stamp_on_the_wall_clock(served):
    assert served["offset_ns"] != 0
    as_unix, unix = served["unix_now"]
    assert abs(as_unix - unix) < 1e9       # the same instant, to the second


def test_the_queued_flight_event_carries_submit_ms(served):
    """``/debug/requests?id=`` shows an operator the number the benchmark
    reads: the request's ``submit`` spans, summed up to the event."""
    span_ms = {}
    for s in served["steps"]:
        for name, t0, t1, rid in T.span_quads(s.request_spans):
            if name == "submit":
                span_ms[rid] = span_ms.get(rid, 0.0) + (t1 - t0) / 1e6
    for k in range(N):
        timeline = get_flight_recorder().timeline(f"s{k}")
        queued = [e["event"] for e in timeline["events"] if e["event"]["kind"] == "queued"]
        assert len(queued) == 1
        ms = queued[0]["submit_ms"]
        assert isinstance(ms, float) and 0.0 < ms <= span_ms[f"s{k}"] + 0.001
        # the recorder's own stamps stay unix nanoseconds (stepped back here)
        assert all(e["timestamp"] > 10**18 for e in timeline["events"])


class _Engine:
    def __init__(self):
        self.stats_hook = print
        self._host_spans = T.pending_spans()
        self._request_spans = T.pending_request_spans()


async def test_a_submit_span_is_cut_at_an_await():
    """A span covers only time the thread was held: ``away`` closes it for
    the length of the await and opens the next stretch after it."""
    engine = _Engine()
    t_lo = time.monotonic_ns()
    with T.submit_span(engine) as sub:
        sub.request_id = "r"
        await sub.away(asyncio.sleep(0.05))
        held_inside = sub.held_ms()
    elapsed_ms = (time.monotonic_ns() - t_lo) / 1e6
    (a, b) = list(T.span_quads(tuple(engine._request_spans)))
    assert (a[0], a[3]) == (b[0], b[3]) == ("submit", "r")
    assert b[1] - a[2] >= 0.045e9           # the await lies between the two
    total_ms = ((a[2] - a[1]) + (b[2] - b[1])) / 1e6
    assert held_inside <= sub.held_ms() == pytest.approx(total_ms, abs=0.05)
    assert total_ms < elapsed_ms - 45.0
    assert len(engine._host_spans) == 0


async def test_a_failed_await_still_closes_the_span():
    engine = _Engine()

    async def boom():
        raise ValueError("refused")

    with pytest.raises(ValueError):
        with T.submit_span(engine) as sub:
            sub.request_id = "r"
            await sub.away(boom())
    assert sub._open is None
    assert [q[0] for q in T.span_quads(tuple(engine._request_spans))] == ["submit", "submit"]


def test_the_slow_step_warning_names_the_phase_that_held_the_time():
    ms = 1_000_000
    step = T.StepStats(
        phase="mixed", duration_s=1.9, batch_occupancy=8, batch_size=8,
        tokens=520, queue_depth=0, kv_active_blocks=1, kv_free_blocks=1,
        kv_total_blocks=2,
        # the executor's spans lie inside ``step``: it is not the answer
        host_spans=("yield", 0, 14 * ms, "pack", 15 * ms, 17 * ms,
                    "launch", 17 * ms, 18 * ms, "sync", 18 * ms, 1888 * ms,
                    "step", 14 * ms, 1890 * ms),
    )
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    T.log.addHandler(handler)
    try:
        tele = T.EngineTelemetry(M.MetricsScope(), slow_step_s=1.0)
        tele.on_step(step)
        tele.on_step(T.StepStats(**{**step.__dict__, "host_spans": ()}))
    finally:
        T.log.removeHandler(handler)
    assert len(seen) == 2 and tele.slow_steps == 2
    assert seen[0].startswith("slow mixed step: 1900 ms of which sync 1870 ms (threshold 1000 ms")
    assert "of which no span 0 ms" in seen[1]
