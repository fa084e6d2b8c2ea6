"""End-to-end request lifecycle observability.

Cross-plane trace propagation (frontend span -> worker span via the
traceparent annotation), engine phase spans + step telemetry
(engine/telemetry.py), and the request flight recorder
(runtime/flight_recorder.py + /debug/requests).
"""

import json
import time

import aiohttp
import jax
import jax.numpy as jnp

from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.engine.telemetry import EngineTelemetry, StepStats
from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.protocols.common import (
    BackendOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.llm.tokenizer import load_tokenizer
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.parallel.mesh import make_mesh
from dynamo_tpu.runtime import metrics as M
from dynamo_tpu.runtime.engine import Context, FnEngine
from dynamo_tpu.runtime.flight_recorder import (
    FlightRecorder,
    get_flight_recorder,
    set_flight_recorder,
)
from dynamo_tpu.runtime.health import HealthState, StatusServer
from dynamo_tpu.runtime.tracing import (
    InMemoryExporter,
    OtlpHttpExporter,
    Tracer,
    set_tracer,
)


def _with_tracer(exp):
    tracer = Tracer(exp, batch_size=1)
    set_tracer(tracer)
    return tracer


# ------------------------------------------------- cross-plane propagation
async def test_worker_span_parents_on_frontend_span():
    """The frontend's span id must appear as the parent of the worker-side
    Backend span after the traceparent crosses the request plane as a
    request annotation (the wire hop is a plain dict round trip)."""
    exp = InMemoryExporter()
    tracer = _with_tracer(exp)
    try:
        async def fake_engine(req, ctx):
            yield BackendOutput(token_ids=[65], finish_reason="stop").to_obj()

        backend = Backend(FnEngine(fake_engine), load_tokenizer("byte"))
        with tracer.span("http.generate", request_id="r1") as frontend:
            preq = PreprocessedRequest(
                request_id="r1", model="m", token_ids=[1, 2, 3],
                annotations={"traceparent": frontend.traceparent()},
            )
            # the annotation survives a request-plane serialization round trip
            wire = PreprocessedRequest.from_obj(preq.to_obj())
            async for _ in backend.generate(wire, Context("r1")):
                pass
        worker = next(s for s in exp.spans if s.name == "worker.generate")
        assert worker.trace_id == frontend.trace_id
        assert worker.parent_id == frontend.span_id
    finally:
        set_tracer(None)


def test_tracer_emit_parents_and_preserves_timestamps():
    exp = InMemoryExporter()
    tracer = _with_tracer(exp)
    try:
        with tracer.span("root") as root:
            hdr = root.traceparent()
        sp = tracer.emit("engine.queue", 100, 200, traceparent=hdr, request_id="r")
        assert sp.trace_id == root.trace_id and sp.parent_id == root.span_id
        otlp = sp.to_otlp()
        assert otlp["startTimeUnixNano"] == "100"
        assert otlp["endTimeUnixNano"] == "200"
        assert any(s.name == "engine.queue" for s in exp.spans)
    finally:
        set_tracer(None)


# ------------------------------------------------- engine lifecycle trace
def _tiny_engine():
    mcfg = LlamaConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=128, dtype=jnp.float32,
    )
    cfg = TpuEngineConfig(
        model=mcfg, num_blocks=64, block_size=4, max_batch_size=4,
        max_context=256, prefill_buckets=(16, 32, 64),
    )
    return TpuEngine(cfg, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))


async def test_engine_phase_spans_and_flight_timeline():
    """One engine request produces engine.queue/prefill/decode spans in the
    caller's trace, and a flight-recorder timeline covering the lifecycle
    (queued -> admitted -> first_token -> finish)."""
    exp = InMemoryExporter()
    tracer = _with_tracer(exp)
    rec = FlightRecorder(capacity=16)
    set_flight_recorder(rec)
    engine = _tiny_engine()
    try:
        with tracer.span("http.generate", request_id="tr1") as frontend:
            hdr = frontend.traceparent()
        req = PreprocessedRequest(
            request_id="tr1", model="m", token_ids=list(range(40, 52)),
            stop=StopConditions(max_tokens=4, ignore_eos=True),
            sampling=SamplingOptions(temperature=0.0),
            annotations={"traceparent": hdr},
        )
        toks = []
        async for out in engine.generate(req, Context("tr1")):
            toks.extend(out.token_ids)
        assert len(toks) == 4
        names = {s.name for s in exp.spans}
        assert {"engine.queue", "engine.prefill", "engine.decode"} <= names
        for name in ("engine.queue", "engine.prefill", "engine.decode"):
            sp = next(s for s in exp.spans if s.name == name)
            assert sp.trace_id == frontend.trace_id
            assert sp.parent_id == frontend.span_id
            assert sp.end_ns >= sp.start_ns
        flight = rec.timeline("tr1")
        assert flight is not None and flight["done"] and flight["error"] is None
        kinds = [e["event"]["kind"] for e in flight["events"]]
        for kind in ("queued", "admitted", "first_token", "finish"):
            assert kind in kinds, kinds
        assert kinds.index("queued") < kinds.index("admitted") < kinds.index(
            "first_token"
        )
    finally:
        engine.stop()
        set_tracer(None)
        set_flight_recorder(None)


async def test_engine_step_stats_hook_fires():
    engine = _tiny_engine()
    seen = []
    engine.stats_hook = seen.append
    try:
        req = PreprocessedRequest(
            request_id="ss1", model="m", token_ids=list(range(30, 42)),
            stop=StopConditions(max_tokens=4, ignore_eos=True),
            sampling=SamplingOptions(temperature=0.0),
        )
        async for _ in engine.generate(req, Context("ss1")):
            pass
        phases = {s.phase for s in seen}
        assert "prefill" in phases and "decode" in phases
        pre = next(s for s in seen if s.phase == "prefill")
        assert pre.tokens == 12 and pre.kv_total_blocks == 64
        dec = next(s for s in seen if s.phase == "decode")
        assert dec.tokens >= 1 and dec.duration_s >= 0
        # occupancy is an instantaneous gauge: the prefill step observed the
        # admitted request (the last decode step may already see it reaped)
        assert any(s.batch_occupancy >= 1 for s in seen)
    finally:
        engine.stop()


# --------------------------------------------------------- flight recorder
def test_flight_recorder_ring_eviction():
    rec = FlightRecorder(capacity=2)
    for i in range(3):
        rec.record(f"r{i}", "received", model="m")
    assert len(rec) == 2
    assert rec.timeline("r0") is None  # oldest evicted wholesale
    assert rec.timeline("r2") is not None
    snap = rec.snapshot()
    assert snap["capacity"] == 2 and snap["retained"] == 2
    # most-recent-first ordering
    assert [f["request_id"] for f in snap["requests"]] == ["r2", "r1"]


def test_flight_recorder_failure_dump(tmp_path):
    path = str(tmp_path / "failures.jsonl")
    rec = FlightRecorder(capacity=8, dump_path=path)
    rec.record("bad", "received", model="m")
    rec.record("bad", "routed", worker="w1")
    rec.finish("bad", error="worker exploded", error_class="internal_error")
    rec.record("good", "received", model="m")
    rec.finish("good")  # success: not dumped
    lines = [json.loads(l) for l in open(path)]
    # recorder.py event model: {"timestamp", "event"} lines, loadable as-is
    from dynamo_tpu.runtime.recorder import Recorder

    loaded = Recorder.load(path)
    assert len(lines) == len(loaded) == 3  # received, routed, abort
    assert all(e["event"]["request_id"] == "bad" for e in lines)
    assert loaded[-1][1]["kind"] == "abort"
    assert loaded[-1][1]["error_class"] == "internal_error"
    flight = rec.timeline("bad")
    assert flight["done"] and flight["error"] == "worker exploded"


def test_flight_recorder_caps_events_but_keeps_terminal():
    rec = FlightRecorder(capacity=4)
    for i in range(100):
        rec.record("r", "migration", attempt=i)
    flight = rec.timeline("r")
    assert len(flight["events"]) == 64 and flight["dropped_events"] == 36
    # the terminal abort must land even on a capped timeline — it is the
    # record a failure dump exists to preserve
    rec.finish("r", error="boom", error_class="internal_error")
    flight = rec.timeline("r")
    assert flight["events"][-1]["event"]["kind"] == "abort"
    assert flight["error"] == "boom"


def test_flight_recorder_snapshot_limit_clamped():
    rec = FlightRecorder(capacity=8)
    for i in range(4):
        rec.record(f"r{i}", "received")
    assert rec.snapshot(limit=0)["requests"] == []
    assert rec.snapshot(limit=-3)["requests"] == []
    assert len(rec.snapshot(limit=2)["requests"]) == 2


async def test_status_server_debug_requests_endpoint():
    rec = FlightRecorder(capacity=8)
    rec.record("req-ok", "received", model="m")
    rec.finish("req-ok", status="200")
    rec.record("req-bad", "received", model="m")
    rec.finish("req-bad", error="boom", error_class="internal_error")
    server = StatusServer(
        HealthState(), host="127.0.0.1", flight_recorder=rec
    )
    await server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        async with aiohttp.ClientSession() as s:
            async with s.get(base + "/debug/requests") as r:
                assert r.status == 200
                body = await r.json()
            assert {f["request_id"] for f in body["requests"]} == {
                "req-ok", "req-bad"
            }
            failed = next(
                f for f in body["requests"] if f["request_id"] == "req-bad"
            )
            assert failed["error"] == "boom"
            async with s.get(base + "/debug/requests?id=req-ok") as r:
                assert r.status == 200
                one = await r.json()
            assert one["request_id"] == "req-ok" and one["done"]
            async with s.get(base + "/debug/requests?id=nope") as r:
                assert r.status == 404
    finally:
        await server.stop()


# ----------------------------------------------------------- step telemetry
def test_step_telemetry_label_hierarchy_and_gauges():
    scope = M.MetricsScope().child(dtpu_namespace="ns1", dtpu_component="be1")
    tele = EngineTelemetry(scope, slow_step_s=0.05)

    def stats(duration_s, queue_depth=3):
        return StepStats(
            phase="decode", duration_s=duration_s, batch_occupancy=2,
            batch_size=4, tokens=16, queue_depth=queue_depth,
            kv_active_blocks=10, kv_free_blocks=54, kv_total_blocks=64,
            spec_acceptance=0.75,
        )

    tele.on_step(stats(0.01))
    tele.on_step(stats(0.2))  # over the slow threshold
    text = scope.expose().decode()
    # hierarchy labels stamped on the engine metrics
    assert 'dtpu_namespace="ns1"' in text and 'dtpu_component="be1"' in text
    assert M.STEP_DURATION_SECONDS + "_bucket" in text
    assert M.STEP_TOKENS + "_bucket" in text
    # admission-queue depth rides the canonical QUEUED_REQUESTS gauge
    q_line = next(
        l for l in text.splitlines()
        if l.startswith(M.QUEUED_REQUESTS + "{")
    )
    assert q_line.rstrip().endswith("3.0")
    slow_line = next(
        l for l in text.splitlines()
        if l.startswith(M.SLOW_STEPS_TOTAL + "{")
    )
    assert 'phase="decode"' in slow_line and slow_line.rstrip().endswith("1.0")
    assert M.SPEC_ACCEPTANCE in text and M.KV_ACTIVE_BLOCKS + "{" in text
    # one gauge for the blocks active sequences pin: its twin under another
    # name (dtpu_worker_active_decode_blocks) had no reader and went, PR 35
    assert "worker_active_decode_blocks" not in text


def test_kv_router_overlap_emits_hit_tokens():
    from dynamo_tpu.kv_router import KvRouter, KvRouterConfig, WorkerWithDpRank
    from dynamo_tpu.runtime.event_plane.base import InProcEventPlane

    scope = M.MetricsScope()
    router = KvRouter(
        InProcEventPlane(), "ns", "be", block_size=4,
        config=KvRouterConfig(use_kv_events=False),
        metrics=scope,
    )
    cands = [WorkerWithDpRank(1, 0)]
    tokens = list(range(16))
    router.schedule_tokens(tokens, cands, request_id="a")  # cold: no overlap
    router.schedule_tokens(tokens, cands, request_id="b")  # warm: full overlap
    text = scope.expose().decode()
    line = next(
        l for l in text.splitlines() if l.startswith(M.KV_HIT_TOKENS + "{")
    )
    assert float(line.rsplit(" ", 1)[1]) >= 16.0


# ------------------------------------------------------------ otlp exporter
def test_otlp_export_does_not_block_request_path():
    """export() must return immediately even with an unreachable collector
    (the POST runs on the worker thread); flush() bounds the drain wait."""
    exp = OtlpHttpExporter("http://127.0.0.1:9", timeout_s=0.2)
    tracer = Tracer(exp, batch_size=1)
    t0 = time.monotonic()
    with tracer.span("a"):
        pass
    assert time.monotonic() - t0 < 1.0
    exp.flush(timeout_s=5.0)


def test_otlp_export_queue_bounded():
    exp = OtlpHttpExporter("http://127.0.0.1:9", timeout_s=0.2, queue_max=1)
    # flood faster than the dead-endpoint worker can drain: drops are counted,
    # never raised
    from dynamo_tpu.runtime.tracing import Span, new_span_id, new_trace_id

    for _ in range(50):
        exp.export([Span("s", new_trace_id(), new_span_id())])
    exp.flush(timeout_s=5.0)
    assert exp.dropped_spans >= 0  # bookkeeping present; no exception raised


# ----------------------------------------------- global recorder defaults
def test_global_flight_recorder_env(monkeypatch):
    set_flight_recorder(None)
    monkeypatch.setenv("DTPU_FLIGHT_CAPACITY", "7")
    try:
        rec = get_flight_recorder()
        assert rec.capacity == 7
    finally:
        set_flight_recorder(None)


# ----------------------------------------------- disagg trace reconstruction
async def test_disagg_trace_reconstructs_hop_sequence(tmp_path, monkeypatch):
    """Acceptance: one disagg request (frontend -> router -> prefill ->
    transfer -> decode) produces ONE trace id whose JsonlExporter spans
    reconstruct the hop sequence with router/transfer attributes."""
    import asyncio

    from dynamo_tpu.llm import (
        ModelDeploymentCard,
        ModelManager,
        ModelWatcher,
        register_llm,
    )
    from dynamo_tpu.llm.model_card import MODEL_TYPE_PREFILL
    from dynamo_tpu.runtime import (
        DistributedRuntime,
        InProcEventPlane,
        MemKVStore,
        RouterMode,
        RuntimeConfig,
    )
    from dynamo_tpu.runtime.tracing import JsonlExporter

    # force the wire protocol so the transfer serve/pull spans cover real
    # bytes (co-resident engines would silently take the ICI device path)
    monkeypatch.setenv("DTPU_ICI_TRANSFER", "0")
    monkeypatch.setenv("DTPU_DEVICE_TRANSFER", "0")
    # the 30-token prompt is under deflect_max_tokens (128): without this the
    # router serves it aggregated and no prefill hop exists to trace
    monkeypatch.setenv("DTPU_DEFLECT", "0")
    path = str(tmp_path / "spans.jsonl")
    tracer = Tracer(JsonlExporter(path), batch_size=1)
    set_tracer(tracer)

    store, plane = MemKVStore(), InProcEventPlane()

    def rt():
        return DistributedRuntime(
            RuntimeConfig(store="mem", event_plane="inproc", lease_ttl_s=2.0),
            store=store, event_plane=plane,
        )

    prefill_rt, decode_rt, frontend_rt = await rt().start(), await rt().start(), await rt().start()
    prefill_engine, decode_engine = _tiny_engine(), _tiny_engine()
    await prefill_engine.serve_transfer()
    s_prefill = await register_llm(prefill_rt, prefill_engine, ModelDeploymentCard(
        name="dm", component="backend_prefill", model_type=[MODEL_TYPE_PREFILL],
        tokenizer="byte", kv_block_size=4, context_length=256,
    ))
    s_decode = await register_llm(decode_rt, decode_engine, ModelDeploymentCard(
        name="dm", component="backend", tokenizer="byte",
        kv_block_size=4, context_length=256,
    ))
    manager = ModelManager()
    watcher = await ModelWatcher(frontend_rt, manager, RouterMode.ROUND_ROBIN).start()
    try:
        for _ in range(100):
            pipe = manager.get("dm")
            if (
                pipe is not None and pipe.client.instances
                and pipe.prefill_router is not None
                and pipe.prefill_router.has_workers
            ):
                break
            await asyncio.sleep(0.05)
        pipe = manager.get("dm")
        assert pipe is not None and pipe.prefill_router is not None

        # the http layer's job, done by hand here: open the root span and
        # stamp its traceparent on the request annotations
        preq = PreprocessedRequest(
            request_id="dtrace", model="dm", token_ids=list(range(100, 130)),
            stop=StopConditions(max_tokens=8, ignore_eos=True),
            sampling=SamplingOptions(temperature=0.0),
        )
        with tracer.span("http.generate", request_id="dtrace") as root:
            preq.annotations["traceparent"] = root.traceparent()
            got = []
            async for out in pipe.generate_tokens(preq, Context("dtrace")):
                got.extend(out.token_ids)
        assert len(got) == 8
        tracer.flush()

        spans = [json.loads(l) for l in open(path)]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        for hop in (
            "http.generate", "router.prefill", "router.schedule",
            "worker.generate", "engine.queue", "engine.prefill",
            "engine.decode", "kv.transfer.pull", "kv.transfer.serve",
        ):
            assert hop in by_name, f"missing {hop} in {sorted(by_name)}"
        # ONE trace id across every hop
        assert {s["traceId"] for s in spans} == {root.trace_id}
        # both sides of the disagg pair ran a worker span
        assert len(by_name["worker.generate"]) == 2

        def attrs(span):
            return {a["key"]: a["value"] for a in span["attributes"]}

        # router attributes: chosen worker on the decode-hop decision
        sched = attrs(by_name["router.schedule"][-1])
        assert "worker" in sched and "mode" in sched
        # transfer attributes: wire format + bytes moved (the C++ agent, when
        # built, upgrades the wire from inline frames to native bulk fetch)
        pull = attrs(by_name["kv.transfer.pull"][0])
        assert pull["wire"]["stringValue"] in ("inline", "native")
        assert int(pull["bytes"]["intValue"]) > 0
        assert int(pull["blocks"]["intValue"]) > 0
        serve = attrs(by_name["kv.transfer.serve"][0])
        assert int(serve["bytes"]["intValue"]) > 0
        # causal order: the root opens first, decode-side engine.decode ends last
        assert int(by_name["http.generate"][0]["startTimeUnixNano"]) <= min(
            int(s["startTimeUnixNano"]) for s in spans if s["name"] != "http.generate"
        )
    finally:
        await watcher.stop()
        await s_prefill.stop()
        await s_decode.stop()
        prefill_engine.stop()
        decode_engine.stop()
        await prefill_rt.shutdown()
        await decode_rt.shutdown()
        await frontend_rt.shutdown()
        set_tracer(None)
