"""One cell, once: ``python3 benchmarks/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``. The last line of standard output is the
contract's JSON object; everything before it starts with ``#``.

One process holds the cell's chips, builds the engine the worker builds and
drives ``engine.generate`` on one asyncio loop. No server, no child that
touches JAX. Nothing here names a cell, a model or a metric: the manifest
names them, and their files are found by name (README.md).
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import contract, costs, system, trace_reduce  # noqa: E402
from benchmarks import traffic as traffic_mod  # noqa: E402
from benchmarks.metrics import _lib  # noqa: E402

TRACE_SECONDS = 5.0      # the traced sub-window (a whole window's trace is large)
TRACE_START_SHARE = 0.4  # where in the window it starts: the batch is steady by then


def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


class Context:
    """What a metric reader sees (metrics/_lib.py describes the fields)."""

    trace: Optional[trace_reduce.Reduced] = None
    trace_host: Tuple[float, float] = (0.0, 0.0)


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(f"benchmarks_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def request_record(rec: Dict[str, Any], t_ref: float, end_of_drain: float) -> Dict[str, Any]:
    t_first = rec["t_chunks"][0] if rec["t_chunks"] else None
    t_last = rec["t_chunks"][-1] if rec["t_chunks"] else None
    n_out = len(rec["tokens"])
    ok = rec["error"] is None and rec["finish"] == "length" and n_out == rec["asked"]
    return {
        "id": rec["id"], "t_ref": t_ref, "ok": ok, "error": rec["error"],
        "finish": rec["finish"], "asked": rec["asked"], "n_out": n_out,
        "prompt_tokens": rec["prompt_tokens"], "cached_tokens": rec["cached_tokens"],
        "t_first": t_first, "t_last": t_last,
        "t_last_or_end": t_last if rec["finish"] is not None and t_last is not None else end_of_drain,
        "ttft_s": (t_first - t_ref) if t_first is not None else None,
        "tpot_s": ((t_last - t_first) / (n_out - 1)) if n_out >= 2 else None,
        "t_chunks": rec["t_chunks"], "n_chunks": rec["n_chunks"],
    }


async def trace_sub_window(t0: float, seconds: float, trace_dir: str, marker: str, out: Dict[str, Any]) -> None:
    """Profile ``TRACE_SECONDS`` of the window. The marker event spans the
    traced sub-window on the trace's clock; its host times are kept so that
    host-clock records can be laid beside device events."""
    import jax

    loop = asyncio.get_running_loop()
    length = min(TRACE_SECONDS, 0.5 * seconds)
    await asyncio.sleep(max(0.0, t0 + TRACE_START_SHARE * seconds - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the Python tracer slows the host most
    opts.host_tracer_level = 2
    await loop.run_in_executor(None, lambda: jax.profiler.start_trace(trace_dir, profiler_options=opts))
    try:
        ann = jax.profiler.TraceAnnotation(marker)
        h_lo = time.monotonic()
        ann.__enter__()
        try:
            await asyncio.sleep(length)
        finally:
            ann.__exit__(None, None, None)
            h_hi = time.monotonic()
    finally:
        await loop.run_in_executor(None, jax.profiler.stop_trace)
    out["host"] = (h_lo, h_hi)


async def run_window(engine, tr: traffic_mod.Traffic, seconds: float,
                     trace_dir: Optional[str], marker: str) -> Dict[str, Any]:
    """Offer the cell's load for ``seconds`` and drain. Requests DUE in the
    window are attempted; each is timed from its due time (open loop) or its
    send time (closed loop)."""
    raw: List[Tuple[Dict[str, Any], float]] = []   # (record, t_ref)
    state = {"tokens": 0, "late": 0.0}
    t0 = time.monotonic()
    end = t0 + seconds

    def on_chunk(now: float, n: int) -> None:
        if now < end:
            state["tokens"] += n

    async def one(req: traffic_mod.Request, t_ref: float) -> None:
        rec: Dict[str, Any] = {}
        raw.append((rec, t_ref))
        await system.generate(engine, f"w-{req.index}", tr.tokens(req), req.output_tokens,
                              on_chunk=on_chunk, rec=rec)

    tasks: List[asyncio.Future] = []
    traced: Dict[str, Any] = {}
    tracer = None
    if trace_dir is not None:
        tracer = asyncio.ensure_future(trace_sub_window(t0, seconds, trace_dir, marker, traced))

    if tr.loop == "open":
        for req in tr.open_schedule():
            due = t0 + req.due_s
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            state["late"] = max(state["late"], time.monotonic() - due)
            tasks.append(asyncio.ensure_future(one(req, due)))
        await asyncio.sleep(max(0.0, end - time.monotonic()))
    else:
        async def client(k: int) -> None:
            while True:
                now = time.monotonic()
                if now >= end:
                    return
                await one(tr.next_request(k), now)

        tasks = [asyncio.ensure_future(client(k)) for k in range(tr.clients)]
        await asyncio.sleep(max(0.0, end - time.monotonic()))

    pending = [t for t in tasks if not t.done()]
    if pending:
        await asyncio.wait(pending, timeout=tr.drain_s)
    end_of_drain = time.monotonic()
    unfinished = 0
    for t in tasks:
        if not t.done():
            unfinished += 1
            t.cancel()
    if unfinished:
        await asyncio.gather(*tasks, return_exceptions=True)
    for t in tasks:
        if t.done() and not t.cancelled() and t.exception() is not None:
            raise t.exception()
    if tracer is not None:
        await tracer
    return {
        "t0": t0, "end": end, "end_of_drain": end_of_drain,
        "raw": raw, "tokens_in_window": state["tokens"],
        "late_s": state["late"], "unfinished": unfinished,
        "trace_host": traced.get("host"),
    }


async def reference_comparison(engine, cfg: Dict[str, Any], seed: int, calibrate: bool) -> Dict[str, Any]:
    """Hold the engine to the plain float32 reference, at the configuration's
    published widths, outside the window: prefill, then decode through the
    paged cache, against one full forward."""
    import jax

    ref = importlib.import_module(f"benchmarks.reference.{cfg['reference']}")
    plan = {"n": 4, "lo": 200, "hi": 600, "tokens": 32, **cfg.get("reference_sample", {})}
    prompts = list(traffic_mod.iter_sample_prompts(
        seed, cfg["vocab_size"], plan["n"], plan["lo"], plan["hi"],
        longer_than=engine.cfg.prefill_chunk,
    ))
    recs = await asyncio.gather(*[
        system.generate(engine, f"ref-{i}", p, plan["tokens"]) for i, p in enumerate(prompts)
    ])
    for r in recs:
        if r["error"] is not None or len(r["tokens"]) != plan["tokens"]:
            return {"ok": False, "reason": f"the engine did not answer a sample: {r['error']} {r['finish']}"}
    samples = [{"prompt": p, "tokens": r["tokens"], "logprobs": r["logprobs"]} for p, r in zip(prompts, recs)]
    params = system.load_adapter(cfg).reference_params(engine)
    pad_to = -(-(plan["hi"] + plan["tokens"]) // 128) * 128
    dev = jax.devices()[0]
    t = time.monotonic()
    res = ref.compare(cfg, params, samples, pad_to, device=dev)
    res["seconds"] = time.monotonic() - t
    res["prompt_lengths"] = [len(p) for p in prompts]
    if calibrate:
        L = cfg["num_hidden_layers"]
        res["if_a_layer_were_skipped"] = ref.compare(cfg, params, samples, pad_to, device=dev, skip_layer=L // 2)
        res["if_the_cache_held_8_bits"] = ref.compare(cfg, params, samples, pad_to, device=dev, kv_bits=8)
    return res


async def prefill_shared_prefixes(engine, tr: traffic_mod.Traffic) -> float:
    """What the traffic needs before the window: each shared prefix sent
    once, so the prefix cache holds it (one at a time: the order of the pages
    is then the same in every run)."""
    t = time.monotonic()
    for g in range(tr.groups):
        rec = await system.generate(engine, f"prefix-{g}", tr.prefix(g).tolist() + [0], 1)
        if rec["error"] is not None:
            raise RuntimeError(f"prefilling shared prefix {g} failed: {rec['error']}")
    return time.monotonic() - t


def say_window_statistics(ctx, win, attempted, failed, errors, wrong_count) -> None:
    """Earlier lines for the builder: the steps by phase, whether a backlog
    grew through the window, and the latency statistics a metric could be
    chosen from."""
    phases: Dict[str, int] = {}
    thirds: List[List[int]] = [[], [], []]
    for t, s in ctx.steps:
        phases[s.phase] = phases.get(s.phase, 0) + 1
        thirds[min(2, int(3 * (t - win["t0"]) / ctx.seconds))].append(s.queue_depth + s.batch_occupancy)
    backlog = [round(sum(x) / len(x), 2) if x else None for x in thirds]
    in_flight_at_end = sum(1 for r in ctx.requests if r["t_last_or_end"] > win["end"] or not r["ok"])
    say(f"window: attempted {attempted}, failed {failed}, errors {errors}, wrong token count {wrong_count}; "
        f"steps {json.dumps(phases)}; output tokens in the window {ctx.tokens_in_window}; requests in the "
        f"system (waiting + running), mean over each third of the window: {backlog}; in flight when the "
        f"window closed: {in_flight_at_end}")
    answered = [r["ttft_s"] for r in ctx.requests if r["ttft_s"] is not None]
    say("latency statistics over the window's requests, for choosing metrics (not the result): " + json.dumps({
        "n": attempted,
        "ttft_ms": {q: _lib.ttft_ms(ctx, q) for q in (50, 90, 95, 99)},
        "ttft_mean_ms": sum(answered) / len(answered) * 1e3 if answered else None,
        "tpot_ms": {q: _lib.tpot_ms(ctx, q) for q in (50, 90, 95, 99)},
        "output_tokens_per_s": ctx.tokens_in_window / ctx.seconds,
    }))


def read_trace(ctx, trace_dir: str, layout: Dict[str, Any], dump_to: Optional[str], name: str) -> Dict[str, Any]:
    """Reduce the traced sub-window into ``ctx.trace``; returns ``breakdown``."""
    if dump_to:
        os.makedirs(dump_to, exist_ok=True)
        with open(os.path.join(dump_to, f"{name}.trace.txt"), "w") as f:
            trace_reduce.dump(trace_dir, out=f, save_small=os.path.join(dump_to, f"{name}.small.json"))
    t_load = time.monotonic()
    trace = trace_reduce.load_xplane(trace_dir, layout)
    t_reduce = time.monotonic()
    ctx.trace = red = trace_reduce.Reduced(trace, layout)
    # host-clock intervals with a request in flight, moved to the trace's clock
    shift = red.lo - int(ctx.trace_host[0] * 1e9)
    in_flight = [(int(r["t_ref"] * 1e9) + shift, int(r["t_last_or_end"] * 1e9) + shift)
                 for r in ctx.requests_all]
    breakdown = {"device_ops": red.top_ops(10), "idle_gaps": red.idle_by_class(in_flight)}
    say(f"trace read in {t_reduce - t_load:.1f} s, reduced in {time.monotonic() - t_reduce:.1f} s")
    say(f"trace: window {red.window_s:.4f} s (host {ctx.trace_host[1] - ctx.trace_host[0]:.4f} s), busy per device "
        f"{red.busy_s_per_device}, {len(red.ops)} ops and {len(red.modules)} program executions on device 0")
    return breakdown


async def main_async(args, manifest, cell, cfg, spec) -> int:
    import jax

    from dynamo_tpu.runtime.device import enable_compile_cache

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if args.rehearsal:
        if platform != "cpu":
            sys.exit("the rehearsal is for the CPU; on a chip run the real cell")
        peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    else:
        if platform != "tpu":
            sys.exit(f"this benchmark measures the chip, and JAX's platform here is {platform!r} "
                     f"({kind} x{len(devs)}); nothing falls back")
        peaks = costs.peaks_for(kind)  # an unknown kind raises
    if len(devs) < cell["chips"]:
        sys.exit(f"cell {cell['name']!r} needs {cell['chips']} chips, JAX sees {len(devs)}")

    compiles = {"n": 0}

    def count_compile(event: str, _seconds: float, **_kw) -> None:
        # fires for a program compiled AND for one loaded from the cache
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(count_compile)
    say(f"compile cache: {enable_compile_cache()}")
    say(f"device: {platform} {kind!r} x{len(devs)}; cell {cell['name']!r} uses {cell['chips']}")

    tr = traffic_mod.Traffic(spec, args.seed, args.seconds, cfg["vocab_size"])
    say(f"traffic: {json.dumps(tr.describe())}")
    t = time.monotonic()
    engine = system.build_engine(cfg, spec, args.seed)
    facts = system.engine_facts(engine)
    say(f"engine built in {time.monotonic() - t:.2f} s: {json.dumps(facts)}")
    need = tr.longest_prompt() + tr.max_output + 2 * facts["decode_steps"] * facts["decode_pipeline"]
    if need >= facts["max_context"]:
        sys.exit(f"max_context {facts['max_context']} leaves no headroom above the longest "
                 f"prompt + output + horizon ({need})")
    steps_log: List[Tuple[float, Any]] = []
    engine.stats_hook = lambda s: steps_log.append((time.monotonic(), s))
    try:
        t = time.monotonic()
        await system.warm_up(engine, cfg["vocab_size"], args.seed, log=say)
        say(f"warm-up: {time.monotonic() - t:.2f} s, {compiles['n']} programs compiled or loaded so far")
        ref = await reference_comparison(engine, cfg, args.seed, args.calibrate)
        say(f"reference: {json.dumps(ref)}")
        if tr.prefill_in_setup:
            say(f"shared prefixes prefilled in {await prefill_shared_prefixes(engine, tr):.2f} s")

        trace_dir = None
        # the rehearsal reads the CPU backend's host threads in the device's place
        layout = trace_reduce.load_layout(
            os.path.join(HERE, "rehearsal", "trace_layout.json") if args.rehearsal else None)
        if args.trace:
            trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
        compiled_before = compiles["n"]
        steps_log.clear()
        setup_s = time.monotonic() - T_START
        win = await run_window(engine, tr, args.seconds, trace_dir, layout["marker"])
        compiled_in_window = compiles["n"] - compiled_before
    finally:
        engine.stop()
    say(f"set-up {setup_s:.3f} s; programs compiled or loaded inside the window and drain: "
        f"{compiled_in_window}; the generator's worst lateness: {win['late_s'] * 1e3:.3f} ms; "
        f"unfinished at the end of the drain: {win['unfinished']}")

    ctx = Context()
    ctx.requests_all = [request_record(rec, t_ref, win["end_of_drain"]) for rec, t_ref in win["raw"]]
    ctx.requests = [r for r in ctx.requests_all if r["t_ref"] < win["end"]]
    ctx.steps_all = steps_log
    ctx.steps = [(t, s) for t, s in steps_log if win["t0"] <= t < win["end"]]
    ctx.cfg, ctx.engine, ctx.peaks = cfg, facts, peaks
    ctx.seconds, ctx.setup_s = float(args.seconds), setup_s
    ctx.tokens_in_window = win["tokens_in_window"]
    ctx.drain_end = win["end_of_drain"]
    attempted = len(ctx.requests)
    failed = sum(1 for r in ctx.requests if not r["ok"])
    errors = sorted({r["error"] for r in ctx.requests if r["error"]})
    wrong_count = [r["id"] for r in ctx.requests if r["finish"] is not None and r["error"] is None and r["n_out"] != r["asked"]]
    correct = bool(ref.get("ok")) and not errors and not wrong_count
    say_window_statistics(ctx, win, attempted, failed, errors, wrong_count)
    device = {
        "platform": platform, "kind": kind, "count": len(devs),
        "memory_peak_bytes": max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs[: cell["chips"]]
        ),
    }
    if args.rehearsal and not device["memory_peak_bytes"]:
        device["memory_peak_bytes"] = 1  # the CPU backend reports no memory statistics
    line: Dict[str, Any] = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        ctx.trace_host = win["trace_host"]
        line["breakdown"] = read_trace(ctx, trace_dir, layout, args.dump_trace, cell["name"])
        device["window_s"], device["busy_s"] = ctx.trace.window_s, ctx.trace.busy_s
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics: Dict[str, Any] = {}
    for m in contract.metrics_of(manifest, cell["name"], bool(args.trace)):
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line["metrics"], line["device"] = metrics, device

    bad = contract.check_line(manifest, cell["name"], bool(args.trace), line)
    try:
        text = contract.dumps(line)
    except ValueError as e:
        bad.append(f"the line does not serialise: {e}")
    if bad:
        print("the run's line breaks the contract and is not printed:", file=sys.stderr)
        for b in bad:
            print(f"  - {b}", file=sys.stderr)
        print(f"  the line was: {line!r}", file=sys.stderr)
        return 4
    if args.rehearsal:
        print(f"REHEARSAL on the CPU, not a result: {text}", file=sys.stderr, flush=True)
        print("# rehearsal only: no result line", flush=True)
        return 0
    print(text, flush=True)
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal of the harness on benchmarks/rehearsal's tiny cells; never prints a result line")
    ap.add_argument("--calibrate", action="store_true",
                    help="also print how far a skipped layer and an 8-bit cache move the reference comparison")
    ap.add_argument("--override-rate", type=float, default=None,
                    help="open loop only: offer this rate (the one sweep that finds a cell's knee)")
    ap.add_argument("--dump-trace", default=None, help="directory for a by-hand summary of the trace")
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args()

    base = os.path.join(HERE, "rehearsal") if args.rehearsal else ROOT
    manifest = contract.load_manifest(os.path.join(base, "BENCHMARK.json"))
    cell = contract.cell_of(manifest, args.workload)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    spec = traffic_mod.load(os.path.join(ROOT, manifest["paths"][0], "traffic", f"{cell['traffic']}.json"))
    if args.override_rate is not None:
        spec["rate_per_s"] = args.override_rate
    sys.exit(asyncio.run(main_async(args, manifest, cell, cfg, spec)))


if __name__ == "__main__":
    main()
