"""The system under test, built the way a user's worker builds it.

``build_engine`` makes the ``TpuEngineConfig`` a cell's configuration and
traffic files describe and constructs ``TpuEngine`` — the class
``python -m dynamo_tpu.engine`` builds. Every engine option the files do not
set stays at the program's default. ``warm_up`` then reaches, through
``engine.generate`` alone, every step program the cell's traffic can reach.
"""

from __future__ import annotations

import asyncio
import importlib
import time
from typing import Any, Dict, List, Optional

import numpy as np


def load_adapter(cfg: Dict[str, Any]):
    return importlib.import_module(f"benchmarks.adapters.{cfg['adapter']}")


def build_engine(cfg: Dict[str, Any], traffic_spec: Dict[str, Any], seed: int):
    from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig

    eng = dict(cfg["engine"])
    eng.update(traffic_spec["engine"])
    mcfg = load_adapter(cfg).model_config(cfg)
    ecfg = TpuEngineConfig(
        model=mcfg,
        num_blocks=int(eng["num_blocks"]),
        block_size=int(eng["block_size"]),
        max_batch_size=int(eng["max_batch_size"]),
        max_context=int(eng["max_context"]),
        tp=int(eng.get("tp", 1)),
        prefill_buckets=tuple(int(b) for b in eng["prefill_buckets"]),
        # PRNGKey takes 32 bits; the driver's seeds are larger
        seed=int(seed) % (2**31 - 1),
        **eng.get("options", {}),
    )
    return TpuEngine(ecfg)


def engine_facts(engine) -> Dict[str, Any]:
    """What the options left open resolved to (printed on an earlier line)."""
    c = engine.cfg
    return {
        "use_pallas": bool(engine.use_pallas),
        "mixed_enabled": bool(engine.mixed_enabled),
        "kernels_interpreted": bool(engine.kernels_interpreted),
        "decode_steps": int(c.decode_steps),
        "decode_pipeline": int(c.decode_pipeline),
        "max_batch_size": int(c.max_batch_size),
        "max_context": int(c.max_context),
        "prefill_buckets": list(c.prefill_buckets),
        "num_blocks": int(c.num_blocks),
        "block_size": int(c.block_size),
        "tp": int(c.tp),
        "kv_dtype": str(c.kv_dtype),
    }


async def generate(engine, request_id: str, token_ids: List[int], max_tokens: int,
                   on_chunk=None, rec: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One greedy request through ``engine.generate``; every chunk's arrival
    is stamped with the host clock. ``rec`` (filled in place) lets the caller
    keep the record of a request that never finishes."""
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        request_id=request_id, model="benchmark", token_ids=token_ids,
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling=SamplingOptions(temperature=0.0),
    )
    rec = {} if rec is None else rec
    rec.update({
        "id": request_id, "prompt_tokens": len(token_ids),
        "asked": max_tokens, "tokens": [], "logprobs": [], "t_chunks": [],
        "n_chunks": [], "cached_tokens": None, "finish": None, "error": None,
        "t_send": time.monotonic(),
    })
    ctx = Context()
    try:
        async for out in engine.generate(req, ctx):
            now = time.monotonic()
            if out.annotations and "cached_tokens" in out.annotations:
                rec["cached_tokens"] = int(out.annotations["cached_tokens"])
            if out.token_ids:
                rec["tokens"].extend(out.token_ids)
                rec["logprobs"].extend(out.logprobs or [])
                rec["t_chunks"].append(now)
                rec["n_chunks"].append(len(out.token_ids))
                if on_chunk is not None:
                    on_chunk(now, len(out.token_ids))
            if out.finish_reason is not None:
                rec["finish"] = out.finish_reason
                if out.finish_reason == "error":
                    rec["error"] = str((out.annotations or {}).get("error", "error"))
    except asyncio.CancelledError:
        ctx.stop_generating()
        raise
    except Exception as e:  # a refusal or an engine failure: counted, not hidden
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["t_done"] = time.monotonic()
    return rec


def _random_tokens(rng: np.random.Generator, vocab: int, n: int) -> List[int]:
    return rng.integers(0, vocab, n, dtype=np.int32).tolist()


async def warm_up(engine, vocab: int, seed: int, log=print) -> None:
    """Reach every step program this cell's traffic can reach, through the
    public entry point, so that nothing compiles in the window.

    By enumeration of the engine's dispatch rules, not by sample traffic.
    For each prefill bucket: a lone prompt of that length (``prefill`` at
    that bucket, then ``decode_multi``), and the same length arriving while
    another request decodes (``mixed_step`` at that bucket, where the engine
    fuses). Then more requests than slots at once, which makes the loop fall
    back to the single-step ``decode`` while one waits. A prompt above the
    largest bucket goes through the same programs chunk by chunk.

    A program whose donated arguments first came from ``device_put`` is
    compiled again when they come from another program: the smallest
    bucket's ``prefill`` runs first and again in every later scenario, so
    both variants exist when the pass ends. ``reset_slot`` is not reached:
    greedy traffic without penalties never calls it. Going through
    ``generate`` and not through the jitted functions keeps the benchmark
    off the program's internals, which later PRs may change; the window
    counts compilations and has to count none."""
    c = engine.cfg
    rng = np.random.default_rng([int(seed), 0x3A2F])
    steps = int(c.decode_steps)
    small = min(c.prefill_buckets[0], 32)
    t0 = time.monotonic()
    took = {}

    async def timed(name, coro):
        t = time.monotonic()
        out = await coro
        took[name] = round(time.monotonic() - t, 2)
        return out

    for b in c.prefill_buckets:
        n = min(b, c.max_context - 8 * steps)
        await timed(f"lone-{b}", generate(engine, f"warm-lone-{b}", _random_tokens(rng, vocab, n), steps + 2))
        # a resident decode, then an arriving chunk of this bucket
        started = asyncio.Event()
        resident = asyncio.ensure_future(generate(
            engine, f"warm-res-{b}", _random_tokens(rng, vocab, small), 4 * steps,
            on_chunk=lambda *_: started.set(),
        ))
        await started.wait()
        await timed(f"mixed-{b}", generate(engine, f"warm-mix-{b}", _random_tokens(rng, vocab, n), 2))
        await resident
    # more requests than slots: one waits, the rest decode step by step
    await timed("crowd", asyncio.gather(*[
        generate(engine, f"warm-crowd-{i}", _random_tokens(rng, vocab, small), 2 * steps)
        for i in range(c.max_batch_size + 1)
    ]))
    log(f"warm-up scenarios: {time.monotonic() - t0:.2f} s {took}")
