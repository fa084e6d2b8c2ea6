"""python -m dynamo_tpu.run — single-command wiring of inputs to engines.

Analog of the reference's dynamo-run CLI (launch/dynamo-run/src/main.rs:30-33,
opt.rs:6-17: `dynamo-run in=<input> out=<engine>`): everything in one
process with in-proc planes — the fastest way to poke a model or script a
batch, no services to stand up.

    python -m dynamo_tpu.run in=text:"hello world" out=tiny
    python -m dynamo_tpu.run in=stdin out=mocker
    python -m dynamo_tpu.run in=batch:prompts.txt out=qwen3-0.6b --max-tokens 32
    python -m dynamo_tpu.run in=http:8000 out=tiny        # OpenAI frontend

Engines (`out=`): echo | mocker | any model preset | a local HF checkpoint
path. Inputs (`in=`): text:<prompt> | stdin | batch:<file> (one prompt per
line, results as JSONL on stdout) | http:<port>.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
from typing import Any, AsyncIterator

from .llm import ModelDeploymentCard, register_llm
from .llm.protocols.common import BackendOutput
from .runtime import DistributedRuntime, RuntimeConfig, init_logging
from .runtime.engine import Context


class EchoEngine:
    """Reference engines.rs:67 make_echo_engine: tokens in, tokens out."""

    async def generate(self, request: Any, context: Context) -> AsyncIterator[Any]:
        req = request if isinstance(request, dict) else request.to_obj()
        for tid in req.get("token_ids", []):
            yield BackendOutput(token_ids=[tid])
        yield BackendOutput(finish_reason="stop", token_ids=[])


def _build_engine(out: str, args):
    if out == "echo":
        return EchoEngine(), "byte", 4096
    if out == "mocker":
        from .mocker.engine import MockEngineArgs, MockerEngine

        return MockerEngine(MockEngineArgs(speedup_ratio=args.speedup)), "byte", 4096
    from .engine.engine import TpuEngine, TpuEngineConfig
    from .engine import __main__ as engine_main

    if out in engine_main.PRESETS:
        mcfg = engine_main.PRESETS[out]()
        params, tokenizer = None, "byte"
    else:  # a local HF checkpoint directory or hub reference (llm/hub.py)
        from .engine.warm import load_params_warm
        from .engine.weights import config_from_hf
        from .llm.hub import resolve_model_path

        out = resolve_model_path(out)
        mcfg = config_from_hf(out)
        params = load_params_warm(out, mcfg)
        tokenizer = out
    cfg = TpuEngineConfig(
        model=mcfg, max_context=args.max_context,
        num_blocks=max(512, (args.max_context // 16) * 16),
        prefill_buckets=tuple(
            b for b in (64, 128, 256, 512, 1024, 2048) if b < args.max_context
        ) + (args.max_context,),
    )
    return TpuEngine(cfg, params=params), tokenizer, args.max_context


async def _serve(engine, tokenizer: str, ctx_len: int, model: str):
    rt = await DistributedRuntime(
        RuntimeConfig(store="mem", event_plane="inproc")
    ).start()
    card = ModelDeploymentCard(
        name=model, tokenizer=tokenizer, kv_block_size=16, context_length=ctx_len,
    )
    await register_llm(rt, engine, card)
    return rt, card


async def _client_pipeline(rt, card):
    from .llm.discovery import ModelManager, ModelWatcher

    manager = ModelManager()
    watcher = await ModelWatcher(rt, manager).start()
    for _ in range(200):
        if manager.get(card.name) is not None:
            break
        await asyncio.sleep(0.02)
    pipeline = manager.get(card.name)
    if pipeline is None:
        raise RuntimeError(f"model {card.name!r} never appeared in discovery")
    return watcher, manager, pipeline


async def _gen_text(pipeline, model: str, prompt: str, args) -> AsyncIterator[str]:
    from .llm.protocols.openai import CompletionRequest

    req = CompletionRequest(
        model=model, prompt=prompt, max_tokens=args.max_tokens, stream=True,
        temperature=args.temperature,
    )
    preq = pipeline.preprocessor.preprocess_completion(req, prompt)
    ctx = Context(preq.request_id)
    try:
        async for out in pipeline.generate_tokens(preq, ctx):
            if out.text:
                yield out.text
            if out.finish_reason is not None:
                return
    finally:
        ctx.stop_generating()


async def run(args) -> None:
    init_logging()
    kind, _, val = args.input.partition(":")
    engine, tokenizer, ctx_len = _build_engine(args.out, args)
    model = args.model or (args.out if not args.out.startswith("/") else "local")
    rt, card = await _serve(engine, tokenizer, ctx_len, model)

    if kind == "http":
        from .llm.discovery import ModelManager, ModelWatcher
        from .llm.http.service import HttpService

        manager = ModelManager()
        await ModelWatcher(rt, manager).start()
        svc = HttpService(manager, port=int(val or 8000))
        await svc.start()
        print(f"OpenAI frontend on :{svc.port} serving {model!r} (ctrl-c to stop)",
              file=sys.stderr)
        try:
            await asyncio.Event().wait()
        except (KeyboardInterrupt, asyncio.CancelledError):
            pass
        await svc.stop()
        return

    watcher, manager, pipeline = await _client_pipeline(rt, card)
    try:
        if kind == "text":
            async for delta in _gen_text(pipeline, model, val, args):
                print(delta, end="", flush=True)
            print()
        elif kind == "stdin":
            print(f"interactive with {model!r} — empty line quits", file=sys.stderr)
            loop = asyncio.get_running_loop()
            while True:
                line = await loop.run_in_executor(None, sys.stdin.readline)
                line = (line or "").strip()
                if not line:
                    break
                async for delta in _gen_text(pipeline, model, line, args):
                    print(delta, end="", flush=True)
                print()
        elif kind == "batch":
            with open(val) as f:
                prompts = [l.rstrip("\n") for l in f if l.strip()]
            for n, prompt in enumerate(prompts):
                chunks = []
                async for delta in _gen_text(pipeline, model, prompt, args):
                    chunks.append(delta)
                print(json.dumps({"index": n, "prompt": prompt,
                                  "text": "".join(chunks)}))
        else:
            raise SystemExit(f"unknown input {args.input!r} "
                             "(text:<prompt> | stdin | batch:<file> | http:<port>)")
    finally:
        await watcher.stop()
        for p in manager.pipelines():
            await p.stop()
        if hasattr(engine, "stop"):
            engine.stop()
        await rt.shutdown()


def main() -> None:
    p = argparse.ArgumentParser(
        "dynamo_tpu.run",
        usage='python -m dynamo_tpu.run in=<input> out=<engine> [options]',
    )
    p.add_argument("io", nargs=2, metavar="in=|out=",
                   help="in=text:<p>|stdin|batch:<f>|http:<port>  "
                        "out=echo|mocker|<preset>|<hf-dir>")
    p.add_argument("--model", default=None, help="served model name")
    p.add_argument("--max-tokens", type=int, default=64)
    p.add_argument("--max-context", type=int, default=2048)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--speedup", type=float, default=1.0, help="mocker clock")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    args = p.parse_args()

    spec = {}
    for part in args.io:
        k, _, v = part.partition("=")
        spec[k] = v
    if "in" not in spec or "out" not in spec:
        p.error("need both in=<input> and out=<engine>")
    args.input, args.out = spec["in"], spec["out"]
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from dynamo_tpu.runtime.device import enable_compile_cache

    enable_compile_cache()
    asyncio.run(run(args))


if __name__ == "__main__":
    main()
