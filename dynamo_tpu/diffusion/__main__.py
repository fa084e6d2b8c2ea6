"""python -m dynamo_tpu.diffusion — image-generation worker.

Registers a DiffusionEngine under model_type ["images"] so the frontend's
/v1/images/generations routes to it (reference: SGLang diffusion serving,
components/src/dynamo/sglang/main.py:309,458).
"""

import argparse
import asyncio
import os
import signal

from dynamo_tpu.llm import ModelDeploymentCard, register_llm
from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig, init_logging


def parse_args():
    p = argparse.ArgumentParser("dynamo_tpu.diffusion")
    p.add_argument("--model", default="image-model", help="served model name")
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--component", default="image_backend")
    p.add_argument("--endpoint", default="generate")
    p.add_argument("--store", default=None)
    p.add_argument("--store-path", default=None)
    p.add_argument("--event-plane", default=None)
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--patch-size", type=int, default=8)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=6)
    p.add_argument("--steps", type=int, default=30, help="DDIM steps")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="force the JAX backend (wins over JAX_PLATFORMS)")
    return p.parse_args()


async def main() -> None:
    args = parse_args()
    plat = args.platform or os.environ.get("JAX_PLATFORMS")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat.split(",")[0])
    from dynamo_tpu.runtime.device import enable_compile_cache

    enable_compile_cache()
    init_logging()
    from dynamo_tpu.diffusion.engine import DiffusionEngine
    from dynamo_tpu.diffusion.model import DiffusionConfig

    cfg = RuntimeConfig.from_env(
        store=args.store, store_path=args.store_path, event_plane=args.event_plane
    )
    runtime = await DistributedRuntime(cfg).start()
    dcfg = DiffusionConfig(
        image_size=args.image_size, patch_size=args.patch_size,
        hidden=args.hidden, layers=args.layers, steps=args.steps,
    )
    engine = DiffusionEngine(dcfg, seed=args.seed)
    card = ModelDeploymentCard(
        name=args.model,
        namespace=args.namespace,
        component=args.component,
        endpoint=args.endpoint,
        model_type=["images"],
        tokenizer="byte",
    )
    served = await register_llm(runtime, engine, card, raw_token_stream=True)
    print(f"DIFFUSION_READY {args.model} {args.image_size}x{args.image_size}",
          flush=True)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await served.stop()
    await runtime.shutdown()


if __name__ == "__main__":
    asyncio.run(main())
