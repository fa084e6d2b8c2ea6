"""python -m dynamo_tpu.profiler — measure a worker's capacity envelope,
or replay a load trace for SLA attainment.

Analog of the reference's `profile_sla.py` entrypoint: sweeps (isl, batch)
on a real engine (or the mocker), writes a profile JSON the planner loads
via `--profile` / PerfInterpolator.from_profile and the mocker loads for
timing calibration.

Trace replay (reference burstgpt/sin loadgens + aiperf wrapper):

    python -m dynamo_tpu.profiler replay --shape sin --duration 60 --rate 20
    python -m dynamo_tpu.profiler replay --trace trace.jsonl --workers 4

prints one JSON line of SLA attainment (profiler/loadgen.py) measured on a
mocker fleet's simulated clocks.
"""

import argparse
import asyncio
import json
import sys

from dynamo_tpu.profiler.sweep import calibrate_mocker_args, profile_engine


async def _replay_main(argv) -> None:
    p = argparse.ArgumentParser("dynamo_tpu.profiler replay")
    p.add_argument("--trace", default=None, help="JSONL trace to replay "
                   "(default: synthesize from --shape)")
    p.add_argument("--shape", default="sin", choices=["sin", "burst", "poisson"])
    p.add_argument("--duration", type=float, default=60.0)
    p.add_argument("--rate", type=float, default=20.0)
    p.add_argument("--amplitude", type=float, default=0.8)
    p.add_argument("--period", type=float, default=30.0)
    p.add_argument("--burst-rate", type=float, default=80.0)
    p.add_argument("--burst-len", type=float, default=3.0)
    p.add_argument("--isl", type=int, default=256)
    p.add_argument("--osl", type=int, default=64)
    p.add_argument("--prefix-share", type=float, default=0.5)
    p.add_argument("--workers", type=int, default=4)
    p.add_argument("--speedup", type=float, default=20.0)
    p.add_argument("--ttft", type=float, default=0.5, help="TTFT SLA (s)")
    p.add_argument("--itl", type=float, default=0.05, help="ITL SLA (s)")
    args = p.parse_args(argv)

    from dynamo_tpu.mocker.engine import MockEngineArgs, MockerEngine
    from dynamo_tpu.profiler import loadgen

    if args.trace:
        trace = loadgen.load_trace(args.trace)
    elif args.shape == "sin":
        trace = loadgen.sinusoidal_trace(
            args.duration, args.rate, args.amplitude, args.period,
            isl=args.isl, osl=args.osl,
        )
    elif args.shape == "burst":
        trace = loadgen.bursty_trace(
            args.duration, args.rate, args.burst_rate, args.burst_len,
            args.period, isl=args.isl, osl=args.osl,
        )
    else:
        trace = loadgen.poisson_trace(
            int(args.duration * args.rate), args.rate,
            isl=args.isl, osl=args.osl,
        )
    engines = [
        MockerEngine(MockEngineArgs(
            emit_sim_ts=True, speedup_ratio=args.speedup,
        ))
        for _ in range(args.workers)
    ]
    try:
        rep = await loadgen.replay(
            trace, engines, args.ttft, args.itl,
            prefix_share=args.prefix_share, speedup=args.speedup,
        )
    finally:
        for e in engines:
            e.stop()
    # one source of truth for SLA math + report shape (profiler/loadgen.py
    # -> runtime/slo.py); byte-identical output pinned by tests/test_slo.py
    print(json.dumps(loadgen.sla_report_obj(rep, args.workers)))


def parse_args():
    p = argparse.ArgumentParser(
        "dynamo_tpu.profiler",
        epilog="subcommand: 'python -m dynamo_tpu.profiler replay ...' "
        "replays a load trace (sin/burst/poisson or a JSONL file) against "
        "a mocker fleet and prints SLA attainment; see 'replay --help'.",
    )
    p.add_argument("--engine", default="tpu", choices=["tpu", "mocker"])
    p.add_argument("--pp-bubble", action="store_true",
                   help="instead of a capacity sweep, measure the PP decode "
                        "schedules (M=1 cond-skip vs microbatched; "
                        "fleet_bench.pp_bubble_bench) and exit")
    p.add_argument("--pp", type=int, default=2,
                   help="pipeline width for --pp-bubble")
    p.add_argument("--preset", default="tiny")
    p.add_argument("--model-path", default=None)
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--isl", default="128,512,1024")
    p.add_argument("--osl", type=int, default=64)
    p.add_argument("--batch", default="1,2,4,8")
    p.add_argument("--reps", type=int, default=2)
    p.add_argument("--num-blocks", type=int, default=4096)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=2048)
    p.add_argument("--out", default="profile.json")
    p.add_argument("--print-mocker-args", action="store_true",
                   help="also print calibrated mocker timing constants")
    return p.parse_args()


async def main() -> None:
    args = parse_args()
    if args.pp_bubble:
        import json
        import os

        if args.platform == "cpu":
            # the accelerator-free path needs pp virtual devices BEFORE the
            # backend initializes (same trick as tests/conftest.py)
            flags = os.environ.get("XLA_FLAGS", "")
            if "host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + f" --xla_force_host_platform_device_count={args.pp}"
                ).strip()
        if args.platform:
            import jax

            jax.config.update("jax_platforms", args.platform)
        from dynamo_tpu.runtime.device import enable_compile_cache

        enable_compile_cache()
        from dynamo_tpu.profiler.fleet_bench import pp_bubble_bench

        print(json.dumps(pp_bubble_bench(pp=args.pp), indent=2))
        return
    isl_list = [int(x) for x in args.isl.split(",")]
    batch_list = [int(x) for x in args.batch.split(",")]

    if args.engine == "mocker":
        from dynamo_tpu.mocker.engine import MockEngineArgs, MockerEngine

        engine = MockerEngine(
            MockEngineArgs(num_blocks=args.num_blocks, block_size=args.block_size)
        )
        stopper = getattr(engine, "stop", lambda: None)
    else:
        if args.platform:
            import jax

            jax.config.update("jax_platforms", args.platform)
        from dynamo_tpu.runtime.device import enable_compile_cache

        enable_compile_cache()
        from dynamo_tpu.engine.__main__ import PRESETS
        from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
        from dynamo_tpu.engine.weights import config_from_hf, load_params

        params = None
        if args.model_path:
            mcfg = config_from_hf(args.model_path)
            params = load_params(args.model_path, mcfg)
        else:
            mcfg = PRESETS[args.preset]()
        bs = args.block_size
        ctx = ((args.max_context + bs - 1) // bs) * bs
        buckets = tuple(
            b for b in (64, 128, 256, 512, 1024, 2048, 4096, 8192) if b < ctx
        ) + (ctx,)
        engine = TpuEngine(
            TpuEngineConfig(
                model=mcfg, num_blocks=args.num_blocks, block_size=bs,
                max_batch_size=max(batch_list), max_context=ctx,
                prefill_buckets=buckets, tp=args.tp,
            ),
            params=params,
        )
        stopper = engine.stop

    try:
        result = await profile_engine(
            engine, isl_list=isl_list, osl=args.osl,
            batch_list=batch_list, reps=args.reps,
        )
    finally:
        stopper()
    result.meta["engine"] = args.engine
    result.meta["preset"] = args.preset
    result.save(args.out)
    print(json.dumps(result.to_obj()))
    if args.print_mocker_args:
        cal = calibrate_mocker_args(result)
        print(
            f"mocker timing: prefill {cal.prefill_base_s:.4f}s + "
            f"{cal.prefill_per_token_s * 1e6:.2f}us/tok; decode "
            f"{cal.decode_base_s * 1e3:.2f}ms + "
            f"{cal.decode_per_kv_block_s * 1e6:.3f}us/kv-block",
        )


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "replay":
        asyncio.run(_replay_main(sys.argv[2:]))
    else:
        asyncio.run(main())
