"""python -m dynamo_tpu.engine — a real TPU/JAX engine worker.

The TPU-native analog of `python -m dynamo.vllm` (components/src/dynamo/vllm/
main.py): brings up a TpuEngine (paged KV, continuous batching, TP-sharded
forward), registers the model card + endpoint, publishes KV events and load
metrics for the router.

Model selection:
  --model-path /path/to/hf_checkpoint   local HF llama/qwen checkpoint
  --preset tiny|qwen3-0.6b|llama3-8b|llama3-70b  random-init architecture
"""

import argparse
import asyncio
import os
import signal

from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.engine.weights import config_from_hf, load_params
from dynamo_tpu.kv_router import KvEventPublisher, WorkerMetricsPublisher
from dynamo_tpu.llm import ModelDeploymentCard, ModelRuntimeConfig, register_llm
from dynamo_tpu.models.cohere2_moe import Cohere2MoeConfig
from dynamo_tpu.models.dots3_note import Dots3NoteConfig
from dynamo_tpu.models.evabyte import EvaByteConfig
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.gemma import GemmaConfig
from dynamo_tpu.models.gptoss import GptOssConfig
from dynamo_tpu.models.minicpm_sala import MiniCpmSalaConfig
from dynamo_tpu.models.mla import MlaConfig
from dynamo_tpu.models.moe import MoeConfig
from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig, init_logging
from dynamo_tpu.runtime.component import new_instance_id
from dynamo_tpu.runtime.config import (
    ENV_KVBM_DISK_CACHE_GB,
    ENV_KVBM_DISK_PATH,
    ENV_KVBM_HOST_CACHE_GB,
    ENV_KVBM_REMOTE,
    ENV_MIGRATION_LIMIT,
    ENV_NAMESPACE,
    env_float,
    env_int,
    env_str,
)

PRESETS = {
    "tiny": lambda: LlamaConfig(),
    "qwen3-0.6b": LlamaConfig.qwen3_0_6b,
    "llama3-8b": LlamaConfig.llama3_8b,
    "llama3-70b": LlamaConfig.llama3_70b,
    "tiny-moe": MoeConfig.tiny_moe,
    "qwen3-30b-a3b": MoeConfig.qwen3_30b_a3b,
    "tiny-gptoss": GptOssConfig.tiny_gptoss,
    "gpt-oss-20b": GptOssConfig.gpt_oss_20b,
    "gpt-oss-120b": GptOssConfig.gpt_oss_120b,
    "tiny-gemma2": GemmaConfig.tiny_gemma2,
    "tiny-gemma3": GemmaConfig.tiny_gemma3,
    "gemma2-2b": GemmaConfig.gemma2_2b,
    "gemma3-4b": GemmaConfig.gemma3_4b,
    "tiny-mla": MlaConfig.tiny_mla,
    "tiny-mla-moe": MlaConfig.tiny_mla_moe,
    "deepseek-v2-lite": MlaConfig.deepseek_v2_lite,
    "deepseek-v3": MlaConfig.deepseek_v3,
    "tiny-vl": lambda: LlamaConfig(),  # language side; vision below
    # pages as a ring of one window with summaries by window (byte-level;
    # --block-size 16, --prefill-chunk at most the window: 256 / 2048)
    "tiny-evabyte": EvaByteConfig.tiny,
    "evabyte-6.5b": EvaByteConfig.evabyte_6_5b,
    # pages by layer kind: sliding layers hold one window, full layers all
    "tiny-cohere2-moe": Cohere2MoeConfig.tiny,
    "command-a-plus": Cohere2MoeConfig.command_a_plus,
    "tiny-dots3-note": Dots3NoteConfig.tiny,
    "dots3-note": Dots3NoteConfig.dots3_note,
    # block-sparse attention over pooled keys in one layer of four, slot
    # state (lightning attention) in the other three (--block-size 16: a
    # pooled key's stride is the page)
    "tiny-minicpm-sala": MiniCpmSalaConfig.tiny,
    "minicpm-sala": MiniCpmSalaConfig.minicpm_sala_9b,
}

from dynamo_tpu.models.vision import VisionConfig

# vision towers paired with language presets (models/vision.py)
VISION_PRESETS = {
    "tiny-vl": lambda mcfg: VisionConfig.tiny(out_hidden_size=mcfg.hidden_size),
}


def parse_args():
    p = argparse.ArgumentParser("dynamo_tpu.engine")
    p.add_argument("--model", default="tpu-model", help="served model name")
    p.add_argument("--model-path", default=None, help="local HF checkpoint dir")
    p.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    p.add_argument("--tokenizer", default=None, help="tokenizer path (default: model-path or byte)")
    p.add_argument("--tool-parser", default=None,
                   help="streaming tool-call dialect for this model's card "
                        "(parsers/tool_calls.py registry); default: harmony "
                        "for gpt-oss presets, else none")
    p.add_argument("--reasoning-parser", default=None,
                   help="reasoning-block parser for the card "
                        "(e.g. deepseek_r1, qwen3, gpt_oss; "
                        "parsers/reasoning.py registry); default: gpt_oss "
                        "for gpt-oss presets, else none")
    p.add_argument("--namespace", default=env_str(ENV_NAMESPACE, "dynamo"))
    p.add_argument("--component", default="backend")
    p.add_argument("--endpoint", default="generate")
    p.add_argument("--store", default=None)
    p.add_argument("--store-path", default=None)
    p.add_argument("--event-plane", default=None)
    p.add_argument("--status-port", type=int, default=-1,
                   help="system status server port (/health /live /metrics "
                   "/metadata); 0 = ephemeral, -1 = disabled")
    p.add_argument("--graceful-timeout", type=float, default=10.0,
                   help="seconds to wait for in-flight requests on shutdown")
    p.add_argument(
        "--platform", default=None, choices=["cpu", "tpu"],
        help="force the JAX backend (applied with jax.config.update before "
        "the first device use, so it wins over JAX_PLATFORMS). With tpu the "
        "worker fails at start-up when no TPU answers; nothing falls back",
    )
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel ranks served by this worker: each rank "
                   "gets its own engine + KV pool on its own tp-sized device "
                   "group; the KV router targets (worker, dp_rank)")
    p.add_argument("--num-blocks", type=int, default=2048)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--kv-dtype", default="auto",
                   choices=("auto", "model", "int8"),
                   help="paged-KV storage precision (docs/operations.md "
                        "'KV precision'): int8 = quantized cache w/ "
                        "per-block scales, ~0.51x bf16 KV bytes; auto "
                        "defers to DTPU_KV_DTYPE (default: model dtype)")
    p.add_argument("--mixed", default="auto", choices=("auto", "on", "off"),
                   help="mixed continuous batching (docs/operations.md 5c): "
                        "a prefill chunk fuses with the resident decode "
                        "batch through the unified ragged kernel; auto "
                        "defers to DTPU_MIXED (default on, auto-gated off "
                        "for pp/sp/spec/vision/LoRA/multihost)")
    p.add_argument("--max-batch-size", type=int, default=8)
    p.add_argument("--max-context", type=int, default=2048,
                   help="may exceed the largest prefill bucket: long prompts "
                   "prefill in bounded chunks")
    p.add_argument("--prefill-chunk", type=int, default=2048,
                   help="largest single prefill dispatch (= largest bucket)")
    p.add_argument("--sp", type=int, default=1,
                   help="context-parallel ring attention width for chunk "
                   "prefill (sequence sharded over the sp mesh axis)")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel stages for serving: layers + "
                   "paged KV shard over a pp mesh axis, activations ride a "
                   "shard_map wavefront (parallel/pp_serving.py)")
    p.add_argument("--migration-limit", type=int,
                   default=env_int(ENV_MIGRATION_LIMIT, 0))
    p.add_argument("--kvbm-host-gb", type=float,
                   default=env_float(ENV_KVBM_HOST_CACHE_GB, 0.0),
                   help="host DRAM KV tier size (G2); 0 disables kvbm")
    p.add_argument("--kvbm-disk-gb", type=float,
                   default=env_float(ENV_KVBM_DISK_CACHE_GB, 0.0),
                   help="disk KV tier size (G3)")
    p.add_argument("--kvbm-disk-path",
                   default=env_str(ENV_KVBM_DISK_PATH, "/tmp/dtpu_kvbm"))
    p.add_argument("--kvbm-remote",
                   default=(env_str(ENV_KVBM_REMOTE, "") or None),
                   metavar="HOST:PORT",
                   help="G4 fleet-shared block store "
                        "(python -m dynamo_tpu.kvbm)")
    p.add_argument("--lora-max-adapters", type=int, default=0,
                   help="static multi-LoRA slots; enables the load_lora/"
                        "unload_lora/list_loras endpoints (reference "
                        "components/src/dynamo/vllm/main.py:712)")
    p.add_argument("--lora-rank", type=int, default=16)
    p.add_argument("--no-warm-cache", action="store_true",
                   help="disable the host weight cache (engine/warm.py)")
    p.add_argument("--decode-steps", type=int, default=None,
                   help="decode iterations per compiled horizon; default "
                        "auto-tunes from the measured device RTT (multihost "
                        "pins 32 — per-process autotune would desync the "
                        "replayed programs)")
    p.add_argument("--decode-pipeline", type=int, default=None,
                   help="in-flight decode horizons; default auto-tunes with "
                        "--decode-steps (multihost pins 2)")
    p.add_argument("--ready-single-step", action="store_true",
                   help="build the single-step decode program (the loop's "
                        "fallback while a request waits) at start, not at "
                        "the first tick that finds a request waiting")
    p.add_argument("--weight-service", default=None, metavar="SOCK",
                   help="unix socket of a weight owner process "
                        "(engine/weight_service.py; reference "
                        "lib/gpu_memory_service): import weights from host "
                        "shared memory instead of parsing the checkpoint; "
                        "also honors $DTPU_WEIGHT_SERVICE")
    p.add_argument("--logits-processors", default=None,
                   help="named example processors to register, e.g. "
                        "'ban=5,7,9;temperature=0.7;norepeat=2.0' — requests "
                        "opt in via the logits_processors field "
                        "(dynamo_tpu/logits_processing)")
    p.add_argument("--spec-draft", default=None, choices=sorted(PRESETS),
                   help="enable speculative decoding with this draft "
                        "architecture (random-init unless --spec-draft-path; "
                        "docs/speculative_decoding.md). Greedy requests are "
                        "served spec; sampled ones fall back per dispatch")
    p.add_argument("--spec-draft-path", default=None,
                   help="local HF checkpoint (or hub ref) for the draft "
                        "model; implies --spec-draft semantics with the "
                        "checkpoint's architecture")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens per speculative round (clamped to "
                        "decode-steps)")
    p.add_argument("--guided-max-states", type=int, default=1024,
                   help="guided decoding automaton cap (dynamo_tpu/guided): "
                        "grammars compile to per-slot device tables "
                        "[states x classes]; 0 disables guided decoding "
                        "entirely (no mask ops in the decode programs)")
    p.add_argument("--guided-max-classes", type=int, default=320,
                   help="guided decoding token-class cap (see above)")
    p.add_argument("--eplb-redundant-experts", type=int, default=0,
                   help="EPLB (models/eplb.py): add N redundant physical "
                        "expert slots to a MoE model and spread hot "
                        "experts' tokens across replicas; rebalance at "
                        "runtime from measured loads. MoE presets/"
                        "checkpoints only; (E+N) must divide over tp")
    p.add_argument(
        "--disagg",
        choices=["none", "prefill", "decode"],
        default="none",
        help="prefill: join the prefill pool + serve kv_fetch; decode: serve "
        "decode with remote-KV import (also serves kv_fetch for peers)",
    )
    p.add_argument(
        "--multihost", default=None,
        metavar="COORD:PORT,NPROCS,PROC_ID[,CONTROL:PORT]",
        help="multi-process serving over one jax.distributed mesh: process 0 "
        "owns the endpoint + scheduler and broadcasts every dispatch; other "
        "processes replay them (runtime/multihost.py). tp*sp must equal the "
        "GLOBAL device count. Reference analog: one logical worker per TP "
        "group with non-leader ranks idling in the engine step loop "
        "(components/src/dynamo/vllm/main.py:67)",
    )
    return p.parse_args()


def _load_guided_vocab(engine_cfg, tokenizer_ref):
    """(vocab byte forms, eos_id) when the config enables guided decoding,
    else None. Shared by leader AND followers of a multihost group (the
    guided programs are traced on every process; a vocab drift would desync
    the replayed dispatches). A tokenizer without an EOS id cannot terminate
    grammars — guided is disabled rather than refusing to serve."""
    if engine_cfg.guided_max_states <= 0:
        return None
    from dynamo_tpu.guided import vocab_bytes_from_tokenizer
    from dynamo_tpu.llm.tokenizer import load_tokenizer

    try:
        return vocab_bytes_from_tokenizer(load_tokenizer(tokenizer_ref))
    except ValueError as e:
        print(f"guided decoding disabled: {e}", flush=True)
        engine_cfg.guided_max_states = 0
        return None


def _load_draft(args):
    """(draft_cfg, draft_params) for --spec-draft/--spec-draft-path, or
    (None, None). Checkpoint drafts ride the same warm-cache path as the
    main model."""
    if getattr(args, "spec_draft_path", None):
        from dynamo_tpu.llm.hub import resolve_model_path

        path = resolve_model_path(args.spec_draft_path)
        dcfg = config_from_hf(path)
        if args.no_warm_cache:
            return dcfg, load_params(path, dcfg)
        from dynamo_tpu.engine.warm import load_params_warm

        return dcfg, load_params_warm(path, dcfg)
    if getattr(args, "spec_draft", None):
        return PRESETS[args.spec_draft](), None
    return None, None


def make_engine_config(args, mcfg, vcfg=None, logits_procs=(), spec_draft=None):
    """TpuEngineConfig from CLI args — ONE code path for every process of a
    multihost group (leader/follower config drift would desync the replayed
    XLA programs)."""
    bs = args.block_size

    def rnd(n):  # round up to a block multiple
        return ((n + bs - 1) // bs) * bs

    ctx = rnd(args.max_context)
    # buckets bound the CHUNK size, not the context: long prompts prefill in
    # chunks of the largest bucket, so a 16k+ context never compiles a 16k-
    # wide prefill program
    chunk_cap = min(ctx, rnd(args.prefill_chunk))
    buckets = tuple(
        rnd(b) for b in (64, 128, 256, 512, 1024, 2048, 4096, 8192)
        if rnd(b) < chunk_cap
    ) + (chunk_cap,)
    args.max_context = ctx
    # decode schedule: per-process RTT autotune is NOT multihost-safe (the
    # horizon length is baked into the compiled program; leader/follower
    # resolving different steps from noisy RTT medians would desync the
    # replayed dispatches) — multihost pins one schedule for every process
    # unless the flags say otherwise
    decode_steps = getattr(args, "decode_steps", None)
    decode_pipeline = getattr(args, "decode_pipeline", None)
    if getattr(args, "multihost", None):
        decode_steps = decode_steps if decode_steps is not None else 32
        decode_pipeline = decode_pipeline if decode_pipeline is not None else 2
    return TpuEngineConfig(
        decode_steps=decode_steps,
        decode_pipeline=decode_pipeline,
        ready_single_step=getattr(args, "ready_single_step", False),
        model=mcfg,
        num_blocks=args.num_blocks,
        block_size=args.block_size,
        kv_dtype=getattr(args, "kv_dtype", "auto"),
        mixed_admission=(
            None if getattr(args, "mixed", "auto") == "auto"
            else getattr(args, "mixed") == "on"
        ),
        max_batch_size=args.max_batch_size,
        max_context=ctx,
        tp=args.tp,
        sp=args.sp,
        pp=getattr(args, "pp", 1),
        prefill_buckets=buckets,
        lora_max_adapters=args.lora_max_adapters,
        lora_rank=args.lora_rank,
        logits_processors=logits_procs,
        vision=vcfg,
        spec_draft=spec_draft,
        spec_k=getattr(args, "spec_k", 4),
        # guidance is refused under pp (not tested there) — force it off
        # rather than fail construction on default flags
        guided_max_states=(
            0 if getattr(args, "pp", 1) > 1
            else getattr(args, "guided_max_states", 0)
        ),
        guided_max_classes=getattr(args, "guided_max_classes", 320),
    )


def _build_logits_procs(args):
    """Parse --logits-processors into static (name, fn) pairs. Shared by the
    leader AND followers of a multihost group: the processors are traced into
    the XLA programs, so a config drift would desync the replayed programs."""
    if not args.logits_processors:
        return ()
    from dynamo_tpu.logits_processing import (
        ban_tokens_processor,
        repetition_window_processor,
        temperature_processor,
    )

    built = []
    for spec in args.logits_processors.split(";"):
        pname, _, val = spec.strip().partition("=")
        if pname == "ban":
            built.append(("ban", ban_tokens_processor(
                [int(t) for t in val.split(",") if t]
            )))
        elif pname == "temperature":
            built.append(("temperature", temperature_processor(float(val))))
        elif pname == "norepeat":
            built.append(("norepeat", repetition_window_processor(float(val))))
        else:
            raise SystemExit(f"unknown logits processor {pname!r}")
    return tuple(built)


def _load_model(args):
    """(mcfg, params, tokenizer_ref) from CLI args; shared by every process
    of a multihost group (identical host weights on each process are what
    make the collective device_put shards consistent)."""
    params = None
    if args.model_path:
        # --model-path accepts a local dir OR a hub reference ("org/name"
        # resolved through the HF cache / optional download — llm/hub.py,
        # reference lib/llm/src/hub.rs)
        from dynamo_tpu.llm.hub import resolve_model_path

        path = resolve_model_path(args.model_path)
        mcfg = config_from_hf(path)
        ws_sock = getattr(args, "weight_service", None) or os.environ.get(
            "DTPU_WEIGHT_SERVICE"
        )
        if ws_sock:
            # out-of-process weight import (engine/weight_service.py,
            # gpu_memory_service analog): zero-copy mmap from the owner's
            # tmpfs; the client connection is the lease — parked on args so
            # it lives as long as the process
            from dynamo_tpu.engine.weight_service import load_params_served

            params, args._weight_lease = load_params_served(
                path, mcfg, ws_sock,
                warm_fallback=not args.no_warm_cache,
            )
        elif args.no_warm_cache:
            params = load_params(path, mcfg)
        else:
            # warm restore (engine/warm.py): restarted workers skip the
            # checkpoint parse (chrek/CRIU analog, SURVEY §2.4)
            from dynamo_tpu.engine.warm import load_params_warm

            params = load_params_warm(path, mcfg)
        tokenizer_ref = args.tokenizer or path
    else:
        mcfg = PRESETS[args.preset]()
        tokenizer_ref = args.tokenizer or "byte"
    n_red = getattr(args, "eplb_redundant_experts", 0)
    if n_red > 0:
        import dataclasses as _dc

        if getattr(mcfg, "num_experts", 0) <= 0 or not hasattr(
            mcfg, "redundant_experts"
        ):
            raise SystemExit(
                "--eplb-redundant-experts needs a MoeConfig-family model"
            )
        mcfg = _dc.replace(mcfg, redundant_experts=n_red)
    return mcfg, params, tokenizer_ref


def _multihost_mesh(args, mh, rank: int = 0):
    """Rank ``rank``'s mesh, built identically on every process of the group.

    dp ranks take a STRIDED slice of the global device list
    (``devices[rank::dp]``): with process-major global ordering every rank's
    mesh spans every process, which is required — a process can only build /
    replay an engine whose arrays have addressable shards on it. (Contiguous
    slices would make each rank process-local; that layout is just N
    independent workers and needs no multihost group.)"""
    import jax

    from dynamo_tpu.parallel.mesh import make_mesh

    n = jax.device_count()
    group = (args.pp * args.tp) if args.pp > 1 else (args.tp * args.sp)
    if args.dp * group != n:
        raise SystemExit(
            f"--multihost needs dp*(pp*)tp*sp == global device count: "
            f"dp={args.dp} pp={args.pp} tp={args.tp} sp={args.sp} vs {n} "
            f"devices over {mh.num_processes} processes"
        )
    if args.dp > 1 and jax.local_device_count() % args.dp:
        raise SystemExit(
            f"--multihost dp={args.dp} needs local device count "
            f"({jax.local_device_count()}) divisible by dp so every rank "
            f"spans every process"
        )
    devs = jax.devices()[rank :: args.dp]
    if args.pp > 1:
        from dynamo_tpu.parallel.pp_serving import make_pp_mesh

        return make_pp_mesh(pp=args.pp, tp=args.tp, devices=devs)
    return make_mesh(tp=args.tp, sp=args.sp, devices=devs)


def _mh_ns(args, rank: int) -> str:
    return f"dp{rank}" if args.dp > 1 else ""


async def main() -> None:
    args = parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from dynamo_tpu.runtime.device import (
        CompileCacheCounter,
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    compile_cache = CompileCacheCounter()
    init_logging()
    print(f"compile cache: {cache_dir or 'off'}", flush=True)
    mh = None
    if args.multihost:
        from dynamo_tpu.runtime.multihost import MultihostContext, MultihostSpec

        mh = MultihostContext(MultihostSpec.parse(args.multihost))
        mh.initialize_jax()  # must precede any device use
        mh.start_control()

    if mh is not None and not mh.is_leader:
        # follower: no endpoint, no discovery — join the mesh, build the
        # SAME engines (params + caches are collective device_puts), replay
        # the leader's dispatches until it stops
        mcfg, params, follower_tok = _load_model(args)
        draft_cfg, draft_params = _load_draft(args)
        engine_cfg = make_engine_config(
            args, mcfg, logits_procs=_build_logits_procs(args),
            spec_draft=draft_cfg,
        )
        follower_gv = _load_guided_vocab(engine_cfg, follower_tok)
        engines = [
            TpuEngine(
                engine_cfg, params=params, draft_params=draft_params,
                guided_vocab=follower_gv,
                mesh=_multihost_mesh(args, mh, r),
                multihost=mh, mh_ns=_mh_ns(args, r),
            )
            for r in range(args.dp)
        ]
        print(f"TPU_ENGINE_FOLLOWER_READY proc={mh.spec.process_id}", flush=True)
        loop = asyncio.get_running_loop()
        try:
            # ONE replay loop serves every rank's table (namespaced ops)
            await loop.run_in_executor(None, mh.router.follow)
        except Exception:
            import traceback

            traceback.print_exc()
            mh.close()
            # skip the distributed-shutdown barrier: the leader is still
            # serving and would never join it — exit hard so a supervisor
            # can restart the group instead of wedging on a half-dead mesh
            import os as _os

            _os._exit(1)
        mh.close()
        mh.shutdown_jax()
        return

    cfg = RuntimeConfig.from_env(
        store=args.store, store_path=args.store_path, event_plane=args.event_plane
    )
    runtime = await DistributedRuntime(cfg).start()

    mcfg, params, tokenizer_ref = _load_model(args)
    vcfg = None
    if args.preset in VISION_PRESETS and not args.model_path:
        vcfg = VISION_PRESETS[args.preset](mcfg)

    component = args.component
    model_type = ["chat", "completions", "embedding"]
    if args.disagg == "prefill":
        component = (
            args.component + "_prefill" if args.component == "backend" else args.component
        )
        model_type = ["prefill"]

    instance_id = new_instance_id()
    kvbm = None
    if args.kvbm_host_gb > 0 or args.kvbm_disk_gb > 0 or args.kvbm_remote:
        from dynamo_tpu.kvbm.layout import kv_bytes_per_token
        from dynamo_tpu.kvbm.pool import KvbmTiers
        from dynamo_tpu.ops.quant import resolve_kv_dtype

        # size tiers in STORED bytes per block (model dtype, or the int8
        # codec buffer) — a hardcoded 4 bytes/element would under-use the
        # configured budget 2-4x for bf16/int8 caches. kv_bytes_per_token
        # is the one byte-accounting source (kvbm/layout).
        kvd = resolve_kv_dtype(getattr(args, "kv_dtype", "auto"))
        block_nbytes = int(
            kv_bytes_per_token(mcfg, args.block_size, kvd) * args.block_size
        )
        remote = None
        if args.kvbm_remote:
            from dynamo_tpu.kvbm.remote import RemoteBlockPool

            remote = RemoteBlockPool(args.kvbm_remote)
        kvbm = KvbmTiers(
            block_nbytes,
            host_capacity_bytes=int(args.kvbm_host_gb * (1 << 30)),
            disk_capacity_bytes=int(args.kvbm_disk_gb * (1 << 30)),
            disk_path=args.kvbm_disk_path,
            remote=remote,
        )
    draft_cfg, draft_params = _load_draft(args)
    engine_cfg = make_engine_config(
        args, mcfg, vcfg=vcfg, logits_procs=_build_logits_procs(args),
        spec_draft=draft_cfg,
    )
    guided_vocab = _load_guided_vocab(engine_cfg, tokenizer_ref)

    import jax as _jax

    from dynamo_tpu.parallel.mesh import make_mesh

    def rank_mesh(rank: int):
        """Each dp_rank serves from its own device group when the host has
        enough chips; otherwise ranks share (CPU smoke / 1 chip)."""
        devs = _jax.devices()
        if args.pp > 1:
            from dynamo_tpu.parallel.pp_serving import make_pp_mesh

            group = args.pp * args.tp
            if len(devs) < group:
                raise SystemExit(
                    f"--pp {args.pp} --tp {args.tp} needs {group} devices; "
                    f"{len(devs)} available (pp stages cannot share a chip)"
                )
            if len(devs) >= args.dp * group:
                lo = rank * group
            else:
                lo = 0
                if rank == 0 and args.dp > 1 and _jax.default_backend() != "cpu":
                    print(
                        f"WARNING: {len(devs)} device(s) < dp*pp*tp="
                        f"{args.dp * group}; all {args.dp} ranks share the "
                        f"same chips (HBM use scales with dp).",
                        flush=True,
                    )
            return make_pp_mesh(
                pp=args.pp, tp=args.tp, devices=devs[lo : lo + group]
            )
        group = args.tp * args.sp
        lo = rank * group
        if len(devs) >= args.dp * group:
            return make_mesh(tp=args.tp, sp=args.sp, devices=devs[lo : lo + group])
        if rank == 0 and args.dp > 1 and _jax.default_backend() != "cpu":
            # sharing chips means every rank allocates a FULL KV cache +
            # param copy on the same HBM — fine for smoke runs, an OOM
            # hazard on real hardware
            print(
                f"WARNING: {len(devs)} device(s) < dp*tp={args.dp * args.tp}; "
                f"all {args.dp} ranks share the same chips (HBM use scales "
                f"with dp). Provision dp*tp chips for real dp serving.",
                flush=True,
            )
        n = min(len(devs), args.tp * args.sp)
        sp = args.sp if n >= args.tp * args.sp else 1
        return make_mesh(tp=args.tp, sp=sp, devices=devs[: args.tp * sp])

    engines = []
    for r in range(args.dp):
        kv_pub = KvEventPublisher(
            runtime.event_plane, args.namespace, component,
            worker_id=instance_id, dp_rank=r, block_size=args.block_size,
        )
        m_pub = WorkerMetricsPublisher(
            runtime.event_plane, args.namespace, component,
            worker_id=instance_id, dp_rank=r,
        )
        engines.append(
            TpuEngine(
                engine_cfg,
                params=params,
                draft_params=draft_params,
                guided_vocab=guided_vocab,
                mesh=(_multihost_mesh(args, mh, r) if mh is not None
                      else rank_mesh(r)),
                kv_publisher=kv_pub,
                metrics_publisher=m_pub,
                kvbm=kvbm if r == 0 else None,  # host tiers are rank-0 only
                multihost=mh,
                mh_ns=_mh_ns(args, r),
            )
        )
    if args.dp > 1:
        from dynamo_tpu.engine.dp import DpEngineGroup

        engine = DpEngineGroup(engines)
    else:
        engine = engines[0]
    # step telemetry (engine/telemetry.py): every rank's loop feeds StepStats
    # into the runtime registry under the component hierarchy labels, so
    # /metrics exposes step-duration/occupancy/queue-depth per (worker, rank)
    from dynamo_tpu.engine.telemetry import EngineTelemetry

    tele_scope = runtime.metrics.child(
        dtpu_namespace=args.namespace, dtpu_component=component,
        dtpu_endpoint=args.endpoint,
    )
    # degradation detectors (runtime/health.py): the step hook below feeds
    # measured-vs-modeled step time into cost_model_drift; events land on
    # the flight recorder, the metrics registry, and the event plane
    from dynamo_tpu.runtime.health import get_health_monitor

    health_monitor = get_health_monitor()
    health_monitor.bind_metrics(tele_scope)

    def _predicted_step_s(s) -> float:
        """ops/costs.py roofline floor for the step the hook just saw.
        The exact per-row mix is gone by hook time, so rows are the
        occupancy-mean context — fine for drift detection, which trips on
        the measured/predicted RATIO moving, not its absolute level
        (calibrate DTPU_HEALTH_DRIFT_RATIO per platform)."""
        from dynamo_tpu.ops.costs import predict_step_seconds

        occ = max(s.batch_occupancy, 1)
        mean_len = max(s.kv_active_blocks * args.block_size // occ, 1)
        q = max(s.tokens // occ, 1) if s.phase != "decode" else 1
        return predict_step_seconds(
            [(q, mean_len)] * occ,
            block_size=args.block_size,
            kv_heads=getattr(mcfg, "num_kv_heads", 8),
            num_heads=getattr(mcfg, "num_heads", 32),
            head_dim=getattr(mcfg, "head_dim", 128),
            layers=getattr(mcfg, "num_layers", 32),
            # sustained HBM stream prior (v5e-class, ~0.8 TB/s); only the
            # ratio's drift matters, not the absolute calibration
            hbm_bytes_s=8.0e11,
            dispatch_s=5e-3,
        )

    telemetries = []
    for r, e in enumerate(engines):
        tele = EngineTelemetry(tele_scope.child(dp_rank=str(r)))
        telemetries.append(tele)

        def _hook(s, _tele=tele, _r=r):
            _tele.on_step(s)
            try:
                health_monitor.observe_step(
                    f"worker/{instance_id:016x}/dp{_r}",
                    s.duration_s, _predicted_step_s(s), phase=s.phase,
                )
            except Exception:
                pass  # the detector must never take the step loop down

        e.stats_hook = _hook
    # per-wire KV transfer bandwidth EWMA onto /metrics (the decode side of
    # a disagg pair observes pulls here; routing elsewhere reads the gauge)
    from dynamo_tpu.runtime.bandwidth import get_bandwidth_estimator

    get_bandwidth_estimator().attach_metrics(tele_scope)
    # worker-side SLO ledger (runtime/slo.py): the engine feeds the global
    # accountant from milestone timestamps; binding it here puts goodput +
    # attainment/burn gauges on this worker's /metrics (and /debug/slo on
    # the status server reads the same ledger)
    from dynamo_tpu.runtime.slo import get_slo_accountant

    get_slo_accountant().bind_metrics(tele_scope)
    if mh is not None:
        # follower death is unrecoverable for the group (its mesh shards are
        # gone): mark every engine unhealthy — the watchdog deregisters and
        # exits us for a supervisor restart — and slam the group closed so a
        # wedged dispatch raises instead of hanging. In-flight client streams
        # drop with the process; the frontend's Migration replays them on
        # another worker (llm/migration.py).
        def _on_follower_death() -> None:
            print("MULTIHOST_FOLLOWER_LOST", flush=True)
            for e in engines:
                e.healthy = False
            mh.router.close(timeout_s=2.0)

        mh.watch_followers(_on_follower_death)
    transfer_md = {}
    if args.disagg in ("prefill", "decode"):
        transfer_engine = engines[0]
        addr = await transfer_engine.serve_transfer(host=cfg.host_ip)
        print(f"KV_TRANSFER at {addr}", flush=True)
        # advertise the fetch address at registration: streamed disagg
        # dispatches the decode hop before prefill finishes, so the
        # frontend needs it at routing time (register_llm also picks it up
        # from the engine; setting it here covers dp groups whose facade
        # object is not engines[0])
        transfer_md = {
            "transfer_address": addr,
            "kv_wire": os.environ.get("DTPU_KV_WIRE", "inline"),
        }

    kv_directory = None
    if kvbm is not None:
        from dynamo_tpu.kvbm.directory import GlobalKvDirectory, directory_enabled

        if directory_enabled():
            # fleet-wide KV reuse (kvbm/directory.py): rank 0 owns the host
            # tiers, so it advertises sealed blocks under a store lease and
            # serves peer pulls over the kv_fetch transfer plane — start
            # that plane even in aggregated mode, where --disagg did not.
            gkv_addr = transfer_md.get("transfer_address")
            if gkv_addr is None:
                gkv_addr = await engines[0].serve_transfer(host=cfg.host_ip)
                print(f"KV_TRANSFER at {gkv_addr}", flush=True)
                transfer_md = {
                    "transfer_address": gkv_addr,
                    "kv_wire": os.environ.get("DTPU_KV_WIRE", "inline"),
                }
            kv_directory = GlobalKvDirectory(
                runtime.store, f"worker/{instance_id}", address=gkv_addr,
                metrics=runtime.metrics,
            )
            await kv_directory.start()
            engines[0].kv_directory = kv_directory

    # parser names fail FAST at worker startup (the frontend's _safe_parser
    # degrades unknown names to pass-through with only a warning); gpt-oss
    # presets default to the harmony dialect + its reasoning channels
    is_oss = isinstance(mcfg, GptOssConfig)
    tool_parser = args.tool_parser if args.tool_parser is not None else (
        "harmony" if is_oss else None
    )
    reasoning_parser = (
        args.reasoning_parser if args.reasoning_parser is not None
        else ("gpt_oss" if is_oss else None)
    )
    from dynamo_tpu.parsers import get_reasoning_parser, get_tool_parser

    get_tool_parser(tool_parser)
    get_reasoning_parser(reasoning_parser)

    card = ModelDeploymentCard(
        name=args.model,
        namespace=args.namespace,
        component=component,
        endpoint=args.endpoint,
        model_type=model_type,
        tokenizer=tokenizer_ref,
        context_length=args.max_context,
        kv_block_size=args.block_size,
        migration_limit=args.migration_limit,
        image_tokens=(vcfg.num_patches if vcfg is not None else 0),
        image_size=(vcfg.image_size if vcfg is not None else 0),
        image_token_id=engine_cfg.image_token_id,
        tool_parser=tool_parser,
        reasoning_parser=reasoning_parser,
        runtime_config=ModelRuntimeConfig(
            total_kv_blocks=args.num_blocks,
            data_parallel_size=args.dp,
            kv_block_size=args.block_size,
            max_batch_size=args.max_batch_size,
            tensor_parallel_size=args.tp,
            max_context_len=args.max_context,
        ),
    )
    served = await register_llm(
        runtime, engine, card, instance_id=instance_id,
        metadata=transfer_md or None,
    )

    # LoRA management endpoints (load/unload/list), served beside generate
    lora_served = []
    if args.lora_max_adapters > 0:
        from dynamo_tpu.lora import LoRACache, LocalLoRASource, load_adapter

        lora_cache = LoRACache()
        lora_source = LocalLoRASource()
        # every dp rank owns its own engine (and mesh), so each gets its own
        # adapter table: load/unload fan out to all of them
        lora_engines = [e for e in engines if e.lora is not None]

        async def handle_load(request, context):
            name, uri = request["name"], request["uri"]
            loop_ = asyncio.get_event_loop()

            def work():
                path = lora_source.fetch(uri, lora_cache)
                weights, alpha = load_adapter(path)
                return [e.lora.load(name, weights, alpha) for e in lora_engines]

            try:
                slots = await loop_.run_in_executor(None, work)
                yield {"ok": True, "name": name, "slot": slots[0]}
            except Exception as e:
                yield {"ok": False, "error": str(e)}

        async def handle_unload(request, context):
            oks = [e.lora.unload(request["name"]) for e in lora_engines]
            yield {"ok": all(oks)}

        async def handle_list(request, context):
            yield {"adapters": lora_engines[0].lora.list_adapters()}

        comp = runtime.namespace(args.namespace).component(component)
        for ep_name, handler in (
            ("load_lora", handle_load),
            ("unload_lora", handle_unload),
            ("list_loras", handle_list),
        ):
            lora_served.append(await comp.endpoint(ep_name).serve(handler))

    # runtime cache reset (reference http/clear_kv_blocks.rs); dp>1 fans to
    # every rank's engine
    from dynamo_tpu.llm.serve import serve_clear_endpoint

    clear_served = await serve_clear_endpoint(
        runtime, args.namespace, component, engines, served.instance_id
    )
    eplb_served = None
    if getattr(mcfg, "redundant_experts", 0) > 0:
        from dynamo_tpu.llm.serve import serve_eplb_endpoint

        eplb_served = await serve_eplb_endpoint(
            runtime, args.namespace, component, engines, served.instance_id
        )

    # health: engine watchdog + endpoint canary + status side-port
    # (reference: engine_monitor.py, health_check.rs, system_status_server.rs)
    from dynamo_tpu.engine.monitor import EngineWatchdog
    from dynamo_tpu.runtime.health import EndpointCanary, HealthState, StatusServer

    stop = asyncio.Event()
    health = HealthState()

    # planned reclaims (docs/operations.md §13): restore warm state from a
    # prior drain's G3 checkpoint, then stand up the drain coordinator so a
    # POST /drain (or supervisor call) runs the evacuate-and-checkpoint
    # pipeline before the kill
    from dynamo_tpu.engine.checkpoint import restore_engine, weights_ref_for
    from dynamo_tpu.engine.drain import DrainCoordinator
    from dynamo_tpu.runtime import metrics as M_
    from dynamo_tpu.runtime.config import ENV_CKPT_DIR

    ckpt_dir = env_str(ENV_CKPT_DIR, "") or None
    restore_mode = None
    if ckpt_dir:
        restored = await restore_engine(engines[0], ckpt_dir)
        restore_mode = restored["mode"]
        tele_scope.gauge(
            M_.CHECKPOINT_RESTORE_MODE,
            "1 for the restore mode this worker booted with",
            extra_labels=("mode",),
        ).set(1, mode=restored["mode"])
        print(
            f"CHECKPOINT_RESTORE mode={restored['mode']} "
            f"blocks={restored['blocks']}", flush=True,
        )
    drain_coordinator = DrainCoordinator(
        engine, served,
        ckpt_dir=ckpt_dir,
        weights_ref=weights_ref_for(args.model_path or args.preset, mcfg),
        metrics_scope=tele_scope,
        on_drained=stop.set,
    )

    async def on_down() -> None:
        stop.set()  # watchdog already deregistered; exit so a supervisor restarts

    watchdog = EngineWatchdog(engine, [served], state=health, on_down=on_down).start()
    canary = EndpointCanary(
        {f"{card.component}/{card.endpoint}": served.address}, state=health
    ).start()
    status_server = None
    if args.status_port >= 0:
        g_running = runtime.metrics.gauge("dtpu_engine_running_seqs", "active sequences")
        g_waiting = runtime.metrics.gauge("dtpu_engine_waiting_seqs", "queued sequences")
        g_free = runtime.metrics.gauge("dtpu_engine_free_blocks", "free KV blocks")
        g_cached = runtime.metrics.gauge("dtpu_engine_cached_blocks", "prefix-cached KV blocks")

        def refresh_gauges() -> None:
            snap = engine.snapshot()
            ranks = snap["ranks"] if "ranks" in snap else [snap]
            g_running.set(sum(r["running"] for r in ranks))
            g_waiting.set(sum(r["waiting"] for r in ranks))
            g_free.set(sum(r["free_blocks"] for r in ranks))
            g_cached.set(sum(r["cached_blocks"] for r in ranks))
            # rolling attainment/burn gauges follow the scrape clock
            get_slo_accountant().export_metrics()

        def worker_snapshot() -> dict:
            """The ``/debug/worker`` document — everything the frontend's
            ``/debug/fleet`` fan-out (llm/fleet.py) merges from this worker
            in one call: engine + step telemetry, the SLO ledger, the
            attribution windows, KV occupancy, drain/restore state, the
            global-KV directory stats, wire bandwidth, health events."""
            from dynamo_tpu.runtime.attribution import get_attribution
            from dynamo_tpu.runtime.slo import debug_slo_payload

            snap = engine.snapshot()
            ranks = snap["ranks"] if "ranks" in snap else [snap]
            doc = {
                "instance_id": f"{instance_id:016x}",
                "model": args.model,
                "tp": args.tp,
                "dp": args.dp,
                "engine": snap,
                "telemetry": [t.snapshot() for t in telemetries],
                "slo": debug_slo_payload(get_slo_accountant()),
                "attribution": get_attribution().snapshot(),
                "bandwidth": get_bandwidth_estimator().snapshot(),
                "health": health_monitor.snapshot(),
                "drain": {"draining": drain_coordinator.ledger.draining},
                "kv": {
                    "active_blocks": sum(
                        r.get("active_blocks", 0) for r in ranks
                    ),
                    "free_blocks": sum(r.get("free_blocks", 0) for r in ranks),
                    "total_blocks": args.num_blocks * args.dp,
                    "cached_blocks": sum(
                        r.get("cached_blocks", 0) for r in ranks
                    ),
                },
            }
            if restore_mode is not None:
                doc["restore_mode"] = restore_mode
            if kv_directory is not None:
                doc["global_kv"] = {
                    "published": kv_directory.published_count,
                    "inflight_fetches": kv_directory.inflight_fetches(),
                    "dedupe_skipped": kv_directory.dedupe_skipped,
                }
            return doc

        status_server = StatusServer(
            health,
            metrics_scope=runtime.metrics,
            pre_expose=refresh_gauges,
            metadata_fn=lambda: {
                "model": args.model,
                "instance_id": f"{instance_id:016x}",
                "tp": args.tp,
                "engine": engine.snapshot(),
                "canary_rtt_s": canary.last_rtt,
                "compile_cache": compile_cache.snapshot(),
            },
            port=args.status_port,
            loras_fn=(
                (lambda: engines[0].lora.list_adapters())
                if engines[0].lora is not None else None
            ),
            drain_fn=drain_coordinator.begin,
            worker_snapshot_fn=worker_snapshot,
        )
        await status_server.start()
        # advertise the side port on the discovery record so the frontend's
        # /debug/fleet fan-out can find this worker's /debug/worker
        await served.update_metadata({
            "status_address": f"{cfg.host_ip}:{status_server.port}",
        })

    # health events onto the event plane: planners/supervisors subscribe to
    # dtpu.health.* without scraping; the subscription handle is closed on
    # shutdown (RESOURCE-LEAK health-subscription)
    import json as _json

    from dynamo_tpu.runtime.tasks import spawn_bg as _spawn_bg

    _main_loop = asyncio.get_running_loop()

    def _publish_health(ev) -> None:
        payload = _json.dumps(ev.to_dict()).encode()
        coro = runtime.event_plane.publish(
            f"dtpu.health.{ev.detector}", payload
        )
        try:
            _main_loop.call_soon_threadsafe(_spawn_bg, coro)
        except RuntimeError:
            coro.close()  # loop already closed during shutdown

    health_sub = health_monitor.subscribe(_publish_health)
    print(f"TPU_ENGINE_READY {args.model} tp={args.tp}", flush=True)

    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    # graceful drain: deregister first (discovery stops routing here), then
    # the request server waits out in-flight streams before closing
    await watchdog.stop()
    await canary.stop()
    health_sub.close()
    if status_server is not None:
        await status_server.stop()
    if not watchdog.fired:
        await served.stop(graceful_timeout_s=args.graceful_timeout)
    await clear_served.stop()
    if eplb_served is not None:
        await eplb_served.stop()
    for s in lora_served:
        await s.stop()
    engine.stop()
    await runtime.shutdown()
    if mh is not None:
        if any(not e.healthy for e in engines):
            # dead group (follower lost / engine crash): the distributed-
            # shutdown barrier would wait for a peer that isn't coming, and
            # jax's atexit hook would do the same — exit hard so the
            # supervisor restarts the whole group
            import os as _os

            _os._exit(2)
        mh.shutdown_jax()


if __name__ == "__main__":
    asyncio.run(main())
