"""Time the engine's REAL decode_multi program (device time per horizon).

Unlike tools/profile_decode.py (a synthetic scan harness), this dispatches
the exact production program with donation, measuring what serving pays.

Env: B (batch), CTX, PALLAS=0/1, STEPS (horizon length).

One process, which owns the chip. Timed regions end in
jax.block_until_ready (chip_smoke.py prints whether it waits for the
device on the machine at hand) after a double warmup: donated-arg jits
compile a SECOND time on their second call. Prefer e2e bench.py or
jax.profiler.trace for anything reported.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.runtime.device import enable_compile_cache, hbm_bytes_per_s

enable_compile_cache()

from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.llama import LlamaConfig

B = int(os.environ.get("B", "8"))
CTX = int(os.environ.get("CTX", "512"))
STEPS = int(os.environ.get("STEPS", "16"))
PALLAS = os.environ.get("PALLAS", "1") not in ("0", "")

mcfg = LlamaConfig.qwen3_0_6b()
cfg = TpuEngineConfig(
    model=mcfg,
    num_blocks=(CTX // 16) * (B + 2),
    block_size=16,
    max_batch_size=B,
    max_context=CTX,
    prefill_buckets=(256,),
    decode_steps=STEPS,
    use_pallas=PALLAS,
)
engine = TpuEngine(cfg)

bs = cfg.block_size
max_blocks = cfg.max_blocks_per_seq
tables = np.zeros((B, max_blocks), np.int32)
for i in range(B):
    tables[i] = np.arange(1 + i * max_blocks, 1 + (i + 1) * max_blocks) % cfg.num_blocks
start_len = CTX - STEPS - 2

args = dict(
    tokens=jnp.zeros((B,), jnp.int32),
    seq_lens=jnp.full((B,), start_len, jnp.int32),
    block_tables=jnp.asarray(tables),
    active=jnp.ones((B,), bool),
    seeds=jnp.zeros((B,), jnp.uint32),
    steps0=jnp.zeros((B,), jnp.int32),
    temps=jnp.zeros((B,), jnp.float32),
    top_ks=jnp.zeros((B,), jnp.int32),
    top_ps=jnp.ones((B,), jnp.float32),
    min_ps=jnp.zeros((B,), jnp.float32),
    pres=jnp.zeros((B,), jnp.float32),
    freqs=jnp.zeros((B,), jnp.float32),
    reps=jnp.ones((B,), jnp.float32),
    lp_need=jnp.bool_(False),
)


def dispatch():
    global k, v, counts
    (k2, v2, c2, packed, toks, lens, steps) = engine._decode_multi_fn(
        engine.params, k, v, counts,
        args["tokens"], args["seq_lens"], args["block_tables"], args["active"],
        args["seeds"], args["steps0"], args["temps"], args["top_ks"],
        args["top_ps"], args["min_ps"], args["pres"], args["freqs"],
        args["reps"], engine.prompt_masks, args["lp_need"],
        engine._lora_tables(), jnp.zeros((B,), jnp.int32),
        engine._dev("proc_masks", engine._lp_masks),
    )
    k, v, counts = k2, v2, c2
    return packed


k, v, counts = engine.k_caches, engine.v_caches, engine.output_counts
t0 = time.perf_counter()
packed = dispatch()
jax.block_until_ready(packed)
print(f"compile+first: {time.perf_counter()-t0:.1f}s")

reps = 6
t0 = time.perf_counter()
for _ in range(reps):
    packed = dispatch()
jax.block_until_ready(packed)
dt = (time.perf_counter() - t0) / reps
per_step = dt / STEPS * 1e3

param_bytes = 2 * (
    mcfg.vocab_size * mcfg.hidden_size
    + mcfg.num_layers * (
        mcfg.hidden_size * (mcfg.q_size + 2 * mcfg.kv_size)
        + mcfg.q_size * mcfg.hidden_size
        + 3 * mcfg.hidden_size * mcfg.intermediate_size
    )
)
kv_bytes = 2 * 2 * mcfg.num_layers * start_len * mcfg.kv_size * B
roof = (param_bytes + kv_bytes) / hbm_bytes_per_s(jax.devices()[0]) * 1e3
print(
    f"B={B} CTX={CTX} steps={STEPS} pallas={PALLAS}: "
    f"{per_step:.3f} ms/step ({dt*1e3:.1f} ms/horizon), "
    f"roofline {roof:.3f} ms/step, eff {roof/per_step*100:.1f}%, "
    f"{B/per_step*1e3:.0f} tok/s"
)
engine.stop()
