"""Decode-step component profiler: where does the per-token time go?

Times, on the real device, N-step scans of:
  - full decode step (forward + lm_head + sample)       [the engine program]
  - forward only (28 layers, paged attention, no head)
  - lm_head only
  - paged attention only (num_layers calls per step)
  - mlp+qkv matmuls only (no attention)

Run: python tools/profile_decode.py [BATCH] [CTX]

One process, which owns the chip. Timed regions end in
jax.block_until_ready (chip_smoke.py prints whether it waits for the
device on the machine at hand) after a double warmup: donated-arg jits
compile a SECOND time on their second call. Prefer e2e bench.py or
jax.profiler.trace for anything reported.
"""

import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.runtime.device import (
    enable_compile_cache,
    hbm_bytes_per_s,
    on_tpu,
)

enable_compile_cache()

from dynamo_tpu.models.llama import LlamaConfig, init_params, forward, lm_logits
from dynamo_tpu.ops import pallas_attention as pa
from dynamo_tpu.engine.sampling import sample_tokens

B = int(sys.argv[1]) if len(sys.argv) > 1 else 8
CTX = int(sys.argv[2]) if len(sys.argv) > 2 else 384
STEPS = 16
BS = 16  # block size

cfg = LlamaConfig.qwen3_0_6b()
rng = jax.random.PRNGKey(0)
params = init_params(rng, cfg)

num_blocks = (CTX // BS) * B + 64
kshape = (num_blocks, BS, cfg.num_kv_heads, cfg.head_dim)
k_cache = jax.random.normal(jax.random.PRNGKey(1), kshape, cfg.dtype)
v_cache = jax.random.normal(jax.random.PRNGKey(2), kshape, cfg.dtype)
k_caches = [k_cache] * cfg.num_layers
v_caches = [v_cache] * cfg.num_layers

max_blocks = CTX // BS
tables = np.zeros((B, max_blocks), np.int32)
for i in range(B):
    tables[i] = np.arange(i * max_blocks, (i + 1) * max_blocks)
tables = jnp.asarray(tables)
seq_lens = jnp.full((B,), CTX - 2, jnp.int32)
tokens0 = jnp.zeros((B,), jnp.int32)
temps = jnp.zeros((B,), jnp.float32)
top_ks = jnp.zeros((B,), jnp.int32)
top_ps = jnp.ones((B,), jnp.float32)
seeds = jnp.zeros((B,), jnp.uint32)
steps0 = jnp.zeros((B,), jnp.int32)

interp = not on_tpu()


def paged(q, kc, vc):
    return pa.paged_decode_attention(q, kc, vc, tables, seq_lens, interpret=interp)


def step_full(params, carry, _):
    tokens, kcs, vcs = carry
    positions = seq_lens - 1

    def attend(q, k_new, v_new, li):
        out = paged(q[:, 0], kcs[li], vcs[li])
        return out[:, None]

    hidden = forward(params, cfg, tokens[:, None], positions[:, None], attend)
    logits = lm_logits(params, cfg, hidden[:, 0])
    toks = sample_tokens(logits, seeds, steps0, temps, top_ks, top_ps)
    return (toks, kcs, vcs), toks


def step_fwd_only(params, carry, _):
    tokens, kcs, vcs = carry
    positions = seq_lens - 1

    def attend(q, k_new, v_new, li):
        out = paged(q[:, 0], kcs[li], vcs[li])
        return out[:, None]

    hidden = forward(params, cfg, tokens[:, None], positions[:, None], attend)
    # cheap reduction keeps hidden live without the vocab matmul
    toks = jnp.argmax(hidden[:, 0, :64], axis=-1).astype(jnp.int32)
    return (toks, kcs, vcs), toks


def step_head_only(params, carry, _):
    h, = carry
    logits = lm_logits(params, cfg, h)
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    h = h + toks[:, None].astype(cfg.dtype) * 1e-6
    return (h,), toks


def step_attn_only(params, carry, _):
    q, = carry
    out = q
    for li in range(cfg.num_layers):
        out = paged(out, k_caches[li], v_caches[li])
    return (out,), jnp.zeros((B,), jnp.int32)


def step_noattn(params, carry, _):
    tokens, = carry
    positions = seq_lens - 1

    def attend(q, k_new, v_new, li):
        return q

    hidden = forward(params, cfg, tokens[:, None], positions[:, None], attend)
    toks = jnp.argmax(hidden[:, 0, :64], axis=-1).astype(jnp.int32)
    return (tokens,), toks


def bench(name, fn, init):
    # params enter as a jit ARGUMENT: a closure would bake them into the HLO
    # as constants (1.2GB)
    jfn = jax.jit(lambda p, c: jax.lax.scan(partial(fn, p), c, None, length=STEPS))
    out = jfn(params, init)
    jax.block_until_ready(out)
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jfn(params, init)
        jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    per_step = dt / STEPS * 1e3
    print(f"{name:18s}  {per_step:7.3f} ms/step   ({dt*1e3:8.2f} ms / {STEPS} steps)")
    return per_step


print(f"device={jax.devices()[0]}  B={B} CTX={CTX} steps={STEPS}")
h0 = jax.random.normal(jax.random.PRNGKey(3), (B, cfg.hidden_size), cfg.dtype)
q0 = jax.random.normal(jax.random.PRNGKey(4), (B, cfg.num_heads, cfg.head_dim), cfg.dtype)

BENCHES = {
    "full": ("full step", step_full, lambda: (tokens0, k_caches, v_caches)),
    "fwd": ("forward only", step_fwd_only, lambda: (tokens0, k_caches, v_caches)),
    "head": ("lm_head only", step_head_only, lambda: (h0,)),
    "attn": ("attention only", step_attn_only, lambda: (q0,)),
    "noattn": ("fwd no-attention", step_noattn, lambda: (tokens0,)),
}

which = os.environ.get("PROFILE_WHICH", "")
names = which.split(",") if which else list(BENCHES)
for n in names:
    label, fn, init = BENCHES[n]
    bench(label, fn, init())

param_bytes = 2 * (
    cfg.vocab_size * cfg.hidden_size
    + cfg.num_layers
    * (
        cfg.hidden_size * (cfg.q_size + 2 * cfg.kv_size)
        + cfg.q_size * cfg.hidden_size
        + 3 * cfg.hidden_size * cfg.intermediate_size
    )
)
kv_bytes = 2 * 2 * cfg.num_layers * CTX * cfg.num_kv_heads * cfg.head_dim * B
hbm = hbm_bytes_per_s(jax.devices()[0])
roof_ms = (param_bytes + kv_bytes) / hbm * 1e3
print(f"roofline step: {roof_ms:.3f} ms  (params {param_bytes/1e6:.0f} MB + kv {kv_bytes/1e6:.0f} MB @{hbm/1e9:.0f}GB/s)")
