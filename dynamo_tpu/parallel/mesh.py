"""Device mesh + sharding helpers: the TPU-native parallelism substrate.

Where the reference passes TP/PP/EP sizes through to engine-internal NCCL
groups (components/src/dynamo/trtllm/engine.py:100-127, vllm/args.py:341),
this framework owns the model, so parallelism is expressed directly as a
``jax.sharding.Mesh`` with named axes and ``NamedSharding`` annotations; XLA
inserts the ICI collectives (psum for TP row-parallel, all-to-all for EP).

Axes:
    dp  — data parallel (replicated params, independent KV pools per rank)
    tp  — tensor parallel (heads/ffn sharded, psum over ICI)
    ep  — expert parallel (MoE experts sharded, all-to-all dispatch)
    sp  — sequence/context parallel (ring attention over long prefills)
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DP = "dp"
AXIS_TP = "tp"
AXIS_EP = "ep"
AXIS_SP = "sp"


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """The one shard_map construction site of the package (ring/sp, pp
    wavefront, EP psum, pallas sharding all route through here)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def make_mesh(
    tp: int = 1,
    dp: int = 1,
    sp: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (dp, sp, tp) mesh. tp innermost so TP collectives ride the
    fastest ICI links (nearest-neighbor within a slice row)."""
    devs = list(devices) if devices is not None else jax.devices()
    needed = tp * dp * sp
    if len(devs) < needed:
        raise ValueError(f"need {needed} devices (tp={tp} dp={dp} sp={sp}), have {len(devs)}")
    grid = np.array(devs[:needed]).reshape(dp, sp, tp)
    return Mesh(grid, (AXIS_DP, AXIS_SP, AXIS_TP))


def single_device_mesh() -> Mesh:
    return make_mesh(tp=1, dp=1, sp=1, devices=jax.devices()[:1])


# -- canonical partition specs ---------------------------------------------
def param_specs_llama() -> dict:
    """PartitionSpecs for llama-family params (megatron-style TP).

    Column-parallel (shard output dim): q/k/v/gate/up projections, embedding.
    Row-parallel (shard input dim, psum after): o/down projections.
    """
    return {
        "embed": P(None, AXIS_TP),                 # [vocab, hidden] shard hidden
        "wq": P(None, AXIS_TP),                    # [hidden, heads*hd] shard heads
        "wk": P(None, AXIS_TP),
        "wv": P(None, AXIS_TP),
        "wo": P(AXIS_TP, None),                    # [heads*hd, hidden] row-parallel
        "w_gate": P(None, AXIS_TP),                # [hidden, inter]
        "w_up": P(None, AXIS_TP),
        "w_down": P(AXIS_TP, None),                # [inter, hidden]
        "norm": P(None),
        "lm_head": P(None, AXIS_TP),               # [hidden, vocab] shard vocab
    }


def kv_cache_spec() -> P:
    """Paged KV cache [num_blocks, block_size, kv_heads, head_dim]: shard the
    kv_heads axis across TP (each shard holds its own heads' cache)."""
    return P(None, None, AXIS_TP, None)


def shard(mesh: Mesh, spec: P):
    return NamedSharding(mesh, spec)


def tp_size(mesh: Mesh) -> int:
    return mesh.shape[AXIS_TP]


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def mesh_info(mesh: Mesh) -> Tuple[int, int, int]:
    return mesh.shape[AXIS_DP], mesh.shape[AXIS_SP], mesh.shape[AXIS_TP]
