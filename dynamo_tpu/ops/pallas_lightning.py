"""Linear attention with a FIXED DECAY A HEAD (Lightning Attention, arXiv
2401.04658; models/minicpm_sala.py), for the two shapes serving has: ONE
token a row (a decode step: ``lightning_state_update``, a Pallas TPU launch
with a pure-``jnp`` twin) and a run of tokens of one request (a prefill
chunk: ``lightning_scan``, blocks of tokens in matmul form).

Per head, with the state ``S`` [d_k, d_v] float32 and ``lambda`` in (0, 1)::

    S_t = lambda S_{t-1} + k_t v_t^T
    y_t = S_t^T q_t                      (q already scaled)

No delta correction, no gate, no convolution: the gated delta rule's launch
(ops/pallas_kda.py) with ``alpha`` one number a head and without its
``beta`` operand. The launch skeleton (rows compacted to the live ones, the
state updated in place, what a key channel indexes transposed into one tile
a block of heads) is that module's; what is this module's is the launch's
NAME on the device trace, ``lightning_state_update``, so that the
benchmark's roofline reader tells it from a delta-rule layer's
(benchmarks/costs_sala.py counts its bytes), the twin, and the chunk scan.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from . import pallas_kda

KERNEL_NAME = "lightning_state_update"
# tokens a block of the chunk scan: the intra-block scores are [C, C] a head
SCAN_BLOCK = 128
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def lightning_state_update_reference(
    S: jax.Array,      # [R, H, dk, dv] float32
    q: jax.Array,      # [R, H, dk] float32 (scaled)
    k: jax.Array,      # [R, H, dk]
    v: jax.Array,      # [R, H, dv]
    decay: jax.Array,  # [H] float32 in (0, 1)
    live: jax.Array,   # [R] bool
) -> Tuple[jax.Array, jax.Array]:
    """The twin: one token a row in plain ``jnp``, float32 on the vector
    unit. A row that is not live keeps its state and returns zeros."""
    S1 = (decay[None, :, None, None] * S
          + k.astype(F32)[..., None] * v.astype(F32)[:, :, None, :])
    y = jnp.sum(S1 * q.astype(F32)[..., None], axis=2)
    keep = live[:, None, None]
    return jnp.where(keep[..., None], S1, S), jnp.where(keep, y, 0.0)


def lightning_state_update(S, q, k, v, decay, live, *, interpret: bool = False):
    """``lightning_state_update_reference`` as one Pallas launch: ``S``
    (donated) is updated in place, live rows only. Returns (S', y [R, H, dv]
    float32)."""
    alpha = jnp.broadcast_to(decay.astype(F32)[None, :, None], q.shape)
    return pallas_kda.kda_state_update(
        S, q, k, v, alpha, None, live, interpret=interpret, name=KERNEL_NAME,
    )


def lightning_scan(
    S: jax.Array,          # [H, dk, dv] float32: the state before the run
    q: jax.Array,          # [T, H, dk] (scaled)
    k: jax.Array,          # [T, H, dk]
    v: jax.Array,          # [T, H, dv]
    log_decay: jax.Array,  # [H] float32 < 0
    n_real,                # tokens from here on are padding: the identity
    *,
    block: int = SCAN_BLOCK,
) -> Tuple[jax.Array, jax.Array]:
    """A run of tokens of one request in blocks of ``block`` tokens, never a
    loop over tokens. With ``G_t`` the log-decay cumulated inside a block
    (a padding token adds none and its key is zero)::

        Y   = ((Q K^T) * D) V + (Q e^G) S_0      D[t, j] = e^(G_t - G_j), j <= t
        S_C = e^(G_C) S_0 + (K e^(G_C - G))^T V

    Every exponent is a difference taken first and never positive (the
    fastest head passes e^-100 inside a block: an underflow to 0, which is
    right, and never an overflow). Products with a float32 factor run at the
    highest precision: the run is under 4% of a layer's FLOPs and its state
    is what every later decode step compounds. Returns (y [T, H, dv]
    float32, the state after the run)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(block, T)
    real = jnp.arange(T) < n_real
    g = jnp.where(real[:, None], log_decay.astype(F32)[None], 0.0)      # [T, H]
    k = jnp.where(real[:, None, None], k, jnp.zeros((), k.dtype))
    pad = (-T) % C
    if pad:  # whole blocks: no decay and a zero key change nothing
        q, k, v = (jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        g = jnp.pad(g, ((0, pad), (0, 0)))
    nc = (T + pad) // C
    shape = lambda x: x.reshape(nc, C, H, -1).transpose(0, 2, 1, 3)  # noqa: E731
    qb, kb, vb = shape(q), shape(k), shape(v.astype(F32))           # [nc, H, C, d]
    G = jnp.cumsum(g.reshape(nc, C, H).transpose(0, 2, 1), axis=2)  # [nc, H, C] <= 0
    causal = jnp.tril(jnp.ones((C, C), bool))
    D = jnp.where(causal, jnp.exp(jnp.minimum(G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    scores = jnp.einsum("nhtd,nhjd->nhtj", qb, kb, precision=_HI,
                        preferred_element_type=F32) * D
    y_in = jnp.einsum("nhtj,nhjv->nhtv", scores, vb, precision=_HI)
    q_in = qb.astype(F32) * jnp.exp(G)[..., None]
    k_end = kb.astype(F32) * jnp.exp(G[..., -1:] - G)[..., None]
    through = jnp.exp(G[..., -1])                                   # [nc, H]

    def one_block(S0, inp):
        y_c, q_c, k_c, v_c, thr = inp
        y = y_c + jnp.einsum("htk,hkv->htv", q_c, S0, precision=_HI)
        S1 = thr[:, None, None] * S0 + jnp.einsum("htk,htv->hkv", k_c, v_c, precision=_HI)
        return S1, y

    S_out, y = jax.lax.scan(one_block, S, (y_in, q_in, k_end, vb, through))
    return y.transpose(0, 2, 1, 3).reshape(nc * C, H, dv)[:T], S_out
