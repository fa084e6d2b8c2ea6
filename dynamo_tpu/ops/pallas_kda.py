"""The gated delta rule with a decay a CHANNEL (Kimi Delta Attention, arXiv
2510.26692), for the two shapes serving has: ONE token a row (a decode step:
``kda_state_update``, a Pallas TPU kernel with a pure-``jnp`` twin) and a run
of tokens of one request (a prefill chunk: ``kda_scan``, the chunked WY / UT
form in ``jnp``).

Per head, with the state ``S`` [d_k, d_v] float32, ``alpha_t = exp(g_t)`` in
(0, 1)^d_k and ``beta_t`` in (0, 2)::

    S'  = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    y_t = S_t^T q_t

The state is held as written: the key's channels on sublanes, the value's on
lanes. ``v``, ``beta`` and ``y`` are rows of lanes and broadcast over
sublanes for free; the two contractions over the key's channels are sums
over sublanes (adds between registers). What has to change layout is what is
indexed by a key channel, ``alpha``, ``k`` and ``q``: they come to the kernel
already TRANSPOSED, packed three lanes a head into one [d_k, 128] tile a
block of heads (XLA transposes 3 x 32 KiB a row; the kernel broadcasts a
lane over the lanes).

A decode step's recurrence is bound by BYTES: it reads and writes a row's
whole state (2 x H x d_k x d_v x 4 bytes a row a layer; 8.39 MB at 64 x 128
x 128) for about 7 operations an element. The kernel updates the state IN
PLACE (``input_output_aliases``) and visits live rows only, by the
compaction ``ops/pallas_ssm.py`` defines for every slot-state kernel.

Every launch carries the name ``kda_state_update``: the device trace and the
benchmark's roofline reader find it by that name.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ssm import live_block_map, live_row_table

KERNEL_NAME = "kda_state_update"
# heads a program: [16, 128, 128] float32 is 1 MiB; in and out, each double
# buffered, 4 MiB of the 16 MiB a kernel may use by default (on the chip, 128
# rows x 64 heads: 1.72 ms at 16 heads a program, 1.98 at 8)
HEAD_BLOCK = 16
# tokens a chunk of the scan, and a diagonal sub-block of it (on the chip a
# 512-token run read 1.79 ms at 32 and 2.32 at 64)
SCAN_CHUNK = 32
SCAN_SUB = 16
LANES = 128
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def kda_state_update_reference(
    S: jax.Array,      # [R, H, dk, dv] float32
    q: jax.Array,      # [R, H, dk] float32 (normalised, scaled)
    k: jax.Array,      # [R, H, dk] float32 (normalised)
    v: jax.Array,      # [R, H, dv]
    alpha: jax.Array,  # [R, H, dk] float32 in (0, 1]
    beta: jax.Array,   # [R, H] float32
    live: jax.Array,   # [R] bool
) -> Tuple[jax.Array, jax.Array]:
    """The twin: one token a row in plain ``jnp``, float32 on the vector
    unit. A row that is not live keeps its state and returns zeros."""
    vf = v.astype(F32)
    S1 = alpha[..., None] * S
    u = jnp.sum(S1 * k[..., None], axis=2)                       # [R, H, dv]
    S2 = S1 + k[..., None] * (beta[..., None] * (vf - u))[:, :, None, :]
    y = jnp.sum(S2 * q[..., None], axis=2)
    keep = live[:, None, None]
    return jnp.where(keep[..., None], S2, S), jnp.where(keep, y, 0.0)


def _update_kernel(rows_ref, n_ref, s_ref, cols_ref, v_ref, *rest, delta: bool):
    """``delta``: the delta rule's correction (``beta`` rides as one operand
    more); without it the rule is plain decayed accumulation, ``S' + k
    v^T`` (ops/pallas_lightning.py)."""
    del rows_ref  # read by the index maps
    beta_ref, o_ref, y_ref = rest if delta else (None, *rest)
    i, n = pl.program_id(0), n_ref[0]
    hb = s_ref.shape[1]

    @pl.when(i < n)
    def _():
        cols = cols_ref[0, 0]                                    # [dk, 128]
        for h in range(hb):
            a = cols[:, 3 * h:3 * h + 1]                         # [dk, 1]
            kc = cols[:, 3 * h + 1:3 * h + 2]
            qc = cols[:, 3 * h + 2:3 * h + 3]
            s1 = a * s_ref[0, h]
            w = v_ref[0, h:h + 1, :]
            if delta:
                u = jnp.sum(s1 * kc, axis=0, keepdims=True)      # [1, dv]
                w = beta_ref[0, h:h + 1, :] * (w - u)
            s2 = s1 + kc * w
            o_ref[0, h] = s2
            y_ref[0, h:h + 1, :] = jnp.sum(s2 * qc, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(i >= n, n == 0))
    def _():
        # no live row at all: every visit is the one block the output
        # buffer will write back, so hand it what was there
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret", "head_block", "name"),
                   donate_argnums=(0,))
def kda_state_update(S, q, k, v, alpha, beta, live, *, interpret: bool = False,
                     head_block: int = HEAD_BLOCK, name: str = KERNEL_NAME):
    """``kda_state_update_reference`` as one Pallas launch: ``S`` (donated)
    is updated in place, live rows only. Returns (S', y [R, H, dv] float32).
    ``beta`` None: the rule WITHOUT its delta correction, ``S = alpha S +
    k v^T`` (a linear-attention layer with a plain decay: its launch, under
    its own ``name``, is this skeleton: ops/pallas_lightning.py)."""
    R, H, dk, dv = S.shape
    hb = min(head_block, H)
    if H % hb or 3 * hb > LANES:
        raise ValueError(f"{H} heads do not cut into blocks of {hb} (3 lanes a head of {LANES})")
    nj = H // hb
    live = live.astype(bool)
    rows, n_live = live_row_table(live)
    # what a key channel indexes, transposed: lane 3h + (0, 1, 2) of head
    # block j holds alpha, k, q of its head h
    cols = jnp.stack([alpha, k, q], axis=2).astype(F32)          # [R, H, 3, dk]
    cols = cols.reshape(R, nj, 3 * hb, dk).transpose(0, 1, 3, 2)
    cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, LANES - 3 * hb),))
    delta = beta is not None
    operands = [S, cols, v.astype(F32)]
    if delta:
        operands.append(jnp.broadcast_to(beta.astype(F32)[..., None], (R, H, dv)))

    head_map = live_block_map(nj)

    def state_idx(i, j, rows_ref, n_ref):
        r, jj = head_map(i, j, rows_ref, n_ref)
        return r, jj, 0, 0

    def vec_idx(i, j, rows_ref, n_ref):
        r, jj = head_map(i, j, rows_ref, n_ref)
        return r, jj, 0

    state_spec = pl.BlockSpec((1, hb, dk, dv), state_idx)
    cols_spec = pl.BlockSpec((1, 1, dk, LANES), state_idx)
    vec_spec = pl.BlockSpec((1, hb, dv), vec_idx)
    S_new, y = pl.pallas_call(
        functools.partial(_update_kernel, delta=delta),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, nj),
            in_specs=[state_spec, cols_spec] + [vec_spec] * (len(operands) - 2),
            out_specs=[state_spec, vec_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct((R, H, dv), F32),
        ],
        input_output_aliases={2: 0},  # S, after the two prefetched tables
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(rows, n_live[None], *operands)
    # a dead row's y was never written: select, do not multiply
    return S_new, jnp.where(live[:, None, None], y, 0.0)


def kda_scan(
    S: jax.Array,     # [H, dk, dv] float32: the state before the run
    q: jax.Array,     # [T, H, dk] float32 (normalised, scaled)
    k: jax.Array,     # [T, H, dk] float32 (normalised)
    v: jax.Array,     # [T, H, dv]
    g: jax.Array,     # [T, H, dk] float32 <= 0, the log of the decay; 0 = none
    beta: jax.Array,  # [T, H] float32; 0 (with g = 0) = the identity
    *,
    chunk: int = SCAN_CHUNK,
    sub: int = SCAN_SUB,
) -> Tuple[jax.Array, jax.Array]:
    """A run of tokens of one request in the chunked form (WY / UT transform).
    With ``G`` the cumulated log-decay inside a chunk and ``w_t = v_t -
    S_{t-1}^T (alpha_t k_t)`` the rule's pseudo-value::

        (I + A) W = V - (K e^G) S_0        A[t, j] = beta_j sum_c k_t k_j e^(G_t - G_j), j < t
        Y = (Q e^G) S_0 + B W              B[t, j] = beta_j sum_c q_t k_j e^(G_t - G_j), j <= t
        S_C = Diag(e^(G_C)) S_0 + (K e^(G_C - G))^T (beta W)

    so a chunk costs one triangular solve ahead of the scan over chunks and
    three small products inside it. THE HAZARD: with a decay a channel
    ``e^(G_t - G_j)`` cannot be factored as ``e^(G_t) e^(-G_j)`` over a whole
    chunk (a channel that forgets fast passes -88 inside 64 tokens and the
    second factor overflows float32). Here every exponent is a DIFFERENCE
    taken first and never positive: on the diagonal sub-blocks (``sub``
    tokens) token by token, off them against the row block's first token
    (``e^(G_t - G_r) e^(G_r - G_j)``, both factors at most 1). Products with
    a float32 factor run at the highest precision: the run is under 5% of a
    layer's FLOPs and its state is what every later decode step compounds.
    Returns (y [T, H, dv] float32, the state after the run)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    if C % sub:
        raise ValueError(f"a chunk of {C} tokens does not cut into sub-blocks of {sub}")
    pad = (-T) % C
    if pad:  # whole chunks: beta = 0 and g = 0 change nothing
        q, k, v, g = (jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))
    nc, nb = (T + pad) // C, C // sub
    shape = lambda x: x.astype(F32).reshape(nc, C, H, -1).transpose(0, 2, 1, 3)  # noqa: E731
    q, k, v, g = shape(q), shape(k), shape(v), shape(g)           # [nc, H, C, d]
    beta = beta.astype(F32).reshape(nc, C, H).transpose(0, 2, 1)   # [nc, H, C]
    G = jnp.cumsum(g, axis=2)                                     # <= 0, falling

    # -- the two lower-triangular forms, no exponent above 0 -------------------
    blk = lambda x: x.reshape(nc, H, nb, sub, dk)                 # noqa: E731
    Gb, kb, qb = blk(G), blk(k), blk(q)
    G0 = Gb[:, :, :, :1]                                          # a row block's first token
    to_first = jnp.exp(Gb - G0)                                   # e^(G_t - G_r), t in the block
    # e^(G_r - G_j) for every j of the chunk BEFORE block r; 0 elsewhere
    before = (jnp.arange(C)[None, :] < (jnp.arange(nb) * sub)[:, None])   # [nb, C]
    from_first = G0 - G[:, :, None]                               # [nc, H, nb, C, dk]
    k_from = jnp.where(before[..., None], jnp.exp(jnp.minimum(from_first, 0.0)), 0.0) * k[:, :, None]
    off = lambda x: jnp.einsum(                                   # noqa: E731
        "nhbic,nhbjc->nhbij", x * to_first, k_from, precision=_HI).reshape(nc, H, C, C)
    # the diagonal sub-blocks token by token: [.., nb, sub (t), sub (j), dk]
    diff = Gb[:, :, :, :, None] - Gb[:, :, :, None, :]
    e_diag = jnp.exp(jnp.minimum(diff, 0.0))
    # (one fused multiply and sum over the channels: nothing of that size is kept)
    on = lambda x: jnp.sum(                                       # noqa: E731
        x[:, :, :, :, None, :] * e_diag * kb[:, :, :, None, :, :], axis=-1)
    eye_b = jnp.eye(nb, dtype=bool)[:, None, :, None]             # block (b, b') is diagonal

    def tri(x, strict: bool):
        d = on(x)                                                 # [nc, H, nb, sub, sub]
        full = jnp.where(eye_b, d[:, :, :, :, None, :], 0.0).reshape(nc, H, C, C) + off(x)
        keep = jnp.tril(jnp.ones((C, C), bool), -1 if strict else 0)
        return jnp.where(keep, full, 0.0) * beta[:, :, None, :]

    A, B = tri(kb, True), tri(qb, False)

    # -- ahead of the scan: (I + A)^-1 applied to V and to K e^G ---------------
    eG = jnp.exp(G)
    rhs = jnp.concatenate([v, k * eG], axis=-1)                   # [nc, H, C, dv + dk]
    sol = jax.scipy.linalg.solve_triangular(
        jnp.eye(C, dtype=F32) + A, rhs, lower=True, unit_diagonal=True)
    U, Kd = sol[..., :dv], sol[..., dv:]
    q_in = q * eG
    k_end = k * jnp.exp(G[:, :, -1:] - G) * beta[..., None]       # [nc, H, C, dk]
    through = eG[:, :, -1]                                        # [nc, H, dk]

    def one_chunk(S0, inp):
        U_c, Kd_c, B_c, q_c, k_c, thr = inp
        W = U_c - jnp.einsum("htk,hkv->htv", Kd_c, S0, precision=_HI)
        y = (jnp.einsum("htk,hkv->htv", q_c, S0, precision=_HI)
             + jnp.einsum("htj,hjv->htv", B_c, W, precision=_HI))
        S1 = thr[..., None] * S0 + jnp.einsum("htk,htv->hkv", k_c, W, precision=_HI)
        return S1, y

    S_out, y = jax.lax.scan(one_chunk, S, (U, Kd, B, q_in, k_end, through))
    return y.transpose(0, 2, 1, 3).reshape(nc * C, H, dv)[:T], S_out
