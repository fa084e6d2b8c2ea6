"""The Mamba-2 recurrence (state-space duality), for the two shapes serving
has: ONE token a row (a decode step: ``ssm_state_update``, a Pallas TPU
kernel with a pure-``jnp`` twin) and a run of tokens of one request (a
prefill chunk: ``ssm_scan``, the chunked dual form in ``jnp``).

Per head ``i`` of group ``g(i) = i // (H / G)``, with ``a = dt_i A_i <= 0``::

    S_t = exp(a) S_{t-1} + dt_i * B_t[g] (x) x_t[i]
    y_t = C_t[g] . S_t + D_i x_t[i]

THE STATE IS HELD TRANSPOSED, ``[heads, state N, head size P]`` float32: the
state dimension on sublanes, the head's lanes on lanes. ``x`` then
broadcasts over sublanes for free, ``y`` is a sum over sublanes (adds between
registers, no cross-lane reduction), and the one operand that has to change
layout, a row of ``B`` or ``C`` turned into a column, is shared by a whole
group of heads (it is made on the idle matrix unit: ``diag(B) @ ones``, exact
because ``B`` is bf16).

A decode step's recurrence is bound by BYTES: it reads and writes a row's
whole state (2 x H x N x P x 4 bytes a row a layer; 8.39 MB at 32 x 256 x
128) for 3 multiply-adds an element. The kernel updates the state IN PLACE
(``input_output_aliases``) and visits live rows only: the rows are compacted
by a scalar-prefetched table, and the visits past the live count repeat the
last one's block indices, so a dead row is neither read nor written.

Every launch carries the name ``ssm_state_update``: the device trace and the
benchmark's roofline reader find it by that name.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "ssm_state_update"
# heads a program: [8, N, P] float32 is 1 MiB at 256 x 128; in and out, each
# double buffered, 4 MiB of the 16 MiB a kernel may use by default
HEAD_BLOCK = 8
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def live_row_table(live: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The scalar-prefetched compaction a slot-state kernel visits its rows
    by (this module's and ops/pallas_kda.py's): (rows [R] int32, the live
    rows first and in order, the rest repeating the last live row; the live
    count, int32)."""
    live = live.astype(bool)
    R = live.shape[0]
    n_live = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    rows = jnp.where(jnp.arange(R) < n_live, order, order[jnp.maximum(n_live - 1, 0)])
    return rows, n_live


def live_block_map(n_blocks: int):
    """The index-map helper beside ``live_row_table``: visit ``i`` of head
    block ``j`` reads row ``rows[i]``; a visit past the live count repeats
    the last visit's block indices, so a dead row is neither read nor
    written."""
    def head_map(i, j, rows_ref, n_ref):
        return rows_ref[i], jnp.where(i < n_ref[0], j, n_blocks - 1)
    return head_map


def _per_head(v: jax.Array, heads: int) -> jax.Array:
    """[..., G, N] -> [..., H, N]: head ``i`` reads group ``i // (H / G)``."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssm_state_update_reference(
    S: jax.Array,     # [R, H, N, P] float32
    x: jax.Array,     # [R, H, P]
    B: jax.Array,     # [R, G, N]
    C: jax.Array,     # [R, G, N]
    dt: jax.Array,    # [R, H] float32, after the softplus
    A: jax.Array,     # [H] float32, negative
    D: jax.Array,     # [H] float32
    live: jax.Array,  # [R] bool
) -> Tuple[jax.Array, jax.Array]:
    """The twin: one token a row in plain ``jnp``, float32 on the vector
    unit. A row that is not live keeps its state and returns zeros."""
    H = S.shape[1]
    xf = x.astype(F32)
    Bh, Ch = _per_head(B.astype(F32), H), _per_head(C.astype(F32), H)
    decay = jnp.exp(dt * A)
    S_new = (decay[:, :, None, None] * S
             + Bh[:, :, :, None] * (xf * dt[..., None])[:, :, None, :])
    y = jnp.sum(S_new * Ch[:, :, :, None], axis=2) + D[:, None] * xf
    keep = live[:, None, None]
    return (jnp.where(keep[..., None], S_new, S),
            jnp.where(keep, y, 0.0).astype(x.dtype))


def _update_kernel(rows_ref, n_ref, s_ref, xdt_ref, decay_ref, b_ref, c_ref,
                   o_ref, y_ref):
    del rows_ref  # read by the index maps
    i, n = pl.program_id(0), n_ref[0]
    hb, N, P = s_ref.shape[1:]

    @pl.when(i < n)
    def _():
        eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))
        exact = b_ref.dtype == jnp.bfloat16

        def column(ref):
            # a row of N lanes -> [N, P], its value n on every lane of
            # sublane n: one nonzero a sum, so ONE bf16 pass is exact for
            # bf16 operands (float32 ones, the tests', take the full passes;
            # the select runs in float32: the mask is laid out for 32 bits)
            row = jnp.broadcast_to(ref[0].astype(F32), (N, N))
            diag = jnp.where(eye, row, 0.0).astype(ref.dtype)
            return jnp.dot(diag, jnp.ones((N, P), ref.dtype),
                           preferred_element_type=F32,
                           precision=None if exact else _HI)

        Bc, Cc = column(b_ref), column(c_ref)
        for h in range(hb):
            s = decay_ref[0, h:h + 1, :] * s_ref[0, h] + Bc * xdt_ref[0, h:h + 1, :]
            o_ref[0, h] = s
            y_ref[0, h:h + 1, :] = jnp.sum(s * Cc, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(i >= n, n == 0))
    def _():
        # no live row at all: every visit is the one block the output
        # buffer will write back, so hand it what was there
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret", "head_block"),
                   donate_argnums=(0,))
def ssm_state_update(S, x, B, C, dt, A, D, live, *, interpret: bool = False,
                     head_block: int = HEAD_BLOCK):
    """``ssm_state_update_reference`` as one Pallas launch: ``S`` (donated)
    is updated in place, live rows only. Returns (S', y [R, H, P])."""
    R, H, N, P = S.shape
    G = B.shape[1]
    hb = min(head_block, H // G)
    if H % hb or (H // G) % hb:
        raise ValueError(f"{H} heads in {G} groups do not cut into blocks of {hb}")
    nj = H // hb
    live = live.astype(bool)
    rows, n_live = live_row_table(live)
    xf = x.astype(F32)
    xdt = xf * dt[..., None]
    decay = jnp.broadcast_to(jnp.exp(dt * A)[..., None], (R, H, P))
    b3, c3 = B.reshape(R * G, 1, N), C.reshape(R * G, 1, N)

    head_map = live_block_map(nj)

    def state_idx(i, j, rows_ref, n_ref):
        r, jj = head_map(i, j, rows_ref, n_ref)
        return r, jj, 0, 0

    def vec_idx(i, j, rows_ref, n_ref):
        r, jj = head_map(i, j, rows_ref, n_ref)
        return r, jj, 0

    def group_idx(i, j, rows_ref, n_ref):
        r, jj = head_map(i, j, rows_ref, n_ref)
        return r * G + (jj * hb) // (H // G), 0, 0

    state_spec = pl.BlockSpec((1, hb, N, P), state_idx)
    vec_spec = pl.BlockSpec((1, hb, P), vec_idx)
    group_spec = pl.BlockSpec((1, 1, N), group_idx)
    S_new, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, nj),
            in_specs=[state_spec, vec_spec, vec_spec, group_spec, group_spec],
            out_specs=[state_spec, vec_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct((R, H, P), F32),
        ],
        input_output_aliases={2: 0},  # S, after the two prefetched tables
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(rows, n_live[None], S, xdt, decay, b3, c3)
    # a dead row's y was never written: select, do not multiply
    y = jnp.where(live[:, None, None], y + D[:, None] * xf, 0.0)
    return S_new, y.astype(x.dtype)


def ssm_scan(
    S: jax.Array,    # [H, N, P] float32: the state before the run
    x: jax.Array,    # [T, H, P]
    B: jax.Array,    # [T, G, N]
    C: jax.Array,    # [T, G, N]
    dt: jax.Array,   # [T, H] float32 after the softplus; 0 = the identity
    A: jax.Array,    # [H]
    D: jax.Array,    # [H]
    *,
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """A run of tokens of one request in the chunked dual form: inside a
    chunk of ``chunk`` tokens a masked [chunk, chunk] product on the matrix
    unit, one state a chunk handed on. Products with a float32 factor (a
    decay) run at the highest precision: the run is about 1% of a prefill
    chunk's FLOPs, and its state is what every later decode step compounds.
    Returns (y [T, H, P] in x's dtype, the state after the run)."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    hg, Q = H // G, chunk
    pad = (-T) % Q
    if pad:  # whole chunks: a step size of 0 changes nothing
        x, B, C, dt = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, B, C, dt))
    nc = (T + pad) // Q
    xf = x.astype(F32).reshape(nc, Q, G, hg, P)
    Bc, Cc = B.reshape(nc, Q, G, N), C.reshape(nc, Q, G, N)
    dtc = dt.reshape(nc, Q, G, hg)
    L = jnp.cumsum(dtc * A.reshape(G, hg), axis=1)          # [nc, Q, G, hg], <= 0

    # inside a chunk: y_t += sum_{s <= t} exp(L_t - L_s) dt_s (C_t . B_s) x_s
    cb = jnp.einsum("ctgn,csgn->cgts", Cc, Bc, preferred_element_type=F32)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None, None]
    seg = L[:, :, None] - L[:, None, :]                     # [nc, t, s, G, hg]
    w = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    w = w * dtc[:, None] * cb.transpose(0, 2, 3, 1)[..., None]
    y = jnp.einsum("ctsgh,csghp->ctghp", w, xf, precision=_HI)

    # a chunk's own contribution to the state at its end
    to_end = jnp.exp(L[:, -1:] - L) * dtc                   # [nc, Q, G, hg]
    own = jnp.einsum("csgn,csghp->cghnp", Bc.astype(F32), xf * to_end[..., None],
                     precision=_HI)
    through = jnp.exp(L[:, -1]).reshape(nc, H)              # a chunk's whole decay

    def carry(s, inp):
        d, o = inp
        return d[:, None, None] * s + o, s

    S_out, S_in = jax.lax.scan(carry, S, (through, own.reshape(nc, H, N, P)))
    # what the state entering a chunk adds: exp(L_t) C_t . S_in
    y = y + jnp.exp(L)[..., None] * jnp.einsum(
        "ctgn,cghnp->ctghp", Cc.astype(F32), S_in.reshape(nc, G, hg, N, P),
        precision=_HI)
    y = y + D.reshape(G, hg)[..., None] * xf
    return y.reshape(nc * Q, H, P)[:T].astype(x.dtype), S_out
