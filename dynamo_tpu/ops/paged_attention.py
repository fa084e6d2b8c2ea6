"""The attention seam: which implementation serves a set of paged rows.

The step programs (engine/engine.py) state WHAT rows they have; this module
decides HOW they are served, once, from what an engine knows when it is built:
the mesh, whether the Pallas kernels are on, and whether they are interpreted.
Every question takes a layer's ``extra`` (``window``, ``sinks``, ``softcap``)
straight through.

- Pallas on: every question is one launch of the ragged kernel
  (ops/pallas_unified.py): a chunk is one row at its context's tail, verify
  rows are ``n``-token rows, decode rows under a window, sinks or a softcap
  are one-token rows. Decode rows with none of them (a shape the seam sees at
  trace time: the decode question, no ``extra``) go to the decode-only kernel
  (ops/pallas_attention.py): PERF.md section 6, PR 28 has the chip runs in
  which the ragged kernel's one-token rows read level by every median and not
  by their tail.
- Pallas off (CPU, pp, latent attention, the families off the auto rule): the
  pure-JAX twins of ops/attention.py, which are also the tests' reference.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import Mesh

from ..parallel.mesh import AXIS_TP
from . import attention as att


class PagedAttention:
    def __init__(self, mesh: Mesh, use_pallas: bool, interpret: bool = False):
        self.mesh = mesh
        self.use_pallas = use_pallas
        self.interpret = interpret

    def _launch(self, q, kc, vc, tables, q_starts, q_lens, seq_lens,
                window=None, sinks=None, softcap=None, **kw):
        from . import pallas_unified as pun

        # a layer's scalar window -> the kernel's per-row bounds (every row
        # of one launch is the same layer's)
        windows = (
            None if window is None
            else jnp.full((tables.shape[0],), window, jnp.int32)
        )
        return pun.sharded_ragged_paged_attention(
            self.mesh, AXIS_TP, q, kc, vc, tables,
            q_starts.astype(jnp.int32), q_lens.astype(jnp.int32),
            seq_lens.astype(jnp.int32),
            windows=windows, sinks=sinks, softcap=softcap,
            interpret=self.interpret, **kw,
        )

    def decode(self, q, kc, vc, tables, seq_lens, **extra):
        """Decode rows: ``q [B, h, d]``, one token a row at the end of a
        context of ``seq_lens[b]`` tokens (0 = an empty row)."""
        if not self.use_pallas:
            return att.paged_decode_attention(
                q, kc, vc, tables, seq_lens, **extra
            )
        if not extra:
            from . import pallas_attention as pa

            return pa.sharded_paged_decode_attention(
                self.mesh, AXIS_TP, q, kc, vc, tables, seq_lens,
                interpret=self.interpret,
            )
        return self._launch(
            q, kc, vc, tables, jnp.arange(q.shape[0]), seq_lens > 0,
            seq_lens, **extra,
        )

    def chunk(self, q, kc, vc, table, chunk_start, total_len, positions,
              **extra):
        """One chunk at its context's tail: ``q [S_pad, h, d]`` at absolute
        ``positions``, the real ones ``chunk_start .. total_len - 1``, over
        ONE ``table``; the chunk's own keys are already in the cache."""
        if not self.use_pallas:
            k_ctx, v_ctx = att.gather_kv(kc, vc, table)
            return att.extend_attention(
                q, k_ctx, v_ctx, positions, total_len, **extra
            )
        return self._launch(
            q, kc, vc, table[None], jnp.zeros((1,), jnp.int32),
            (total_len - chunk_start)[None], total_len[None], **extra,
        )

    def ragged(self, q, kc, vc, tables, q_starts, q_lens, seq_lens, **extra):
        """Ragged rows over a packed ``q [Tq, h, d]``: row ``r`` owns
        ``q[q_starts[r] : q_starts[r] + q_lens[r]]`` at the tail of its
        context (ops/attention.ragged_paged_attention has the contract)."""
        launch = self._launch if self.use_pallas else att.ragged_paged_attention
        return launch(q, kc, vc, tables, q_starts, q_lens, seq_lens, **extra)

    def verify(self, q, kc, vc, tables, seq_lens, **extra):
        """Ragged rows of one static length: ``q [B, n, h, d]``, row ``b``'s
        ``n`` tokens at the tail of a context of ``seq_lens[b]`` (0 = an
        empty row). The pure-JAX side keeps the batched extend op: the
        ragged twin would score the whole packed buffer for every row."""
        B, n, h, d = q.shape
        if not self.use_pallas:
            return att.paged_extend_attention(
                q, kc, vc, tables, jnp.maximum(seq_lens - n, 0), seq_lens,
                **extra,
            )
        out = self._launch(
            q.reshape(B * n, h, d), kc, vc, tables, jnp.arange(B) * n,
            jnp.where(seq_lens > 0, n, 0), seq_lens, **extra,
        )
        return out.reshape(B, n, h, d)
