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
- Pallas off (CPU, pp, a one-head latent, the families off the auto rule): the
  pure-JAX twins of ops/attention.py, which are also the tests' reference.
- Before a chunk is read it is written: ``write_chunk`` puts a chunk's whole
  pages into the pool (``prefill``, and the chunk of a ``mixed_step``) on the
  view the kernels read wherever a kernel takes the pool whole, so that
  nothing between the write and the launch re-tiles the pool
  (ops/attention.write_prefill_kv has the reason). Decode rows' tokens are
  written by the programs themselves (ops/attention.write_decode_kv).
- A layer that hands a ``dsa`` (ops/attention.DsaQuery: latent attention over
  the positions a learned indexer selects, models/mla.py) asks ONE further
  question of the same rows, ``_selected``: score the indexer against the
  paged index keys (or take the selection the layer inherited), then attend
  each query over its own selected token rows. Pallas on, that is the launch
  ``sparse_latent_attention`` (ops/pallas_sparse.py) for decode rows, a chunk
  and a mixed step alike, and the index keys come out of the pages by the
  launch ``paged_index_keys`` beside it (the one tile a token that holds
  them, by runs of pages; the twin's slice of row 1 has XLA re-tile the whole
  second array first); the scoring and the top-k stay XLA under the scopes
  ``dsa_index`` and ``dsa_select`` (a cut by counting and a list by
  compaction: nothing is sorted). The seam tells the launch how many of
  its first queries are ONE row's chunk (``n_chunk``): the kernel stages
  that row's pages in VMEM once and those queries pick their keys there,
  where the table's width fits (a shape of the launch); decode rows, each a
  row of its own, gather token by token from HBM.
- A layer that hands a ``latent`` (ops/attention.LatentQuery: a latent layer
  WITHOUT an indexer, the same rows layout, nothing selects) asks the other
  further question: every query attends over every causal key of its row,
  ``_latent``. Pallas on, that is the launch
  ``paged_latent_attention`` (ops/pallas_latent.py), page-contiguous, for
  decode rows, a chunk and a mixed step alike (one launch each). With a
  ``window`` (a sliding layer's latent, models/dots3_note.py: the rows are a
  windowed page group's, below) it is the same launch with a lower bound a
  query, under the name ``windowed_latent_attention``: a row's walk starts at
  the chunk of pages its window starts in, never at its context's head.
- A layer that hands an ``eva`` (ops/attention.EvaQuery: exact attention in
  the query's window, one learned summary a chunk of the windows before it,
  models/evabyte.py) keeps a RING of pages and summary blocks by window in
  the same pool, and asks the questions above of rows that are each ONE
  paged sequence (``ops/attention.eva_paged_view``: the closed windows'
  summaries, then the ring): decode rows are the launch
  ``eva_decode_attention`` (ops/pallas_eva.py), a chunk and a mixed step the
  ragged launch the dense family uses, with no change to it. Before the rows
  are read their summaries are written (``summarise_chunk``,
  ``summarise_rows``, scope ``eva_summarise``): a chunk's summary is final
  once its page is full, and the step that fills the page writes it.
- A layer that hands an ``infllm`` (ops/attention.InfLlmQuery: block-sparse
  attention over pooled keys, models/minicpm_sala.py) keeps ONE POOLED KEY a
  page a kv head beside its pages, by block id, in the K pool's pages above
  ``summary_base`` (ops/attention.py has the layout). Before the rows are
  read the keys their tokens made final are written (``pool_chunk``,
  ``pool_rows``, scope ``infllm_pool_write``). Decode rows read the pooled
  keys of their tables and choose their blocks a kv head (scope
  ``infllm_select``, XLA), then attend as ONE paged sequence a (row, kv
  head) (``ops/attention.infllm_paged_view``: the chosen blocks' pages,
  ascending) through the decode kernel's own walk under the launch name
  ``infllm_decode_attention``; never every key. A chunk whose context ends
  within ``dense_len`` is the dense family's launch; past it the chunk's
  queries attend under a mask over the row's gathered pages (pure JAX: the
  block-sparse prefill kernel is queued, ROADMAP). A mixed step asks both
  questions, the chunk's and the rows'.
- A family whose pages are kept BY LAYER KIND (models/registry.page_groups)
  hands the launches above the rows of a layer's OWN group (``GroupView``):
  a windowed group's run of a row's table, which starts at the oldest page
  the row still holds, with lengths and query offsets shifted by that page's
  first position. Causal and window masks depend on differences of positions
  only, and keys are rotated before they are written, so the launches run as
  they are.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..parallel.mesh import AXIS_TP
from . import attention as att


@dataclasses.dataclass(frozen=True)
class GroupView:
    """One page group's share of a row's block table, for a family whose
    pages are kept by layer kind (engine/allocator.WindowGroup has the
    host's side): the group's pages at columns ``col .. col + pages`` and,
    for a windowed group (``shifted``), the page index its run starts at in
    column ``col + pages``. A step program's ``attend`` finds a layer's view
    by the layer's place among the page layers and asks it for the rows as
    that group's launch takes them."""

    col: int
    pages: int
    page: int                  # tokens a page
    shifted: bool = True

    def _run(self, tables):
        """(the group's columns, the position its first entry starts at)."""
        run = tables[..., self.col : self.col + self.pages]
        if not self.shifted:
            return run, jnp.zeros(tables.shape[:-1], jnp.int32)
        return run, tables[..., self.col + self.pages] * self.page

    def rows(self, tables, seq_lens, live):
        """Decode rows (one token a row, written at ``seq_lens - 1``) ->
        (tables, lengths, write pages) of this group; scratch page 0 for a
        row that is not ``live``."""
        run, base = self._run(tables)
        lens = jnp.where(seq_lens > 0, seq_lens - base, 0)
        entry = jnp.clip((lens - 1) // self.page, 0, self.pages - 1)
        pages = jnp.take_along_axis(run, entry[:, None], axis=1)[:, 0]
        return run, lens, jnp.where(live, pages, 0)

    def chunk(self, table, chunk_start, total_len, positions, ids):
        """One chunk from ``chunk_start``, ``ids`` its pages in the group
        that lives as long as the request (the host's) -> (table,
        chunk_start, total_len, positions, the chunk's pages) of this
        group. A windowed group's pages are read off its run: the run is as
        wide as a window, a chunk and a page, so the slice never clamps."""
        run, base = self._run(table)
        if self.shifted:
            ids = jax.lax.dynamic_slice(
                run, ((chunk_start - base) // self.page,), ids.shape
            )
        return run, chunk_start - base, total_len - base, positions - base, ids


# the launch over the chosen pages a (row, kv head): the decode kernel's body
# (ops/pallas_attention.py) under a name of its own, so that no reader of the
# dense launch's roofline, which reckons every causal key, ever counts it
INFLLM_KERNEL_NAME = "infllm_decode_attention"


class PagedAttention:
    def __init__(self, mesh: Mesh, use_pallas: bool, interpret: bool = False,
                 summary_base: int = 0):
        self.mesh = mesh
        self.use_pallas = use_pallas
        self.interpret = interpret
        # the pool's first page of summary blocks (a family with a ring:
        # ops/attention.py has the layout); unused by every other family
        self.summary_base = summary_base

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

    def _selected(self, q, kc, vc, tables, rows, q_pos, q_valid, dsa,
                  n_chunk):
        """Attention of packed queries ``q [Tq, h, rank + 128]`` over the
        token positions each selected: query ``i`` sits at ``q_pos[i]`` of
        the context of ``tables[rows[i]]`` (``q_valid`` false = padding or
        an empty row: nothing selected, zeros back). The first ``n_chunk``
        queries are one row's chunk and are scored against that row's keys
        in one product; every later query is a row of its own. A layer that
        selects leaves in ``dsa.index_chunk_reads`` what the read of the
        index keys takes of these tables whole and as runs (the step's
        counters; dead code where no one reads them)."""
        from . import pallas_sparse as ps

        if dsa.selected is None:
            Tq = q.shape[0]
            iq = dsa.index_q.reshape(Tq, *dsa.index_q.shape[-2:])
            iw = dsa.index_w.reshape(Tq, -1)
            with jax.named_scope(f"{dsa.scope}_index"):
                dsa.index_chunk_reads = ps.index_chunk_reads(tables)
                if self.use_pallas:
                    keys = ps.paged_index_keys(
                        vc, tables, iq.shape[-1], interpret=self.interpret
                    )
                else:
                    keys = att.paged_index_keys(vc, tables, iq.shape[-1])

            def chunk_scores(a, b):
                with jax.named_scope(f"{dsa.scope}_index"):
                    return att.dsa_index_scores(a, b, keys[0])

            def row_scores():
                one = lambda a, b, k: att.dsa_index_scores(  # noqa: E731
                    a[None], b[None], k
                )[0]
                with jax.named_scope(f"{dsa.scope}_index"):
                    return jax.vmap(one)(
                        iq[n_chunk:], iw[n_chunk:], keys[bool(n_chunk):]
                    )

            def select(scores, pos, valid):
                with jax.named_scope(f"{dsa.scope}_select"):
                    return att.dsa_select(scores, pos, valid, dsa.topk)

            slab = att.index_slab_rows(n_chunk, keys.shape[1]) if n_chunk else 0
            if slab == n_chunk:
                scores = []
                if n_chunk:
                    scores.append(chunk_scores(iq[:n_chunk], iw[:n_chunk]))
                if Tq > n_chunk:
                    scores.append(row_scores())
                dsa.selected = select(jnp.concatenate(scores, axis=0), q_pos, q_valid)
            else:
                # a chunk wider than a slab of its index scores: a slab's
                # lists are read out of its scores while those are on chip
                # (the chunk's scores never lie in HBM); the decode rows' apart
                parts = [att.by_slabs(
                    lambda a, b, pos, valid: select(chunk_scores(a, b), pos, valid),
                    slab, iq[:n_chunk], iw[:n_chunk], q_pos[:n_chunk],
                    q_valid[:n_chunk],
                )]
                if Tq > n_chunk:
                    parts.append(select(row_scores(), q_pos[n_chunk:], q_valid[n_chunk:]))
                dsa.selected = jnp.concatenate(parts, axis=0)
        with jax.named_scope("sparse_attend"):
            if not self.use_pallas:
                return att.sparse_latent_attention(
                    q, kc, vc, tables, rows, dsa.selected, dsa.scale
                )
            return ps.sparse_latent_attention(
                q, kc, vc, tables, rows, dsa.selected, scale=dsa.scale,
                n_chunk=n_chunk, interpret=self.interpret,
            )

    def _latent(self, q, kc, vc, tables, q_lens, seq_lens, latent, n_chunk):
        """Attention of packed queries ``q [Tq, h, rank + 128]`` over every
        causal latent row of their contexts: the first ``n_chunk`` queries
        are ``tables[0]``'s chunk (``q_lens[0]`` of them real), every later
        query is a one-token row of its own (``q_lens`` 0 = empty). Leaves
        in ``latent.chunk_reads`` what the kernel's chunk rule reads of these
        tables whole and as runs (the step's counters; dead code where no
        one reads them)."""
        from . import pallas_latent as plat

        with jax.named_scope(f"{latent.scope}_attend"):
            latent.chunk_reads = plat.chunk_reads(kc, tables, q_lens, seq_lens)
            # a window is the same launch under a name of its own: a reader of
            # ``paged_latent_attention``'s roofline reckons every causal key
            windowed = {} if latent.window is None else {
                "window": latent.window,
                "name": plat.WINDOWED_KERNEL_NAME,
            }
            if not self.use_pallas:
                first = 1 if n_chunk else 0
                q_starts = jnp.concatenate([
                    jnp.zeros((first,), jnp.int32),
                    n_chunk + jnp.arange(tables.shape[0] - first),
                ])
                return att.paged_latent_attention(
                    q, kc, vc, tables, q_starts, q_lens, seq_lens,
                    latent.scale, window=latent.window,
                )
            return plat.paged_latent_attention(
                q, kc, vc, tables, q_lens, seq_lens, scale=latent.scale,
                n_chunk=n_chunk, interpret=self.interpret, **windowed,
            )

    def summarise_chunk(self, kc, vc, k_new, v_new, table, chunk_start,
                        total_len, eva):
        """The summaries of a chunk's WHOLE pages (``k_new`` [S_pad, h, d]
        from ``chunk_start``, real up to ``total_len``; a chunk lies in one
        window) into its window's summary block; a page the prompt does not
        fill is summarised by the decode step that fills it."""
        with jax.named_scope("eva_summarise"):
            C = eva.chunk
            n = k_new.shape[0] // C
            ks, vs = att.eva_summarise(
                k_new.reshape(n, C, *k_new.shape[1:]),
                v_new.reshape(n, C, *v_new.shape[1:]), eva,
            )
            first = chunk_start + jnp.arange(n) * C
            pages, offsets = att.eva_summary_slots(
                jnp.broadcast_to(table[None], (n, table.shape[0])), first,
                first + C <= total_len, eva, kc.shape[1], self.summary_base,
            )
            return att.write_decode_kv(kc, vc, ks, vs, pages, offsets)

    def summarise_rows(self, kc, vc, tables, seq_lens, write_blocks,
                       write_offsets, eva):
        """Decode rows, their token written at ``(write_blocks,
        write_offsets)`` (scratch page 0: not a live row): a row whose token
        filled its page writes the page's summary, read back whole from the
        pool, into its window's block."""
        with jax.named_scope("eva_summarise"):
            full = (write_blocks > 0) & (write_offsets == eva.chunk - 1)
            pages, offsets = att.eva_summary_slots(
                tables, jnp.maximum(seq_lens - 1, 0), full, eva, kc.shape[1],
                self.summary_base,
            )
            # a row fills a page one step in ``chunk``: a loop over the rows
            # that did (1.5 of 24 a step), not a product over all of them
            rows = jnp.nonzero(full, size=full.shape[0], fill_value=0)[0]

            def one(i, pools):
                kc, vc = pools
                r = rows[i]
                ks, vs = att.eva_summarise(
                    kc[write_blocks[r]], vc[write_blocks[r]], eva
                )
                return (kc.at[pages[r], offsets[r]].set(ks),
                        vc.at[pages[r], offsets[r]].set(vs))

            return jax.lax.fori_loop(0, jnp.sum(full), one, (kc, vc))

    def pool_chunk(self, kc, k_new, table, chunk_start, total_len, infllm):
        """The pooled keys a chunk's whole pages make final, into the K pool
        (ops/attention.infllm_pool_chunk)."""
        with jax.named_scope("infllm_pool_write"):
            return att.infllm_pool_chunk(
                kc, k_new, table, chunk_start, total_len, infllm,
                self.summary_base, page_view=self._page_view,
            )

    def pool_rows(self, kc, tables, seq_lens, write_blocks, write_offsets,
                  infllm):
        """Decode rows: the pooled key a row's token made final by filling
        its page (ops/attention.infllm_pool_rows)."""
        with jax.named_scope("infllm_pool_write"):
            return att.infllm_pool_rows(
                kc, tables, seq_lens, write_blocks, write_offsets, infllm,
                self.summary_base,
            )

    def _infllm_decode(self, q, kc, vc, tables, seq_lens, infllm):
        """Decode rows of a block-sparse layer: the selection, then ONE
        launch over a table a (row, kv head)."""
        if not self.use_pallas:
            return att.infllm_paged_decode_attention(
                q, kc, vc, tables, seq_lens, infllm, self.summary_base
            )
        from . import pallas_attention as pa

        qv, view, lens = att.infllm_decode_rows(
            q, kc, tables, seq_lens, infllm, self.summary_base
        )
        with jax.named_scope("infllm_attend"):
            out = pa.paged_decode_attention(
                qv, kc, vc, view, lens, interpret=self.interpret,
                name=INFLLM_KERNEL_NAME,
            )
        return att.infllm_own_heads(out, kc.shape[2])

    def _eva_view(self, kc, tables, seq_lens, eva):
        return att.eva_paged_view(
            tables, seq_lens, eva, kc.shape[1], self.summary_base
        )

    @property
    def _page_view(self) -> bool:
        """Whether a chunk's writes go through the view the kernels read."""
        return self.use_pallas and self.mesh.size == 1

    def write_chunk(self, kc, vc, k_new, v_new, block_ids):
        """A chunk's whole pages into the pool, before the launch that reads
        them (ops/attention.write_prefill_kv has the contract). Where a
        Pallas kernel takes the pool whole (Pallas on, one device: under
        ``tp`` the kernel's view exists only inside its ``shard_map``) the
        pages are written on the view the kernel reads, so that nothing
        between the write and the launch has the pool to re-tile."""
        return att.write_prefill_kv(
            kc, vc, k_new, v_new, block_ids, page_view=self._page_view,
        )

    def decode_chunk_pages(self, kc, tables, extra):
        """Pages a chunk of the decode-only kernel's walk over decode rows
        asked with ``extra`` (``decode``'s keywords), or None where another
        launch serves them: what the host counts a step's whole chunks and
        runs of pages by (engine ``_count_paged``). Shapes only."""
        if not self.use_pallas or extra:
            return None
        from . import pallas_paged as paged

        pages = getattr(kc, "data", kc)     # QuantizedKV: its int8 payload
        _, bs, kvh, d = pages.shape
        return paged.chunk_pages(
            bs, kvh // self.mesh.shape[AXIS_TP], d, pages.dtype,
            tables.shape[1],
        )

    def decode(self, q, kc, vc, tables, seq_lens, dsa=None, latent=None,
               eva=None, infllm=None, **extra):
        """Decode rows: ``q [B, h, d]``, one token a row at the end of a
        context of ``seq_lens[b]`` tokens (0 = an empty row)."""
        if infllm is not None:
            return self._infllm_decode(q, kc, vc, tables, seq_lens, infllm)
        if eva is not None:
            with jax.named_scope("eva_attend"):
                if not self.use_pallas:
                    return att.eva_paged_decode_attention(
                        q, kc, vc, tables, seq_lens, eva, self.summary_base
                    )
                from . import pallas_eva

                return pallas_eva.eva_decode_attention(
                    q, kc, vc, tables, seq_lens, eva, self.summary_base,
                    interpret=self.interpret,
                )
        if latent is not None:
            return self._latent(
                q, kc, vc, tables, seq_lens > 0, seq_lens, latent, 0
            )
        if dsa is not None:
            return self._selected(
                q, kc, vc, tables, jnp.arange(q.shape[0]), seq_lens - 1,
                seq_lens > 0, dsa, 0,
            )
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
              dsa=None, latent=None, eva=None, infllm=None, **extra):
        """One chunk at its context's tail: ``q [S_pad, h, d]`` at absolute
        ``positions``, the real ones ``chunk_start .. total_len - 1``, over
        ONE ``table``; the chunk's own keys are already in the cache."""
        if infllm is not None:
            dense = lambda: self.chunk(  # noqa: E731
                q, kc, vc, table, chunk_start, total_len, positions
            )
            if table.shape[0] * kc.shape[1] <= infllm.dense_len:
                return dense()      # no context this table holds selects
            return jax.lax.cond(
                total_len > infllm.dense_len,
                lambda: att.infllm_chunk_attention(
                    q, kc, vc, table, positions, total_len, infllm,
                    self.summary_base,
                ),
                dense,
            )
        if eva is not None:
            # the row as one paged sequence, the chunk still at its tail
            with jax.named_scope("eva_attend"):
                view, lens = self._eva_view(kc, table[None], total_len[None], eva)
                shift = total_len - lens[0]
                return self.chunk(
                    q, kc, vc, view[0], chunk_start - shift, lens[0],
                    jnp.where(positions < total_len, positions - shift,
                              lens[0] - 1),
                )
        if latent is not None:
            return self._latent(
                q, kc, vc, table[None], (total_len - chunk_start)[None],
                total_len[None], latent, q.shape[0],
            )
        if dsa is not None:
            S = q.shape[0]
            return self._selected(
                q, kc, vc, table[None], jnp.zeros((S,), jnp.int32),
                positions, positions < total_len, dsa, S,
            )
        if not self.use_pallas:
            k_ctx, v_ctx = att.gather_kv(kc, vc, table)
            return att.extend_attention(
                q, k_ctx, v_ctx, positions, total_len, **extra
            )
        return self._launch(
            q, kc, vc, table[None], jnp.zeros((1,), jnp.int32),
            (total_len - chunk_start)[None], total_len[None], **extra,
        )

    def ragged(self, q, kc, vc, tables, q_starts, q_lens, seq_lens,
               dsa=None, latent=None, eva=None, infllm=None, **extra):
        """Ragged rows over a packed ``q [Tq, h, d]``: row ``r`` owns
        ``q[q_starts[r] : q_starts[r] + q_lens[r]]`` at the tail of its
        context (ops/attention.ragged_paged_attention has the contract).
        With a ``dsa`` or a ``latent`` the rows are the mixed step's: row 0 a chunk at the
        front of ``q``, every further row one token behind it."""
        if infllm is not None:
            # the mixed step's two questions, asked apart: the chunk's
            # queries (dense or under their mask), the rows' chosen pages
            n_chunk = q.shape[0] - (tables.shape[0] - 1)
            return jnp.concatenate([
                self.chunk(
                    q[:n_chunk], kc, vc, tables[0], seq_lens[0] - q_lens[0],
                    seq_lens[0],
                    seq_lens[0] - q_lens[0] + jnp.arange(n_chunk), infllm=infllm,
                ),
                self._infllm_decode(
                    q[n_chunk:], kc, vc, tables[1:], seq_lens[1:], infllm
                ),
            ])
        if eva is not None:
            with jax.named_scope("eva_attend"):
                view, lens = self._eva_view(kc, tables, seq_lens, eva)
                return self.ragged(q, kc, vc, view, q_starts, q_lens, lens)
        if latent is not None:
            return self._latent(
                q, kc, vc, tables, q_lens, seq_lens, latent,
                q.shape[0] - (tables.shape[0] - 1),
            )
        if dsa is not None:
            Tq, R = q.shape[0], tables.shape[0]
            n_chunk = Tq - (R - 1)
            i = jnp.arange(Tq)
            rows = jnp.maximum(i - n_chunk + 1, 0)
            local = i - q_starts[rows]
            return self._selected(
                q, kc, vc, tables, rows,
                seq_lens[rows] - q_lens[rows] + local,
                (local < q_lens[rows]) & (seq_lens[rows] > 0), dsa, n_chunk,
            )
        launch = self._launch if self.use_pallas else att.ragged_paged_attention
        return launch(q, kc, vc, tables, q_starts, q_lens, seq_lens, **extra)

    def verify(self, q, kc, vc, tables, seq_lens, **extra):
        # no ``dsa``: a latent held as rows is refused a speculative draft
        # at construction (models/registry.check_supported)
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
