"""Rehearsal compiles: every Pallas kernel of the serving path, at
qwen3-0.6b's real widths, compiled by the TPU's own compiler for a v5e that
is described and not attached (on-chip-measurement guide, section 2).

Interpret-mode tests cannot see what Mosaic refuses — a DMA slice not
aligned to the tiling, more VMEM than a kernel may use, a kernel that cannot
be partitioned. These compiles can, at about two seconds each and no chip
time; nothing runs, so they say nothing about results (chip_smoke.py does,
on the chip). Skipped only where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import base64
import collections
import functools
import hashlib
import json
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import block_copy as bc
from dynamo_tpu.ops import pallas_moe as pmoe
from dynamo_tpu.ops import pallas_unified as pun
from dynamo_tpu.ops.paged_attention import PagedAttention
from dynamo_tpu.ops.quant import QuantizedKV

# qwen3-0.6b as `python -m dynamo_tpu.engine --preset qwen3-0.6b` serves it:
# 16 q heads / 8 kv heads x 128, 2048 pages x 16 tokens, context 2048, batch 8
NB, BS, KVH, H, D, MB, B = 2048, 16, 8, 16, 128, 128, 8
BF, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def mosaic_dump(tmp_path_factory):
    """Where Mosaic writes its passes for every kernel this module compiles:
    the flag is read once, when the library starts, so it is set before `v5e`
    describes a chip; `_empty_mosaic_dump` empties the directory after each
    test (all of this module's kernels would leave 2 GB)."""
    was = os.environ.get("LIBTPU_INIT_ARGS")
    dump = tmp_path_factory.mktemp("mosaic")
    os.environ["LIBTPU_INIT_ARGS"] = f"{was or ''} --xla_mosaic_dump_to={dump}"
    yield dump
    if was is None:
        os.environ.pop("LIBTPU_INIT_ARGS", None)
    else:
        os.environ["LIBTPU_INIT_ARGS"] = was


@pytest.fixture(autouse=True)
def _empty_mosaic_dump(mosaic_dump):
    yield
    for f in mosaic_dump.iterdir():
        f.unlink()


@pytest.fixture(scope="module")
def v5e(mosaic_dump):
    """Devices of a described v5e 2x2, with the persistent compilation cache
    off around the module (an entry written for a described chip cannot be
    read back without one; the next compile would warn and redo it)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip_seam(v5e):
    """The attention seam on its Pallas side over one described chip."""
    from dynamo_tpu.parallel.mesh import make_mesh

    return PagedAttention(make_mesh(tp=1, devices=v5e[:1]), True)


def _shapes(sharding, kvh=KVH, h=H):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    cache = s((NB, BS, kvh, D), BF)
    unified = (  # one prefill chunk + B decode rows: the mixed step's launch
        s((512 + B, h, D), BF), cache, cache, s((B + 1, MB), I32),
        s((B + 1,), I32), s((B + 1,), I32), s((B + 1,), I32),
    )
    rows = s((B + 1,), I32)
    ids = s((32,), I32)
    return s, cache, unified, rows, ids


def sparse_shapes(tq, rows):
    def build(sharding):
        def s(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        page = s((14336, 16, 4, 128), BF)
        return (s((tq, 64, 640), BF), page, page, s((rows, 1600), I32),
                s((tq, 32, 128), BF), s((tq, 32), F32))
    return build


def _dsa(iq, iw):
    from dynamo_tpu.ops.attention import DsaQuery

    return DsaQuery(scale=1 / 16, topk=2048, index_q=iq, index_w=iw)


def sparse_mixed(seam, q, kc, vc, tables, iq, iw):
    R = tables.shape[0]
    S = q.shape[0] - (R - 1)
    q_starts = jnp.concatenate([jnp.zeros((1,), I32), S + jnp.arange(R - 1, dtype=I32)])
    q_lens = jnp.concatenate([jnp.full((1,), S, I32), jnp.ones((R - 1,), I32)])
    return seam.ragged(q, kc, vc, tables, q_starts, q_lens,
                       jnp.full((R,), 25000, I32), dsa=_dsa(iq, iw))


def sparse_decode(seam, q, kc, vc, tables, iq, iw):
    return seam.decode(q, kc, vc, tables, jnp.full((q.shape[0],), 25000, I32),
                       dsa=_dsa(iq, iw))


sparse_mixed.asks_seam = sparse_decode.asks_seam = True


def sparse_launch(mb):
    """The kernel itself, 512 chunk queries + 8 decode rows, over tables of
    ``mb`` pages: 4 096 pages of 16 tokens x 1.5 KB are all that
    ``STAGED_VMEM_BYTES`` holds (the launch then asks Mosaic for 112 MiB);
    one page more and every query gathers."""
    from dynamo_tpu.ops import pallas_sparse as ps

    def fn(q, kc, vc, tables, rows, sel):
        return ps.sparse_latent_attention(
            q, kc, vc, tables, rows, sel, scale=1 / 16, n_chunk=512)

    def build(sharding):
        def s(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        page = s((14336, 16, 4, 128), BF)
        return (s((520, 64, 640), BF), page, page, s((9, mb), I32),
                s((520,), I32), s((520, 2048), I32))
    return fn, build


def index_keys(tables):
    """The read of the index keys in front of the selection (PR 48), at the
    long-document cell's sizes: the second array of 14 336 pages, ``tables``
    tables of 1 600 pages (8 decode rows; 1 a lone chunk)."""
    from dynamo_tpu.ops import pallas_sparse as ps

    def build(sharding):
        def s(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        return s((14336, 16, 4, 128), BF), s((tables, 1600), I32)
    return (lambda vc, tb: ps.paged_index_keys(vc, tb, 128)), build


def _cases():
    """name -> (fn, shape-args builder). Builders take the ShapeDtypeStruct
    factory so one table serves any sharding."""

    def seam(question):
        """A question of the attention seam on its Pallas side: the test
        hands ``fn`` the seam over the described chip."""
        def fn(attn, *args):
            return getattr(attn, question)(*args)
        fn.asks_seam = True
        return fn

    def decode(kvh, h, rows=B, max_blocks=MB, num_blocks=NB):
        def build(sh):
            s, *_ = _shapes(sh, kvh, h)
            cache = s((num_blocks, BS, kvh, D), BF)
            return (s((rows, h, D), BF), cache, cache,
                    s((rows, max_blocks), I32), s((rows,), I32))
        return seam("decode"), build

    def chunk(S):
        # a lone prefill chunk as ONE ragged row over the long-cache cell's
        # table width (544 pages) and page pool
        def build(sh):
            s, *_ = _shapes(sh)
            cache = s((6400, BS, KVH, D), BF)
            return (s((S, H, D), BF), cache, cache, s((544,), I32),
                    s((), I32), s((), I32), s((S,), I32))
        return seam("chunk"), build

    def unified(fn, extra=()):
        def build(sh):
            s, _, args, rows, _ = _shapes(sh)
            return args + tuple(
                rows if e == "rows" else s((H,), F32) for e in extra
            )
        return fn, build

    def unified_wide(sh):
        s, cache, _, _, _ = _shapes(sh)
        return (s((2048 + B, H, D), BF), cache, cache, s((B + 1, MB), I32),
                s((B + 1,), I32), s((B + 1,), I32), s((B + 1,), I32))

    def windowed(q, k, v, t, a, b, c, w):
        return pun.ragged_paged_attention(q, k, v, t, a, b, c, windows=w)

    def sinks(q, k, v, t, a, b, c, w, snk):
        return pun.ragged_paged_attention(
            q, k, v, t, a, b, c, windows=w, sinks=snk, softcap=30.0
        )

    def windowed_moe_cell(sh):
        # the benchmark's mellum2 cell (BENCHMARK.json): 32 q / 4 kv heads,
        # 7 168 pages of 16 tokens, contexts to 7 168 (448 pages a row), a
        # 512-token chunk row plus 16 q_len=1 rows, window 1 024
        s, *_ = _shapes(sh)
        cache = s((7168, BS, 4, D), BF)
        rows = s((17,), I32)
        return (s((512 + 16, 32, D), BF), cache, cache, s((17, 448), I32),
                rows, rows, rows, rows)

    def unified_cell(h, kvh, q_tokens, rows, max_blocks, num_blocks,
                     window=False):
        # a cell's real launch (BENCHMARK.json): the packed tokens of one
        # bucketed chunk + one token a resident row, tables of the cell's
        # context, the cell's page pool
        def build(sh):
            s, *_ = _shapes(sh)
            cache = s((num_blocks, BS, kvh, D), BF)
            r = s((rows,), I32)
            return (s((q_tokens, h, D), BF), cache, cache,
                    s((rows, max_blocks), I32), r, r, r) + (
                        (r,) if window else ())
        return (windowed if window else pun.ragged_paged_attention), build

    def grouped(rows_sorted, k, n, n_rhs, experts=64):
        # the same cell's expert layer: 64 experts, hidden 2 304, width 896;
        # 128 sorted rows is a decode step (16 rows x top 8), 4 224 a
        # 512-token chunk beside 16 decode rows
        def build(sh):
            s, *_ = _shapes(sh)
            return (s((rows_sorted, k), BF), s((experts,), I32)) + (
                s((experts, k, n), BF),
            ) * n_rhs

        def fn(lhs, sizes, *rhs):
            return pmoe.grouped_matmul(lhs, rhs, sizes)
        return fn, build

    def latent(question, q_tokens, rows, max_blocks=1600):
        # latent attention over every causal key at A.X-K1's widths and the
        # document-QA cell's sizes: 64 absorbed heads over 512 + 64 lanes in
        # rows of 128, 14 336 pages, tables of 1 600 pages (a chunk of 64
        # pages: the VMEM side of the chunk rule) or of 32 (the table's side)
        from dynamo_tpu.ops.attention import LatentQuery

        def fn(attn, *args):
            return getattr(attn, question)(
                *args, latent=LatentQuery(scale=0.13086))
        fn.asks_seam = True

        def build(sh):
            s, *_ = _shapes(sh)
            page = s((14336, BS, 4, D), BF)
            q = s((q_tokens, 64, 640), BF)
            r = s((rows,), I32)
            if question == "decode":
                return (q, page, page, s((rows, max_blocks), I32), r)
            if question == "chunk":
                return (q, page, page, s((max_blocks,), I32), s((), I32),
                        s((), I32), s((q_tokens,), I32))
            return (q, page, page, s((rows, max_blocks), I32), r, r, r)
        return fn, build

    def windowed_latent(question, q_tokens, rows, max_blocks=162):
        # a sliding layer's latent at dots3-note's widths and the agent cell's
        # sizes: 64 absorbed heads over 1024 + 64 lanes, the latent 8 rows of
        # 128 and the second array its one tile (2 rows), a windowed group's
        # pool of 3 121 pages and its run of a row's table (513 keys + a
        # 2 048-token chunk + a page: 162 pages, a chunk of 32)
        from dynamo_tpu.ops.attention import LatentQuery

        def fn(attn, *args):
            return getattr(attn, question)(
                *args, latent=LatentQuery(scale=0.0625, window=513))
        fn.asks_seam = True

        def build(sh):
            s, *_ = _shapes(sh)
            kp, vp = s((3121, BS, 8, D), BF), s((3121, BS, 2, D), BF)
            q = s((q_tokens, 64, 1152), BF)
            r = s((rows,), I32)
            if question == "decode":
                return (q, kp, vp, s((rows, max_blocks), I32), r)
            if question == "chunk":
                return (q, kp, vp, s((max_blocks,), I32), s((), I32),
                        s((), I32), s((q_tokens,), I32))
            return (q, kp, vp, s((rows, max_blocks), I32), r, r, r)
        return fn, build

    def dots3_sparse(tq, rows, mb=2336):
        # a full layer of dots3-note: 128 absorbed heads over 512 + 64 lanes,
        # 64 index heads, the second array its one tile, tables of 37 376
        # tokens (staged: 56 MiB of the chunk row's pages in VMEM)
        def build(sh):
            s, *_ = _shapes(sh)
            return (s((tq, 128, 640), BF), s((37888, BS, 4, D), BF),
                    s((37888, BS, 2, D), BF), s((rows, mb), I32),
                    s((tq, 64, 128), BF), s((tq, 64), F32))
        return build

    def ssm_update(rows):
        # the state-space mixer's decode recurrence at Falcon-H1-34B's
        # widths: 32 heads x [256 state, 128 lanes] float32 a row, 2 groups
        from dynamo_tpu.ops import pallas_ssm

        def build(sh):
            s, *_ = _shapes(sh)
            return (s((rows, 32, 256, 128), F32), s((rows, 32, 128), BF),
                    s((rows, 2, 256), BF), s((rows, 2, 256), BF),
                    s((rows, 32), F32), s((32,), F32), s((32,), F32),
                    s((rows,), jnp.bool_))
        return pallas_ssm.ssm_state_update, build

    def kda_update(rows):
        # a KDA layer's decode recurrence at Solar-Open2-250B's widths: 64
        # heads x [128 key channels, 128 value lanes] float32 a row
        from dynamo_tpu.ops import pallas_kda

        def build(sh):
            s, *_ = _shapes(sh)
            vec = s((rows, 64, 128), F32)
            return (s((rows, 64, 128, 128), F32), vec, vec, s((rows, 64, 128), BF),
                    vec, s((rows, 64), F32), s((rows,), jnp.bool_))
        return pallas_kda.kda_state_update, build

    def lightning_update(rows):
        # a lightning layer's decode recurrence at MiniCPM-SALA's widths: 32
        # heads x [128 key channels, 128 value lanes] float32 a row, the
        # delta rule's skeleton without its correction
        from dynamo_tpu.ops import pallas_lightning

        def build(sh):
            s, *_ = _shapes(sh)
            vec = s((rows, 32, 128), BF)
            return (s((rows, 32, 128, 128), F32), s((rows, 32, 128), F32), vec,
                    vec, s((32,), F32), s((rows,), jnp.bool_))
        return pallas_lightning.lightning_state_update, build

    def infllm(question, q_tokens, rows):
        # block-sparse attention over pooled keys at MiniCPM-SALA's widths
        # and the long-document cell's sizes (PR 54): 32 q / 2 kv heads, 32
        # rows over tables of 1 160 pages in a pool of 37 136 + 2 321 pages;
        # decode rows are the launch ``infllm_decode_attention`` over a view
        # a (row, kv head), a chunk the dense launch or the mask
        spec = att.InfLlmQuery(32, 16, 64, 64, 1, 2048, 8192)

        def fn(attn, *args):
            attn = PagedAttention(attn.mesh, True, summary_base=37136)
            return getattr(attn, question)(*args, infllm=spec)
        fn.asks_seam = True

        def build(sh):
            s, *_ = _shapes(sh)
            page = s((37136 + 2321, BS, 2, D), BF)
            q = s((q_tokens, 32, D), BF)
            r = s((rows,), I32)
            if question == "decode":
                return (q, page, page, s((rows, 1160), I32), r)
            if question == "chunk":
                return (q, page, page, s((1160,), I32), s((), I32),
                        s((), I32), s((q_tokens,), I32))
            return (q, page, page, s((rows, 1160), I32), r, r, r)
        return fn, build

    def eva(question, q_tokens, rows):
        # EVA's attention at EvaByte's widths and the long-answer cell's
        # sizes (PR 46): 32 heads x 128, multi-head, a ring of 128 pages and
        # 5 summary blocks a row in a pool of 3 073 + 121 x 8 pages; decode
        # rows are the launch ``eva_decode_attention``, a chunk and a mixed
        # step the ragged launch over the rows as one paged sequence each
        def fn(attn, *args):
            *args, mu, phi = args
            attn = PagedAttention(attn.mesh, True, summary_base=3073)
            return getattr(attn, question)(
                *args, eva=att.EvaQuery(mu, phi, 2048, 16))
        fn.asks_seam = True

        def build(sh):
            s, *_ = _shapes(sh)
            page = s((3073 + 121 * 8, BS, 32, D), BF)
            q, vec = s((q_tokens, 32, D), BF), s((32, D), BF)
            r = s((rows,), I32)
            if question == "decode":
                return (q, page, page, s((rows, 133), I32), r, vec, vec)
            if question == "chunk":
                return (q, page, page, s((133,), I32), s((), I32), s((), I32),
                        s((q_tokens,), I32), vec, vec)
            return (q, page, page, s((rows, 133), I32), r, r, r, vec, vec)
        return fn, build

    def moves(fn, n_ids, with_pages):
        def build(sh):
            s, cache, _, _, ids = _shapes(sh)
            pages = (s((32, BS, KVH, D), BF),) if with_pages else ()
            return (cache,) + (ids,) * n_ids + pages
        return fn, build

    return {
        "decode-bf16-kvh8": decode(KVH, H),
        "decode-bf16-kvh2-tp4-shard": decode(KVH // 4, H // 4),
        # the benchmark's internlm2 cells (BENCHMARK.json): 6 400 pages,
        # batch 12 over 8 704-token contexts, batch 32 over 3 072
        "decode-bf16-longcache-cell": decode(KVH, H, 12, 544, 6400),
        "decode-bf16-chat-cell": decode(KVH, H, 32, 192, 6400),
        "chunk-row-S128": chunk(128),
        "chunk-row-S512": chunk(512),
        "chunk-row-S2048": chunk(2048),
        "unified-plain": unified(pun.ragged_paged_attention),
        "unified-plain-chunk2048": (pun.ragged_paged_attention, unified_wide),
        "unified-windowed": unified(windowed, ("rows",)),
        "unified-window-sinks-softcap": unified(sinks, ("rows", "sinks")),
        "unified-windowed-moe-cell": (windowed, windowed_moe_cell),
        # the cells' mixed launches through the rebuilt loop (PR 27):
        # InternLM2 512 + 12 rows over 8 704-token tables, Mistral 512 + 8
        # over 4 608, Mellum's sliding layers in a decode step (16 q_len=1
        # rows) and its full layers in a mixed step, one tp=4 shard (kvh 2)
        "unified-longcache-cell": unified_cell(16, 8, 524, 13, 544, 6400),
        "unified-rag-cell": unified_cell(32, 8, 520, 9, 288, 2048),
        "unified-windowed-moe-cell-decode": unified_cell(
            32, 4, 16, 16, 448, 7168, window=True),
        "unified-moe-cell-full-layers": unified_cell(
            32, 4, 528, 17, 448, 7168),
        "unified-kvh2-tp4-shard": unified_cell(8, 2, 520, 9, 192, 8192),
        "grouped-matmul-gate-up-rows128": grouped(128, 2304, 896, 2),
        "grouped-matmul-down-rows128": grouped(128, 896, 2304, 1),
        "grouped-matmul-gate-up-rows4224": grouped(4224, 2304, 896, 2),
        "grouped-matmul-down-rows4224": grouped(4224, 896, 2304, 1),
        # latent attention over selected keys at GLM-5.2's widths and the
        # long-document cell's sizes: 64 heads, a 512-lane latent in rows of
        # 128, 14336 pages, 2048 selected of 25600; a mixed step's 512 + 8
        # queries, and eight decode rows with the indexer's scoring
        "sparse-latent-mixed": (sparse_mixed, sparse_shapes(520, 9)),
        "sparse-latent-decode": (sparse_decode, sparse_shapes(8, 8)),
        "sparse-latent-staged-all-the-vmem": sparse_launch(4096),
        "sparse-latent-too-wide-to-stage": sparse_launch(4097),
        "index-keys-decode": index_keys(8),
        "index-keys-chunk": index_keys(1),
        # the seam's dense-latent question (PR 33): decode rows, a lone chunk
        # and the mixed step, each one launch; the chunk rule's two sides
        "paged-latent-decode": latent("decode", 8, 8),
        "paged-latent-chunk-S512": latent("chunk", 512, 1),
        "paged-latent-mixed": latent("ragged", 520, 9),
        "paged-latent-mixed-S128-narrow-table": latent("ragged", 136, 9, 32),
        # the same launch under a window, at rank 1024 (dots3-note's sliding
        # layers): decode rows, a lone 2 048-token chunk, the mixed step
        "windowed-latent-decode": windowed_latent("decode", 16, 16),
        "windowed-latent-chunk-S2048": windowed_latent("chunk", 2048, 1),
        "windowed-latent-mixed-S512": windowed_latent("ragged", 528, 17),
        # ... and its full layers' selection at 128 heads and 64 index heads
        "sparse-latent-dots3-mixed": (sparse_mixed, dots3_sparse(528, 17)),
        "sparse-latent-dots3-decode": (sparse_decode, dots3_sparse(16, 16)),
        # the agent cell's widest step: a chunk of 2 048 queries + 16 rows
        "sparse-latent-dots3-chunk": (sparse_mixed, dots3_sparse(2064, 17)),
        # the wide-chat cell (PR 39): 20 q / 4 kv heads, FIVE query heads a kv
        # head (every other cell has a power of two), 128 rows over tables of
        # 82 pages; a 512-token chunk beside them; and the recurrence's launch
        "decode-bf16-5-heads-a-group-wide-cell": decode(4, 20, 128, 82, 8192),
        "unified-5-heads-a-group-wide-cell": unified_cell(
            20, 4, 512 + 128, 129, 82, 8192),
        "ssm-state-update-rows128": ssm_update(128),
        # the long-answer cell (PR 41): 64 q / 8 kv heads without positions,
        # 128 rows over tables of 130 pages of a 12 288-page pool; a 512-token
        # chunk beside them; and the delta rule's launch
        "decode-bf16-64q-8kv-reason-cell": decode(8, 64, 128, 130, 12288),
        "unified-64q-8kv-reason-cell": unified_cell(
            64, 8, 512 + 128, 129, 130, 12288),
        "kda-state-update-rows128": kda_update(128),
        "lightning-state-update-rows32": lightning_update(32),
        "infllm-decode-32-rows": infllm("decode", 32, 32),
        "infllm-chunk-S512": infllm("chunk", 512, 1),
        "infllm-mixed-S512-32-rows": infllm("ragged", 544, 33),
        "eva-decode-24-rows": eva("decode", 24, 24),
        "eva-chunk-S512": eva("chunk", 512, 1),
        "eva-mixed-S512-24-rows": eva("ragged", 536, 25),
        # the contract-sessions cell (PR 49): 128 q / 8 kv heads, SIXTEEN
        # query heads a kv head, pages by layer kind. The full layer's rows
        # over tables of 2 112 pages of a 53 248-page pool (24 decode rows at
        # 33k keys; a 512-token chunk beside them), the sliding layers' over
        # their group's SHIFTED run of 290 pages of a 13 105-page pool (24
        # one-token rows at 4 096 keys under the window; the chunk beside
        # them), and the expert multiplication at 16 held experts of
        # [4 096, 4 096]: 192 sorted rows is a decode step (24 rows x top 8),
        # 4 288 a 512-token chunk beside them
        "decode-bf16-128q-8kv-contract-cell-full": decode(
            8, 128, 24, 2112, 53248),
        "unified-128q-8kv-contract-cell-windowed-decode": unified_cell(
            128, 8, 24, 24, 290, 13105, window=True),
        "unified-128q-8kv-contract-cell-full-mixed": unified_cell(
            128, 8, 512 + 24, 25, 2112, 53248),
        "unified-128q-8kv-contract-cell-windowed-mixed": unified_cell(
            128, 8, 512 + 24, 25, 290, 13105, window=True),
        # the sparse-expert cell's full layers (PR 50: 32 q / 4 kv heads, 16
        # KiB pages, 64 of them a chunk): 16 decode rows over tables of 448
        "decode-bf16-32q-4kv-moe-cell-full": decode(4, 32, 16, 448, 7168),
        "grouped-matmul-gate-up-16x4096-rows192": grouped(
            192, 4096, 4096, 2, experts=16),
        "grouped-matmul-down-16x4096-rows4288": grouped(
            4288, 4096, 4096, 1, experts=16),
        # the looped decoder's cell (PR 60): 16 q / 16 kv heads, ONE query
        # head a kv head (no other cell's group is 1), 64 KiB pages; 8 decode
        # rows over tables of 42 pages of a layer's four pools one behind
        # another (4 x 344 pages), and a 256-token chunk beside them
        "decode-bf16-16q-16kv-reason-steps-cell": decode(16, 16, 8, 42, 1376),
        "unified-16q-16kv-reason-steps-cell": unified_cell(
            16, 16, 256 + 8, 9, 42, 1376),
        "gather-blocks": moves(bc.gather_blocks, 1, False),
        "scatter-blocks": moves(bc.scatter_blocks, 1, True),
        "copy-blocks": moves(bc.copy_blocks, 2, False),
    }


CASES = _cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, chip_seam, name):
    """The kernel lowers through Mosaic for one v5e chip as a real custom
    call (not interpreted)."""
    fn, build = CASES[name]
    if getattr(fn, "asks_seam", False):
        fn = functools.partial(fn, chip_seam)
    args = build(SingleDeviceSharding(v5e[0]))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _copies_of(text: str, elements: int):
    """The ``copy`` / ``copy-start`` instructions of a compiled program whose
    result has ``elements`` elements (an array of a pool's size), as (name,
    layout)."""
    found = re.findall(
        r"^\s*(?:ROOT )?(\S+) = \(?\w+\[([\d,]+)\](\{[^}]*\})?.* copy(?:-start)?\(",
        text, re.M)
    return [(name, layout) for name, dims, layout in found
            if math.prod(map(int, dims.split(","))) == elements]


def _loop_bodies(llo: str, at_least: int = 1000):
    """Mosaic's last pass as text -> a Counter of ``llo.`` operations for each
    ``scf.for`` body of ``at_least`` lines (a chunk visit of a kernel that
    walks chunks; nested loops counted with the body that holds them)."""
    lines, open_loops, bodies = llo.split("\n"), [], []
    for i, line in enumerate(lines):
        text = line.lstrip()
        indent = len(line) - len(text)
        if text.startswith("scf.for"):
            open_loops.append((indent, i))
        elif text.startswith("}") and open_loops and open_loops[-1][0] == indent:
            _, start = open_loops.pop()
            if i - start >= at_least:
                bodies.append(collections.Counter(
                    op for l in lines[start:i]
                    for op in re.findall(r"llo\.([a-z_.0-9]+)", l)))
    return bodies


def test_a_latent_chunk_visit_unpacks_whole_registers(v5e, chip_seam, mosaic_dump):
    """ISSUE 47's tripwire, no chip needed. The decode launch at the
    document-QA cell's shapes, by Mosaic's own dump: a chunk visit's body (the
    two chunk loops, masked and not) reads its word-rows with sublane-strided
    loads and holds no sublane rotate and no select but the causal mask's
    (136 in the masked body), the second array's at one word-row a token
    (PR 55: its slot holds a token's first tile alone). Read as
    ``k_words[slot, :, w, :]`` the unpack
    came back one token a vector register: 4 480 rotates, 4 480 selects and
    45 000 vector instructions a visit, half of a decode row's time on the
    chip (PERF.md section 6, PR 47)."""
    fn, build = CASES["paged-latent-decode"]
    jax.jit(functools.partial(fn, chip_seam)).lower(
        *build(SingleDeviceSharding(v5e[0]))).compile()
    last = sorted(mosaic_dump.glob("*paged_latent_attention*finalize-llo*"))
    if not last:
        pytest.skip("this libtpu wrote no Mosaic dump (--xla_mosaic_dump_to)")
    llo = last[-1].read_text()
    visits = _loop_bodies(llo)
    assert len(visits) == 2, [sum(v.values()) for v in visits]
    for ops in visits:
        vector = sum(n for op, n in ops.items()
                     if op.startswith("v") and op != "vbitcast")
        assert ops["vmatmul"] and ops["vector_load_slane_stride"] == 384, ops
        assert ops["vrot.slane"] + ops["vselect"] <= 256, ops
        assert vector <= 8000, (vector, ops)
    # a visit's 384: 256 over the latent's two word-rows a token (every fourth
    # word-row: an even or an odd token's) and, since PR 55, 128 over the
    # second array's ONE (every second); until then all 384 took every fourth
    strides = collections.Counter(re.findall(
        r"vector_load_slane_stride .*sublane_stride = (\d+)", llo))
    assert strides == {"4": 2 * 256, "2": 2 * 128}, strides


@pytest.mark.parametrize("case", ["sparse-latent-decode", "sparse-latent-mixed"])
def test_a_selecting_attend_copies_no_array_of_the_pools_size(v5e, chip_seam, case):
    """ISSUE 48's tripwire. Until PR 48 the seam took the index keys as
    ``v_cache[tables, :, 1, :dim]``; row 1 of a token shares its 32-bit words
    with row 0 in the pool's tiling, so XLA transposed the WHOLE second array
    (117 440 512 elements, 235 MB in and out) in front of the gather, in
    every ``full`` layer of every step: 0.72 ms of a launch's 0.86 on the chip
    (PERF.md section 6, PR 48). The compiled attend holds no such copy."""
    fn, build = CASES[case]
    text = jax.jit(functools.partial(fn, chip_seam)).lower(
        *build(SingleDeviceSharding(v5e[0]))).compile().as_text()
    assert _copies_of(text, 14336 * 16 * 4 * 128) == []
    assert text.count("tpu_custom_call") >= 2        # the keys, then the attend


SELECTING_ATTENDS = ["sparse-latent-decode", "sparse-latent-mixed",
                     "sparse-latent-dots3-decode", "sparse-latent-dots3-mixed",
                     "sparse-latent-dots3-chunk"]


def _sorts_as_wide_as(text: str, width: int):
    """The ``sort`` instructions of a compiled program with an operand
    dimension of at least ``width``, as (name, dims)."""
    found = re.findall(
        r"^\s*(?:ROOT )?(\S+) = \(?\w+\[([\d,]+)\][^=]* sort\(", text, re.M)
    return [(name, dims) for name, dims in found
            if max(map(int, dims.split(","))) >= width]


@pytest.mark.parametrize("case", SELECTING_ATTENDS)
def test_a_selecting_attend_sorts_nothing_as_wide_as_its_context(v5e, chip_seam, case):
    """ISSUE 57's tripwire. Until PR 57 ``dsa_select`` was ``lax.top_k``,
    which the TPU's compiler lowers at this width to a full stable sort of
    every query's scores with their indices (48 us a row of 37 376, 30% of
    the agent cell's busy time: PERF.md section 6, PR 57). The selection
    counts and compacts now: the compiled attend holds no ``sort`` over an
    array as wide as the context."""
    fn, build = CASES[case]
    args = build(SingleDeviceSharding(v5e[0]))
    context = args[3].shape[1] * 16
    text = jax.jit(functools.partial(fn, chip_seam)).lower(*args).compile().as_text()
    assert _sorts_as_wide_as(text, context) == []
    assert text.count("tpu_custom_call") >= 2        # the keys, then the attend


def _loop_carried(text: str, dtype: str = "f32"):
    """The arrays of ``dtype`` that the ``while`` loops of a compiled program
    carry, as (dims, on chip): ``S(1)`` in a layout is the compiler's on-chip
    memory space, no ``S`` is HBM."""
    found = []
    for carry in re.findall(r"^\s*\S+ = \((.*)\) while\(", text, re.M):
        for dims, layout in re.findall(dtype + r"\[([\d,]+)\]\{([^}]*)\}", carry):
            found.append((tuple(map(int, dims.split(","))), "S(1)" in layout))
    return found


def test_a_chunk_of_2048_queries_carries_its_index_sum_a_slab_on_chip(v5e, chip_seam):
    """ISSUE 59's tripwire. Until PR 59 the head scan of ``dsa_index_scores``
    carried the float32 sum of ALL of a chunk's queries: at the agent cell's
    widest step (2 048 + 16 rows behind 37 376 keys) ``f32[2048,37376]``, which
    the compiler leaves in HBM, so a head read and wrote 306 MB each way (39 GB
    a call, two calls a step: PERF.md section 6, PR 59). The compiled attend
    now carries a SLAB's sum through the heads, on chip, counts the slab's
    ordered keys there too (``dsa_select`` inside the slab loop), and holds
    no array of the whole chunk's scores at all."""
    rows = att.index_slab_rows(2048, 37376)
    assert rows < 2048
    fn, build = CASES["sparse-latent-dots3-chunk"]
    text = jax.jit(functools.partial(fn, chip_seam)).lower(
        *build(SingleDeviceSharding(v5e[0]))).compile().as_text()
    for scores in ("2048,37376", "2064,37376", "37376,2048", "37376,2064",
                   f"{2048 // rows},{rows},37376"):
        assert f"[{scores}]" not in text, scores
    sums, ordered_keys = _loop_carried(text), _loop_carried(text, "u32")
    assert ((rows, 37376), True) in sums and ((rows, 37376), False) not in sums, sums
    assert ((37376, rows), True) in ordered_keys, ordered_keys


def test_the_reader_of_carries_finds_the_whole_chunks_sum_in_hbm(v5e, monkeypatch):
    """What the tripwire above looks for is there to be found: the scores of
    2 048 queries as ONE slab compile for a v5e to a head scan whose carry,
    ``f32[2048,37376]``, is not on chip; GLM's mixed step (512 queries behind
    25 600 keys, one slab by the constant as it stands) carries its sum there."""
    sh = SingleDeviceSharding(v5e[0])

    def carried(Q, n, T):
        args = (jax.ShapeDtypeStruct((Q, n, 128), BF, sharding=sh),
                jax.ShapeDtypeStruct((Q, n), F32, sharding=sh),
                jax.ShapeDtypeStruct((T, 128), BF, sharding=sh))
        fn = lambda *a: att.dsa_index_scores(*a)         # noqa: E731  a trace of its own
        return _loop_carried(jax.jit(fn).lower(*args).compile().as_text())

    assert att.index_slab_rows(512, 25600) == 512
    assert ((512, 25600), True) in carried(512, 32, 25600)
    monkeypatch.setattr(att, "INDEX_SLAB_BYTES", 2048 * 37376 * 4)
    assert ((2048, 37376), False) in carried(2048, 64, 37376)


def test_the_reader_of_sorts_finds_the_one_lax_top_k_compiles_to(v5e):
    """What the tripwire above looks for is there to be found: ``lax.top_k``
    of 2 048 of a decode launch's 25 600 scores compiles for a v5e to a
    ``sort`` as wide as the context."""
    scores = jax.ShapeDtypeStruct((8, 25600), F32, sharding=SingleDeviceSharding(v5e[0]))
    text = jax.jit(lambda x: jax.lax.top_k(x, 2048)[1]).lower(scores).compile().as_text()
    assert _sorts_as_wide_as(text, 25600)


def test_the_index_keys_unpack_moves_whole_registers(v5e, mosaic_dump):
    """The launch's program body by Mosaic's own dump, at the cell's decode
    shapes: a chunk of 1 024 tokens comes in as sublane-strided loads of
    whole registers (128: the even and the odd tokens of 64 registers of
    keys) and no sublane rotate or select a token; read as ``buf[slot, :, 0,
    :]`` it would come back one token a register (PR 47)."""
    fn, build = CASES["index-keys-decode"]
    # a function jit has not seen: a cached executable writes no dump
    jax.jit(lambda *a: fn(*a)).lower(*build(SingleDeviceSharding(v5e[0]))).compile()
    last = sorted(mosaic_dump.glob("*paged_index_keys*finalize-llo*"))
    if not last:
        pytest.skip("this libtpu wrote no Mosaic dump (--xla_mosaic_dump_to)")
    ops = collections.Counter(re.findall(r"llo\.([a-z_.0-9]+)", last[-1].read_text()))
    vector = sum(n for op, n in ops.items() if op.startswith("v") and op != "vbitcast")
    assert ops["vector_load_slane_stride"] == 128, ops
    assert ops["vrot.slane"] + ops["vselect"] <= 16, ops
    assert vector <= 1500, (vector, ops)


@pytest.mark.parametrize("h,kvh,rows,mb,nb", [
    (H, KVH, B, MB, NB),
    (128, 8, 24, 2112, 53248),       # the contract cell's full layer
    (32, 4, 16, 448, 7168),          # the sparse-expert cell's full layers
], ids=["qwen3", "contract-cell", "moe-cell"])
def test_sharded_decode_compiles_on_tp4_mesh(v5e, h, kvh, rows, mb, nb):
    """The decode question on a tp=4 mesh of the described devices: q on
    heads, pages on kv heads (2, 2 and 1 a shard), one custom call per device
    and no collective (attention is head-wise independent; each shard
    computes the tables' run flags for itself)."""
    from dynamo_tpu.parallel.mesh import AXIS_TP, make_mesh

    mesh = make_mesh(tp=4, devices=v5e)

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    cache = s((nb, BS, kvh, D), BF, P(None, None, AXIS_TP, None))
    args = (
        s((rows, h, D), BF, P(None, AXIS_TP, None)), cache, cache,
        s((rows, mb), I32, P()), s((rows,), I32, P()),
    )
    text = jax.jit(PagedAttention(mesh, True).decode).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce(" not in text and "all-gather(" not in text


def _dma_sites(llo: str):
    """Mosaic's last pass as text -> its DMA operations in order, as (op, the
    innermost construct that holds it, how many it holds)."""
    stack, sites = [], []
    for line in llo.split("\n"):
        text = line.lstrip()
        indent = len(line) - len(text)
        if text.startswith("}") and not text.startswith("} else"):
            while stack and stack[-1][0] >= indent:
                stack.pop()
        opened = re.match(r"(?:%[^=]*= )?(scf\.(?:for|if|while))", text)
        if opened:
            stack.append((indent, opened.group(1), len(stack) and id(line)))
        for op in re.findall(r"llo\.(enqueue_dma|dma_done)", text):
            key = (op, stack[-1][1:] if stack else None)
            if sites and sites[-1][0] == key:
                sites[-1][1] += 1
            else:
                sites.append([key, 1])
    return [(op, held[0] if held else None, n) for (op, held), n in sites]


def _page_reader_sites():
    """What ``_dma_sites`` finds in a kernel that reads its chunks by
    ``pallas_paged.PageReader`` with runs: each of its three places that start
    a chunk holds a branch of TWO descriptors (a whole chunk that is a run:
    one an array), a loop of ``UNROLL`` pages' (a whole chunk that is none)
    and a loop of one page's (a tail); each of its two places that wait holds
    two waits for a whole chunk, however it was started, and a loop of one
    page's."""
    from dynamo_tpu.ops import pallas_paged as paged

    start = [("enqueue_dma", "scf.if", 2),
             ("enqueue_dma", "scf.for", 2 * paged.UNROLL),
             ("enqueue_dma", "scf.for", 2)]
    wait = [("dma_done", "scf.if", 2), ("dma_done", "scf.for", 2)]
    return start + start + wait + start + wait


@pytest.mark.parametrize("case", [
    "decode-bf16-128q-8kv-contract-cell-full", "decode-bf16-32q-4kv-moe-cell-full"])
def test_a_run_chunk_of_the_decode_kernel_starts_one_dma_an_array(
        v5e, chip_seam, mosaic_dump, case):
    """ISSUE 50's tripwire, no chip needed. By Mosaic's own dump of the
    decode launch at the contract cell's and the sparse-expert cell's
    shapes: each of its three places that start a chunk holds a branch of
    TWO descriptors (a whole chunk that is a run: K and V, one each), a loop
    of ``UNROLL`` pages' (a whole chunk that is none) and a loop of one
    page's (a tail); each of its two places that wait holds two waits for a
    whole chunk, however it was started, and a loop of one page's. Until
    PR 50 a chunk of 32 or 64 pages was 64 or 128 starts and as many waits,
    one after the other in the products' instruction stream."""
    fn, build = CASES[case]
    jax.jit(functools.partial(fn, chip_seam)).lower(
        *build(SingleDeviceSharding(v5e[0]))).compile()
    last = sorted(mosaic_dump.glob("*paged_decode_attention*finalize-llo*"))
    if not last:
        pytest.skip("this libtpu wrote no Mosaic dump (--xla_mosaic_dump_to)")
    assert _dma_sites(last[-1].read_text()) == _page_reader_sites()


def test_a_run_chunk_of_the_latent_kernel_starts_one_dma_an_array_the_second_strided(
        v5e, chip_seam, mosaic_dump):
    """ISSUE 55's tripwire, no chip needed. By Mosaic's own dump of the
    latent decode launch at the document-QA cell's shapes (a chunk of 64
    pages, 1 024 tokens): its places that start and wait for a chunk are
    ``PageReader``'s, as the decode kernel's (a whole chunk that is a run:
    ONE descriptor an array); every descriptor of the second array
    reads the FIRST tile of its tokens, ``[tokens, 1 of rows / 2, 2, 128]``,
    a run's over a source of ``cp * bs`` tokens, into a slot buffer of
    ``[2, T, 2, 128]``: 512 of a token's 1 024 bytes. Until PR 55 it copied
    the whole token into ``[2, T, 4, 128]``."""
    fn, build = CASES["paged-latent-decode"]
    # a function jit has not seen: a cached executable writes no dump
    jax.jit(lambda *a: fn(chip_seam, *a)).lower(
        *build(SingleDeviceSharding(v5e[0]))).compile()
    last = sorted(mosaic_dump.glob("*paged_latent_attention*finalize-llo*"))
    first = sorted(mosaic_dump.glob("*paged_latent_attention*original*"))
    if not last or not first:
        pytest.skip("this libtpu wrote no Mosaic dump (--xla_mosaic_dump_to)")
    assert _dma_sites(last[-1].read_text()) == _page_reader_sites()
    module = first[-1].read_text()
    T, tokens = 64 * BS, 14336 * BS
    pool = f"memref<{tokens}x2x2x128xbf16, #tpu.memory_space<any>>"
    assert f"memref<2x{T}x2x128xbf16, #tpu.memory_space<vmem>>" in module
    assert f"memref<2x{T}x4x128xbf16, #tpu.memory_space<vmem>>" in module  # the latent's
    # every slice of the second array: a first tile, of a run's tokens or a page's
    tiles = set(re.findall(
        re.escape(pool) + r" -> memref<(\d+)x(\d+)x2x128xbf16", module))
    assert tiles == {(str(T), "1"), (str(BS), "1")}, tiles
    sources = re.findall(r"tpu\.enqueue_dma source\(%\w+ : memref<([\dx]+)xbf16", module)
    assert set(sources) == {
        f"{T}x4x128", f"{T}x2x128", f"{BS}x4x128", f"{BS}x2x128"}, set(sources)
    assert sources.count(f"{T}x2x128") == sources.count(f"{T}x4x128") == 3


# sha256 of what these launches lowered to at the PARENT of PR 50 (commit
# 786917a, this installation's JAX, the described v5e): the StableHLO around
# the custom call and the Mosaic module in it, printed without debug
# locations (the serialised module carries file names and line numbers). A
# PR that changes one of these kernels on purpose re-records its hash (the
# three ``paged-latent-*`` are what PR 55's launch lowers to).
PARENT_KERNEL_TEXTS = json.loads(open(os.path.join(
    os.path.dirname(__file__), "data", "kernel_texts_pr49.json")).read())


def _lowered_text_hash(text: str) -> str:
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    body = r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)'
    parts = [re.sub(body, r"\1", text)]
    for _, payload in re.findall(body, text):
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(payload))
            parts.append(module.operation.get_asm(enable_debug_info=False))
    assert len(parts) > 1, "no Mosaic module in the lowered text"
    return hashlib.sha256("\n".join(parts).encode()).hexdigest()


def _lowered_hash_of(v5e, chip_seam, case: str) -> str:
    fn, build = CASES[case]
    if getattr(fn, "asks_seam", False):
        fn = functools.partial(fn, chip_seam)
    text = jax.jit(fn).lower(*build(SingleDeviceSharding(v5e[0]))).as_text()
    return _lowered_text_hash(text)


@pytest.mark.parametrize("case", sorted(PARENT_KERNEL_TEXTS))
def test_the_ragged_and_the_latent_launch_lower_to_the_parents_text(
        v5e, chip_seam, case):
    """PR 50 moved the run rule of ``_LatentPages`` into ``PageReader`` and
    gave it to the decode kernel ALONE: the ragged launch (plain, windowed,
    with sinks and a softcap, at the sparse-expert and the contract cell's
    shapes) still starts and waits page by page: it lowers to the text the
    parent lowered. The three latent launches' hashes are PR 55's (the second
    array's first tile alone is copied): re-recorded on purpose, every other
    entry PR 49's."""
    assert _lowered_hash_of(v5e, chip_seam, case) == PARENT_KERNEL_TEXTS[case]


# ... and of every other launch of this table at the PARENT of PR 57 (commit
# 25a2f4e), the four selecting attends apart (``SELECTING_ATTENDS``: the
# selection in front of their kernel is what PR 57 rewrote).
PARENT_LAUNCH_TEXTS = json.loads(open(os.path.join(
    os.path.dirname(__file__), "data", "launch_texts_pr56.json")).read())


# launches added to the table since those texts were recorded: no parent
# lowered them
SINCE_THE_PARENTS_TEXTS = {
    "decode-bf16-16q-16kv-reason-steps-cell", "unified-16q-16kv-reason-steps-cell",
}


def test_every_launch_is_pinned_but_the_selecting_attends():
    pinned = set(PARENT_KERNEL_TEXTS) | set(PARENT_LAUNCH_TEXTS)
    assert set(CASES) - pinned == set(SELECTING_ATTENDS) | SINCE_THE_PARENTS_TEXTS


@pytest.mark.parametrize("case", sorted(PARENT_LAUNCH_TEXTS))
def test_a_launch_without_a_selection_lowers_to_the_parents_text(
        v5e, chip_seam, case):
    """PR 57 rewrote ``dsa_select`` and nothing else: every launch of this
    file that selects nothing (the families without an indexer, and the
    indexer's own kernels taken alone) lowers to the text the parent lowered.
    A PR that changes one on purpose re-records its hash (run
    ``_lowered_text_hash`` in a checkout of its parent)."""
    assert _lowered_hash_of(v5e, chip_seam, case) == PARENT_LAUNCH_TEXTS[case]


# ... and of the selecting attends whose chunk is ONE slab of
# ``dsa_index_scores`` at the PARENT of PR 59 (commit 5a2699e): PR 59 scores
# and selects a slab at a time the chunks whose float32 sum is over
# ``INDEX_SLAB_BYTES`` and changes nothing else, so GLM's mixed step (512
# queries behind 25 600 keys, 52.4 MB) and the decode rows of both models keep
# the program they had. dots3-note's two buckets slab (``-dots3-mixed``: 512
# queries behind 37 376 keys are two slabs of 256; ``-dots3-chunk``: eight).
PARENT_SELECTING_TEXTS = json.loads(open(os.path.join(
    os.path.dirname(__file__), "data", "selecting_texts_pr58.json")).read())


def test_every_selecting_attend_is_pinned_but_the_two_that_slab():
    assert set(SELECTING_ATTENDS) - set(PARENT_SELECTING_TEXTS) == {
        "sparse-latent-dots3-mixed", "sparse-latent-dots3-chunk"}


@pytest.mark.parametrize("case", sorted(PARENT_SELECTING_TEXTS))
def test_a_selecting_attend_of_one_slab_lowers_to_the_parents_text(
        v5e, chip_seam, case):
    assert _lowered_hash_of(v5e, chip_seam, case) == PARENT_SELECTING_TEXTS[case]


def test_sharded_unified_compiles_on_tp4_mesh(v5e):
    """The shard_map'd ragged kernel on a tp=4 mesh at Mistral's widths (8
    q / 2 kv heads a shard, 8 KiB pages): a mixed launch of 512 + 8 rows
    compiles to one custom call a device and no collective."""
    from dynamo_tpu.parallel.mesh import AXIS_TP, make_mesh

    mesh = make_mesh(tp=4, devices=v5e)

    def s(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    cache = s((8192, BS, 8, D), BF, P(None, None, AXIS_TP, None))
    rows = s((9,), I32)
    args = (
        s((520, 32, D), BF, P(None, AXIS_TP, None)), cache, cache,
        s((9, 192), I32), rows, rows, rows,
    )
    fn = functools.partial(pun.sharded_ragged_paged_attention, mesh, AXIS_TP)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce(" not in text and "all-gather(" not in text


# One layer's ``attend`` of ``mixed_step`` (engine/engine.py): the chunk's
# whole pages and the decode rows' tokens written into a donated pool, then
# the one launch that reads it. name -> (q heads, rows a token, decode rows,
# chunk tokens, pages a table, pages in the pool, latent): the wide-chat
# cell's Falcon-H1 (20 q / 4 kv heads, 128 rows), the sparse-expert cell's
# Mellum2 (32 / 4), the long-cache cell's InternLM2 (8 kv heads: a sublane
# tile of its own), the document-QA cell's latent held as 4 rows of 128 lanes
MIXED_ATTENDS = {
    "kvh4-h20-wide-cell": (20, 4, 128, 128, 82, 8192, False),
    "kvh4-h32-moe-cell": (32, 4, 16, 512, 448, 7168, False),
    "kvh8-h16-longcache-cell": (16, 8, 12, 512, 544, 6400, False),
    "latent-rows-docqa-cell": (64, 4, 8, 512, 1600, 14336, True),
}


def _pool_copies(seam, write_chunk, case):
    """The ``copy`` / ``copy-start`` instructions of the compiled attend
    whose result has as many elements as one array of the pool."""
    h, kvh, rows, S, mb, pages, latent = MIXED_ATTENDS[case]
    kw = {"latent": att.LatentQuery(scale=0.13086)} if latent else {}

    def attend(kc, vc, q, k_new, v_new, c_blocks, wb, wo, tables, q_lens, lens):
        kc, vc = write_chunk(kc, vc, k_new[:S], v_new[:S], c_blocks)
        kc, vc = att.write_decode_kv(kc, vc, k_new[S:], v_new[S:], wb, wo)
        q_starts = jnp.concatenate(
            [jnp.zeros((1,), I32), S + jnp.arange(rows, dtype=I32)])
        return kc, vc, seam.ragged(q, kc, vc, tables, q_starts, q_lens, lens, **kw)

    s, *_ = _shapes(SingleDeviceSharding(seam.mesh.devices.flat[0]))
    pool, new, r = s((pages, BS, kvh, D), BF), s((S + rows, kvh, D), BF), s((rows + 1,), I32)
    q = s((S + rows, 64, 640) if latent else (S + rows, h, D), BF)
    text = jax.jit(attend, donate_argnums=(0, 1)).lower(
        pool, pool, q, new, new, s((S // BS,), I32), s((rows,), I32),
        s((rows,), I32), s((rows + 1, mb), I32), r, r,
    ).compile().as_text()
    assert "tpu_custom_call" in text
    return _copies_of(text, pages * BS * kvh * D)


@pytest.mark.parametrize("case", sorted(MIXED_ATTENDS))
def test_mixed_attend_copies_no_array_of_the_pools_size(chip_seam, case):
    """With the chunk's pages written as the seam writes them
    (``write_chunk``: on the kernel's own view of the pool) nothing between
    the two cache writes and the launch re-tiles the pool: the compiled
    attend holds no ``copy`` of an array of the pool's size. On the chip
    each was 0.36 ms a 134 MB array, four a layer (PERF.md section 6, PR
    40)."""
    assert _pool_copies(chip_seam, chip_seam.write_chunk, case) == []


def test_eva_mixed_attend_copies_no_array_of_the_pools_size(chip_seam):
    """One layer's attend of a mixed step of the ring family (PR 46) at
    EvaByte's widths: the chunk's pages and the rows' tokens written, the
    chunk's summaries and the summaries of the pages the rows filled written
    (a gather of those pages from the pool between the writes), then the
    ragged launch over the rows as paged sequences. No ``copy`` of an array
    of the pool's size (1 GB an array here)."""
    B, S, h, pages = 24, 512, 32, 3073 + 121 * 8
    seam = PagedAttention(chip_seam.mesh, True, summary_base=3073)

    def attend(kc, vc, q, k_new, v_new, c_blocks, wb, wo, tables, q_lens, lens,
               start, total, mu, phi):
        e = att.EvaQuery(mu, phi, 2048, 16)
        kc, vc = seam.write_chunk(kc, vc, k_new[:S], v_new[:S], c_blocks)
        kc, vc = att.write_decode_kv(kc, vc, k_new[S:], v_new[S:], wb, wo)
        kc, vc = seam.summarise_chunk(kc, vc, k_new[:S], v_new[:S], tables[0], start, total, e)
        kc, vc = seam.summarise_rows(kc, vc, tables[1:], lens[1:], wb, wo, e)
        q_starts = jnp.concatenate([jnp.zeros((1,), I32), S + jnp.arange(B, dtype=I32)])
        return kc, vc, seam.ragged(q, kc, vc, tables, q_starts, q_lens, lens, eva=e)

    s, *_ = _shapes(SingleDeviceSharding(seam.mesh.devices.flat[0]))
    pool, new = s((pages, BS, h, D), BF), s((S + B, h, D), BF)
    r, r1, vec = s((B,), I32), s((B + 1,), I32), s((h, D), BF)
    text = jax.jit(attend, donate_argnums=(0, 1)).lower(
        pool, pool, new, new, new, s((S // BS,), I32), r, r, s((B + 1, 133), I32),
        r1, r1, s((), I32), s((), I32), vec, vec,
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, pages * BS * h * D) == []


@pytest.mark.parametrize("case,relaid", [
    ("kvh4-h20-wide-cell", True), ("kvh4-h32-moe-cell", True),
    ("latent-rows-docqa-cell", True), ("kvh8-h16-longcache-cell", False)])
def test_the_four_dimensional_chunk_write_is_what_relays_the_pool(
        chip_seam, case, relaid):
    """The control: the same attend with the chunk's pages scattered into
    the 4-D pool (``write_prefill_kv`` as every other program calls it) has
    each of the two arrays copied to the scatter's tiling and back where a
    token is 4 rows of 128 lanes, and none at 8 kv heads: the test above
    sees what the chip's trace saw. When a compiler stops doing that at 4
    rows, this fails: ``write_chunk`` can write the 4-D pool again."""
    copies = _pool_copies(chip_seam, att.write_prefill_kv, case)
    assert (len(copies) >= 4) == relaid, copies


@pytest.mark.parametrize("kernel", ["decode", "unified", "gather-scales"])
def test_int8_scale_rows_are_refused_by_mosaic(v5e, chip_seam, kernel):
    """Why the engine refuses kv_dtype=int8 with the Pallas kernels on the
    TPU backend (engine construction; tests/test_kv_quant.py): the [kv_heads]
    f32 scale-row DMA is not aligned to the 128-lane tiling. When a layout
    change makes these compile, this test fails — lift the refusal then."""
    s, cache, unified, _, ids = _shapes(SingleDeviceSharding(v5e[0]))
    qcache = QuantizedKV(
        jax.ShapeDtypeStruct(cache.shape, jnp.int8, sharding=cache.sharding),
        s((NB, KVH), F32),
    )
    if kernel == "decode":
        fn = chip_seam.decode
        args = (s((B, H, D), BF), qcache, qcache, s((B, MB), I32),
                s((B,), I32))
    elif kernel == "unified":
        fn = pun.ragged_paged_attention
        args = (unified[0], qcache, qcache) + unified[3:]
    else:
        fn, args = bc.gather_blocks, (qcache.scale, ids)
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(fn).lower(*args).compile()
