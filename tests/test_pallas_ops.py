"""Pallas kernels vs pure-JAX references, run in interpreter mode on CPU.

Mirrors the reference's strategy of unit-testing its CUDA block-copy kernel
and delegated attention kernels behaviorally; here the same kernels that run
compiled on TPU execute under the Pallas interpreter so CI needs no chips.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import block_copy as bc
from dynamo_tpu.ops import pallas_attention as pa
from dynamo_tpu.ops import pallas_paged as paged


def _make_paged_case(rng, B, h, kvh, d, bs, num_blocks, max_blocks, dtype):
    q = jnp.asarray(rng.standard_normal((B, h, d)), dtype)
    k_cache = jnp.asarray(rng.standard_normal((num_blocks, bs, kvh, d)), dtype)
    v_cache = jnp.asarray(rng.standard_normal((num_blocks, bs, kvh, d)), dtype)
    # ragged lengths; each sequence gets distinct pages (block 0 is scratch)
    seq_lens = rng.integers(1, max_blocks * bs, size=B).astype(np.int32)
    tables = np.zeros((B, max_blocks), np.int32)
    free = list(range(1, num_blocks))
    for b in range(B):
        n = -(-int(seq_lens[b]) // bs)
        for j in range(n):
            tables[b, j] = free.pop()
    return q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(seq_lens)


@pytest.mark.parametrize(
    "B,h,kvh,d,bs", [(4, 8, 4, 32, 16), (2, 8, 8, 64, 8), (3, 4, 1, 32, 16)]
)
def test_pallas_decode_matches_reference(B, h, kvh, d, bs):
    rng = np.random.default_rng(0)
    q, kc, vc, tables, lens = _make_paged_case(
        rng, B, h, kvh, d, bs, num_blocks=64, max_blocks=6, dtype=jnp.float32
    )
    ref = att.paged_decode_attention(q, kc, vc, tables, lens)
    got = pa.paged_decode_attention(
        q, kc, vc, tables, lens, chunk_tokens=32, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_pallas_decode_single_token_context():
    """seq_len=1 (first decode step after a 0-token... minimal context)."""
    rng = np.random.default_rng(1)
    q, kc, vc, tables, lens = _make_paged_case(
        rng, 2, 4, 2, 16, 8, num_blocks=16, max_blocks=3, dtype=jnp.float32
    )
    lens = jnp.asarray([1, 2], jnp.int32)
    ref = att.paged_decode_attention(q, kc, vc, tables, lens)
    got = pa.paged_decode_attention(
        q, kc, vc, tables, lens, chunk_tokens=16, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_pallas_decode_chunk_larger_than_context():
    """One chunk covers everything (no multi-chunk accumulation)."""
    rng = np.random.default_rng(2)
    q, kc, vc, tables, lens = _make_paged_case(
        rng, 2, 8, 4, 32, 16, num_blocks=32, max_blocks=4, dtype=jnp.float32
    )
    ref = att.paged_decode_attention(q, kc, vc, tables, lens)
    got = pa.paged_decode_attention(
        q, kc, vc, tables, lens, chunk_tokens=4 * 16, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def _poisoned_case(rng, lens, h, kvh, d, bs, dtype, int8):
    """A paged case whose every token slot OUTSIDE a row's context would show
    in the output if the kernel read it: the clean caches (zeros there) go to
    the reference, the poisoned ones (NaN values under keys aligned with the
    query; NaN scales on the unused pages for int8) to the kernel. Page 0 is
    what the padding of a block table points at."""
    B = len(lens)
    pages = [-(-n // bs) for n in lens]
    mb = max(max(pages), 1)
    nb = sum(pages) + 2
    q = rng.standard_normal((B, h, d)).astype(np.float32)
    k = rng.standard_normal((nb, bs, kvh, d)).astype(np.float32)
    v = rng.standard_normal((nb, bs, kvh, d)).astype(np.float32)
    inside = np.zeros((nb, bs), bool)
    tables = np.zeros((B, mb), np.int32)
    free = list(range(1, nb))
    for b, n in enumerate(lens):
        for j in range(pages[b]):
            tables[b, j] = page = free.pop()
            inside[page, : min(bs, n - j * bs)] = True
        if n:
            # the LAST token of a context decides the output, so a kernel
            # that drops it is off by far more than the tolerance
            page, slot = tables[b, pages[b] - 1], (n - 1) % bs
            k[page, slot] = 4.0 * q[b].reshape(kvh, h // kvh, d).mean(1)
    k[~inside] = 0.0
    v[~inside] = 0.0
    args = jnp.asarray(q, dtype), jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    if int8:
        from dynamo_tpu.ops import quant

        kq, ks = quant.quantize_blocks(jnp.asarray(k))
        vq, vs = quant.quantize_blocks(jnp.asarray(v))
        unused = jnp.asarray(~inside.any(axis=1))[:, None]
        clean = quant.QuantizedKV(kq, ks), quant.QuantizedKV(vq, vs)
        dirty = (quant.QuantizedKV(kq, jnp.where(unused, jnp.nan, ks)),
                 quant.QuantizedKV(vq, jnp.where(unused, jnp.nan, vs)))
        return args, clean, dirty
    kd, vd = k.copy(), v.copy()
    kd[~inside] = 50.0
    vd[~inside] = np.nan
    as_dtype = lambda *xs: tuple(jnp.asarray(x, dtype) for x in xs)
    return args, as_dtype(k, v), as_dtype(kd, vd)


# lens, h, kvh, d, bs, dtype, chunk_tokens (None: the derived size), int8
DECODE_CASES = {
    # the long-cache cell's shapes (BENCHMARK.json): one token short of the
    # full 544 pages, and all of them; 34 chunks of 256 tokens in f32, 17 of
    # 512 in bf16
    "cell-8703-8704-f32": ([8703, 8704], 16, 8, 128, 16, jnp.float32, None, False),
    "cell-8703-8704-bf16": ([8703, 8704], 16, 8, 128, 16, jnp.bfloat16, None, False),
    # padding rows first, in the middle and last; one token; exactly one
    # chunk, one chunk + 1, a multiple of the chunk (64 tokens a chunk)
    "ragged-with-empty-rows": (
        [0, 1, 64, 0, 0, 65, 128, 192, 17, 0], 16, 8, 32, 16, jnp.float32, 64, False),
    "ragged-with-empty-rows-bf16": (
        [0, 1, 64, 0, 0, 65, 128, 192, 17, 0], 16, 8, 32, 16, jnp.bfloat16, 64, False),
    "all-rows-empty": ([0, 0, 0], 8, 4, 32, 16, jnp.float32, 64, False),
    # a tp=4 shard of Mistral-7B: 2 kv heads, 4 query heads each
    "kvh2-g4-tp4-shard": (
        [0, 1, 64, 65, 130, 0, 300], 8, 2, 32, 16, jnp.float32, 64, False),
    "kvh1-g-is-h": ([5, 0, 64, 65, 129], 4, 1, 32, 16, jnp.float32, 64, False),
    "derived-chunk-one-row-many-chunks": (
        [1500, 0, 3], 8, 4, 128, 16, jnp.float32, None, False),
    "int8-ragged-with-empty-rows": (
        [0, 1, 64, 0, 0, 65, 128, 192, 17, 0], 16, 8, 32, 16, jnp.float32, 64, True),
    "int8-kvh2-g4": (
        [0, 1, 64, 65, 130, 0, 300], 8, 2, 32, 16, jnp.float32, 64, True),
}


@pytest.mark.parametrize("name", sorted(DECODE_CASES))
def test_pallas_decode_reads_exactly_the_context(name):
    """Every row attends to exactly its seq_len tokens (interpret mode,
    against the pure-JAX twin at `highest` precision): the shapes the chip
    benchmark runs and its `correct` check, over 200-600 token contexts,
    does not. An empty row reads nothing and returns zeros."""
    lens, h, kvh, d, bs, dtype, chunk_tokens, int8 = DECODE_CASES[name]
    (q, tables, seq_lens), clean, dirty = _poisoned_case(
        np.random.default_rng(25), lens, h, kvh, d, bs, dtype, int8
    )
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(
            att.paged_decode_attention(q, *clean, tables, seq_lens), np.float32)
    got = np.asarray(
        pa.paged_decode_attention(
            q, *dirty, tables, seq_lens, chunk_tokens=chunk_tokens,
            interpret=True),
        np.float32,
    )
    live = np.asarray(lens) > 0
    assert not got[~live].any()
    tol = 2e-5 if dtype == jnp.float32 else 2.0 ** -7  # bf16: the output's ulp
    np.testing.assert_allclose(got[live], ref[live], atol=tol, rtol=tol)


def _run_tables(how, rows, cp, chunks, rng):
    """``rows`` tables of ``chunks`` whole chunks of ``cp`` pages (+ a tail
    chunk's room): every chunk consecutive ids (``runs``), none (``shuffled``)
    or every second one (``mixed``). The same CONTENT is at the same table
    place in all three: ``place[i]`` is where ``runs``' page ``i`` lives."""
    mb = (chunks + 1) * cp
    runs = 1 + np.arange(rows * mb).reshape(rows, mb)
    nb = rows * mb + 1
    if how == "runs":
        return runs, np.arange(nb)
    while True:
        place = np.arange(nb)
        moved = rng.permutation(np.arange(1, nb))
        if how == "mixed":
            # a chunk in two stays where it is; the others trade places
            # among themselves
            stays = (((np.arange(1, nb) - 1) % mb) // cp) % 2 == 0
            moved = np.arange(1, nb)
            moved[~stays] = rng.permutation(moved[~stays])
        place[1:] = moved
        got = np.asarray(paged.chunk_runs(jnp.asarray(place[runs]), cp))
        want = np.zeros_like(got) if how == "shuffled" else np.tile(
            np.arange(chunks + 1) % 2 == 0, (rows, 1))
        if (got == want).all():
            return place[runs], place


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("chunk_pages", [2, 8])
def test_pallas_decode_reads_a_run_of_pages_as_one_copy_bitwise(chunk_pages, int8):
    """A whole chunk whose table entries are consecutive block ids is ONE
    descriptor an array; any other whole chunk goes page by page; both are
    waited for once an array (ops/pallas_paged.PageReader). The same pages
    behind tables that are (a) all runs, (b) all shuffled, (c) every second
    chunk a run give BITWISE the same output, and the twin's within today's
    tolerance: rows of whole chunks + a tail chunk, of whole chunks exactly
    (the row's last chunk, masked, is then a run), a tail alone, an empty
    row, one token."""
    from dynamo_tpu.ops import quant

    cp, bs, kvh, h, d, chunks = chunk_pages, 16, 4, 8, 32, 3
    T = cp * bs
    lens = [3 * T + 5, 3 * T, 0, T - 3, 2 * T + bs, 1]
    rng = np.random.default_rng(50 + cp)
    nb = len(lens) * (chunks + 1) * cp + 1
    q = jnp.asarray(rng.standard_normal((len(lens), h, d)), jnp.bfloat16)
    k, v = (rng.standard_normal((nb, bs, kvh, d)).astype(np.float32)
            for _ in range(2))
    seq = jnp.asarray(lens, jnp.int32)

    def launch(how):
        tables, place = _run_tables(how, len(lens), cp, chunks, rng)
        back = np.argsort(place)
        pools = [jnp.asarray(x[back], jnp.bfloat16) for x in (k, v)]
        if int8:
            pools = [quant.QuantizedKV(*quant.quantize_blocks(
                x.astype(jnp.float32))) for x in pools]
        tables = jnp.asarray(tables, jnp.int32)
        got = pa.paged_decode_attention(
            q, *pools, tables, seq, chunk_tokens=T, interpret=True)
        with jax.default_matmul_precision("highest"):
            twin = att.paged_decode_attention(q, *pools, tables, seq)
        return np.asarray(got, np.float32), np.asarray(twin, np.float32)

    runs, twin = launch("runs")
    live = np.asarray(lens) > 0
    assert not runs[~live].any()
    np.testing.assert_allclose(runs[live], twin[live], atol=2.0 ** -7, rtol=2.0 ** -7)
    for how in ("shuffled", "mixed"):
        got, _ = launch(how)
        assert (got == runs).all(), how


def test_pallas_decode_reads_runs_the_same_under_tp2():
    """Each shard of a tp=2 mesh computes the tables' run flags for itself
    (the tables are replicated) and reads its own kv heads' pages by them:
    the one-device launch's answer."""
    from dynamo_tpu.parallel.mesh import AXIS_TP, make_mesh

    cp, bs, kvh, h, d = 2, 16, 4, 8, 32
    T = cp * bs
    lens = jnp.asarray([3 * T + 5, 0, T - 3, 2 * T + bs], jnp.int32)
    rng = np.random.default_rng(52)
    tables, _ = _run_tables("mixed", 4, cp, 3, rng)
    tables = jnp.asarray(tables, jnp.int32)
    k, v = (jnp.asarray(rng.standard_normal((tables.size + 1, bs, kvh, d)),
                        jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((4, h, d)), jnp.bfloat16)
    kw = dict(chunk_tokens=T, interpret=True)
    sharded = pa.sharded_paged_decode_attention(
        make_mesh(tp=2), AXIS_TP, q, k, v, tables, lens, **kw)
    one = pa.paged_decode_attention(q, k, v, tables, lens, **kw)
    # a shard's product sums over half the columns: the output's ulp
    np.testing.assert_allclose(
        np.asarray(sharded, np.float32), np.asarray(one, np.float32),
        atol=2.0 ** -7, rtol=2.0 ** -7)


def test_derived_chunk_fits_the_vmem_budget():
    """The chunk is what the budget holds in two slots of K and V, and never
    more pages than a row has."""
    page = 16 * 8 * 128 * 2
    assert paged.chunk_pages(16, 8, 128, jnp.bfloat16, 544) == 32
    assert 4 * 32 * page == paged.VMEM_CHUNK_BYTES
    assert paged.chunk_pages(16, 8, 128, jnp.float32, 544) == 16
    assert paged.chunk_pages(16, 2, 128, jnp.bfloat16, 192) == 128
    assert paged.chunk_pages(16, 8, 128, jnp.bfloat16, 6) == 6
    assert paged.chunk_pages(16, 8, 128, jnp.int8, 544) == 64


def test_gather_blocks():
    rng = np.random.default_rng(3)
    cache = jnp.asarray(rng.standard_normal((32, 8, 2, 16)), jnp.float32)
    ids = jnp.asarray([5, 1, 30, 7], jnp.int32)
    got = bc.gather_blocks(cache, ids, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(cache[ids]))


def test_scatter_blocks():
    rng = np.random.default_rng(4)
    cache = jnp.asarray(rng.standard_normal((16, 4, 2, 8)), jnp.float32)
    blocks = jnp.asarray(rng.standard_normal((3, 4, 2, 8)), jnp.float32)
    ids = jnp.asarray([2, 9, 14], jnp.int32)
    expect = np.asarray(cache.at[ids].set(blocks))
    got = bc.scatter_blocks(cache, ids, blocks, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), expect)


def test_copy_blocks():
    rng = np.random.default_rng(5)
    cache = jnp.asarray(rng.standard_normal((16, 4, 2, 8)), jnp.float32)
    src = jnp.asarray([1, 3], jnp.int32)
    dst = jnp.asarray([10, 11], jnp.int32)
    expect = np.asarray(cache.at[dst].set(cache[src]))
    got = bc.copy_blocks(cache, src, dst, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), expect)


def test_sharded_wrapper_single_tp():
    """tp=1 path routes straight to the kernel."""
    from dynamo_tpu.parallel import mesh as meshlib

    rng = np.random.default_rng(6)
    q, kc, vc, tables, lens = _make_paged_case(
        rng, 2, 8, 4, 32, 16, num_blocks=32, max_blocks=4, dtype=jnp.float32
    )
    mesh = meshlib.single_device_mesh()
    got = pa.sharded_paged_decode_attention(
        mesh, meshlib.AXIS_TP, q, kc, vc, tables, lens, interpret=True
    )
    ref = att.paged_decode_attention(q, kc, vc, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def _paged_context(rng, T, kvh, d, bs, spare=3):
    """A context of ``T`` tokens scattered over pages in a shuffled order:
    (k_cache, v_cache, table). Page 0 is scratch."""
    mb = -(-T // bs)
    nb = mb + spare
    k = rng.standard_normal((mb * bs, kvh, d)).astype(np.float32)
    v = rng.standard_normal((mb * bs, kvh, d)).astype(np.float32)
    table = rng.permutation(np.arange(1, nb))[:mb].astype(np.int32)
    kc = np.zeros((nb, bs, kvh, d), np.float32)
    vc = np.zeros((nb, bs, kvh, d), np.float32)
    kc[table] = k.reshape(mb, bs, kvh, d)
    vc[table] = v.reshape(mb, bs, kvh, d)
    return jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(table)


class TestChunkQuestion:
    """The seam's chunk question (ops/paged_attention.py) on the Pallas side,
    interpreted: a lone prefill chunk is ONE ragged row of the unified kernel
    for every bucket, held to ``extend_attention`` over the gathered context
    (the pure-JAX side's answer)."""

    def _check(self, mesh, S_pad, start, total, h=8, kvh=4, d=32, T=256,
               seed=0, **kw):
        from dynamo_tpu.ops.paged_attention import PagedAttention

        rng = np.random.default_rng(seed)
        kc, vc, table = _paged_context(rng, T, kvh, d, bs=16)
        q = jnp.asarray(rng.standard_normal((S_pad, h, d)), jnp.float32)
        real = total - start
        # the engine's padding: pad rows sit at the last position
        pos = np.full(S_pad, T - 1, np.int32)
        pos[:real] = np.arange(start, total)
        args = (q, kc, vc, table, jnp.int32(start), jnp.int32(total),
                jnp.asarray(pos))
        ref = PagedAttention(mesh, False).chunk(*args)
        got = PagedAttention(mesh, True, True).chunk(*args, **kw)
        np.testing.assert_allclose(
            np.asarray(got)[:real], np.asarray(ref)[:real], atol=2e-5)
        assert not np.asarray(got)[real:].any()

    def test_matches_dense_first_chunk(self):
        from dynamo_tpu.parallel.mesh import single_device_mesh

        self._check(single_device_mesh(), 128, 0, 128, chunk_tokens=64)

    def test_matches_dense_chunked_continuation(self):
        """Chunk starting mid-context against a cached prefix, with a padded
        tail of the bucket and pages past total_len never read."""
        from dynamo_tpu.parallel.mesh import single_device_mesh

        self._check(single_device_mesh(), 128, 100, 220, chunk_tokens=64)

    def test_serves_an_unaligned_bucket(self):
        """A bucket on no tile grid (S=100) is SERVED by the ragged row and
        matches the reference."""
        from dynamo_tpu.parallel.mesh import single_device_mesh

        self._check(single_device_mesh(), 100, 0, 100)

    def test_tp_sharded_matches_dense(self):
        """The chunk question over a tp=2 mesh == the dense single-device
        answer (heads split across shards under shard_map)."""
        from dynamo_tpu.parallel.mesh import make_mesh

        self._check(make_mesh(tp=2), 128, 100, 228, chunk_tokens=64)
