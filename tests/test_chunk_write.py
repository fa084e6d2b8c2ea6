"""A chunk's whole pages written on the view the Pallas kernels read
(``ops/attention.write_prefill_kv(page_view=True)``, asked for by the seam's
``write_chunk``): the same bytes in the same pages as the 4-D scatter every
other program keeps, beside the decode rows' write of the same mixed step;
which of the two the seam takes; and the engines whose fused steps take the
new one (Pallas forced, interpreted) against the split pure-JAX dispatches.

Why the view: tests/test_tpu_compile.py (the chip's compiler re-tiles a pool
of 4 rows of 128 lanes a token around the 4-D scatter); PERF.md section 6,
PR 40.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops.paged_attention import PagedAttention
from dynamo_tpu.ops.quant import QuantizedKV
from dynamo_tpu.parallel.mesh import make_mesh

BS, D = 16, 128


def _pool(key, pages, kvh, dtype):
    k, v = jax.random.normal(key, (2, pages, BS, kvh, D), jnp.float32)
    return k.astype(dtype), v.astype(dtype)


def _same_bits(a, b):
    np.testing.assert_array_equal(
        np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8))


# ---------------------------------------------------------------------------
# the write itself: bit for bit the 4-D scatter's pool
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [1, 32, 128])
@pytest.mark.parametrize("offset", [0, 15])
@pytest.mark.parametrize("kvh,dtype", [
    (4, jnp.bfloat16), (8, jnp.bfloat16), (4, jnp.float32), (1, jnp.bfloat16)],
    ids=["4-rows-bf16", "8-rows-bf16", "4-rows-f32", "1-row-bf16"])
def test_a_mixed_steps_two_writes_leave_the_same_pool_either_way(rows, offset, kvh, dtype):
    """The chunk's pages on the kernel's view, then the decode rows' tokens
    (``write_decode_kv``), against both on the 4-D pool: the two arrays bit
    for bit, at 1, 32 and 128 rows, every row at the page's first or last
    offset, every third row inactive (block 0, offset 0), the chunk's last
    two pages padding (block 0 too), one row's page and one of the chunk's
    the pool's last two. Block 0 is scratch: rows that share it land in an
    order nobody defines, and it is left out."""
    pages = 2 * rows + 8
    keys = jax.random.split(jax.random.PRNGKey(rows * 31 + offset + kvh), 3)
    kc, vc = _pool(keys[0], pages, kvh, dtype)
    S = 6 * BS
    k_new, v_new = jax.random.normal(keys[1], (2, S + rows, kvh, D)).astype(dtype)
    c_blocks = jnp.array([3, pages - 1, 5, 2, 0, 0], jnp.int32)
    active = np.arange(rows) % 3 != 2
    wb = jnp.where(active, pages - 2 - 2 * np.arange(rows), 0).astype(jnp.int32)
    wo = jnp.where(active, offset, 0).astype(jnp.int32)

    def both(page_view):
        k, v = att.write_prefill_kv(kc, vc, k_new[:S], v_new[:S], c_blocks, page_view=page_view)
        return att.write_decode_kv(k, v, k_new[S:], v_new[S:], wb, wo)

    (k3, v3), (k4, v4) = jax.jit(both, static_argnums=0)(True), both(False)
    assert k3.shape == kc.shape and k3.dtype == kc.dtype
    _same_bits(k3[1:], k4[1:])
    _same_bits(v3[1:], v4[1:])
    # and they are the bytes that were handed in, where they were sent
    _same_bits(k3[pages - 1], k_new[BS:2 * BS])
    _same_bits(v3[2], v_new[3 * BS:4 * BS])
    _same_bits(k3[pages - 2, offset], k_new[S])
    untouched = np.setdiff1d(np.arange(1, pages), np.concatenate([c_blocks, wb]))
    _same_bits(k3[untouched], kc[untouched])


@pytest.mark.parametrize("chunk_pages", [1, 2, 32])
def test_a_lone_chunks_pages_are_the_same_either_way(chunk_pages):
    """``prefill``'s write, no decode rows behind it: one page, two, a whole
    512-token bucket; the last page padding."""
    kc, vc = _pool(jax.random.PRNGKey(chunk_pages), 48, 4, jnp.bfloat16)
    k_new, v_new = jax.random.normal(
        jax.random.PRNGKey(7), (2, chunk_pages * BS, 4, D)).astype(jnp.bfloat16)
    blocks = jnp.asarray(
        (np.arange(chunk_pages) * 7 % 47 + 1).tolist()[:-1] + [0], jnp.int32)
    k3, v3 = att.write_prefill_kv(kc, vc, k_new, v_new, blocks, page_view=True)
    k4, v4 = att.write_prefill_kv(kc, vc, k_new, v_new, blocks)
    _same_bits(k3[1:], k4[1:])
    _same_bits(v3[1:], v4[1:])


def test_an_eight_bit_pool_keeps_its_own_branch():
    """``QuantizedKV`` never meets the kernels on the chip; asked for the
    view it quantizes a block at a time as it did."""
    kc, vc = (QuantizedKV(jnp.zeros((8, BS, 2, D), jnp.int8), jnp.zeros((8, 2))) for _ in "kv")
    new = jax.random.normal(jax.random.PRNGKey(0), (2 * BS, 2, D))
    blocks = jnp.array([3, 5], jnp.int32)
    (k3, _), (k4, _) = (
        att.write_prefill_kv(kc, vc, new, new, blocks, page_view=p) for p in (True, False))
    _same_bits(k3.data, k4.data)
    _same_bits(k3.scale, k4.scale)
    assert k3.data.shape == (8, BS, 2, D) and bool(jnp.any(k3.data[3] != 0))


# ---------------------------------------------------------------------------
# which write the seam takes
# ---------------------------------------------------------------------------


def _scatter_ranks(fn, *args):
    return [len(e.invars[0].aval.shape) for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "scatter"]


@pytest.mark.parametrize("use_pallas,tp,ranks", [
    (True, 1, [3, 3]), (False, 1, [4, 4]), (True, 2, [4, 4])],
    ids=["pallas-one-device", "pure-jax", "pallas-tp2"])
def test_the_seam_writes_the_kernels_view_only_where_a_kernel_takes_the_pool_whole(
        use_pallas, tp, ranks):
    """Pallas on and one device: the scatter's operand is the 3-D view.
    Pure JAX: the program is the one it was, equation for equation (nothing
    there constrains the pool's tiling). Under ``tp`` the kv heads are cut
    between devices and the merged ``bs * kvh`` dimension has no sharding: the
    kernel's view exists only inside its ``shard_map``, and the write stays
    on the 4-D pool."""
    seam = PagedAttention(make_mesh(tp=tp, devices=jax.devices()[:tp]), use_pallas, use_pallas)
    kc, vc = _pool(jax.random.PRNGKey(0), 8, 4, jnp.bfloat16)
    new = jnp.ones((2 * BS, 4, D), jnp.bfloat16)
    args = (kc, vc, new, new, jnp.array([3, 5], jnp.int32))
    assert _scatter_ranks(seam.write_chunk, *args) == ranks
    if ranks == [4, 4]:
        assert str(jax.make_jaxpr(seam.write_chunk)(*args)) == str(
            jax.make_jaxpr(att.write_prefill_kv)(*args))


# ---------------------------------------------------------------------------
# the engines whose fused steps take it, against the split dispatches
# ---------------------------------------------------------------------------


def _llama_4_kv_heads():
    from test_mixed_batching import make_engine

    model = LlamaConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=8,
        num_kv_heads=4, head_dim=16, intermediate_size=128, dtype=jnp.float32)
    return lambda mixed, **kw: make_engine(mixed, model=model, **kw)


def _falcon_h1():
    from test_falcon_h1 import engine_of, file_cfg  # FalconH1Config.tiny's shape

    return lambda mixed, **kw: engine_of(
        file_cfg(), mixed_admission=mixed, max_batch_size=4, prefill_buckets=(16, 32),
        decode_steps=4, decode_pipeline=2, **kw)


def _latent_rows():
    from test_mla_latent import engine_of, file_cfg

    # the latent kernel reads bf16 rows, and in bf16 a tiny model's fused and
    # split steps part at a near tie whatever the write: both sides fused and
    # interpreted here, one of them writing the 4-D pool as the parent did
    return lambda mixed, use_pallas: engine_of(
        file_cfg("bfloat16"), use_pallas=True, mixed_admission=True, decode_steps=4,
        decode_pipeline=2)


@pytest.mark.parametrize("family", ["llama-4-kv-heads", "falcon-h1", "latent-rows"])
def test_mixed_steps_on_the_kernels_view_are_the_split_dispatches_tokens(family, monkeypatch):
    """A resident request decodes, a three-chunk prompt arrives behind its
    first token: the engine with the Pallas side forced (interpreted) runs
    fused mixed steps whose chunk pages go in on the kernel's view; the pure
    JAX engine without mixed steps runs ``prefill`` and ``decode_multi`` on
    the 4-D pool. Greedy tokens identical, float32. The latent held as rows
    (bf16 only) is held to ITSELF with the chunk's pages scattered into the
    4-D pool: tokens and logprobs bit for bit; the float32 reference holds
    its mixed steps in tests/test_mla_latent.py."""
    from test_mixed_batching import P_ARRIVER, P_RESIDENT, overlap_scenario, preq

    make = {"llama-4-kv-heads": _llama_4_kv_heads, "falcon-h1": _falcon_h1,
            "latent-rows": _latent_rows}[family]()
    views = []
    write_chunk = PagedAttention.write_chunk
    monkeypatch.setattr(PagedAttention, "write_chunk", lambda seam, *a: (
        views.append(seam.use_pallas) or write_chunk(seam, *a)))

    async def run(mixed):
        engine = make(mixed, use_pallas=mixed)
        phases = set()
        engine.stats_hook = lambda s: phases.add(s.phase)
        try:
            assert engine.mixed_enabled >= mixed <= engine.kernels_interpreted
            assert engine.mesh.size == 1
            out = await overlap_scenario(
                engine, preq("r1", P_RESIDENT, 16, logprobs=1), preq("r2", P_ARRIVER, 6))
        finally:
            engine.stop()
        assert ("mixed" in phases) == engine.mixed_enabled, phases
        return out

    fused = asyncio.run(asyncio.wait_for(run(True), 600))
    assert views and all(views)  # every chunk went in on the view
    if family == "latent-rows":
        monkeypatch.setattr(
            PagedAttention, "write_chunk", lambda seam, *a: att.write_prefill_kv(*a))
    split = asyncio.run(asyncio.wait_for(run(False), 600))
    assert [len(toks) for toks, _ in fused] == [16, 6]
    assert [toks for toks, _ in fused] == [toks for toks, _ in split]
    if family == "latent-rows":
        assert fused[0][1] == split[0][1]
