"""Int8 paged KV cache (ops/quant.py + kv_dtype="int8" engine mode).

What is pinned here, per the layout/format contract in ops/quant.py:

  - quantize/dequantize round-trip error is bounded by scale/2 = amax/254
    per element (per block, per kv head);
  - quantized paged attention (pure-JAX and both Pallas kernels in
    interpreter mode) computes the SAME function as float attention over
    the dequantized cache — the quantization error enters once, at the
    cache, never again in the math;
  - greedy decode through the engine matches the float engine
    token-for-token on a short horizon;
  - blocks round-trip bit-exactly (int8 payload + scales, no float detour)
    through the transfer wire and the KVBM offload/onboard path;
  - the storage format is <= 0.55x of bf16 bytes per token (the acceptance
    gate the bench's kv_bytes_per_token field reports against).
"""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.kvbm.layout import (
    QuantizedBlockCodec,
    block_shape_for,
    kv_bytes_per_token,
)
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import quant
from dynamo_tpu.runtime import Context

MODEL = LlamaConfig(
    vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, intermediate_size=128, dtype=jnp.float32,
)


def _quant_cache(rng, nb=32, bs=8, kvh=2, d=16):
    kc = jnp.asarray(rng.standard_normal((nb, bs, kvh, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, bs, kvh, d)), jnp.float32)
    kQ = quant.QuantizedKV(*quant.quantize_blocks(kc))
    vQ = quant.QuantizedKV(*quant.quantize_blocks(vc))
    return kc, vc, kQ, vQ


# ------------------------------------------------------------- numerics unit
class TestQuantNumerics:
    def test_roundtrip_error_bound(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((8, 16, 4, 32)) * 3.0, jnp.float32)
        q, s = quant.quantize_blocks(x)
        back = quant.dequantize_blocks(q, s)
        err = np.abs(np.asarray(back) - np.asarray(x))
        # per-(block, head) bound: half a quantization step = amax / 254
        bound = np.asarray(s)[:, None, :, None] / 2.0
        assert np.all(err <= bound + 1e-7), float(err.max())

    def test_zero_block_exact(self):
        q, s = quant.quantize_blocks(jnp.zeros((2, 4, 2, 8), jnp.float32))
        assert np.all(np.asarray(s) == 0)
        assert np.all(np.asarray(quant.dequantize_blocks(q, s)) == 0)

    def test_dequant_requant_bit_exact(self):
        """The property that makes float<->int8 cache handoffs lossless past
        the first quantization: max|q| == 127 by construction, so the
        recomputed amax reproduces the scale and the ints re-round to
        themselves."""
        rng = np.random.default_rng(1)
        x = rng.standard_normal((4, 8, 2, 16)).astype(np.float32)
        q, s = quant.quantize_blocks_np(x)
        q2, s2 = quant.quantize_blocks_np(quant.dequantize_blocks_np(q, s))
        np.testing.assert_array_equal(q, q2)
        np.testing.assert_array_equal(s, s2)

    def test_decode_write_rescale_stable(self):
        """A decode write whose token does not raise the block amax leaves
        the existing ints bit-identical (ratio == 1 no-op)."""
        rng = np.random.default_rng(2)
        _, _, kQ, vQ = _quant_cache(rng)
        small = jnp.full((2, 2, 16), 1e-4, jnp.float32)  # below any amax
        wb = jnp.asarray([3, 7], jnp.int32)
        wo = jnp.asarray([1, 5], jnp.int32)
        kQ2, _ = att.write_decode_kv(kQ, vQ, small, small, wb, wo)
        before = np.array(kQ.data[wb])
        after = np.asarray(kQ2.data[wb])
        rows = np.arange(2)
        before[rows, np.asarray(wo)] = after[rows, np.asarray(wo)]
        np.testing.assert_array_equal(before, after)
        np.testing.assert_array_equal(
            np.asarray(kQ.scale[wb]), np.asarray(kQ2.scale[wb])
        )

    def test_decode_write_resets_recycled_block_scale(self):
        """A decode write at offset 0 enters a freshly-(re)allocated block:
        the previous occupant's scale must not survive, or a recycled block
        that once held large activations quantizes a small new token to 0."""
        rng = np.random.default_rng(4)
        _, _, kQ, vQ = _quant_cache(rng)
        # poison block 5 with a huge stale scale
        kQ = quant.QuantizedKV(kQ.data, kQ.scale.at[5].set(100.0 / 127.0))
        tok = jnp.full((1, 2, 16), 0.05, jnp.float32)
        kQ2, _ = att.write_decode_kv(
            kQ, vQ, tok, tok, jnp.asarray([5], jnp.int32),
            jnp.asarray([0], jnp.int32),
        )
        deq = quant.dequantize_blocks(kQ2.data[5], kQ2.scale[5])
        got = np.asarray(deq)[0]  # the written row
        assert np.all(np.abs(got - 0.05) <= 0.05 / 254 + 1e-7), got
        # the rest of the recycled block is zeroed, not stale garbage
        assert np.all(np.asarray(kQ2.data[5])[1:] == 0)

    def test_decode_write_token_error_bound(self):
        rng = np.random.default_rng(3)
        _, _, kQ, vQ = _quant_cache(rng)
        B, kvh, d = 2, 2, 16
        tok = jnp.asarray(rng.standard_normal((B, kvh, d)) * 2.0, jnp.float32)
        wb = jnp.asarray([5, 9], jnp.int32)
        wo = jnp.asarray([0, 3], jnp.int32)
        kQ2, _ = att.write_decode_kv(kQ, vQ, tok, tok, wb, wo)
        deq = quant.dequantize_blocks(kQ2.data[wb], kQ2.scale[wb])
        got = np.asarray(deq)[np.arange(B), np.asarray(wo)]
        bound = np.asarray(kQ2.scale[wb])[:, :, None] / 2.0
        assert np.all(np.abs(got - np.asarray(tok)) <= bound + 1e-7)


# -------------------------------------------------------- attention parity
class TestQuantAttentionParity:
    def _paged_case(self, rng, B=3, h=4, kvh=2, d=16, bs=8, nb=32, mb=4):
        q = jnp.asarray(rng.standard_normal((B, h, d)), jnp.float32)
        kc, vc, kQ, vQ = _quant_cache(rng, nb=nb, bs=bs, kvh=kvh, d=d)
        lens = rng.integers(1, mb * bs, size=B).astype(np.int32)
        tables = np.zeros((B, mb), np.int32)
        free = list(range(1, nb))
        for b in range(B):
            for j in range(-(-int(lens[b]) // bs)):
                tables[b, j] = free.pop()
        return q, kc, vc, kQ, vQ, jnp.asarray(tables), jnp.asarray(lens)

    def test_paged_decode_quant_equals_dequant_reference(self):
        """int8 paged attention == float attention over the dequantized
        cache: quantization error enters at the cache only."""
        rng = np.random.default_rng(10)
        q, _, _, kQ, vQ, tables, lens = self._paged_case(rng)
        kd = quant.dequantize_blocks(kQ.data, kQ.scale)
        vd = quant.dequantize_blocks(vQ.data, vQ.scale)
        ref = att.paged_decode_attention(q, kd, vd, tables, lens)
        got = att.paged_decode_attention(q, kQ, vQ, tables, lens)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-6, rtol=2e-6
        )

    def test_paged_decode_quant_near_float(self):
        """...and stays within quantization tolerance of the FLOAT cache."""
        rng = np.random.default_rng(11)
        q, kc, vc, kQ, vQ, tables, lens = self._paged_case(rng)
        ref = att.paged_decode_attention(q, kc, vc, tables, lens)
        got = att.paged_decode_attention(q, kQ, vQ, tables, lens)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=0.05
        )

    def test_pallas_decode_quant_matches_pure_jax(self):
        from dynamo_tpu.ops import pallas_attention as pa

        rng = np.random.default_rng(12)
        q, _, _, kQ, vQ, tables, lens = self._paged_case(
            rng, B=4, h=8, kvh=4, d=32, bs=16, nb=64, mb=6
        )
        ref = att.paged_decode_attention(q, kQ, vQ, tables, lens)
        got = pa.paged_decode_attention(
            q, kQ, vQ, tables, lens, chunk_tokens=32, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_chunk_question_quant_matches_reference(self):
        """A lone chunk over an int8 cache: one ragged row of the unified
        kernel (interpreted; pages and scale rows dequantized in-register)
        against the dense extend over the dequantizing gather."""
        from dynamo_tpu.ops.paged_attention import PagedAttention
        from dynamo_tpu.parallel.mesh import single_device_mesh

        rng = np.random.default_rng(13)
        _, _, kQ, vQ = _quant_cache(rng, nb=32, bs=16, kvh=4, d=32)
        table = jnp.asarray(np.arange(1, 17), jnp.int32)  # T = 256
        q = jnp.asarray(rng.standard_normal((128, 8, 32)), jnp.float32)
        qpos = jnp.arange(100, 228, dtype=jnp.int32)
        args = (q, kQ, vQ, table, jnp.int32(100), jnp.int32(228), qpos)
        mesh = single_device_mesh()
        ref = PagedAttention(mesh, False).chunk(*args)
        got = PagedAttention(mesh, True, True).chunk(*args, chunk_tokens=64)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_paged_extend_quant_equals_dequant_reference(self):
        """The spec-decode verify shape over a quantized main cache."""
        rng = np.random.default_rng(14)
        _, _, kQ, vQ = _quant_cache(rng, nb=32, bs=8, kvh=2, d=16)
        B, S_new, h, d = 2, 3, 4, 16
        q = jnp.asarray(rng.standard_normal((B, S_new, h, d)), jnp.float32)
        tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
        start = jnp.asarray([10, 7], jnp.int32)
        tlen = jnp.asarray([13, 10], jnp.int32)
        kd = quant.dequantize_blocks(kQ.data, kQ.scale)
        vd = quant.dequantize_blocks(vQ.data, vQ.scale)
        ref = att.paged_extend_attention(q, kd, vd, tables, start, tlen)
        got = att.paged_extend_attention(q, kQ, vQ, tables, start, tlen)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-6, rtol=2e-6
        )


# ------------------------------------------------------------ format bytes
class TestBlockCodec:
    def test_codec_roundtrip_bit_exact(self):
        rng = np.random.default_rng(20)
        codec = QuantizedBlockCodec(block_shape_for(MODEL, 4, "int8"))
        pay = rng.integers(-127, 128, size=codec.payload_shape).astype(np.int8)
        scl = rng.random(codec.scales_shape).astype(np.float32)
        buf = codec.encode(pay, scl)
        assert buf.dtype == np.uint8 and buf.nbytes == codec.nbytes
        p2, s2 = codec.decode(buf)
        np.testing.assert_array_equal(p2, pay)
        np.testing.assert_array_equal(s2, scl)
        p3, s3 = codec.decode_many(np.stack([buf, buf]))
        np.testing.assert_array_equal(p3[1], pay)
        np.testing.assert_array_equal(s3[0], scl)

    def test_bulk_pack_matches_encode(self):
        """The transfer arena's vectorized pack (one concatenate over n
        blocks) is byte-identical to per-block codec.encode."""
        rng = np.random.default_rng(21)
        codec = QuantizedBlockCodec(block_shape_for(MODEL, 4, "int8"))
        n = 3
        pb = rng.integers(-127, 128, size=(n,) + codec.payload_shape).astype(
            np.int8
        )
        sb = rng.random((n,) + codec.scales_shape).astype(np.float32)
        bulk = np.concatenate([
            np.ascontiguousarray(pb).reshape(n, -1).view(np.uint8),
            np.ascontiguousarray(sb).reshape(n, -1).view(np.uint8),
        ], axis=1)
        ref = np.stack([codec.encode(pb[i], sb[i]) for i in range(n)])
        np.testing.assert_array_equal(bulk, ref)

    def test_bytes_per_token_acceptance_ratio(self):
        """int8 (payload + amortized scales) <= 0.55x of bf16 — the bench's
        kv_bytes_per_token field is this same helper."""
        bf16 = LlamaConfig(
            vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128,
        )  # default dtype bf16
        ratio = kv_bytes_per_token(MODEL, 16, "int8") / kv_bytes_per_token(
            bf16, 16, "model"
        )
        assert ratio <= 0.55, ratio
        # and the fp32 storage fix: bf16 models store half of f32 bytes
        assert kv_bytes_per_token(bf16, 16, "model") == (
            kv_bytes_per_token(MODEL, 16, "model") / 2
        )

    def test_block_shape_honors_model_dtype(self):
        bf16 = LlamaConfig(vocab_size=512, hidden_size=64, num_layers=2,
                           num_heads=4, num_kv_heads=2, head_dim=16,
                           intermediate_size=128)
        assert block_shape_for(bf16, 4).dtype == np.dtype(jnp.bfloat16)
        assert block_shape_for(MODEL, 4).dtype == np.dtype(np.float32)
        assert block_shape_for(MODEL, 4, "int8").dtype == np.dtype(np.int8)

    def test_resolve_kv_dtype_env(self, monkeypatch):
        monkeypatch.setenv("DTPU_KV_DTYPE", "int8")
        assert quant.resolve_kv_dtype("auto") == "int8"
        monkeypatch.delenv("DTPU_KV_DTYPE")
        assert quant.resolve_kv_dtype("auto") == "model"
        assert quant.resolve_kv_dtype("model") == "model"
        with pytest.raises(ValueError, match="kv_dtype"):
            quant.resolve_kv_dtype("fp8")


# ----------------------------------------------------------------- engine
def _engine(kv_dtype, num_blocks=32, kvbm=None):
    cfg = TpuEngineConfig(
        model=MODEL, num_blocks=num_blocks, block_size=4, max_batch_size=2,
        max_context=128, prefill_buckets=(16, 32, 64), decode_steps=6,
        decode_pipeline=2, kv_dtype=kv_dtype,
    )
    return TpuEngine(cfg, kvbm=kvbm)


def _preq(rid, tokens, n=6):
    return PreprocessedRequest(
        request_id=rid, model="m", token_ids=tokens,
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        sampling=SamplingOptions(temperature=0.0),
    )


async def _run(eng, req):
    toks, cached = [], None
    async for out in eng.generate(req, Context()):
        toks.extend(out.token_ids)
        if out.annotations:
            cached = out.annotations.get("cached_tokens")
    return toks, cached


PROMPTS = [
    [(i * 37 + 11) % 500 for i in range(9)],
    [(i * 13 + 5) % 500 for i in range(21)],
]


@pytest.mark.slow
async def test_e2e_greedy_matches_float_engine():
    """kv_dtype=int8 greedy decode is token-for-token identical to the float
    engine over a short horizon (chunked prefill + multi-step decode both
    read the quantized cache)."""
    e = _engine("model")
    try:
        ref = [
            (await _run(e, _preq(f"r{i}", p)))[0] for i, p in enumerate(PROMPTS)
        ]
    finally:
        e.stop()
    eq = _engine("int8")
    try:
        got = [
            (await _run(eq, _preq(f"q{i}", p)))[0]
            for i, p in enumerate(PROMPTS)
        ]
    finally:
        eq.stop()
    assert got == ref


@pytest.mark.slow
async def test_transfer_roundtrip_bit_exact():
    """int8 engine -> wire (kv_fetch) -> int8 engine moves the int8 payload
    + scales bit-exactly (the quantized gate skips the ICI/device fast
    paths; the inline wire format ships the pair)."""
    from dynamo_tpu.tokens import compute_sequence_hashes

    a = _engine("int8")
    b = _engine("int8")
    try:
        prompt = list(range(50, 70))  # 5 blocks of 4; 4 sealed prefix blocks
        await _run(a, _preq("a", prompt, n=2))
        addr = await a.serve_transfer()
        hashes = compute_sequence_hashes(prompt, 4)[: (len(prompt) - 1) // 4]
        got = await b._get_transfer_client().fetch_and_import(addr, hashes)
        assert got == len(hashes) * 4
        ids_a = a.allocator.acquire_prefix(hashes)
        ids_b = b.allocator.acquire_prefix(hashes)
        assert len(ids_b) == len(hashes)
        ia = np.asarray(ids_a, np.int32)
        ib = np.asarray(ids_b, np.int32)
        for ca, cb in zip(a.k_caches + a.v_caches, b.k_caches + b.v_caches):
            np.testing.assert_array_equal(
                np.asarray(ca.data[ia]), np.asarray(cb.data[ib])
            )
            np.testing.assert_array_equal(
                np.asarray(ca.scale[ia]), np.asarray(cb.scale[ib])
            )
        a.allocator.release(ids_a)
        b.allocator.release(ids_b)
    finally:
        a.stop()
        b.stop()


async def test_transfer_int8_to_float_peer_dequantizes():
    """Mixed fleet: a FLOAT decode engine pulling from an int8 prefill
    worker imports the dequantized pages (exact floats of the int8 pair)."""
    from dynamo_tpu.tokens import compute_sequence_hashes

    a = _engine("int8")
    b = _engine("model")
    try:
        prompt = list(range(80, 100))
        await _run(a, _preq("a", prompt, n=2))
        addr = await a.serve_transfer()
        hashes = compute_sequence_hashes(prompt, 4)[: (len(prompt) - 1) // 4]
        got = await b._get_transfer_client().fetch_and_import(addr, hashes)
        assert got == len(hashes) * 4
        ids_a = a.allocator.acquire_prefix(hashes)
        ids_b = b.allocator.acquire_prefix(hashes)
        assert len(ids_b) == len(hashes)
        ia, ib = np.asarray(ids_a, np.int32), np.asarray(ids_b, np.int32)
        for ca, cb in zip(a.k_caches + a.v_caches, b.k_caches + b.v_caches):
            want = quant.dequantize_blocks_np(
                np.asarray(ca.data[ia]), np.asarray(ca.scale[ia])
            )
            np.testing.assert_array_equal(np.asarray(cb[ib]), want)
        a.allocator.release(ids_a)
        b.allocator.release(ids_b)
    finally:
        a.stop()
        b.stop()


@pytest.mark.slow
async def test_kvbm_offload_onboard_bit_exact():
    """Offloaded int8 blocks are the flat codec buffer (payload+scales);
    after device eviction the onboard path scatters them back bit-exactly
    and greedy output is unchanged."""
    from dynamo_tpu.kvbm.pool import KvbmTiers
    from dynamo_tpu.tokens import compute_sequence_hashes

    codec = QuantizedBlockCodec(block_shape_for(MODEL, 4, "int8"))
    kvbm = KvbmTiers(codec.nbytes, host_capacity_bytes=64 * codec.nbytes)
    e = _engine("int8", num_blocks=14, kvbm=kvbm)
    try:
        prompt_a = list(range(100, 124))  # 24 tokens = 6 blocks
        t1, _ = await _run(e, _preq("a", prompt_a))
        await asyncio.sleep(0.1)
        assert kvbm.stats()["offloaded"] >= 6
        h0 = compute_sequence_hashes(prompt_a, 4)[0]
        stored0 = kvbm.host.get(h0)
        assert stored0 is not None and stored0.dtype == np.uint8
        assert stored0.nbytes == codec.nbytes
        stored0 = stored0.copy()
        # churn the 13 usable device blocks so prompt_a's pages evict
        for i in range(4):
            await _run(
                e, _preq(f"c{i}", list(range(200 + 30 * i, 224 + 30 * i)))
            )
        t2, cached2 = await _run(e, _preq("a2", prompt_a))
        assert t2 == t1
        assert cached2 and cached2 > 0
        # the onboarded device block re-encodes to the exact stored bytes
        ids = e.allocator.acquire_prefix([h0])
        assert ids
        i0 = np.asarray(ids, np.int32)
        pay = np.empty(codec.payload_shape, np.int8)
        scl = np.empty(codec.scales_shape, np.float32)
        for li, (kc, vc) in enumerate(zip(e.k_caches, e.v_caches)):
            pay[li, 0] = np.asarray(kc.data[i0])[0]
            pay[li, 1] = np.asarray(vc.data[i0])[0]
            scl[li, 0] = np.asarray(kc.scale[i0])[0]
            scl[li, 1] = np.asarray(vc.scale[i0])[0]
        np.testing.assert_array_equal(codec.encode(pay, scl), stored0)
        e.allocator.release(ids)
    finally:
        e.stop()


def test_int8_rejects_uncovered_modes():
    cfg = TpuEngineConfig(
        model=MODEL, num_blocks=16, block_size=4, max_batch_size=2,
        max_context=64, prefill_buckets=(16, 32, 64), kv_dtype="int8", pp=2,
    )
    with pytest.raises(ValueError, match="int8"):
        TpuEngine(cfg)


def test_int8_with_pallas_is_refused_on_the_tpu_backend(monkeypatch):
    """On the TPU backend the Pallas kernels' scale-row DMA does not compile
    (tests/test_tpu_compile.py pins Mosaic's refusal), so the engine says so
    at construction — never a compile error at the first decode, never a
    silent switch to the pure-JAX ops. Off-TPU (the interpreter) and with
    use_pallas=False the combination keeps working."""
    from dynamo_tpu.engine import engine as eng

    cfg = TpuEngineConfig(
        model=MODEL, num_blocks=16, block_size=4, max_batch_size=2,
        max_context=64, prefill_buckets=(16, 32, 64), kv_dtype="int8",
        use_pallas=True, decode_steps=4, decode_pipeline=1,
    )
    monkeypatch.setattr(eng, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="does not compile for the TPU"):
        TpuEngine(cfg)
    monkeypatch.setattr(eng, "on_tpu", lambda: False)
    e = TpuEngine(cfg)
    try:
        assert e.use_pallas and e.kernels_interpreted
        snap = e.snapshot()
        assert snap["kernels_interpreted"] is True
        assert snap["device"]["platform"] == "cpu"
    finally:
        e.stop()
