"""Unified ragged paged-attention kernel (ops/pallas_unified) vs its
pure-JAX reference twin (ops/attention.ragged_paged_attention), plus the
kernel-side deterministic byte gate (ops/costs).

The kernel runs under the Pallas interpreter on CPU (same strategy as
tests/test_pallas_ops.py): every mixed-row shape — decode-only,
prefill-only, mixed, empty rows, single-token prefill, block-boundary
sequence lengths — in both KV dtypes (float and int8+per-block scales),
including the grow-scale rescale RMW path the PR 2 in-kernel caveat
flagged as interpret-only-verified (pinned here by a test instead of a
comment). The cost model's mixed <= split assertion is the tier-1 stand-in
for the dead device bench (ROADMAP item 5's kernel-side half).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import costs
from dynamo_tpu.ops import pallas_unified as pu
from dynamo_tpu.ops.quant import QuantizedKV, quantize_blocks

ATOL = 2e-5  # same pallas-vs-reference bounds as the split kernels' tests


def _make_case(rng, rows, h, kvh, d, bs, num_blocks, max_blocks,
               dtype=jnp.float32, quant=False, gap_after=0, pad_rows=0,
               pad_q=1):
    """rows: [(q_len, seq_len)]; packs segments densely with an optional
    padding gap after the first segment (tokens belonging to no row).
    ``pad_rows`` / ``pad_q`` append idle rows and trailing unowned tokens so
    parametrized cases share one kernel shape (one compile, not one each)."""
    rows = list(rows) + [(0, 0)] * (pad_rows - len(rows))
    R = len(rows)
    Tq = sum(max(q, 0) for q, _ in rows) + gap_after
    Tq = max(Tq, pad_q)
    q = jnp.asarray(rng.standard_normal((Tq, h, d)), dtype)
    k_cache = jnp.asarray(rng.standard_normal((num_blocks, bs, kvh, d)), dtype)
    v_cache = jnp.asarray(rng.standard_normal((num_blocks, bs, kvh, d)), dtype)
    tables = np.zeros((R, max_blocks), np.int32)
    q_starts = np.zeros(R, np.int32)
    q_lens = np.zeros(R, np.int32)
    seq_lens = np.zeros(R, np.int32)
    free = list(range(1, num_blocks))
    off = 0
    for r, (ql, sl) in enumerate(rows):
        q_starts[r] = off
        q_lens[r] = ql
        seq_lens[r] = sl
        off += max(ql, 0)
        if r == 0:
            off += gap_after
        for j in range(-(-sl // bs)):
            tables[r, j] = free.pop()
    if quant:
        kq, ks = quantize_blocks(k_cache)
        vq, vs = quantize_blocks(v_cache)
        k_cache, v_cache = QuantizedKV(kq, ks), QuantizedKV(vq, vs)
    return (q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(q_starts),
            jnp.asarray(q_lens), jnp.asarray(seq_lens))


ROW_MIXES = {
    # chunk + decode rows + an idle slot — the engine's mixed step shape
    "mixed": [(12, 20), (1, 9), (0, 0), (1, 33)],
    "decode_only": [(1, 5), (1, 31), (1, 1), (1, 16)],
    "prefill_only": [(24, 24)],
    # chunked continuation: 8 new tokens against a 32-token cached prefix
    "chunk_continue": [(8, 40), (1, 7)],
    "single_token_prefill": [(1, 1), (1, 12)],
    # every context exactly on a block boundary
    "block_boundary": [(16, 16), (1, 32), (1, 16)],
    "empty_rows": [(0, 0), (1, 10), (0, 0)],
}


@pytest.mark.parametrize("name", sorted(ROW_MIXES))
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_unified_matches_reference(name, quant):
    rng = np.random.default_rng(hash(name) % (2**32))
    args = _make_case(
        rng, ROW_MIXES[name], h=8, kvh=4, d=32, bs=16, num_blocks=64,
        max_blocks=6, quant=quant, gap_after=3, pad_rows=4, pad_q=32,
    )
    ref = att.ragged_paged_attention(*args)
    got = pu.ragged_paged_attention(
        *args, chunk_tokens=32, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=ATOL, rtol=ATOL
    )


# --------------------------------------------- per-row attributes (PR 14)
# Windowed rows, sink rows, softcap rows, spec-verify rows (q_len = k+1)
# and combinations — each against the pure-JAX twin, float and int8, mixed
# with plain rows in ONE launch.
ATTR_CASES = {
    # per-row windows: a windowed chunk + windowed decode rows + a full-
    # attention row (w=0) in one launch; small window over a longer context
    # exercises the page-granular head skip
    "windowed_rows": dict(
        rows=[(12, 36), (1, 33), (0, 0), (1, 9)],
        windows=[7, 16, 0, 0],
    ),
    # gpt-oss shape: sinks on every row, window on some (alternating-layer
    # pattern collapses to per-launch extras; rows still differ in shape)
    "sink_rows": dict(rows=[(8, 24), (1, 17), (1, 5)], sinks=True),
    "softcap_rows": dict(
        rows=[(8, 24), (1, 17), (1, 5)], softcap=30.0,
    ),
    "window_sink_softcap": dict(
        rows=[(12, 20), (1, 33), (0, 0), (1, 9)],
        windows=[6, 12, 0, 5], sinks=True, softcap=50.0,
    ),
    # spec-decode verify rows (q_len = k+1, candidates at the context
    # tail) riding alongside a plain decode row and an idle slot
    "verify_rows": dict(rows=[(4, 12), (4, 21), (0, 0), (1, 33)]),
    # verify + windowed in one launch: the mixed-step shape for a gemma
    # sliding layer while spec-verify rows are in flight
    "verify_windowed": dict(
        rows=[(4, 36), (4, 21), (1, 17)], windows=[9, 0, 11],
    ),
}


@pytest.mark.parametrize("name", sorted(ATTR_CASES))
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_unified_row_attributes_match_reference(name, quant):
    """Interpret parity (<= 1e-5 abs err, the acceptance bound) for every
    new per-row attribute against the pure-JAX twin."""
    case = ATTR_CASES[name]
    rng = np.random.default_rng(hash(name) % (2**32))
    args = _make_case(
        rng, case["rows"], h=8, kvh=4, d=32, bs=8, num_blocks=64,
        max_blocks=8, quant=quant, gap_after=3, pad_rows=4, pad_q=24,
    )
    kw = {}
    if "windows" in case:
        kw["windows"] = jnp.asarray(
            case["windows"] + [0] * (4 - len(case["windows"])), jnp.int32
        )
    if case.get("sinks"):
        kw["sinks"] = jnp.asarray(rng.standard_normal(8), jnp.float32)
    if case.get("softcap"):
        kw["softcap"] = case["softcap"]
    ref = att.ragged_paged_attention(*args, **kw)
    got = pu.ragged_paged_attention(
        *args, **kw, chunk_tokens=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=1e-5, rtol=ATOL
    )


@pytest.mark.parametrize("attrs", ["plain", "window-sinks-softcap"])
def test_unified_rows_spanning_query_blocks(attrs):
    """The grid is over BLOCKS of packed query tokens: a prefill row that
    spans several blocks re-streams its causal prefix per block, rows that
    share a block (and a sub-tile) keep each other's outputs, and a row cut
    by a block edge is served by both programs. q_block=8 forces all three
    on a small case; a gap leaves tokens no row owns (zeros)."""
    rng = np.random.default_rng(17)
    rows = [(21, 37), (1, 9), (1, 30), (0, 0), (6, 22)]
    args = _make_case(
        rng, rows, h=8, kvh=4, d=32, bs=8, num_blocks=64, max_blocks=8,
        gap_after=2,
    )
    kw = {}
    if attrs != "plain":
        kw = dict(
            windows=jnp.asarray([11, 0, 7, 0, 5], jnp.int32),
            sinks=jnp.asarray(rng.standard_normal(8), jnp.float32),
            softcap=40.0,
        )
    ref = att.ragged_paged_attention(*args, **kw)
    got = pu.ragged_paged_attention(
        *args, **kw, q_block=8, chunk_tokens=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=1e-5, rtol=ATOL
    )


# ------------------------------------------ the cells' head geometries (PR 27)
# The benchmark's three configurations at their real head counts, head_dim
# and page size, so that the DERIVED chunk and tile sizes are the ones the
# chip runs: bf16 caches (the per-head side cuts heads out of 32-bit words of
# the dense chunk), a chunk row that fills its blocks beside decode rows
# (one row on each side of the regime rule), contexts over several chunks
# with a ragged last page, an empty row between live rows (the read-ahead
# across rows must skip it), a window that starts mid-chunk and mid-page.
GEOMETRY_CASES = {
    # InternLM2 (g 2 / kvh 8): a 512-token chunk + decode rows
    "g2-kvh8-chunk512": dict(
        h=16, kvh=8, rows=[(512, 1300), (1, 1100), (0, 0), (1, 1037),
                           (1, 513), (2, 700)],
    ),
    # Mistral (g 4 / kvh 8): a 128-token bucket holding 100 tokens, a
    # spec-verify row (q_len 4), a two-token row, rows of one page
    "g4-kvh8-chunk128": dict(
        h=32, kvh=8, gap_after=28,
        rows=[(100, 1124), (1, 1500), (4, 900), (0, 0), (2, 600), (1, 17)],
    ),
    # Mellum (g 8 / kvh 4), sliding layers: window 1 024 over contexts to
    # 2 600, chunks of 1 024 tokens; one context shorter than the window
    "g8-kvh4-windowed-chunk512": dict(
        h=32, kvh=4, window=1024,
        rows=[(512, 2600), (1, 2500), (0, 0), (1, 1030), (1, 900),
              (3, 2100)],
    ),
    "g8-kvh4-windowed-decode": dict(
        h=32, kvh=4, window=1024,
        rows=[(1, 2300), (1, 1100), (0, 0), (0, 0), (1, 1025), (1, 1024),
              (1, 16), (1, 2047)],
    ),
    # float32 pages (the generic head cut) at tight tolerance
    "g4-kvh8-float32": dict(
        h=32, kvh=8, dtype=jnp.float32, tol=2e-5,
        rows=[(128, 700), (1, 530), (0, 0), (2, 300), (1, 257)],
    ),
    "g8-kvh4-windowed-float32": dict(
        h=32, kvh=4, window=300, dtype=jnp.float32, tol=2e-5,
        rows=[(128, 1400), (1, 1300), (0, 0), (3, 700), (1, 200), (1, 301)],
    ),
    # int8 pages (interpreted only) and the gated families' attributes
    "g2-kvh8-int8": dict(
        h=16, kvh=8, dtype=jnp.float32, quant=True, tol=2e-5,
        rows=[(128, 1200), (1, 1100), (0, 0), (4, 530), (1, 513)],
    ),
    "g8-kvh4-window-sinks-softcap": dict(
        h=32, kvh=4, window=300, sinks=True, softcap=30.0,
        rows=[(128, 1400), (1, 1300), (0, 0), (4, 700), (1, 200)],
    ),
}


@pytest.mark.parametrize("name", sorted(GEOMETRY_CASES))
def test_unified_cell_geometries(name):
    case = GEOMETRY_CASES[name]
    rng = np.random.default_rng(sorted(GEOMETRY_CASES).index(name))
    dtype = case.get("dtype", jnp.bfloat16)
    rows = case["rows"]
    pages = sum(-(-sl // 16) for _, sl in rows)
    args = _make_case(
        rng, rows, h=case["h"], kvh=case["kvh"], d=128, bs=16,
        num_blocks=pages + 2, max_blocks=max(-(-sl // 16) for _, sl in rows),
        dtype=dtype, quant=case.get("quant", False),
        gap_after=case.get("gap_after", 0),
    )
    kw = {}
    if "window" in case:
        kw["windows"] = jnp.full((len(rows),), case["window"], jnp.int32)
    if case.get("sinks"):
        kw["sinks"] = jnp.asarray(
            rng.standard_normal(case["h"]), jnp.float32)
    if case.get("softcap"):
        kw["softcap"] = case["softcap"]
    ref = att.ragged_paged_attention(*args, **kw)
    got = pu.ragged_paged_attention(*args, **kw, interpret=True)
    tol = case.get("tol", 3e-2)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


def test_unified_derived_sizes():
    """Chunk and tile sizes follow the shapes: no call site sets them."""
    assert pu._tile_tokens(2, 128) == 128      # InternLM2: a block, 256 rows
    assert pu._tile_tokens(4, 128) == 128      # Mistral: 512 rows a tile
    assert pu._tile_tokens(8, 128) == 64       # Mellum: 512 rows a tile
    assert pu._tile_tokens(1, 128) == 128
    assert pu._tile_tokens(8, 8) == 8          # the tests' small blocks
    # tokens of a row in a block that still take the masked product
    assert pu._few_tokens(16, jnp.bfloat16, 128) == 8    # InternLM2
    assert pu._few_tokens(32, jnp.bfloat16, 128) == 4    # Mistral, Mellum
    assert pu._few_tokens(8, jnp.bfloat16, 128) == 1     # a tp=4 shard
    assert pu._few_tokens(8, jnp.float32, 128) == 8
    import inspect

    params = inspect.signature(pu.ragged_paged_attention).parameters
    assert "q_seg" not in params
    assert params["q_block"].default is None
    assert params["chunk_tokens"].default is None


def test_unified_scalar_window_equals_per_row():
    """The twin's scalar ``window`` (the engine's per-layer form) and the
    per-row ``windows`` array agree when every row shares the bound."""
    rng = np.random.default_rng(21)
    args = _make_case(
        rng, [(8, 24), (1, 17)], h=4, kvh=2, d=32, bs=8, num_blocks=32,
        max_blocks=4,
    )
    a = att.ragged_paged_attention(*args, window=9)
    b = att.ragged_paged_attention(
        *args, windows=jnp.full((2,), 9, jnp.int32)
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unified_sharded_wrapper_with_attributes():
    """TP shard_map wrapper threads windows (replicated) and sinks (head-
    sharded) through to per-shard kernels."""
    from dynamo_tpu.parallel.mesh import AXIS_TP, make_mesh

    rng = np.random.default_rng(5)
    args = _make_case(
        rng, [(8, 16), (1, 9)], h=8, kvh=4, d=32, bs=8, num_blocks=32,
        max_blocks=4,
    )
    windows = jnp.asarray([5, 0], jnp.int32)
    sinks = jnp.asarray(rng.standard_normal(8), jnp.float32)
    ref = att.ragged_paged_attention(
        *args, windows=windows, sinks=sinks, softcap=40.0
    )
    mesh = make_mesh(tp=2, devices=jax.devices()[:2])
    with mesh:
        got = pu.sharded_ragged_paged_attention(
            mesh, AXIS_TP, *args, windows=windows, sinks=sinks,
            softcap=40.0, chunk_tokens=16, interpret=True,
        )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=1e-5, rtol=ATOL
    )


def test_per_row_adapter_ids_in_packed_buffer():
    """Per-row LoRA adapter indices threaded through the packed buffer:
    the per-token branch of lora/adapters.make_lora_fn applies each row's
    adapter to exactly its own segment — equal to applying each adapter's
    dense delta per segment."""
    from dynamo_tpu.lora.adapters import make_lora_fn

    rng = np.random.default_rng(3)
    L_layers, H, r, out = 2, 16, 4, 16
    N = 3  # slot 0 = identity
    A = jnp.asarray(rng.standard_normal((N, L_layers, H, r)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((N, L_layers, r, out)), jnp.float32)
    A = A.at[0].set(0.0)
    Bm = Bm.at[0].set(0.0)
    scales = jnp.asarray([0.0, 0.5, 2.0], jnp.float32)
    tables = {"wq.A": A, "wq.B": Bm, "scales": scales}
    # packed buffer: chunk of 5 tokens (adapter 1), decode rows with
    # adapters [0, 2, 1]
    token_ids = jnp.asarray([1] * 5 + [0, 2, 1], jnp.int32)
    x = jnp.asarray(rng.standard_normal((8, H)), jnp.float32)
    got = make_lora_fn(tables, token_ids)("wq", 1, x)
    for t in range(8):
        a = int(token_ids[t])
        want = (x[t] @ A[a, 1]) @ Bm[a, 1] * scales[a]
        np.testing.assert_allclose(
            np.asarray(got[t]), np.asarray(want), atol=1e-5, rtol=1e-5
        )
    # the [B]-ids decode branch is untouched: 3-dim activations
    xb = jnp.asarray(rng.standard_normal((3, 1, H)), jnp.float32)
    ids_b = jnp.asarray([0, 2, 1], jnp.int32)
    got_b = make_lora_fn(tables, ids_b)("wq", 0, xb)
    for b in range(3):
        a = int(ids_b[b])
        want = (xb[b, 0] @ A[a, 0]) @ Bm[a, 0] * scales[a]
        np.testing.assert_allclose(
            np.asarray(got_b[b, 0]), np.asarray(want), atol=1e-5, rtol=1e-5
        )


def test_unified_bf16_and_head_layouts():
    """bf16 queries/pages and MQA-ish head grouping (kvh=1)."""
    rng = np.random.default_rng(7)
    for h, kvh in [(8, 1), (4, 4)]:
        args = _make_case(
            rng, [(8, 24), (1, 15)], h=h, kvh=kvh, d=32, bs=8,
            num_blocks=32, max_blocks=5, dtype=jnp.bfloat16,
        )
        ref = att.ragged_paged_attention(*args)
        got = pu.ragged_paged_attention(
            *args, chunk_tokens=16, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2,
        )


def test_unified_int8_grow_scale_rmw():
    """PR 2 caveat pinned by a test: a decode write that GROWS a block's
    scale (requantize_token's rescale RMW) feeds the unified kernel's
    scale-row DMA path — the kernel must read the grown scales, not stale
    ones, and match the reference twin within quantization tolerance."""
    rng = np.random.default_rng(11)
    bs, kvh, d, h = 8, 2, 32, 4
    num_blocks = 16
    k_cache = QuantizedKV(
        jnp.zeros((num_blocks, bs, kvh, d), jnp.int8),
        jnp.zeros((num_blocks, kvh), jnp.float32),
    )
    v_cache = QuantizedKV(
        jnp.zeros((num_blocks, bs, kvh, d), jnp.int8),
        jnp.zeros((num_blocks, kvh), jnp.float32),
    )
    # prefill 8 small-amplitude tokens into block 1 (scale saturates small)
    k_new = jnp.asarray(rng.standard_normal((bs, kvh, d)) * 0.1, jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((bs, kvh, d)) * 0.1, jnp.float32)
    blocks = jnp.asarray([1], jnp.int32)
    k_cache, v_cache = att.write_prefill_kv(k_cache, v_cache, k_new, v_new, blocks)
    # decode-write a LARGE token into block 2 offset 1 after a small one:
    # the second write's amax exceeds the inherited scale -> rescale RMW
    for off, amp in [(0, 0.05), (1, 5.0)]:
        kd = jnp.asarray(rng.standard_normal((1, kvh, d)) * amp, jnp.float32)
        vd = jnp.asarray(rng.standard_normal((1, kvh, d)) * amp, jnp.float32)
        k_cache, v_cache = att.write_decode_kv(
            k_cache, v_cache, kd, vd,
            jnp.asarray([2], jnp.int32), jnp.asarray([off], jnp.int32),
        )
    assert float(k_cache.scale[2].max()) > 0.01  # the grow actually happened
    # row 0: extend over block 1's 8 tokens; row 1: decode over block 2's 2
    q = jnp.asarray(rng.standard_normal((5, h, d)), jnp.float32)
    tables = jnp.asarray([[1, 0, 0], [2, 0, 0]], jnp.int32)
    q_starts = jnp.asarray([0, 4], jnp.int32)
    q_lens = jnp.asarray([4, 1], jnp.int32)
    seq_lens = jnp.asarray([8, 2], jnp.int32)
    args = (q, k_cache, v_cache, tables, q_starts, q_lens, seq_lens)
    ref = att.ragged_paged_attention(*args)
    got = pu.ragged_paged_attention(
        *args, chunk_tokens=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=ATOL, rtol=ATOL
    )


def test_unified_sharded_wrapper_tp():
    """TP shard_map wrapper: per-head-shard kernel equals the full kernel."""
    from dynamo_tpu.parallel.mesh import AXIS_TP, make_mesh

    rng = np.random.default_rng(3)
    args = _make_case(
        rng, [(8, 16), (1, 9)], h=8, kvh=4, d=32, bs=8, num_blocks=32,
        max_blocks=4,
    )
    ref = att.ragged_paged_attention(*args)
    mesh = make_mesh(tp=2, devices=jax.devices()[:2])
    with mesh:
        got = pu.sharded_ragged_paged_attention(
            mesh, AXIS_TP, *args, chunk_tokens=16, interpret=True
        )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=ATOL, rtol=ATOL
    )


# ---------------------------------------------------------------- byte gate
def test_mixed_step_moves_fewer_bytes_than_split():
    """Tier-1 kernel perf gate: across representative serving shapes (and
    the bench config's), one mixed step's modeled HBM bytes stay <= the
    split prefill-dispatch + decode-dispatch pair it replaces."""
    shapes = [
        # (chunk_len, total_len, decode_lens, bs, kvh, h, d, mbs, bucket)
        (256, 256, [320] * 8, 16, 8, 16, 128, 64, 256),     # bench-like
        (512, 512, [384] * 32, 16, 8, 16, 128, 64, 512),    # bigger batch
        (32, 160, [40] * 4, 4, 2, 4, 16, 40, 32),           # tiny chunk cont.
        (64, 64, [2000], 16, 1, 8, 128, 256, 64),           # long-context MQA
    ]
    for (cl, tl, dec, bs, kvh, h, d, mbs, bucket) in shapes:
        for quant, esize in [(False, 2), (True, 1)]:
            r = costs.mixed_vs_split(
                chunk_len=cl, chunk_total_len=tl, decode_seq_lens=dec,
                block_size=bs, kv_heads=kvh, num_heads=h, head_dim=d,
                max_blocks_per_seq=mbs, kv_itemsize=esize, quantized=quant,
                bucket=bucket,
            )
            assert r["mixed_step_bytes"] <= r["split_pair_bytes"], r
            assert 0 < r["ratio"] <= 1.0, r


def test_windowed_mixed_moves_fewer_bytes_than_split():
    """Tier-1 gate for the windowed families: a mixed step over sliding-
    window rows (unified kernel skips aged-out pages) stays <= the split
    pair (whose decode side already gathers only the trailing window
    blocks)."""
    shapes = [
        # (chunk, total, decode_lens, window, bs, kvh, h, d, mbs, bucket)
        (256, 256, [320] * 8, 128, 16, 8, 16, 128, 64, 256),  # gpt-oss-ish
        (32, 160, [40] * 4, 16, 4, 2, 4, 16, 40, 32),
        (64, 64, [2000] * 8, 128, 16, 1, 8, 128, 256, 64),    # long context
        (512, 512, [384] * 32, 1024, 16, 8, 16, 128, 64, 512),  # w > ctx
    ]
    for (cl, tl, dec, w, bs, kvh, h, d, mbs, bucket) in shapes:
        for quant, esize in [(False, 2), (True, 1)]:
            r = costs.mixed_vs_split(
                chunk_len=cl, chunk_total_len=tl, decode_seq_lens=dec,
                block_size=bs, kv_heads=kvh, num_heads=h, head_dim=d,
                max_blocks_per_seq=mbs, kv_itemsize=esize, quantized=quant,
                bucket=bucket, window=w,
            )
            assert r["mixed_step_bytes"] <= r["split_pair_bytes"], r
            assert 0 < r["ratio"] <= 1.0, r
            assert r["window"] == w
            # a small window must be CHEAPER than full attention on the
            # same rows (the head-skip actually skips)
            if w < min(dec):
                full = costs.mixed_vs_split(
                    chunk_len=cl, chunk_total_len=tl, decode_seq_lens=dec,
                    block_size=bs, kv_heads=kvh, num_heads=h, head_dim=d,
                    max_blocks_per_seq=mbs, kv_itemsize=esize,
                    quantized=quant, bucket=bucket,
                )
                assert r["mixed_step_bytes"] < full["mixed_step_bytes"]


def test_spec_verify_bytes_leq_split_extend_pair():
    """Tier-1 gate: a spec-verify pass priced as unified q_len=k+1 rows
    moves <= the split prefix-extend launch it replaced (strictly stronger
    than <= the extend+decode pair)."""
    for k in (1, 3, 4, 8):
        for quant, esize in [(False, 2), (True, 1)]:
            r = costs.spec_verify_vs_split(
                k, [320] * 8, block_size=16, kv_heads=8, num_heads=16,
                head_dim=128, max_blocks_per_seq=64, kv_itemsize=esize,
                quantized=quant,
            )
            assert r["unified_verify_bytes"] <= r["split_extend_bytes"], r
            assert 0 < r["ratio"] <= 1.0, r
            # a fortiori vs the pair formulation (extend + one decode step)
            pair = r["split_extend_bytes"] + costs.split_decode_bytes(
                [320] * 8, block_size=16, kv_heads=8, num_heads=16,
                head_dim=128, kv_itemsize=esize, quantized=quant,
            )
            assert r["unified_verify_bytes"] <= pair


def test_bench_kernel_bytes_family_schema():
    """The per-family entries bench.py emits under
    detail.kernel_bytes.families carry the gate fields and pass <= 1.0."""
    base = costs.mixed_vs_split(
        chunk_len=256, chunk_total_len=256, decode_seq_lens=[320] * 8,
        block_size=16, kv_heads=8, num_heads=16, head_dim=128,
        max_blocks_per_seq=64, bucket=256,
    )
    families = {
        "windowed": costs.mixed_vs_split(
            chunk_len=256, chunk_total_len=256, decode_seq_lens=[320] * 8,
            block_size=16, kv_heads=8, num_heads=16, head_dim=128,
            max_blocks_per_seq=64, bucket=256, window=128,
        ),
        "spec_verify": costs.spec_verify_vs_split(
            4, [320] * 8, block_size=16, kv_heads=8, num_heads=16,
            head_dim=128, max_blocks_per_seq=64,
        ),
        "lora": dict(base, note="x"),
    }
    for fam in ("windowed", "lora"):
        for key in ("mixed_step_bytes", "split_pair_bytes", "ratio", "rows"):
            assert key in families[fam], fam
        assert families[fam]["ratio"] <= 1.0, fam
    sv = families["spec_verify"]
    for key in ("unified_verify_bytes", "split_extend_bytes", "ratio",
                "rows", "spec_k"):
        assert key in sv
    assert sv["ratio"] <= 1.0


def test_jaxpr_counts_traces_kernel_and_reference():
    """The jaxpr walker surfaces the unified kernel's pallas_call (for the
    analytic models to price) and counts MXU FLOPs in the reference twin."""
    q = jnp.zeros((12, 4, 16), jnp.float32)
    kc = jnp.zeros((8, 4, 2, 16), jnp.float32)
    vc = jnp.zeros_like(kc)
    tables = jnp.zeros((2, 2), jnp.int32)
    qs = jnp.asarray([0, 10], jnp.int32)
    ql = jnp.asarray([10, 1], jnp.int32)
    sl = jnp.asarray([10, 6], jnp.int32)
    c = costs.jaxpr_counts(
        lambda *a: pu.ragged_paged_attention(*a, interpret=True),
        q, kc, vc, tables, qs, ql, sl,
    )
    assert any(
        p["name"] == "ragged_paged_attention" for p in c["pallas_calls"]
    )
    c2 = costs.jaxpr_counts(
        att.ragged_paged_attention, q, kc, vc, tables, qs, ql, sl
    )
    assert c2["flops"] > 0
    assert c2["hbm_bytes"] > 0
    assert "dot_general" in c2["by_op"]


def test_bench_kernel_bytes_schema():
    """The record bench.py emits as detail.kernel_bytes carries the gate
    fields and passes at <= 1.0 for the bench defaults."""
    r = costs.mixed_vs_split(
        chunk_len=256, chunk_total_len=256, decode_seq_lens=[320] * 8,
        block_size=16, kv_heads=8, num_heads=16, head_dim=128,
        max_blocks_per_seq=64, bucket=256,
    )
    for key in ("mixed_step_bytes", "split_pair_bytes", "ratio", "rows"):
        assert key in r
    assert r["ratio"] <= 1.0
