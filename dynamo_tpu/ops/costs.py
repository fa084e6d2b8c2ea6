"""Deterministic kernel-side perf accounting: FLOP/HBM-byte counts.

Counts computed from shapes: a check of behaviour that needs no chip, not
evidence of speed (the models' one comparison with a chip was on earlier
chip runs, since deleted; not measured on today's code). Two complementary
sources:

- :func:`jaxpr_counts` traces a jitted fn and walks the jaxpr, tallying
  MXU FLOPs (``dot_general``) and memory-moving op bytes (gather / scatter /
  dynamic slices / concatenate) op by op. ``pallas_call`` eqns are opaque to
  XLA's view of bytes (the kernel drives its own DMAs), so they are
  surfaced as entries for the caller to price with the analytic models;
- the analytic models below price the paged-attention DMA traffic of the
  three Pallas kernels — pages touched (window-skipped pages excluded for
  sliding-window rows; a prefill row's prefix once per query block in the
  unified kernel), scale rows, q/o streams, and the gather copies the
  split path pays that the unified kernel does not —
  parameterized by the concrete per-row (query_len, seq_len[, window])
  mix. Spec-decode verify rows (query_len = k+1) price against the
  retired split prefix-extend launch (:func:`spec_verify_vs_split`).

``bench.py`` folds :func:`mixed_vs_split` into BENCH JSON as
``detail.kernel_bytes`` and ``tests/test_unified_attention.py`` gates
mixed <= split on every PR, so a byte regression in the unified path fails
tier-1 without any hardware in the loop.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, List, Sequence, Tuple

SCALE_BYTES = 4  # f32 per-block-per-kv-head scale rows (ops/quant.py)

# primitives whose cost is dominated by the bytes they move; priced as
# sum of operand + result nbytes
_MEMORY_PRIMS = {
    "gather", "scatter", "scatter-add", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "take", "take_along_axis",
}


# --------------------------------------------------------------- jaxpr walk
def _aval_bytes(aval) -> int:
    try:
        return int(math.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _dot_flops(eqn) -> int:
    """2*M*N*K (times batch) for one dot_general."""
    (lhs, rhs) = eqn.invars[:2]
    dims = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dims
    lshape = lhs.aval.shape
    rshape = rhs.aval.shape
    contract = math.prod(lshape[i] for i in lc) if lc else 1
    batch = math.prod(lshape[i] for i in lb) if lb else 1
    m = math.prod(
        s for i, s in enumerate(lshape) if i not in lc and i not in lb
    )
    n = math.prod(
        s for i, s in enumerate(rshape) if i not in rc and i not in rb
    )
    return 2 * batch * m * n * contract


def _walk(jaxpr, acc: Dict[str, Any]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            f = _dot_flops(eqn)
            acc["flops"] += f
            acc["by_op"][name] = acc["by_op"].get(name, 0) + f
        elif name == "pallas_call":
            acc["pallas_calls"].append({
                # every kernel in ops/ passes pallas_call a stable name
                "name": eqn.params["name"] or "pallas_call",
                "in_shapes": [tuple(v.aval.shape) for v in eqn.invars],
                "out_shapes": [tuple(v.aval.shape) for v in eqn.outvars],
            })
        elif name in _MEMORY_PRIMS:
            b = sum(_aval_bytes(v.aval) for v in eqn.invars)
            b += sum(_aval_bytes(v.aval) for v in eqn.outvars)
            acc["hbm_bytes"] += b
            acc["by_op"][name] = acc["by_op"].get(name, 0) + b
        # recurse into sub-jaxprs (jit/scan/cond/while/shard_map bodies)
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                inner = sub.jaxpr if hasattr(sub.jaxpr, "eqns") else sub
                _walk(inner, acc)
            elif isinstance(sub, (list, tuple)):
                for s in sub:
                    if hasattr(s, "jaxpr"):
                        _walk(s.jaxpr, acc)


def jaxpr_counts(fn, *args, **kwargs) -> Dict[str, Any]:
    """Trace ``fn(*args, **kwargs)`` and return op-level cost tallies:
    ``{"flops", "hbm_bytes", "by_op", "pallas_calls"}``. FLOPs come from
    dot_general shapes; hbm_bytes from memory-moving primitives;
    ``pallas_calls`` lists the opaque kernel launches for the caller to
    price with the analytic models (their DMA traffic is invisible to the
    jaxpr)."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    acc: Dict[str, Any] = {
        "flops": 0, "hbm_bytes": 0, "by_op": {}, "pallas_calls": [],
    }
    _walk(closed.jaxpr, acc)
    return acc


# ------------------------------------------------------- analytic DMA models
def _pages(seq_len: int, bs: int) -> int:
    return -(-max(int(seq_len), 0) // bs)


def unified_attention_bytes(
    rows: Sequence[Tuple[int, ...]],   # (query_len, seq_len[, window])
    *,
    block_size: int,
    kv_heads: int,
    num_heads: int,
    head_dim: int,
    kv_itemsize: int = 2,              # bf16 pages; 1 for int8
    q_itemsize: int = 2,
    quantized: bool = False,
    q_block: int = 128,                # ops/pallas_unified.Q_BLOCK
) -> int:
    """HBM bytes one unified ragged launch moves (ops/pallas_unified): the
    grid is over blocks of ``q_block`` packed query tokens, and for every
    block a row has tokens in, the row's LIVE pages stream once as whole
    pages — up to the causal limit of the row's last token in that block —
    plus int8 scale rows, plus the packed q read and o write, each in the
    kernel's two layouts (token-major for rows of few tokens, kv-head-major
    for the rest; a token is written in one and zero in the other). No gather,
    and no read past a block's causal limit. A decode row (one token, one
    block) streams its pages exactly once; a prefill chunk spanning several
    blocks re-streams its growing prefix once per block. Rows are taken as
    packed densely in order, the engine's layout up to bucket padding.

    A row may carry a third element — a positive sliding-window bound —
    in which case the kernel never DMAs the pages the window aged out:
    live pages start at the page of the first key the block's earliest
    token can see (page-granular, matching the kernel's windowed head
    skip)."""
    total_q = sum(max(r[0], 0) for r in rows)
    page_bytes = block_size * kv_heads * head_dim * kv_itemsize
    kv = 0
    start = 0                          # packed offset of the row's segment
    for row in rows:
        q_len, seq_len = row[0], row[1]
        w = row[2] if len(row) > 2 else 0
        if q_len <= 0 or seq_len <= 0:
            start += max(q_len, 0)
            continue
        ctx_start = seq_len - q_len
        for blk in range(start // q_block, (start + q_len - 1) // q_block + 1):
            a = max(start, blk * q_block)
            b = min(start + q_len, (blk + 1) * q_block)
            p = _pages(min(ctx_start + (b - start), seq_len), block_size)
            if w and w > 0:
                p -= max(ctx_start + (a - start) - w + 1, 0) // block_size
            kv += 2 * p * page_bytes
            if quantized:
                # one [kvh] f32 scale row rides each page DMA
                kv += 2 * p * kv_heads * SCALE_BYTES
        start += q_len
    qo = 2 * 2 * total_q * num_heads * head_dim * q_itemsize
    return kv + qo


def split_prefill_bytes(
    chunk_len: int,
    total_len: int,
    table_blocks: int,                 # gather width: max_blocks_per_seq
    *,
    block_size: int,
    kv_heads: int,
    num_heads: int,
    head_dim: int,
    kv_itemsize: int = 2,
    q_itemsize: int = 2,
    quantized: bool = False,
    q_tile: int = 128,
    bucket: int = None,
) -> int:
    """HBM bytes a gather-then-flash prefill path moves for one chunk (a
    layout the serving path no longer has; bench.py's byte gate compares
    against it): gather_kv materializes the FULL padded table (read +
    write, both K and V), then a flash kernel streams the gathered context
    once per q tile, plus the q read / o write at the bucketed width."""
    del total_len  # the split gather width is the PADDED table, not the
    # real context — that is exactly the waste being priced
    S_pad = bucket if bucket is not None else chunk_len
    T = table_blocks * block_size
    ctx_elems = T * kv_heads * head_dim
    gather = 2 * 2 * ctx_elems * kv_itemsize      # K+V, read+write
    if quantized:
        gather += 2 * 2 * table_blocks * kv_heads * SCALE_BYTES
    nq = -(-S_pad // q_tile)
    kernel_kv = 2 * nq * ctx_elems * kv_itemsize
    if quantized:
        # per-position scale columns stream with the tiles
        kernel_kv += 2 * nq * T * kv_heads * SCALE_BYTES
    qo = 2 * S_pad * num_heads * head_dim * q_itemsize
    return gather + kernel_kv + qo


def split_decode_bytes(
    seq_lens: Iterable[int],
    *,
    block_size: int,
    kv_heads: int,
    num_heads: int,
    head_dim: int,
    kv_itemsize: int = 2,
    q_itemsize: int = 2,
    quantized: bool = False,
    window: int = None,
) -> int:
    """HBM bytes one ragged decode launch moves (ops/pallas_attention):
    each row's real pages once (+ scale rows), one query token per row.
    ``window``: the split windowed-decode path gathers only the trailing
    ``ceil(w / bs) + 1`` blocks (ops/attention.paged_decode_attention)."""
    kv = 0
    n = 0
    for L in seq_lens:
        if L <= 0:
            continue
        n += 1
        p = _pages(L, block_size)
        if window is not None and window > 0:
            p = min((window + block_size - 1) // block_size + 1, p)
        kv += 2 * p * block_size * kv_heads * head_dim * kv_itemsize
        if quantized:
            kv += 2 * p * kv_heads * SCALE_BYTES
    qo = 2 * n * num_heads * head_dim * q_itemsize
    return kv + qo


def split_extend_bytes(
    n_rows: int,
    s_new: int,                        # candidate tokens per row (spec: k+1)
    table_blocks: int,                 # gather width: max_blocks_per_seq
    *,
    block_size: int,
    kv_heads: int,
    num_heads: int,
    head_dim: int,
    kv_itemsize: int = 2,
    q_itemsize: int = 2,
    quantized: bool = False,
) -> int:
    """HBM bytes the SPLIT prefix-extend launch moves for a batch — the
    pre-unification spec-decode verify pass
    (ops/attention.paged_extend_attention): per row, ``gather_kv``
    materializes the FULL padded table (read + write, K and V), the dense
    extend scores read the gathered context once more, plus the q read /
    o write over the ``s_new`` candidate positions."""
    T = table_blocks * block_size
    ctx_elems = T * kv_heads * head_dim
    per_row = 2 * 2 * ctx_elems * kv_itemsize   # gather: K+V, read+write
    per_row += 2 * ctx_elems * kv_itemsize      # dense scores re-read K+V
    if quantized:
        per_row += 2 * 2 * table_blocks * kv_heads * SCALE_BYTES
    qo = 2 * s_new * num_heads * head_dim * q_itemsize
    return n_rows * (per_row + qo)


def spec_verify_vs_split(
    spec_k: int,
    decode_seq_lens: Sequence[int],
    *,
    block_size: int,
    kv_heads: int,
    num_heads: int,
    head_dim: int,
    max_blocks_per_seq: int,
    kv_itemsize: int = 2,
    q_itemsize: int = 2,
    quantized: bool = False,
) -> Dict[str, Any]:
    """Price ONE spec-decode verify pass as unified ragged rows
    (``query_len = k+1`` per row, candidates at the context tail) against
    the split prefix-extend launch it replaced. Returned as a
    ``detail.kernel_bytes.families`` entry by ``bench.py``; tier-1 asserts
    the ratio <= 1.0 — strictly stronger than the acceptance bound (the
    split side here omits the decode dispatch the pair formulation adds).
    """
    rows = [(spec_k + 1, int(L) + spec_k) for L in decode_seq_lens if L > 0]
    kw = dict(
        block_size=block_size, kv_heads=kv_heads, num_heads=num_heads,
        head_dim=head_dim, kv_itemsize=kv_itemsize, q_itemsize=q_itemsize,
        quantized=quantized,
    )
    unified = unified_attention_bytes(rows, **kw)
    split = split_extend_bytes(
        len(rows), spec_k + 1, max_blocks_per_seq, **kw
    )
    return {
        "unified_verify_bytes": int(unified),
        "split_extend_bytes": int(split),
        "ratio": round(unified / split, 4) if split else 0.0,
        "rows": len(rows),
        "spec_k": int(spec_k),
        "quantized": bool(quantized),
    }


# ------------------------------------------------- analytic transfer model
def streamed_transfer_model(
    prompt_tokens: int,
    *,
    block_size: int,
    prefill_chunk: int,
    kv_bytes_per_block: int,
    bandwidth_bytes_s: float,
    prefill_chunk_s: float,
    window_blocks: int = 8,
    handshake_s: float = 0.0,
    decode_step_s: float = 0.0,
) -> Dict[str, Any]:
    """Deterministic TTFT model of blocking vs streamed disagg KV transfer.

    The prefill side computes ``ceil(prompt/chunk)`` chunks, each taking
    ``prefill_chunk_s``; a chunk's blocks become transferable when it lands
    (the engine content-addresses them per chunk). The decode side cannot
    produce its first token until every prompt block arrived (+ one decode
    step).

    - blocking: the pull starts only after the LAST chunk — TTFT pays
      prefill then the whole serialized wire transfer back to back.
    - streamed: windows of ``window_blocks`` ship as soon as their blocks
      are committed, on one wire (transfers serialize with each other but
      overlap prefill compute) — TTFT pays prefill plus only the wire TAIL
      that could not hide under compute.

    Pure function of its arguments (the tier-1 gate asserts streamed <=
    blocking across a parameter grid; ``bench.py`` folds one call at the
    bench shapes into BENCH JSON as ``detail.transfer``).
    """
    blocks = max(_pages(prompt_tokens, block_size), 0)
    chunks = max(_pages(prompt_tokens, prefill_chunk), 1)
    prefill_s = chunks * prefill_chunk_s
    bw = max(float(bandwidth_bytes_s), 1.0)
    total_bytes = blocks * kv_bytes_per_block
    blocking_ttft = prefill_s + handshake_s + total_bytes / bw + decode_step_s
    # streamed pipeline: walk windows in commit order; a window starts when
    # both its last block is committed and the wire is free
    blocks_per_chunk = prefill_chunk // block_size
    wire_free = handshake_s
    done_at = handshake_s  # no blocks -> transfer adds nothing
    sent = 0
    while sent < blocks:
        take = min(window_blocks, blocks - sent)
        last_block = sent + take  # 1-based index of the window's last block
        commit_chunk = _pages(last_block, blocks_per_chunk) if blocks_per_chunk else 1
        committed_at = min(commit_chunk, chunks) * prefill_chunk_s
        start = max(wire_free, committed_at)
        wire_free = start + take * kv_bytes_per_block / bw
        done_at = wire_free
        sent += take
    streamed_ttft = max(done_at, prefill_s) + decode_step_s
    transfer_s = total_bytes / bw
    hidden = max(blocking_ttft - streamed_ttft, 0.0)
    return {
        "prompt_tokens": int(prompt_tokens),
        "blocks": int(blocks),
        "prefill_chunks": int(chunks),
        "prefill_s": round(prefill_s, 6),
        "transfer_s": round(transfer_s, 6),
        "bytes": int(total_bytes),
        "bandwidth_bytes_s": round(bw, 1),
        "window_blocks": int(window_blocks),
        "blocking_ttft_s": round(blocking_ttft, 6),
        "streamed_ttft_s": round(streamed_ttft, 6),
        "speedup": round(blocking_ttft / streamed_ttft, 4)
        if streamed_ttft > 0 else 1.0,
        # fraction of the wire time hidden under prefill compute
        "overlap_fraction": round(hidden / transfer_s, 4)
        if transfer_s > 0 else 0.0,
    }


# tier read-latency priors (seconds per block window): G2 host DRAM is a
# memcpy, G3 disk a file read. Only the RATIO to wire time matters for the
# decision; absolute values are deliberately conservative.
TIER_READ_S_PER_BLOCK = {"g2": 2e-4, "g3": 2e-3}


def fetch_vs_recompute(
    num_blocks: int,
    *,
    block_size: int,
    kv_bytes_per_block: int,
    bandwidth_bytes_s: float,
    prefill_base_s: float,
    prefill_per_token_s: float,
    tier: str = "g2",
    window_blocks: int = 8,
    handshake_s: float = 0.01,
    tier_read_s_per_block: float = None,
    margin: float = 1.0,
) -> Dict[str, Any]:
    """Deterministic price of onboarding ``num_blocks`` sealed KV blocks
    from a peer worker's G2/G3 tier vs recomputing them as local prefill —
    the global-directory routing decision (ROADMAP item 3).

    Fetch is pipelined in ``window_blocks`` windows over one wire: the
    peer reads a window from its tier while the previous window is in
    flight, so steady state pays ``max(wire, tier read)`` per window plus
    the first window's un-overlapped tier read and the handshake.
    Recompute pays the local prefill model for the same tokens.

    ``fetch`` is chosen iff ``fetch_s <= margin * recompute_s`` — so
    "wherever the router chooses fetch, fetch is no slower than
    recompute" holds *by construction* for ``margin <= 1`` (the tier-1
    grid gate asserts exactly this over wire/tier/block-count
    combinations). Pure function of its arguments; ``bench.py`` feeds the
    same model from the wire-bandwidth EWMA at run time.
    """
    n = max(int(num_blocks), 0)
    bw = max(float(bandwidth_bytes_s), 1.0)
    read_s = (
        float(tier_read_s_per_block)
        if tier_read_s_per_block is not None
        else TIER_READ_S_PER_BLOCK.get(tier, TIER_READ_S_PER_BLOCK["g3"])
    )
    win = max(int(window_blocks), 1)
    n_windows = -(-n // win) if n else 0
    window_wire_s = win * kv_bytes_per_block / bw
    window_read_s = win * read_s
    if n:
        # last window may be partial; pricing it full keeps the model
        # monotone in num_blocks (a conservative over-estimate of fetch)
        fetch_s = (
            handshake_s
            + window_read_s
            + n_windows * max(window_wire_s, window_read_s)
        )
    else:
        fetch_s = 0.0
    recompute_s = (
        prefill_base_s + n * block_size * prefill_per_token_s if n else 0.0
    )
    fetch_wins = n > 0 and fetch_s <= margin * recompute_s
    return {
        "num_blocks": n,
        "tier": tier,
        "bytes": n * int(kv_bytes_per_block),
        "bandwidth_bytes_s": round(bw, 1),
        "window_blocks": win,
        "fetch_s": round(fetch_s, 6),
        "recompute_s": round(recompute_s, 6),
        "fetch_wins": bool(fetch_wins),
        "margin": float(margin),
        "speedup": round(recompute_s / fetch_s, 4) if fetch_s > 0 else 1.0,
    }


def predict_step_seconds(
    rows: Sequence[Tuple[int, ...]],   # (query_len, seq_len[, window])
    *,
    block_size: int,
    kv_heads: int,
    num_heads: int,
    head_dim: int,
    hbm_bytes_s: float,
    dispatch_s: float = 0.0,
    weight_bytes: int = 0,
    layers: int = 1,
    kv_itemsize: int = 2,
    q_itemsize: int = 2,
    quantized: bool = False,
) -> float:
    """Roofline floor for one unified-attention engine step in SECONDS:
    the step's modeled HBM traffic (attention pages via
    :func:`unified_attention_bytes`, once per layer, + one weight stream)
    over the device's sustained HBM bandwidth, plus a fixed host dispatch
    overhead.

    This is the expectation side of the ``cost_model_drift`` degradation
    detector (runtime/health.py): the engine's measured step wall time is
    compared against this prediction for the same row mix, and a worker
    whose ratio climbs while its neighbours' stays flat has a local
    problem (thermal throttle, noisy neighbour, dying HBM) that no
    fleet-wide average would localize. A memory-bound floor is exactly
    what is wanted for that comparison: real steps run a bounded factor
    above it, and the detector trips on the RATIO drifting, not on the
    absolute value.
    """
    att_bytes = unified_attention_bytes(
        rows, block_size=block_size, kv_heads=kv_heads, num_heads=num_heads,
        head_dim=head_dim, kv_itemsize=kv_itemsize, q_itemsize=q_itemsize,
        quantized=quantized,
    )
    bw = max(float(hbm_bytes_s), 1.0)
    total = att_bytes * max(int(layers), 1) + max(int(weight_bytes), 0)
    return total / bw + max(dispatch_s, 0.0)


def mixed_vs_split(
    chunk_len: int,
    chunk_total_len: int,
    decode_seq_lens: Sequence[int],
    *,
    block_size: int,
    kv_heads: int,
    num_heads: int,
    head_dim: int,
    max_blocks_per_seq: int,
    kv_itemsize: int = 2,
    q_itemsize: int = 2,
    quantized: bool = False,
    bucket: int = None,
    window: int = None,
) -> Dict[str, Any]:
    """Price ONE mixed continuous-batching step against the equivalent
    split pair (one prefill-chunk dispatch + one decode dispatch over the
    same rows). Returns the byte counts and their ratio — the deterministic
    gate `bench.py` emits as ``detail.kernel_bytes`` and tier-1 asserts
    stays <= 1.0. ``window``: price every row with a sliding-window bound
    (gpt-oss/gemma sliding layers) — the unified side skips aged-out pages,
    the split decode side gathers only the trailing window blocks."""
    w = int(window) if window else 0
    rows: List[Tuple[int, int, int]] = [(chunk_len, chunk_total_len, w)]
    rows += [(1, int(L), w) for L in decode_seq_lens]
    kw = dict(
        block_size=block_size, kv_heads=kv_heads, num_heads=num_heads,
        head_dim=head_dim, kv_itemsize=kv_itemsize, q_itemsize=q_itemsize,
        quantized=quantized,
    )
    mixed = unified_attention_bytes(rows, **kw)
    split = split_prefill_bytes(
        chunk_len, chunk_total_len, max_blocks_per_seq, bucket=bucket, **kw
    ) + split_decode_bytes(decode_seq_lens, window=window, **kw)
    out = {
        "mixed_step_bytes": int(mixed),
        "split_pair_bytes": int(split),
        "ratio": round(mixed / split, 4) if split else 0.0,
        "rows": len(rows),
        "quantized": bool(quantized),
    }
    if window is not None:
        out["window"] = int(window)
    return out
