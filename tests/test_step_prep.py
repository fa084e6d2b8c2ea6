"""Async host step-prep (engine/prep.py): the exact-match handoff
semantics that make prebuilt chunk packs byte-identical to serial prep.

The engine-level proof lives in tests/test_mixed_batching.py — the mixed
engine runs with async prep ON against a serial-prep split reference and
the token streams match byte-for-byte while prep hits are observed. These
are the fast unit pieces: key-mismatch fallback, identical arrays, failure
isolation, and the StepStats plumbing bench.py summarizes.
"""

import numpy as np

from dynamo_tpu.engine.prep import ChunkPrep, async_prep_enabled
from dynamo_tpu.engine.telemetry import StepStats


def _chunk_arrays(token_ids, start, chunk_len, block_ids):
    """A stand-in with the engine's shape contract (pure function)."""
    bs = 4
    S_pad = ((chunk_len + 15) // 16) * 16
    tokens = np.zeros(S_pad, np.int32)
    tokens[:chunk_len] = token_ids[start : start + chunk_len]
    positions = np.arange(start, start + S_pad, dtype=np.int32)
    nbi = np.zeros(S_pad // bs, np.int32)
    real = block_ids[start // bs :][: S_pad // bs]
    nbi[: len(real)] = real
    return tokens, positions, nbi


def test_prep_hit_returns_identical_arrays():
    prep = ChunkPrep(_chunk_arrays, upload=None)
    prompt = list(range(100))
    blocks = list(range(1, 26))
    prep.schedule("r1", prompt, 16, 16, blocks)
    got = prep.take("r1", prompt, 16, 16, blocks)
    assert got is not None
    arrays, uploads = got
    serial = _chunk_arrays(prompt, 16, 16, blocks)
    for a, b in zip(arrays, serial):
        np.testing.assert_array_equal(a, b)
    assert uploads is None
    assert prep.last is True
    prep.stop()


def test_prep_key_mismatch_falls_back():
    """Any divergence from the scheduled (start, len, token-slice,
    block-span) — a migration resume, block surgery, a REUSED request id
    with an edited prompt — must MISS, never hand stale arrays."""
    prep = ChunkPrep(_chunk_arrays, upload=None)
    prompt = list(range(100))
    blocks = list(range(1, 26))
    prep.schedule("r1", prompt, 16, 16, blocks)
    assert prep.take("r1", prompt, 32, 16, blocks) is None  # moved start
    assert prep.last is False
    prep.schedule("r1", prompt, 16, 16, blocks)
    assert prep.take("r1", prompt, 16, 16, blocks[:-1]) is None  # block span
    assert prep.take("r2", prompt, 16, 16, blocks) is None  # unknown request
    assert prep.last is None
    # request-id reuse with a DIFFERENT prompt but same geometry: the
    # content key over the chunk's token slice must miss (a stale prebuild
    # here would silently write the old prompt's KV)
    prep.schedule("r1", prompt, 16, 16, blocks)
    edited = list(prompt)
    edited[20] = 999
    assert prep.take("r1", edited, 16, 16, blocks) is None
    # content outside the chunk's slice is irrelevant by construction
    prep.schedule("r1", prompt, 16, 16, blocks)
    tail_edit = list(prompt)
    tail_edit[90] = 999
    assert prep.take("r1", tail_edit, 16, 16, blocks) is not None
    prep.stop()


def test_prep_upload_callable_and_failure_isolation():
    calls = []

    def upload(a):
        calls.append(a.shape)
        return ("dev", a)

    prep = ChunkPrep(_chunk_arrays, upload=upload)
    prompt = list(range(64))
    blocks = list(range(1, 17))
    prep.schedule("r", prompt, 0, 16, blocks)
    arrays, uploads = prep.take("r", prompt, 0, 16, blocks)
    assert len(uploads) == 3 and all(u[0] == "dev" for u in uploads)
    assert len(calls) == 3

    # a prep-thread failure surfaces as a MISS (serial path recomputes and
    # raises the real error), never a crashed dispatch
    def boom(*a):
        raise RuntimeError("prep exploded")

    bad = ChunkPrep(boom, upload=None)
    bad.schedule("r", prompt, 0, 16, blocks)
    assert bad.take("r", prompt, 0, 16, blocks) is None
    assert bad.last is False
    bad.stop()
    prep.stop()


def test_prep_env_gate(monkeypatch):
    monkeypatch.delenv("DTPU_ASYNC_PREP", raising=False)
    assert async_prep_enabled()
    for off in ("0", "false", "off", ""):
        monkeypatch.setenv("DTPU_ASYNC_PREP", off)
        assert not async_prep_enabled()
    monkeypatch.setenv("DTPU_ASYNC_PREP", "1")
    assert async_prep_enabled()


def test_step_stats_carries_prep_hit():
    """The field bench.py's detail.step_telemetry.<phase>.prep summary and
    tier-1's byte-identity proof read. The prebuild takes no time of its
    own: what a dispatch waits for it lies in its ``pack`` span."""
    s = StepStats(
        phase="mixed", duration_s=0.01, batch_occupancy=2, batch_size=4,
        tokens=33, queue_depth=0, kv_active_blocks=1, kv_free_blocks=1,
        kv_total_blocks=2, prep_hit=True,
    )
    assert s.prep_hit is True
    assert not hasattr(s, "prep_build_s") and not hasattr(s, "prep_wait_s")
    # defaults keep decode-only steps clean
    d = StepStats(
        phase="decode", duration_s=0.01, batch_occupancy=2, batch_size=4,
        tokens=4, queue_depth=0, kv_active_blocks=1, kv_free_blocks=1,
        kv_total_blocks=2,
    )
    assert d.prep_hit is None

    import bench

    summary = bench._phase_summary([s, s])
    assert summary["prep"] == {"steps": 2, "hits": 2}
    assert "prep" not in bench._phase_summary([d])
