"""Block hashing + token block sequence tests."""

import hashlib
import random
import struct

import pytest

from dynamo_tpu.tokens import (
    TokenBlock,
    TokenBlockSequence,
    blocks,
    compute_block_hash,
    compute_sequence_hashes,
    hash_blocks,
)

LONG = 24_896  # the document-QA cell's prompt: 1 556 blocks of 16


def _by_definition(tokens, block_size, extra_key=None):
    """The hash as the routers, the KV events and KVBM hold it, written out:
    blake2b/8 over the block's tokens packed ``<Q``, then the chain."""

    def h(payload):
        return struct.unpack("<Q", hashlib.blake2b(payload, digest_size=8).digest())[0]

    block_hashes, seq_hashes = [], []
    for i in range(len(tokens) // block_size):
        payload = b"".join(
            struct.pack("<Q", t & 0xFFFFFFFFFFFFFFFF)
            for t in tokens[i * block_size : (i + 1) * block_size]
        )
        if extra_key:
            payload += b"\x00" + extra_key
        block_hashes.append(h(payload))
        parent = struct.pack("<Q", seq_hashes[-1]) if seq_hashes else b"root"
        seq_hashes.append(h(parent + struct.pack("<Q", block_hashes[-1])))
    return block_hashes, seq_hashes


def _ids(kind, n):
    rng = random.Random(n)
    if kind == "vocab":
        return [rng.randrange(20_480) for _ in range(n)]
    # ids a 32-bit or a signed 64-bit word does not hold, and negative ones
    edge = [2**31, 2**32 + 7, 2**63 - 1, 2**63, 2**64 - 1, 2**64 + 5, -1, -(2**31), -(2**63)]
    return [rng.choice(edge) if i % 3 else rng.randrange(-(2**40), 2**40) for i in range(n)]


def test_block_hash_deterministic():
    assert compute_block_hash([1, 2, 3]) == compute_block_hash([1, 2, 3])
    assert compute_block_hash([1, 2, 3]) != compute_block_hash([1, 2, 4])
    assert compute_block_hash([1, 2]) != compute_block_hash([2, 1])


def test_extra_key_changes_hash():
    assert compute_block_hash([1, 2], b"lora-A") != compute_block_hash([1, 2])
    assert compute_block_hash([1, 2], b"lora-A") != compute_block_hash([1, 2], b"lora-B")


def test_sequence_hash_chaining():
    toks = list(range(64))
    h4 = compute_sequence_hashes(toks, block_size=16)
    assert len(h4) == 4
    # shared prefix -> identical leading hashes
    other = list(range(48)) + [999] * 16
    h_other = compute_sequence_hashes(other, block_size=16)
    assert h_other[:3] == h4[:3]
    assert h_other[3] != h4[3]
    # same block contents at a different position -> different sequence hash
    swapped = toks[16:32] + toks[:16] + toks[32:]
    h_swapped = compute_sequence_hashes(swapped, block_size=16)
    assert h_swapped[0] != h4[0]


def test_partial_blocks_excluded():
    assert len(compute_sequence_hashes(list(range(17)), 16)) == 1
    assert len(compute_sequence_hashes(list(range(15)), 16)) == 0


def test_token_block_sequence_incremental_matches_batch():
    toks = list(range(50))
    seq = TokenBlockSequence(block_size=16)
    sealed = []
    for t in toks:
        b = seq.append(t)
        if b:
            sealed.append(b)
    assert len(sealed) == 3
    assert seq.tail_tokens == toks[48:]
    assert seq.sequence_hashes() == compute_sequence_hashes(toks, 16)
    assert seq.tokens() == toks
    assert len(seq) == 50

    batch = TokenBlockSequence(toks, block_size=16)
    assert batch.sequence_hashes() == seq.sequence_hashes()


def test_block_parent_links():
    seq = TokenBlockSequence(list(range(32)), block_size=16)
    b0, b1 = seq.blocks
    assert b0.parent_hash is None
    assert b1.parent_hash == b0.sequence_hash
    assert (b0.position, b1.position) == (0, 1)


def test_hash_values_are_the_published_ones():
    # literals from the per-token implementation this one replaced: other
    # processes hold these values
    assert compute_sequence_hashes(list(range(32)), 16) == [
        11452072000639660797, 12039117128291867492]
    assert compute_sequence_hashes(list(range(32)), 16, b"lora-A") == [
        960289621228691040, 14252572188170714542]
    assert compute_block_hash([-1, 2**31, 2**63, 2**64 + 5]) == 9800565597619849775


@pytest.mark.parametrize("kind", ["vocab", "wide"])
@pytest.mark.parametrize("extra_key", [None, b"lora-A"])
@pytest.mark.parametrize("length", ["empty", "short_of_a_block", "a_block", "long"])
@pytest.mark.parametrize("block_size", [1, 16, 32])
def test_bulk_hashes_match_the_definition(block_size, length, extra_key, kind):
    n = {"empty": 0, "short_of_a_block": block_size - 1, "a_block": block_size, "long": LONG}[length]
    toks = _ids(kind, n)
    want_blocks, want_seq = _by_definition(toks, block_size, extra_key)
    assert len(want_seq) == n // block_size
    assert hash_blocks(toks, block_size, extra_key) == (want_blocks, want_seq)
    assert compute_sequence_hashes(toks, block_size, extra_key) == want_seq
    seq = TokenBlockSequence(toks, block_size, extra_key)
    assert seq.sequence_hashes() == want_seq
    assert [b.block_hash for b in seq.blocks] == want_blocks
    assert (seq.tokens(), len(seq), seq.num_blocks()) == (toks, n, len(want_seq))
    assert seq.tail_tokens == toks[len(want_seq) * block_size :]
    if n >= block_size:
        assert compute_block_hash(toks[:block_size], extra_key) == want_blocks[0]
        # a chain picked up in the middle carries on from its parent
        assert hash_blocks(toks[block_size:], block_size, extra_key, want_seq[0]) == (
            want_blocks[1:], want_seq[1:])


@pytest.mark.parametrize("extra_key", [None, b"lora-A"])
@pytest.mark.parametrize("bulk", [0, 15, 16, 40, 64])
@pytest.mark.parametrize("block_size", [1, 16, 32])
def test_bulk_then_append_equals_append_alone(block_size, bulk, extra_key):
    toks = _ids("wide", 100)
    grown = TokenBlockSequence(toks[:bulk], block_size, extra_key)
    alone = TokenBlockSequence(block_size=block_size, extra_key=extra_key)
    sealed_alone = [b for b in map(alone.append, toks) if b is not None]
    sealed_grown = [b for b in map(grown.append, toks[bulk:]) if b is not None]
    assert grown.sequence_hashes() == alone.sequence_hashes()
    assert grown.blocks == alone.blocks == sealed_alone
    assert sealed_grown == sealed_alone[bulk // block_size :]
    assert (grown.tokens(), grown.tail_tokens, len(grown)) == (toks, alone.tail_tokens, 100)
    want_blocks, want_seq = _by_definition(toks, block_size, extra_key)
    for i, b in enumerate(sealed_alone):
        assert b == TokenBlock(
            tokens=tuple(toks[i * block_size : (i + 1) * block_size]),
            block_hash=want_blocks[i],
            sequence_hash=want_seq[i],
            parent_hash=want_seq[i - 1] if i else None,
            position=i,
        )
    # extend hands back the blocks it sealed, over an open tail too
    ext = TokenBlockSequence(toks[:bulk], block_size, extra_key)
    assert ext.extend(toks[bulk:]) == sealed_grown
    assert ext.blocks == alone.blocks


def test_a_long_prompt_builds_no_block_and_two_digests_a_block(monkeypatch):
    """Structure, not timing: what the loop's thread pays for a 24.9k-token
    prompt is its 2 x 1 556 digests, and no block object nobody asked for."""
    built, digests = [], []

    class Counted(TokenBlock):
        def __init__(self, *a, **kw):
            built.append(1)
            super().__init__(*a, **kw)

    def counting_blake2b(*a, **kw):
        digests.append(1)
        return hashlib.blake2b(*a, **kw)

    monkeypatch.setattr(blocks, "TokenBlock", Counted)
    monkeypatch.setattr(blocks, "_blake2b", counting_blake2b)
    toks = _ids("vocab", LONG + 5)
    seq = TokenBlockSequence(toks, 16)
    assert len(digests) == 2 * (LONG // 16)
    assert seq.tokens() == toks and len(seq.sequence_hashes()) == LONG // 16
    assert (len(seq), seq.num_blocks(), seq.tail_tokens) == (LONG + 5, LONG // 16, toks[LONG:])
    compute_sequence_hashes(toks, 16)
    assert len(digests) == 4 * (LONG // 16)
    assert built == []
    # a block is made when one is asked for: the one an append seals
    sealed = [seq.append(t) for t in range(11)]
    assert [b is not None for b in sealed] == [False] * 10 + [True]
    assert (len(built), len(digests)) == (1, 4 * (LONG // 16) + 2)
    assert sealed[-1].position == LONG // 16
    assert len(seq.blocks) == LONG // 16 + 1 == len(built) - 1
