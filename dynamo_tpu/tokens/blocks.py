"""Chained block hashing over token id sequences.

Hash design: ``seq_hash[i] = H(seq_hash[i-1] || H(tokens[i]))`` with a 64-bit
stable digest (blake2b/8), optionally salted by an "extra key" (lora id,
multimodal content hash) the way the reference mixes extra state into its
``PositionalSequenceHash`` (lib/tokens/src/blocks.rs:59). Stability across
processes and hosts matters: routers and workers must agree on hashes.

The one pass lives in ``hash_blocks``: the tokens become ONE little-endian
64-bit buffer, each whole block's digest is taken from a slice of it and
chained onto its parent's 8 bytes. ``compute_sequence_hashes`` (routers,
frontend) and ``TokenBlockSequence`` (engine, mocker) both call it, so a
24.9k-token prompt costs its 2 x 1 556 digests and no per-token statement.
"""

from __future__ import annotations

import dataclasses
import hashlib
import struct
from typing import Iterable, List, Optional, Sequence, Tuple

BlockHash = int      # hash of one block's tokens alone
SequenceHash = int   # chained hash: identifies block *in its prefix context*

_U64 = struct.Struct("<Q")
_MASK64 = 0xFFFFFFFFFFFFFFFF
_blake2b = hashlib.blake2b


def _le64(tokens: Sequence[int]) -> bytes:
    """``tokens`` as little-endian 64-bit words (two's complement, mod 2^64)."""
    try:
        return struct.pack("<%dq" % len(tokens), *tokens)
    except struct.error:  # an id outside int64: the same wrap, id by id
        return struct.pack("<%dQ" % len(tokens), *[t & _MASK64 for t in tokens])


def _unpack64(digests: List[bytes]) -> List[int]:
    return list(struct.unpack("<%dQ" % len(digests), b"".join(digests)))


def hash_blocks(
    tokens: Sequence[int],
    block_size: int,
    extra_key: Optional[bytes] = None,
    parent: Optional[SequenceHash] = None,
) -> Tuple[List[BlockHash], List[SequenceHash]]:
    """Block and sequence hashes of every *complete* block of ``tokens``,
    chained from ``parent`` (the sequence hash of the block before the first,
    None at the root)."""
    step = 8 * block_size
    words = _le64(tokens)
    salt = b"\x00" + extra_key if extra_key else None
    link = b"root" if parent is None else _U64.pack(parent)
    blake = _blake2b
    block_digests: List[bytes] = []
    links: List[bytes] = []
    for off in range(0, len(words) - len(words) % step, step):
        h = blake(words[off : off + step], digest_size=8)
        if salt:
            h.update(salt)
        digest = h.digest()
        link = blake(link + digest, digest_size=8).digest()
        block_digests.append(digest)
        links.append(link)
    return _unpack64(block_digests), _unpack64(links)


def compute_block_hash(tokens: Sequence[int], extra_key: Optional[bytes] = None) -> BlockHash:
    payload = _le64(tokens)
    if extra_key:
        payload += b"\x00" + extra_key
    return _U64.unpack(_blake2b(payload, digest_size=8).digest())[0]


def compute_sequence_hashes(
    tokens: Sequence[int],
    block_size: int,
    extra_key: Optional[bytes] = None,
) -> List[SequenceHash]:
    """Sequence hashes for every *complete* block of ``tokens``."""
    return hash_blocks(tokens, block_size, extra_key)[1]


@dataclasses.dataclass(frozen=True)
class TokenBlock:
    tokens: tuple
    block_hash: BlockHash
    sequence_hash: SequenceHash
    parent_hash: Optional[SequenceHash]
    position: int  # block index within the sequence


class TokenBlockSequence:
    """A token id sequence chunked into hashed blocks + a mutable partial tail.

    Stored flat: one token list, one list of block hashes and one of sequence
    hashes; a ``TokenBlock`` is made when one is asked for (``append``'s
    return, ``blocks``). Supports incremental append (decode loop grows the
    sequence one token at a time and new blocks seal as they fill), mirroring
    the reference's TokenBlockSequence (lib/tokens/src/lib.rs).
    """

    def __init__(
        self,
        tokens: Iterable[int] = (),
        block_size: int = 16,
        extra_key: Optional[bytes] = None,
    ):
        if block_size <= 0:
            raise ValueError(f"block_size must be positive, got {block_size}")
        self.block_size = block_size
        self.extra_key = extra_key
        self._tokens: List[int] = list(tokens)
        self._block_hashes: List[BlockHash] = []
        self._seq_hashes: List[SequenceHash] = []
        self._seal()

    # -- growth -------------------------------------------------------------
    def append(self, token: int) -> Optional[TokenBlock]:
        """Add one token; returns the newly sealed block if one completed."""
        self._tokens.append(token)
        if len(self._tokens) % self.block_size == 0:
            return self._block(self._seal())
        return None

    def extend(self, tokens: Iterable[int]) -> List[TokenBlock]:
        self._tokens.extend(tokens)
        return [self._block(i) for i in range(self._seal(), len(self._seq_hashes))]

    def _seal(self) -> int:
        """Hash every whole block past the last sealed one, in one pass;
        returns the index of the first block it sealed."""
        first = len(self._seq_hashes)
        start = first * self.block_size
        if len(self._tokens) - start >= self.block_size:
            block_hashes, seq_hashes = hash_blocks(
                # a whole prompt is hashed where it lies, not from a copy
                self._tokens[start:] if start else self._tokens,
                self.block_size,
                self.extra_key,
                self._seq_hashes[-1] if first else None,
            )
            self._block_hashes += block_hashes
            self._seq_hashes += seq_hashes
        return first

    def _block(self, i: int) -> TokenBlock:
        bs = self.block_size
        return TokenBlock(
            tokens=tuple(self._tokens[i * bs : (i + 1) * bs]),
            block_hash=self._block_hashes[i],
            sequence_hash=self._seq_hashes[i],
            parent_hash=self._seq_hashes[i - 1] if i else None,
            position=i,
        )

    # -- views --------------------------------------------------------------
    @property
    def blocks(self) -> List[TokenBlock]:
        return [self._block(i) for i in range(len(self._seq_hashes))]

    @property
    def tail_tokens(self) -> List[int]:
        return self._tokens[len(self._seq_hashes) * self.block_size :]

    def sequence_hashes(self) -> List[SequenceHash]:
        return list(self._seq_hashes)

    def tokens(self) -> List[int]:
        return list(self._tokens)

    def __len__(self) -> int:
        return len(self._tokens)

    def num_blocks(self) -> int:
        return len(self._seq_hashes)
