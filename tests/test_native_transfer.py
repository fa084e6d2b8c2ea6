"""C++ transfer agent tests: build, register, scatter/gather fetch.

The native agent is the NIXL-analog data plane (native/transfer/agent.cpp);
these tests exercise the C ABI through the ctypes surface exactly as the
engine does, including concurrent fetches and failure paths."""

import threading

import numpy as np
import pytest

from dynamo_tpu.transfer import NativeAgent, ensure_native, native_fetch

# blocking build: native_available() only kicks `make` off and answers False
# until it lands, which in a fresh checkout would skip this whole file
pytestmark = pytest.mark.skipif(
    not ensure_native(), reason="native toolchain unavailable"
)


@pytest.fixture()
def agent():
    a = NativeAgent(host="127.0.0.1")
    yield a
    a.close()


def test_roundtrip_gather(agent):
    block_bytes = 4096
    arena = np.arange(64 * block_bytes, dtype=np.uint8).reshape(64, block_bytes)
    agent.register(7, arena, block_bytes)
    got = native_fetch("127.0.0.1", agent.port, 7, [3, 60, 0], block_bytes)
    np.testing.assert_array_equal(got[0], arena[3])
    np.testing.assert_array_equal(got[1], arena[60])
    np.testing.assert_array_equal(got[2], arena[0])


def test_large_payload(agent):
    # a realistic KV page batch: 32 blocks x 256 KiB = 8 MiB
    block_bytes = 256 * 1024
    rng = np.random.default_rng(0)
    arena = rng.integers(0, 256, size=(32, block_bytes), dtype=np.uint8)
    agent.register(1, arena, block_bytes)
    ids = list(range(32))
    got = native_fetch("127.0.0.1", agent.port, 1, ids, block_bytes)
    np.testing.assert_array_equal(got, arena)


def test_unknown_region_fails(agent):
    with pytest.raises(RuntimeError):
        native_fetch("127.0.0.1", agent.port, 999, [0], 64)


def test_out_of_range_block_fails(agent):
    arena = np.zeros((4, 64), np.uint8)
    agent.register(2, arena, 64)
    with pytest.raises(RuntimeError):
        native_fetch("127.0.0.1", agent.port, 2, [4], 64)


def test_unregister(agent):
    arena = np.zeros((4, 64), np.uint8)
    agent.register(3, arena, 64)
    agent.unregister(3)
    with pytest.raises(RuntimeError):
        native_fetch("127.0.0.1", agent.port, 3, [0], 64)


def test_concurrent_fetches(agent):
    block_bytes = 64 * 1024
    arena = np.random.default_rng(1).integers(
        0, 256, size=(16, block_bytes), dtype=np.uint8
    )
    agent.register(4, arena, block_bytes)
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        for _ in range(5):
            ids = rng.choice(16, size=8, replace=False)
            got = native_fetch("127.0.0.1", agent.port, 4, list(ids), block_bytes)
            if not np.array_equal(got, arena[ids]):
                errors.append(seed)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors


def test_connection_refused():
    with pytest.raises(RuntimeError):
        native_fetch("127.0.0.1", 1, 0, [0], 64)
