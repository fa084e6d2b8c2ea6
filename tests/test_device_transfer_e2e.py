"""Cross-PROCESS device-to-device KV transfer e2e.

Two real OS processes: a source engine (tests/_kv_src_helper.py) prefills a
prompt and serves kv_fetch; this process's destination engine fetches the
pages. The source is NOT in this process's LOCAL_SERVERS, so the fetch takes
the wire control round-trip, receives a device offer, and pulls the pages
through PJRT's transfer server — device buffers crossing process boundaries
with no host staging in the protocol (reference NIXL,
docs/design_docs/disagg_serving.md:20,54)."""

import asyncio
import os
import subprocess
import sys
import time
import zlib

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4
PROMPT = list(range(50, 50 + 5 * BS))


def test_cross_process_device_pull(tmp_path):
    asyncio.run(asyncio.wait_for(_run(tmp_path), timeout=400))


async def _run(tmp_path):
    log_path = str(tmp_path / "src.log")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tests", "_kv_src_helper.py")],
        stdout=open(log_path, "wb"), stderr=subprocess.STDOUT,
        env=env, cwd=REPO,
    )
    try:
        deadline = time.monotonic() + 300
        line = None
        while time.monotonic() < deadline:
            content = open(log_path, "rb").read().decode(errors="replace")
            for ln in content.splitlines():
                if ln.startswith("KV_SRC_READY"):
                    line = ln
                    break
            if line:
                break
            if proc.poll() is not None:
                raise AssertionError(f"src died rc={proc.returncode}:\n{content[-4000:]}")
            await asyncio.sleep(0.25)
        assert line, "source never became ready"
        _, addr, src_crc = line.split()

        import jax.numpy as jnp

        from dynamo_tpu.engine import transfer as xfer
        from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
        from dynamo_tpu.models.llama import LlamaConfig
        from dynamo_tpu.parallel.mesh import make_mesh
        from dynamo_tpu.tokens import compute_sequence_hashes

        mcfg = LlamaConfig(
            vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128, dtype=jnp.float32,
        )
        cfg = TpuEngineConfig(
            model=mcfg, num_blocks=32, block_size=BS, max_batch_size=2,
            max_context=128, prefill_buckets=(16, 32, 64, 128), tp=2,
        )
        dst = TpuEngine(cfg, mesh=make_mesh(tp=2, devices=jax.devices()[:2]))
        try:
            assert addr not in xfer.LOCAL_SERVERS  # genuinely cross-process
            hashes = compute_sequence_hashes(PROMPT, BS)[: (len(PROMPT) - 1) // BS]
            got = await dst._get_transfer_client().fetch_and_import(addr, hashes)
            assert got == len(hashes) * BS
            # the pull really crossed the device plane
            assert xfer._proc_xfer_conns, "no transfer-server connection made"

            ids = dst.allocator.acquire_prefix(hashes)
            crc = 0
            for kc, vc in zip(dst.k_caches, dst.v_caches):
                crc = zlib.crc32(np.asarray(kc[np.asarray(ids)]).tobytes(), crc)
                crc = zlib.crc32(np.asarray(vc[np.asarray(ids)]).tobytes(), crc)
            dst.allocator.release(ids)
            assert str(crc) == src_crc, "imported pages differ from source pages"
        finally:
            dst.stop()
    finally:
        proc.kill()
        proc.wait(timeout=30)
