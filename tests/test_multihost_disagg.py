"""Multihost xPyD e2e: disaggregated prefill/decode where the DECODE engine
is a 2-OS-process jax.distributed group.

The round-4 verdict's #1: the serving shapes that matter — disagg + multi-
process at once — must work together. Flow: HTTP frontend (this process) →
PrefillRouter sends the request to the single-process prefill worker → its
kv_fetch hands the prefix KV to the decode group over the wire → the decode
LEADER imports via the replayed ``kv_scatter`` collective (both decode
processes scatter their shards) → tokens stream back. Reference:
docs/design_docs/disagg_serving.md:67-69.
"""

import asyncio
import os
import signal
import socket
import subprocess
import sys
import time

import aiohttp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "xpd-model"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    return env


def _base_cmd(store_path: str) -> list:
    return [
        sys.executable, "-m", "dynamo_tpu.engine",
        "--platform", "cpu", "--preset", "tiny", "--model", MODEL,
        "--max-batch-size", "2", "--num-blocks", "64", "--max-context", "256",
        "--store", "file", "--store-path", store_path,
        "--event-plane", "inproc",
    ]


def _spawn(cmd: list, log_path: str) -> subprocess.Popen:
    return subprocess.Popen(
        cmd, stdout=open(log_path, "wb"), stderr=subprocess.STDOUT,
        env=_env(), cwd=REPO,
    )


async def _wait_marker(proc, log_path, marker: bytes, timeout: float) -> bytes:
    deadline = time.monotonic() + timeout
    content = b""
    while time.monotonic() < deadline:
        try:
            content = open(log_path, "rb").read()
        except FileNotFoundError:
            content = b""
        if marker in content:
            return content
        if proc.poll() is not None:
            raise AssertionError(
                f"process died rc={proc.returncode}:\n"
                f"{content.decode(errors='replace')[-4000:]}"
            )
        await asyncio.sleep(0.25)
    raise AssertionError(f"no {marker!r} within {timeout}s; saw: {content[-2000:]!r}")


def test_multihost_decode_group_imports_disagg_kv(tmp_path):
    asyncio.run(asyncio.wait_for(_run(tmp_path), timeout=560))


async def _run(tmp_path):
    store_path = str(tmp_path / "store")
    coord, control = _free_port(), _free_port()
    mh = f"127.0.0.1:{coord},2,{{pid}},127.0.0.1:{control}"
    plog = str(tmp_path / "prefill.log")
    flog, llog = str(tmp_path / "follower.log"), str(tmp_path / "leader.log")

    prefill = _spawn(
        _base_cmd(store_path) + ["--disagg", "prefill"], plog
    )
    decode_cmd = _base_cmd(store_path) + [
        "--tp", "2", "--disagg", "decode",
        "--multihost", None,  # placeholder, filled per process
    ]
    follower = _spawn(decode_cmd[:-1] + [mh.format(pid=1)], flog)
    leader = _spawn(decode_cmd[:-1] + [mh.format(pid=0)], llog)
    frontend_rt = watcher = service = None
    try:
        await _wait_marker(prefill, plog, b"TPU_ENGINE_READY", 240)
        await _wait_marker(leader, llog, b"TPU_ENGINE_READY", 300)

        from dynamo_tpu.llm import ModelManager, ModelWatcher
        from dynamo_tpu.llm.http.service import HttpService
        from dynamo_tpu.runtime import (
            DistributedRuntime,
            InProcEventPlane,
            RouterMode,
            RuntimeConfig,
        )

        cfg = RuntimeConfig(
            store="file", store_path=store_path, event_plane="inproc",
            lease_ttl_s=2.0,
        )
        frontend_rt = await DistributedRuntime(
            cfg, event_plane=InProcEventPlane()
        ).start()
        manager = ModelManager()
        watcher = await ModelWatcher(
            frontend_rt, manager, RouterMode.ROUND_ROBIN
        ).start()
        service = HttpService(manager, host="127.0.0.1", port=0)
        await service.start()
        for _ in range(400):
            entry = manager.get(MODEL)
            if (
                entry is not None
                and entry.client.instances
                and entry.prefill_router is not None
            ):
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError("model + prefill pool never appeared")

        async with aiohttp.ClientSession() as s:
            r = await s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={
                    "model": MODEL,
                    "messages": [{
                        "role": "user",
                        "content": "the quick brown fox " * 8,
                    }],
                    "max_tokens": 6,
                    "temperature": 0.0,
                },
                timeout=aiohttp.ClientTimeout(total=300),
            )
            assert r.status == 200, await r.text()
            body = await r.json()
        assert body["usage"]["completion_tokens"] > 0
        # the decode group imported prefix KV computed by the prefill worker
        assert body["usage"].get("cached_tokens", 0) > 0, body["usage"]

        leader.send_signal(signal.SIGTERM)
        assert leader.wait(timeout=60) == 0, (
            open(llog, "rb").read().decode(errors="replace")[-4000:]
        )
        assert follower.wait(timeout=60) == 0, (
            open(flog, "rb").read().decode(errors="replace")[-4000:]
        )
    finally:
        if service is not None:
            await service.stop()
        if watcher is not None:
            await watcher.stop()
        if frontend_rt is not None:
            await frontend_rt.shutdown()
        for p in (prefill, leader, follower):
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
