"""Out-of-process weight ownership: weights survive engine crashes.

TPU-native analog of the reference's gpu_memory_service
(lib/gpu_memory_service/README.md:1-50): there, a separate owner process
holds model weights in CUDA VMM so worker crashes don't lose them and
respawned workers *import* instead of reloading. CUDA VMM has no TPU
equivalent — TPU HBM is owned by the runtime, not mappable across
processes — so the survey-prescribed analog (SURVEY §2.4) applies at the
host layer:

- A **weight owner** process parses checkpoints ONCE and publishes each
  tensor as an mmap-able ``.npy`` file in a tmpfs directory (``/dev/shm``):
  host shared memory with filesystem naming.
- Workers **import** over a unix socket: the owner replies with the
  manifest directory; the worker maps the tensors zero-copy (no safetensors
  parse, no dtype casts, no disk I/O) and ``device_put``s straight from the
  shared pages.
- Imports are leased per connection: a worker killed with SIGKILL drops its
  socket and the owner reclaims its references, exactly like the
  reference's ownership handshake. Weight sets with live references refuse
  eviction.

The on-disk format is the warm-cache manifest (engine/warm.py) so the two
restore paths — same-process warm restart and cross-process import — share
one layout and one loader (``warm.load_manifest_dir``).

Wire protocol: JSON lines over a unix socket. Ops: import / release /
evict / stat / shutdown.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import shutil
import socket
import time
from typing import Any, Dict, Optional

from ..runtime.logging import get_logger
from .warm import WarmWeightCache, _fingerprint, load_manifest_dir

log = get_logger("engine.weight_service")

DEFAULT_ROOT = os.environ.get("DTPU_WEIGHT_SHM", "/dev/shm/dtpu_weights")


def _cfg_to_obj(cfg: Any) -> Optional[Dict[str, Any]]:
    if cfg is None:
        return None
    d = dataclasses.asdict(cfg)
    d["__kind__"] = type(cfg).__name__  # LlamaConfig / MoeConfig dispatch
    dt = d.get("dtype")
    if dt is not None and not isinstance(dt, str):
        import numpy as np

        d["dtype"] = np.dtype(dt).name if not hasattr(dt, "__name__") else dt.__name__
    return d


def _cfg_from_obj(obj: Optional[Dict[str, Any]]) -> Any:
    if obj is None:
        return None
    d = dict(obj)
    kind = d.pop("__kind__", "LlamaConfig")
    if kind == "MoeConfig":
        from ..models.moe import MoeConfig as cls
    elif kind == "MlaConfig":
        from ..models.mla import MlaConfig as cls
    elif kind == "GptOssConfig":
        from ..models.gptoss import GptOssConfig as cls
    else:
        from ..models.llama import LlamaConfig as cls
    dt = d.get("dtype")
    if isinstance(dt, str):
        import jax.numpy as jnp

        d["dtype"] = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                      "float16": jnp.float16}.get(dt, jnp.bfloat16)
    return cls(**d)


@dataclasses.dataclass
class _WeightSet:
    source: str
    dir: str
    refs: int = 0
    bytes: int = 0
    loaded_at: float = 0.0
    load_s: float = 0.0


class WeightOwner:
    """The owner process' server half."""

    def __init__(self, sock_path: str, root: Optional[str] = None):
        self.sock_path = sock_path
        self.root = root or DEFAULT_ROOT
        self.cache = WarmWeightCache(self.root)
        self._sets: Dict[str, _WeightSet] = {}
        self._loads: Dict[str, asyncio.Lock] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._stop = asyncio.Event()

    async def start(self) -> "WeightOwner":
        os.makedirs(self.root, exist_ok=True)
        if os.path.exists(self.sock_path):
            os.unlink(self.sock_path)
        self._server = await asyncio.start_unix_server(
            self._handle, path=self.sock_path
        )
        log.info("weight owner on %s (root %s)", self.sock_path, self.root)
        return self

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        if os.path.exists(self.sock_path):
            try:
                os.unlink(self.sock_path)
            except OSError:
                pass

    async def wait_shutdown(self) -> None:
        await self._stop.wait()

    # -- load ---------------------------------------------------------------
    async def _ensure_loaded(self, source: str, cfg_obj) -> _WeightSet:
        ws = self._sets.get(source)
        if ws is not None:
            return ws
        lock = self._loads.setdefault(source, asyncio.Lock())
        async with lock:
            ws = self._sets.get(source)
            if ws is not None:
                return ws
            t0 = time.monotonic()
            cfg = _cfg_from_obj(cfg_obj)

            def _load():
                from .weights import config_from_hf, load_params

                c = cfg if cfg is not None else config_from_hf(source)
                d = self.cache._dir(_fingerprint(source, c))
                if not os.path.exists(os.path.join(d, "MANIFEST.json")):
                    params = load_params(source, c)
                    d = self.cache.save(source, c, params)
                return d

            d = await asyncio.get_running_loop().run_in_executor(None, _load)
            nbytes = sum(
                os.path.getsize(os.path.join(d, f)) for f in os.listdir(d)
            )
            ws = _WeightSet(
                source=source, dir=d, bytes=nbytes,
                loaded_at=time.time(), load_s=time.monotonic() - t0,
            )
            self._sets[source] = ws
            log.info(
                "weights resident: %s -> %s (%.1f MB, %.2fs)",
                source, d, nbytes / 1e6, ws.load_s,
            )
            return ws

    # -- connection ---------------------------------------------------------
    async def _handle(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        # source -> [weight_set, count]: the set identity is pinned so a
        # force-evict + re-import between a worker's import and its
        # disconnect can't leak this connection's stale references onto the
        # NEW set (which would let a live lease be evicted)
        conn_refs: Dict[str, list] = {}
        try:
            while True:
                line = await reader.readline()
                if not line:
                    break
                try:
                    req = json.loads(line)
                    resp = await self._dispatch(req, conn_refs)
                except Exception as e:  # noqa: BLE001 — protocol error reply
                    resp = {"ok": False, "error": str(e)}
                writer.write(json.dumps(resp).encode() + b"\n")
                await writer.drain()
        except (ConnectionResetError, BrokenPipeError):
            pass
        finally:
            # lease reclaim: a SIGKILLed worker never sent release — its
            # socket EOF returns every reference it held (gms ownership
            # handshake semantics). Only the set the references were taken
            # on is decremented; an evicted-and-replaced set is left alone.
            for src, (ws, n) in conn_refs.items():
                if self._sets.get(src) is ws:
                    ws.refs = max(0, ws.refs - n)
            writer.close()

    async def _dispatch(self, req: dict, conn_refs: Dict[str, list]) -> dict:
        op = req.get("op")
        if op == "import":
            source = req["source"]
            ws = await self._ensure_loaded(source, req.get("cfg"))
            ws.refs += 1
            ent = conn_refs.get(source)
            if ent is not None and ent[0] is ws:
                ent[1] += 1
            else:
                # first import, or the previously-imported set was evicted
                # out from under this connection (its refs died with it)
                conn_refs[source] = [ws, 1]
            return {"ok": True, "dir": ws.dir, "bytes": ws.bytes,
                    "load_s": ws.load_s, "refs": ws.refs}
        if op == "release":
            source = req["source"]
            ws = self._sets.get(source)
            if ws is None:
                return {"ok": False, "error": "unknown weight set"}
            ent = conn_refs.get(source)
            if ent is None or ent[0] is not ws or ent[1] <= 0:
                return {"ok": False, "error": "no reference held"}
            ent[1] -= 1
            ws.refs = max(0, ws.refs - 1)
            return {"ok": True, "refs": ws.refs}
        if op == "evict":
            source = req["source"]
            ws = self._sets.get(source)
            if ws is None:
                return {"ok": False, "error": "unknown weight set"}
            if ws.refs > 0 and not req.get("force"):
                return {"ok": False, "error": f"{ws.refs} live references"}
            del self._sets[source]
            shutil.rmtree(ws.dir, ignore_errors=True)
            return {"ok": True}
        if op == "stat":
            return {"ok": True, "sets": [
                {"source": w.source, "dir": w.dir, "refs": w.refs,
                 "bytes": w.bytes, "load_s": w.load_s}
                for w in self._sets.values()
            ]}
        if op == "shutdown":
            self._stop.set()
            return {"ok": True}
        return {"ok": False, "error": f"unknown op {op!r}"}


class WeightServiceClient:
    """Worker half: sync (engine startup is synchronous). The connection is
    the lease — keep the client open for the worker's lifetime."""

    def __init__(self, sock_path: str, timeout: float = 600.0):
        self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._sock.settimeout(timeout)
        self._sock.connect(sock_path)
        self._buf = b""

    def _call(self, req: dict) -> dict:
        self._sock.sendall(json.dumps(req).encode() + b"\n")
        while b"\n" not in self._buf:
            chunk = self._sock.recv(65536)
            if not chunk:
                raise ConnectionError("weight owner closed the connection")
            self._buf += chunk
        line, self._buf = self._buf.split(b"\n", 1)
        resp = json.loads(line)
        if not resp.get("ok"):
            raise RuntimeError(f"weight service: {resp.get('error')}")
        return resp

    def import_params(self, source: str, cfg: Any = None):
        """Returns (params pytree of zero-copy mmap'd host arrays, info)."""
        resp = self._call({"op": "import", "source": source,
                           "cfg": _cfg_to_obj(cfg)})
        return load_manifest_dir(resp["dir"]), resp

    def release(self, source: str) -> None:
        self._call({"op": "release", "source": source})

    def stat(self) -> list:
        return self._call({"op": "stat"})["sets"]

    def evict(self, source: str, force: bool = False) -> None:
        self._call({"op": "evict", "source": source, "force": force})

    def shutdown_owner(self) -> None:
        self._call({"op": "shutdown"})

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


def load_params_served(
    source: str, cfg: Any = None, sock_path: Optional[str] = None,
    warm_fallback: bool = True,
):
    """Engine-facing loader: import from the weight service when one is
    configured and reachable, else fall back to the local warm-cache path
    (or a plain checkpoint parse when ``warm_fallback`` is off — e.g. the
    engine ran with --no-warm-cache). Returns (params, client-or-None) —
    the caller must keep the client alive (it is the lease) and close it on
    clean shutdown."""
    sock_path = sock_path or os.environ.get("DTPU_WEIGHT_SERVICE")
    if sock_path:
        try:
            client = WeightServiceClient(sock_path)
            params, info = client.import_params(source, cfg)
            log.info(
                "weights imported from owner (%.1f MB shared, owner load %.2fs)",
                info["bytes"] / 1e6, info["load_s"],
            )
            return params, client
        except (OSError, ConnectionError, RuntimeError) as e:
            log.warning("weight service unavailable (%s); loading locally", e)
    if warm_fallback:
        from .warm import load_params_warm

        return load_params_warm(source, cfg), None
    from .weights import load_params

    return load_params(source, cfg), None


def main(argv=None) -> None:
    """``python -m dynamo_tpu.engine.weight_service`` — run a weight owner."""
    import argparse

    p = argparse.ArgumentParser(description="dynamo-tpu weight owner")
    p.add_argument("--sock", required=True, help="unix socket path")
    p.add_argument("--root", default=None, help=f"tmpfs dir (default {DEFAULT_ROOT})")
    p.add_argument("--preload", action="append", default=[],
                   help="checkpoint dir(s) to load at startup")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="force the JAX backend (same flag as the engine "
                        "CLI); default: JAX_PLATFORMS, else cpu")
    args = p.parse_args(argv)
    # the owner never needs a TPU: checkpoint parse + host shm only. A chip
    # belongs to one process at a time, and that process is the engine
    # worker — so unless told otherwise the owner takes the CPU backend,
    # applied BEFORE any jax backend init.
    plat = args.platform or os.environ.get("JAX_PLATFORMS") or "cpu"
    import jax

    jax.config.update("jax_platforms", plat.split(",")[0])

    async def run():
        owner = await WeightOwner(args.sock, args.root).start()
        for src in args.preload:
            await owner._ensure_loaded(src, None)
        try:
            await owner.wait_shutdown()
        finally:
            await owner.stop()

    asyncio.run(run())


if __name__ == "__main__":
    main()
