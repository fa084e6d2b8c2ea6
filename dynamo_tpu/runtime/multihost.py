"""Multi-process (multi-host) execution context.

The TPU analog of the reference's one-logical-worker-per-parallel-group model
(reference components/src/dynamo/vllm/main.py:67: non-leader ranks of a TP
group idle inside the engine while rank 0 owns the endpoint): in JAX's
multi-controller model EVERY process must issue the same XLA programs over the
shared mesh, so "idling" followers are really a replay loop.

  - process 0 (leader) owns the control plane: discovery registration, the
    request plane endpoint, the scheduler, and every host-side decision.
  - processes 1..N-1 (followers) join the same ``jax.distributed`` cluster,
    hold their own handles of the globally-sharded state (params, KV caches,
    sampling tables), and replay each dispatch the leader broadcasts so the
    collective programs line up across processes.

The broadcast channel is a plain TCP fan-out (length-prefixed msgpack), NOT
the request plane: dispatch replay is a lockstep data-path concern, ordered
and point-to-point, with no discovery or retry semantics — the same reason
the reference runs NCCL alongside (not through) its NATS/etcd control plane.

Wire format: one frame per dispatch ``{"op": name, "a": [encoded args]}``.
numpy arrays ride as ``{"__nd__": [dtype.str, shape, bytes]}``; the sentinel
``{"__carry__": key}`` tells the follower to substitute its device-resident
carry state (decode horizon chaining never round-trips through the host —
engine/engine.py _dispatch_horizon).
"""

from __future__ import annotations

import os
import socket
import struct
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import msgpack
import numpy as np

from .logging import get_logger

log = get_logger("runtime.multihost")

_LEN = struct.Struct("!I")
_TRACE = os.environ.get("DTPU_MH_TRACE") == "1"


def _trace(fmt: str, *args) -> None:
    if _TRACE:
        import sys

        print("[mh] " + (fmt % args), file=sys.stderr, flush=True)


@dataclass
class MultihostSpec:
    """Parsed ``--multihost coord:port,nprocs,proc_id[,control:port]``."""

    coordinator: str
    num_processes: int
    process_id: int
    control: str  # host:port the leader's control channel binds/dials

    @classmethod
    def parse(cls, text: str) -> "MultihostSpec":
        parts = text.split(",")
        if len(parts) < 3:
            raise ValueError(
                "--multihost wants coord_host:port,num_processes,process_id"
                "[,control_host:port]"
            )
        coord, nprocs, pid = parts[0], int(parts[1]), int(parts[2])
        if len(parts) > 3:
            control = parts[3]
        else:
            # default control port: coordinator port + 1 on the same host
            host, _, port = coord.rpartition(":")
            control = f"{host}:{int(port) + 1}"
        return cls(coord, nprocs, pid, control)


def _encode_arg(a: Any) -> Any:
    # dtype.name (not .str): extension dtypes like ml_dtypes' bfloat16 have
    # no char code — .str degrades to raw void ('|V2') which jit rejects —
    # but their registered NAME round-trips through np.dtype()
    if isinstance(a, np.ndarray):
        return {"__nd__": [a.dtype.name, list(a.shape), a.tobytes()]}
    if isinstance(a, (np.generic,)):  # 0-d scalar (np.int32(3), np.bool_(True))
        arr = np.asarray(a)
        return {"__nd0__": [arr.dtype.name, arr.tobytes()]}
    return a


def _decode_arg(a: Any) -> Any:
    if isinstance(a, dict):
        if "__nd__" in a:
            dt, shape, raw = a["__nd__"]
            return np.frombuffer(raw, dtype=np.dtype(dt)).reshape(shape)
        if "__nd0__" in a:
            dt, raw = a["__nd0__"]
            return np.frombuffer(raw, dtype=np.dtype(dt))[0]
    return a


class MultihostContext:
    """Owns the jax.distributed membership + the dispatch broadcast channel."""

    def __init__(self, spec: MultihostSpec):
        self.spec = spec
        self._socks: List[socket.socket] = []  # leader: one per follower
        self._sock: Optional[socket.socket] = None  # follower: to leader
        self._rbuf = b""
        self._lock = threading.Lock()
        self._closed = False
        self._router: Optional["MultihostRouter"] = None

    @property
    def router(self) -> "MultihostRouter":
        """The process-wide dispatch router (one per group membership)."""
        if self._router is None:
            self._router = MultihostRouter(self)
        return self._router

    # ------------------------------------------------------------ membership
    @property
    def is_leader(self) -> bool:
        return self.spec.process_id == 0

    @property
    def num_processes(self) -> int:
        return self.spec.num_processes

    def initialize_jax(self) -> None:
        """Join the jax.distributed cluster (must run before device use)."""
        import jax

        jax.distributed.initialize(
            coordinator_address=self.spec.coordinator,
            num_processes=self.spec.num_processes,
            process_id=self.spec.process_id,
        )
        log.info(
            "joined jax cluster as process %d/%d (%d local / %d global devices)",
            self.spec.process_id, self.spec.num_processes,
            jax.local_device_count(), jax.device_count(),
        )

    # --------------------------------------------------------- control plane
    def start_control(self, timeout_s: float = 60.0) -> None:
        """Leader: accept one connection per follower. Follower: dial."""
        host, _, port = self.spec.control.rpartition(":")
        port = int(port)
        if self.is_leader:
            srv = socket.create_server((host, port), reuse_port=False)
            deadline = time.monotonic() + timeout_s
            try:
                pending = self.spec.num_processes - 1
                seen: Dict[int, socket.socket] = {}
                while len(seen) < pending:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"only {len(seen)}/{pending} followers dialed in"
                        )
                    srv.settimeout(remaining)
                    conn, _addr = srv.accept()
                    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    # bound the hello read too: a stray connection (port
                    # scanner, dead follower) must not wedge startup — drop
                    # it and keep accepting
                    conn.settimeout(5.0)
                    try:
                        hello = b""
                        while len(hello) < 4:
                            part = conn.recv(4 - len(hello))
                            if not part:
                                raise ConnectionError("hello truncated")
                            hello += part
                        (pid,) = _LEN.unpack(hello)
                    except (OSError, ConnectionError) as e:
                        log.warning("control dial-in rejected: %s", e)
                        conn.close()
                        continue
                    conn.settimeout(None)  # dispatch gaps are unbounded
                    seen[pid] = conn
                # deterministic fan-out order
                self._socks = [seen[k] for k in sorted(seen)]
            finally:
                srv.close()
        else:
            deadline = time.monotonic() + timeout_s
            last: Optional[Exception] = None
            while time.monotonic() < deadline:
                try:
                    s = socket.create_connection((host, port), timeout=5.0)
                    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    # connect timeout must NOT linger: recv() blocks across
                    # arbitrarily long idle gaps between dispatches
                    s.settimeout(None)
                    s.sendall(_LEN.pack(self.spec.process_id))
                    self._sock = s
                    return
                except OSError as e:  # leader not up yet
                    last = e
                    time.sleep(0.2)
            raise TimeoutError(f"control channel dial failed: {last}")

    def broadcast(self, op: str, args: List[Any]) -> None:
        """Leader: fan one dispatch out to every follower, in order.

        Fails FAST on the first dead socket: the leader will not execute the
        op either, so delivering the frame to later survivors would only
        push them into a collective the leader (and the dead peer) never
        join. Survivors that already received it may wedge mid-collective —
        unrecoverable in-process (XLA collectives have no cancel); the
        jax.distributed coordination-service timeout reaps them, and the
        follower-death teardown (watch_followers → group close → supervisor
        restart) handles the rest.
        """
        payload = msgpack.packb(
            {"op": op, "a": [_encode_arg(a) for a in args]}, use_bin_type=True
        )
        frame = _LEN.pack(len(payload)) + payload
        with self._lock:
            for s in self._socks:
                try:
                    s.sendall(frame)
                except OSError as e:
                    raise ConnectionError(
                        f"follower unreachable during broadcast of {op!r}: {e}"
                    ) from e

    def watch_followers(self, on_death: Callable[[], None]) -> None:
        """Leader: detect follower death between dispatches.

        Followers never send after their hello, so a readable control socket
        means EOF (process died / connection reset). One background thread
        select()s on all follower sockets; the first death fires ``on_death``
        once and the thread exits — the group is unrecoverable (the dead
        process held mesh shards; any later collective would hang), so the
        caller's job is to deregister and exit for a supervisor restart.
        Reference analog: vllm engine_monitor killing the worker when an
        engine rank dies (components/src/dynamo/vllm/engine_monitor.py).
        """
        import select

        def run() -> None:
            socks = list(self._socks)
            while not self._closed and socks:
                try:
                    r, _, x = select.select(socks, [], socks, 1.0)
                except (OSError, ValueError):
                    return  # sockets closed under us: normal group stop
                dead = False
                for s in set(r) | set(x):
                    try:
                        if not s.recv(1):
                            dead = True
                    except OSError:
                        dead = True
                if dead:
                    if not self._closed:
                        log.error("multihost follower died; tearing down group")
                        on_death()
                    return

        threading.Thread(target=run, daemon=True, name="mh-follower-watch").start()

    def recv(self) -> Dict[str, Any]:
        """Follower: block for the next dispatch frame."""
        assert self._sock is not None
        while True:
            if len(self._rbuf) >= 4:
                (n,) = _LEN.unpack(self._rbuf[:4])
                if len(self._rbuf) >= 4 + n:
                    raw = self._rbuf[4 : 4 + n]
                    self._rbuf = self._rbuf[4 + n :]
                    msg = msgpack.unpackb(raw, raw=False)
                    msg["a"] = [_decode_arg(a) for a in msg.get("a", [])]
                    return msg
            chunk = self._sock.recv(1 << 20)
            if not chunk:
                raise ConnectionError("control channel closed by leader")
            self._rbuf += chunk

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.is_leader:
            try:
                self.broadcast("__stop__", [])
            except OSError:
                pass
        for s in self._socks:
            try:
                s.close()
            except OSError:
                pass
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass

    def shutdown_jax(self) -> None:
        import jax

        try:
            jax.distributed.shutdown()
        except Exception:  # already torn down / never initialized
            pass


CARRY = "__carry__"


class MultihostRouter:
    """Process-level dispatch fabric: ONE broadcast channel, one total order,
    any number of engine replay tables (dp ranks, disagg roles) multiplexed
    by a namespace prefix on the op name (``dp1:decode``).

    Dispatches come from more than one thread (each engine's step executor
    AND the asyncio loop thread); broadcast + local XLA dispatch happen under
    ONE process-wide lock so every process executes the same total order —
    jit returns after async-enqueue, so the hold is ~ms.
    """

    def __init__(self, mh: MultihostContext):
        self.mh = mh
        self._tables: Dict[str, "MultihostOps"] = {}
        self._closed = False
        self.dispatch_lock = threading.Lock()

    def table(
        self,
        state_get: Dict[str, Callable[[], Any]],
        state_set: Dict[str, Callable[[Any], None]],
        ns: str = "",
    ) -> "MultihostOps":
        if ns in self._tables:
            raise ValueError(f"multihost namespace {ns!r} already registered")
        ops = MultihostOps(self, ns, state_get, state_set)
        self._tables[ns] = ops
        return ops

    def close(self, timeout_s: float = 5.0) -> None:
        """Stop the group, serialized against in-flight dispatches.

        Taking the dispatch lock means any dispatch racing this close either
        fully broadcast+executed BEFORE the __stop__ frame (the follower
        replays it, then exits) or is rejected after — a late collective
        executed by the leader alone would block forever waiting for peers.
        Idempotent: every engine of a dp group calls it on stop.

        The lock acquire is BOUNDED: on a follower-death teardown a dispatch
        may be wedged mid-broadcast holding the lock; after ``timeout_s`` we
        close anyway (slamming the sockets makes the wedged sendall raise,
        failing that dispatch — correct in a death scenario).
        """
        got = self.dispatch_lock.acquire(timeout=timeout_s)
        try:
            if self._closed:
                return
            self._closed = True
            self.mh.close()
        finally:
            if got:
                self.dispatch_lock.release()

    @property
    def closed(self) -> bool:
        return self._closed

    def follow(self) -> None:
        """Follower body: replay dispatches (all namespaces) until stop."""
        while True:
            msg = self.mh.recv()
            op = msg["op"]
            _trace("follower: recv %s", op)
            if op == "__stop__":
                return
            ns, _, name = op.rpartition(":")
            self._tables[ns].replay(name, msg)


class MultihostOps:
    """Per-engine dispatch replay table (one namespace of the router).

    Each op is registered with:
      - ``state_in``:  {arg_pos: state_name} — args the follower substitutes
        with its OWN handle of the shared global array (params, caches, ...)
      - ``state_out``: {out_pos: state_name} — outputs both sides store back
        (donated caches, penalty tables, the decode carry)
      - ``carry_in``:  {arg_pos: state_name} — args that are EITHER a host
        resync value (numpy → broadcast by value) or the device carry of the
        previous dispatch (jax.Array → broadcast as a carry sentinel)

    The leader-side wrapper converts every non-state arg to host numpy before
    both the broadcast AND the local call: in multi-controller JAX a committed
    single-device array cannot feed a mesh-spanning computation, while plain
    numpy shards consistently on every process.
    """

    def __init__(self, router: MultihostRouter, ns: str,
                 state_get: Dict[str, Callable[[], Any]],
                 state_set: Dict[str, Callable[[Any], None]]):
        self.router = router
        self.ns = ns
        self.mh = router.mh
        self._get = state_get
        self._set = state_set
        self._ops: Dict[str, tuple] = {}
        self._carry: Dict[str, Any] = {}

    def close(self) -> None:
        self.router.close()

    def register(self, name: str, fn: Callable, state_in: Dict[int, str],
                 state_out: Dict[int, str], carry_in: Optional[Dict[int, str]] = None):
        self._ops[name] = (fn, state_in, state_out, carry_in or {})

    # ------------------------------------------------------------- leader side
    def leader_fn(self, name: str) -> Callable:
        fn, state_in, state_out, carry_in = self._ops[name]
        mh = self.mh
        wire_name = f"{self.ns}:{name}"

        def dispatch(*args):
            import jax

            send: List[Any] = []
            call: List[Any] = list(args)
            for i, a in enumerate(args):
                if i in state_in:
                    continue  # follower substitutes its own handle
                if i in carry_in and isinstance(a, jax.Array):
                    send.append({CARRY: carry_in[i]})
                    continue
                host = (
                    a if isinstance(a, (int, float, bool, type(None)))
                    else np.asarray(a)
                )
                send.append(
                    _encode_arg(host)
                    if isinstance(host, (np.ndarray, np.generic)) else host
                )
                call[i] = host
            with self.router.dispatch_lock:
                if self.router.closed:
                    raise RuntimeError(
                        f"multihost group stopped; dropping dispatch {name!r}"
                    )
                _trace("leader: broadcast %s", wire_name)
                mh.broadcast(wire_name, send)
                out = fn(*call)
                _trace("leader: dispatched %s", wire_name)
                return out

        # the program under the broadcast: the launch ledger reads its name
        # and its cache (engine/telemetry.py launch)
        dispatch.jitted = getattr(fn, "jitted", fn)
        return dispatch

    # ----------------------------------------------------------- follower side
    def replay(self, op: str, msg: Dict[str, Any]) -> None:
        fn, state_in, state_out, carry_in = self._ops[op]
        data = msg["a"]
        n_args = len(data) + len(state_in)
        args: List[Any] = [None] * n_args
        it = iter(data)
        for i in range(n_args):
            if i in state_in:
                args[i] = self._get[state_in[i]]()
            else:
                a = next(it)
                if isinstance(a, dict) and CARRY in a:
                    args[i] = self._carry[a[CARRY]]
                else:
                    args[i] = a
        out = fn(*args)
        _trace("follower: executed %s:%s", self.ns, op)
        outs = out if isinstance(out, tuple) else (out,)
        for pos, sname in state_out.items():
            if sname.startswith("carry_"):
                self._carry[sname] = outs[pos]
            else:
                self._set[sname](outs[pos])

    def follow(self) -> None:
        """Single-table convenience: replay until stop (delegates to the
        router; valid when this is the only namespace)."""
        self.router.follow()
