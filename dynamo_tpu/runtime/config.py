"""Layered runtime configuration + centralized environment-variable catalog.

Analog of the reference's figment-based RuntimeConfig (lib/runtime/src/config.rs)
and its ``DYN_*`` env catalog (lib/runtime/src/config/environment_names.rs).
We use a ``DTPU_*`` prefix. Precedence: explicit kwargs > env > defaults
(code that passes a value means it; env configures what code left open).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional

# ---------------------------------------------------------------------------
# Environment variable catalog (single source of truth for names)
# ---------------------------------------------------------------------------

ENV_LOG = "DTPU_LOG"                                  # log level (debug/info/warn/error)
ENV_LOG_JSONL = "DTPU_LOGGING_JSONL"                  # structured JSONL logging on/off
ENV_REQUEST_PLANE = "DTPU_REQUEST_PLANE"              # tcp | http | inproc
ENV_EVENT_PLANE = "DTPU_EVENT_PLANE"                  # zmq | inproc
ENV_STORE = "DTPU_STORE"                              # mem | file | tcp | etcd
ENV_STORE_PATH = "DTPU_STORE_PATH"                    # file path / tcp host:port / etcd endpoint
ENV_SYSTEM_PORT = "DTPU_SYSTEM_PORT"                  # system status server port
ENV_SYSTEM_HOST = "DTPU_SYSTEM_HOST"
ENV_HOST_IP = "DTPU_HOST_IP"                          # advertised host for request plane
ENV_LEASE_TTL_S = "DTPU_LEASE_TTL_S"                  # discovery lease ttl
ENV_NAMESPACE = "DTPU_NAMESPACE"
ENV_KV_BLOCK_SIZE = "DTPU_KV_BLOCK_SIZE"              # tokens per kv block
ENV_ROUTER_REPLICA_SYNC = "DTPU_ROUTER_REPLICA_SYNC"
ENV_MIGRATION_LIMIT = "DTPU_MIGRATION_LIMIT"
ENV_WORKER_GRACEFUL_SHUTDOWN_TIMEOUT = "DTPU_WORKER_GRACEFUL_SHUTDOWN_TIMEOUT"
ENV_CANARY_WAIT_TIME = "DTPU_CANARY_WAIT_TIME"
ENV_KVBM_HOST_CACHE_GB = "DTPU_KVBM_HOST_CACHE_GB"    # G2 host DRAM pool size
ENV_KVBM_DISK_CACHE_GB = "DTPU_KVBM_DISK_CACHE_GB"    # G3 local disk pool size
ENV_KVBM_DISK_PATH = "DTPU_KVBM_DISK_PATH"
ENV_HTTP_PORT = "DTPU_HTTP_PORT"
ENV_BUSY_THRESHOLD = "DTPU_BUSY_THRESHOLD"
# observability (runtime/tracing.py, llm/audit.py)
ENV_AUDIT_SINKS = "DTPU_AUDIT_SINKS"                  # stderr,jsonl:<path>,event
ENV_AUDIT_FORCE_LOGGING = "DTPU_AUDIT_FORCE_LOGGING"  # audit every request
ENV_AUDIT_SUBJECT = "DTPU_AUDIT_SUBJECT"              # event-plane audit topic
ENV_OTLP_ENDPOINT = "DTPU_OTLP_ENDPOINT"              # OTLP/HTTP collector
ENV_TRACE_JSONL = "DTPU_TRACE_JSONL"                  # span JSONL file
# request flight recorder (runtime/flight_recorder.py) + step telemetry
ENV_FLIGHT_CAPACITY = "DTPU_FLIGHT_CAPACITY"          # retained request timelines
ENV_FLIGHT_DUMP = "DTPU_FLIGHT_DUMP"                  # JSONL path for failure dumps
ENV_SLOW_STEP_MS = "DTPU_SLOW_STEP_MS"                # slow-step log threshold
ENV_ASYNC_PREP = "DTPU_ASYNC_PREP"                    # async host step-prep on/off
# SLO accounting (runtime/slo.py)
ENV_SLA_CLASSES = "DTPU_SLA_CLASSES"                  # "interactive:ttft=0.5,itl=0.05;batch:ttft=30"
ENV_SLA_DEFAULT = "DTPU_SLA_DEFAULT"                  # class stamped when a request names none
ENV_SLO_OBJECTIVE = "DTPU_SLO_OBJECTIVE"              # attainment objective for burn rate (0.99)
# lora (lora/cache.py)
ENV_LORA_CACHE = "DTPU_LORA_CACHE"                    # adapter cache dir
# kvbm remote tier (kvbm/remote.py)
ENV_KVBM_REMOTE = "DTPU_KVBM_REMOTE"                  # G4 block store host:port
ENV_CONFIG_FILE = "DTPU_CONFIG"                       # layered config file (json/toml)
# resilience + chaos (runtime/resilience.py, runtime/faults.py).
# Retry/breaker scopes are layered specs: DTPU_RETRY_DEFAULT applies to every
# policy, DTPU_RETRY_<SCOPE> (scope upper-cased, dots -> underscores, e.g.
# DTPU_RETRY_TRANSFER_PULL) overrides per scope; same shape for DTPU_CB_*.
ENV_RETRY_DEFAULT = "DTPU_RETRY_DEFAULT"              # "attempts=3,base=0.05,max=2,timeout=10,deadline=30"
ENV_CB_DEFAULT = "DTPU_CB_DEFAULT"                    # "threshold=5,rate=0.5,window=30,reset=5,half_open=1"
ENV_FAULTS = "DTPU_FAULTS"                            # fault-injection spec, e.g. "transfer.pull:drop@2"
# engine + kernels (engine/engine.py, ops/quant.py, engine/warm.py,
# engine/weight_service.py, parallel/pp_serving.py, runtime/multihost.py)
ENV_MIXED = "DTPU_MIXED"                              # mixed continuous batching on/off/auto
ENV_KV_DTYPE = "DTPU_KV_DTYPE"                        # paged KV cache dtype (int8 opt-in)
ENV_WARM_CACHE = "DTPU_WARM_CACHE"                    # host weight cache dir
ENV_WEIGHT_SERVICE = "DTPU_WEIGHT_SERVICE"            # shared weight service address
ENV_WEIGHT_SHM = "DTPU_WEIGHT_SHM"                    # weight shm segment prefix
ENV_PP_MICROBATCHES = "DTPU_PP_MICROBATCHES"          # pp wavefront microbatch count
ENV_PP_COND_SKIP = "DTPU_PP_COND_SKIP"                # pp conditional bubble skip
ENV_MH_TRACE = "DTPU_MH_TRACE"                        # multihost replay debug trace
# KV transfer plane (engine/transfer.py, transfer/native.py)
ENV_STREAM_WINDOW = "DTPU_STREAM_WINDOW"              # streamed fetch window (blocks)
ENV_STREAM_WAIT_S = "DTPU_STREAM_WAIT_S"              # streamed fetch commit-wait budget
ENV_DEVICE_TRANSFER = "DTPU_DEVICE_TRANSFER"          # device-to-device pull path on/off
ENV_ICI_TRANSFER = "DTPU_ICI_TRANSFER"                # same-process ICI fast path on/off
ENV_XFER_HOST = "DTPU_XFER_HOST"                      # advertised transfer-plane host
ENV_KV_WIRE = "DTPU_KV_WIRE"                          # advertised kv wire class (ici/tcp/...)
# router scale (kv_router/scheduler.py, docs/operations.md 9b)
ENV_ROUTER_TOPK = "DTPU_ROUTER_TOPK"                  # two-stage routing candidate K
ENV_ROUTER_SHARDS = "DTPU_ROUTER_SHARDS"              # postings/snapshot index shards
ENV_ROUTER_POSTINGS_BUCKET = "DTPU_ROUTER_POSTINGS_BUCKET"  # per-block postings cap
# disagg routing + prefill deflection (llm/prefill_router.py, PR 10 knobs)
ENV_STREAM_KV = "DTPU_STREAM_KV"                      # streamed (vs sequential) disagg dispatch
ENV_DEFLECT = "DTPU_DEFLECT"                          # prefill deflection valve on/off
ENV_DEFLECT_MAX_TOKENS = "DTPU_DEFLECT_MAX_TOKENS"    # short-prompt deflection bound
ENV_DEFLECT_OVERLAP = "DTPU_DEFLECT_OVERLAP"          # decode-pool radix-hit deflection share
ENV_DEFLECT_MARGIN = "DTPU_DEFLECT_MARGIN"            # load-skew deflection margin
ENV_PREFILL_BLOCK_MS = "DTPU_PREFILL_BLOCK_MS"        # per-block prefill cost prior
ENV_KV_BYTES_PER_BLOCK = "DTPU_KV_BYTES_PER_BLOCK"    # wire-cost bytes/block override
# fleet-wide KV reuse (kvbm/directory.py, llm/prefill_router.py): the global
# content-addressed block directory over the discovery plane + the
# fetch-vs-recompute decision (ops/costs.py)
ENV_GLOBAL_KV = "DTPU_GLOBAL_KV"                      # global KV directory on/off
ENV_GLOBAL_KV_TTL_S = "DTPU_GLOBAL_KV_TTL_S"          # directory entry ttl (s)
ENV_GLOBAL_KV_DEDUPE = "DTPU_GLOBAL_KV_DEDUPE"        # max advertised holders per hash
ENV_GLOBAL_KV_FETCH_MARGIN = "DTPU_GLOBAL_KV_FETCH_MARGIN"  # fetch <= margin*recompute gate
# fleet observability plane (runtime/health.py detectors, llm/fleet.py
# /debug/fleet fan-out)
ENV_FLEET_FANOUT = "DTPU_FLEET_FANOUT"                # /debug/fleet concurrent worker fetches
ENV_FLEET_TIMEOUT_S = "DTPU_FLEET_TIMEOUT_S"          # per-worker snapshot fetch timeout (s)
ENV_HEALTH_MIN_INTERVAL_S = "DTPU_HEALTH_MIN_INTERVAL_S"  # min s between health events per subject
ENV_HEALTH_DRIFT_RATIO = "DTPU_HEALTH_DRIFT_RATIO"    # measured/predicted step-time trip ratio
# planned reclaims + checkpoint/restore (engine/drain.py, engine/checkpoint.py)
ENV_DRAIN_DEADLINE_S = "DTPU_DRAIN_DEADLINE_S"        # default reclaim deadline (s)
ENV_DRAIN_MARGIN_S = "DTPU_DRAIN_MARGIN_S"            # stop evacuating this early (s)
ENV_CKPT_DIR = "DTPU_CKPT_DIR"                        # G3 checkpoint directory
ENV_CKPT_MAX_BLOCKS = "DTPU_CKPT_MAX_BLOCKS"          # sealed blocks per checkpoint cap
# model hub + media fetch (llm/hub.py, llm/media.py)
ENV_HUB_CACHE = "DTPU_HUB_CACHE"                      # checkpoint cache dir
ENV_HUB_OFFLINE = "DTPU_HUB_OFFLINE"                  # forbid hub network fetches
ENV_MEDIA_FILE_ROOT = "DTPU_MEDIA_FILE_ROOT"          # multimodal file:// jail root

_TRUTHY = {"1", "true", "yes", "on", "enabled"}
_FALSEY = {"0", "false", "no", "off", "disabled", ""}


def is_truthy(val: Optional[str]) -> bool:
    """Permissive env-var boolean parsing (reference: lib/config/src/lib.rs:20)."""
    if val is None:
        return False
    return val.strip().lower() in _TRUTHY


def is_falsey(val: Optional[str]) -> bool:
    if val is None:
        return True
    return val.strip().lower() in _FALSEY


def env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


def env_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return int(raw)
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def env_bool(name: str, default: bool = False) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return is_truthy(raw)


@dataclasses.dataclass
class RuntimeConfig:
    """Top-level runtime knobs; every field has an env override."""

    request_plane: str = "tcp"           # tcp | http | inproc
    event_plane: str = "zmq"             # zmq | inproc
    store: str = "mem"                   # mem | file | etcd
    store_path: str = "/tmp/dtpu_store"
    host_ip: str = "127.0.0.1"
    system_port: int = 0                 # 0 = disabled
    lease_ttl_s: float = 10.0
    graceful_shutdown_timeout_s: float = 30.0

    @classmethod
    def from_env(cls, **overrides: Any) -> "RuntimeConfig":
        """Layered resolution (figment analog, lib/runtime/src/config.rs):
        defaults < config file (DTPU_CONFIG, json/toml) < env < kwargs."""
        base: Dict[str, Any] = {}
        cfg_file = os.environ.get(ENV_CONFIG_FILE)
        if cfg_file:
            base.update(load_config_file(cfg_file))
        def layered(field: str, env_name: str, conv) -> Any:
            default = getattr(cls, field)
            if field in base:
                # file values get the same coercion as env values (a JSON
                # string "9100" for a port must not flow through as str)
                try:
                    default = conv(base[field])
                except (TypeError, ValueError):
                    pass
            raw = os.environ.get(env_name)
            if raw is None or raw == "":
                return default
            try:
                return conv(raw)
            except (TypeError, ValueError):
                return default

        cfg = cls(
            request_plane=layered("request_plane", ENV_REQUEST_PLANE, str),
            event_plane=layered("event_plane", ENV_EVENT_PLANE, str),
            store=layered("store", ENV_STORE, str),
            store_path=layered("store_path", ENV_STORE_PATH, str),
            host_ip=layered("host_ip", ENV_HOST_IP, str),
            system_port=layered("system_port", ENV_SYSTEM_PORT, int),
            lease_ttl_s=layered("lease_ttl_s", ENV_LEASE_TTL_S, float),
            graceful_shutdown_timeout_s=layered(
                "graceful_shutdown_timeout_s",
                ENV_WORKER_GRACEFUL_SHUTDOWN_TIMEOUT, float,
            ),
        )
        for k, v in overrides.items():
            if v is not None:
                setattr(cfg, k, v)
        return cfg

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def load_config_file(path: str) -> Dict[str, Any]:
    """json or toml (stdlib tomllib); unknown keys are ignored by callers."""
    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".toml"):
        try:
            import tomllib  # py3.11+
        except ImportError:
            try:
                import tomli as tomllib  # type: ignore[no-redef]
            except ImportError:
                import toml

                return toml.loads(raw.decode())
        return tomllib.loads(raw.decode())
    import json

    return json.loads(raw.decode())
