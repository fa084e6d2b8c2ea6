"""Hierarchical Prometheus metrics.

Analog of the reference's metrics registry hierarchy
DRT -> Namespace -> Component -> Endpoint (lib/runtime/src/metrics.rs) and its
canonical name catalog (lib/runtime/src/metrics/prometheus_names.rs).

Each level of the component tree owns a ``MetricsScope`` that stamps
``dtpu_namespace`` / ``dtpu_component`` / ``dtpu_endpoint`` labels onto every
metric created beneath it, all backed by one ``CollectorRegistry`` per
DistributedRuntime so ``/metrics`` exposes everything in one scrape.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, Optional, Tuple

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

# Canonical metric name fragments (keep in one place, like prometheus_names.rs)
PREFIX = "dtpu"

REQUESTS_TOTAL = f"{PREFIX}_requests_total"
REQUEST_DURATION_SECONDS = f"{PREFIX}_request_duration_seconds"
INFLIGHT_REQUESTS = f"{PREFIX}_inflight_requests"
QUEUED_REQUESTS = f"{PREFIX}_queued_requests"
TTFT_SECONDS = f"{PREFIX}_time_to_first_token_seconds"
ITL_SECONDS = f"{PREFIX}_inter_token_latency_seconds"
INPUT_TOKENS = f"{PREFIX}_input_tokens_total"
OUTPUT_TOKENS = f"{PREFIX}_output_tokens_total"
KV_ACTIVE_BLOCKS = f"{PREFIX}_kv_active_blocks"
KV_TOTAL_BLOCKS = f"{PREFIX}_kv_total_blocks"
KV_HIT_TOKENS = f"{PREFIX}_kv_cached_tokens_total"
# engine step telemetry (engine/telemetry.py): per-step loop observability
KV_FREE_BLOCKS = f"{PREFIX}_kv_free_blocks"
STEP_DURATION_SECONDS = f"{PREFIX}_engine_step_duration_seconds"
STEP_TOKENS = f"{PREFIX}_engine_tokens_per_step"
BATCH_OCCUPANCY = f"{PREFIX}_engine_batch_occupancy"
SPEC_ACCEPTANCE = f"{PREFIX}_engine_spec_acceptance_rate"
SLOW_STEPS_TOTAL = f"{PREFIX}_engine_slow_steps_total"
LOOP_PHASE_SECONDS_TOTAL = f"{PREFIX}_engine_loop_phase_seconds_total"
MOE_LOAD_IMBALANCE = f"{PREFIX}_engine_moe_load_imbalance"
SSM_STATE_BYTES = f"{PREFIX}_engine_ssm_state_bytes"
# resilience (runtime/resilience.py): per-policy retry/breaker observability
KV_WIRE_BANDWIDTH = f"{PREFIX}_kv_wire_bandwidth_bytes_per_s"
PREFILL_DEFLECTED_TOTAL = f"{PREFIX}_prefill_deflected_total"
# SLO accounting plane (runtime/slo.py): per-(model, sla_class) promises
SLO_ATTAINMENT = f"{PREFIX}_slo_attainment_ratio"
SLO_BURN_RATE = f"{PREFIX}_slo_burn_rate"
GOODPUT_TOKENS = f"{PREFIX}_goodput_tokens_total"

# critical-path attribution (runtime/attribution.py): per-request phase
# decomposition that sums to the e2e duration
REQUEST_PHASE_SECONDS = f"{PREFIX}_request_phase_seconds"
# degradation detectors (runtime/health.py): typed, rate-limited events
HEALTH_EVENTS_TOTAL = f"{PREFIX}_health_events_total"

# fleet-wide KV reuse (kvbm/directory.py): global block directory + peer-
# tier fetch accounting
GLOBAL_KV_HITS_TOTAL = f"{PREFIX}_global_kv_hits_total"
GLOBAL_KV_DIRECTORY_ENTRIES = f"{PREFIX}_global_kv_directory_entries"
GLOBAL_KV_DEDUP_BLOCKS_TOTAL = f"{PREFIX}_global_kv_dedup_blocks_total"

# planned reclaims (engine/drain.py, engine/checkpoint.py)
DRAIN_EVACUATED_BLOCKS = f"{PREFIX}_drain_evacuated_blocks_total"
DRAIN_DEADLINE_MARGIN = f"{PREFIX}_drain_deadline_margin_seconds"
CHECKPOINT_RESTORE_MODE = f"{PREFIX}_checkpoint_restore_mode"

RETRY_ATTEMPTS_TOTAL = f"{PREFIX}_retry_attempts_total"
RETRY_GIVEUPS_TOTAL = f"{PREFIX}_retry_giveups_total"
CIRCUIT_STATE = f"{PREFIX}_circuit_state"
CIRCUIT_TRANSITIONS_TOTAL = f"{PREFIX}_circuit_transitions_total"

LABEL_NAMESPACE = "dtpu_namespace"
LABEL_COMPONENT = "dtpu_component"
LABEL_ENDPOINT = "dtpu_endpoint"
LABEL_MODEL = "model"
LABEL_SLA_CLASS = "sla_class"
LABEL_WINDOW = "window"


class MetricsScope:
    """A labelled view over a shared registry; child scopes append labels."""

    def __init__(
        self,
        registry: Optional[CollectorRegistry] = None,
        const_labels: Optional[Dict[str, str]] = None,
        _cache: Optional[Dict[Tuple[str, str], object]] = None,
        _lock: Optional[threading.Lock] = None,
    ):
        self.registry = registry or CollectorRegistry()
        self.const_labels: Dict[str, str] = dict(const_labels or {})
        # metric objects are shared across scopes (prometheus_client forbids
        # re-registering a name), keyed by (kind, name, labelnames)
        self._cache: Dict[Tuple, object] = _cache if _cache is not None else {}
        self._lock = _lock if _lock is not None else threading.Lock()

    def child(self, **labels: str) -> "MetricsScope":
        merged = dict(self.const_labels)
        merged.update(labels)
        return MetricsScope(self.registry, merged, self._cache, self._lock)

    # -- metric constructors ------------------------------------------------
    def _get(self, kind: str, cls, name: str, doc: str, extra_labels: Iterable[str], **kw):
        # prometheus_client allows one collector per name per registry, so the
        # label set is fixed at first creation. Always include the hierarchy
        # labels so creation order (root vs child scope) doesn't matter; the
        # registered labelnames are authoritative on cache hits and _Bound
        # fills any label it has no value for with "".
        labelnames = tuple(
            sorted(
                {LABEL_NAMESPACE, LABEL_COMPONENT, LABEL_ENDPOINT}
                | set(self.const_labels)
                | set(extra_labels)
            )
        )
        with self._lock:
            key = (kind, name)
            entry = self._cache.get(key)
            if entry is None:
                metric = cls(name, doc, labelnames=labelnames, registry=self.registry, **kw)
                self._cache[key] = (metric, labelnames)
            else:
                metric, labelnames = entry
        return metric, labelnames

    def counter(self, name: str, doc: str = "", extra_labels: Iterable[str] = ()):
        metric, labelnames = self._get("counter", Counter, name, doc, extra_labels)
        return _Bound(metric, self.const_labels, labelnames)

    def gauge(self, name: str, doc: str = "", extra_labels: Iterable[str] = ()):
        metric, labelnames = self._get("gauge", Gauge, name, doc, extra_labels)
        return _Bound(metric, self.const_labels, labelnames)

    def histogram(self, name: str, doc: str = "", extra_labels: Iterable[str] = (), buckets=None):
        kw = {"buckets": buckets} if buckets else {}
        metric, labelnames = self._get("histogram", Histogram, name, doc, extra_labels, **kw)
        return _Bound(metric, self.const_labels, labelnames)

    def expose(self) -> bytes:
        return generate_latest(self.registry)


class _Bound:
    """A metric pre-bound to the scope's constant labels; extra labels fill at use."""

    __slots__ = ("_metric", "_const", "_labelnames")

    def __init__(self, metric, const: Dict[str, str], labelnames: Tuple[str, ...]):
        self._metric = metric
        self._const = const
        self._labelnames = labelnames

    def _resolve(self, extra: Dict[str, str]):
        values = {}
        for ln in self._labelnames:
            if ln in extra:
                values[ln] = extra[ln]
            elif ln in self._const:
                values[ln] = self._const[ln]
            else:
                values[ln] = ""
        if not self._labelnames:
            return self._metric
        return self._metric.labels(**values)

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        self._resolve(labels).inc(amount)

    def dec(self, amount: float = 1.0, **labels: str) -> None:
        self._resolve(labels).dec(amount)

    def set(self, value: float, **labels: str) -> None:
        self._resolve(labels).set(value)

    def observe(self, value: float, **labels: str) -> None:
        self._resolve(labels).observe(value)
