"""Which device this process computes on, and where its compiled programs go.

One place answers the three questions every entry point that compiles for a
device used to answer on its own (engine worker, diffusion worker, profiler,
``run.py``, ``bench.py``, ``chip_smoke.py``):

- is the backend a TPU (``on_tpu`` — the one predicate the kernel selection
  and the interpret switch share);
- what does JAX call the device (``device_info`` — the ``platform`` /
  ``kind`` / ``count`` triple every record about speed carries), and what is
  its published HBM bandwidth (``hbm_bytes_per_s`` — one table keyed by
  ``device_kind``; a device that is not in it is an error, not a default);
- where does the persistent compilation cache live (``compile_cache_dir`` /
  ``enable_compile_cache``).

JAX is imported inside the functions: importing this module initialises no
backend, so a launcher that must leave the chip to its children
(``chip_smoke.py``) can use the cache helper without touching JAX.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# Published peak HBM bandwidth per chip, bytes/s, keyed by
# ``jax.Device.device_kind``. v5e: 819 GB/s (Google Cloud documentation,
# "TPU v5e"). The CPU entry exists for the test suite only: an order of
# magnitude for host DRAM, so the decode-schedule model has an input there.
HBM_BYTES_PER_S: Dict[str, float] = {
    "TPU v5 lite": 819e9,
    "cpu": 5e10,
}


def on_tpu() -> bool:
    """True iff the default JAX backend is platform ``tpu`` — no other
    platform string counts. Initialises the backend on first use."""
    import jax

    return jax.default_backend() == "tpu"


def device_info() -> Dict[str, Any]:
    """``{"platform", "kind", "count"}`` as JAX reports the default backend."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def hbm_bytes_per_s(device) -> float:
    """Published HBM bandwidth of ``device`` from the table above."""
    try:
        return HBM_BYTES_PER_S[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no HBM bandwidth on record for device kind "
            f"{device.device_kind!r} (platform {device.platform!r}); add it, "
            f"with its source, to runtime/device.py HBM_BYTES_PER_S"
        ) from None


def compile_cache_dir() -> Optional[str]:
    """Directory of JAX's persistent compilation cache for this process.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the answer (JAX reads it
    itself; set to the empty string it turns the cache off, which is how the
    CPU test suite runs). Unset, the cache lives at ``<checkout>/.jax_cache``
    — a fixed path, because the path is part of the cache key: a temporary,
    pid- or time-derived directory never hits."""
    if ENV_COMPILE_CACHE in os.environ:
        return os.environ[ENV_COMPILE_CACHE] or None
    return os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Point JAX at ``compile_cache_dir()``; call before the first compile.
    With the env var set nothing is configured in code. Returns the path in
    use (None = cache off)."""
    path = compile_cache_dir()
    if ENV_COMPILE_CACHE not in os.environ:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCacheCounter:
    """Counts this process's persistent-cache traffic from the events JAX
    records: ``hits`` = programs loaded from the cache instead of compiled,
    ``misses`` = programs compiled and written to it (compiles below JAX's
    size/time thresholds are neither). Listens for the process's lifetime —
    create one per process, before the first compile."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self) -> None:
        import jax.monitoring

        self.counts = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event in self._EVENTS:
            self.counts[self._EVENTS[event]] += 1

    def snapshot(self) -> Dict[str, Any]:
        return {"dir": compile_cache_dir(), **self.counts}
