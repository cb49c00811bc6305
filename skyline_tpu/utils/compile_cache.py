"""Persistent XLA compilation cache for long-lived processes.

The streaming engine compiles one executable per (capacity-bucket, batch,
dims) shape combination; on the chip a fresh compile costs seconds to tens
of seconds. Enabling JAX's persistent cache lets a restarted
worker (or a repeated benchmark) reuse every previously compiled executable,
collapsing warmup — the operational equivalent of the reference's long-lived
warmed Flink job (its published numbers come from an already-running JVM,
BASELINE.md).
"""

from __future__ import annotations

import os
import threading

# process-wide persistent-cache effectiveness counters, fed by JAX's
# monitoring events (registered once in enable_compile_cache): a rising
# miss count on a warm cache is a retrace regression visible on /metrics
# without running the jaxpr audit
_stats_lock = threading.Lock()
_stats = {"hits": 0, "misses": 0}  # guarded-by: _stats_lock
_listener_registered = False  # guarded-by: _stats_lock

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _on_event(event, **kwargs) -> None:
    if event == _HIT_EVENT:
        with _stats_lock:
            _stats["hits"] += 1
    elif event == _MISS_EVENT:
        with _stats_lock:
            _stats["misses"] += 1


def _register_listener() -> None:
    global _listener_registered
    with _stats_lock:
        if _listener_registered:
            return
        _listener_registered = True
    try:  # jax.monitoring is stable API but guard against slim builds
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
    except Exception:
        pass


def compile_cache_stats() -> dict:
    """{"hits": n, "misses": n} for the bench ``analysis`` block and the
    ``compile_cache.{hits,misses}`` Prometheus counters."""
    with _stats_lock:
        return dict(_stats)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    must not move between runs). Safe to call more than once."""
    import jax

    from skyline_tpu.analysis.registry import env_str

    d = env_str("JAX_COMPILATION_CACHE_DIR")
    if not d:
        checkout = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        d = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    _register_listener()
    return d
