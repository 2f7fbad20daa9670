"""Device/HBM introspection — phase-attributed memory gauges.

``device.memory_stats()`` is the accelerator's own allocator telemetry
(bytes in use, peak, limit on TPU/GPU); CPU backends return None, so the
sampler falls back to the two host-side signals that still move when HBM
would — the sum of live JAX buffer bytes (``jax.live_arrays``) and the
process RSS. Every sample is attributed to a *phase* (``train`` /
``aggregate`` / ``stage`` / ``train_agg`` / ``prefetch`` / ``eval``) so
staging-induced growth on the PR 2 prefetch worker is distinguishable
from model growth on the round path.

Each sample lands three ways:

- ``mem/*`` gauges in the metrics registry, labelled ``{phase, ...}``;
- one ``mem_sample`` event (with the round index) in
  ``<run_dir>/health.jsonl`` — the time series ``telemetry doctor`` fits
  its memory-growth slope over;
- the flight-recorder ring, so a crash dump shows where memory stood.

XLA compile-cache behaviour is counted beside it: the process's one
``jax.monitoring`` listener (``spans.install_jax_compile_listener``)
counts compilation-cache hit/miss/request events (``jax/compile_cache_*``;
actual compiles are the ``jax/compile_ms`` histogram's count), so
"round N recompiled" shows up as a counter step, not a mystery stall.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional

from fedml_tpu.telemetry import flight_recorder
from fedml_tpu.telemetry.registry import get_registry
from fedml_tpu.telemetry.spans import install_jax_compile_listener

__all__ = [
    "DeviceStatsSampler",
    "install_compile_cache_counters",
    "memory_snapshot",
    "sample_now",
]

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def install_compile_cache_counters() -> None:
    """Count compilation-cache hits/misses/requests as typed counters
    (``jax/compile_cache_*``; a miss is an entry written, a hit a compile
    skipped). Kept for its callers: it installs the process's one compile
    listener, :func:`~fedml_tpu.telemetry.spans.install_jax_compile_listener`,
    which counts them (idempotent)."""
    install_jax_compile_listener()


def _host_rss_bytes() -> float:
    """Current resident set size (Linux /proc; 0 where unreadable)."""
    try:
        with open("/proc/self/statm") as f:
            return float(int(f.read().split()[1]) * _PAGE_SIZE)
    except (OSError, ValueError, IndexError):
        return 0.0


def memory_snapshot() -> Dict[str, float]:
    """One cross-device memory reading, no gauges touched.

    ``bytes_in_use`` / ``peak_bytes`` / ``bytes_limit`` sum the per-device
    allocator stats where the backend exposes them (TPU/GPU) and stay 0
    on CPU; ``live_buffer_bytes`` (all live jax Arrays) and
    ``host_rss_bytes`` are always populated.
    """
    import jax

    in_use = peak = limit = 0.0
    have_device_stats = False
    for dev in jax.devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        have_device_stats = True
        in_use += float(stats.get("bytes_in_use", 0) or 0)
        peak += float(stats.get("peak_bytes_in_use", 0) or 0)
        limit += float(stats.get("bytes_limit", 0) or 0)
    try:
        live = float(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:  # pragma: no cover - live_arrays is stable API
        live = 0.0
    snap = {
        "bytes_in_use": in_use,
        "peak_bytes": peak,
        "bytes_limit": limit,
        "live_buffer_bytes": live,
        "host_rss_bytes": _host_rss_bytes(),
        "device_stats_available": have_device_stats,
    }
    if limit > 0:
        snap["utilization"] = in_use / limit
    return snap


class DeviceStatsSampler:
    """Phase-attributed memory sampling for a round-based engine.

    ``min_interval_s`` rate-limits per phase so a tight loop (e.g. the
    async server's per-update path) cannot turn introspection into a
    hot-path cost; round loops sample every call by default.
    """

    def __init__(self, registry=None, min_interval_s: float = 0.0):
        # a pinned registry is honored; otherwise resolve per sample, so
        # the long-lived process-global sampler (the prefetch worker's)
        # follows registry resets instead of writing into a dead one
        self._pinned_reg = registry
        self.min_interval_s = float(min_interval_s)
        self._last_sample: Dict[str, float] = {}
        self._lock = threading.Lock()
        install_compile_cache_counters()

    @property
    def _reg(self):
        return self._pinned_reg or get_registry()

    def sample(self, phase: str, round_idx: Optional[int] = None,
               **extra: Any) -> Optional[Dict[str, float]]:
        now = time.time()
        with self._lock:
            last = self._last_sample.get(phase, 0.0)
            if self.min_interval_s and now - last < self.min_interval_s:
                return None
            self._last_sample[phase] = now
        snap = memory_snapshot()
        labels = {"phase": str(phase)}
        self._reg.gauge("mem/device_bytes_in_use", labels=labels).set(
            snap["bytes_in_use"])
        self._reg.gauge("mem/device_peak_bytes", labels=labels).set(
            snap["peak_bytes"])
        self._reg.gauge("mem/bytes_limit", labels=labels).set(
            snap["bytes_limit"])
        self._reg.gauge("mem/live_buffer_bytes", labels=labels).set(
            snap["live_buffer_bytes"])
        self._reg.gauge("mem/host_rss_bytes", labels=labels).set(
            snap["host_rss_bytes"])
        if "utilization" in snap:
            self._reg.gauge("mem/hbm_utilization", labels=labels).set(
                snap["utilization"])
        event = {"kind": "mem_sample", "phase": str(phase), **snap, **extra}
        if round_idx is not None:
            event["round"] = int(round_idx)
        from fedml_tpu.telemetry.health import log_health_event

        log_health_event(event)
        flight_recorder.record(**event)
        # phase samples double as the profile/* refresh tick: the program
        # catalog's live MFU/roofline gauges update on the same cadence
        # the mem/* gauges do, so the live plane streams both together
        from fedml_tpu.telemetry.profiling import pump_profile_gauges

        pump_profile_gauges()
        return snap


_default_sampler: Optional[DeviceStatsSampler] = None
_default_lock = threading.Lock()


def sample_now(phase: str, round_idx: Optional[int] = None,
               **extra: Any) -> Optional[Dict[str, float]]:
    """Sample through a shared process-global sampler — the entry point
    for call sites that don't own an engine (the prefetch worker)."""
    global _default_sampler
    with _default_lock:
        if _default_sampler is None:
            _default_sampler = DeviceStatsSampler()
        sampler = _default_sampler
    return sampler.sample(phase, round_idx, **extra)
