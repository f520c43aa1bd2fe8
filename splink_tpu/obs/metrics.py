"""Metrics registry + jit compile monitor + device memory snapshots.

Three metric kinds (counters, gauges, histogram summaries) plus free-form
``records`` for structured payloads that are data, not scalars (per-column
gamma histograms, largest-block tables). The registry is plain host-side
Python — nothing here touches the jax dataflow.

The compile monitor hangs one process-global listener on
``jax.monitoring``'s duration stream (``/jax/core/compile/*``: jaxpr trace,
MLIR lowering, backend compile). jax offers registration only — listeners
cannot be removed individually — so it is installed once, by the first
linker's ``begin_run``, and accumulates process totals. Each duration also
lands in the run's span table as a ``build`` span under the span that was
open on the compiling thread (utils/profiling.py): that is what splits a
stage's wall time into compile vs execute — the cold-start number the Spark
UI showed as query-planning time.
"""

from __future__ import annotations

import logging
import math
import threading

logger = logging.getLogger("splink_tpu")


class MetricsRegistry:
    """Counters, gauges, histogram summaries and structured records."""

    def __init__(self):
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.histograms: dict[str, dict] = {}
        self.records: dict[str, object] = {}

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        h = self.histograms.setdefault(
            name, {"count": 0, "sum": 0.0, "min": math.inf, "max": -math.inf}
        )
        h["count"] += 1
        h["sum"] += float(value)
        h["min"] = min(h["min"], float(value))
        h["max"] = max(h["max"], float(value))

    def record(self, name: str, payload) -> None:
        self.records[name] = payload

    def snapshot(self) -> dict:
        """One JSON-ready dict of everything recorded so far."""
        hists = {}
        for name, h in self.histograms.items():
            hists[name] = {
                "count": h["count"],
                "sum": h["sum"],
                "min": h["min"] if math.isfinite(h["min"]) else None,
                "max": h["max"] if math.isfinite(h["max"]) else None,
                "mean": (h["sum"] / h["count"]) if h["count"] else None,
            }
        return {
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "histograms": hists,
            "records": dict(self.records),
        }


# ---------------------------------------------------------------------------
# Compile monitor
# ---------------------------------------------------------------------------

_COMPILE_LOCK = threading.Lock()
# ``requests`` counts every trip through XLA's backend_compile entry point;
# ``cache_hits`` counts the subset answered by the persistent compilation
# cache (jax fires backend_compile_duration on a HIT too — the duration is
# the cache deserialize, milliseconds, not a compile); ``aot_restores``
# counts executables restored from a serialized AOT sidecar, which never
# enter backend_compile at all (the engine reports them explicitly via
# :func:`note_aot_restore`). Real compiles = requests - cache_hits.
_COMPILE = {
    "requests": 0,
    "seconds": 0.0,
    "cache_hits": 0,
    "aot_restores": 0,
}
_MONITOR_INSTALLED = False


# jax.monitoring duration event -> the build span it becomes in the run's
# span table (utils/profiling.py)
_BUILD_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower",
    "/jax/core/compile/backend_compile_duration": "jax_backend_compile",
}
# Every jnp function traced INSIDE a kernel's trace fires its own trace
# duration, by the hundred and each well under a millisecond; the kernel's own
# event covers them. Below this a trace is no span (granularity rule).
_MIN_TRACE_S = 1e-3
# a persistent-cache hit is announced (event, then its retrieval seconds)
# BEFORE the backend_compile_duration that wraps it, on the same thread
_PENDING_HIT = threading.local()


def install_compile_monitor() -> None:
    """Install the process-global jax compile listeners (idempotent). They
    fire on compile events only; each duration also becomes a closed
    ``build`` span under whatever span is open on the compiling thread."""
    global _MONITOR_INSTALLED
    if _MONITOR_INSTALLED:
        return
    import jax

    from ..utils.profiling import add_closed

    def _on_duration(name: str, secs: float, **kw) -> None:
        if name == "/jax/compilation_cache/cache_retrieval_time_sec":
            _PENDING_HIT.read_s = secs
            return
        if not name.startswith("/jax/core/compile"):
            return
        backend = name.endswith("backend_compile_duration")
        with _COMPILE_LOCK:
            _COMPILE["seconds"] += secs
            if backend:
                _COMPILE["requests"] += 1
        span = _BUILD_SPANS.get(name)
        if span is None or (span == "jax_trace" and secs < _MIN_TRACE_S):
            return
        counts = {"fun": str(kw.get("fun_name", ""))}
        if backend:
            counts["cache_hit"] = int(getattr(_PENDING_HIT, "hit", 0))
            if counts["cache_hit"]:
                counts["cache_read_s"] = getattr(_PENDING_HIT, "read_s", 0.0)
            _PENDING_HIT.hit, _PENDING_HIT.read_s = 0, 0.0
        add_closed(span, "build", secs, **counts)

    def _on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            _PENDING_HIT.hit = 1
            with _COMPILE_LOCK:
                _COMPILE["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _MONITOR_INSTALLED = True


def note_aot_restore(n: int = 1) -> None:
    """Record ``n`` executables restored from an AOT sidecar (deserialized,
    never compiled — jax emits no monitoring event for these, so the serve
    engine reports them here)."""
    with _COMPILE_LOCK:
        _COMPILE["aot_restores"] += int(n)


def compile_totals() -> tuple[int, float]:
    """(REAL backend compiles, total backend-compile seconds) accumulated
    so far in this process. A persistent-cache hit is NOT a compile: jax
    fires the same backend_compile_duration event for a hit (the cache
    read), which used to inflate this count and trip the zero-recompile
    gates and the compile-stall health signal on a cache-restored replica —
    hits are subtracted here and reported separately by
    :func:`compile_stats`. (0, 0.0) until the monitor is installed."""
    with _COMPILE_LOCK:
        return _COMPILE["requests"] - _COMPILE["cache_hits"], _COMPILE["seconds"]


def compile_requests() -> int:
    """Raw backend_compile entry count (real compiles + persistent-cache
    hits). THE counter for steady-state zero-recompile gates: a hot path
    that re-lowers a warmed shape stalls on trace+lower+cache-read even
    when the persistent cache serves the executable, and a gate on
    :func:`compile_totals` (real compiles only) would miss exactly that
    regression."""
    with _COMPILE_LOCK:
        return _COMPILE["requests"]


def compile_stats() -> dict:
    """The full accounting split: ``requests`` (backend_compile entries),
    ``compiles`` (real backend compiles = requests - cache_hits),
    ``cache_hits`` (persistent-cache restores), ``aot_restores``
    (sidecar-deserialized executables; never touch backend_compile) and
    ``seconds`` (total time inside backend_compile, hits included)."""
    with _COMPILE_LOCK:
        return {
            "requests": _COMPILE["requests"],
            "compiles": _COMPILE["requests"] - _COMPILE["cache_hits"],
            "cache_hits": _COMPILE["cache_hits"],
            "aot_restores": _COMPILE["aot_restores"],
            "seconds": _COMPILE["seconds"],
        }


def device_memory_snapshot() -> list[dict]:
    """Per-device memory stats where the backend reports them (TPU/GPU);
    empty on backends without ``memory_stats`` (CPU). Never raises — this
    is called at stage boundaries on the production path."""
    try:
        import jax

        out = []
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 - per-device probe may not exist
                stats = None
            if not stats:
                continue
            out.append(
                {
                    "device": str(d),
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                }
            )
        return out
    except Exception as e:  # noqa: BLE001 - telemetry must never kill a run
        logger.debug("device memory snapshot unavailable: %s", e)
        return []
