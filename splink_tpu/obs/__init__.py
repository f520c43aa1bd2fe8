"""Runtime telemetry: structured spans, metrics, EM convergence stream.

The reference implementation leaned on the Spark UI for runtime visibility
(stage timelines, shuffle sizes, skewed blocks) and on driver prints for EM
convergence. This package is the TPU-native replacement: one machine-readable
JSONL record per run describing where time went (compile vs execute), how EM
converged, which blocks dominated, and which resilience events fired.

Layers (each importable on its own, none imports jax at module scope):

  * :mod:`.events`  — thread-safe JSONL event sink + the ambient ``publish``
    hook the resilience stack emits through (zero-cost no-op when no sink
    is registered).
  * :mod:`.tracer`  — the record's span events (run -> call -> stage ->
    sub-span / EM iteration; ``utils.profiling`` keeps the one span table
    and times them) and chrome-trace (Perfetto-loadable) export.
  * :mod:`.metrics` — counters/gauges/histograms, the process-wide jit
    compile monitor (``jax.monitoring`` duration listeners) and device
    memory snapshots.
  * :mod:`.runtime` — :class:`RunContext`, the per-linker object wiring the
    three together; created from the ``telemetry_dir`` settings key.
  * :mod:`.reqtrace` — request-level serve tracing (obs v2): per-request
    span trees whose phase durations sum to the wall latency, sampled via
    ``serve_trace_sample_rate``.
  * :mod:`.slo`     — rolling deadline-hit-rate objectives + multi-window
    error-budget burn rates.
  * :mod:`.exposition` — stdlib Prometheus text endpoint
    (``obs_exposition_port``).
  * :mod:`.flight`  — bounded crash flight recorder, dumped to JSONL on
    breaker-open / worker restart / swap rollback / drift alert / SIGUSR2
    (``obs_flight_records``).
  * :mod:`.quality` — training-reference quality profiles (captured at
    ``build_index`` into the LinkageIndex artifact) + offline EM
    identifiability diagnostics (``quality_profile``).
  * :mod:`.drift`   — serve-time device drift sketches, PSI /
    Jensen-Shannon scoring of rolling windows vs the reference, and the
    two-window drift alerts (``drift_window_s`` / ``drift_alert_psi``).
  * :mod:`.kernelwatch` — serve-time execute-latency regression monitor
    (``perf_alert_ratio`` / ``perf_window_s``): post-warmup anchors,
    two-window p95 alerts, EWMAs and native-histogram series over
    signals the service already collects — the runtime half of the
    performance observatory (:mod:`..analysis.perf_audit` is the CI
    half).
  * :mod:`.cli`     — ``python -m splink_tpu.obs
    summarize|export-trace|attribute|drift|serve-dash|fleet-dash``.

Zero-cost contract: with no sink configured (``telemetry_dir`` empty) the
linker adds NO host callbacks and compiled programs are unchanged — the
trace-audit kernel registry pins this (the plain ``em_step`` kernel allows
no callback primitive at all; the ``em_step_telemetry`` variant declares
the single sanctioned ``io_callback``).

See docs/observability.md for the event schema and CLI usage.
"""

from .drift import DriftMonitor, js_divergence, psi
from .events import EventSink, publish, read_events
from .exposition import (
    ExpositionServer,
    HistogramSample,
    Sample,
    process_samples,
)
from .flight import FlightRecorder
from .kernelwatch import KernelWatch
from .quality import QualityProfile, em_diagnostics
from .metrics import MetricsRegistry, compile_totals, install_compile_monitor
from .reqtrace import PHASES, PhaseProfile, RequestTrace, ServeTracer
from .runtime import RunContext
from .slo import SLOTracker
from .tracer import Tracer, chrome_trace_from_events

__all__ = [
    "EventSink",
    "publish",
    "read_events",
    "MetricsRegistry",
    "compile_totals",
    "install_compile_monitor",
    "RunContext",
    "Tracer",
    "chrome_trace_from_events",
    "PHASES",
    "PhaseProfile",
    "RequestTrace",
    "ServeTracer",
    "SLOTracker",
    "ExpositionServer",
    "Sample",
    "HistogramSample",
    "process_samples",
    "FlightRecorder",
    "KernelWatch",
    "QualityProfile",
    "em_diagnostics",
    "DriftMonitor",
    "psi",
    "js_divergence",
]
