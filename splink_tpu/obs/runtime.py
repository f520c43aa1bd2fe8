"""RunContext: the per-linker telemetry object.

One RunContext per ``Splink`` instance, created from the settings. When the
``telemetry_dir`` key is empty the context is *disabled*: every method is a
single attribute check and returns immediately, no sink exists, and the
linker adds no host callbacks to compiled programs (the trace-audit
registry pins the jaxprs). When enabled it owns:

  * an :class:`~.events.EventSink` writing
    ``<telemetry_dir>/run_<run_id>.jsonl`` (registered as an ambient sink
    so resilience events land in the same file);
  * the JSONL form of the run's span table: ``utils.profiling`` keeps the
    one table (and the one stack of open spans) and hands each closed span
    to :meth:`RunContext.stage_exit`, which emits it with the compile-vs-
    execute split from the build spans below it; EM-iteration and run
    spans, which exist only in the record, come through
    :class:`~.tracer.Tracer`;
  * a :class:`~.metrics.MetricsRegistry` snapshotted into the record at
    the end of each public linker call.

Every emitting method is wrapped to never raise: a telemetry bug must not
take down the run it observes.
"""

from __future__ import annotations

import functools
import logging
import os
import time
import uuid
import weakref
from contextlib import contextmanager

from .events import EventSink, register_ambient
from .metrics import (
    MetricsRegistry,
    compile_totals,
    device_memory_snapshot,
    install_compile_monitor,
)
from .tracer import Tracer

logger = logging.getLogger("splink_tpu")


def _never_raise(fn):
    """Telemetry emission must never break the run it observes."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        try:
            return fn(self, *args, **kwargs)
        except Exception as e:  # noqa: BLE001 - observability is best-effort
            logger.warning("telemetry %s failed: %s", fn.__name__, e)
            return None

    return wrapper


# (span name, count) -> the run counter it feeds: the counts made at the
# spans' boundaries are the one source of these
_COUNTERS = {
    ("encode", "rows"): "rows_encoded",
    ("blocking", "pairs"): "pairs_blocked",
    ("em", "pairs"): "pairs_gamma_scored",
    ("em_streamed", "pairs"): "pairs_gamma_scored",
}


def _union_seconds(intervals) -> float:
    """Length of the union of (t0, t1) intervals: build spans overlap (a
    jit traced inside another reports its own trace time)."""
    total, end = 0.0, None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


class RunContext:
    """Telemetry scope for one linker run (see module docstring)."""

    def __init__(
        self,
        run_id: str | None = None,
        sink: EventSink | None = None,
        memory_snapshots: bool = True,
        config_hash: str = "",
    ):
        self.run_id = run_id or uuid.uuid4().hex[:12]
        self.sink = sink
        self.memory_snapshots = memory_snapshots
        self.tracer = Tracer()
        self.metrics = MetricsRegistry()
        # Per-stage execute-time EWMAs + windows (obs/kernelwatch.py):
        # the offline half of the performance observatory — fed with each
        # stage's execute split (wall minus compile), alerting disabled
        # (offline stages have no steady state to anchor alerts on); the
        # snapshot lands in the run record at finish() as the
        # ``kernel_watch`` metrics record.
        from .kernelwatch import KernelWatch

        self.kernelwatch = KernelWatch(window_s=300.0, alert_ratio=0.0)
        self._t0 = time.monotonic()
        # EM stream state: parent span + previous params for the host-side
        # delta/max-movement computation (the io_callback hook hands us the
        # new params; the dataflow is untouched)
        self._em_parent: int | None = None
        self._em_prev = None
        self._em_last_mono: float | None = None
        if sink is not None:
            install_compile_monitor()  # RunContext.span's compile split
            register_ambient(sink)
            sink.emit("run_start", config_hash=config_hash)
            # The ambient registry holds a strong reference to the sink, so
            # without this a dropped linker would keep receiving (and
            # misattributing) every later run's resilience events, and file
            # handles would accumulate for the life of the process. Closing
            # unregisters; close() is idempotent, so an explicit close()
            # before collection is also fine.
            self._finalizer = weakref.finalize(self, sink.close)

    @property
    def enabled(self) -> bool:
        return self.sink is not None

    @classmethod
    def from_settings(cls, settings: dict) -> "RunContext":
        """Build the run's context from (completed or partial) settings;
        disabled unless ``telemetry_dir`` is set."""
        run_id = uuid.uuid4().hex[:12]
        tdir = settings.get("telemetry_dir") or ""
        sink = None
        if tdir:
            try:
                from ..parallel.distributed import host_tags

                tags = host_tags()
                path = os.path.join(
                    os.path.expanduser(tdir), f"run_{run_id}.jsonl"
                )
                sink = EventSink(path, run_id, tags)
            except Exception as e:  # noqa: BLE001 - telemetry must not block init
                logger.warning("telemetry disabled (sink init failed): %s", e)
                sink = None
        ctx = cls(
            run_id=run_id,
            sink=sink,
            memory_snapshots=bool(settings.get("telemetry_memory", True)),
        )
        return ctx

    # -- spans (closed by utils.profiling.StageTimer) ----------------------

    @_never_raise
    def stage_exit(self, span: dict, failed: bool = False):
        """Emit one closed span of the run's span table (utils/profiling.py
        hands over every span that closes under this context: ``id``,
        ``name``, ``kind``, ``t0``, ``t1``, ``parent``, ``counts`` and
        ``build``, the build spans below it)."""
        if not self.enabled:
            return
        builds = span.get("build", ())
        self._emit_span(
            span["id"], span["name"], span["kind"], span["t0"], span["t1"],
            span["parent"], span["counts"],
            compiles=sum(
                1 for b in builds
                if b["name"] == "jax_backend_compile"
                and not b["counts"].get("cache_hit")
            ),
            compile_s=_union_seconds((b["t0"], b["t1"]) for b in builds),
            failed=failed,
        )

    @_never_raise
    def _emit_span(self, span_id, name, kind, t0, t1, parent, counts,
                   compiles, compile_s, failed):
        """One span into the record. Stages also feed the compile/execute
        split, the stage metrics and a device-memory snapshot; a span's
        counts are THE source of the run's counters."""
        # a generator's span sat out its consumer's time (suspended_s)
        elapsed = t1 - t0 - counts.get("suspended_s", 0.0)
        execute_s = max(elapsed - compile_s, 0.0)
        attrs = dict(counts)
        if kind != "build":
            attrs.update(compile_count=compiles, compile_s=compile_s,
                         execute_s=execute_s, failed=failed)
        self.sink.emit("span", **self.tracer.emit_closed(
            name, kind, t0, t1, parent=parent, span_id=span_id, **attrs
        ))
        for key, n in counts.items():
            counter = "pairs_scored_output" if (
                kind == "call" and key == "pairs"
            ) else _COUNTERS.get((name, key))
            if counter:
                self.metrics.count(counter, n)
        if kind != "stage":
            return
        self.metrics.observe(f"stage_s.{name}", elapsed)
        self.metrics.count("compile_count", compiles)
        self.metrics.count("compile_s", compile_s)
        self.metrics.count("execute_s", execute_s)
        self.kernelwatch.observe(name, execute_s)
        if self.memory_snapshots:
            devices = device_memory_snapshot()
            if devices:
                self.sink.emit("memory", stage=name, devices=devices)
                peak = max(d.get("peak_bytes_in_use") or 0 for d in devices)
                if peak:
                    self.metrics.gauge("peak_bytes_in_use", peak)

    @contextmanager
    def span(self, name: str, **attrs):
        """Standalone stage-kind span for callers outside a linker run (the
        serve loop's batches): emitted to the record and kept in
        no span table, so a long-lived service does not grow one."""
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        c0, s0 = compile_totals()
        failed = False
        try:
            yield
        except BaseException:
            failed = True
            raise
        finally:
            c1, s1 = compile_totals()
            self._emit_span(
                None, name, "stage", t0, time.perf_counter(), None, attrs,
                c1 - c0, max(s1 - s0, 0.0), failed,
            )

    # -- EM convergence stream --------------------------------------------

    @_never_raise
    def em_begin(self, mode: str, lam0, m0, u0, start_iteration: int = 0):
        if not self.enabled:
            return
        import numpy as np

        from ..utils.profiling import current_span_id

        self._em_parent = current_span_id()
        self._em_prev = (np.asarray(m0, float), np.asarray(u0, float))
        self._em_last_mono = time.monotonic()
        self.sink.emit(
            "em_start", mode=mode, lam=float(lam0),
            start_iteration=int(start_iteration),
        )

    @_never_raise
    def em_update(self, it, lam, m, u, ll=None, converged=False):
        """One completed EM update (host side of the ``run_em`` host-hook
        io_callback, or the streamed driver's per-pass callback). Emits an
        iteration span (bounded by callback arrivals) plus the convergence
        record: lambda, log-likelihood (under the pre-update params) and
        ``delta`` — the max absolute m/u parameter movement, recomputed
        host-side from the streamed params."""
        if not self.enabled:
            return
        import math

        import numpy as np

        now = time.monotonic()
        it = int(it)
        m = np.asarray(m, float)
        u = np.asarray(u, float)
        delta = None
        if self._em_prev is not None and self._em_prev[0].shape == m.shape:
            delta = float(
                max(
                    np.max(np.abs(m - self._em_prev[0])),
                    np.max(np.abs(u - self._em_prev[1])),
                )
            )
        ll_val = None
        if ll is not None:
            ll_f = float(ll)
            ll_val = ll_f if math.isfinite(ll_f) else None
        t0 = self._em_last_mono if self._em_last_mono is not None else now
        span = self.tracer.emit_closed(
            f"em_iteration_{it}", "em_iteration", t0, now,
            parent=self._em_parent, iteration=it,
        )
        self.sink.emit("span", **span)
        self.sink.emit(
            "em_iteration",
            iteration=it,
            lam=float(lam),
            ll=ll_val,
            delta=delta,
            converged=bool(converged),
        )
        self.metrics.count("em_updates")
        self.metrics.gauge("em_lam", float(lam))
        if delta is not None:
            self.metrics.gauge("em_delta", delta)
        self._em_prev = (m, u)
        self._em_last_mono = now

    # -- structured one-off events ----------------------------------------

    @_never_raise
    def emit_event(self, type: str, **fields) -> None:
        """Emit one typed event into this run's record (no-op when
        disabled). For structured payloads readers filter by type —
        ``em_diagnostics`` rides this — as opposed to :meth:`record`,
        whose payloads live inside the metrics snapshot."""
        if self.enabled:
            self.sink.emit(type, **fields)

    # -- metrics convenience (no-ops when disabled) ------------------------

    def count(self, name: str, n: float = 1) -> None:
        if self.enabled:
            self.metrics.count(name, n)

    def gauge(self, name: str, value: float) -> None:
        if self.enabled:
            self.metrics.gauge(name, value)

    def observe(self, name: str, value: float) -> None:
        if self.enabled:
            self.metrics.observe(name, value)

    def record(self, name: str, payload) -> None:
        if self.enabled:
            self.metrics.record(name, payload)

    # -- run completion ----------------------------------------------------

    @_never_raise
    def finish(self):
        """Emit the metrics snapshot and a run span. Called at the end of
        each public linker entry point; safe to call repeatedly (summaries
        are cumulative — readers take the LAST metrics/run events). The
        sink stays open: later calls on the same linker append to the same
        record."""
        if not self.enabled:
            return
        if self.kernelwatch.phases():
            self.metrics.record("kernel_watch", self.kernelwatch.snapshot())
        self.sink.emit("metrics", **self.metrics.snapshot())
        span = self.tracer.emit_closed(
            "run", "run", self._t0, time.monotonic(), parent=None
        )
        self.sink.emit("span", **span)

    def close(self) -> None:
        """Close the sink now (unregisters it from the ambient publisher).
        Otherwise happens automatically when the context is collected."""
        if self.sink is not None:
            self.sink.close()
