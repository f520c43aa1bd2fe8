"""The program's one span table: where a run's host time goes.

Every linker registers a run scope at construction (:func:`begin_run`) and
each scope keeps an in-memory table of spans::

    {"id", "name", "kind", "t0", "t1", "parent", "thread", "counts"}

``t0``/``t1`` are ``time.perf_counter()`` seconds (CLOCK_MONOTONIC on Linux,
the clock a caller's own job wall uses), ``id`` is the span's index in its
run's table (assigned when it opens), ``parent`` the id of the enclosing
open span of the same thread in the same run (or None), ``counts`` the
work done inside it (rows, pairs, batches, bytes). Kinds:

  * ``call``  — one public linker call (the roots: ``init``,
    ``scored_comparisons``, ``tf``, ...);
  * ``stage`` — a pipeline stage, ``StageTimer(name)``; these alone are what
    :func:`stage_timings` returns, under the names it always had;
  * ``span``  — a sub-stage or one batch's wait, ``span(name, **counts)``;
  * ``build`` — jax tracing / lowering / backend compile (or persistent-cache
    read), appended closed by the ``jax.monitoring`` listener in
    ``obs/metrics.py`` under whatever span was open on that thread.

Always on: no setting, no environment switch. A span costs two clock reads,
one append and a ``jax.profiler.TraceAnnotation`` (a flag check while no
profiler session is active). Whenever one IS active — wrap any call in
``jax.profiler.trace(dir)`` — every span lies in the ``.xplane.pb`` by name
on the device trace's clock.

Granularity rule: spans at stage, sub-stage and BATCH level only — never per
pair, per row or per pattern. A job closes on the order of 10^2 spans, not
10^4.

A span's SELF time is its duration minus the part its children cover; build
spans may overlap (a jit traced inside another reports its own trace time),
so sum them as the union of their intervals.

A span that a GENERATOR holds open lives across its yields: around each
``yield`` it steps aside (:meth:`StageTimer.suspended`) — off this thread's
stack, so that nothing the consumer opens becomes its child, with the seconds
until the generator runs again added to its count ``suspended_s``. ``t0`` to
``t1`` then holds the consumer's time too; :func:`stage_timings` leaves it
out of a stage's seconds.

Spans opened outside any run scope (ad-hoc profiling, tests) land in a
default scope. Retained scopes are bounded (``_MAX_RETAINED_RUNS``).
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

# run id -> span table, in begin_run order; "" is the default (no-linker) scope
_DEFAULT_RUN = ""
_TABLES: dict[str, list[dict]] = {_DEFAULT_RUN: []}
_CURRENT_RUN = _DEFAULT_RUN
_LOCAL = threading.local()  # .stack: this thread's open StageTimers
_APPEND_LOCK = threading.Lock()  # id == index must hold across threads

# Retained run scopes are bounded: a long-lived service constructing one
# linker per request must not grow the tables forever. Oldest scopes are
# evicted FIFO past this cap; the default scope and the current run are
# never evicted.
_MAX_RETAINED_RUNS = 64


def begin_run(run_id: str) -> str:
    """Open (and make current) a run scope with an empty span table. Called
    by the linker at construction; a later linker beginning its own run
    leaves this one's table untouched (until it ages past the
    ``_MAX_RETAINED_RUNS`` eviction window). Also makes sure the build
    listener is installed, so build spans exist without telemetry."""
    global _CURRENT_RUN
    from ..obs.metrics import install_compile_monitor

    install_compile_monitor()
    _TABLES.pop(run_id, None)  # a re-begun id moves to the end
    _TABLES[run_id] = []
    _CURRENT_RUN = run_id
    while len(_TABLES) > _MAX_RETAINED_RUNS + 1:  # +1: the default scope
        oldest = next(
            (k for k in _TABLES if k not in (_DEFAULT_RUN, _CURRENT_RUN)),
            None,
        )
        if oldest is None:  # pragma: no cover - cap >= 1 prevents this
            break
        _TABLES.pop(oldest, None)
    return run_id


def discard_run(run_id: str) -> None:
    """Drop a run scope's recorded state (tests / long-lived processes)."""
    global _CURRENT_RUN
    if run_id == _DEFAULT_RUN:
        _TABLES[_DEFAULT_RUN] = []
        return
    _TABLES.pop(run_id, None)
    if _CURRENT_RUN == run_id:
        _CURRENT_RUN = _DEFAULT_RUN


def runs() -> list[str]:
    """The retained run ids, in ``begin_run`` order."""
    return [k for k in _TABLES if k != _DEFAULT_RUN]


def _stack() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


def _append(table: list[dict], name, kind, t0, t1, parent, counts) -> dict:
    rec = {"name": name, "kind": kind, "t0": t0, "t1": t1, "parent": parent,
           "thread": threading.get_ident(), "counts": counts}
    with _APPEND_LOCK:
        rec["id"] = len(table)
        table.append(rec)
    return rec


class StageTimer(contextlib.AbstractContextManager):
    """Context manager recording one span (a pipeline stage by default).

    Usage::

        with StageTimer("blocking") as st:
            ...
            st.count(pairs=n)

    Args:
        stage: the span's name.
        run: run scope to record into (default: the enclosing open span's,
            else the current scope).
        telemetry: optional ``obs.runtime.RunContext`` the closed span is
            handed to (default: the enclosing open span's) — emitted to the
            run's JSONL record when telemetry is enabled.
        kind: ``"stage"`` | ``"call"`` | ``"span"``.
        counts: initial counts.
    """

    def __init__(self, stage: str, run: str | None = None, telemetry=None,
                 kind: str = "stage", counts: dict | None = None):
        self.stage = stage
        self.run = run
        self.telemetry = telemetry
        self.kind = kind
        self.counts = dict(counts or {})

    def count(self, **counts) -> None:
        """Add to the span's counts (work done inside it)."""
        for key, n in counts.items():
            self.counts[key] = self.counts.get(key, 0) + n

    @contextlib.contextmanager
    def suspended(self):
        """Around a ``yield`` of the generator that holds this span open: the
        span leaves this thread's stack and its trace annotation closes, and
        the seconds until the generator is resumed (or closed) are added to
        the count ``suspended_s`` — the consumer's time, not the program's."""
        stack = _stack()
        if self in stack:
            stack.remove(self)
        self._annotation.__exit__(None, None, None)
        t = time.perf_counter()
        try:
            yield
        finally:
            self.count(suspended_s=time.perf_counter() - t)
            self._annotation = _trace_annotation(self.stage)
            self._annotation.__enter__()
            _stack().append(self)

    def __enter__(self):
        stack = _stack()
        outer = stack[-1] if stack else None
        if self.run is not None:
            self._table = _TABLES.setdefault(self.run, [])
        else:
            self._table = (
                outer._table if outer is not None
                else _TABLES.setdefault(_CURRENT_RUN, [])
            )
        if self.telemetry is None and outer is not None:
            self.telemetry = outer.telemetry
        parent = (
            outer.span["id"]
            if outer is not None and outer._table is self._table else None
        )
        self.span = _append(
            self._table, self.stage, self.kind, None, None, parent, self.counts
        )
        stack.append(self)
        self._annotation = _trace_annotation(self.stage)
        self._annotation.__enter__()
        self.span["t0"] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.span["t1"] = t1 = time.perf_counter()
        self.elapsed = t1 - self.span["t0"]
        self._annotation.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # a generator's stage closed out of order
            stack.remove(self)
        if self.telemetry is not None and self.telemetry.enabled:
            self.telemetry.stage_exit(
                dict(self.span, build=builds_under(self._table, self.span)),
                failed=exc[0] is not None,
            )
        return False


def span(name: str, **counts) -> StageTimer:
    """A sub-span (``kind="span"``) under whatever is open on this thread."""
    return StageTimer(name, kind="span", counts=counts)


def count(**counts) -> None:
    """Add to the counts of the innermost open STAGE of this thread: how a
    batch loop below the linker reports its work at the stage's boundary.
    Outside any stage there is nothing to count into."""
    for timer in reversed(getattr(_LOCAL, "stack", None) or ()):
        if timer.kind == "stage":
            timer.count(**counts)
            return


def fetch(x, via=np.asarray):
    """``np.asarray(x)`` for a device array, under a ``d2h_wait`` span: the
    one place the driver thread blocks on a device-to-host copy. ``via`` is
    the copy itself — ``parallel.mesh.gather_from_mesh`` for an array
    sharded over a mesh, whose ``mesh_gather`` span then lies inside."""
    with span("d2h_wait") as sp:
        arr = via(x)
        sp.count(bytes=arr.nbytes)
    return arr


def add_closed(name: str, kind: str, secs: float, **counts) -> None:
    """Append the already-timed interval ``[now - secs, now]`` under the
    innermost open span of this thread (the build listener's entry point).
    With nothing open there is nothing to attribute it to: dropped."""
    stack = getattr(_LOCAL, "stack", None)
    if not stack:
        return
    outer = stack[-1]
    now = time.perf_counter()
    rec = _append(outer._table, name, kind, now - secs, now,
                  outer.span["id"], counts)
    if outer.telemetry is not None and outer.telemetry.enabled:
        outer.telemetry.stage_exit(dict(rec, build=[]))


def current_span_id() -> int | None:
    """Id of the innermost open span of this thread (None outside any)."""
    stack = getattr(_LOCAL, "stack", None)
    return stack[-1].span["id"] if stack else None


def builds_under(table: list[dict], span: dict) -> list[dict]:
    """The closed build spans below ``span``: the table is in open order
    and a thread's spans nest, so they are the build spans of its thread
    that were appended after it opened."""
    return [
        s for s in table[span["id"] + 1:]
        if s["kind"] == "build" and s["thread"] == span["thread"]
    ]


_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, imported on first use


def _trace_annotation(name: str):
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(name)


def spans(run: str | None = None) -> list[dict]:
    """The closed spans of the current run scope, or of ``run`` when given,
    in the order they opened (copies; ``parent`` refers to ``id``)."""
    key = _CURRENT_RUN if run is None else run
    return [
        dict(s, counts=dict(s["counts"]))
        for s in _TABLES.get(key, ())
        if s["t1"] is not None
    ]


def stage_timings(run: str | None = None) -> dict[str, list[float]]:
    """Recorded stage timings (stage -> list of seconds, in the order the
    stages closed) for the current run scope, or for ``run`` when given:
    the ``kind="stage"`` spans and nothing else."""
    key = _CURRENT_RUN if run is None else run
    stages = [
        s for s in _TABLES.get(key, ())
        if s["kind"] == "stage" and s["t1"] is not None
    ]
    out: dict[str, list[float]] = {}
    for s in sorted(stages, key=lambda s: s["t1"]):
        out.setdefault(s["name"], []).append(
            s["t1"] - s["t0"] - s["counts"].get("suspended_s", 0.0)
        )
    return out


def reset_timings(run: str | None = None) -> None:
    key = _CURRENT_RUN if run is None else run
    _TABLES[key] = []
