"""The program's one span table: where a run's host time goes.

Every linker registers a run scope at construction (:func:`begin_run`) and
each scope keeps an in-memory table of spans::

    {"id", "name", "kind", "t0", "t1", "parent", "thread", "counts"}

``t0``/``t1`` are ``time.perf_counter()`` seconds (CLOCK_MONOTONIC on Linux,
the clock a caller's own job wall uses), ``id`` is the span's index in its
run's table (assigned when it opens), ``parent`` the id of the enclosing
open span of the same thread in the same run (or None), ``counts`` the
work done inside it (rows, pairs, batches, bytes). A span a generator held
open also carries ``suspended``, the ``(t0, t1)`` of each time it stepped
aside. Kinds:

  * ``call``  — one public linker call (the roots: ``init``,
    ``scored_comparisons``, ``tf``, ...);
  * ``stage`` — a pipeline stage, ``StageTimer(name)``; these alone are what
    :func:`stage_timings` returns, under the names it always had;
  * ``span``  — a sub-stage or one batch's wait, ``span(name, **counts)``;
  * ``build`` — jax tracing / lowering / backend compile (or persistent-cache
    read), appended closed by the ``jax.monitoring`` listener in
    ``obs/metrics.py`` under whatever span was open on that thread;
  * ``device`` — one jitted BATCH program the host asked the device to run,
    ``dispatched(name, out, **counts)``: the other party. ``t0`` is when the
    dispatching call returned, ``t1`` the first moment the host KNEW the
    program had finished, ``parent`` the span open on the dispatching
    thread, ``name`` the program's jitted name (``fn`` for the gamma
    programs, ``block_pair_emit``, ``run_em``, ``score_pairs``, ...),
    ``counts`` what it was asked to do (``positions`` or ``rows``;
    ``devices`` under a mesh). No wait is added to learn ``t1``: the output
    is polled with the non-blocking ``Array.is_ready()`` whenever a span
    opens or closes, a program is dispatched or a download lands
    (:func:`poll`), oldest first, so a record never closes before its
    program ended and closes at most one such boundary after it.
    :func:`spans`, :func:`stage_timings` and ``span_seconds`` never see
    these records (a span's self time is its own); :func:`device_spans`
    returns them and :func:`exposure` sets the two lanes side by side:
    which driver seconds had nothing in flight and which the device hid.

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
from collections import deque

import numpy as np

# run id -> span table, in begin_run order; "" is the default (no-linker) scope
_DEFAULT_RUN = ""
_TABLES: dict[str, list[dict]] = {_DEFAULT_RUN: []}
_CURRENT_RUN = _DEFAULT_RUN
_LOCAL = threading.local()  # .stack: this thread's open StageTimers
_APPEND_LOCK = threading.Lock()  # id == index must hold across threads
# the open device records with the output each is polled by, in dispatch
# order (process-wide: a chip runs programs in the order they were given)
_INFLIGHT: deque = deque()
_POLL_LOCK = threading.Lock()  # one poller at a time; the others move on

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
        poll()
        try:
            yield
        finally:
            poll()
            back = time.perf_counter()
            self.count(suspended_s=back - t)
            self.span.setdefault("suspended", []).append((t, back))
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
        poll()  # what ended before this span opened closes before it
        self.span["t0"] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.span["t1"] = t1 = time.perf_counter()
        self.elapsed = t1 - self.span["t0"]
        poll()  # after t1: a wait this span held is in flight to its end
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


def count_here(**counts) -> None:
    """Add to the counts of the innermost open span of this thread, whatever
    its kind: work a callee reports to whoever opened a span round it."""
    stack = getattr(_LOCAL, "stack", None)
    if stack:
        stack[-1].count(**counts)


def fetch(x, via=np.asarray):
    """``np.asarray(x)`` for a device array (or each array of a tuple),
    under a ``d2h_wait`` span: how the driver thread blocks on the device —
    the program that makes ``x`` plus the copy home; ``bytes`` is what came
    (0 where the wait brings nothing). ``via`` is the copy itself —
    ``parallel.mesh.gather_from_mesh`` for an array sharded over a mesh,
    whose ``mesh_gather`` span then lies inside."""
    with span("d2h_wait") as sp:
        home = tuple(via(a) for a in x) if isinstance(x, tuple) else via(x)
        sp.count(bytes=sum(
            a.nbytes for a in (home if isinstance(home, tuple) else (home,))
            if hasattr(a, "nbytes")
        ))
    return home


def fetch_pooled(future):
    """What a download running on a pool thread brings home, under the
    DRIVER's ``d2h_wait`` span (the pool thread's own copy is under none:
    it is not the driver's time)."""
    return fetch(future, via=_result)


def _result(future):
    return future.result()


def dispatched(name: str, out, **counts) -> None:
    """Mark one jitted BATCH program just dispatched: a ``device`` record
    under the span open on this thread, open until ``out`` — an output of
    the program, kept alive until then, so name a small one — is ready.
    Call it right after the jitted call returns; per-batch metadata uploads
    and eager slices get none."""
    stack = getattr(_LOCAL, "stack", None)
    outer = stack[-1] if stack else None
    table = (
        outer._table if outer is not None
        else _TABLES.setdefault(_CURRENT_RUN, [])
    )
    rec = _append(
        table, name, "device", time.perf_counter(), None,
        None if outer is None else outer.span["id"], counts,
    )
    _INFLIGHT.append((rec, out))
    poll()


def poll() -> None:
    """Close every device record at the head of the dispatch order whose
    output is ready — non-blocking, one ``is_ready()`` call past the last
    one closed. Spans poll as they open and close; a pool thread calls it
    when its download has landed."""
    if not _INFLIGHT or not _POLL_LOCK.acquire(blocking=False):
        return
    try:
        while _INFLIGHT:
            rec, out = _INFLIGHT[0]
            try:
                # is_ready() on a deleted buffer aborts the process
                if not (out.is_deleted() or out.is_ready()):
                    return
            except AttributeError:
                pass  # a host value: nothing to poll
            _INFLIGHT.popleft()
            rec["t1"] = time.perf_counter()
    finally:
        _POLL_LOCK.release()


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


def _closed(run: str | None, device: bool) -> list[dict]:
    key = _CURRENT_RUN if run is None else run
    return [
        dict(s, counts=dict(s["counts"]))
        for s in _TABLES.get(key, ())
        if s["t1"] is not None and (s["kind"] == "device") == device
    ]


def spans(run: str | None = None) -> list[dict]:
    """The closed HOST spans of the current run scope, or of ``run`` when
    given, in the order they opened (copies; ``parent`` refers to ``id``).
    Device records are not among them: see :func:`device_spans`."""
    return _closed(run, device=False)


def device_spans(run: str | None = None) -> list[dict]:
    """The closed ``device`` records of the scope, in dispatch order
    (copies; ``parent`` is the ``id`` of a span of :func:`spans`)."""
    poll()
    return _closed(run, device=True)


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


def _union(intervals) -> list[tuple[float, float]]:
    """The intervals merged: sorted, disjoint, none empty."""
    out: list[tuple[float, float]] = []
    for t0, t1 in sorted(intervals):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1] = (out[-1][0], t1)
        else:
            out.append((t0, t1))
    return out


def _meet(a: list, b: list) -> list[tuple[float, float]]:
    """What two unions share."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _less(a: list, b: list) -> list[tuple[float, float]]:
    """Union ``a`` without what union ``b`` covers."""
    out, j = [], 0
    for t0, t1 in a:
        while j < len(b) and b[j][1] <= t0:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t1:
            if b[k][0] > t0:
                out.append((t0, b[k][0]))
            t0 = max(t0, b[k][1])
            k += 1
        if t0 < t1:
            out.append((t0, t1))
    return out


def _seconds(a: list) -> float:
    return sum(t1 - t0 for t0, t1 in a)


def exposure(run: str | None = None) -> dict:
    """Why was the chip idle: one run's two lanes side by side, from its one
    table. The DRIVER is the thread of the run's root ``call`` spans; its
    clock inside them splits three ways::

        wall_s = exposed_s + inflight_s + suspended_s

    ``inflight_s`` — the union of the device records' intervals, clipped to
    the driver's running time: some program was dispatched and not yet
    known to have ended (host work in it is hidden behind the device);
    ``exposed_s`` — the driver ran with nothing in flight (the chip sat idle
    for it), of which ``head_s`` before the run's first dispatch, ``tail_s``
    after its last record closed and ``unspanned_exposed_s`` in the call
    spans' own self time; ``suspended_s`` — a generator-held call span
    stepped aside (the consumer's time: neither). ``spans`` gives per host
    span name of the driver ``n``, ``self_s`` and its split ``exposed_s`` /
    ``hidden_s``; ``other_threads`` the spans closed elsewhere (pooled
    downloads, ``mesh_gather``) with their own self time, never added to
    the driver's; ``device`` per program ``n`` and its summed seconds.
    An empty dict where the run closed no call span."""
    host, device = spans(run), device_spans(run)
    roots = [s for s in host if s["kind"] == "call" and s["parent"] is None]
    if not roots:
        return {}
    driver = roots[0]["thread"]
    roots = [s for s in roots if s["thread"] == driver]

    def ran(s):  # a span's own interval less the times it stepped aside
        return _less([(s["t0"], s["t1"])], _union(s.get("suspended", ())))

    wall = _union((s["t0"], s["t1"]) for s in roots)
    running = _union(iv for s in roots for iv in ran(s))
    inflight = _meet(_union((d["t0"], d["t1"]) for d in device), running)
    exposed = _less(running, inflight)
    children: dict = {}
    for s in host:
        children.setdefault(s["parent"], []).append(s)
    by_name: dict = {}
    elsewhere: dict = {}
    unspanned = 0.0
    for s in host:
        own = _less(ran(s), _union(
            iv for c in children.get(s["id"], ()) for iv in ran(c)
        ))
        if s["thread"] != driver:
            row = elsewhere.setdefault(
                s["name"], {"n": 0, "self_s": 0.0, "threads": set()}
            )
            row["threads"].add(s["thread"])
        else:
            row = by_name.setdefault(
                s["name"],
                {"n": 0, "self_s": 0.0, "exposed_s": 0.0, "hidden_s": 0.0},
            )
            bare = _seconds(_meet(own, exposed))
            row["exposed_s"] += bare
            row["hidden_s"] += _seconds(_meet(own, inflight))
            if s["kind"] == "call" and s["parent"] is None:
                unspanned += bare
        row["n"] += 1
        row["self_s"] += _seconds(own)
    for row in elsewhere.values():
        row["threads"] = sorted(row["threads"])
    programs: dict = {}
    for d in device:
        row = programs.setdefault(d["name"], {"n": 0, "seconds": 0.0})
        row["n"] += 1
        row["seconds"] += d["t1"] - d["t0"]
    first = min((d["t0"] for d in device), default=wall[-1][1])
    last = max((d["t1"] for d in device), default=wall[-1][1])
    return {
        "wall_s": _seconds(wall),
        "suspended_s": _seconds(wall) - _seconds(running),
        "inflight_s": _seconds(inflight),
        "exposed_s": _seconds(exposed),
        "head_s": _seconds(_meet(exposed, [(wall[0][0], first)])),
        "tail_s": _seconds(_meet(exposed, [(last, wall[-1][1])])),
        "unspanned_exposed_s": unspanned,
        "spans": by_name,
        "other_threads": elsewhere,
        "device": programs,
    }


def reset_timings(run: str | None = None) -> None:
    key = _CURRENT_RUN if run is None else run
    _TABLES[key] = []
