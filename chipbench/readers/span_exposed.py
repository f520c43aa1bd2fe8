"""Driver seconds a job with nothing in flight on the device, from the
program's one span table: the host spans (``profiling.spans``: ``id``,
``parent``, ``thread``, ``t0``, ``t1``, and ``suspended``, the intervals a
generator-held span stepped aside) beside its ``device`` records
(``profiling.device_spans``: one a dispatched batch program, ``t0`` when the
dispatching call returned, ``t1`` when the host first knew it had ended).
Its own arithmetic, like ``span_seconds``; the program's
``profiling.exposure`` is not called.

The DRIVER is the thread of the job's root call spans. Its clock inside them,
less the suspended intervals (the consumer's time), is RUNNING time; the union
of the device records clipped to it is IN FLIGHT; the rest is EXPOSED: the
chip sat idle for it. ``what``:

* ``"exposed"`` — mean exposed seconds a job; with ``spans``, only the part
  that lies in the self time of the driver's spans of those names;
* ``"inflight"`` — mean in-flight seconds a job;
* ``"inflight_over_busy"`` — the TRACED job's in-flight seconds over the
  trace's ``busy_s``: how far the program's lane overstates the chip's.

The means leave the traced job out when the window has another (it runs under
the Python tracer). Where the table exists and nothing is exposed the answer
is 0.0; a failed job, a program without device records (the parent commit) or
a scope without a root call span has nothing to read: nothing returned."""


def _union(intervals) -> list:
    out = []
    for t0, t1 in sorted(intervals):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t1))
        else:
            out.append((t0, t1))
    return out


def _meet(a: list, b: list) -> list:
    return _union((max(a0, b0), min(a1, b1)) for a0, a1 in a for b0, b1 in b)


def _less(a: list, b: list) -> list:
    for b0, b1 in b:
        a = [(lo, hi) for t0, t1 in a for lo, hi in ((t0, min(t1, b0)), (max(t0, b1), t1))
             if hi > lo]
    return a


def _seconds(a: list) -> float:
    return sum(t1 - t0 for t0, t1 in a)


def _ran(span: dict) -> list:
    return _less([(span["t0"], span["t1"])], _union(span.get("suspended", ())))


def lanes(host: list[dict], device: list[dict], names=None):
    """(exposed, in flight, running) intervals of one job's driver, exposed
    narrowed to the self time of the spans called ``names`` when given; None
    where the table has no root call span."""
    roots = [s for s in host if s["kind"] == "call" and s["parent"] is None]
    if not roots:
        return None
    driver = roots[0]["thread"]
    running = _union(iv for s in roots if s["thread"] == driver for iv in _ran(s))
    inflight = _meet(_union((d["t0"], d["t1"]) for d in device), running)
    exposed = _less(running, inflight)
    if names is not None:
        children: dict = {}
        for s in host:
            children.setdefault(s["parent"], []).append(s)
        own = _union(iv for s in host if s["name"] in names and s["thread"] == driver
                     for iv in _less(_ran(s), _union(
                         iv for c in children.get(s["id"], ()) for iv in _ran(c))))
        exposed = _meet(exposed, own)
    return exposed, inflight, running


def read(run: dict, what: str, spans: list[str] | None = None):
    from splink_tpu.utils import profiling

    jobs = run["jobs"]
    if not all(hasattr(profiling, f) for f in ("spans", "runs", "device_spans")):
        return None
    scopes = profiling.runs()[-len(jobs):] if jobs else []
    if run["failed"] or not jobs or len(scopes) < len(jobs):
        return None
    if what not in ("exposed", "inflight", "inflight_over_busy"):
        raise ValueError(f"unknown quantity {what!r}")
    traced = [bool(j.get("traced")) for j in jobs]
    if what == "inflight_over_busy":
        trace = run.get("trace")
        if not trace or not trace["devices"] or trace["busy_s"] <= 0 or not any(traced):
            return None
        keep = traced
    else:
        keep = [not t for t in traced] if not all(traced) else traced
    total, n = 0.0, 0
    for scope, kept in zip(scopes, keep):
        if not kept:
            continue
        found = lanes(profiling.spans(run=scope), profiling.device_spans(run=scope),
                      None if spans is None else set(spans))
        if found is None:
            return None
        total += _seconds(found[0] if what == "exposed" else found[1])
        n += 1
    return total / run["trace"]["busy_s"] if what == "inflight_over_busy" else total / n
