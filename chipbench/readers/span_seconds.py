"""Mean seconds per job of the named spans of the program's own span table
(``splink_tpu.utils.profiling.spans``: one table per linker, spans with
``id``, ``parent``, ``t0``, ``t1`` on the host clock the job wall uses). The
window's jobs are the last ``len(run["jobs"])`` run scopes of ``runs()``.
``mode`` is ``"self"`` — each span's duration minus the part its children
cover — or ``"union"`` — the length of the union of the spans' intervals,
for build spans, which overlap (a jit traced inside another reports its own
trace time). A failed job leaves a scope that is no job's, a program without a
span table has nothing to read, and a name no job closed measures nothing:
nothing returned."""


def _union(intervals) -> float:
    total, end = 0.0, None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


def _self_seconds(table: list[dict], names: set) -> float:
    children: dict = {}
    for s in table:
        children.setdefault(s["parent"], []).append(s)
    total = 0.0
    for s in table:
        if s["name"] in names:
            covered = _union((max(c["t0"], s["t0"]), min(c["t1"], s["t1"]))
                             for c in children.get(s["id"], ())
                             if c["t1"] > s["t0"] and c["t0"] < s["t1"])
            total += (s["t1"] - s["t0"]) - covered
    return total


def read(run: dict, spans: list[str], mode: str):
    from splink_tpu.utils import profiling

    jobs = run["jobs"]
    if not hasattr(profiling, "spans") or not hasattr(profiling, "runs"):
        return None
    scopes = profiling.runs()[-len(jobs):] if jobs else []
    if run["failed"] or not jobs or len(scopes) < len(jobs):
        return None
    names, total, found = set(spans), 0.0, False
    for scope in scopes:
        table = profiling.spans(run=scope)
        found = found or any(s["name"] in names for s in table)
        if mode == "self":
            total += _self_seconds(table, names)
        elif mode == "union":
            total += _union((s["t0"], s["t1"]) for s in table if s["name"] in names)
        else:
            raise ValueError(f"unknown mode {mode!r}")
    return total / len(jobs) if found else None
