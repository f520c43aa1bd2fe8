"""Mean per job of one named count over the named spans of the program's own
span table (``splink_tpu.utils.profiling.spans``; the window's jobs are the
last ``len(run["jobs"])`` run scopes, as ``span_seconds`` takes them): the
count's values on every span of those names, summed. A failed job, a program
without a span table, and a program none of whose named spans carries the
count have nothing to read: nothing returned."""


def read(run: dict, spans: list[str], count: str):
    from splink_tpu.utils import profiling

    jobs = run["jobs"]
    if not hasattr(profiling, "spans") or not hasattr(profiling, "runs"):
        return None
    scopes = profiling.runs()[-len(jobs):] if jobs else []
    if run["failed"] or not jobs or len(scopes) < len(jobs):
        return None
    values = [s["counts"][count] for scope in scopes for s in profiling.spans(run=scope)
              if s["name"] in spans and count in s["counts"]]
    return sum(values) / len(jobs) if values else None
