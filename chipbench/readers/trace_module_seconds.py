"""Device seconds, in the traced job, of the XLA modules whose name contains
any of ``modules`` (``run["trace"]["modules"]``: module name -> seconds on
the device, what ``trace_roofline`` reads). No trace, or no module of that
name in it: nothing returned."""


def read(run: dict, modules: list[str]):
    trace = run.get("trace")
    if not trace:
        return None
    seconds = [t for name, t in trace["modules"].items()
               if any(part in name for part in modules)]
    return sum(seconds) if seconds else None
