"""Device seconds, in the traced job, of the ops whose name contains any of
``ops`` (``run["trace"]["device_ops"]``: the trace's ten longest ops by
name, summed over the job's executions; a Pallas kernel's op is named after
its ``pallas_call``). No trace, or no such op among them: nothing returned."""


def read(run: dict, ops: list[str]):
    trace = run.get("trace")
    if not trace:
        return None
    seconds = [t for name, t in trace["device_ops"] if any(part in name for part in ops)]
    return sum(seconds) if seconds else None
