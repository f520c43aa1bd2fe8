"""Mean seconds per job of the named linker stages
(``splink_tpu.utils.profiling.stage_timings``, host clock)."""


def read(run: dict, stages: list[str]):
    jobs = run["jobs"]
    found = [s for s in stages if any(s in j["stages"] for j in jobs)]
    if not jobs or not found:
        return None
    return sum(j["stages"].get(s, 0.0) for j in jobs for s in found) / len(jobs)
