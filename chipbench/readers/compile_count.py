"""Compile requests that reached the backend inside the window, per job:
``compiled`` (really compiled there) or ``cache_read`` (served by the
persistent cache)."""


def read(run: dict, kind: str):
    if not run["jobs"]:
        return None
    key = {"compiled": "window_compiles", "cache_read": "window_cache_reads"}[kind]
    return run[key] / len(run["jobs"])
