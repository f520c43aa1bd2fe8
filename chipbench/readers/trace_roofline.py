"""Share of the memory roofline the gamma work reached: the least time the
chip could take to move the job's gamma bytes, over the device time of the
XLA modules that hold the gamma body. The bytes come from the configuration's
shapes (``gamma_bytes_per_pair``), not from the program. Nothing matched in
the trace: nothing returned."""


def read(run: dict, modules: list[str]):
    trace = run.get("trace")
    if not trace or not run.get("peaks") or not run.get("gamma_bytes_per_pair"):
        return None
    seconds = sum(t for name, t in trace["modules"].items()
                  if any(part in name for part in modules))
    traced = [j for j in run["jobs"] if j.get("traced")]
    if seconds <= 0 or not traced:
        return None
    least = traced[0]["pairs"] * run["gamma_bytes_per_pair"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
