"""Share of the memory roofline the named ops reached: the least time the
chip could take to move their operands and results for the traced job's
pairs (``run["string_bytes_per_pair"]``, which the runner reckons with
``chipbench/string_bytes.py`` from each comparison's width), over their
device seconds (``trace_op_seconds``). A runner that reckons no bytes, no
trace, or no such op: nothing returned."""

from chipbench.readers import trace_op_seconds


def read(run: dict, ops: list[str]):
    seconds = trace_op_seconds.read(run, ops)
    per_pair = run.get("string_bytes_per_pair")
    traced = [j for j in run["jobs"] if j.get("traced")]
    if not seconds or not per_pair or not run.get("peaks") or not traced:
        return None
    least = traced[0]["pairs"] * per_pair / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
