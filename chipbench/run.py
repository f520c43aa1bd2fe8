"""One run of one cell of the benchmark.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and everything that belongs to it BY
NAME: ``configs/<config>.json``, ``traffic/<traffic>.json``, the runner module
``runners/<runner>.py`` the traffic names, and for each per-layer metric that
applies ``metrics/<metric>.json`` with its reader ``readers/<reader>.py``.
Nothing is registered in code. The last line of standard output is the result
(README.md); without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints none. ``CHIPBENCH_REHEARSAL=1`` runs the same
code on whatever backend jax has, at sizes overridden by
``CHIPBENCH_REHEARSAL_OVERRIDES``, and prints a labelled summary and no
result line.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def by_name(entries: list[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"chipbench: no {what} named {name!r} in the manifest")


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        out[key] = merge(out[key], value) if isinstance(value, dict) and isinstance(out.get(key), dict) else value
    return out


def load_cell(workload: str, manifest_path: str | None = None):
    """(manifest, cell, configuration, traffic) of a cell, found by name. In
    rehearsal the JSON in CHIPBENCH_REHEARSAL_OVERRIDES is merged into the
    configuration (tiny rows, small batch)."""
    with open(manifest_path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = by_name(manifest["workloads"], workload, "cell")
    config = load("configs", cell["config"])
    if rehearsing():
        config = merge(config, json.loads(os.environ.get("CHIPBENCH_REHEARSAL_OVERRIDES", "{}")))
    return manifest, cell, config, load("traffic", cell["traffic"])


def rehearsing() -> bool:
    return os.environ.get("CHIPBENCH_REHEARSAL") == "1"


def metrics_of(manifest: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's metrics for this kind of run, as the manifest lists them."""
    group = manifest["per_layer"] if trace else manifest["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def device_identity(chips: int) -> dict:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if rehearsing():
        return device
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"chipbench: the cell needs {chips} TPU chip(s); jax reports {device}",
              file=sys.stderr)
        raise SystemExit(2)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest")
    args = ap.parse_args(argv)
    rehearsal = rehearsing()
    manifest, cell, config, traffic = load_cell(args.workload, args.manifest)

    sys.path.insert(0, ROOT)
    device = device_identity(int(cell["chips"]))
    from splink_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    runner = importlib.import_module(f"chipbench.runners.{traffic['runner']}")
    run = runner.run({
        "config": config, "traffic": traffic, "seed": args.seed,
        "seconds": args.seconds, "trace": bool(args.trace),
        "trace_dir": os.path.join(ROOT, ".chipbench_trace", args.workload),
        "t_process_start": T_PROCESS_START,
    })

    from chipbench import trace_reduce

    run["peaks"] = None if rehearsal else trace_reduce.load_peaks(device["kind"])
    run["gamma_bytes_per_pair"] = config.get("gamma_bytes_per_pair")
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    breakdown = None
    if args.trace and run.get("trace_file"):
        summary = trace_reduce.reduce(trace_reduce.read_planes(run["trace_file"]))
        shutil.rmtree(os.path.join(ROOT, ".chipbench_trace"), ignore_errors=True)  # tens of MB a run
        traced = [j for j in run["jobs"] if j.get("traced")]
        summary["window_s"] = traced[0]["wall_s"] if traced else 0.0
        run["trace"] = summary
        device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}

    values = {"setup_s": run["setup_s"],
              "pairs_per_s": run["pairs"] / run["window_s"] if run["window_s"] > 0 else 0.0}
    metrics = {}
    for m in metrics_of(manifest, args.workload, bool(args.trace)):
        if m["name"] in values:
            value = values[m["name"]]
        else:
            spec = load("metrics", m["name"])
            reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
            value = reader.read(run, **spec.get("args", {}))
        if value is not None:  # a reader with nothing to read reports nothing
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": bool(run["correct"]), "attempted": len(run["jobs"]) + run["failed"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["window"] = {k: run.get(k) for k in (
        "window_s", "pairs", "frames_s", "setup_compiles", "setup_cache_reads",
        "window_compiles", "window_cache_reads",
        "reference_s", "memory_peak_in_use_bytes", "memory_peak_reserved_bytes",
        "memory_limit_bytes")}
    result["window"]["job_wall_s"] = [j["wall_s"] for j in run["jobs"]]
    if run.get("trace"):
        modules = sorted(run["trace"]["modules"].items(), key=lambda kv: -kv[1])
        result["window"]["trace_modules"] = [[k[:80], v] for k, v in modules[:10]]
    result["checks"] = run["checks"]

    for name, value, limit in run["checks"]:
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    if rehearsal:
        print("REHEARSAL " + json.dumps(result), flush=True)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
