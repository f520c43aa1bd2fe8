"""The ``job`` runner with the reference and the comparison that judge the
cell named by its traffic file: ``runners/job.py``'s window, frames and job
(imported from it, not copied), judged by ``chipbench.<traffic["reference"]>``
and ``chipbench.<traffic["correct"]>`` — ``reference`` and ``correct`` where the
file names none, so this runner can take ``job.run``'s place. ``job.run``
names the two in its own body; the next configuration with kinds of its own
brings a reference, a comparison and a traffic file, and no third runner
(PERF.md §7).
"""

from __future__ import annotations

import gc
import glob
import importlib
import os
import shutil
import time

from chipbench.runners.job import CompileCounter, _light, make_frames, run_job


def judges(traffic: dict):
    """(reference, comparison): the modules the traffic file names."""
    return tuple(importlib.import_module(f"chipbench.{traffic.get(key, key)}")
                 for key in ("reference", "correct"))


def run(ctx: dict) -> dict:
    """Drive one cell, as ``job.run`` drives it: set-up, window, memory, then
    the reference and the comparison the traffic file names."""
    import jax

    config, traffic = ctx["config"], ctx["traffic"]
    reference, correct = judges(traffic)
    settings, calls = config["settings"], traffic["calls"]
    compiles = CompileCounter()
    frames = make_frames(config, traffic, ctx["seed"])
    t_frames = time.perf_counter()
    warm = run_job(settings, frames, calls)
    setup_compiled, setup_reads = compiles.snapshot()
    del warm
    gc.collect()

    jobs, last, failed = [], None, 0
    trace_file = None
    t_start = time.perf_counter()
    setup_s = t_start - ctx["t_process_start"]
    while time.perf_counter() - t_start < ctx["seconds"]:
        tracing = ctx["trace"] and not jobs and not failed
        if tracing:
            shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
            jax.profiler.start_trace(ctx["trace_dir"])
        try:
            job = run_job(settings, frames, calls)
        except Exception:  # noqa: BLE001 - a failed job is counted, then shown
            import traceback

            traceback.print_exc()
            failed += 1
            if failed >= 3:
                break
            continue
        finally:
            if tracing:
                jax.profiler.stop_trace()
                found = glob.glob(os.path.join(ctx["trace_dir"], "**", "*.xplane.pb"),
                                  recursive=True)
                trace_file = found[0] if found else None
        job["traced"] = tracing
        jobs.append(_light(job))
        last = job
    window_s = time.perf_counter() - t_start
    compiled, reads = compiles.snapshot()

    # XLA's program scratch is counted apart from live buffers on this runtime
    # (peak_bytes_reserved, not peak_bytes_in_use): the larger of the two is a
    # lower bound of the chip's true peak, their sum the upper bound
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    fullest = max(stats, key=lambda s: max(s.get("peak_bytes_in_use", 0),
                                           s.get("peak_bytes_reserved", 0)))
    in_use = int(fullest.get("peak_bytes_in_use", 0))
    reserved = int(fullest.get("peak_bytes_reserved", 0))

    out = {
        "jobs": jobs, "failed": failed, "window_s": window_s, "setup_s": setup_s,
        "frames_s": t_frames - ctx["t_process_start"],
        "window_compiles": compiled - setup_compiled,
        "window_cache_reads": reads - setup_reads,
        "setup_compiles": setup_compiled, "setup_cache_reads": setup_reads,
        "pairs": sum(j["pairs"] for j in jobs),
        "memory_peak_bytes": max(in_use, reserved),
        "memory_peak_in_use_bytes": in_use, "memory_peak_reserved_bytes": reserved,
        "memory_limit_bytes": int(fullest.get("bytes_limit", 0)),
        "trace_file": trace_file,
    }
    if last is None:
        out.update(correct=False, checks=[["jobs_finished", 0, 1]])
        return out

    # the program's device state goes before the reference touches the chip
    produced = {"frame": last["frame"], "tf_frame": last["tf_frame"],
                "params": last["params"], "digests": [j["digest"] for j in jobs],
                "uid": settings.get("unique_id_column_name", "unique_id")}
    last = None
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    numbers = correct.compare(produced, reference.prepare(settings, frames))
    ok, rows = correct.verdict(numbers, config["limits"])
    out.update(correct=ok and failed == 0, checks=rows,
               reference_s=time.perf_counter() - t_ref)
    return out
