"""The ``job_stream`` runner: whole linkage jobs whose scoring call returns a
GENERATOR of scored chunks (``Splink.stream_scored_comparisons``), back to
back, each from the pandas frame to its last chunk consumed.

The consumer is the traffic file's: of every chunk its length and its float64
sum of ``match_probability`` go to the job's digest and the chunk is appended
to a list — no concat, no disk, nothing else inside the window; the list of
the job before is dropped when a job ends. After the window the last job's
chunks are joined ONCE into the frame the comparison takes, beside each
chunk's length and schema (``correct_stream``).

Frames, the compile counter and the judges are ``runners/job.py``'s and
``runners/job_case_library.py``'s, imported; the window loop is a copy of
theirs, because neither takes the job as a parameter (PERF.md §7).
"""

from __future__ import annotations

import copy
import gc
import glob
import os
import shutil
import time

import numpy as np

from chipbench.correct_stream import schema
from chipbench.runners.job import CompileCounter, _light, make_frames
from chipbench.runners.job_case_library import judges


def run_job(settings: dict, frames: dict, calls: list[str]) -> dict:
    """One job: the constructor, then the one streaming call driven to
    exhaustion. Returns the keys the readers take of a ``job.run_job`` job
    (the whole stream counts as the scoring call), the chunks, how many
    there were and the seconds the consumer held them."""
    from splink_tpu import Splink
    from splink_tpu.utils.profiling import stage_timings

    (call,) = calls
    t0 = time.perf_counter()
    linker = Splink(copy.deepcopy(settings), **frames)
    t1 = time.perf_counter()
    chunks, pairs, total, consumer_s = [], 0, 0.0, 0.0
    for chunk in getattr(linker, call)():
        t_chunk = time.perf_counter()
        pairs += len(chunk)
        total += float(chunk["match_probability"].to_numpy().sum(dtype=np.float64))
        chunks.append(chunk)
        consumer_s += time.perf_counter() - t_chunk
    t_end = time.perf_counter()
    stages = {k: sum(v) for k, v in stage_timings().items()}
    return {
        "frame": chunks,  # under the key ``job._light`` drops
        "params": copy.deepcopy(linker.params.params),
        "pairs": pairs,
        "chunks": len(chunks),
        "consumer_s": consumer_s,
        "wall_s": t_end - t0,
        "scored_s": t_end - t0,
        "scored_stages": stages,
        "stages": stages,
        "call_s": {call: t_end - t1},
        "digest": (pairs, total, float(linker.params.params["λ"])),
    }


def joined(chunks: list, settings: dict) -> dict:
    """What ``correct_stream.compare`` takes of the checked job's chunks: the
    one frame they make end to end, and what only the chunks can say."""
    import pandas as pd

    return {
        "frame": pd.concat(chunks, ignore_index=True),
        "chunk_rows": [len(c) for c in chunks],
        "chunk_schemas": [schema(c) for c in chunks],
        "pair_batch_size": int(settings["pair_batch_size"]),
    }


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

    # the program's device state goes before the reference touches the chip;
    # the one join of the checked job's chunks is made here, outside the window
    produced = {**joined(last["frame"], settings), "tf_frame": None,
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
