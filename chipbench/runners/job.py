"""The ``job`` runner: whole linkage jobs through the public facade, back to
back, each from the pandas frame(s) to the scored frame in host memory.

Set-up (all inside ``setup_s``): frame(s) from the seed at exactly the
configuration's rows, one whole warm-up job on those same frames (so every
program the window runs, pattern EM's data-shaped one included, is compiled
or read from the cache). Window: start another job while elapsed <
``seconds``; the job in flight is finished. With ``trace`` the first job of
the window runs under the profiler.

The traffic file gives the job's shape: ``inputs`` ("single" frame or the
"split" pair of a link) and ``calls``, the facade methods in order — the
first takes nothing and returns the scored frame, each later one takes the
frame before it.
"""

from __future__ import annotations

import copy
import gc
import glob
import os
import shutil
import time

import numpy as np


class CompileCounter:
    """Compile requests that reached the backend, as jax.monitoring reports
    them: ``requests`` counts every one, ``cache_reads`` those the persistent
    cache served; the difference was compiled."""

    def __init__(self):
        import jax

        self.requests = 0
        self.cache_reads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, secs: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.requests += 1

    def _on_event(self, name: str, **_kw) -> None:
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_reads += 1

    def snapshot(self) -> tuple[int, int]:
        return self.requests - self.cache_reads, self.cache_reads


def make_frames(config: dict, traffic: dict, seed: int) -> dict:
    """The job's input frame(s). The PEOPLE are the configuration's
    (``population_seed``): block sizes shape the program's compiled kernels, so
    a population that changed with every seed would make every run a cold
    compile (75-120 s on the chip). ``seed`` gives every run the same people
    in another order under other unique ids."""
    from chipbench import datagen

    gen = dict(config["generator"])
    kind, population = gen.pop("kind"), gen.pop("population_seed")
    if kind != "people":
        raise ValueError(f"unknown generator kind {kind!r}")
    df = datagen.make_people(seed=population, **gen)
    if traffic["inputs"] == "single":
        parts = {"df": df}
    elif traffic["inputs"] == "split":
        parts = dict(zip(("df_l", "df_r"), datagen.split_for_linking(df)))
    else:
        raise ValueError(f"unknown inputs {traffic['inputs']!r}")
    rng = np.random.default_rng(seed)
    first_id = 0
    for name, part in parts.items():
        part = part.iloc[rng.permutation(len(part))].reset_index(drop=True)
        part["unique_id"] = np.arange(first_id, first_id + len(part))
        first_id += len(part)
        parts[name] = part
    return parts


def run_job(settings: dict, frames: dict, calls: list[str]) -> dict:
    """One job. Returns its frames, parameters, wall and stage seconds."""
    from splink_tpu import Splink
    from splink_tpu.utils.profiling import stage_timings

    t0 = time.perf_counter()
    linker = Splink(copy.deepcopy(settings), **frames)
    outs, call_s = [], {}
    staged = None
    for call in calls:
        t1 = time.perf_counter()
        outs.append(getattr(linker, call)(*outs[-1:]))
        call_s[call] = time.perf_counter() - t1
        if staged is None:  # stages of the constructor and the scoring call
            staged = {k: sum(v) for k, v in stage_timings().items()}
            scored_s = time.perf_counter() - t0
    wall = time.perf_counter() - t0
    stages = {k: sum(v) for k, v in stage_timings().items()}
    frame = outs[0]
    p = frame["match_probability"].to_numpy()
    return {
        "frame": frame,
        "tf_frame": outs[1] if len(outs) > 1 else None,
        "params": copy.deepcopy(linker.params.params),
        "pairs": len(frame),
        "wall_s": wall,
        "scored_s": scored_s,
        "scored_stages": staged,
        "stages": stages,
        "call_s": call_s,
        "digest": (len(frame), float(p.sum(dtype=np.float64)),
                   float(linker.params.params["λ"])),
    }


def _light(job: dict) -> dict:
    return {k: v for k, v in job.items() if k not in ("frame", "tf_frame", "params")}


def run(ctx: dict) -> dict:
    """Drive one cell. ``ctx``: config, traffic, seed, seconds, trace,
    trace_dir, t_process_start. Returns what run.py turns into the result."""
    import jax

    from chipbench import correct, reference

    config, traffic = ctx["config"], ctx["traffic"]
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
