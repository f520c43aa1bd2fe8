"""Sizing run for one cell (README.md, "Sizing"): one warm-up job and one timed
job at the configuration's rows and batch, or at those given, printing the
job's wall, its pairs and the device's peak memory. Not part of a run.

    python chipbench/sizing.py --workload <cell> [--rows N] [--batch B]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from chipbench import run as harness
    from chipbench.runners import job

    _, cell, config, traffic = harness.load_cell(args.workload)
    if args.rows:
        config["generator"]["rows"] = args.rows
    if args.batch:
        config["settings"]["pair_batch_size"] = args.batch
    harness.device_identity(int(cell["chips"]))
    import jax

    t0 = time.perf_counter()
    frames = job.make_frames(config, traffic, args.seed)
    t_frames = time.perf_counter() - t0
    walls = []
    for _ in range(2):
        out = job.run_job(config["settings"], frames, traffic["calls"])
        walls.append(out["wall_s"])
    stats = jax.local_devices()[0].memory_stats() or {}
    print("SIZING " + json.dumps({
        "workload": args.workload, "rows": config["generator"]["rows"],
        "pair_batch_size": config["settings"].get("pair_batch_size"),
        "frames_s": t_frames, "first_job_s": walls[0], "second_job_s": walls[1],
        "pairs": out["pairs"], "stages": out["stages"], "call_s": out["call_s"],
        "memory_stats": stats,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
