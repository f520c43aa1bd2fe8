"""``readings.py`` for a cell whose traffic file names the reference and the
comparison that judge it (``runners/job_case_library.judges``; ``readings.py``
names its own in its body): the numbers the comparison compares for the
PROGRAM (the lower readings) and for the CONTROL, the reference in bfloat16
put in the program's place (the upper readings), one line per seed, at the
cell's own size. Not part of a run; run it on the chip.

    python chipbench/readings_case_library.py --workload c4lib_dedupe_virtual --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--rows", type=int)
    ap.add_argument("--populations", help="comma-separated population seeds, one per --seeds "
                    "entry: other PEOPLE, not only another order (each is a cold compile)")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from chipbench import run as harness
    from chipbench.runners import job
    from chipbench.runners.job_case_library import judges

    _, cell, config, traffic = harness.load_cell(args.workload)
    reference, correct = judges(traffic)
    if args.rows:
        config["generator"]["rows"] = args.rows
    harness.device_identity(int(cell["chips"]))
    uid = config["settings"].get("unique_id_column_name", "unique_id")
    seeds = [int(s) for s in args.seeds.split(",")]
    populations = ([int(s) for s in args.populations.split(",")] if args.populations
                   else [config["generator"]["population_seed"]] * len(seeds))
    for seed, population in zip(seeds, populations):
        config["generator"]["population_seed"] = population
        t0 = time.perf_counter()
        frames = job.make_frames(config, traffic, seed)
        prep = reference.prepare(config["settings"], frames)
        for what in args.what.split(","):
            if what == "program":
                out = job.run_job(config["settings"], frames, traffic["calls"])
                produced = {"frame": out["frame"], "tf_frame": out["tf_frame"],
                            "params": out["params"], "digests": [out["digest"]], "uid": uid}
                extra = {"job_wall_s": out["wall_s"]}
                del out
            else:
                control = reference.run(config["settings"], frames, precision="bfloat16")
                produced, extra = correct.stand_in(control, uid), {"updates": control["updates"]}
            ok, rows = correct.verdict(correct.compare(produced, prep), config["limits"])
            print("READING " + json.dumps({
                "workload": args.workload, "seed": seed, "population": population,
                "what": what, "correct": ok,
                "checks": rows, **extra,
                "seconds": time.perf_counter() - t0}), flush=True)
            del produced
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
