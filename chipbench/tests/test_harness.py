"""The harness end to end on the CPU backend (rehearsal, tiny rows): both
cells; a cell, configuration and metric added as files only; no TPU and no
rehearsal flag means no result; and the timed path broken underneath has to
come out as not correct."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = {
    "c4_dedupe_virtual": {"generator": {"rows": 6000},
                          "settings": {"pair_batch_size": 65536, "max_resident_pairs": 4096}},
    "c3_link_tf": {"generator": {"rows": 9000}, "settings": {"pair_batch_size": 65536}},
}


def run_cell(cell, trace=0, overrides=None, manifest=None, rehearsal=True, seconds=2):
    env = {k: v for k, v in os.environ.items() if not k.startswith("CHIPBENCH_")}
    env["JAX_PLATFORMS"] = "cpu"
    if rehearsal:
        env["CHIPBENCH_REHEARSAL"] = "1"
        env["CHIPBENCH_REHEARSAL_OVERRIDES"] = json.dumps(overrides or TINY.get(cell, {}))
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
           "--seed", str(2**31 + 5), "--seconds", str(seconds), "--trace", str(trace)]
    if manifest:
        cmd += ["--manifest", manifest]
    return subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)


def result_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines and lines[-1].startswith("REHEARSAL "), proc.stdout[-2000:]
    return json.loads(lines[-1][len("REHEARSAL "):])


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_runs_end_to_end(cell):
    res = result_of(run_cell(cell, trace=0))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"pairs_per_s", "setup_s"}
    assert res["metrics"]["pairs_per_s"]["value"] > 0
    assert list(res)[-1] == "checks" and all(len(row) == 3 for row in res["checks"])


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_run_reports_the_per_layer_metrics(cell):
    res = result_of(run_cell(cell, trace=1))
    assert res["correct"] is True, res["checks"]
    want = {m["name"] for m in manifest()["per_layer"]
            if cell in m.get("workloads", [cell]) and m["source"] != "device_trace"}
    assert want <= set(res["metrics"]), sorted(want - set(res["metrics"]))
    # no device plane on the CPU backend: trace readers find nothing and say nothing
    assert "device_idle_pct" not in res["metrics"]
    assert "gamma_hbm_roofline" not in res["metrics"]
    assert ("tf_s" in res["metrics"]) == (cell == "c3_link_tf")


def test_no_tpu_and_no_rehearsal_flag_prints_no_result():
    proc = run_cell("c4_dedupe_virtual", rehearsal=False)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_a_cell_a_configuration_and_a_metric_are_added_as_files_only(tmp_path):
    """Throw-away files dropped into the directories, one manifest entry each,
    no edit to any file that is there."""
    with open(os.path.join(BENCH, "configs", "baseline_c3.json")) as f:
        config = json.load(f)
    config["generator"]["rows"] = 4000
    config["settings"]["pair_batch_size"] = 32768
    added = {
        os.path.join(BENCH, "configs", "zz_throwaway.json"): config,
        os.path.join(BENCH, "traffic", "zz_scored_only.json"):
            {"runner": "job", "inputs": "split", "calls": ["get_scored_comparisons"]},
        os.path.join(BENCH, "metrics", "zz_scoring_call_s.json"):
            {"reader": "call_wall", "args": {"call": "get_scored_comparisons"}},
    }
    m = manifest()
    m["configs"].append({**m["configs"][0], "name": "zz_throwaway",
                         "file": "chipbench/configs/zz_throwaway.json"})
    m["workloads"].append({"name": "zz_cell", "config": "zz_throwaway",
                           "traffic": "zz_scored_only", "chips": 1, "why": "test"})
    m["per_layer"].append({**m["per_layer"][0], "name": "zz_scoring_call_s",
                           "workloads": ["zz_cell"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    try:
        for name, content in added.items():
            with open(name, "w") as f:
                json.dump(content, f)
        res = result_of(run_cell("zz_cell", trace=1, overrides={}, manifest=str(path)))
    finally:
        for name in added:
            if os.path.exists(name):
                os.remove(name)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["zz_scoring_call_s"]["value"] > 0
    # metrics without a workloads key apply to the new cell unasked; tf_s lists
    # its cells and stays out
    assert "encode_s" in res["metrics"] and "tf_s" not in res["metrics"]


# ---------------------------------------------------------------------------
# The timed path broken underneath: `correct` has to come out false
# ---------------------------------------------------------------------------


def _half_the_pairs(job):
    job["frame"] = job["frame"].iloc[::2].reset_index(drop=True)
    job["pairs"] = len(job["frame"])


def _one_answer_altered(job):
    frame = job["frame"].copy()
    col = frame.columns.get_loc("match_probability")
    p = float(frame.iat[len(frame) // 2, col])
    frame.iat[len(frame) // 2, col] = p + 0.05 if p < 0.5 else p - 0.05
    job["frame"] = frame


def _one_level_altered(job):
    frame = job["frame"].copy()
    col = [c for c in frame.columns if c.startswith("gamma_")][0]
    frame.loc[len(frame) // 3, col] = 1 - min(int(frame.loc[len(frame) // 3, col]), 1)
    job["frame"] = frame


def _em_left_out(job):
    """The step that returns its state unchanged: parameters still the priors."""
    from splink_tpu import Splink

    job["params"] = copy.deepcopy(Splink(job["_settings"], **job["_frames"]).params.params)


FAULTS = {"half_the_pairs": (_half_the_pairs, "pairs_wrong"),
          "one_answer_altered": (_one_answer_altered, "score_gap"),
          "one_level_altered": (_one_level_altered, "gamma_wrong"),
          "em_left_out": (_em_left_out, "param_gap")}


@pytest.fixture(scope="module")
def rehearsal_ctx():
    sys.path.insert(0, ROOT)
    from chipbench import run as harness

    config = harness.merge(harness.load("configs", "baseline_c4"), TINY["c4_dedupe_virtual"])
    traffic = harness.load("traffic", "dedupe_jobs")
    import time

    return lambda: {"config": config, "traffic": traffic, "seed": 77, "seconds": 0.5,
                    "trace": False, "trace_dir": "", "t_process_start": time.perf_counter()}


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_broken_timed_path_is_not_correct(fault, rehearsal_ctx, monkeypatch):
    from chipbench.runners import job

    sound = job.run_job

    def broken(settings, frames, calls):
        out = sound(settings, frames, calls)
        out["_settings"], out["_frames"] = copy.deepcopy(settings), frames
        FAULTS[fault][0](out)
        return out

    if fault:
        monkeypatch.setattr(job, "run_job", broken)
    out = job.run(rehearsal_ctx())
    failing = {name for name, value, limit in out["checks"]
               if limit is not None and value > limit}
    if fault is None:
        assert out["correct"] is True and not failing, out["checks"]
    else:
        assert out["correct"] is False
        assert FAULTS[fault][1] in failing, out["checks"]
