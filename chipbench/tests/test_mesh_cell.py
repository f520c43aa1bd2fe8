"""The four-chip cell ``c4_dedupe_mesh4`` (configuration ``baseline_c4_v5e4``)
on the CPU backend: its files are well formed and differ from the one-chip
control's in the sharding alone; the cell rehearses end to end on four forced
CPU devices through the unedited harness; the faults ``test_harness`` plants
under the job runner are still caught when the job is sharded.

``conftest.py`` forces no device count, so every run here is a process this
file starts with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench.tests.test_harness import FAULTS, ROOT, manifest, result_of

CELL, CONFIG, CONTROL = "c4_dedupe_mesh4", "baseline_c4_v5e4", "baseline_c4"
TINY = {"generator": {"rows": 6000},
        "settings": {"pair_batch_size": 65536, "max_resident_pairs": 4096}}


def config_file(name):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def four_devices(argv, **env):
    env = {**{k: v for k, v in os.environ.items() if not k.startswith("CHIPBENCH_")},
           "JAX_PLATFORMS": "cpu", "CHIPBENCH_REHEARSAL": "1",
           "CHIPBENCH_REHEARSAL_OVERRIDES": json.dumps(TINY),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4", **env}
    return subprocess.run([sys.executable, *argv], env=env, capture_output=True, text=True,
                          timeout=600, cwd=ROOT)


def run_cell(trace=0, **env):
    return four_devices([os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL,
                         "--seed", str(2**31 + 29), "--seconds", "2", "--trace", str(trace)],
                        **env)


def test_the_configuration_is_the_controls_but_for_the_sharding():
    config, control = config_file(CONFIG), config_file(CONTROL)
    changed = {k for k in set(config["settings"]) | set(control["settings"])
               if config["settings"].get(k) != control["settings"].get(k)}
    assert changed == {"mesh", "pair_batch_size"}
    assert config["settings"]["mesh"] == {"data": 4}
    assert config["settings"]["pair_batch_size"] == 4 * control["settings"]["pair_batch_size"]
    assert {k: v for k, v in config["generator"].items() if k != "rows"} == \
           {k: v for k, v in control["generator"].items() if k != "rows"}
    assert 1_080_000 <= config["generator"]["rows"] <= 1_240_000
    assert config["reduced"] == ["rows"] and config["published"] == {"rows": 10_000_000, "chips": 4}
    assert config["gamma_bytes_per_pair"] == control["gamma_bytes_per_pair"]
    assert set(config["limits"]) == set(control["limits"])
    assert all(config["limits"][k] == 0 for k in
               ("pairs_wrong", "gamma_wrong", "scores_not_finite", "jobs_differ"))
    for key in ("source", "deployment", "guarantees", "assumed", "sizing"):
        assert config[key], key
    assert "chips" in " ".join(config["guarantees"].values())


def test_the_manifest_names_the_cell_once_on_four_chips():
    m = manifest()
    entry = [c for c in m["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == ["rows"]
    assert entry[0]["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry[0]["source"] == config_file(CONFIG)["source"]
    cells = [w for w in m["workloads"] if w["name"] == CELL]
    assert cells == [{"name": CELL, "config": CONFIG, "traffic": "dedupe_jobs", "chips": 4,
                      "why": cells[0]["why"]}]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    own = {p["name"] for p in m["per_layer"] if p.get("workloads") == [CELL]}
    assert own == {"mesh_transfer_s", "mesh_gamma_device_s", "mesh_gamma_hbm_roofline"}
    for name in own:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", f"{name}.json"))
    # no accepted list took the new cell: those are a benchmark PR's to widen
    assert not [p["name"] for p in m["per_layer"]
                if CELL in p.get("workloads", []) and p["name"] not in own]


def test_the_cell_rehearses_on_four_devices():
    res = result_of(run_cell(trace=0))
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"pairs_per_s", "setup_s"}
    checks = {name: value for name, value, _ in res["checks"]}
    assert checks["pairs_wrong"] == checks["gamma_wrong"] == checks["jobs_differ"] == 0
    assert res["window"]["window_compiles"] == 0


def test_the_traced_rehearsal_reads_the_mesh_spans():
    res = result_of(run_cell(trace=1))
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["mesh_transfer_s"]["value"] > 0
    # no device plane on the CPU backend: the trace readers say nothing
    assert "mesh_gamma_device_s" not in res["metrics"]
    assert "mesh_gamma_hbm_roofline" not in res["metrics"]
    for name in ("encode_s", "blocking_s", "gamma_pass_s", "em_s", "score_output_s"):
        assert name in res["metrics"], name


def test_without_four_tpu_chips_there_is_no_result():
    proc = run_cell(JAX_PLATFORMS="cpu", CHIPBENCH_REHEARSAL="0")
    assert proc.returncode == 2 and not proc.stdout.strip()


PLANTED = """
import copy, json, sys, time
sys.path.insert(0, {root!r})
from chipbench import run as harness
from chipbench.runners import job
from chipbench.tests.test_harness import FAULTS
import jax
assert jax.device_count() == 4
fault = {fault!r}
config = harness.merge(harness.load("configs", {config!r}), {tiny!r})
assert config["settings"]["mesh"] == {{"data": 4}}
sound = job.run_job
def broken(settings, frames, calls):
    out = sound(settings, frames, calls)
    out["_settings"], out["_frames"] = copy.deepcopy(settings), frames
    FAULTS[fault][0](out)
    return out
if fault:
    job.run_job = broken
out = job.run({{"config": config, "traffic": harness.load("traffic", "dedupe_jobs"), "seed": 77,
               "seconds": 0.5, "trace": False, "trace_dir": "",
               "t_process_start": time.perf_counter()}})
print("PLANTED " + json.dumps({{"correct": out["correct"], "checks": out["checks"]}}))
"""


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_planted_faults_are_caught_under_the_mesh(fault):
    proc = four_devices(["-c", PLANTED.format(root=ROOT, fault=fault, config=CONFIG, tiny=TINY)])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("PLANTED ")][-1]
    out = json.loads(line[len("PLANTED "):])
    failing = {name for name, value, limit in out["checks"]
               if limit is not None and value > limit}
    if fault is None:
        assert out["correct"] is True and not failing, out["checks"]
    else:
        assert out["correct"] is False
        assert FAULTS[fault][1] in failing, out["checks"]
