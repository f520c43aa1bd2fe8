"""The configuration ``baseline_c5`` and its cell ``c5_dedupe_stream`` on the
CPU backend: the files are well formed and differ from the one-frame control's
(``baseline_c4``) in ``virtual_materialise_ids`` alone; the cell rehearses
through an unedited ``run.py`` and its traced line holds every new metric that
needs no device trace; faults planted in the chunks under the runner are
caught, each by its own number; no accepted ``workloads`` list names the cell.
"""

import json
import os
import sys
import time

import numpy as np
import pandas as pd
import pytest

from chipbench.tests.test_harness import ROOT, manifest, result_of, run_cell

CELL, CONFIG, CONTROL, PEOPLE = ("c5_dedupe_stream", "baseline_c5", "baseline_c4",
                                 "baseline_c4_v5e4")
TINY = {"generator": {"rows": 6000},
        "settings": {"pair_batch_size": 1024, "max_resident_pairs": 1024}}
OWN = {"stream_gamma_device_s", "stream_gamma_hbm_roofline", "stream_d2h_wait_s",
       "stream_frame_assembly_s", "stream_decode_pairs_s", "stream_recomputed_positions",
       "stream_redo_positions", "stream_suspended_s"}


def config_file(name):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_controls_but_for_the_ids():
    config, control = config_file(CONFIG), config_file(CONTROL)
    changed = {k for k in set(config["settings"]) | set(control["settings"])
               if config["settings"].get(k) != control["settings"].get(k)}
    assert changed == {"virtual_materialise_ids"}
    assert config["settings"]["virtual_materialise_ids"] == "off"
    assert config["generator"] == config_file(PEOPLE)["generator"]
    assert config["reduced"] == ["rows", "chips"]
    assert config["published"] == {"rows": 100_000_000, "chips": 8}
    assert config["gamma_bytes_per_pair"] == 2 * control["gamma_bytes_per_pair"]
    stream = {"chunks_oversize", "chunk_schema_differs", "chunks_empty"}
    assert set(config["limits"]) == set(control["limits"]) | stream
    assert all(config["limits"][k] == 0 for k in stream)
    assert {k: config["limits"][k] for k in control["limits"]} == control["limits"]
    assert "stream" in config["guarantees"]
    for key in ("source", "deployment", "assumed", "sizing"):
        assert config[key], key
    assert len(config["source"]) <= 200 and "configs[4]" in config["source"]


def test_the_manifest_adds_the_cell_and_widens_no_accepted_list():
    m = manifest()
    entry = [c for c in m["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == ["rows", "chips"]
    assert entry[0]["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry[0]["source"] == config_file(CONFIG)["source"]
    assert m["configs"][-1] is entry[0] and m["workloads"][-1]["name"] == CELL
    cells = [w for w in m["workloads"] if w["name"] == CELL]
    assert cells == [{"name": CELL, "config": CONFIG, "traffic": "dedupe_stream_jobs",
                      "chips": 1, "why": cells[0]["why"]}]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    own = {p["name"] for p in m["per_layer"] if p.get("workloads") == [CELL]}
    assert own == OWN
    assert [p["name"] for p in m["per_layer"][-len(OWN):]] == [
        p["name"] for p in m["per_layer"] if p["name"] in OWN]  # appended, at the end
    for name in own:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", f"{name}.json"))
    assert not [p["name"] for p in m["per_layer"]
                if CELL in p.get("workloads", []) and p["name"] not in own]


def test_the_cell_rehearses_end_to_end():
    res = result_of(run_cell(CELL, trace=0, overrides=TINY))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"pairs_per_s", "setup_s"}
    checks = {name: value for name, value, _ in res["checks"]}
    for exact in ("pairs_wrong", "gamma_wrong", "jobs_differ", "chunks_oversize",
                  "chunk_schema_differs", "chunks_empty"):
        assert checks[exact] == 0, res["checks"]
    assert res["window"]["window_compiles"] == 0


def test_the_traced_rehearsal_reads_the_new_metrics():
    res = result_of(run_cell(CELL, trace=1, overrides=TINY))
    assert res["correct"] is True, res["checks"]
    want = {p["name"] for p in manifest()["per_layer"]
            if CELL in p.get("workloads", [CELL]) and p["source"] != "device_trace"}
    assert want <= set(res["metrics"]), sorted(want - set(res["metrics"]))
    value = {k: v["value"] for k, v in res["metrics"].items()}
    # the regime engaged: every position's id was computed a second time
    assert value["stream_recomputed_positions"] > res["checks"][0][1] > 1024
    assert value["stream_redo_positions"] == 0  # a batch of 1024 cannot overflow
    assert value["stream_suspended_s"] > 0 and value["stream_d2h_wait_s"] > 0
    # no device plane on the CPU backend: the trace readers say nothing
    assert not {"stream_gamma_device_s", "stream_gamma_hbm_roofline"} & set(value)
    # the accepted cells' own lists stay their own
    assert not {"gamma_device_s", "d2h_wait_s", "frame_assembly_s", "decode_pairs_s",
                "facade_self_s"} & set(value)


# ---------------------------------------------------------------------------
# Faults planted in the chunks, under the runner
# ---------------------------------------------------------------------------


def _dropped(job):
    del job["frame"][1]


def _twice(job):
    job["frame"].insert(1, job["frame"][0])


def _too_long(job):
    job["frame"][:2] = [pd.concat(job["frame"][:2], ignore_index=True)]


def _column_cast(job):
    job["frame"][1] = job["frame"][1].astype({"gamma_dob": np.int32})


def _empty(job):
    job["frame"].insert(2, job["frame"][0].iloc[:0])


FAULTS = {"chunk_dropped": (_dropped, "pairs_wrong"), "chunk_twice": (_twice, "pairs_wrong"),
          "chunk_too_long": (_too_long, "chunks_oversize"),
          "chunk_column_cast": (_column_cast, "chunk_schema_differs"),
          "chunk_empty": (_empty, "chunks_empty")}


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_a_fault_in_the_chunks_is_caught_under_the_runner(fault, monkeypatch):
    sys.path.insert(0, ROOT)
    from chipbench import run as harness
    from chipbench.runners import job_stream

    sound = job_stream.run_job

    def broken(settings, frames, calls):
        out = sound(settings, frames, calls)
        assert len(out["frame"]) >= 4 and len(out["frame"][0]) + len(out["frame"][1]) > 1024
        FAULTS[fault][0](out)
        return out

    if fault:
        monkeypatch.setattr(job_stream, "run_job", broken)
    out = job_stream.run({
        "config": harness.merge(harness.load("configs", CONFIG), TINY),
        "traffic": harness.load("traffic", "dedupe_stream_jobs"), "seed": 77, "seconds": 0.5,
        "trace": False, "trace_dir": "", "t_process_start": time.perf_counter()})
    failing = {name for name, value, limit in out["checks"]
               if limit is not None and value > limit}
    if fault is None:
        assert out["correct"] is True and not failing, out["checks"]
        assert {"chunks", "consumer_s"} <= set(out["jobs"][0])
    else:
        assert out["correct"] is False
        assert FAULTS[fault][1] in failing, out["checks"]
        stream = {"chunks_oversize", "chunk_schema_differs", "chunks_empty"}
        assert failing & stream == {FAULTS[fault][1]} & stream, out["checks"]
