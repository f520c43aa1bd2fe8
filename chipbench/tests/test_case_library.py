"""The configuration ``c4_case_library`` and its cell ``c4lib_dedupe_virtual``
on the CPU backend: the files are well formed and differ from the control's
(``baseline_c4``) in the comparison columns alone; the bfloat16 control fails at
a small size; faults planted in the levels are caught, each by its own
number; the runner, the traffic and the metric files load through an
unedited ``run.py`` in rehearsal; no accepted ``workloads`` list names the cell.
"""

import json
import os

import numpy as np
import pytest

from chipbench import correct_case_library as correct
from chipbench import datagen
from chipbench import reference_case_library as reference
from chipbench.tests.test_harness import ROOT, manifest, result_of, run_cell

CELL, CONFIG, CONTROL = "c4lib_dedupe_virtual", "c4_case_library", "baseline_c4"
TINY = {"generator": {"rows": 6000},
        "settings": {"pair_batch_size": 65536, "max_resident_pairs": 4096}}
OWN = {"lib_gamma_device_s", "lib_gamma_hbm_roofline", "lib_d2h_wait_s",
       "lib_frame_assembly_s", "lib_string_evals", "lib_decode_pairs_s",
       "lib_facade_self_s"}


def config_file(name):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def frames_of(config, rows, seed):
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "rows", "population_seed")}
    return {"df": datagen.make_people(rows, seed=seed, **gen)}


def test_the_configuration_is_the_controls_but_for_the_comparisons():
    config, control = config_file(CONFIG), config_file(CONTROL)
    changed = {k for k in set(config["settings"]) | set(control["settings"])
               if config["settings"].get(k) != control["settings"].get(k)}
    assert changed == {"comparison_columns"}
    assert config["generator"] == control["generator"]
    assert config["reduced"] == ["rows"] and config["published"] == {"rows": 10_000_000}
    kinds = [(c.get("custom_name", c.get("col_name")), c["comparison"]["kind"],
              c.get("num_levels", 2)) for c in config["settings"]["comparison_columns"]]
    assert kinds == [("first_name", "name_inversion", 4), ("surname", "name_inversion", 4),
                     ("dob", "levenshtein", 3), ("city", "exact", 2),
                     ("postcode", "levenshtein", 3), ("surname_lev", "levenshtein", 4)]
    assert set(config["limits"]) == set(control["limits"]) | {"lev_tie_flips"}
    assert all(config["limits"][k] == 0 for k in
               ("pairs_wrong", "gamma_wrong", "lev_tie_flips", "scores_not_finite",
                "jobs_differ"))
    for key in ("source", "deployment", "guarantees", "assumed", "sizing"):
        assert config[key], key
    assert len(config["source"]) <= 200


def test_the_manifest_adds_the_cell_and_widens_no_accepted_list():
    m = manifest()
    entry = [c for c in m["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == ["rows"]
    assert entry[0]["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry[0]["source"] == config_file(CONFIG)["source"]
    cells = [w for w in m["workloads"] if w["name"] == CELL]
    assert cells == [{"name": CELL, "config": CONFIG, "traffic": "dedupe_jobs_case_library",
                      "chips": 1, "why": cells[0]["why"]}]
    assert CONTROL.replace("baseline_", "") + "_dedupe_virtual" in cells[0]["why"]
    own = {p["name"] for p in m["per_layer"] if p.get("workloads") == [CELL]}
    assert own == OWN
    for name in own:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", f"{name}.json"))
    assert not [p["name"] for p in m["per_layer"]
                if CELL in p.get("workloads", []) and p["name"] not in own]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_passes_and_the_bfloat16_control_fails(seed):
    config = config_file(CONFIG)
    frames = frames_of(config, 12_000, seed)
    prep = reference.prepare(config["settings"], frames)
    ref = reference.run(config["settings"], frames)
    assert len(ref["p"]) > 1000 and ref["updates"] > 1
    ok, rows = correct.verdict(correct.compare(correct.stand_in(ref), prep), config["limits"])
    assert ok, rows
    control = reference.run(config["settings"], frames, precision="bfloat16")
    ok, rows = correct.verdict(correct.compare(correct.stand_in(control), prep),
                               config["limits"])
    assert not ok, rows
    failed = {n for n, v, lim in rows if lim is not None and v > lim}
    assert failed & {"gamma_wrong", "param_gap", "score_gap"}, rows


# ---------------------------------------------------------------------------
# Faults planted in the levels: each has to be caught by its own number
# ---------------------------------------------------------------------------


def _strict_less(ref, prep, settings):
    """``<`` for ``<=``: EVERY Levenshtein ratio on its threshold falls a
    level. Each such cell is reachable by the tie rule, and counted."""
    G = ref["gamma"].copy()
    for c in (2, 4, 5):
        G[:, c] -= prep["boundary"][:, c]
    return G


def _division_one_ulp_high(ref, prep, settings):
    """What the parent's program did on the chip: SOME ties of one column
    (surname_lev's 0.2 / 0.4) fall a level, the others hold."""
    G = ref["gamma"].copy()
    ties = np.flatnonzero(prep["boundary"][:, 5])
    G[ties[::4], 5] -= 1
    return G


def _no_null_guard(ref, prep, settings):
    """The inversion branch without its null guard, over rows whose null
    surname holds another record's bytes (here: the commonest first name)."""
    table = prep["table"].copy()
    stale = table["first_name"].mode()[0]
    table["surname"] = table["surname"].where(table["surname"].notna(), stale)
    first = {"comparison_columns": settings["comparison_columns"][:1]}
    G = ref["gamma"].copy()
    G[:, 0] = reference.gamma_levels(first, table, prep["idx_l"], prep["idx_r"])[0][:, 0]
    return G


def _levels_exchanged(ref, prep, settings):
    """Levels 1 and 2 of the inversion columns exchanged."""
    G = ref["gamma"].copy()
    for c in (0, 1):
        G[:, c] = np.where(G[:, c] == 1, 2, np.where(G[:, c] == 2, 1, G[:, c]))
    return G


@pytest.mark.parametrize("fault,number", [(_strict_less, "lev_tie_flips"),
                                          (_division_one_ulp_high, "lev_tie_flips"),
                                          (_no_null_guard, "gamma_wrong"),
                                          (_levels_exchanged, "gamma_wrong")])
def test_a_planted_level_fault_is_caught_by_its_own_number(fault, number):
    config = config_file(CONFIG)
    settings = config["settings"]
    frames = frames_of(config, 12_000, 4)
    prep = reference.prepare(settings, frames)
    ref = reference.run(settings, frames)
    shown = dict(ref, gamma=fault(ref, prep, settings))
    cells = int((shown["gamma"] != ref["gamma"]).sum())
    assert cells > 0
    numbers = correct.compare(correct.stand_in(shown), prep)
    ok, rows = correct.verdict(numbers, config["limits"])
    failing = {n for n, v, lim in rows if lim is not None and v > lim}
    assert not ok and number in failing, rows
    assert numbers["pairs_wrong"] == 0
    if number == "gamma_wrong":
        # a tie's other side is forgiven, anything else is wrong; scores and
        # parameters follow, but the levels name the fault
        assert 0 < numbers["gamma_wrong"] <= cells
    else:
        assert numbers["gamma_wrong"] == 0
        assert numbers["lev_tie_flips"] == numbers["gamma_boundary_flips"] == cells


# ---------------------------------------------------------------------------
# Through the unedited harness, in rehearsal
# ---------------------------------------------------------------------------


def test_the_cell_rehearses_end_to_end():
    res = result_of(run_cell(CELL, trace=0, overrides=TINY))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"pairs_per_s", "setup_s"}
    checks = {name: value for name, value, _ in res["checks"]}
    assert checks["pairs_wrong"] == checks["gamma_wrong"] == checks["jobs_differ"] == 0
    assert res["window"]["window_compiles"] == 0


def test_the_traced_rehearsal_reads_the_new_metrics():
    res = result_of(run_cell(CELL, trace=1, overrides=TINY))
    assert res["correct"] is True, res["checks"]
    m = manifest()
    want = {p["name"] for p in m["per_layer"]
            if CELL in p.get("workloads", [CELL]) and p["source"] != "device_trace"
            and p["name"] != "kernel_build_s"}
    assert want <= set(res["metrics"]), sorted(want - set(res["metrics"]))
    evals = res["metrics"]["lib_string_evals"]
    assert evals["unit"] == "count" and evals["value"] > 0 and evals["value"] % 7 == 0
    # no device plane on the CPU backend: the trace readers say nothing
    assert "lib_gamma_device_s" not in res["metrics"]
    assert "lib_gamma_hbm_roofline" not in res["metrics"]
    # the accepted cell's own lists stay its own
    assert not {"gamma_device_s", "d2h_wait_s", "frame_assembly_s", "decode_pairs_s",
                "facade_self_s"} & set(res["metrics"])


def test_the_span_count_reader_reads_nothing_from_a_program_without_the_count(monkeypatch):
    from chipbench.readers import span_count
    from splink_tpu.utils import profiling

    monkeypatch.setattr(profiling, "runs", lambda: ["r"])
    monkeypatch.setattr(profiling, "spans", lambda run: [
        {"name": "gammas_patterns", "counts": {"pairs": 10, "batches": 1}}])
    run = {"jobs": [{}], "failed": 0}
    assert span_count.read(run, ["gammas_patterns"], "string_evals") is None
    assert span_count.read(run, ["gammas_patterns"], "pairs") == 10
    assert span_count.read({"jobs": [], "failed": 0}, ["gammas_patterns"], "pairs") is None
