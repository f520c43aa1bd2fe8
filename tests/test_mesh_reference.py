"""BASELINE config 4 under a mesh against the plain reference.

The deployment ``chipbench/configs/baseline_c4_v5e4.json`` (the cell
``c4_dedupe_mesh4``: one dedupe job sharded over the four chips of a v5e host)
at twelve thousand rows on the forced CPU devices: the facade job with
``mesh: {"data": n}`` has to give the pair set, every gamma level, λ/m/u and
every score of ``chipbench.reference`` — which knows nothing of chips — within
the limits the configuration's file states, whatever n is and however the
batches fall on the shards. The mesh path's spans and counts (``mesh_put``,
``mesh_gather``, ``devices`` / ``pairs_per_device`` on the pattern stage, the
mesh in ``kernel_lookup``) are held here too; a job without a mesh closes none.
"""

import copy
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import correct, datagen, reference  # noqa: E402
from splink_tpu import Splink  # noqa: E402
from splink_tpu.utils.profiling import spans  # noqa: E402

ROWS = 12000
MASKED = ["l.dob = r.dob", "l.dob = r.dob AND l.city = r.city",
          "l.first_name = r.first_name AND l.surname = r.surname"]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "baseline_c4_v5e4.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def people(config):
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "population_seed", "rows")}
    return datagen.make_people(rows=ROWS, seed=config["generator"]["population_seed"], **gen)


@pytest.fixture(scope="module")
def references(config, people):
    """The reference's half of the comparison, once per set of blocking rules
    (the only part of the settings it reads that a case changes)."""
    memo = {}

    def prepared(settings):
        key = tuple(settings["blocking_rules"])
        if key not in memo:
            memo[key] = reference.prepare(settings, {"df": people})
        return memo[key]

    return prepared


def small(config, n, **over):
    """The configuration's settings at the test's size: the batch and the
    resident bound cut with the rows, the mesh as asked (None: no mesh)."""
    settings = copy.deepcopy(config["settings"])
    settings.update({"pair_batch_size": 1 << 16, "max_resident_pairs": 1024, **over})
    if n is None:
        del settings["mesh"]
    else:
        settings["mesh"] = {"data": n}
    return settings


def job(settings, people):
    linker = Splink(copy.deepcopy(settings), df=people)
    frame = linker.get_scored_comparisons()
    return linker, frame


def numbers(linker, frame, prep):
    p = frame["match_probability"].to_numpy()
    digest = (len(frame), float(p.sum(dtype=np.float64)), float(linker.params.params["λ"]))
    return correct.compare(
        {"frame": frame, "tf_frame": None, "params": linker.params.params,
         "digests": [digest], "uid": "unique_id"}, prep)


CASES = [
    # id, devices, settings changed
    ("one_batch_a_rule-1", 1, {}),
    ("one_batch_a_rule-2", 2, {}),
    ("one_batch_a_rule-4", 4, {}),
    ("one_batch_a_rule-8", 8, {}),
    ("batch_no_multiple_of_the_mesh-2", 2, {"pair_batch_size": 1025}),
    ("batch_no_multiple_of_the_mesh-3", 3, {"pair_batch_size": 1027}),
    ("batch_no_multiple_of_the_mesh-4", 4, {"pair_batch_size": 1030}),
    ("several_batches_a_rule-4", 4, {"pair_batch_size": 2048}),
    ("later_rule_all_masked-2", 2, {"blocking_rules": MASKED}),
    ("later_rule_all_masked-4", 4, {"blocking_rules": MASKED, "pair_batch_size": 1500}),
]


@pytest.mark.parametrize("n,over", [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_mesh_job_equals_the_reference(config, people, references, n, over):
    settings = small(config, n, **over)
    prep = references(settings)
    linker, frame = job(settings, people)
    got = numbers(linker, frame, prep)
    ok, rows = correct.verdict(got, config["limits"])
    assert ok, rows
    assert got["pairs_wrong"] == 0 and got["gamma_wrong"] == 0, rows
    assert got["pairs_produced"] == len(prep["uid_l"]) > 1024

    table = spans(run=linker.run_id)
    stage = [s for s in table if s["name"] == "gammas_patterns"]
    assert len(stage) == 1
    candidates = linker._virtual.n_candidates
    assert stage[0]["counts"]["devices"] == n
    assert stage[0]["counts"]["pairs_per_device"] == -(-candidates // n)
    # one body on one chip and four: the stage has no count of a pruned one
    assert "two_phase" not in stage[0]["counts"]
    assert stage[0]["counts"]["string_evals"] == candidates * 4
    puts = [s for s in table if s["name"] == "mesh_put"]
    gathers = [s for s in table if s["name"] == "mesh_gather"]
    assert puts and all(s["counts"]["devices"] == n and s["counts"]["bytes"] > 0 for s in puts)
    assert gathers and all(s["counts"]["shards"] == n for s in gathers)
    # every candidate position's id came home once, sharded
    assert sum(s["counts"]["bytes"] for s in gathers) >= candidates * 2
    if "blocking_rules" in over:
        # the second rule's candidates are all the first rule's pairs: every
        # shard of its batches is blank, and the job is still the reference's
        rule_pairs = [rp.total for rp in linker._virtual.rules]
        assert rule_pairs[1] > 0 and len(frame) <= candidates - rule_pairs[1]


def test_second_linker_hits_the_mesh_keyed_entries(config, people):
    settings = small(config, 4)
    first, frame_1 = job(settings, people)
    second, frame_2 = job(settings, people)
    lookups = [s for s in spans(run=second.run_id) if s["name"] == "kernel_lookup"]
    sharded = [s for s in lookups if s["counts"]["fun"] == "virtual_pattern"]
    assert len(sharded) == 3  # one program a rule
    assert all(s["counts"] == {"fun": "virtual_pattern", "hit": 1, "shared": 1, "devices": 4}
               for s in sharded)
    assert all(s["counts"]["hit"] == 1 for s in lookups)
    built = [s for s in spans(run=first.run_id) if s["name"] == "kernel_lookup"]
    assert all(s["counts"]["hit"] == 0 for s in built)
    assert not [s for s in spans(run=second.run_id) if s["kind"] == "build"
                and s["name"] != "kernel_lookup"]  # nothing traced, lowered or compiled
    assert frame_1.equals(frame_2)
    # another mesh is another program: its lookups miss
    third, _ = job(small(config, 2), people)
    assert {s["counts"]["hit"] for s in spans(run=third.run_id)
            if s["name"] == "kernel_lookup" and s["counts"]["fun"] == "virtual_pattern"} == {0}


def test_job_without_a_mesh_closes_no_mesh_span_and_scores_the_same(config, people, references):
    settings = small(config, None)
    linker, frame = job(settings, people)
    ok, rows = correct.verdict(numbers(linker, frame, references(settings)), config["limits"])
    assert ok, rows
    table = spans(run=linker.run_id)
    assert not [s for s in table if s["name"] in ("mesh_put", "mesh_gather")]
    stage = [s for s in table if s["name"] == "gammas_patterns"][0]
    assert "devices" not in stage["counts"] and "pairs_per_device" not in stage["counts"]
    assert "two_phase" not in stage["counts"]  # one chip runs the mesh's body
    assert stage["counts"]["redo_positions"] == 0
    assert {s["counts"]["devices"] for s in table if s["name"] == "kernel_lookup"} == {1}
    # the same pairs, levels and scores whatever the number of chips
    _, frame_4 = job(small(config, 4), people)
    cols = [c for c in frame.columns if c != "match_probability"]
    assert frame[cols].equals(frame_4[cols])
    assert np.abs(frame["match_probability"].to_numpy()
                  - frame_4["match_probability"].to_numpy()).max() <= 1e-6
