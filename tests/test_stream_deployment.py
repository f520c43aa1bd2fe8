"""BASELINE configs[4], the streamed two-pass dedupe, against the plain reference.

The deployment ``chipbench/configs/baseline_c5.json`` (the cell
``c5_dedupe_stream``: the candidates' ids do not fit host memory, so EM runs
on a histogram-only pass, the scores come from a second device pass and the
output leaves as chunks) at twelve thousand rows on the CPU: the streamed job
has to give the pair set, every gamma level, λ/m/u and every score of
``chipbench.reference`` within the limits the configuration's file states,
through ``chipbench.correct_stream``, at batches that give every rule several
batches — on the people as they are, and with a prefix shared by every value
of one and of all three Jaro-Winkler columns (the data a prefilter once could
not prune: every pair close to the thresholds). Every batch runs once in each
pass. Held beside it: the chunks put end to end are the one-frame job's
frame; the spans and counts the deployment adds (the histogram-only pass
against its ids-keeping twin: ``tests/test_virtual_pairs.py``).
"""

import copy
import json
import os
import sys
import time

import numpy as np
import pandas as pd
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import correct_stream, datagen, reference  # noqa: E402
from chipbench.runners import job_stream  # noqa: E402
from splink_tpu import Splink  # noqa: E402
from splink_tpu.utils.profiling import StageTimer, spans, stage_timings  # noqa: E402

ROWS = 12000
# a shared prefix puts every unequal pair of a Jaro-Winkler column close to
# the thresholds: the same data and batch sizes that once overflowed a
# prefilter's capacity (no batch, the dob rule's batches, every batch)
CASES = {
    # id: (columns that get the shared prefix, settings changed)
    "people_as_they_are": ((), {"pair_batch_size": 1024}),
    "surname_prefixed": (("surname",), {"pair_batch_size": 2048}),
    "every_name_prefixed": (("first_name", "surname", "postcode"), {"pair_batch_size": 2048}),
}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "baseline_c5.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def jobs(config):
    """Per case, once: its people, its settings, the reference's half of the
    comparison, and the streamed job (linker and chunks)."""
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "population_seed", "rows")}
    people = datagen.make_people(rows=ROWS, seed=config["generator"]["population_seed"], **gen)
    memo = {}

    def made(case):
        if case not in memo:
            prefixed, over = CASES[case]
            df = people.assign(**{c: "zzzz" + people[c] for c in prefixed})
            settings = {**copy.deepcopy(config["settings"]), "max_resident_pairs": 1024, **over}
            linker = Splink(copy.deepcopy(settings), df=df)
            chunks = list(linker.stream_scored_comparisons())
            memo[case] = {"df": df, "settings": settings, "linker": linker, "chunks": chunks,
                          "prep": reference.prepare(settings, {"df": df})}
        return memo[case]

    return made


def stage_counts(linker, name):
    [stage] = [s for s in spans(run=linker.run_id) if s["name"] == name]
    return stage["counts"]


def numbers(job):
    chunks, linker = job["chunks"], job["linker"]
    p = np.concatenate([c["match_probability"].to_numpy() for c in chunks])
    digest = (len(p), float(p.sum(dtype=np.float64)), float(linker.params.params["λ"]))
    return correct_stream.compare(
        {**job_stream.joined(chunks, job["settings"]), "tf_frame": None,
         "params": linker.params.params, "digests": [digest], "uid": "unique_id"},
        job["prep"])


@pytest.mark.parametrize("case", sorted(CASES))
def test_streamed_job_equals_the_reference(config, jobs, case):
    job = jobs(case)
    got = numbers(job)
    ok, rows = correct_stream.verdict(got, config["limits"])
    assert ok, rows
    for exact in ("pairs_wrong", "gamma_wrong", "chunks_oversize", "chunk_schema_differs",
                  "chunks_empty"):
        assert got[exact] == 0, rows
    assert got["pairs_produced"] == len(job["prep"]["uid_l"]) > 10 * 1024


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_batch_runs_once_in_both_passes(jobs, case):
    job = jobs(case)
    linker, plan = job["linker"], job["linker"]._virtual
    batch = job["settings"]["pair_batch_size"]
    per_rule = [-(-rp.total // batch) for rp in plan.rules]
    assert min(per_rule) >= 2  # every rule runs several batches
    for name in ("gammas_patterns", "score_patterns"):
        counts = stage_counts(linker, name)
        assert counts["batches"] == sum(per_rule)  # no batch of a second pass
        assert counts["ids_kept"] == 0
        # present and 0 on both virtual stages (chipbench's
        # stream_redo_positions reads it); nothing counts an overflow
        assert counts["redo_positions"] == 0
        assert not [k for k in counts if k.startswith("overflow") or k == "two_phase"]
    assert stage_counts(linker, "score_patterns")["recomputed_positions"] == plan.n_candidates
    assert stage_counts(linker, "gammas_patterns")["hist_flushes"] == 1
    # the histogram-only pass waited once, for the accumulator, and the
    # second pass once a batch, for ids and row pairs (10 B a position)
    table = spans(run=linker.run_id)
    stage = {s["name"]: s["id"] for s in table if s["kind"] == "stage"}
    waits = {n: [s["counts"]["bytes"] for s in table
                 if s["name"] == "d2h_wait" and s["parent"] == stage[n]]
             for n in ("gammas_patterns", "score_patterns")}
    program = linker._ensure_pattern_program()
    acc_bytes = 4 * (program.n_patterns + 1)
    assert waits["gammas_patterns"] == [acc_bytes]
    assert sorted(waits["score_patterns"]) == [acc_bytes] + [10 * batch] * sum(per_rule)


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunks_end_to_end_are_the_one_frame_jobs_frame(jobs, case):
    job = jobs(case)
    kept = Splink({**copy.deepcopy(job["settings"]), "virtual_materialise_ids": "on"},
                  df=job["df"])
    whole = kept.get_scored_comparisons()
    assert stage_counts(kept, "gammas_patterns")["ids_kept"] == 1
    assert stage_counts(kept, "score_patterns")["ids_kept"] == 1
    assert stage_counts(kept, "score_patterns")["recomputed_positions"] == 0
    chunks = job["chunks"]
    assert len(chunks) >= 6 and max(map(len, chunks)) <= job["settings"]["pair_batch_size"]
    pd.testing.assert_frame_equal(pd.concat(chunks, ignore_index=True), whole)
    for chunk in chunks:
        pd.testing.assert_series_equal(chunk.dtypes, whole.dtypes)


def test_the_bfloat16_control_fails(config, jobs):
    job = jobs("people_as_they_are")
    frames = {"df": job["df"]}
    control = reference.run(job["settings"], frames, precision="bfloat16")
    got = correct_stream.compare(correct_stream.stand_in(control), job["prep"])
    ok, rows = correct_stream.verdict(got, config["limits"])
    assert not ok, rows
    failed = {n for n, v, lim in rows if lim is not None and v > lim}
    assert {"gamma_wrong", "param_gap", "score_gap"} <= failed, rows
    assert got["chunks_oversize"] == got["chunk_schema_differs"] == got["chunks_empty"] == 0
    # and the reference in the program's place passes
    same = reference.run(job["settings"], frames)
    ok, rows = correct_stream.verdict(
        correct_stream.compare(correct_stream.stand_in(same), job["prep"]), config["limits"])
    assert ok, rows


@pytest.mark.parametrize("fault,number", [
    (lambda c: c[:2] + c[3:], "pairs_wrong"),  # a chunk dropped
    (lambda c: c[:3] + c[2:], "pairs_wrong"),  # a chunk handed out twice
    (lambda c: [pd.concat(c[:2], ignore_index=True)] + c[2:], "chunks_oversize"),
    (lambda c: c[:1] + [c[1].astype({"gamma_dob": np.int32})] + c[2:], "chunk_schema_differs"),
    (lambda c: c[:4] + [c[0].iloc[:0]] + c[4:], "chunks_empty"),
], ids=["chunk_dropped", "chunk_twice", "chunk_too_long", "chunk_column_cast", "chunk_empty"])
def test_a_fault_in_the_chunks_is_caught_by_its_own_number(config, jobs, fault, number):
    job = jobs("people_as_they_are")
    assert len(job["chunks"][0]) + len(job["chunks"][1]) > 1024
    got = numbers({**job, "chunks": fault(list(job["chunks"]))})
    ok, rows = correct_stream.verdict(got, config["limits"])
    failed = {n for n, v, lim in rows if lim is not None and v > lim}
    assert not ok and number in failed, rows
    stream = {"chunks_oversize", "chunk_schema_differs", "chunks_empty"}
    assert failed & stream == ({number} & stream), rows


def test_no_whole_pass_rerun_is_left_in_the_program():
    import pathlib

    import splink_tpu

    root = pathlib.Path(splink_tpu.__file__).parent
    assert not [str(p) for p in sorted(root.rglob("*.py"))
                if "recomputing the histogram pass" in p.read_text()]


def test_call_span_counts_chunks_pairs_and_the_consumers_time(jobs):
    job = jobs("people_as_they_are")
    linker = Splink(copy.deepcopy(job["settings"]), df=job["df"])
    nap, taken, inside = 0.02, 0, []
    for chunk in linker.stream_scored_comparisons():
        with StageTimer("consumer", kind="span") as mine:  # the consumer's own span
            time.sleep(nap)
        inside.append(mine.span["parent"])
        taken += len(chunk)
    table = spans(run=linker.run_id)
    [call] = [s for s in table if s["name"] == "stream_scored_comparisons"]
    [stage] = [s for s in table if s["name"] == "score_patterns"]
    chunks = len(job["chunks"])
    assert call["kind"] == "call" and call["parent"] is None
    assert call["counts"]["chunks"] == chunks and call["counts"]["pairs"] == taken
    assert call["counts"]["suspended_s"] >= chunks * nap
    assert stage["counts"]["suspended_s"] >= call["counts"]["suspended_s"]
    # nothing the consumer opened became a child of the generator's spans
    assert inside == [None] * chunks
    # the stage's seconds are the program's: the consumer's are left out
    wall = stage["t1"] - stage["t0"]
    [seconds] = stage_timings(run=linker.run_id)["score_patterns"]
    assert seconds == pytest.approx(wall - stage["counts"]["suspended_s"])
    assert seconds < wall - chunks * nap
    # every chunk's frame work lies under the stage, the stage under the call
    assert stage["parent"] == call["id"]
    assemble = [s for s in table if s["name"] == "assemble_frame"]
    assert len(assemble) == chunks and {s["parent"] for s in assemble} == {stage["id"]}


def test_a_stream_closed_after_its_first_chunk_closes_its_call_span(jobs):
    job = jobs("people_as_they_are")
    linker = Splink({**copy.deepcopy(job["settings"]), "virtual_materialise_ids": "on"},
                    df=job["df"])
    stream = linker.stream_scored_comparisons()
    first = next(stream)
    assert linker._P_virtual is not None  # ids kept while the stream is due
    stream.close()
    assert linker._P_virtual is None
    table = spans(run=linker.run_id)  # closed spans only
    [call] = [s for s in table if s["name"] == "stream_scored_comparisons"]
    assert call["counts"]["chunks"] == 1 and call["counts"]["pairs"] == len(first)
    assert [s["name"] for s in table if s["name"] == "score_patterns"] == ["score_patterns"]
    # nothing of the generator's is left open on this thread
    with StageTimer("after", kind="span") as after:
        pass
    assert after.span["parent"] is None
