"""The run's one span table (utils/profiling.py): parent linkage and self
time, thread isolation, the ``stage_timings()`` guarantee, the spans and
counts each job closes, build spans from jax.monitoring, the JSONL form of
the table, the profiler's view of it, and the names of the jitted programs.
"""

import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.utils import profiling
from splink_tpu.utils.profiling import (
    StageTimer,
    add_closed,
    begin_run,
    count,
    discard_run,
    runs,
    span,
    spans,
    stage_timings,
)

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _self_seconds(table: list[dict]) -> dict[int, float]:
    """Self time by span id: duration minus the union of the children's
    intervals, clipped to the span."""
    by_parent: dict[int, list[dict]] = {}
    for s in table:
        by_parent.setdefault(s["parent"], []).append(s)
    out = {}
    for s in table:
        covered, end = 0.0, s["t0"]
        for c in sorted(by_parent.get(s["id"], ()), key=lambda c: c["t0"]):
            lo, hi = max(c["t0"], end), min(c["t1"], s["t1"])
            if hi > lo:
                covered += hi - lo
                end = hi
        out[s["id"]] = (s["t1"] - s["t0"]) - covered
    return out


@pytest.fixture
def scope():
    """A fresh run scope, dropped afterwards."""
    run = begin_run(f"test-{time.perf_counter_ns()}")
    yield run
    discard_run(run)


def _people(n: int, seed: int) -> pd.DataFrame:
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "first_name": rng.choice(["ann", "bob", "cat", "dan", "eve"], n),
            "surname": rng.choice(["smith", "jones", "taylor", "brown"], n),
            "city": rng.choice(["x", "y", "z"], n),
        }
    )


def _dedupe_job():
    """A tiny dedupe on the virtual pair index (the c4 cell's path)."""
    settings = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name"},
            {"col_name": "surname"},
        ],
        "blocking_rules": ["l.city = r.city", "l.surname = r.surname"],
        "max_iterations": 3,
        "device_pair_generation": "on",
        "max_resident_pairs": 1024,
        "pair_batch_size": 1 << 16,
    }
    linker = Splink(settings, df=_people(900, 1))
    linker.get_scored_comparisons()
    return linker


def _link_tf_job():
    """A tiny two-frame link plus the TF pass (the c3 cell's path)."""
    settings = {
        "link_type": "link_only",
        "comparison_columns": [
            {"col_name": "first_name", "term_frequency_adjustments": True},
            {"col_name": "surname"},
        ],
        "blocking_rules": ["l.city = r.city"],
        "max_iterations": 3,
    }
    df = _people(900, 2)
    linker = Splink(settings, df_l=df.iloc[:600], df_r=df.iloc[600:])
    linker.make_term_frequency_adjustments(linker.get_scored_comparisons())
    return linker


_JOBS = {"dedupe": _dedupe_job, "link_tf": _link_tf_job}
# the stage names each job's stage_timings() has always had
_STAGES = {
    "dedupe": {"encode", "pairgen_plan", "gammas_patterns", "em",
               "score_patterns"},
    "link_tf": {"encode", "blocking", "gammas", "em", "score"},
}
# the span names of ISSUE 27 §2 that each job has to close, and the four
# that the first chip trace asked for (what was left in the roots' self time)
_SPANS = {
    "dedupe": {"init", "scored_comparisons", "assemble_frame", "lut_gather",
               "concat_frame", "d2h_wait", "h2d_put", "pack_table",
               "gamma_histogram", "decode_pairs"},
    "link_tf": {"init", "scored_comparisons", "tf", "assemble_frame",
                "d2h_wait", "h2d_put", "tf_align_check", "tf_token_ids",
                "tf_device", "tf_frame", "pair_bound", "pack_table",
                "gamma_histogram"},
}


@pytest.fixture(scope="module", params=sorted(_JOBS))
def job(request):
    linker = _JOBS[request.param]()
    return request.param, linker, spans(run=linker.run_id)


# ---------------------------------------------------------------------------
# the table itself
# ---------------------------------------------------------------------------


def test_parent_linkage_and_self_time(scope):
    with StageTimer("outer") as outer:
        time.sleep(0.02)
        with span("a", rows=3) as a:
            time.sleep(0.03)
            with span("leaf"):
                time.sleep(0.01)
        with span("b"):
            time.sleep(0.02)
    table = spans()
    by_name = {s["name"]: s for s in table}
    assert [s["name"] for s in table] == ["outer", "a", "leaf", "b"]
    assert [s["id"] for s in table] == [0, 1, 2, 3]
    assert by_name["outer"]["parent"] is None
    assert by_name["a"]["parent"] == by_name["b"]["parent"] == 0
    assert by_name["leaf"]["parent"] == by_name["a"]["id"]
    assert by_name["outer"]["kind"] == "stage" and by_name["a"]["kind"] == "span"
    assert by_name["a"]["counts"] == {"rows": 3}
    own = _self_seconds(table)
    # outer slept 0.02 s itself; a 0.03 s; the rest is its children's
    assert 0.015 < own[0] < 0.04
    assert 0.025 < own[1] < 0.05
    assert outer.elapsed == pytest.approx(
        by_name["outer"]["t1"] - by_name["outer"]["t0"]
    )
    assert outer.elapsed > a.elapsed > 0.04
    # only the stage is a stage timing
    assert list(stage_timings()) == ["outer"]


def test_span_closes_and_records_when_body_raises(scope):
    with pytest.raises(RuntimeError, match="boom"):
        with StageTimer("failing"):
            with span("inner"):
                raise RuntimeError("boom")
    table = spans()
    assert [s["name"] for s in table] == ["failing", "inner"]
    assert all(s["t1"] >= s["t0"] for s in table)
    assert profiling.current_span_id() is None  # the stack unwound
    with span("after"):
        pass
    assert spans()[-1]["parent"] is None


def test_pool_thread_spans_leave_the_driver_stack_alone(scope):
    """Spans opened on D2H-pool-like worker threads nest under nothing of
    the driver's and never disturb its stack, also under contention."""
    barrier = threading.Barrier(8, timeout=10)

    def worker(k):
        barrier.wait()
        for _ in range(50):
            with span("pool_span", k=k):
                with span("pool_leaf"):
                    pass
        return threading.get_ident()

    with StageTimer("driver") as driver:
        with ThreadPoolExecutor(max_workers=8) as pool:
            idents = [f.result(timeout=30) for f in
                      [pool.submit(worker, k) for k in range(8)]]
        assert profiling.current_span_id() == driver.span["id"]
        with span("driver_child"):
            pass
    table = spans()
    assert [s["id"] for s in table] == list(range(len(table)))  # no id lost
    assert len(table) == 2 + 8 * 50 * 2
    mine = threading.get_ident()
    assert mine not in idents
    by_id = {s["id"]: s for s in table}
    for s in table:
        if s["name"] == "pool_span":
            assert s["parent"] is None and s["thread"] != mine
        elif s["name"] == "pool_leaf":
            assert by_id[s["parent"]]["name"] == "pool_span"
            assert by_id[s["parent"]]["thread"] == s["thread"]
        elif s["name"] == "driver_child":
            assert s["parent"] == driver.span["id"] and s["thread"] == mine


def test_generator_stage_closed_out_of_order_keeps_the_stack(scope):
    """A stage wrapped round a generator stays open while the consumer
    works; closing the generator late must not pop someone else's span."""

    def chunks():
        with StageTimer("stream"):
            yield 1
            yield 2

    gen = chunks()
    next(gen)
    with StageTimer("consumer") as consumer:
        gen.close()  # "stream" closes while "consumer" is innermost
        assert profiling.current_span_id() == consumer.span["id"]
    assert profiling.current_span_id() is None
    assert set(stage_timings()) == {"stream", "consumer"}


def test_count_targets_the_innermost_stage(scope):
    count(batches=1)  # nothing open: nothing to count into
    with StageTimer("stage") as st:
        with span("sub"):
            count(batches=1)
            count(batches=1, pairs=7)
    assert st.counts == {"batches": 2, "pairs": 7}
    by_name = {s["name"]: s for s in spans()}
    assert by_name["stage"]["counts"] == {"batches": 2, "pairs": 7}
    assert by_name["sub"]["counts"] == {}


def test_count_here_targets_the_innermost_span_of_any_kind(scope):
    profiling.count_here(units=1)  # nothing open: nothing to count into
    with StageTimer("stage") as st:
        profiling.count_here(units=2)
        with span("sub") as sub:
            profiling.count_here(units=3)
            count(units=4)  # the stage's, as ever
        profiling.count_here(units=5)
    assert sub.counts == {"units": 3} and st.counts == {"units": 11}


def test_add_closed_lands_under_the_open_span_or_nowhere(scope):
    add_closed("jax_lower", "build", 0.5, fun="f")  # nothing open: dropped
    assert spans() == []
    with StageTimer("stage") as st:
        add_closed("jax_lower", "build", 0.25, fun="f")
    build = spans()[-1]
    assert build["kind"] == "build" and build["parent"] == st.span["id"]
    assert build["t1"] - build["t0"] == pytest.approx(0.25)
    assert build["counts"] == {"fun": "f"}
    assert list(stage_timings()) == ["stage"]  # a build span is no stage


def test_runs_lists_scopes_in_begin_order_and_spans_are_copies():
    a, b = begin_run("order-a"), begin_run("order-b")
    try:
        assert runs()[-2:] == [a, b]
        begin_run(a)  # re-begun: moves to the end, table emptied
        assert runs()[-2:] == [b, a]
        with StageTimer("x", run=b):
            pass
        got = spans(run=b)
        got[0]["counts"]["tampered"] = 1
        got[0]["name"] = "y"
        assert spans(run=b)[0]["name"] == "x"
        assert spans(run=b)[0]["counts"] == {}
        assert spans(run=a) == [] and spans(run="never-begun") == []
    finally:
        discard_run(a)
        discard_run(b)
    assert a not in runs() and b not in runs()


def test_retained_runs_are_bounded():
    ids = [begin_run(f"bound-{k}") for k in range(profiling._MAX_RETAINED_RUNS + 5)]
    try:
        kept = runs()
        assert len(kept) <= profiling._MAX_RETAINED_RUNS
        assert ids[0] not in kept and ids[-1] in kept
    finally:
        for run in ids:
            discard_run(run)


def test_one_span_stack_and_no_profiler_session_code():
    """ISSUE 27 acceptance: the second stack and the profile_dir hook are
    gone, everywhere."""
    from splink_tpu.obs.tracer import Tracer

    for gone in ("begin", "end", "_stack", "span", "current_id"):
        assert not hasattr(Tracer(), gone), gone
    for gone in ("_TRACE_DIRS", "_TRACED_STAGES", "_TRACE_ACTIVE",
                 "set_trace_dir", "_TIMINGS"):
        assert not hasattr(profiling, gone), gone
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hits = []
    for top in ("splink_tpu", "docs"):
        for folder, _dirs, files in os.walk(os.path.join(root, top)):
            for name in files:
                if name.endswith((".py", ".md", ".json")):
                    path = os.path.join(folder, name)
                    with open(path, encoding="utf-8") as f:
                        if "profile_dir" in f.read():
                            hits.append(path)
    assert hits == []
    with open(os.path.join(root, "splink_tpu", "utils", "profiling.py")) as f:
        source = f.read()
    code = source.split('"""', 2)[2]  # past the module docstring
    for session in ("start_trace", "stop_trace", "profiler.trace("):
        assert session not in code, session


# ---------------------------------------------------------------------------
# the two jobs
# ---------------------------------------------------------------------------


def test_stage_timings_hold_only_the_stage_names(job):
    name, linker, table = job
    timings = stage_timings(run=linker.run_id)
    assert set(timings) == _STAGES[name]
    stages = sorted(
        (s for s in table if s["kind"] == "stage"), key=lambda s: s["t1"]
    )
    expect: dict[str, list[float]] = {}
    for s in stages:
        expect.setdefault(s["name"], []).append(s["t1"] - s["t0"])
    assert timings == expect
    assert list(timings) == list(expect)  # same order: as the stages closed


def test_every_named_span_appears_in_its_job(job):
    name, _linker, table = job
    seen = {s["name"] for s in table}
    assert _SPANS[name] <= seen, _SPANS[name] - seen
    kinds = {s["name"]: s["kind"] for s in table}
    for root in ("init", "scored_comparisons"):
        assert kinds[root] == "call"
    for sub in _SPANS[name] - {"init", "scored_comparisons", "tf"}:
        assert kinds[sub] == "span", sub
    # the granularity rule: a job closes on the order of 10^2 spans
    assert len(table) < 400, len(table)


def test_each_public_call_is_one_root_whose_self_time_is_small(job):
    name, _linker, table = job
    roots = [s for s in table if s["parent"] is None]
    calls = ["init", "scored_comparisons"] + (["tf"] if name == "link_tf" else [])
    assert [s["name"] for s in roots] == calls
    assert all(s["kind"] == "call" for s in roots)
    own = _self_seconds(table)
    for s in roots[1:]:  # init has no children: all of it is self time
        dur = s["t1"] - s["t0"]
        assert own[s["id"]] < 0.10 * dur, (s["name"], own[s["id"]], dur)
    # and everything else hangs below one of them
    by_id = {s["id"]: s for s in table}
    for s in table:
        top = s
        while top["parent"] is not None:
            top = by_id[top["parent"]]
        assert top["kind"] == "call"


def test_counts_at_the_stage_boundaries(job):
    name, linker, table = job
    by_name: dict[str, list[dict]] = {}
    for s in table:
        by_name.setdefault(s["name"], []).append(s)
    [scored] = by_name["scored_comparisons"]
    pairs = scored["counts"]["pairs"]
    assert pairs > 0
    assert by_name["init"][0]["counts"] == {"rows": 900}
    assert by_name["encode"][0]["counts"] == {"rows": 900}
    [em] = by_name["em"]
    assert em["counts"]["pairs"] == pairs
    assert 1 <= em["counts"]["iterations"] <= 3
    assert em["counts"]["patterns"] >= 1
    if name == "dedupe":
        [gp] = by_name["gammas_patterns"]
        assert gp["counts"]["pairs"] == pairs and gp["counts"]["batches"] >= 2
        [sp] = by_name["score_patterns"]
        assert sp["counts"]["pairs"] == pairs
        assert sp["counts"]["batches"] == len(by_name["assemble_frame"])
        assert sum(s["counts"]["rows"] for s in by_name["lut_gather"]) == pairs
        [concat] = by_name["concat_frame"]
        assert concat["counts"] == {
            "chunks": sp["counts"]["batches"], "rows": pairs
        }
        assert sum(s["counts"]["rows"] for s in by_name["assemble_frame"]) == pairs
    else:
        assert by_name["blocking"][0]["counts"] == {"pairs": pairs}
        # two default (Jaro-Winkler) columns, one evaluation each; the stage
        # has no count of a pruned body, and pack_table says the row's words
        assert by_name["gammas"][0]["counts"] == {
            "pairs": pairs, "batches": 1, "string_evals": 2 * pairs,
            "levenshtein_columns": 0, "name_inversion_columns": 0,
        }
        [pack] = by_name["pack_table"]
        assert pack["counts"]["rows"] > 0 and pack["counts"]["lanes"] >= 2 * (2 + 2)
        assert by_name["score"][0]["counts"] == {"pairs": pairs, "batches": 1}
        [frame] = by_name["assemble_frame"]
        assert frame["counts"]["rows"] == pairs
        assert frame["counts"]["string_columns"] >= 2
        assert frame["counts"]["columns"] > frame["counts"]["string_columns"]
        assert by_name["tf"][0]["counts"] == {"rows": pairs}
    assert all(s["counts"]["bytes"] > 0 for s in by_name["d2h_wait"])
    assert all(s["counts"]["bytes"] > 0 for s in by_name["h2d_put"])


def test_assemble_frame_counts_its_string_columns_and_how_they_came(job):
    """Every frame of both jobs retains first_name and surname per side:
    ``string_columns`` counts the four, and ``columnar_strings`` says how
    many of them entered the frame as a typed column taken by the pair index
    — all of them where pyarrow is installed (pandas then infers an
    Arrow-backed string array for the input column), none without it."""
    import importlib.util

    _name, _linker, table = job
    frames = [s["counts"] for s in table if s["name"] == "assemble_frame"]
    assert frames
    arrow = importlib.util.find_spec("pyarrow") is not None
    for counts in frames:
        assert counts["string_columns"] == 4
        assert counts["columnar_strings"] == (4 if arrow else 0)
        assert counts["columns"] > counts["string_columns"]


def test_every_row_is_written_in_place_and_the_stage_covers_the_fill(job):
    """The one-frame path of both jobs writes every row straight into the
    columns of the frame it hands out (``in_place_rows`` == ``rows`` on
    every ``assemble_frame``, one a chunk written), and the dedupe job's
    ``score_patterns`` stage closes round ALL of the frame's work — the
    writes, the per-pattern takes and what is left of the concat — so
    ``score_output_s`` covers ``frame_assembly_s`` (ROADMAP D14)."""
    name, _linker, table = job
    by_id = {s["id"]: s for s in table}
    frames = [s for s in table if s["name"] == "assemble_frame"]
    [scored] = [s for s in table if s["name"] == "scored_comparisons"]
    assert sum(s["counts"]["in_place_rows"] for s in frames) == scored["counts"]["pairs"]
    assert all(s["counts"]["in_place_rows"] == s["counts"]["rows"] for s in frames)
    gathers = [s for s in table if s["name"] == "lut_gather"]
    [concat] = [s for s in table if s["name"] == "concat_frame"]
    assert concat["counts"] == {
        "chunks": len(frames), "rows": scored["counts"]["pairs"]
    }
    if name != "dedupe":
        assert len(frames) == 1 and not gathers  # nothing per pattern to take
        return
    assert len(frames) == len(gathers) >= 2
    assert all(by_id[s["parent"]]["name"] == "assemble_frame" for s in gathers)
    [stage] = [s for s in table if s["name"] == "score_patterns"]
    for s in (*frames, concat):
        assert s["parent"] == stage["id"], s["name"]
        assert stage["t0"] <= s["t0"] and s["t1"] <= stage["t1"]


def test_tf_frame_counts_the_columns_it_shares_and_the_columns_it_adds(job):
    """The TF pass hands every column of the scored frame on as it is
    (``shared_columns`` == the frame's column count: the counter that says
    ROADMAP A4's mechanism engaged) and allocates two: the flagged column's
    adjustment and ``tf_adjusted_match_prob``. A job without a TF call
    closes no ``tf_frame``."""
    name, _linker, table = job
    frames = [s["counts"] for s in table if s["name"] == "tf_frame"]
    if name != "link_tf":
        assert not frames
        return
    [scored] = [s["counts"] for s in table if s["name"] == "assemble_frame"]
    [tf] = [s["counts"] for s in table if s["name"] == "tf"]
    assert frames == [{
        "rows": tf["rows"], "shared_columns": scored["columns"],
        "added_columns": 2,
    }]


@pytest.mark.parametrize("pool_rows", [1, 1 << 62], ids=["above", "below"])
@pytest.mark.parametrize("make", sorted(_JOBS))
def test_assemble_frame_counts_the_rows_its_pool_of_threads_wrote(
        make, pool_rows, monkeypatch):
    """A chunk of ``_POOL_ROWS`` rows or more is filled by a pool of host
    threads: ``pooled_rows`` == ``rows`` and ``fill_threads`` the workers
    (more than one where the process may use more than one core); below it
    ``pooled_rows`` is 0 and ``fill_threads`` 1. Either way ``lut_gather``
    stays the child of its chunk's ``assemble_frame`` with the chunk's rows,
    and the TF fold's uploads stay the driver's, under ``assemble_frame``."""
    import splink_tpu.linker as linker_module

    monkeypatch.setattr(linker_module, "_POOL_ROWS", pool_rows)
    linker = _JOBS[make]()
    table = spans(run=linker.run_id)
    by_id = {s["id"]: s for s in table}
    frames = [s for s in table if s["name"] == "assemble_frame"]
    assert frames
    cores = linker_module._host_cores()
    for frame in frames:
        counts = frame["counts"]
        assert counts["rows"] > 0
        if pool_rows == 1:
            assert counts["pooled_rows"] == counts["rows"]
            assert (counts["fill_threads"] > 1) == (cores > 1)
        else:
            assert counts["pooled_rows"] == 0 and counts["fill_threads"] == 1
    gathers = [s for s in table if s["name"] == "lut_gather"]
    assert len(gathers) == (len(frames) if make == "dedupe" else 0)
    for gather in gathers:
        parent = by_id[gather["parent"]]
        assert parent["name"] == "assemble_frame"
        assert gather["counts"] == {"rows": parent["counts"]["rows"]}
    if make == "link_tf":
        [frame] = frames
        puts = [s for s in table if s["name"] == "h2d_put" and s["parent"] == frame["id"]]
        assert puts  # the fold's, opened on the driver


def test_a_failing_fill_task_raises_from_write_and_leaves_no_thread(monkeypatch):
    """The first exception a pool task raises is raised from ``write()``,
    and the pool's threads are gone when it has."""
    import itertools

    import splink_tpu.linker as linker_module

    class Planted(RuntimeError):
        pass

    linker = _dedupe_job()
    calls = itertools.count()
    put = linker_module._put

    def failing_put(out, src, by):
        if next(calls) == 2:
            raise Planted("the third task")
        put(out, src, by)

    monkeypatch.setattr(linker_module, "_POOL_ROWS", 1)
    monkeypatch.setattr(linker_module, "_put", failing_put)
    with pytest.raises(Planted, match="the third task"):
        linker.manually_apply_fellegi_sunter_weights()
    assert next(calls) > 2
    assert not [t for t in threading.enumerate() if t.name.startswith("frame_fill")]
    [frame] = [s for s in spans(run=linker.run_id) if s["name"] == "assemble_frame"][-1:]
    assert "pooled_rows" not in frame["counts"]  # the chunk was never written


def test_numeric_only_frame_counts_no_string_column():
    """Nothing retained (the config-4 cells' frame): ids, levels and
    probabilities only, so neither counter finds a string column."""
    settings = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "first_name"}, {"col_name": "surname"}],
        "blocking_rules": ["l.city = r.city"],
        "retain_matching_columns": False,
        "max_iterations": 2,
    }
    linker = Splink(settings, df=_people(300, 3))
    frame = linker.get_scored_comparisons()
    [counts] = [s["counts"] for s in spans(run=linker.run_id)
                if s["name"] == "assemble_frame"]
    assert counts["rows"] == len(frame) > 0
    assert counts["columns"] == len(frame.columns)
    assert counts["string_columns"] == 0 and counts["columnar_strings"] == 0
    assert not any(isinstance(t, pd.StringDtype) or t == object for t in frame.dtypes)


def test_decode_pairs_counts_positions_in_pairs_out_and_who_decoded(job):
    """The score stream's ``decode_pairs`` spans: every candidate position
    goes in (``rows``), the unmasked ones come out (``kept``), and the row
    pair of every one of them was decoded by the kernel and not a second
    time on the host (``device_decoded`` == ``rows``) — ROADMAP A3."""
    name, linker, table = job
    decodes = [s for s in table if s["name"] == "decode_pairs"]
    if name != "dedupe":
        assert not decodes  # materialised pairs: nothing virtual to decode
        return
    by_id = {s["id"]: s for s in table}
    assert decodes and all(
        by_id[s["parent"]]["name"] == "score_patterns" for s in decodes
    )
    assert all(set(s["counts"]) == {"rows", "kept", "device_decoded"}
               for s in decodes)
    assert sum(s["counts"]["rows"] for s in decodes) == linker._virtual.n_candidates
    assert all(s["counts"]["device_decoded"] == s["counts"]["rows"] for s in decodes)
    [scored] = [s for s in table if s["name"] == "scored_comparisons"]
    assert sum(s["counts"]["kept"] for s in decodes) == scored["counts"]["pairs"]
    assert any(s["counts"]["kept"] < s["counts"]["rows"] for s in decodes)


def _gamma_pass_builds(table: list[dict]) -> dict[str, list[dict]]:
    """The build spans directly under the job's gamma pass, by name."""
    by_id = {s["id"]: s for s in table}
    out: dict[str, list[dict]] = {}
    for s in table:
        if s["kind"] == "build" and by_id[s["parent"]]["name"] == "gammas_patterns":
            out.setdefault(s["name"], []).append(s)
    return out


def test_build_spans_under_the_first_linker_of_a_key_and_none_after():
    """The first linker of a registry key traces, lowers and compiles (or
    reads back) the pattern kernels; a second call on it, and a second
    fresh linker on the same settings and frame, get the same jitted
    programs from the registry and build nothing — ROADMAP A2."""
    linker = _dedupe_job()
    table = spans(run=linker.run_id)
    under = _gamma_pass_builds(table)
    assert {"jax_lower", "jax_backend_compile", "kernel_lookup"} <= set(under)
    assert any(s["counts"]["fun"] == "jit(fn)" for s in under["jax_lower"])
    assert all(s["counts"]["cache_hit"] in (0, 1)
               for s in under["jax_backend_compile"])
    assert all(s["t1"] - s["t0"] >= 1e-3 for s in under.get("jax_trace", ()))
    lookups = [s["counts"] for s in under["kernel_lookup"]]
    assert {c["fun"] for c in lookups} == {"virtual_pattern"}
    assert all(c == {"fun": c["fun"], "hit": 0, "shared": 1, "devices": 1} for c in lookups)
    # second pass over the same linker: the program holds its kernels
    before = len(table)
    linker._pattern_counts = None
    linker._ensure_pattern_ids()
    again = spans(run=linker.run_id)[before:]
    assert [s["name"] for s in again if s["kind"] == "stage"] == ["gammas_patterns"]
    assert [s for s in again if s["kind"] == "build"] == []
    # a second linker, same key: every lookup a hit, nothing built
    second = _gamma_pass_builds(spans(run=_dedupe_job().run_id))
    assert set(second) == {"kernel_lookup"}, set(second)
    assert [s["counts"] for s in second["kernel_lookup"]] == [
        dict(c, hit=1) for c in lookups
    ]


def test_telemetry_record_carries_the_sub_spans(tmp_path):
    settings = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "first_name"}],
        "blocking_rules": ["l.city = r.city"],
        "max_iterations": 2,
        "telemetry_dir": str(tmp_path),
    }
    linker = Splink(settings, df=_people(200, 3))
    df_e = linker.get_scored_comparisons()
    linker.close_telemetry()
    [path] = [p for p in os.listdir(tmp_path) if p.endswith(".jsonl")]
    with open(tmp_path / path) as f:
        events = [json.loads(line) for line in f]
    emitted = {e["span_id"]: e for e in events if e["type"] == "span"}
    table = {s["id"]: s for s in spans(run=linker.run_id)}
    assert set(table) <= set(emitted)  # every table span is in the record
    for sid, s in table.items():
        e = emitted[sid]
        assert (e["name"], e["kind"], e["parent_id"]) == (
            s["name"], s["kind"], s["parent"]
        )
        assert e["dur_s"] == pytest.approx(s["t1"] - s["t0"])
    subs = [e for e in emitted.values() if e["kind"] == "span"]
    assert {"assemble_frame", "d2h_wait", "h2d_put"} <= {e["name"] for e in subs}
    assert all(e["parent_id"] in emitted for e in subs)
    [frame] = [e for e in subs if e["name"] == "assemble_frame"]
    assert frame["attrs"]["rows"] == len(df_e)
    assert emitted[frame["parent_id"]]["name"] == "scored_comparisons"
    # record-only spans (run, em iterations) never collide with table ids
    assert all(sid < 0 for sid, e in emitted.items()
               if e["kind"] in ("run", "em_iteration"))
    counters = [e for e in events if e["type"] == "metrics"][-1]["counters"]
    assert counters["rows_encoded"] == 200
    assert counters["pairs_blocked"] == len(df_e)
    assert counters["pairs_gamma_scored"] == len(df_e)
    assert counters["pairs_scored_output"] == len(df_e)


def test_standalone_runcontext_span_grows_no_table(tmp_path):
    from splink_tpu.obs.events import read_events
    from splink_tpu.obs.runtime import RunContext

    ctx = RunContext.from_settings({"telemetry_dir": str(tmp_path)})
    before = {run: len(spans(run=run)) for run in runs()}
    default_before = len(spans(run=""))
    for k in range(3):
        with ctx.span("serve_batch", batch=k):
            pass
    ctx.close()
    assert {run: len(spans(run=run)) for run in runs()} == before
    assert len(spans(run="")) == default_before
    got = [e for e in read_events(ctx.sink.path) if e["type"] == "span"]
    assert [e["attrs"]["batch"] for e in got] == [0, 1, 2]
    assert len({e["span_id"] for e in got}) == 3 and all(e["span_id"] < 0 for e in got)


def test_spans_lie_in_a_profiler_trace_by_name(tmp_path, scope):
    """With a profiler session active the spans are TraceAnnotations in the
    host plane, on the trace's clock: no profile_dir, no second session."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with StageTimer("span_test_stage"):
            with span("span_test_sub"):
                jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("span_test_stage", "span_test_sub"):
                    found[ev.name] = (ev.start_ns, ev.duration_ns)
    assert set(found) == {"span_test_stage", "span_test_sub"}
    (s0, sd), (c0, cd) = found["span_test_stage"], found["span_test_sub"]
    assert s0 <= c0 and c0 + cd <= s0 + sd  # nested on the trace's clock


# ---------------------------------------------------------------------------
# device names
# ---------------------------------------------------------------------------


def test_jitted_programs_have_names_of_their_own():
    """Every jitted program on the two cells' paths names its XLA module;
    only the two gamma programs are ``fn`` (what the benchmark's
    gamma_hbm_roofline matches as ``jit_fn(``)."""
    import jax.numpy as jnp

    from splink_tpu import blocking_device, em, term_frequencies
    from splink_tpu.data import encode_table
    from splink_tpu.gammas import GammaProgram, _jit_pattern_batch, _pattern_counts_batch
    from splink_tpu.pairgen import make_virtual_pattern_fn
    from splink_tpu.settings import complete_settings_dict

    settings = complete_settings_dict({
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "first_name"}],
        "blocking_rules": ["l.city = r.city"],
    })
    program = GammaProgram(settings, encode_table(_people(50, 4), settings),
                           float_dtype=jnp.float32)
    gamma = [
        make_virtual_pattern_fn(program, 64, n_prev=0, has_uid_mask=False),
        program._gamma_batch_fn,
    ]
    assert [f.__name__ for f in gamma] == ["fn", "fn"]
    others = [
        blocking_device.make_segment_sort_fn(),
        blocking_device.make_bucket_csr_fn(),
        blocking_device.make_pair_emit_fn(64, 0, False, False),
        blocking_device.make_chunk_digest_fn(),
        blocking_device.make_chunk_digest_compact_fn(),
        term_frequencies._device_token_stats_fn(8),
        term_frequencies._device_token_gather_fn(8),
        term_frequencies.make_tf_fold_fn(((0, "first_name", 1),)),
        program._kernel("pattern_batch", (), _jit_pattern_batch),
        _pattern_counts_batch,
        em.run_em,
        em.score_pairs,
        em.score_pairs_with_intermediates,
        em.score_pairs_with_logits,
        em.score_pairs_with_intermediates_logits,
    ]
    names = [f.__name__ for f in others]
    assert "fn" not in names and "fold" not in names
    assert len(set(names)) == len(names), names
    assert {n for n in names if n.startswith("block_")} == {
        "block_segment_sort", "block_bucket_csr", "block_pair_emit",
        "block_chunk_digest", "block_chunk_digest_compact",
    }
    assert {n for n in names if n.startswith("tf_")} == {
        "tf_token_stats", "tf_token_gather", "tf_fold",
    }


# ---------------------------------------------------------------------------
# the device lane: one record a dispatched batch program, and exposure()
# ---------------------------------------------------------------------------

_PROGRAMS = {
    "dedupe": {"fn", "run_em", "score_pairs"},
    "link_tf": {"fn", "run_em", "score_pairs", "tf_fold", "tf_token_stats",
                "tf_token_gather"},
}


class _Handle:
    """Stands for a program's output: ready when told."""

    def __init__(self, ready=False):
        self.ready = ready

    def is_deleted(self):
        return False

    def is_ready(self):
        return self.ready


def _raw(run):
    return profiling._TABLES[run]


def test_every_dispatched_program_has_a_closed_record_under_a_host_span(job):
    name, linker, table = job
    records = profiling.device_spans(run=linker.run_id)
    assert {d["name"] for d in records} == _PROGRAMS[name]
    assert not [s for s in _raw(linker.run_id) if s["t1"] is None]  # none left open
    host = {s["id"]: s for s in table}
    for d in records:
        assert d["kind"] == "device" and d["t1"] >= d["t0"]
        assert d["id"] not in host and d["parent"] in host
        assert host[d["parent"]]["t0"] <= d["t0"] <= host[d["parent"]]["t1"]
        assert d["counts"].get("rows", 0) + d["counts"].get("positions", 0) > 0
    assert all(s["kind"] != "device" for s in table)
    # the virtual pass dispatches one gamma program a batch of a rule's
    # candidate positions: together every position, masked ones included
    if name == "dedupe":
        stage = {s["name"]: s for s in table}["gammas_patterns"]
        fns = [d for d in records if d["name"] == "fn"]
        assert len(fns) == stage["counts"]["batches"] >= 2
        assert sum(d["counts"]["positions"] for d in fns
                   if d["parent"] == stage["id"]) == stage["counts"]["pairs"] + sum(
            s["counts"]["rows"] - s["counts"]["kept"]
            for s in table if s["name"] == "decode_pairs")


def test_device_blocking_records_its_sort_and_emit_programs_and_waits_in_spans():
    settings = {
        "link_type": "link_only",
        "comparison_columns": [{"col_name": "first_name"}, {"col_name": "surname"}],
        "blocking_rules": ["l.city = r.city"],
        "max_iterations": 2,
        "device_blocking": "on",
    }
    df = _people(900, 5)
    linker = Splink(settings, df_l=df.iloc[:600], df_r=df.iloc[600:])
    frame = linker.get_scored_comparisons()
    table = spans(run=linker.run_id)
    blocking = {s["name"]: s for s in table}["blocking"]
    under = [d for d in profiling.device_spans(run=linker.run_id)
             if d["parent"] == blocking["id"]]
    assert {d["name"] for d in under} == {"block_segment_sort", "block_pair_emit"}
    assert sum(d["counts"].get("positions", 0) for d in under) >= len(frame)
    waits = [s for s in table
             if s["name"] == "d2h_wait" and s["parent"] == blocking["id"]]
    # the sort's six arrays in one wait, and one wait a pooled chunk download
    assert len(waits) == 1 + sum(d["name"] == "block_pair_emit" for d in under)
    assert sum(w["counts"]["bytes"] for w in waits) >= 2 * 4 * len(frame)


def test_a_record_stays_open_until_its_own_output_turns_ready(scope):
    import jax.numpy as jnp

    late = _Handle()
    with StageTimer("call", kind="call"):
        profiling.dispatched("late", late, rows=1)
        with span("boundary"):
            pass
        profiling.fetch(jnp.arange(8) + 1)  # a wait on ANOTHER output returns
        assert profiling.device_spans() == []
        assert [s["name"] for s in _raw(scope) if s["t1"] is None] == [
            "call", "late"]
        late.ready = True
        turned = time.perf_counter()
        assert _raw(scope)[1]["t1"] is None  # nobody has looked yet
        with span("next_boundary"):
            pass
    (rec,) = profiling.device_spans()
    assert rec["name"] == "late" and rec["counts"] == {"rows": 1}
    assert rec["t1"] >= turned > rec["t0"]
    assert rec["parent"] == 0 and rec["thread"] == threading.get_ident()


def test_records_close_in_dispatch_order_when_a_later_wait_returns(scope):
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: x * 2 + 1)
    with StageTimer("call", kind="call"):
        x = jnp.ones(1 << 12)
        outs = []
        for k in range(3):
            x = step(x)
            profiling.dispatched("step", x, rows=k)
            outs.append(x)
        np.testing.assert_array_equal(profiling.fetch(outs[-1]), np.full(1 << 12, 15.0))
        # the wait on the LAST output has returned: every record up to it is closed
        closed = profiling.device_spans()
        assert [d["counts"]["rows"] for d in closed] == [0, 1, 2]
        t1s = [d["t1"] for d in closed]
        assert t1s == sorted(t1s) and all(d["t1"] >= d["t0"] for d in closed)
        # a record whose output is not ready holds those dispatched after it
        head, behind = _Handle(), _Handle(ready=True)
        profiling.dispatched("head", head)
        profiling.dispatched("behind", behind)
        with span("boundary"):
            pass
        assert len(profiling.device_spans()) == 3
        head.ready = True
    names = [d["name"] for d in profiling.device_spans()]
    assert names == ["step"] * 3 + ["head", "behind"]
    by = {d["name"]: d for d in profiling.device_spans()}
    assert by["head"]["t1"] <= by["behind"]["t1"]


def test_a_host_value_or_a_deleted_buffer_closes_at_once(scope):
    import jax.numpy as jnp

    gone = jnp.arange(4) * 2
    gone.delete()
    with StageTimer("call", kind="call"):
        profiling.dispatched("host_value", np.arange(3))
        profiling.dispatched("deleted", gone)
    assert [d["name"] for d in profiling.device_spans()] == ["host_value", "deleted"]


def test_device_records_change_no_host_reading(scope):
    """The same work with and without a device record under it: ids stay
    the table's indices, ``spans()`` and ``stage_timings()`` see host spans
    alone, and a span's self time is not shrunk by the record it dispatched."""
    with StageTimer("stage") as st:
        time.sleep(0.01)
        profiling.dispatched("program", _Handle(ready=False), rows=5)
        with span("child"):
            time.sleep(0.01)
        time.sleep(0.03)
        profiling._INFLIGHT[-1][1].ready = True
    table = spans()
    assert [(s["id"], s["name"]) for s in table] == [(0, "stage"), (2, "child")]
    (rec,) = profiling.device_spans()
    assert rec["id"] == 1 and rec["parent"] == 0 and rec["t1"] - rec["t0"] > 0.035
    own = _self_seconds(table)
    child = table[1]["t1"] - table[1]["t0"]
    assert own[0] == pytest.approx(st.elapsed - child)  # the record covers nothing
    assert stage_timings() == {"stage": [st.elapsed]}
    assert profiling.builds_under(_raw(scope), st.span) == []


def test_exposure_identity_and_lanes_on_both_jobs(job):
    name, linker, table = job
    e = profiling.exposure(run=linker.run_id)
    assert e["suspended_s"] == 0.0
    assert e["exposed_s"] + e["inflight_s"] == pytest.approx(e["wall_s"], rel=1e-9)
    roots = [s for s in table if s["kind"] == "call" and s["parent"] is None]
    assert e["wall_s"] == pytest.approx(sum(s["t1"] - s["t0"] for s in roots))
    assert 0 < e["inflight_s"] < e["wall_s"] and e["head_s"] > 0
    assert e["head_s"] + e["tail_s"] <= e["exposed_s"] + 1e-9
    assert set(e["device"]) == _PROGRAMS[name]
    for row in e["spans"].values():
        assert row["exposed_s"] + row["hidden_s"] == pytest.approx(row["self_s"], abs=1e-9)
    # build spans of one parent may overlap; everything else tiles the driver's clock
    tiled = sum(r["exposed_s"] for n, r in e["spans"].items() if not n.startswith("jax_"))
    assert tiled <= e["exposed_s"] + 1e-9
    # a wait on the device is time with something in flight
    wait = e["spans"]["d2h_wait"]
    assert wait["hidden_s"] >= 0.9 * wait["self_s"]
    assert e["unspanned_exposed_s"] == pytest.approx(
        sum(e["spans"][s["name"]]["exposed_s"] for s in roots))


def test_exposure_on_a_streamed_job_with_a_slow_consumer():
    """The consumer's seconds are neither exposed nor hidden."""
    settings = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "first_name"}, {"col_name": "surname"}],
        "blocking_rules": ["l.city = r.city", "l.surname = r.surname"],
        "max_iterations": 2,
        "device_pair_generation": "on",
        "max_resident_pairs": 1024,
        "pair_batch_size": 1 << 14,
        "virtual_materialise_ids": "off",
    }
    linker = Splink(settings, df=_people(900, 7))
    chunks = 0
    for _chunk in linker.stream_scored_comparisons():
        chunks += 1
        time.sleep(0.03)
    assert chunks >= 3
    e = profiling.exposure(run=linker.run_id)
    table = spans(run=linker.run_id)
    call = {s["name"]: s for s in table}["stream_scored_comparisons"]
    assert len(call["suspended"]) == chunks
    assert all(t1 - t0 >= 0.03 for t0, t1 in call["suspended"])
    assert e["suspended_s"] == pytest.approx(call["counts"]["suspended_s"], rel=1e-6)
    assert e["suspended_s"] >= 0.03 * chunks
    assert e["exposed_s"] + e["inflight_s"] + e["suspended_s"] == pytest.approx(
        e["wall_s"], rel=1e-9)
    # the suspended stretches lie in no span's self time, exposed or hidden
    streamed = e["spans"]["stream_scored_comparisons"]
    assert streamed["self_s"] < (call["t1"] - call["t0"]) - e["suspended_s"]
    assert sum(r["exposed_s"] + r["hidden_s"] for n, r in e["spans"].items()
               if not n.startswith("jax_")) <= e["wall_s"] - e["suspended_s"] + 1e-6
    # both passes dispatched the gamma program: histogram only, then the recompute
    assert e["device"]["fn"]["n"] >= 2 * 2


def test_a_pool_threads_span_never_counts_on_the_driver(scope):
    def download():
        with span("mesh_gather"):
            time.sleep(0.05)
        return threading.get_ident()

    with StageTimer("call", kind="call"):
        with ThreadPoolExecutor(max_workers=1) as pool:
            fut = pool.submit(download)
            with span("driver_work"):
                time.sleep(0.02)
            pool_thread = profiling.fetch_pooled(fut)
    e = profiling.exposure()
    assert set(e["spans"]) == {"call", "driver_work", "d2h_wait"}
    assert e["other_threads"]["mesh_gather"]["n"] == 1
    assert e["other_threads"]["mesh_gather"]["threads"] == [pool_thread]
    assert e["other_threads"]["mesh_gather"]["self_s"] >= 0.05
    # nothing was dispatched: all of the driver's clock is exposed, once
    assert e["inflight_s"] == 0.0 and e["exposed_s"] == pytest.approx(e["wall_s"])
    assert sum(r["self_s"] for r in e["spans"].values()) == pytest.approx(e["wall_s"])
    assert e["spans"]["d2h_wait"]["n"] == 1  # fetch_pooled waited on the driver


def test_exposure_of_a_scope_without_a_call_span_is_empty(scope):
    with StageTimer("stage"):
        profiling.dispatched("program", np.zeros(1))
    assert profiling.exposure() == {}
    assert [d["name"] for d in profiling.device_spans()] == ["program"]


def test_fetch_takes_a_tuple_in_one_wait_and_counts_its_bytes(scope):
    import jax.numpy as jnp

    a, b = profiling.fetch((jnp.arange(4, dtype=jnp.int32), jnp.zeros(3, jnp.float32)))
    assert a.tolist() == [0, 1, 2, 3] and b.shape == (3,)
    (wait,) = spans()
    assert wait["name"] == "d2h_wait" and wait["counts"] == {"bytes": 16 + 12}


# where the offline driver may wait for the device outside a ``d2h_wait``
# span, and why: (file under splink_tpu/, enclosing function)
_WAITS_ELSEWHERE = {
    ("utils/profiling.py", "_result"): "the via of fetch_pooled: inside its d2h_wait",
    ("parallel/mesh.py", "gather_from_mesh"): "the via of fetch, or a pool thread's",
    ("parallel/mesh.py", "put_on_mesh"): "an UPLOAD's wait, under its mesh_put span",
}


def test_no_offline_wait_on_the_device_lies_outside_a_d2h_wait_span():
    """``block_until_ready(`` and ``<future>.result()`` in the offline tree
    (not serve/, obs/, approx/; analysis/ holds the audits, which time
    kernels and drive no job) lie lexically under ``span("d2h_wait")``, or
    are one of the three named above."""
    import ast

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "splink_tpu")
    found, stray = set(), []
    for folder, dirs, files in os.walk(root):
        if folder == root:
            dirs[:] = [d for d in dirs
                       if d not in ("serve", "obs", "approx", "analysis")]
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(folder, fname)
            rel = os.path.relpath(path, root)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())

            def visit(node, func, waiting):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    func = node.name
                if isinstance(node, ast.With):
                    for item in node.items:
                        c = item.context_expr
                        if (isinstance(c, ast.Call) and c.args
                                and isinstance(c.args[0], ast.Constant)
                                and c.args[0].value == "d2h_wait"):
                            waiting = True
                if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                    attr = node.func.attr
                    if attr == "block_until_ready" or (
                            attr == "result" and not node.args and not node.keywords):
                        if (rel, func) in _WAITS_ELSEWHERE:
                            found.add((rel, func))
                        elif not waiting:
                            stray.append(f"{rel}:{node.lineno} in {func}")
                for child in ast.iter_child_nodes(node):
                    visit(child, func, waiting)

            visit(tree, None, False)
    assert stray == []
    assert found == set(_WAITS_ELSEWHERE)  # the list holds no stale entry
