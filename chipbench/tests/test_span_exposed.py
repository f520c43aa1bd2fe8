"""The reader ``span_exposed`` on hand-made span tables (a head, an overlapped
middle, a tail, a suspended stretch, a pool thread's span; the traced job left
out of the mean; 0.0 and not nothing where nothing is exposed), against the
program's own ``profiling.exposure`` on a real tiny job, and a rehearsal run
of the one-frame and the streamed cell: the traced line carries every
``program_span`` metric the reader gives."""

import pytest

from chipbench.readers import span_exposed
from chipbench.tests.test_harness import TINY, manifest, result_of, run_cell
from chipbench.tests.test_stream_cell import CELL as STREAM_CELL
from chipbench.tests.test_stream_cell import TINY as STREAM_TINY

DRIVER, POOL = 1, 2
NAMES = ("host_exposed_s", "device_inflight_s", "exposed_prepare_s", "exposed_output_s",
         "exposed_tf_s", "inflight_over_busy")


def span(id, name, t0, t1, parent=None, kind="span", thread=DRIVER, suspended=None):
    s = {"id": id, "name": name, "kind": kind, "t0": t0, "t1": t1, "parent": parent,
         "thread": thread, "counts": {}}
    if suspended:
        s["suspended"] = suspended
    return s


def device(id, name, t0, t1, parent):
    return span(id, name, t0, t1, parent, kind="device")


# a job of 20 s: a head of 3 s (pack_table 1..3), a program in flight 3..9 with
# the driver's wait inside, frame assembly 8..12 (one second hidden behind the
# program, three exposed), the consumer's 12..15, a second program 15..17 with
# a pool thread's download beside it, and a tail 17..20 (decode_pairs 18..19.5)
JOB_HOST = [
    span(0, "stream_scored_comparisons", 0.0, 20.0, kind="call", suspended=[(12.0, 15.0)]),
    span(1, "pack_table", 1.0, 3.0, 0),
    span(2, "d2h_wait", 4.0, 7.0, 0),
    span(3, "assemble_frame", 8.0, 12.0, 0),
    span(4, "lut_gather", 10.0, 11.0, 3),
    span(5, "mesh_gather", 15.5, 16.5, None, thread=POOL),
    span(6, "decode_pairs", 18.0, 19.5, 0),
]
JOB_DEVICE = [device(7, "fn", 3.0, 9.0, 0), device(8, "fn", 15.0, 17.0, 0),
              device(9, "fn", 13.0, 14.0, 0)]      # dispatched ahead: the consumer's time
# 3 (head) + 3 (frame) + 3 (tail); in flight 6 + 2; suspended 3; wall 20
EXPOSED, INFLIGHT = 9.0, 8.0
# a job whose programs cover all of its driver's time
COVERED_HOST = [span(0, "scored_comparisons", 100.0, 104.0, kind="call"),
                span(1, "assemble_frame", 101.0, 103.0, 0)]
COVERED_DEVICE = [device(2, "fn", 99.0, 105.0, 0)]
# the traced job: twice as slow, to be left out of the means
TRACED_HOST = [span(0, "scored_comparisons", 50.0, 90.0, kind="call"),
               span(1, "assemble_frame", 70.0, 90.0, 0)]
TRACED_DEVICE = [device(2, "fn", 52.0, 62.0, 0)]


@pytest.fixture
def program(monkeypatch):
    from splink_tpu.utils import profiling

    host = {"warm": [span(0, "init", 0.0, 50.0, kind="call")], "t": TRACED_HOST,
            "a": JOB_HOST, "b": COVERED_HOST}
    dev = {"warm": [], "t": TRACED_DEVICE, "a": JOB_DEVICE, "b": COVERED_DEVICE}
    monkeypatch.setattr(profiling, "runs", lambda: list(host), raising=False)
    monkeypatch.setattr(profiling, "spans", lambda run=None: host[run], raising=False)
    monkeypatch.setattr(profiling, "device_spans", lambda run=None: dev[run], raising=False)
    return {"jobs": [{"traced": True}, {"traced": False}, {"traced": False}], "failed": 0,
            "trace": {"devices": 1, "busy_s": 8.0}, "scopes": host}


def test_lanes_of_the_hand_made_job_and_the_identity():
    exposed, inflight, running = span_exposed.lanes(JOB_HOST, JOB_DEVICE)
    assert span_exposed._seconds(exposed) == pytest.approx(EXPOSED)
    assert span_exposed._seconds(inflight) == pytest.approx(INFLIGHT)
    assert span_exposed._seconds(running) == pytest.approx(17.0)
    # exposed + in flight + suspended = the call span's wall
    assert EXPOSED + INFLIGHT + 3.0 == pytest.approx(20.0)
    assert exposed == [(0.0, 3.0), (9.0, 12.0), (17.0, 20.0)]


@pytest.mark.parametrize("what, names, expect", [
    ("exposed", None, (EXPOSED + 0.0) / 2),
    ("inflight", None, (INFLIGHT + 4.0) / 2),
    # pack_table lies in the head: all of it exposed
    ("exposed", ["pack_table"], 2.0 / 2),
    # the frame's self time 8..10 and 11..12: 9..10 and 11..12 exposed; its child 10..11
    ("exposed", ["assemble_frame"], 2.0 / 2),
    ("exposed", ["assemble_frame", "lut_gather"], 3.0 / 2),
    ("exposed", ["decode_pairs"], 1.5 / 2),
    # the wait lies under the program; the pool thread's span is not the driver's
    ("exposed", ["d2h_wait"], 0.0),
    ("exposed", ["mesh_gather"], 0.0),
    ("exposed", ["no_such_span"], 0.0),
    # the traced job alone: 10 s in flight over the trace's 8 s busy
    ("inflight_over_busy", None, 10.0 / 8.0),
])
def test_span_exposed_on_hand_made_tables(program, what, names, expect):
    got = span_exposed.read(program, what, names)
    assert got is not None and got == pytest.approx(expect)


def test_a_window_of_the_traced_job_alone_reads_that_job(program):
    program["jobs"] = [{"traced": True}]
    del program["scopes"]["a"], program["scopes"]["b"]  # the window held one job
    assert span_exposed.read(program, "inflight") == pytest.approx(10.0)
    assert span_exposed.read(program, "exposed") == pytest.approx(30.0)


def test_span_exposed_has_nothing_to_read(program, monkeypatch):
    from splink_tpu.utils import profiling

    assert span_exposed.read({"jobs": [{}, {}], "failed": 1}, "exposed") is None
    assert span_exposed.read({"jobs": [], "failed": 0}, "exposed") is None
    assert span_exposed.read({"jobs": [{}] * 5, "failed": 0}, "exposed") is None
    with pytest.raises(ValueError):
        span_exposed.read(program, "hidden")
    # no trace, or a trace without a device plane: the ratio has no denominator
    assert span_exposed.read(dict(program, trace=None), "inflight_over_busy") is None
    assert span_exposed.read(dict(program, trace={"devices": 0, "busy_s": 0.0}),
                             "inflight_over_busy") is None
    # a scope that closed no root call span is no job's
    assert span_exposed.read({"jobs": [{}] * 4, "failed": 0}, "exposed") is not None
    monkeypatch.setattr(profiling, "spans", lambda run=None: [], raising=False)
    assert span_exposed.read(program, "exposed") is None
    # a program from before the device records (the parent commit)
    monkeypatch.delattr(profiling, "device_spans")
    assert span_exposed.read(program, "exposed") is None


def test_the_reader_agrees_with_the_programs_own_exposure():
    """One real tiny job: the reader's arithmetic and ``profiling.exposure``
    are written apart and have to give the same seconds."""
    import numpy as np
    import pandas as pd

    from splink_tpu import Splink
    from splink_tpu.utils import profiling

    rng = np.random.default_rng(3)
    n = 1500
    df = pd.DataFrame({"unique_id": np.arange(n),
                       "first_name": rng.choice(["ann", "bob", "cat", "dan"], n),
                       "surname": rng.choice(["smith", "jones", "brown"], n),
                       "city": rng.choice(["x", "y", "z"], n)})
    settings = {"link_type": "dedupe_only", "blocking_rules": ["l.city = r.city"],
                "comparison_columns": [{"col_name": "first_name"}, {"col_name": "surname"}],
                "max_iterations": 2, "device_pair_generation": "on",
                "max_resident_pairs": 1024, "pair_batch_size": 1 << 16}
    linker = Splink(settings, df=df)
    chunks = sum(len(c) for c in linker.stream_scored_comparisons())
    assert chunks > 0
    own = profiling.exposure(linker.run_id)
    host, dev = profiling.spans(linker.run_id), profiling.device_spans(linker.run_id)
    assert dev and {d["name"] for d in dev} >= {"fn", "run_em", "score_pairs"}
    exposed, inflight, running = span_exposed.lanes(host, dev)
    assert span_exposed._seconds(exposed) == pytest.approx(own["exposed_s"], abs=1e-9)
    assert span_exposed._seconds(inflight) == pytest.approx(own["inflight_s"], abs=1e-9)
    assert own["exposed_s"] + own["inflight_s"] + own["suspended_s"] == pytest.approx(
        own["wall_s"], rel=1e-6)
    for name in ("pack_table", "assemble_frame", "d2h_wait"):
        narrowed = span_exposed.lanes(host, dev, {name})[0]
        assert span_exposed._seconds(narrowed) == pytest.approx(
            own["spans"][name]["exposed_s"], abs=1e-9), name


def test_the_manifest_adds_six_metrics_of_one_reader_at_its_end():
    per_layer = manifest()["per_layer"]
    assert tuple(m["name"] for m in per_layer[-6:]) == NAMES
    for m in per_layer[-6:]:
        assert m["moves"] == "pairs_per_s" and m["better"] == "lower"
        assert m.get("workloads") == (["c3_link_tf"] if m["name"] == "exposed_tf_s" else None)
        assert m["source"] == ("device_trace" if m["name"] == "inflight_over_busy"
                               else "program_span")


@pytest.mark.parametrize("cell, overrides", [("c4_dedupe_virtual", TINY["c4_dedupe_virtual"]),
                                             (STREAM_CELL, STREAM_TINY),
                                             ("c3_link_tf", TINY["c3_link_tf"])])
def test_rehearsal_line_carries_the_exposure_metrics(cell, overrides):
    res = result_of(run_cell(cell, trace=1, overrides=overrides))
    assert res["correct"] is True, res["checks"]
    got = res["metrics"]
    for name in NAMES[:4]:
        assert got[name]["unit"] == "s" and got[name]["value"] >= 0, name
    assert got["host_exposed_s"]["value"] > 0 and got["device_inflight_s"]["value"] > 0
    assert got["exposed_prepare_s"]["value"] + got["exposed_output_s"]["value"] <= (
        got["host_exposed_s"]["value"] + 1e-9)
    # what is exposed and what is in flight are parts of a job's wall
    walls = res["window"]["job_wall_s"]
    assert got["host_exposed_s"]["value"] + got["device_inflight_s"]["value"] <= max(walls)
    assert ("exposed_tf_s" in got) == (cell == "c3_link_tf")
    assert "inflight_over_busy" not in got  # no device plane on the CPU backend
