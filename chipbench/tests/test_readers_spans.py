"""The span readers on hand-made span tables and a hand-made trace summary,
and a rehearsal run of each cell: the traced result line carries the new
program-span metrics (the device ones need a device plane, which the CPU
backend has not)."""

import pytest

from chipbench.readers import span_seconds, trace_module_seconds
from chipbench.tests.test_harness import TINY, manifest, result_of, run_cell


def span(id, name, t0, t1, parent=None, kind="span"):
    return {"id": id, "name": name, "kind": kind, "t0": t0, "t1": t1,
            "parent": parent, "thread": 1, "counts": {}}


JOB_A = [
    span(0, "scored_comparisons", 0.0, 10.0, kind="call"),
    span(1, "gammas", 1.0, 5.0, 0, "stage"),
    span(2, "jax_trace", 1.0, 3.0, 1, "build"),
    span(3, "jax_trace", 1.5, 2.5, 1, "build"),      # traced inside span 2
    span(4, "jax_lower", 3.0, 3.5, 1, "build"),
    span(5, "d2h_wait", 4.0, 5.0, 1),
    span(6, "assemble_frame", 6.0, 9.0, 0),
    span(7, "d2h_wait", 6.5, 7.0, 6),                # the fold's wait, inside the frame
    span(8, "jax_backend_compile", 7.0, 7.25, 6, "build"),
]
JOB_B = [
    span(0, "scored_comparisons", 100.0, 104.0, kind="call"),
    span(1, "assemble_frame", 101.0, 103.0, 0),
    span(2, "d2h_wait", 102.5, 103.5, 1),            # runs past its parent: clipped
]


@pytest.fixture
def program(monkeypatch):
    """The program's span table, replaced by the hand-made jobs; the scope
    before them stands for the warm-up job and must not be read."""
    from splink_tpu.utils import profiling

    tables = {"warm": [span(0, "d2h_wait", 0.0, 50.0)], "a": JOB_A, "b": JOB_B}
    monkeypatch.setattr(profiling, "runs", lambda: list(tables), raising=False)
    monkeypatch.setattr(profiling, "spans", lambda run=None: tables[run], raising=False)
    return {"jobs": [{}, {}], "failed": 0}


@pytest.mark.parametrize("names, mode, expect", [
    (["d2h_wait"], "self", (1.0 + 0.5 + 1.0) / 2),
    # frame A: 3.0 less the wait and the compile under it; frame B: 2.0 less
    # the half second of its child that lies inside it
    (["assemble_frame"], "self", ((3.0 - 0.5 - 0.25) + (2.0 - 0.5)) / 2),
    (["scored_comparisons"], "self", ((10.0 - 4.0 - 3.0) + (4.0 - 2.0)) / 2),
    (["assemble_frame", "d2h_wait"], "self", (2.25 + 1.5 + 1.5 + 1.0) / 2),
    # the nested trace is counted once
    (["jax_trace", "jax_lower", "jax_backend_compile"], "union", (2.0 + 0.5 + 0.25) / 2),
    (["jax_trace"], "union", 2.0 / 2),
])
def test_span_seconds_on_hand_made_tables(program, names, mode, expect):
    assert span_seconds.read(program, names, mode) == pytest.approx(expect)


def test_span_seconds_has_nothing_to_read(program, monkeypatch):
    from splink_tpu.utils import profiling

    assert span_seconds.read(program, ["no_such_span"], "self") is None
    assert span_seconds.read({"jobs": [{}, {}], "failed": 1}, ["d2h_wait"], "self") is None
    assert span_seconds.read({"jobs": [], "failed": 0}, ["d2h_wait"], "self") is None
    # more jobs than scopes: the scopes are not the jobs'
    assert span_seconds.read({"jobs": [{}] * 4, "failed": 0}, ["d2h_wait"], "self") is None
    with pytest.raises(ValueError):
        span_seconds.read(program, ["d2h_wait"], "sum")
    # a program from before the span table (the parent commit)
    monkeypatch.delattr(profiling, "spans")
    assert span_seconds.read(program, ["d2h_wait"], "self") is None


def test_trace_module_seconds():
    run = {"trace": {"modules": {"jit_fn(123)": 1.5, "jit_fn(456)": 0.25,
                                 "jit_tf_fold(7)": 0.125, "jit_run_em(8)": 0.5}}}
    assert trace_module_seconds.read(run, ["jit_fn("]) == 1.75
    assert trace_module_seconds.read(run, ["jit_tf_"]) == 0.125
    assert trace_module_seconds.read(run, ["jit_tf_", "jit_run_em("]) == 0.625
    assert trace_module_seconds.read(run, ["jit_block_"]) is None
    assert trace_module_seconds.read({}, ["jit_fn("]) is None
    assert trace_module_seconds.read({"trace": None}, ["jit_fn("]) is None


@pytest.mark.parametrize("cell", sorted(TINY))
def test_rehearsal_line_carries_the_span_metrics(cell):
    res = result_of(run_cell(cell, trace=1))
    assert res["correct"] is True, res["checks"]
    got = res["metrics"]
    for name in ("kernel_build_s", "frame_assembly_s", "d2h_wait_s", "facade_self_s"):
        assert got[name]["unit"] == "s" and got[name]["value"] >= 0, name
    assert got["frame_assembly_s"]["value"] > 0 and got["kernel_build_s"]["value"] > 0
    # self time of the roots is a small part of what the old residual guessed at
    wall = sum(res["window"]["job_wall_s"]) / len(res["window"]["job_wall_s"])
    assert got["facade_self_s"]["value"] < 0.25 * wall
    # device-trace metrics: listed for the cell, silent without a device plane
    listed = {m["name"] for m in manifest()["per_layer"] if cell in m.get("workloads", [cell])}
    assert "gamma_device_s" in listed and "gamma_device_s" not in got
    assert ("tf_device_s" in listed) == (cell == "c3_link_tf") and "tf_device_s" not in got
