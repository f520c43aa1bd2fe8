"""The reduction from trace to numbers, on hand-made intervals and on a trace
recorded on the chip in this PR's first traced run (tests/data/: the planes as
``read_planes`` gives them, cut to the 3 s around the job's largest op, host
events of 5 ms and more, names cut to 160 characters, with the busy seconds
the reduction gave on the chip's machine)."""

import gzip
import json
import os

import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_merges_overlaps():
    assert tr.union_seconds([(0, 1e9), (0.5e9, 1e9), (3e9, 1e9)]) == pytest.approx(2.5)
    assert tr.union_seconds([]) == 0.0
    assert tr.union_seconds([(5e9, 1e9), (5.2e9, 0.1e9)]) == pytest.approx(1.0)


def synthetic():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [("fusion.1", 0.0, 2e9), ("fusion.2", 1e9, 2e9),
                                       ("copy.3", 8e9, 1e9)]},
        {"name": "XLA Modules", "events": [("jit_fn(1)", 0.0, 3e9), ("jit_score(2)", 8e9, 1e9)]},
    ]}
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("$job.py:70 run_job", 0.0, 10e9), ("$linker.py:1053 _concat_chunks", 3.1e9, 4.8e9),
        ("$short", 4e9, 0.1e9)]}]}
    return [dev, host]


def test_reduce_on_hand_made_intervals():
    out = tr.reduce(synthetic())
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(4.0)  # [0,3] u [8,9]
    assert out["active_span_s"] == pytest.approx(9.0)
    assert out["modules"] == {"jit_fn(1)": pytest.approx(3.0), "jit_score(2)": pytest.approx(1.0)}
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(2.0)] or \
        out["device_ops"][0] == ["fusion.2", pytest.approx(2.0)]
    # one gap, [3, 8] s, named by the innermost host frame covering it
    assert out["idle_gaps"] == [["$linker.py:1053 _concat_chunks", pytest.approx(5.0)]]


def test_reduce_without_a_device_plane_reads_nothing():
    out = tr.reduce([synthetic()[1]])
    assert out["devices"] == 0 and out["busy_s"] == 0.0 and out["device_ops"] == []


def test_unknown_device_kind_is_an_error():
    assert tr.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        tr.load_peaks("TPU v9 imaginary")


def test_recorded_chip_trace():
    path = os.path.join(HERE, "data", "c4_job_trace_trimmed.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    out = tr.reduce(rec["planes"])
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["active_span_s"]
    assert any(name.startswith("jit_") for name in out["modules"])
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
