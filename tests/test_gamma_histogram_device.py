"""The gamma-level histogram counted where the gamma matrix lives: with the
job's matrix resident on the device, ``Splink._run_em`` dispatches
``gammas.level_counts`` ahead of EM and brings the counts home after it; on
every other path (the streamed regime, the pattern regimes) the host's
``linker._gamma_histograms`` counts as before. Both give one dict, value for
value, and the ``gamma_histogram`` span says which counted (``device_rows``).
"""

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink, gammas
from splink_tpu.linker import _gamma_histograms, _level_dict
from splink_tpu.obs.events import read_events
from splink_tpu.settings import complete_settings_dict
from splink_tpu.utils.profiling import device_spans, spans

# ---------------------------------------------------------------------------
# the count itself against the host's
# ---------------------------------------------------------------------------

_MIXED = complete_settings_dict({
    "link_type": "dedupe_only",
    "comparison_columns": [
        {"col_name": "two", "num_levels": 2},
        {"col_name": "three", "num_levels": 3},
        {"col_name": "four", "num_levels": 4},
        {"col_name": "same", "comparison": {"kind": "exact"}},
    ],
    "blocking_rules": ["l.two = r.two"],
})


def _gammas(n: int, variant: str, levels: list[int]) -> np.ndarray:
    rng = np.random.default_rng(n)
    G = np.stack([rng.integers(-1, L, n) for L in levels], axis=1).astype(np.int8)
    if variant == "no_nulls":
        G[G == -1] = 0
    elif variant == "level_never_taken":
        G[G == 1] = 0  # level 1 of every column is never seen
    elif variant == "out_of_range":
        G[0] = [-7] * len(levels)  # below the null bin
        G[-1] = [L + 3 for L in levels]  # above the top level
        G[n // 2, 0] = 127
    return G


@pytest.mark.parametrize("n", [1, 7, 1000])
@pytest.mark.parametrize(
    "variant", ["nulls", "no_nulls", "level_never_taken", "out_of_range"]
)
def test_device_count_equals_the_host_histogram(n, variant):
    cols = _MIXED["comparison_columns"]
    levels = tuple(int(c["num_levels"]) for c in cols)
    assert sorted(set(levels)) == [2, 3, 4]
    G = _gammas(n, variant, list(levels))
    counts = np.asarray(gammas.level_counts(G, levels))
    assert counts.dtype == np.int32 and counts.shape == (len(cols), max(levels) + 1)
    for c, L in enumerate(levels):
        assert not counts[c, L + 1:].any()  # a shorter column's extra bins
    device, host = _level_dict(_MIXED, counts), _gamma_histograms(_MIXED, G)
    assert device == host
    assert all(sum(v) == n for v in device.values())
    if variant == "level_never_taken":
        assert all(v[2] == 0 for v in device.values())
    if variant == "no_nulls":
        assert all(v[0] == 0 for v in device.values())


# ---------------------------------------------------------------------------
# which path counts, in a job
# ---------------------------------------------------------------------------


def _people(n: int, seed: int) -> pd.DataFrame:
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "unique_id": np.arange(n),
        "first_name": rng.choice(["ann", "bob", "cat", "dan", "eve"], n),
        "surname": rng.choice(["smith", "jones", "taylor", "brown"], n),
        "city": rng.choice(["x", "y", "z"], n),
    })


def _link_job(tmp_path, **over):
    """The two-frame link of the c3 cell's path, with telemetry on so the
    histogram's record and the EM diagnostics can be read back."""
    settings = {
        "link_type": "link_only",
        "comparison_columns": [
            {"col_name": "first_name", "term_frequency_adjustments": True},
            {"col_name": "surname"},
        ],
        "blocking_rules": ["l.city = r.city"],
        "max_iterations": 3,
        "telemetry_dir": str(tmp_path),
        **over,
    }
    df = _people(900, 2)
    linker = Splink(settings, df_l=df.iloc[:600], df_r=df.iloc[600:])
    frame = linker.get_scored_comparisons()
    events = read_events(linker._obs.sink.path)
    record = [e for e in events if e["type"] == "metrics"][-1]["records"]
    [diag] = [e for e in events if e["type"] == "em_diagnostics"]
    support = {c["name"]: c["support"] for c in diag["columns"]}
    assert all(support.values())
    return linker, len(frame), record["gamma_histogram"], support


@pytest.fixture(scope="module")
def resident(tmp_path_factory):
    return _link_job(tmp_path_factory.mktemp("resident"))


@pytest.fixture(scope="module")
def streamed(tmp_path_factory):
    # no pattern regime for this model, and fewer resident pairs than the
    # job has: the streamed regime, which keeps no device copy
    saved = gammas.MAX_PATTERNS
    gammas.MAX_PATTERNS = 1
    try:
        return _link_job(tmp_path_factory.mktemp("streamed"), max_resident_pairs=1024)
    finally:
        gammas.MAX_PATTERNS = saved


def _histogram_span(linker) -> dict:
    [hist] = [s for s in spans(run=linker.run_id) if s["name"] == "gamma_histogram"]
    return hist


def test_a_resident_job_counts_its_levels_on_the_device(resident):
    linker, pairs, _, _ = resident
    assert pairs > 1024
    hist = _histogram_span(linker)
    assert hist["counts"] == {"rows": pairs, "device_rows": pairs}
    [em] = [s for s in spans(run=linker.run_id) if s["name"] == "em"]
    assert em["counts"]["pairs"] == pairs


def test_the_streamed_regime_counts_on_the_host_to_the_same_dict(resident, streamed):
    linker, pairs, record, support = streamed
    assert {s["name"] for s in spans(run=linker.run_id)} >= {"em_streamed"}
    assert _histogram_span(linker)["counts"] == {"rows": pairs, "device_rows": 0}
    _, resident_pairs, resident_record, resident_support = resident
    assert pairs == resident_pairs
    assert record == resident_record
    assert sum(record["first_name"]) == pairs
    assert support == resident_support


def test_a_pattern_path_dedupe_counts_on_the_host():
    settings = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "first_name"}, {"col_name": "surname"}],
        "blocking_rules": ["l.city = r.city", "l.surname = r.surname"],
        "max_iterations": 3,
        "device_pair_generation": "on",
        "max_resident_pairs": 1024,
        "pair_batch_size": 1 << 16,
    }
    linker = Splink(settings, df=_people(900, 1))
    linker.get_scored_comparisons()
    hist = _histogram_span(linker)
    assert hist["counts"]["device_rows"] == 0
    assert "level_counts" not in {d["name"] for d in device_spans(run=linker.run_id)}


def test_nothing_waits_on_the_device_count_before_em(resident):
    """The count is dispatched inside the span and waited for only after EM:
    no ``d2h_wait`` under ``gamma_histogram``, its one device record there,
    and the wait that brings it home opening after the ``em`` stage closed."""
    linker, pairs, _, _ = resident
    table = spans(run=linker.run_id)
    hist = _histogram_span(linker)
    # the first linker of a model compiles the count here: build spans only
    assert {s["kind"] for s in table if s["parent"] == hist["id"]} <= {"build"}
    [record] = [d for d in device_spans(run=linker.run_id) if d["name"] == "level_counts"]
    assert record["parent"] == hist["id"] and record["counts"] == {"rows": pairs}
    [em] = [s for s in table if s["name"] == "em"]
    assert hist["t1"] <= em["t0"]
    levels = [int(c["num_levels"]) for c in linker.settings["comparison_columns"]]
    nbytes = 4 * len(levels) * (max(levels) + 1)  # int32[C, L + 1]
    home = [s for s in table if s["name"] == "d2h_wait" and s["t0"] >= em["t1"]
            and s["counts"]["bytes"] == nbytes]
    assert len(home) == 1
