"""Streaming linker mode: streamed EM + chunked scored output.

Equivalence contract: streaming EM accumulates the same global sufficient
statistics Spark's shuffle gives the reference
(/root/reference/splink/maximisation_step.py:41-59), so parameters and
scores must match the resident path to float tolerance.
"""

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.linker import _FrameWriter


def _df(n=200, seed=0):
    rng = np.random.default_rng(seed)
    firsts = np.array(["amelia", "oliver", "isla", "george", "ava", "noah"])
    lasts = np.array(["smith", "jones", "taylor", "brown"])
    return pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "first_name": firsts[rng.integers(0, 6, n)],
            "surname": lasts[rng.integers(0, 4, n)],
            "city": [f"c{i % 4}" for i in range(n)],
        }
    )


def _settings(**overrides):
    s = {
        "link_type": "dedupe_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 2, "comparison": {"kind": "exact"}},
            {"col_name": "surname", "num_levels": 2, "comparison": {"kind": "exact"}},
        ],
        "max_iterations": 6,
    }
    s.update(overrides)
    return s


def test_streamed_em_matches_resident():
    df = _df()
    resident = Splink(_settings(), df=df)
    df_res = resident.get_scored_comparisons()

    # force streaming: tiny residency threshold and micro-batches
    streamed = Splink(
        _settings(max_resident_pairs=1024, pair_batch_size=1024), df=df
    )
    df_str = streamed.get_scored_comparisons()

    lam_r = resident.params.params["λ"]
    lam_s = streamed.params.params["λ"]
    assert abs(lam_r - lam_s) < 1e-5
    m = df_res.merge(
        df_str, on=["unique_id_l", "unique_id_r"], suffixes=("_a", "_b")
    )
    assert len(m) == len(df_res) == len(df_str)
    np.testing.assert_allclose(
        m.match_probability_a, m.match_probability_b, rtol=1e-3, atol=1e-5
    )


def test_stream_scored_comparisons_chunks():
    df = _df()
    linker = Splink(
        _settings(max_resident_pairs=1024, pair_batch_size=2048), df=df
    )
    chunks = list(linker.stream_scored_comparisons())
    assert len(chunks) > 1
    combined = pd.concat(chunks, ignore_index=True)

    whole = Splink(_settings(), df=df).get_scored_comparisons()
    assert len(combined) == len(whole)
    m = combined.merge(
        whole, on=["unique_id_l", "unique_id_r"], suffixes=("_a", "_b")
    )
    np.testing.assert_allclose(
        m.match_probability_a, m.match_probability_b, rtol=1e-3, atol=1e-5
    )


def test_streamed_save_state_fn_runs_each_iteration():
    df = _df()
    calls = []
    linker = Splink(
        _settings(max_resident_pairs=1024),
        df=df,
        save_state_fn=lambda params, settings: calls.append(
            params.params["λ"]
        ),
    )
    linker.get_scored_comparisons()
    assert len(calls) >= 1
    assert len(calls) == len(linker.params.param_history)


def test_pattern_pipeline_matches_resident_pipeline():
    """The pattern-id regime (one device pass + LUT scoring) must produce
    the same scored frame as the resident gamma-matrix regime."""
    import numpy as np
    import pandas as pd

    from splink_tpu import Splink

    rng = np.random.default_rng(21)
    names = np.array(["ann", "bob", "cath", "dan", "eve", "fred"], dtype=object)
    df = pd.DataFrame(
        {
            "unique_id": np.arange(500),
            "name": names[rng.integers(0, 6, 500)],
            "city": np.array(["x", "y", "z"], dtype=object)[rng.integers(0, 3, 500)],
            "age": rng.integers(20, 70, 500).astype(float),
        }
    )
    base = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "name", "num_levels": 3},
            {"col_name": "city", "comparison": {"kind": "exact"}},
            {"col_name": "age", "data_type": "numeric", "num_levels": 2,
             "comparison": {"kind": "numeric_abs", "thresholds": [2.0]}},
        ],
        "blocking_rules": ["l.city = r.city"],
        "max_iterations": 6,
        "retain_intermediate_calculation_columns": True,
        "float64": True,  # exact pattern-EM == pair-EM identity (f32 diverges
        # a few 1e-4 over an unconverged trajectory from summation order)
    }
    resident = Splink({**base, "max_resident_pairs": 1 << 28}, df=df)
    df_res = resident.get_scored_comparisons()
    patterned = Splink({**base, "max_resident_pairs": 1024}, df=df)
    assert patterned._regime("patterns")
    df_pat = patterned.get_scored_comparisons()

    assert list(df_res.columns) == list(df_pat.columns)
    pd.testing.assert_frame_equal(
        df_res, df_pat, check_exact=False, rtol=1e-5, atol=1e-7
    )
    np.testing.assert_allclose(
        resident.params.params["λ"], patterned.params.params["λ"], rtol=1e-6
    )


def test_spill_dir_memmaps_pair_index(tmp_path):
    import numpy as np
    import pandas as pd

    from splink_tpu import Splink

    rng = np.random.default_rng(3)
    df = pd.DataFrame(
        {
            "unique_id": np.arange(300),
            "name": np.array(["a", "b", "c"], dtype=object)[rng.integers(0, 3, 300)],
        }
    )
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "name", "comparison": {"kind": "exact"}}],
        "blocking_rules": ["l.name = r.name"],
        "max_resident_pairs": 1024,
        "spill_dir": str(tmp_path),
        "max_iterations": 3,
    }
    linker = Splink(s, df=df)
    pairs = linker._ensure_pairs()
    assert pairs.n_pairs > 1024
    assert isinstance(pairs.idx_l, np.memmap)
    out = linker.get_scored_comparisons()
    assert len(out) == pairs.n_pairs
    # spilled and unspilled agree
    linker2 = Splink({**s, "spill_dir": ""}, df=df)
    out2 = linker2.get_scored_comparisons()
    pd.testing.assert_frame_equal(out, out2)


def test_release_input_with_streamed_spill_pipeline(tmp_path):
    """The config-5 production combination: release_input() + streamed
    pattern pipeline + spilled pair index must score like the resident path."""
    df = _df(n=600, seed=7)
    base = _settings(float64=True)  # f32 summation order diverges ~1e-4
    resident = Splink(base, df=df)
    df_res = resident.get_scored_comparisons()

    s = _settings(
        float64=True,
        max_resident_pairs=1024,
        pair_batch_size=1024,
        spill_dir=str(tmp_path),
        retain_matching_columns=False,
        retain_intermediate_calculation_columns=False,
    )
    linker = Splink(s, df=df)
    linker.release_input()
    assert linker.df is None
    chunks = list(linker.stream_scored_comparisons())
    pairs = linker._ensure_pairs()
    assert isinstance(pairs.idx_l, np.memmap)
    df_str = pd.concat(chunks, ignore_index=True)
    m = df_res.merge(
        df_str, on=["unique_id_l", "unique_id_r"], suffixes=("_a", "_b")
    )
    assert len(m) == len(df_res) == len(df_str)
    np.testing.assert_allclose(
        m.match_probability_a, m.match_probability_b, rtol=1e-3, atol=1e-5
    )


def test_stale_spill_dirs_swept(tmp_path):
    import os

    from splink_tpu.blocking import _sweep_stale_spill_dirs

    dead = tmp_path / "splink_pairs_dead"
    dead.mkdir()
    (dead / "owner.pid").write_text("999999999")  # no such pid
    alive = tmp_path / "splink_pairs_alive"
    alive.mkdir()
    (alive / "owner.pid").write_text(str(os.getpid()))
    foreign = tmp_path / "splink_pairs_nopid"
    foreign.mkdir()
    _sweep_stale_spill_dirs(str(tmp_path))
    assert not dead.exists()
    assert alive.exists()
    assert foreign.exists()


def test_blocking_streams_pairs_to_spill_dir(tmp_path):
    """With spill_dir set, blocking writes pair chunks straight to disk —
    no in-RAM concatenated copy — and the PairIndex owns the directory."""
    import gc
    import os

    from splink_tpu.blocking import block_using_rules
    from splink_tpu.data import encode_table
    from splink_tpu.settings import complete_settings_dict

    df = _df(n=300, seed=2)
    s = complete_settings_dict(
        _settings(spill_dir=str(tmp_path), max_resident_pairs=1024)
    )
    table = encode_table(df, s)
    pairs = block_using_rules(s, table, None)
    assert pairs.spill_tmp is not None
    assert isinstance(pairs.idx_l, np.memmap)
    spill_files = os.listdir(pairs.spill_tmp)
    assert {"idx_l.bin", "idx_r.bin", "owner.pid"} <= set(spill_files)
    # identical pair set to the unspilled path
    s2 = complete_settings_dict(_settings())
    ref = block_using_rules(s2, table, None)
    np.testing.assert_array_equal(np.asarray(pairs.idx_l), ref.idx_l)
    np.testing.assert_array_equal(np.asarray(pairs.idx_r), ref.idx_r)
    # dropping the PairIndex reclaims the directory
    tmp = pairs.spill_tmp
    del pairs
    gc.collect()
    assert not os.path.exists(tmp)


def test_blocking_failure_reclaims_partial_spill(tmp_path):
    """An error after the first rule has streamed pairs must close handles
    and remove the partial spill dir (the owner is alive, so the stale
    sweep would rightly skip it)."""
    import os

    import pytest

    from splink_tpu.blocking import block_using_rules
    from splink_tpu.data import encode_table
    from splink_tpu.settings import complete_settings_dict

    df = _df(n=200, seed=1)
    s = complete_settings_dict(_settings(spill_dir=str(tmp_path)))
    table = encode_table(df, s)
    s["blocking_rules"] = ["l.city = r.city", "l.nonexistent = r.nonexistent"]
    with pytest.raises(KeyError):
        block_using_rules(s, table, None)
    assert [d for d in os.listdir(tmp_path) if d.startswith("splink_pairs_")] == []


def test_cartesian_spill_chunks_match_resident(tmp_path, monkeypatch):
    """Chunked cartesian spill emission must produce exactly the resident
    cartesian pair set, for every link type, across chunk boundaries."""
    import splink_tpu.blocking as blocking_mod
    from splink_tpu.blocking import block_using_rules
    from splink_tpu.data import encode_table
    from splink_tpu.settings import complete_settings_dict

    monkeypatch.setattr(blocking_mod, "_CARTESIAN_CHUNK", 7)  # force many chunks

    df = _df(n=20, seed=5)
    for link_type, kwargs in [
        ("dedupe_only", {}),
        ("link_only", {}),
        ("link_and_dedupe", {}),
    ]:
        s = {
            "link_type": link_type,
            "comparison_columns": [
                {"col_name": "first_name", "comparison": {"kind": "exact"}}
            ],
            "blocking_rules": [],
        }
        import warnings as _w

        with _w.catch_warnings():
            _w.simplefilter("ignore")
            s = complete_settings_dict(s)
        if link_type == "dedupe_only":
            table = encode_table(df, s)
            n_left = None
        else:
            from splink_tpu.data import concat_tables

            table = concat_tables(df.iloc[:8], df.iloc[8:], s)
            n_left = 8
        resident = block_using_rules(dict(s, spill_dir=""), table, n_left)
        spilled = block_using_rules(dict(s, spill_dir=str(tmp_path)), table, n_left)
        np.testing.assert_array_equal(np.asarray(spilled.idx_l), resident.idx_l)
        np.testing.assert_array_equal(np.asarray(spilled.idx_r), resident.idx_r)


def test_link_only_spill_release_combination(tmp_path):
    """link_only with released inputs and a spilled pair index scores like
    the plain path (n_left survives release; spill streams the cross-join)."""
    df = _df(n=400, seed=11)
    df_l, df_r = df.iloc[:150].copy(), df.iloc[150:].copy()
    base = {
        "link_type": "link_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {"col_name": "first_name", "comparison": {"kind": "exact"}},
            {"col_name": "surname", "comparison": {"kind": "exact"}},
        ],
        "max_iterations": 4,
        "float64": True,
    }
    plain = Splink(base, df_l=df_l, df_r=df_r).get_scored_comparisons()

    s = dict(base, spill_dir=str(tmp_path), max_resident_pairs=1024)
    linker = Splink(s, df_l=df_l, df_r=df_r)
    linker.release_input()
    chunks = list(linker.stream_scored_comparisons())
    assert isinstance(linker._ensure_pairs().idx_l, np.memmap)
    streamed = pd.concat(chunks, ignore_index=True)
    m = plain.merge(
        streamed, on=["unique_id_l", "unique_id_r"], suffixes=("_a", "_b")
    )
    assert len(m) == len(plain) == len(streamed)
    np.testing.assert_allclose(
        m.match_probability_a, m.match_probability_b, rtol=1e-9
    )


def test_estimate_parameters_train_only():
    """estimate_parameters: EM with no per-pair output; the fitted params
    equal get_scored_comparisons' and scoring afterwards matches."""
    import numpy as np
    import pandas as pd

    from splink_tpu import Splink

    rng = np.random.default_rng(47)
    n = 300
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": rng.choice(["ann", "bob", "cat", "dan", None], n),
            "dob": rng.choice([f"d{k}" for k in range(15)], n),
        }
    )
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "name", "num_levels": 2}],
        "blocking_rules": ["l.dob = r.dob"],
        "max_iterations": 6,
        "device_pair_generation": "on",
        "max_resident_pairs": 1024,
    }
    trained = Splink(dict(s), df=df)
    params = trained.estimate_parameters()
    assert trained._P_virtual is None  # histogram-only: no per-pair state
    scored = pd.concat(
        list(trained.stream_scored_comparisons_after_em()), ignore_index=True
    )

    ref = Splink(dict(s), df=df)
    df_e = ref.get_scored_comparisons()
    assert abs(params.params["λ"] - ref.params.params["λ"]) < 1e-12
    assert len(params.param_history) == len(ref.params.param_history)
    key = ["unique_id_l", "unique_id_r"]
    a = scored.sort_values(key).reset_index(drop=True)
    b = df_e.sort_values(key).reset_index(drop=True)
    np.testing.assert_array_equal(
        a["match_probability"].to_numpy(), b["match_probability"].to_numpy()
    )

    # resident regime too
    s2 = {**s, "device_pair_generation": "off", "max_resident_pairs": 1 << 28}
    t2 = Splink(dict(s2), df=df)
    p2 = t2.estimate_parameters()
    r2 = Splink(dict(s2), df=df)
    r2.get_scored_comparisons()
    assert abs(p2.params["λ"] - r2.params.params["λ"]) < 1e-12


# ----------------------------------------------------------------------
# one dtype per retained column, in every chunk
# ----------------------------------------------------------------------


def _noted_and_unnoted():
    """100 people who block on city and carry a note, then 200 who block on
    dob and carry none (and no city): thousands of consecutive pairs whose
    retained ``note`` is null on both sides."""
    rng = np.random.default_rng(11)
    n_a, n_b = 100, 200
    n = n_a + n_b
    noted = np.arange(n) < n_a
    firsts = np.array(["amelia", "oliver", "isla", "george", "ava", "noah"])
    lasts = np.array(["smith", "jones", "taylor", "brown"])

    def tags(letter, kinds):
        return np.array([f"{letter}{i % kinds}" for i in range(n)], object)

    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "first_name": firsts[rng.integers(0, 6, n)],
            "surname": lasts[rng.integers(0, 4, n)],
            "city": np.where(noted, tags("c", 4), None),
            "dob": np.where(noted, None, tags("d", 2)),
            "note": np.where(noted, tags("n", 3), None),
        }
    )
    settings = _settings(
        blocking_rules=["l.city = r.city", "l.dob = r.dob"],
        additional_columns_to_retain=["note"],
        max_iterations=3,
    )
    return df, settings


_STREAMS = {
    "gamma_chunks": {"pair_batch_size": 1024, "max_resident_pairs": 1 << 20},
    "pattern_chunks": {"pair_batch_size": 1024, "max_resident_pairs": 1024},
    "virtual_chunks": {
        "pair_batch_size": 1024, "max_resident_pairs": 1024,
        "device_pair_generation": "on",
    },
}


@pytest.mark.parametrize("stream", list(_STREAMS))
def test_all_null_chunk_and_empty_frame_carry_the_columns_dtypes(stream):
    """A retained column's dtype is what pandas infers for the WHOLE input
    column, in every chunk: a chunk whose strings are all null, and the
    zero-row frame, are typed like the others, so the concatenated frame has
    the one-shot frame's dtypes (per-chunk inference typed such a chunk
    `object`, and `pd.concat` then up-cast the whole column)."""
    df, settings = _noted_and_unnoted()
    whole = Splink(dict(settings), df=df).get_scored_comparisons()
    assert isinstance(whole["note_l"].dtype, pd.StringDtype)

    linker = Splink({**settings, **_STREAMS[stream]}, df=df)
    assert linker._regime("patterns") == (stream != "gamma_chunks")
    chunks = list(linker.stream_scored_comparisons())
    assert len(chunks) > 4
    nulls = [c["note_l"].isna().all() and c["note_r"].isna().all() for c in chunks]
    assert any(nulls) and not all(nulls)
    for chunk in chunks:
        pd.testing.assert_series_equal(chunk.dtypes, whole.dtypes)
    empty = _FrameWriter(linker, 0).frame()
    assert len(empty) == 0
    pd.testing.assert_series_equal(empty.dtypes, whole.dtypes)

    combined = pd.concat(chunks, ignore_index=True)
    pd.testing.assert_series_equal(combined.dtypes, whole.dtypes)
    kept = ["unique_id_l", "unique_id_r", "note_l", "note_r"]
    pd.testing.assert_frame_equal(
        combined[kept].sort_values(kept[:2]).reset_index(drop=True),
        whole[kept].sort_values(kept[:2]).reset_index(drop=True),
    )
