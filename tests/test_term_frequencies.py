"""Term-frequency adjustment formulas vs hand computation
(reference: /root/reference/splink/term_frequencies.py, tests
/root/reference/tests/test_term_frequencies.py)."""

import numpy as np
import pandas as pd
import pytest

from splink_tpu.params import Params
from splink_tpu.term_frequencies import (
    bayes_combine,
    compute_token_adjustment,
    make_adjustment_for_term_frequencies,
)


def test_bayes_combine_formula():
    # p1*p2 / (p1*p2 + (1-p1)(1-p2))
    got = bayes_combine([np.array([0.9]), np.array([0.3])])
    want = 0.9 * 0.3 / (0.9 * 0.3 + 0.1 * 0.7)
    assert got[0] == pytest.approx(want, rel=1e-12)
    # 0.5 is neutral
    got = bayes_combine([np.array([0.7]), np.array([0.5])])
    assert got[0] == pytest.approx(0.7, rel=1e-12)


def test_token_adjustment_hand_case():
    # Two tokens: "smith" (common, low evidential value) and "zorro" (rare).
    values_l = np.array(["smith", "smith", "zorro", "smith", None], dtype=object)
    values_r = np.array(["smith", "smith", "zorro", "jones", "x"], dtype=object)
    p = np.array([0.2, 0.4, 0.9, 0.99, 0.5])
    lam = 0.3
    adj, lookup = compute_token_adjustment(values_l, values_r, p, lam)

    # smith: adj_lambda = mean(0.2, 0.4) = 0.3; bayes with 1-lam = 0.7:
    want_smith = 0.3 * 0.7 / (0.3 * 0.7 + 0.7 * 0.3)  # = 0.5
    assert lookup["smith"] == pytest.approx(want_smith, rel=1e-12)
    # zorro: adj_lambda = 0.9
    want_zorro = 0.9 * 0.7 / (0.9 * 0.7 + 0.1 * 0.3)
    assert lookup["zorro"] == pytest.approx(want_zorro, rel=1e-12)
    np.testing.assert_allclose(adj, [want_smith, want_smith, want_zorro, 0.5, 0.5])


def _params():
    return Params(
        {
            "link_type": "dedupe_only",
            "proportion_of_matches": 0.3,
            "comparison_columns": [
                {"col_name": "name", "term_frequency_adjustments": True}
            ],
            "blocking_rules": ["l.name = r.name"],
        }
    )


def test_make_adjustment_end_to_end():
    params = _params()
    df_e = pd.DataFrame(
        {
            "match_probability": [0.8, 0.6, 0.9, 0.2],
            "name_l": ["ann", "ann", "bo", "ann"],
            "name_r": ["ann", "ann", "bo", "cat"],
        }
    )
    out = make_adjustment_for_term_frequencies(
        df_e, params, params.settings, retain_adjustment_columns=True
    )
    assert out.columns[0] == "tf_adjusted_match_prob"
    assert "name_adj" in out.columns
    lam = 0.3
    ann_lambda = (0.8 + 0.6) / 2
    ann_adj = ann_lambda * (1 - lam) / (ann_lambda * (1 - lam) + (1 - ann_lambda) * lam)
    # row 0: combine(0.8, ann_adj)
    want0 = 0.8 * ann_adj / (0.8 * ann_adj + 0.2 * (1 - ann_adj))
    assert out.tf_adjusted_match_prob.iloc[0] == pytest.approx(want0, rel=1e-10)
    # disagreeing pair is neutral: tf_adjusted == match_probability
    assert out.tf_adjusted_match_prob.iloc[3] == pytest.approx(0.2, rel=1e-10)


def test_no_tf_columns_warns_and_passes_through():
    params = Params(
        {
            "link_type": "dedupe_only",
            "comparison_columns": [{"col_name": "name"}],
            "blocking_rules": ["l.name = r.name"],
        }
    )
    df_e = pd.DataFrame({"match_probability": [0.5]})
    with pytest.warns(UserWarning, match="No term frequency"):
        out = make_adjustment_for_term_frequencies(df_e, params, params.settings)
    assert out is df_e


def test_linker_tf_integration():
    from splink_tpu import Splink

    rng = np.random.default_rng(0)
    common = ["smith"] * 30
    rare = ["zorro"] * 2
    names = common + rare
    df = pd.DataFrame(
        {
            "unique_id": range(len(names)),
            "name": names,
            "dob": rng.choice(["a", "b"], len(names)),
        }
    )
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "name", "term_frequency_adjustments": True, "comparison": {"kind": "exact"}},
            {"col_name": "dob", "comparison": {"kind": "exact"}},
        ],
        "blocking_rules": [],
        "max_iterations": 3,
    }
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        linker = Splink(s, df=df)
        df_e = linker.get_scored_comparisons()
        out = linker.make_term_frequency_adjustments(df_e)
    assert "tf_adjusted_match_prob" in out.columns
    # mechanical consistency: tf_adjusted == bayes(match_probability, name_adj)
    from splink_tpu.term_frequencies import bayes_combine

    want = bayes_combine(
        [out.match_probability.to_numpy(), out.name_adj.to_numpy()]
    )
    np.testing.assert_allclose(out.tf_adjusted_match_prob.to_numpy(), want, rtol=1e-9)
    # disagreeing pairs are neutral (adj exactly 0.5)
    disagree = out[out.name_l != out.name_r]
    assert (disagree.name_adj == 0.5).all()
    # agreeing pairs on a token carry that token's adjusted lambda, which is
    # the Bayes combination of the token's mean match probability with 1-λ
    lam = linker.params.params["λ"]
    smith = out[(out.name_l == "smith") & (out.name_r == "smith")]
    adj_lambda = smith.match_probability.mean()
    want_adj = (adj_lambda * (1 - lam)) / (
        adj_lambda * (1 - lam) + (1 - adj_lambda) * lam
    )
    np.testing.assert_allclose(smith.name_adj.to_numpy(), want_adj, rtol=1e-6)


def test_device_path_matches_host_groupby():
    """compute_token_adjustment_device (segment_sum over token ids) must agree
    with the host pandas-groupby path on nulls, disagreements and skewed
    token distributions."""
    from splink_tpu.term_frequencies import (
        compute_token_adjustment,
        compute_token_adjustment_device,
    )

    rng = np.random.default_rng(11)
    n, n_tokens = 20_000, 37
    vocab = np.array([f"tok{i}" for i in range(n_tokens)], dtype=object)
    tid_l = rng.integers(-1, n_tokens, n).astype(np.int32)  # -1 = null
    tid_r = np.where(rng.random(n) < 0.5, tid_l, rng.integers(-1, n_tokens, n)).astype(np.int32)
    p = rng.random(n)
    base_lambda = 0.27

    values_l = np.where(tid_l >= 0, vocab[np.maximum(tid_l, 0)], None)
    values_r = np.where(tid_r >= 0, vocab[np.maximum(tid_r, 0)], None)

    adj_host, _ = compute_token_adjustment(values_l, values_r, p, base_lambda)
    adj_dev, _, _ = compute_token_adjustment_device(tid_l, tid_r, p, base_lambda, n_tokens)
    np.testing.assert_allclose(adj_dev, adj_host, rtol=1e-9, atol=1e-12)


@pytest.fixture(scope="module")
def aligned_linker():
    from splink_tpu import Splink

    rng = np.random.default_rng(5)
    names = np.array(["smith", "jones", "patel", "kim", "lee"], dtype=object)
    df = pd.DataFrame(
        {
            "unique_id": np.arange(300),
            "name": names[rng.integers(0, len(names), 300)],
            "city": np.array(["a", "b", "c"], dtype=object)[rng.integers(0, 3, 300)],
        }
    )
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "name", "comparison": {"kind": "exact"},
             "term_frequency_adjustments": True},
            {"col_name": "city", "comparison": {"kind": "exact"}},
        ],
        "blocking_rules": ["l.city = r.city"],
        "max_iterations": 3,
    }
    linker = Splink(s, df=df)
    return linker, linker.get_scored_comparisons()


def test_linker_uses_device_path_and_falls_back_when_misaligned(aligned_linker):
    linker, df_e = aligned_linker
    assert linker._df_e_aligned_with_pairs(df_e)
    out_fast = linker.make_term_frequency_adjustments(df_e)

    shuffled = df_e.sample(frac=1.0, random_state=0)
    assert not linker._df_e_aligned_with_pairs(shuffled)
    out_slow = linker.make_term_frequency_adjustments(shuffled).sort_index()
    np.testing.assert_allclose(
        out_fast.tf_adjusted_match_prob.to_numpy(),
        out_slow.tf_adjusted_match_prob.to_numpy(),
        rtol=1e-9,
    )


def test_device_path_chunked_matches_single_chunk(monkeypatch):
    """The chunked accumulation (HBM-bounded) must give the same answer as a
    single-chunk pass, including at ragged chunk boundaries."""
    import splink_tpu.term_frequencies as tf

    rng = np.random.default_rng(13)
    n, n_tokens = 10_001, 13  # deliberately not a multiple of the chunk size
    tid_l = rng.integers(-1, n_tokens, n).astype(np.int32)
    tid_r = np.where(rng.random(n) < 0.4, tid_l, rng.integers(-1, n_tokens, n)).astype(np.int32)
    p = rng.random(n)

    adj_one, lam_one, cnt_one = tf.compute_token_adjustment_device(
        tid_l, tid_r, p, 0.3, n_tokens
    )
    monkeypatch.setattr(tf, "TF_DEVICE_CHUNK", 4096)
    adj_many, lam_many, cnt_many = tf.compute_token_adjustment_device(
        tid_l, tid_r, p, 0.3, n_tokens
    )
    np.testing.assert_allclose(adj_many, adj_one, rtol=1e-12)
    np.testing.assert_allclose(lam_many, lam_one, rtol=1e-12)
    np.testing.assert_allclose(cnt_many, cnt_one, rtol=0)


def test_tf_with_case_sql_and_custom_multicolumn():
    """TF adjustment works on a col_name column whose comparison is a
    compiled CASE expression, AND on a custom multi-column comparison: each
    of its custom_columns_used gets the per-column adjustment (the
    reference's per-column formula extended to the multi-column case —
    its own selection would KeyError there,
    /root/reference/splink/term_frequencies.py:130-134)."""
    import numpy as np
    import pandas as pd

    from splink_tpu import Splink
    from splink_tpu.term_frequencies import (
        bayes_combine,
        compute_token_adjustment,
        term_frequency_columns,
    )

    rng = np.random.default_rng(0)
    n = 120
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": rng.choice(["ann", "bob", "cat", "dan", "eve"], n),
            "city": rng.choice(["x", "y"], n),
        }
    )
    s = {
        "link_type": "dedupe_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {
                "col_name": "name",
                "num_levels": 2,
                "term_frequency_adjustments": True,
                "case_expression": "case when name_l is null or name_r is "
                "null then -1 when lower(name_l) = lower(name_r) then 1 "
                "else 0 end",
            },
            {
                "custom_name": "combo",
                "custom_columns_used": ["name", "city"],
                "num_levels": 2,
                "term_frequency_adjustments": True,
                "case_expression": "case when name_l = name_r and "
                "city_l = city_r then 1 else 0 end",
            },
        ],
        "max_iterations": 4,
    }
    # flagged columns: "name" (col_name, deduped with combo's use) + "city"
    assert list(term_frequency_columns(Splink(s, df=df).settings)) == [
        "name",
        "city",
    ]
    linker = Splink(s, df=df)
    df_e = linker.get_scored_comparisons()
    out = linker.make_term_frequency_adjustments(df_e)
    assert "tf_adjusted_match_prob" in out.columns
    assert np.isfinite(out.tf_adjusted_match_prob.to_numpy()).all()
    # the custom comparison forced retention of its used columns even
    # without retain_matching_columns
    assert "city_l" in df_e.columns and "city_r" in df_e.columns
    # adjustment columns for BOTH flagged raw columns (linker retains them)
    assert "name_adj" in out.columns and "city_adj" in out.columns

    # oracle: reference formulas computed on the host over raw values
    base_lambda = linker.params.params["λ"]
    p = df_e["match_probability"].to_numpy()
    want = {}
    for col in ("name", "city"):
        want[col], _ = compute_token_adjustment(
            df_e[f"{col}_l"].to_numpy(object),
            df_e[f"{col}_r"].to_numpy(object),
            p,
            base_lambda,
        )
        np.testing.assert_allclose(
            out[f"{col}_adj"].to_numpy(), want[col], rtol=1e-9
        )
    np.testing.assert_allclose(
        out["tf_adjusted_match_prob"].to_numpy(),
        bayes_combine([p, want["name"], want["city"]]),
        rtol=1e-9,
    )


def test_streaming_tf_matches_one_frame_path():
    """stream_tf_adjusted_comparisons (two chunked passes over the
    pattern stream) must reproduce the one-frame
    get_scored_comparisons -> make_term_frequency_adjustments flow."""
    from splink_tpu import Splink

    rng = np.random.default_rng(31)
    surnames = ["smith", "jones", "patel", "lee", "garcia", "chen"]
    n = 400
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "surname": rng.choice(surnames, n, p=[0.5, 0.2, 0.1, 0.1, 0.05, 0.05]),
            "city": rng.choice([f"c{k}" for k in range(6)], n),
            "dob": rng.choice([f"d{k}" for k in range(25)], n),
        }
    )
    df.loc[rng.choice(n, 12, replace=False), "surname"] = None
    df["age"] = rng.choice([20.0, 30.0, 40.0, 55.0], n)
    df.loc[rng.choice(n, 9, replace=False), "age"] = np.nan

    def settings(**kw):
        return {
            "link_type": "dedupe_only",
            "comparison_columns": [
                {"col_name": "surname", "num_levels": 2,
                 "term_frequency_adjustments": True},
                {"col_name": "city", "num_levels": 2},
                {"col_name": "age", "data_type": "numeric", "num_levels": 2,
                 "comparison": {"kind": "numeric_abs", "thresholds": [0.5]},
                 "term_frequency_adjustments": True},
            ],
            "blocking_rules": ["l.dob = r.dob"],
            "max_iterations": 4,
            "retain_matching_columns": True,
            **kw,
        }

    key = ["unique_id_l", "unique_id_r"]
    for kw in (
        dict(device_pair_generation="on", max_resident_pairs=1024),
        dict(device_pair_generation="off", max_resident_pairs=1024),
    ):
        streamed = pd.concat(
            list(Splink(settings(**kw), df=df).stream_tf_adjusted_comparisons()),
            ignore_index=True,
        ).sort_values(key).reset_index(drop=True)

        lk = Splink(settings(**kw), df=df)
        frame = lk.make_term_frequency_adjustments(
            lk.get_scored_comparisons()
        ).sort_values(key).reset_index(drop=True)

        assert list(streamed.columns) == list(frame.columns)
        np.testing.assert_array_equal(
            streamed[key].to_numpy(), frame[key].to_numpy()
        )
        np.testing.assert_allclose(
            streamed["tf_adjusted_match_prob"].to_numpy(),
            frame["tf_adjusted_match_prob"].to_numpy(),
            rtol=1e-9,
        )
        np.testing.assert_allclose(
            streamed["surname_adj"].to_numpy(),
            frame["surname_adj"].to_numpy(),
            rtol=1e-9,
        )
        np.testing.assert_allclose(
            streamed["age_adj"].to_numpy(),
            frame["age_adj"].to_numpy(),
            rtol=1e-9,
        )


def test_streaming_tf_no_tf_columns_falls_back():
    from splink_tpu import Splink

    df = pd.DataFrame(
        {"unique_id": [0, 1, 2, 3], "name": ["a", "a", "b", "b"],
         "dob": ["x", "x", "x", "x"]}
    )
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "name", "num_levels": 2}],
        "blocking_rules": ["l.dob = r.dob"],
        "max_iterations": 1,
        "device_pair_generation": "on",
        "max_resident_pairs": 1024,
    }
    with pytest.warns(UserWarning, match="No term frequency"):
        chunks = list(Splink(s, df=df).stream_tf_adjusted_comparisons())
    assert sum(len(c) for c in chunks) == 6
    assert "tf_adjusted_match_prob" not in pd.concat(chunks).columns


def test_streaming_tf_link_only_and_mesh():
    """Streaming TF over a link_only virtual plan (rectangle units) and
    under an 8-virtual-device mesh must both match the one-frame flow."""
    from splink_tpu import Splink

    rng = np.random.default_rng(41)
    surnames = ["smith", "jones", "patel", "lee"]
    def frame(n, base):
        return pd.DataFrame(
            {
                "unique_id": np.arange(base, base + n),
                "surname": rng.choice(surnames, n, p=[0.5, 0.25, 0.15, 0.1]),
                "dob": rng.choice([f"d{k}" for k in range(12)], n),
            }
        )
    df_l, df_r = frame(150, 0), frame(170, 1000)

    def settings(**kw):
        return {
            "link_type": "link_only",
            "comparison_columns": [
                {"col_name": "surname", "num_levels": 2,
                 "term_frequency_adjustments": True},
            ],
            "blocking_rules": ["l.dob = r.dob"],
            "max_iterations": 3,
            "retain_matching_columns": True,
            "max_resident_pairs": 1024,
            **kw,
        }

    key = ["unique_id_l", "unique_id_r"]
    for kw in (
        dict(device_pair_generation="on"),
        dict(device_pair_generation="on", mesh={"data": 8},
             virtual_materialise_ids="off"),  # recompute branch, sharded
    ):
        streamed = pd.concat(
            list(
                Splink(settings(**kw), df_l=df_l, df_r=df_r)
                .stream_tf_adjusted_comparisons()
            ),
            ignore_index=True,
        ).sort_values(key).reset_index(drop=True)
        lk = Splink(settings(**kw), df_l=df_l, df_r=df_r)
        one = lk.make_term_frequency_adjustments(
            lk.get_scored_comparisons()
        ).sort_values(key).reset_index(drop=True)
        assert len(streamed) and len(streamed) == len(one)
        np.testing.assert_array_equal(
            streamed[key].to_numpy(), one[key].to_numpy()
        )
        np.testing.assert_allclose(
            streamed["tf_adjusted_match_prob"].to_numpy(),
            one["tf_adjusted_match_prob"].to_numpy(),
            rtol=1e-9,
        )


# ---------------------------------------------------------------------------
# The pass touches only the columns it adds (ROADMAP A4): no copy of the
# scored frame, no frame-length temporaries. The old construction — deep
# copy, column inserts, whole-array combine, reorder — is written out here
# as the reference.
# ---------------------------------------------------------------------------


def _bayes_whole(probs):
    """bayes_combine as it was: seven arrays of the inputs' length."""
    num = np.ones_like(np.asarray(probs[0], dtype=np.float64))
    den = np.ones_like(num)
    for p in probs:
        p = np.asarray(p, dtype=np.float64)
        num = num * p
        den = den * (1.0 - p)
    tot = num + den
    with np.errstate(invalid="ignore", divide="ignore"):
        return np.where(
            tot > 0, num / np.maximum(tot, np.finfo(np.float64).tiny), 0.5
        )


def _tf_params():
    return Params(
        {
            "link_type": "dedupe_only",
            "proportion_of_matches": 0.3,
            "comparison_columns": [
                {"col_name": "name", "term_frequency_adjustments": True},
                {"col_name": "city", "term_frequency_adjustments": True},
                {"col_name": "dob"},
            ],
            "blocking_rules": ["l.dob = r.dob"],
        }
    )


def _scored_frame(n=700, seed=3, consolidated=False):
    """A frame shaped like the linker's: float32 probabilities, int64 ids
    and levels, ``str`` (Arrow-backed where pyarrow is) retained columns
    with nulls, one block a column as ``_FrameWriter.frame`` hands it out —
    or consolidated, as a user's own frame usually is. Returns the frame
    and the token ids of its two flagged columns."""
    rng = np.random.default_rng(seed)
    names = np.array(["ann", "bob", "cat", "dan", None], dtype=object)
    cities = np.array(["x", "y", None], dtype=object)
    cols = {
        "match_probability": rng.random(n).astype(np.float32),
        "unique_id_l": np.arange(n),
        "unique_id_r": np.arange(n)[::-1].copy(),
    }
    token_ids = {}
    for col, vocab in (("name", names), ("city", cities)):
        tid_l = rng.integers(0, len(vocab), n)
        tid_r = np.where(rng.random(n) < 0.6, tid_l, rng.integers(0, len(vocab), n))
        for side, tid in (("l", tid_l), ("r", tid_r)):
            cols[f"{col}_{side}"] = pd.array(vocab[tid], dtype="str")
        cols[f"gamma_{col}"] = rng.integers(-1, 3, n)
        null = len(vocab) - 1  # the vocabulary's None
        token_ids[col] = (
            np.where(tid_l == null, -1, tid_l).astype(np.int32),
            np.where(tid_r == null, -1, tid_r).astype(np.int32),
            null,
        )
    cols["prob_gamma_name_match"] = rng.random(n).astype(np.float32)
    # planted: certain and impossible pairs beside any evidence
    cols["match_probability"][:4] = [0.0, 1.0, 0.5, 0.0]
    df_e = pd.DataFrame(cols, copy=False)
    assert df_e._mgr.nblocks == len(df_e.columns)
    if consolidated:
        df_e = df_e.copy()
        assert df_e._mgr.nblocks < len(df_e.columns)
    return df_e, token_ids


def _adjusted_the_old_way(df_e, params, retain, pair_token_ids):
    from splink_tpu.term_frequencies import (
        compute_token_adjustment_device,
        term_frequency_columns,
    )

    tf_cols = list(term_frequency_columns(params.settings))
    df = df_e.copy()
    lam = params.params["λ"]
    adj_arrays = []
    for col in tf_cols:
        if pair_token_ids is not None:
            adj, _, _ = compute_token_adjustment_device(
                *pair_token_ids[col][:2], df["match_probability"].to_numpy(),
                lam, pair_token_ids[col][2],
            )
        else:
            adj, _ = compute_token_adjustment(
                df[f"{col}_l"].to_numpy(dtype=object),
                df[f"{col}_r"].to_numpy(dtype=object),
                df["match_probability"].to_numpy(), lam,
            )
        df[f"{col}_adj"] = adj
        adj_arrays.append(adj)
    df["tf_adjusted_match_prob"] = _bayes_whole(
        [df["match_probability"].to_numpy()] + adj_arrays
    )
    if not retain:
        df = df.drop(columns=[f"{c}_adj" for c in tf_cols])
    lead = ["tf_adjusted_match_prob", "match_probability"]
    return df[lead + [c for c in df.columns if c not in lead]]


@pytest.mark.parametrize("retain", [False, True], ids=["drop_adj", "retain_adj"])
@pytest.mark.parametrize("path", ["device", "host"])
def test_adjusted_frame_equals_the_deep_copy_construction(path, retain):
    params = _tf_params()
    df_e, token_ids = _scored_frame()
    ids = token_ids if path == "device" else None
    want = _adjusted_the_old_way(df_e, params, retain, ids)
    got = make_adjustment_for_term_frequencies(
        df_e, params, params.settings,
        retain_adjustment_columns=retain, pair_token_ids=ids,
    )
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert list(got.columns[:2]) == ["tf_adjusted_match_prob", "match_probability"]
    assert ("name_adj" in got.columns) == retain
    # and again over its own output: the columns it adds replace their
    # namesakes where they stand, as the in-place writes did
    again = make_adjustment_for_term_frequencies(
        got, params, params.settings,
        retain_adjustment_columns=retain, pair_token_ids=ids,
    )
    pd.testing.assert_frame_equal(again, got, check_exact=True)


def _buffers(series):
    """The addresses of the memory a column's values live in."""
    arr = series.array
    if hasattr(arr, "__arrow_array__"):
        return [
            b.address
            for chunk in arr.__arrow_array__().chunks
            for b in chunk.buffers() if b is not None
        ]
    return [series.to_numpy().__array_interface__["data"][0]]


def _overwrite(frame):
    """Every column written through ``.loc``: whole columns and single
    cells, strings and numbers."""
    for i, c in enumerate(frame.columns):
        fill = "zz" if frame[c].dtype.kind in "OTU" else 7
        if i % 2:
            frame.loc[:, c] = fill
        else:
            frame.loc[frame.index[:5], c] = fill


@pytest.mark.parametrize("consolidated", [False, True],
                         ids=["block_a_column", "consolidated"])
@pytest.mark.parametrize("path", ["device", "host"])
def test_adjusted_frame_shares_its_input_and_neither_sees_the_others_writes(
    path, consolidated
):
    params = _tf_params()
    df_e, token_ids = _scored_frame(consolidated=consolidated)
    # a user's index, with a repeated label: nothing may align by it
    df_e.index = pd.Index(np.arange(len(df_e)) // 2 * 3)
    before = df_e.copy(deep=True)
    ids = token_ids if path == "device" else None
    out = make_adjustment_for_term_frequencies(
        df_e, params, params.settings, retain_adjustment_columns=True,
        pair_token_ids=ids,
    )
    pd.testing.assert_frame_equal(df_e, before, check_exact=True)
    assert out.index.equals(before.index)
    # no column's bytes were copied: the input's memory IS the output's
    for c in df_e.columns:
        assert _buffers(out[c]) == _buffers(df_e[c]), c
    kept = out.copy(deep=True)
    _overwrite(out)
    assert not out[list(before.columns)].equals(before)
    pd.testing.assert_frame_equal(df_e, before, check_exact=True)

    out = make_adjustment_for_term_frequencies(
        df_e, params, params.settings, retain_adjustment_columns=True,
        pair_token_ids=ids,
    )
    pd.testing.assert_frame_equal(out, kept, check_exact=True)
    _overwrite(df_e)
    assert not df_e.equals(before)
    pd.testing.assert_frame_equal(out, kept, check_exact=True)


def test_tf_frame_span_counts_shared_and_added_columns():
    from splink_tpu.utils.profiling import begin_run, discard_run, spans

    params = _tf_params()
    df_e, token_ids = _scored_frame()
    run = begin_run("test-tf-frame-counts")
    try:
        for retain, ids in ((True, token_ids), (False, None)):
            make_adjustment_for_term_frequencies(
                df_e, params, params.settings,
                retain_adjustment_columns=retain, pair_token_ids=ids,
            )
        counts = [s["counts"] for s in spans(run) if s["name"] == "tf_frame"]
    finally:
        discard_run(run)
    n_in = len(df_e.columns)
    assert counts == [
        {"rows": len(df_e), "shared_columns": n_in, "added_columns": 3},
        {"rows": len(df_e), "shared_columns": n_in, "added_columns": 1},
    ]


_BLOCK = 1 << 16


@pytest.mark.parametrize("factors", [1, 2, 3])
@pytest.mark.parametrize("n", [0, 1, _BLOCK - 1, _BLOCK, 3 * _BLOCK + 5])
def test_blocked_bayes_combine_is_bit_equal_to_the_whole_array_form(n, factors):
    import splink_tpu.term_frequencies as tf

    assert tf._COMBINE_ROWS == _BLOCK
    rng = np.random.default_rng(1000 * factors + n % 997)
    probs = [rng.random(n) for _ in range(factors)]
    planted = np.array([0.0, 1.0, 0.5, np.nextafter(0.0, 1), np.nextafter(1.0, 0)])
    for p in probs:
        where = rng.random(n) < 0.2
        p[where] = rng.choice(planted, int(where.sum()))
    if n > 8 and factors > 1:
        # contradictory evidence, at a block's first and last rows too
        for row in (0, 7, n - 1, min(n - 1, _BLOCK - 1), min(n - 1, _BLOCK)):
            probs[0][row], probs[-1][row] = 0.0, 1.0
    for first in (np.float64, np.float32):  # match_probability is float32 on the chip
        given = [probs[0].astype(first), *probs[1:]]
        as_given = [p.copy() for p in given]
        got = bayes_combine(given)
        want = _bayes_whole(given)
        assert got.dtype == np.float64 and got.shape == (n,)
        assert got.tobytes() == want.tobytes()
        if n > 8 and factors > 1:
            assert got[0] == 0.5 and got[n - 1] == 0.5
        for p, q in zip(given, as_given):  # the inputs are read, not written
            assert p.tobytes() == q.tobytes()


def test_bayes_combine_refuses_factors_of_unequal_length():
    with pytest.raises(ValueError, match="one length"):
        bayes_combine([np.full(3, 0.5), np.full(1, 0.5)])


class _CountingIds(np.ndarray):
    """The table's ids, counting the takes the check makes of them."""

    takes = 0

    def __getitem__(self, key):
        type(self).takes += 1
        return np.asarray(super().__getitem__(key))


@pytest.mark.parametrize(
    "fault", ["none", "swap_in_last_block", "swap_in_first_block", "one_row_short"]
)
def test_alignment_check_compares_every_row_a_block_at_a_time(
    aligned_linker, monkeypatch, fault
):
    import splink_tpu.linker as linker_mod

    linker, df_e = aligned_linker
    block = 64
    monkeypatch.setattr(linker_mod, "_TAKE_ROWS", block)
    n = len(df_e)
    n_blocks = -(-n // block)
    assert n_blocks > 3 and n % block  # a ragged last block
    table = linker._ensure_encoded()
    monkeypatch.setattr(table, "unique_id", table.unique_id.view(_CountingIds))
    monkeypatch.setattr(_CountingIds, "takes", 0)

    frame = df_e.copy()
    if fault == "one_row_short":
        frame = frame.iloc[:-1]
    elif fault != "none":
        # two rows of one side exchange their ids: a permutation no sample
        # of the column need see
        ids = {c: frame[c].to_numpy().copy() for c in ("unique_id_l", "unique_id_r")}
        if fault == "swap_in_last_block":
            col, a = "unique_id_r", n - 1
            b = next(i for i in range(a - 1, -1, -1) if ids[col][i] != ids[col][a])
            assert b // block == a // block == n_blocks - 1
        else:
            col, a, b = "unique_id_l", 1, n - 2
            assert ids[col][a] != ids[col][b]
        ids = ids[col]
        ids[[a, b]] = ids[[b, a]]
        frame[col] = ids
    assert linker._df_e_aligned_with_pairs(frame) is (fault == "none")
    assert _CountingIds.takes == {
        "none": 2 * n_blocks,  # both sides, every block
        "swap_in_last_block": 2 * n_blocks,  # the right side's last
        "swap_in_first_block": 1,  # and nothing after the block that differs
        "one_row_short": 0,
    }[fault]
