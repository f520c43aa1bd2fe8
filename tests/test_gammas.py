"""Gamma program: comparison specs -> levels, matching the reference's CASE
semantics (/root/reference/splink/case_statements.py) including null -> -1,
levenshtein equality-top-level, and numeric strict-< thresholds."""

import numpy as np
import pandas as pd

from splink_tpu.data import encode_table
from splink_tpu.gammas import GammaProgram
from splink_tpu.settings import complete_settings_dict


def _program(cols, df):
    s = complete_settings_dict(
        {
            "link_type": "dedupe_only",
            "comparison_columns": cols,
            "blocking_rules": ["l.dob = r.dob"] if "dob" in df else ["l.unique_id = r.unique_id"],
        }
    )
    table = encode_table(df, s)
    return GammaProgram(s, table), table


def _pairs_vs_first(df):
    n = len(df)
    return np.zeros(n - 1, np.int64), np.arange(1, n, dtype=np.int64)


def test_jaro_winkler_levels():
    df = pd.DataFrame(
        {
            "unique_id": range(5),
            "name": ["martha", "martha", "marhta", "mx", None],
        }
    )
    prog, _ = _program([{"col_name": "name", "num_levels": 3}], df)
    il, ir = _pairs_vs_first(df)
    G = prog.compute(il, ir)
    # identical -> 2 (jw=1>0.94); marhta jw=0.961>0.94 -> 2; mx -> 0; null -> -1
    assert G[:, 0].tolist() == [2, 2, 0, -1]


def test_device_table_has_a_floor_of_rows_on_a_tpu_and_only_there(monkeypatch):
    """On a TPU the uploaded table never has fewer than _MIN_TPU_TABLE_ROWS rows
    (the compiler pads the gathered rows of a smaller one to 128 lanes:
    tests/test_mesh_tpu_compile.py); the rows appended are zeros that no pair
    index reaches, so the levels are those of the table as packed."""
    import jax

    from splink_tpu import gammas

    df = pd.DataFrame({"unique_id": range(5),
                       "name": ["martha", "martha", "marhta", "mx", None]})
    cols = [{"col_name": "name", "num_levels": 2, "comparison": {"kind": "exact"}}]
    il, ir = _pairs_vs_first(df)
    prog, _ = _program(cols, df)
    want = prog.compute(il, ir)
    assert prog._packed.shape[0] == 5 and want[:, 0].tolist() == [1, 0, 0, -1]
    packed = np.arange(40, dtype=np.uint32).reshape(10, 4)
    assert gammas._device_table(packed) is packed  # this backend: as packed
    monkeypatch.setattr(gammas, "_MIN_TPU_TABLE_ROWS", 16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    padded = gammas._device_table(packed)
    assert padded.shape == (16, 4) and padded.dtype == packed.dtype
    assert np.array_equal(padded[:10], packed) and not padded[10:].any()
    tall = np.zeros((16, 4), np.uint32)
    assert gammas._device_table(tall) is tall  # at the floor: as packed
    prog, _ = _program(cols, df)
    assert prog._packed.shape[0] == 16
    assert np.array_equal(prog.compute(il, ir), want)


def test_exact_levels_and_nulls():
    df = pd.DataFrame(
        {"unique_id": range(4), "name": ["ann", "ann", "bob", None]}
    )
    prog, _ = _program(
        [{"col_name": "name", "comparison": {"kind": "exact"}}], df
    )
    il, ir = _pairs_vs_first(df)
    G = prog.compute(il, ir)
    assert G[:, 0].tolist() == [1, 0, -1]


def test_levenshtein_levels():
    # 3 levels: equal -> 2; ratio <= 0.3 -> 1; else 0 (reference
    # case_statements.py:117-127)
    df = pd.DataFrame(
        {"unique_id": range(5), "name": ["abcde", "abcde", "abcdx", "zzzzz", None]}
    )
    prog, _ = _program(
        [
            {
                "col_name": "name",
                "num_levels": 3,
                "comparison": {"kind": "levenshtein", "thresholds": [0.3]},
            }
        ],
        df,
    )
    il, ir = _pairs_vs_first(df)
    G = prog.compute(il, ir)
    # abcde/abcde equal -> 2; abcdx: lev 1 / 5 = 0.2 <= 0.3 -> 1; zzzzz: 1.0 -> 0
    assert G[:, 0].tolist() == [2, 1, 0, -1]


def test_numeric_perc_levels():
    df = pd.DataFrame(
        {
            "unique_id": range(5),
            "amount": [100.0, 100.0, 104.0, 150.0, None],
        }
    )
    prog, _ = _program(
        [{"col_name": "amount", "data_type": "numeric", "num_levels": 3}], df
    )
    il, ir = _pairs_vs_first(df)
    G = prog.compute(il, ir)
    # equal -> reldiff 0 < 1e-4 -> 2; 4% diff < 5% -> 1; 50% -> 0; null -> -1
    assert G[:, 0].tolist() == [2, 1, 0, -1]


def test_numeric_abs_levels():
    df = pd.DataFrame(
        {"unique_id": range(4), "amount": [10.0, 10.0, 10.000001, 11.0]}
    )
    prog, _ = _program(
        [
            {
                "col_name": "amount",
                "data_type": "numeric",
                "num_levels": 2,
                "comparison": {"kind": "numeric_abs", "thresholds": [0.00001]},
            }
        ],
        df,
    )
    il, ir = _pairs_vs_first(df)
    G = prog.compute(il, ir)
    assert G[:, 0].tolist() == [1, 1, 0]


def test_qgram_comparison_kinds():
    df = pd.DataFrame(
        {"unique_id": range(4), "name": ["hello", "hello", "help", "zzzz"]}
    )
    prog, _ = _program(
        [
            {
                "col_name": "name",
                "num_levels": 2,
                "comparison": {"kind": "qgram_jaccard", "thresholds": [0.5], "q": 2},
            }
        ],
        df,
    )
    il, ir = _pairs_vs_first(df)
    G = prog.compute(il, ir)
    assert G[0, 0] == 1  # identical
    assert G[2, 0] == 0  # disjoint


def test_batching_consistent():
    rng = np.random.default_rng(0)
    names = [f"name{k % 37}" for k in range(500)]
    df = pd.DataFrame({"unique_id": range(500), "name": names})
    prog, _ = _program([{"col_name": "name", "num_levels": 3}], df)
    il = rng.integers(0, 500, 2000).astype(np.int64)
    ir = rng.integers(0, 500, 2000).astype(np.int64)
    G_big = prog.compute(il, ir, batch_size=2048)
    G_small = prog.compute(il, ir, batch_size=128)
    np.testing.assert_array_equal(G_big, G_small)


def test_unicode_strings_character_semantics():
    # non-ASCII strings compare at character level (uint32 codepoints)
    df = pd.DataFrame(
        {"unique_id": range(3), "name": ["josé", "josé", "jose"]}
    )
    prog, table = _program([{"col_name": "name", "num_levels": 3}], df)
    assert table.strings["name"].bytes_.dtype == np.uint32
    assert table.strings["name"].lengths[0] == 4  # characters, not bytes
    il, ir = _pairs_vs_first(df)
    G = prog.compute(il, ir)
    assert G[0, 0] == 2  # identical
    assert G[1, 0] >= 1  # one-character difference, high jw


def test_name_inversion_levels():
    # (reference case_statements.py:248-277): detect surname/forename swaps
    df = pd.DataFrame(
        {
            "unique_id": range(5),
            "surname": ["smith", "smith", "john", "zzz", None],
            "forename": ["john", "john", "smith", "qqq", "x"],
        }
    )
    cols = [
        {
            "custom_name": "surname_inv",
            "custom_columns_used": ["surname", "forename"],
            "num_levels": 4,
            "comparison": {
                "kind": "name_inversion",
                "column": "surname",
                "other_columns": ["forename"],
                "thresholds": [0.94, 0.88],
            },
        },
        {"col_name": "surname", "num_levels": 2},
    ]
    prog, _ = _program(cols, df)
    il, ir = _pairs_vs_first(df)
    G = prog.compute(il, ir)
    # pair (0,1): identical surname -> 3
    # pair (0,2): surname_l 'smith' vs surname_r 'john' low, but matches
    #   forename_r 'smith' -> inversion level 2
    # pair (0,3): nothing matches -> 0
    # pair (0,4): surname_r null -> -1
    assert G[:, 0].tolist() == [3, 2, 0, -1]
