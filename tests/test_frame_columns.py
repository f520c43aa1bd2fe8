"""Retained columns reach the scored frame as columns.

``Splink._assemble_columns`` takes each retained column from the array pandas
infers for the WHOLE input column (``EncodedTable.frame_column``: an Arrow
string array for strings where pyarrow is installed) by the pair index. The
reference here is the plain way it was done before: one object gather per
side from the table's original values, then ``pd.DataFrame`` — the frame
must equal it value for value and dtype for dtype.
"""

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.utils.profiling import spans

_STRINGS = ("first_name", "surname", "city", "note")


def _people(n, seed, first_id=0):
    """People with nulls in every string column, a float column with NaN and
    a column of mixed Python types."""
    rng = np.random.default_rng(seed)
    mixed = np.empty(n, dtype=object)
    mixed[:] = [(7, "seven", 7.5, None)[k] for k in rng.integers(0, 4, n)]
    return pd.DataFrame(
        {
            "unique_id": np.arange(first_id, first_id + n),
            "first_name": rng.choice(["amelia", "oliver", "isla", "ava", None], n),
            "surname": rng.choice(["smith", "jones", "taylor", None], n),
            "city": rng.choice(["leeds", "york", "hull", None], n),
            "dob": rng.choice([f"d{k}" for k in range(max(n // 12, 2))], n),
            "note": rng.choice(["kept", "moved", None], n),
            "height": np.where(rng.random(n) < 0.2, np.nan, rng.normal(170, 9, n)),
            "mixed": mixed,
        }
    )


def _settings(link_type, **over):
    return {
        "link_type": link_type,
        "blocking_rules": ["l.dob = r.dob"],
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 2,
             "term_frequency_adjustments": True},
            {"col_name": "surname", "num_levels": 2},
            {"col_name": "city", "num_levels": 2},
        ],
        "additional_columns_to_retain": ["note", "height", "mixed"],
        "max_iterations": 3,
        **over,
    }


_STREAM = {"max_resident_pairs": 1024, "pair_batch_size": 1024}
_CASES = {
    "resident": ("link_only", {}),
    "pattern_stream": ("dedupe_only", _STREAM),
    "virtual_stream": ("dedupe_only", {**_STREAM, "device_pair_generation": "on"}),
    "link_and_dedupe": ("link_and_dedupe", {}),
    "link_and_dedupe_stream": ("link_and_dedupe", _STREAM),
}


def _linker(case):
    link_type, over = _CASES[case]
    if link_type == "dedupe_only":
        frames = {"df": _people(420, seed=5)}
    else:
        frames = {"df_l": _people(260, seed=5),
                  "df_r": _people(240, seed=6, first_id=1000)}
    return Splink(_settings(link_type, **over), **frames)


def _frame_the_old_way(linker, frame):
    """``frame`` with every retained column made anew the way it was before
    this mechanism: the table's original values gathered per side into a
    numpy array (objects for strings), typed by ``pd.DataFrame`` from that
    subset. Pair rows come from the frame's own (unique) ids."""
    table = linker._ensure_encoded()
    rows = pd.Index(table.unique_id)
    il = rows.get_indexer(frame["unique_id_l"].to_numpy())
    ir = rows.get_indexer(frame["unique_id_r"].to_numpy())
    assert (il >= 0).all() and (ir >= 0).all()
    retained = {"unique_id": table.unique_id}
    for name in (*_STRINGS[:3], *linker.settings["additional_columns_to_retain"]):
        retained[name] = table.column_values(name)
    if table.source_table is not None and "_source_table_l" in frame:
        retained["_source_table"] = np.array(
            ["left", "right"], dtype=object
        )[table.source_table]
    cols = {}
    for column in frame.columns:
        name, _, side = column.rpartition("_")
        if name in retained and side in ("l", "r"):
            values = retained[name]
            assert isinstance(values, np.ndarray)
            cols[column] = values[il if side == "l" else ir]
        else:
            cols[column] = frame[column].to_numpy()
    return pd.DataFrame(cols)


@pytest.mark.parametrize("case", list(_CASES))
def test_scored_frame_equals_the_object_gather_reference(case):
    linker = _linker(case)
    frame = linker.get_scored_comparisons()
    assert len(frame) > 2048
    reference = _frame_the_old_way(linker, frame)
    pd.testing.assert_frame_equal(frame, reference, check_dtype=True)
    # the traffic is what the case says: nulls on both sides of every string
    # column, every Python type of the mixed column, a string dtype and not
    # `object` for the strings, `object` for the mixed column
    for name in _STRINGS:
        for side in "lr":
            col = frame[f"{name}_{side}"]
            assert col.isna().any() and col.notna().any()
            assert isinstance(col.dtype, pd.StringDtype)
    assert frame["mixed_l"].dtype == object
    assert {type(v) for v in frame["mixed_l"]} == {int, str, float, type(None)}
    assert frame["height_l"].dtype == np.float64
    if linker.settings["link_type"] == "link_and_dedupe":
        assert set(frame["_source_table_l"]) == {"left", "right"}
    # several frames where the case says stream, one where it says resident
    frames = [s for s in spans(run=linker.run_id) if s["name"] == "assemble_frame"]
    assert (len(frames) > 1) == ("stream" in case)
    # the TF call reads the frame's string columns: same frame, same answer
    pd.testing.assert_frame_equal(
        linker.make_term_frequency_adjustments(frame),
        linker.make_term_frequency_adjustments(reference),
    )


def test_frame_column_is_encoded_once_and_numbers_stay_numpy():
    linker = _linker("resident")
    table = linker._ensure_encoded()
    first = table.frame_column("first_name")
    assert table.frame_column("first_name") is first
    assert len(first) == table.n_rows
    assert isinstance(first.dtype, pd.StringDtype)
    for name in ("height", "mixed"):
        assert isinstance(table.frame_column(name), np.ndarray)
    assert table.frame_column("height").dtype == np.float64
    assert table.frame_column("mixed").dtype == object
    uid = table.frame_column("unique_id", lambda: table.unique_id)
    assert uid is table.unique_id  # nothing copied for a numeric column
    # a row window is a new table with its own (empty) cache
    assert "_frame_cache" not in table.slice_rows(0, 10).__dict__
