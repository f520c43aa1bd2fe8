"""Retained columns reach the scored frame as columns.

The frame writer (``linker._FrameWriter``) takes each retained column from the array pandas
infers for the WHOLE input column (``EncodedTable.frame_column``: an Arrow
string array for strings where pyarrow is installed) by the pair index. The
reference here is the plain way it was done before: one object gather per
side from the table's original values, then ``pd.DataFrame`` — the frame
must equal it value for value and dtype for dtype.
"""

import warnings

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


# ----------------------------------------------------------------------
# every column of the frame is written once (ROADMAP A1b)
# ----------------------------------------------------------------------
#
# The reference is the way the frame was made before the column writer: a
# dict of fresh arrays a chunk (``PM[Pk]``, ``values[il]``,
# ``G[:, c].astype(int64)``), ``pd.DataFrame`` of it, ``pd.concat`` of the
# chunks. The one-frame result must equal it value for value, dtype for
# dtype, column for column.

_VIRTUAL = {**_STREAM, "device_pair_generation": "on"}
_TWO_RULES = {"blocking_rules": ["l.dob = r.dob", "l.surname = r.surname"]}
# name -> (link type, settings over _settings', how the job is asked for)
_ONE_FRAME = {
    # several chunks a rule, a later rule's positions masked by the first
    "virtual_kept_ids": ("dedupe_only",
                         {**_VIRTUAL, **_TWO_RULES, "virtual_materialise_ids": "on"}),
    "virtual_recompute": ("dedupe_only",
                          {**_VIRTUAL, **_TWO_RULES, "virtual_materialise_ids": "off"}),
    "materialised_patterns": ("dedupe_only", {**_STREAM, **_TWO_RULES}),
    "link_strings": ("link_only", {}),
    "link_strings_no_pyarrow": ("link_only", {}),
    "link_and_dedupe": ("link_and_dedupe", {}),
    "link_and_dedupe_virtual": ("link_and_dedupe", {**_VIRTUAL, **_TWO_RULES}),
    # the default retains the per-column probabilities
    "no_intermediates_resident": ("link_only",
                                  {"retain_intermediate_calculation_columns": False}),
    "no_intermediates_virtual": ("dedupe_only",
                                 {**_VIRTUAL, **_TWO_RULES,
                                  "retain_intermediate_calculation_columns": False}),
    "no_tf_fold_column": ("dedupe_only", {**_VIRTUAL, "serve_tf_adjust": False}),
    # the config-4 cells' frame: ids, levels and one probability
    "nothing_retained": ("dedupe_only",
                         {**_VIRTUAL, "retain_matching_columns": False,
                          "comparison_columns": [
                              {"col_name": name, "num_levels": 2}
                              for name in _STRINGS[:3]
                          ],
                          "retain_intermediate_calculation_columns": False,
                          "additional_columns_to_retain": [],
                          "serve_tf_adjust": False}),
    # no histogram pass: the plan's positions only bound the frame's length
    "manual_weights_virtual": ("dedupe_only", {**_VIRTUAL, **_TWO_RULES}),
    "manual_weights_resident": ("link_only", {}),
    "zero_pairs_virtual": ("dedupe_only",
                           {**_VIRTUAL, "blocking_rules": ["l.unique_id = r.unique_id"]}),
    "zero_pairs_resident": ("link_only",
                            {"blocking_rules": ["l.unique_id = r.unique_id"]}),
    "every_position_masked": (
        "dedupe_only",
        {**_VIRTUAL, "blocking_rules": ["l.dob = r.dob and l.height > r.height + 1e6"]},
    ),
}


def _one_frame_linker(case):
    link_type, over = _ONE_FRAME[case]
    if link_type == "dedupe_only":
        frames = {"df": _people(420, seed=5)}
    else:
        frames = {"df_l": _people(260, seed=5),
                  "df_r": _people(240, seed=6, first_id=1000)}
    return Splink(_settings(link_type, **over), **frames)


def _columns_the_parents_way(linker, G, il, ir, p, prob_m, prob_u, z):
    """The parent commit's ``_assemble_columns``: a fresh array a column."""
    from splink_tpu.settings import comparison_column_name

    table = linker._ensure_encoded()
    settings = linker.settings
    cols = {"match_probability": p}
    ctx = linker._tf_fold_ctx()
    if ctx is not None:
        cols["tf_match_probability"] = (
            linker._tf_fold_pairs(z, il, ir, ctx)
            if z is not None and len(p)
            else np.zeros(len(p), linker._float_dtype)
        )

    def add_lr(name, make=None):
        values = table.frame_column(name, make)
        if isinstance(values, np.ndarray):
            left, right = values[il], values[ir]
        else:
            left, right = values.take(il), values.take(ir)
        cols.setdefault(f"{name}_l", left)
        cols.setdefault(f"{name}_r", right)

    add_lr(settings["unique_id_column_name"], lambda: table.unique_id)
    for c, col in enumerate(settings["comparison_columns"]):
        name = comparison_column_name(col)
        if settings["retain_matching_columns"] or col["term_frequency_adjustments"]:
            add_lr(name)
        cols[f"gamma_{name}"] = G[:, c].astype(np.int64)
        if settings["retain_intermediate_calculation_columns"]:
            cols[f"prob_gamma_{name}_non_match"] = prob_u[:, c]
            cols[f"prob_gamma_{name}_match"] = prob_m[:, c]
    if settings["link_type"] == "link_and_dedupe":
        add_lr(
            "_source_table",
            lambda: np.array(["left", "right"], dtype=object)[table.source_table],
        )
    for extra in settings["additional_columns_to_retain"]:
        add_lr(extra)
    return cols


def _frame_the_parents_way(linker):
    """The linker's CURRENT parameters scored the way the parent commit made
    its one frame: per chunk the gathered arrays, ``pd.DataFrame`` (which
    consolidates and copies), then ``pd.concat``."""
    import jax.numpy as jnp

    from splink_tpu.models.fellegi_sunter import FSParams

    def frame(*arrays):
        return pd.DataFrame(_columns_the_parents_way(linker, *arrays))

    def take(lut, Pk):
        return None if lut is None else lut[Pk]

    if linker._regime("patterns"):
        PM, *luts = linker._pattern_score_luts()
        chunks = [
            frame(PM[Pk], il, ir, *(take(lut, Pk) for lut in luts))
            for il, ir, Pk in linker._iter_pattern_triples()
        ]
        if chunks:
            return pd.concat(chunks, ignore_index=True)
        n_cols = len(linker.settings["comparison_columns"])
        zero = np.zeros((0, n_cols), linker._float_dtype)
        none = np.zeros(0, np.int64)
        return frame(np.zeros((0, n_cols), np.int8), none, none, zero[:, 0], zero, zero, None)
    G = linker._ensure_gammas()
    pairs = linker._ensure_pairs()
    lam, m, u, _ = linker.params.to_arrays(dtype=linker._float_dtype)
    params = FSParams(lam=jnp.asarray(lam), m=jnp.asarray(m), u=jnp.asarray(u))
    scored = linker._score_batched(
        G, params, want_z=linker._tf_fold_ctx() is not None
    )
    return frame(G, pairs.idx_l, pairs.idx_r, *scored)


def _storage(case):
    """pandas without pyarrow keeps strings as Python objects: the option
    makes it infer that storage here, where pyarrow is installed."""
    return pd.option_context(
        "mode.string_storage", "python" if "no_pyarrow" in case else "auto"
    )


def _one_frame(linker, case):
    if case.startswith("manual_weights"):
        assert linker._pattern_counts is None  # no histogram pass ran
        return linker.manually_apply_fellegi_sunter_weights()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # "no candidate pairs" where none are due
        return linker.get_scored_comparisons()


@pytest.mark.parametrize("case", list(_ONE_FRAME))
def test_one_frame_equals_the_per_chunk_frames_concatenated(case):
    with _storage(case):
        linker = _one_frame_linker(case)
        frame = _one_frame(linker, case)
        reference = _frame_the_parents_way(linker)
    pd.testing.assert_frame_equal(frame, reference, check_dtype=True, check_exact=True)
    assert list(frame.columns) == list(reference.columns)
    assert list(frame.dtypes) == list(reference.dtypes)
    assert isinstance(frame.index, pd.RangeIndex)
    # the case is what its name says
    empty = case.startswith("zero_pairs") or case == "every_position_masked"
    assert (len(frame) == 0) == empty
    table = [s for s in spans(run=linker.run_id)]
    chunks = [s["counts"] for s in table if s["name"] == "assemble_frame"]
    if linker._regime("patterns"):
        assert len(chunks) > 2 or empty
        if case == "every_position_masked":
            assert linker._virtual.n_candidates > 0
        if "virtual" in case and not empty:
            assert len(frame) < linker._virtual.n_candidates  # some masked
    else:
        assert len(chunks) == 1
    assert sum(c["rows"] for c in chunks) == len(frame)
    assert all(c["in_place_rows"] == c["rows"] for c in chunks)
    assert ("tf_match_probability" in frame) == (
        "no_tf" not in case and case != "nothing_retained"
    )
    assert ("prob_gamma_city_match" in frame) == (
        "no_intermediates" not in case and case != "nothing_retained"
    )
    assert ("_source_table_l" in frame) == ("link_and_dedupe" in case)
    if case == "nothing_retained":
        assert list(frame.columns) == [
            "match_probability", "unique_id_l", "unique_id_r",
            "gamma_first_name", "gamma_surname", "gamma_city",
        ]
    else:
        storage = "python" if "no_pyarrow" in case else "pyarrow"
        assert frame["first_name_l"].dtype.storage == storage
    # one block a column: pandas was handed the columns and copied nothing
    assert frame._mgr.nblocks == len(frame.columns)


@pytest.mark.parametrize("case", ["virtual_kept_ids", "materialised_patterns",
                                  "link_strings", "link_and_dedupe_virtual"])
def test_streamed_chunks_concatenated_equal_the_one_frame(case):
    linker = _one_frame_linker(case)
    frame = linker.get_scored_comparisons()
    chunks = list(linker.stream_scored_comparisons_after_em())
    if linker._regime("patterns"):
        assert len(chunks) > 2
    pd.testing.assert_frame_equal(
        pd.concat(chunks, ignore_index=True), frame, check_exact=True
    )
    for chunk in chunks:
        assert list(chunk.dtypes) == list(frame.dtypes)


@pytest.mark.parametrize("case", ["virtual_kept_ids", "link_strings",
                                  "nothing_retained"])
def test_the_frame_owns_its_memory(case):
    """Scribbling over every column of a returned frame changes neither the
    next frame of the same linker nor the table's ``frame_column`` cache nor
    the table itself: no column is a view of anything the linker keeps."""
    linker = _one_frame_linker(case)
    table = linker._ensure_encoded()
    first = linker.get_scored_comparisons()
    kept = linker.manually_apply_fellegi_sunter_weights()
    want = kept.copy(deep=True)
    cache = {k: (v.copy() if isinstance(v, np.ndarray) else v[:].copy())
             for k, v in table._frame_cache.items()}
    ids = table.unique_id.copy()
    for frame in (first, kept):
        for name in frame.columns:
            values = frame[name].to_numpy()
            for held in (*table._frame_cache.values(), table.unique_id):
                if isinstance(held, np.ndarray) and held.dtype == values.dtype:
                    assert not np.shares_memory(values, held), name
            if isinstance(frame[name].dtype, pd.StringDtype):
                frame.loc[:, name] = "scribble"
            elif values.dtype == object:
                frame.loc[:, name] = None
            else:
                values.flags.writeable = True  # pandas hands out a locked view
                values[:] = 7
                assert (frame[name] == 7).all()  # it WAS the frame's memory
    again = linker.manually_apply_fellegi_sunter_weights()
    pd.testing.assert_frame_equal(again, want, check_exact=True)
    np.testing.assert_array_equal(table.unique_id, ids)
    for name, held in table._frame_cache.items():
        if isinstance(held, np.ndarray):
            np.testing.assert_array_equal(held, cache[name])
        else:
            assert held.equals(cache[name])


def test_the_writer_refuses_what_numpy_indexing_would_have_refused():
    """The takes write unbuffered (``mode="clip"``), so the writer checks a
    chunk's indices itself, once a chunk: a row, or a pattern id, out of
    range raises as ``values[idx]`` did, and so does a stream longer than
    the frame was allocated for; nothing of such a chunk counts as written."""
    from splink_tpu.linker import _FrameWriter

    linker = _one_frame_linker("nothing_retained")
    rows = linker._ensure_encoded().n_rows
    tables = linker._pattern_frame_tables()
    ok = np.arange(8, dtype=np.int32)
    writer = _FrameWriter(linker, 20)
    writer.write(ok, ok, *tables, by=ok)
    for il, ir, by in (
        (ok + rows - 7, ok, ok), (ok, ok - 1, ok), (ok, ok, ok + len(tables[1])),
    ):
        with pytest.raises(IndexError):
            writer.write(il, ir, *tables, by=by)
    assert writer.rows == 8
    writer.write(ok, ok, *tables, by=ok)
    with pytest.raises(ValueError, match="more than the 20 pairs"):
        writer.write(ok, ok, *tables, by=ok)
    frame = writer.frame()
    assert len(frame) == 16
    assert list(frame["unique_id_l"]) == 2 * list(linker._ensure_encoded().unique_id[:8])


def test_takes_a_block_of_rows_at_a_time_write_the_same_frame(monkeypatch):
    """The takes run a block of rows at a time (numpy's index conversion
    stays cache-sized): with blocks shorter than a chunk — and not dividing
    it — the frame is the frame."""
    import splink_tpu.linker as linker_module

    want = _one_frame_linker("virtual_kept_ids").get_scored_comparisons()
    monkeypatch.setattr(linker_module, "_TAKE_ROWS", 100)
    got = _one_frame_linker("virtual_kept_ids").get_scored_comparisons()
    assert len(got) > 10 * 1024  # chunks of 1024 rows, eleven blocks each
    pd.testing.assert_frame_equal(got, want, check_exact=True)


# ----------------------------------------------------------------------
# the columns filled by a pool of host threads (ROADMAP A1c, step 1)
# ----------------------------------------------------------------------
#
# A chunk of ``_POOL_ROWS`` rows or more is filled by a pool; below it by
# the driver alone. The module constant is set to 0 (every chunk pooled)
# and to above any frame (every chunk serial); ``_TAKE_ROWS`` is cut and
# ``_SPAN_BLOCKS`` set to 3 so that the numeric columns split into several
# row spans of several blocks at this size.

# name -> (the _ONE_FRAME case, how the frames are asked for, _TAKE_ROWS)
_POOLED = {
    "virtual_by_pattern": ("virtual_kept_ids", "frame", 64),
    "resident_tf_fold_strings": ("link_strings", "frame", 64),
    "stream_chunks": ("materialised_patterns", "stream", 64),
    "zero_rows": ("zero_pairs_resident", "frame", 64),
    # blocks of 100 rows, spans of 300: no chunk a multiple of either
    "rows_off_the_blocks": ("virtual_recompute", "frame", 100),
}


def _frames(linker, how):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # "no candidate pairs" where none are due
        if how == "stream":
            return list(linker.stream_scored_comparisons_after_em())
        return [linker.manually_apply_fellegi_sunter_weights()]


def _arrow_chunks(column):
    pa_array = getattr(column.array, "_pa_array", None)
    return None if pa_array is None else pa_array.num_chunks


@pytest.mark.parametrize("case", list(_POOLED))
def test_pooled_fill_writes_the_serial_fill_bit_for_bit(case, monkeypatch):
    import splink_tpu.linker as linker_module

    one_frame, how, take_rows = _POOLED[case]
    linker = _one_frame_linker(one_frame)
    _one_frame(linker, one_frame)  # EM, or the weights the case applies
    monkeypatch.setattr(linker_module, "_TAKE_ROWS", take_rows)
    monkeypatch.setattr(linker_module, "_SPAN_BLOCKS", 3)
    monkeypatch.setattr(linker_module, "_POOL_ROWS", 1 << 62)
    serial = _frames(linker, how)
    monkeypatch.setattr(linker_module, "_POOL_ROWS", 0)
    before = len(spans(run=linker.run_id))
    pooled = _frames(linker, how)
    assert len(pooled) == len(serial) >= (3 if how == "stream" else 1)
    for got, want in zip(pooled, serial):
        assert got.equals(want)
        assert list(got.columns) == list(want.columns)
        assert list(got.dtypes) == list(want.dtypes)
        for name in want.columns:
            a, b = got[name].array, want[name].array
            assert type(a) is type(b), name
            assert _arrow_chunks(got[name]) == _arrow_chunks(want[name]), name
            if b.dtype.kind in "fiu":  # the same bits, NaN payloads and all
                assert got[name].to_numpy().tobytes() == want[name].to_numpy().tobytes()
    # the case is what its name says, and the pool did the writing
    frames = [s["counts"] for s in spans(run=linker.run_id)[before:]
              if s["name"] == "assemble_frame"]
    assert frames and all(c["pooled_rows"] == c["rows"] for c in frames)
    assert (sum(c["rows"] for c in frames) == 0) == (case == "zero_rows")
    if case == "resident_tf_fold_strings":
        [frame] = pooled
        assert "tf_match_probability" in frame and frame["height_l"].dtype == np.float64
        assert _arrow_chunks(frame["first_name_l"]) == 1
    if how == "stream" or case == "virtual_by_pattern":
        assert len(frames) > 2
    if case == "rows_off_the_blocks":
        assert any(c["rows"] % 100 and c["rows"] > 300 for c in frames)
