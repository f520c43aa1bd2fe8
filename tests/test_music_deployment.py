"""Multilingual song records from five sources, against the plain reference.

The deployment ``chipbench/configs/musicbrainz_songs.json`` (the cell
``mb_songs_dedupe_unicode``): title and artist by Jaro-Winkler and album by
the Levenshtein ratio, every one a column of Unicode code points at widths 48
to 64, year by the default 2-level comparison. At 2,000 of the generator's
records on the CPU, through the facade's normal path, the job has to give the
pair set and every gamma level of ``chipbench.reference_unicode`` exactly and
λ/m/u and every score within the configuration's limits; the bfloat16 control
has to fail them. Held beside it: the spans' ``wide_columns`` and
``string_evals_vector`` (every evaluation a vector form off the chip, none on
it at these widths); the vectorised wide encode against the per-string loop
it replaced, bit for bit; what the program does with a character above
U+FFFF (one code point, where the reference library's Java strings see two
UTF-16 units); the runner's two guards; the reference's own pieces.
"""

import copy
import json
import os
import sys

import numpy as np
import pandas as pd
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import correct_case_library as correct  # noqa: E402
from chipbench import reference, reference_unicode, string_bytes  # noqa: E402
from chipbench.datagen_music import FIELDS, SOURCES, WIDTHS, make_music  # noqa: E402
from splink_tpu import Splink, gammas  # noqa: E402
from splink_tpu.utils.profiling import spans  # noqa: E402

ROWS = 2000


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "musicbrainz_songs.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def songs(config):
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "population_seed", "rows")}
    df = make_music(rows=ROWS, seed=config["generator"]["population_seed"], **gen)
    # as the runner hands them over: another order, the ids renumbered
    df = df.iloc[np.random.default_rng(5).permutation(ROWS)].reset_index(drop=True)
    return df.assign(unique_id=np.arange(ROWS))


@pytest.fixture(scope="module")
def job(config, songs):
    """(linker, frame, span table) of one whole job at a tier-1 batch."""
    settings = copy.deepcopy(config["settings"])
    settings.update(pair_batch_size=1024)
    linker = Splink(settings, df=songs)
    frame = linker.get_scored_comparisons()
    return linker, frame, spans(run=linker.run_id)


@pytest.fixture(scope="module")
def small_blocks():
    # the reference's device blocks at a tier-1 size (its numbers do not
    # depend on the block)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(reference, "BLOCK", 1 << 12)
        mp.setattr(reference_unicode, "BLOCK", 1 << 12)
        yield


@pytest.fixture(scope="module")
def prep(config, songs, small_blocks):
    return reference_unicode.prepare(config["settings"], {"df": songs})


def test_the_generator_gives_the_song_schema_at_an_exact_size(songs):
    assert list(songs.columns) == ["unique_id", "source_id", *FIELDS, "number", "length",
                                   "language", "cluster"]
    assert len(songs) == ROWS and set(songs.source_id) == set(range(SOURCES))
    # a song's copies lie in distinct sources; half the songs have copies
    per_song = songs.groupby("cluster").source_id
    assert (per_song.nunique() == per_song.size()).all()
    assert 0.4 < (per_song.size() > 1).mean() < 0.6
    assert per_song.size().max() <= SOURCES
    for field, width in WIDTHS.items():
        values = songs[field].dropna()
        assert values.str.len().max() <= width  # nothing the program truncates
        assert 0.4 < (~values.map(str.isascii)).mean() < 0.65
        assert max(max(map(ord, v)) for v in values) < 0xD800  # the BMP, no surrogates
        assert 0.005 < songs[field].isna().mean() < 0.04
    assert (songs.title.dropna().str.len() > 32).mean() > 0.1


def test_the_job_meets_the_code_point_reference(config, job, prep):
    linker, frame, _ = job
    produced = {"frame": frame, "tf_frame": None, "params": linker.params.params,
                "digests": [(0,)], "uid": "unique_id"}
    numbers = correct.compare(produced, prep)
    ok, rows = correct.verdict(numbers, config["limits"])
    assert ok, rows
    assert numbers["pairs_wrong"] == 0 and numbers["gamma_wrong"] == 0
    assert numbers["lev_tie_flips"] == 0


def test_the_bfloat16_control_fails(config, songs, prep, small_blocks):
    control = reference_unicode.run(config["settings"], {"df": songs}, precision="bfloat16")
    ok, rows = correct.verdict(correct.compare(correct.stand_in(control), prep),
                               config["limits"])
    assert not ok, rows


def test_the_spans_count_wide_columns_and_vector_evaluations(job):
    linker, frame, table = job
    [encode] = [s for s in table if s["name"] == "encode"]
    assert encode["counts"]["wide_columns"] == 3  # title, artist, album; year is ASCII
    [stage] = [s for s in table if s["name"] == "gammas"]
    # off the chip no string kernel is Pallas: every evaluation a vector form
    assert stage["counts"]["string_evals_vector"] == stage["counts"]["string_evals"]
    assert stage["counts"]["string_evals"] == 4 * len(frame)
    program = linker._ensure_pattern_program()
    assert {f.width for f in program._layout.values()} <= {8, 16, 24, 32, 40, 48, 56, 64}
    on_chip = program.kernel_counts(len(frame), backend="tpu")
    assert on_chip["string_evals_vector"] == 0
    assert on_chip["string_evals"] == stage["counts"]["string_evals"]


@pytest.mark.parametrize("kind,width,vector", [
    ("jaro_winkler", 64, 0), ("levenshtein", 64, 0), ("jaro_winkler", 72, 1),
    ("qgram_jaccard", 16, 1), ("exact", 72, 0)])
def test_a_column_past_the_kernels_width_counts_as_vector(kind, width, vector):
    layout = {"c": gammas._StringField("wide", width, slice(0, width), width, width + 1)}
    col = {"col_name": "c", "num_levels": 3, "comparison": {"kind": kind}}
    assert gammas._vector_string_evals(col, layout, "tpu") == vector
    assert gammas._vector_string_evals(col, layout, "cpu") == gammas._string_evals(
        col["comparison"])


def _loop_encode(ustrs, width):
    """The per-string loop the wide branch of ``encode_string_column`` ran
    before it was vectorised."""
    ubytes = np.zeros((len(ustrs), width), dtype=np.uint32)
    ulengths = np.zeros(len(ustrs), dtype=np.int32)
    for i, v in enumerate(ustrs):
        if not v:
            continue
        chars = v[:width]
        ubytes[i, : len(chars)] = np.array([ord(c) for c in chars], dtype=np.uint32)
        ulengths[i] = len(chars)
    return ubytes, ulengths


@pytest.mark.parametrize("values,width", [
    (["Intro", "Интро", "Εισαγωγή", "序曲", "イントロ", "crème brûlée", None, ""], 24),
    (["a\U0001F600b", "\U00020000" * 3, "x", "ok\x00", "é" * 70, "z" * 8], 64),
    (["ж" * 40, "ab", None, "ĳ"], 8),
])
def test_the_wide_encode_is_the_old_loop_bit_for_bit(values, width):
    from splink_tpu.data import _pad_width, encode_string_column

    col = encode_string_column(pd.Series(values, dtype=object), width)
    present = [v for v in values if v is not None]
    uniques = list(dict.fromkeys(present))
    w = min(_pad_width(max(len(v) for v in uniques)), _pad_width(width))
    want_bytes, want_lens = _loop_encode(uniques, w)
    row = {v: i for i, v in enumerate(uniques)}
    for i, v in enumerate(values):
        if v is None:
            assert col.null_mask[i] and col.lengths[i] == 0 and not col.bytes_[i].any()
            continue
        assert col.bytes_.dtype == np.uint32 and col.width == w
        np.testing.assert_array_equal(col.bytes_[i], want_bytes[row[v]])
        assert col.lengths[i] == want_lens[row[v]] and col.lengths.dtype == np.int32


def test_an_astral_character_is_one_code_point():
    """The departure from the reference library, pinned: a character above
    U+FFFF is ONE code point here (lengths, window and matches count it
    once); Java's UTF-16 strings, which the reference's UDF reads, hold it as
    a surrogate pair. In the Basic Multilingual Plane the two agree."""
    import jax.numpy as jnp

    from splink_tpu.data import encode_string_column
    from splink_tpu.ops import strings as string_ops

    from conftest import py_jaro_winkler

    a, b = "\U0001F3B5ab", "\U0001F3B5ac"
    col = encode_string_column(pd.Series([a, b]), 8)
    assert col.lengths.tolist() == [3, 3] and col.bytes_[0, 0] == 0x1F3B5
    sim = float(string_ops.jaro_winkler(
        jnp.asarray(col.bytes_[:1]), jnp.asarray(col.bytes_[1:]),
        jnp.asarray(col.lengths[:1]), jnp.asarray(col.lengths[1:]))[0])
    assert sim == pytest.approx(py_jaro_winkler(a, b), abs=1e-6)

    def utf16(s):
        raw = s.encode("utf-16-le")
        return "".join(chr(int.from_bytes(raw[i:i + 2], "little")) for i in range(0, len(raw), 2))

    assert len(utf16(a)) == 4  # a surrogate pair and two letters
    assert py_jaro_winkler(utf16(a), utf16(b)) != pytest.approx(sim, abs=1e-6)
    # the Basic Multilingual Plane reads the same either way
    assert utf16("Интро") == "Интро"


def test_the_runners_guards(config, songs, monkeypatch):
    import jax

    from chipbench.runners import job_music

    settings = config["settings"]
    with pytest.raises(SystemExit, match="vector forms"):
        job_music.require_pallas_forms(settings)  # no Pallas kernel off the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    job_music.require_pallas_forms(settings)
    wider = copy.deepcopy(settings)
    wider["comparison_columns"][0]["max_string_length"] = 72
    with pytest.raises(SystemExit, match="'title'"):
        job_music.require_pallas_forms(wider)
    job_music.require_untruncated(settings, songs)
    with pytest.raises(SystemExit, match="longer than its max_string_length"):
        job_music.require_untruncated(settings, songs.assign(artist="x" * 49))


def test_the_references_pieces():
    """Its Jaro-Winkler formula on given codes is ``reference._similarity``'s
    table at the same codes; its rolled edit distance is the textbook's."""
    import jax.numpy as jnp

    from conftest import py_levenshtein

    table, _ = reference._similarity("jaro_winkler", 8, np.float64)
    codes = np.arange(len(table))
    got, _ = reference_unicode.jw_similarity(codes, 8, np.float64)
    np.testing.assert_array_equal(got, table)

    rng = np.random.default_rng(48)
    words = ["".join(chr(c) for c in rng.integers(0x4E00, 0x4E04, rng.integers(0, 17)))
             for _ in range(40)]
    mat, length, _ = reference_unicode.encode(pd.Series(words))
    il, ir = rng.integers(0, 40, 60), rng.integers(0, 40, 60)
    got = reference_unicode._edit_distance(
        jnp.asarray(mat[il]), jnp.asarray(mat[ir]), jnp.asarray(length[il]),
        jnp.asarray(length[ir]))
    np.testing.assert_array_equal(got, [py_levenshtein(words[i], words[j])
                                        for i, j in zip(il, ir)])


def test_the_string_bytes_follow_the_widths(songs, config):
    evals = string_bytes.evaluations(config["settings"], songs)
    assert [k for k, _ in evals] == ["jaro_winkler", "jaro_winkler", "levenshtein",
                                     "jaro_winkler"]
    assert evals[-1] == ("jaro_winkler", 8)  # four-digit years
    assert string_bytes.kernel_bytes("jaro_winkler", 64) == 4 * (2 * 64 + 3)
    assert string_bytes.kernel_bytes("levenshtein", 64) == 4 * (2 * 64 + 4)
    assert string_bytes.bytes_per_pair(evals) == sum(
        string_bytes.kernel_bytes(k, w) for k, w in evals)
