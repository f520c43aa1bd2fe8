"""Song records of the Leipzig MusicBrainz benchmark's schema, spread over five
sources, with planted duplicates, at an exact size.

The Leipzig entity-resolution benchmark's MusicBrainz set (Saeedi, Peukert and
Rahm, ADBIS 2017) holds song records drawn from MusicBrainz over five sources,
duplicates made by the DAPO generator. The data is not here, so this makes
records of its schema from a seed: ``source_id``, ``title``, ``artist``,
``album``, ``year``, ``number`` (track), ``length`` (m:ss), ``language``, and a
``cluster`` truth column. What the source does not fix is set here, and the
configuration's ``assumed`` lists it:

* **scripts** by language: Latin ASCII 50 %, Latin with diacritics 20 %,
  Cyrillic 10 %, Greek 5 %, Han and kana 15 %, every character in the Basic
  Multilingual Plane. An artist has one language; its albums and songs are
  written in it.
* **lengths** (characters): titles and albums 1-64 with a fifth above 32,
  artists 1-48; no value is ever longer than ``WIDTHS`` gives its field.
* **catalogue**: albums of ``1 + geometric`` songs (mean ``TRACKS``) of one
  artist and one year; song titles drawn Zipf(``title_zipf``) from a pool per
  language whose head is "Intro" and its kin in that language, so that common
  titles make hot blocks as a catalogue has them.
* **duplicates**: half of the songs are in one source, the other half in two
  to five (copies in distinct sources); each copy's title, artist and album
  take a DAPO-like edit each with its field's rate (insert, delete,
  transpose, substitute, truncate), a quarter of substitutions putting the
  NEIGHBOURING code point (+-1) in the character's place, so that a program
  that rounds codes above 256 to bfloat16 reads two such values as equal;
  2 % of the values of every compared field are missing.

The text grows from syllables of each script laid end to end in a corpus a
language, a value being a run of the corpus from a word's start, cut to its
drawn length: everything is made in bulk with numpy, nothing a row in Python.

The benchmark owns this file: the program receives only the frames.
"""

from __future__ import annotations

import numpy as np
import pandas as pd
import pyarrow as pa

SOURCES = 5
FIELDS = ("title", "artist", "album", "year")  # the compared fields
WIDTHS = {"title": 64, "artist": 48, "album": 64}  # the longest value a field holds
TRACKS = 4.0  # mean songs of one album in the set
# language -> (script, share of the artists)
LANGUAGES = {
    "eng": ("latin", 0.50),
    "fra": ("diacritic", 0.07), "deu": ("diacritic", 0.05), "spa": ("diacritic", 0.04),
    "pol": ("diacritic", 0.04),
    "rus": ("cyrillic", 0.10),
    "ell": ("greek", 0.05),
    "zho": ("han", 0.08), "jpn": ("kana", 0.07),
}
_LATIN_C = list("bcdfghjklmnprstvwz") + ["ch", "sh", "th", "st", "br", "tr"]
_LATIN_V = list("aeiou") + ["ee", "oo", "ai", "ou"]
_DIACRITIC_V = list("éèêëàâäáçñöôóòüûúùíîïøåæœłśźżčřšžőű")
_SCRIPTS = {
    "latin": (_LATIN_C, _LATIN_V),
    "diacritic": (_LATIN_C, _DIACRITIC_V + _LATIN_V),
    "cyrillic": (list("бвгджзклмнпрстфхцчшщ"), list("аеёиоуыэюя")),
    "greek": (list("βγδζθκλμνξπρστφχψ"), list("αεηιουωάέήίόύώ")),
}
# the head of every language's title pool: the catalogue's commonest titles
COMMON_TITLES = {
    "latin": ["Intro", "Outro", "Interlude", "Untitled", "Home", "Love", "Reprise"],
    "diacritic": ["Introducción", "Intermède", "Sans titre", "Prélude", "Canción",
                  "Über alles", "Rêverie"],
    "cyrillic": ["Интро", "Аутро", "Интерлюдия", "Без названия", "Дом", "Любовь", "Реприза"],
    "greek": ["Εισαγωγή", "Έξοδος", "Ιντερλούδιο", "Χωρίς τίτλο", "Σπίτι", "Αγάπη", "Επανάληψη"],
    "han": ["序曲", "尾声", "间奏", "无题", "家", "爱", "再现"],
    "kana": ["イントロ", "アウトロ", "インタールード", "無題", "ホーム", "愛", "リプライズ"],
}
# a duplicate's edit rate a field, as a multiple of ``corruption_rate``
EDIT_SCALE = {"title": 1.0, "artist": 0.6, "album": 0.8}
NEIGHBOUR_SHARE = 0.25  # of substitutions: the code point +-1


def _syllables(script: str) -> list[str]:
    if script == "han":  # one character a syllable: CJK Unified Ideographs
        return [chr(c) for c in range(0x4E00, 0x4E00 + 2500)]
    if script == "kana":  # hiragana, katakana and some kanji, as Japanese mixes them
        return ([chr(c) for c in range(0x3041, 0x3094)] + [chr(c) for c in range(0x30A1, 0x30F5)]
                + [chr(c) for c in range(0x5C00, 0x5C00 + 600)])
    cons, vows = _SCRIPTS[script]
    return [c + v for c in cons for v in vows] + vows


def _corpus(rng, script: str, chars: int):
    """(codes uint32, word starts): random words of 1-3 syllables of the
    script laid end to end, a space between words (none in Han and kana,
    where words run on), a fifth of the words capitalised (where the
    script has capitals)."""
    syl = _syllables(script)
    cjk = script in ("han", "kana")
    width = max(map(len, syl))
    table = np.zeros((len(syl), width), np.uint32)  # a syllable's code points
    for k, piece in enumerate(syl):
        table[k, :len(piece)] = [ord(c) for c in piece]
    n_words = chars // (3 if cjk else 6) + 64
    per_word = rng.integers(1, 4, n_words)
    pick = rng.integers(0, len(syl), int(per_word.sum()))
    codes = table[pick]
    codes = codes[codes != 0]  # the picked syllables' code points, in order
    size = np.count_nonzero(table, axis=1)[pick]
    # each word's first code point and its length, then a space after it
    word_end = np.cumsum(size)[np.cumsum(per_word) - 1]
    word_start = np.concatenate([[0], word_end[:-1]])
    if not cjk:
        first = word_start[rng.random(n_words) < 0.2]
        upper = np.array([ord(chr(c).upper()[0]) for c in range(0x500)], np.uint32)
        codes[first] = upper[codes[first]]
        codes = np.insert(codes, word_end, 32)
        word_start = word_start + np.arange(n_words)
    return codes, word_start[word_start < len(codes) - 70]


def _lengths(rng, n: int, longest: int, long_share: float) -> np.ndarray:
    """Drawn lengths in 1..longest: ``long_share`` of them above 32,
    uniformly, the rest 1 + Binomial(31, 0.45)."""
    short = 1 + rng.binomial(31, 0.45, n)
    long = rng.integers(33, longest + 1, n)
    return np.where(rng.random(n) < long_share, long, short)


def _to_strings(mat: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Fixed-width unicode strings of an (n, w) code-point matrix, each cut at
    its length and stripped of trailing spaces (at least one character
    kept)."""
    n, w = mat.shape
    cols = np.arange(w)[None, :]
    mat = np.where(cols < lens[:, None], mat, 0).astype(np.uint32)
    while True:  # strip trailing spaces, a column at a time
        last = mat[np.arange(n), np.maximum(lens - 1, 0)]
        trail = (last == 32) & (lens > 1)
        if not trail.any():
            break
        mat[np.flatnonzero(trail), lens[trail] - 1] = 0
        lens = lens - trail
    return np.ascontiguousarray(mat).view(f"<U{w}").ravel()


def _texts(rng, corpus, n: int, lens: np.ndarray) -> np.ndarray:
    codes, starts = corpus
    first = starts[rng.integers(0, len(starts), n)]
    w = int(lens.max())
    mat = codes[first[:, None] + np.arange(w)[None, :]]
    return _to_strings(mat, lens)


def _zipf(rng, n_pool: int, n: int, exponent: float) -> np.ndarray:
    weights = 1.0 / np.arange(1, n_pool + 1) ** exponent
    return rng.choice(n_pool, n, p=weights / weights.sum())


def _edit(rng, values: np.ndarray, width: int, alphabet: np.ndarray) -> np.ndarray:
    """One DAPO-like edit a value: substitute (a quarter of them by the
    neighbouring code point), delete, insert, transpose, or truncate, at a
    uniform position; never longer than ``width``. ``alphabet``: per value,
    a code point of its script to insert or substitute."""
    n = len(values)
    if n == 0:
        return values
    lens = np.char.str_len(values).astype(np.int64)
    w = max(int(lens.max()) + 1, 2)
    mat = np.zeros((n, w), np.uint32)
    src = values.astype(f"<U{w}").view(np.uint32).reshape(n, w)
    mat[:] = src
    kind = rng.integers(0, 5, n)
    pos = (rng.random(n) * lens).astype(np.int64)
    rows = np.arange(n)
    cols = np.arange(w)[None, :]
    out = mat.copy()
    # substitute
    sub = kind == 0
    here = mat[rows, pos]
    shift = np.where(rng.random(n) < 0.5, 1, -1).astype(np.int64)
    neighbour = (rng.random(n) < NEIGHBOUR_SHARE) & (here > 32)
    new = np.where(neighbour, here.astype(np.int64) + shift, alphabet).astype(np.uint32)
    out[rows[sub], pos[sub]] = new[sub]
    # delete (a value keeps one character)
    dele = (kind == 1) & (lens > 1)
    take = np.where(cols >= pos[:, None], np.minimum(cols + 1, w - 1), cols)
    out[dele] = np.take_along_axis(mat, take, axis=1)[dele]
    lens_out = lens - dele
    # insert
    ins = kind == 2
    take = np.where(cols > pos[:, None], cols - 1, cols)
    moved = np.take_along_axis(mat, take, axis=1)
    moved[rows, pos] = alphabet
    out[ins] = moved[ins]
    lens_out = np.where(ins, np.minimum(lens + 1, width), lens_out)
    # transpose with the next character
    tra = (kind == 3) & (lens > 1)
    p = np.minimum(pos, lens - 2)
    a, b = mat[rows, p], mat[rows, np.maximum(p + 1, 0)]
    out[rows[tra], p[tra]] = b[tra]
    out[rows[tra], p[tra] + 1] = a[tra]
    # truncate: keep the first half to nine tenths
    tru = (kind == 4) & (lens > 2)
    keep = np.maximum((lens * rng.uniform(0.5, 0.9, n)).astype(np.int64), 1)
    lens_out = np.where(tru, keep, lens_out)
    return _to_strings(out, lens_out)


def make_music(
    rows: int,
    title_zipf: float = 0.9,
    duplicate_share: float = 0.5,
    corruption_rate: float = 0.4,
    missing_rate: float = 0.02,
    seed: int = 0,
) -> pd.DataFrame:
    """Exactly ``rows`` song records over ``SOURCES`` sources: songs, half of
    them (``duplicate_share``) also copied into one to four other sources."""
    rng = np.random.default_rng(seed)
    # songs with copies: 1-4 copies each, uniform; the singletons fill the rest
    n_multi = int(round(rows * duplicate_share / (duplicate_share * 3.5 + 1 - duplicate_share)))
    copies = rng.integers(1, SOURCES, n_multi)
    n_single = rows - n_multi - int(copies.sum())
    if n_single < 0:
        raise ValueError("too few rows for the duplicate share")
    n_songs = n_multi + n_single
    copies = np.concatenate([copies, np.zeros(n_single, np.int64)])
    rng.shuffle(copies)

    # the catalogue: artists (one language each), albums, songs
    n_albums = max(1, int(round(n_songs / TRACKS)))
    n_artists = max(1, n_albums // 3)
    langs = list(LANGUAGES)
    shares = np.array([LANGUAGES[k][1] for k in langs])
    artist_lang = rng.choice(len(langs), n_artists, p=shares / shares.sum())
    album_artist = rng.integers(0, n_artists, n_albums)
    album_year = np.clip(2024 - rng.geometric(1 / 18, n_albums), 1950, 2023)
    song_album = np.sort(rng.integers(0, n_albums, n_songs))
    song_artist = album_artist[song_album]
    song_lang = artist_lang[song_artist]

    title = np.empty(n_songs, "<U64")
    artist = np.empty(n_artists, "<U48")
    album = np.empty(n_albums, "<U64")
    scripts = {s for s, _ in LANGUAGES.values()}
    corpora = {s: _corpus(rng, s, max(50_000, 2 * rows if s == "latin" else rows // 2))
               for s in sorted(scripts)}
    album_lang = artist_lang[album_artist]
    for k, lang in enumerate(langs):
        corpus = corpora[LANGUAGES[lang][0]]
        who = np.flatnonzero(artist_lang == k)
        artist[who] = _texts(rng, corpus, len(who), _lengths(rng, len(who), WIDTHS["artist"], 0.08))
        which = np.flatnonzero(album_lang == k)
        album[which] = _texts(rng, corpus, len(which), _lengths(rng, len(which), WIDTHS["album"], 0.2))
        songs = np.flatnonzero(song_lang == k)
        n_pool = max(64, len(songs) // 2)
        pool = _texts(rng, corpus, n_pool, _lengths(rng, n_pool, WIDTHS["title"], 0.25))
        head = COMMON_TITLES[LANGUAGES[lang][0]]
        pool[:len(head)] = head
        title[songs] = pool[_zipf(rng, n_pool, len(songs), title_zipf)]

    # one record a song and a source: the song's own, then its copies
    of = np.repeat(np.arange(n_songs), 1 + copies)
    nth = np.arange(len(of)) - np.repeat(np.cumsum(1 + copies) - (1 + copies), 1 + copies)
    first_source = rng.integers(0, SOURCES, n_songs)
    record = {
        "source_id": ((first_source[of] + nth) % SOURCES).astype(np.int64),
        "title": title[of],
        "artist": artist[song_artist][of],
        "album": album[song_album][of],
        "year": album_year[song_album][of].astype("U4"),
    }
    is_copy = nth > 0
    script_of = np.array([LANGUAGES[k][0] for k in langs])[song_lang[of]]
    alphabet = np.zeros(len(of), np.uint32)
    for script in sorted(scripts):
        here = np.flatnonzero(script_of == script)
        letters = np.array([ord(c) for c in "".join(_syllables(script))], np.uint32)
        alphabet[here] = letters[rng.integers(0, len(letters), len(here))]
    for field, scale in EDIT_SCALE.items():
        hit = np.flatnonzero(is_copy & (rng.random(len(of)) < corruption_rate * scale))
        values = record[field].astype(f"<U{WIDTHS[field]}")
        values[hit] = _edit(rng, values[hit], WIDTHS[field], alphabet[hit])
        record[field] = values
    moved = is_copy & (rng.random(len(of)) < 0.03)  # a reissue's year
    record["year"][moved] = (album_year[song_album][of][moved] + 1).astype("U4")

    track = np.arange(n_songs) - np.searchsorted(song_album, song_album)
    seconds = 120 + rng.gamma(4.0, 30.0, n_songs).astype(np.int64)
    length = np.char.add(np.char.add((seconds // 60).astype("U"), ":"),
                         np.char.zfill((seconds % 60).astype("U"), 2))
    missing = np.random.default_rng(seed + 1).random((len(of), len(FIELDS))) < missing_rate
    order = rng.permutation(len(of))
    out = {"unique_id": np.arange(rows), "source_id": record["source_id"][order]}
    for c, field in enumerate(FIELDS):
        out[field] = pd.array(pa.array(record[field][order], mask=missing[order, c]),
                              dtype="str")
    out["number"] = (track[of] + 1)[order]
    out["length"] = pd.array(pa.array(length[of][order]), dtype="str")
    out["language"] = pd.array(pa.array(np.array(langs)[song_lang[of]][order]), dtype="str")
    out["cluster"] = of[order]
    return pd.DataFrame(out)
