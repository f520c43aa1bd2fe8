"""FEBRL-style synthetic person data with planted duplicates, at an exact size.

A copy of ``benchmarks/datagen.py`` (same name pools, same Zipf skew, same
corruption kinds and rates, same ``cluster`` truth column) with its three
Python loops replaced by numpy byte-matrix arithmetic, and with the frame
built to EXACTLY ``rows`` rows for every seed: the original draws one
Bernoulli per base row, so its row count (and with it every compiled table
shape) moves with the seed. Here ``n_dup = rows - n_base`` base rows are
drawn without replacement, which keeps the duplicate share at
``duplicate_rate / (1 + duplicate_rate)`` exactly.

The benchmark owns this file: the program receives only the frames.
"""

from __future__ import annotations

import itertools

import numpy as np
import pandas as pd
import pyarrow as pa

FIRSTS = [
    "amelia", "oliver", "isla", "george", "ava", "noah", "emily", "arthur",
    "sophia", "lily", "freya", "leo", "ivy", "oscar", "grace", "archie",
    "willow", "jack", "rosie", "harry", "mia", "charlie", "ella", "jacob",
    "evie", "thomas", "poppy", "oscar", "ruby", "william", "harriet", "james",
]
LASTS = [
    "smith", "jones", "taylor", "brown", "wilson", "evans", "thomas",
    "roberts", "johnson", "lewis", "walker", "robinson", "wood", "thompson",
    "white", "watson", "jackson", "wright", "green", "harris", "cooper",
    "king", "lee", "martin", "clarke", "james", "morgan", "hughes", "edwards",
    "hill", "moore", "clark",
]
CITIES = [
    "leeds", "york", "hull", "bath", "derby", "poole", "truro", "ely",
    "ripon", "wells", "oxford", "exeter", "durham", "lincoln", "chester",
    "salford", "preston", "lancaster",
]

_SYL1 = ["al", "be", "ca", "do", "el", "fa", "ga", "ha", "jo", "ka", "li",
         "ma", "ni", "or", "pa", "ro", "sa", "ta", "vi", "wi"]
_SYL2 = ["bert", "dan", "fred", "lia", "line", "mund", "nard", "rick", "son",
         "ton", "vin", "wyn", "na", "ra", "la", "den", "ley", "more", "ser", "ver"]


def name_pool(rng, base: list[str], size: int):
    """``size`` DISTINCT names grown from a real-name seed list by syllable
    products, shuffled, with Zipf(0.8) weights — the original's pool."""
    pool = set(base)
    for n_syl in (2, 3, 4, 5):
        if len(pool) >= size:
            break
        parts = [_SYL1] + [_SYL2] * (n_syl - 1)
        for combo in itertools.product(*parts):
            pool.add("".join(combo))
            if len(pool) >= size:
                break
    pool = np.array(sorted(pool))
    rng.shuffle(pool)
    weights = 1.0 / np.arange(1, len(pool) + 1) ** 0.8
    return pool, weights / weights.sum()


def _to_bytes(words: np.ndarray, width: int) -> tuple[np.ndarray, np.ndarray]:
    """(n, width) uint8 matrix (zero padded) and (n,) lengths of ASCII words."""
    raw = np.asarray(words).astype(f"S{width}")
    mat = raw.view(np.uint8).reshape(len(raw), width).copy()
    return mat, (mat != 0).sum(axis=1).astype(np.int64)


def _to_str(mat: np.ndarray) -> np.ndarray:
    width = mat.shape[1]
    return np.ascontiguousarray(mat).view(f"S{width}").ravel().astype(f"U{width}")


def typo(rng, words: np.ndarray) -> np.ndarray:
    """One random edit per word (substitute, transpose, delete or insert at a
    random position before the last character), words shorter than two
    characters unchanged: the original's ``_typo``, over a byte matrix."""
    if len(words) == 0:
        return words
    width = max(int(np.char.str_len(words).max()), 1) + 1
    mat, length = _to_bytes(words, width)
    n = len(mat)
    kind = rng.integers(0, 4, n)
    pos = (rng.random(n) * np.maximum(length - 1, 1)).astype(np.int64)
    letter = (97 + rng.integers(0, 26, n)).astype(np.uint8)
    col = np.broadcast_to(np.arange(width), (n, width))
    i = pos[:, None]
    # source column of every output column, per kind
    src_sub = col
    src_swap = np.where(col == i, i + 1, np.where(col == i + 1, i, col))
    src_del = np.where(col < i, col, np.minimum(col + 1, width - 1))
    src_ins = np.where(col <= i, col, col - 1)
    k = kind[:, None]
    src = np.where(k == 0, src_sub, np.where(k == 1, src_swap,
                   np.where(k == 2, src_del, src_ins)))
    out = np.take_along_axis(mat, src, axis=1)
    put = ((k == 0) | (k == 3)) & (col == i)
    out = np.where(put, letter[:, None], out)
    out = np.where((length < 2)[:, None], mat, out)
    return _to_str(out)


def make_people(
    rows: int,
    duplicate_rate: float = 0.3,
    corruption_rate: float = 0.4,
    missing_rate: float = 0.02,
    seed: int = 0,
) -> pd.DataFrame:
    """Exactly ``rows`` rows: ``n_base`` people plus ``rows - n_base``
    corrupted duplicates, with a ``cluster`` truth id."""
    rng = np.random.default_rng(seed)
    n_base = int(round(rows / (1.0 + duplicate_rate)))
    n_dup = rows - n_base
    dup_of = np.sort(rng.choice(n_base, n_dup, replace=False))

    f_pool, f_w = name_pool(rng, FIRSTS, max(64, min(n_base // 20, 200_000)))
    l_pool, l_w = name_pool(rng, LASTS, max(64, min(n_base // 10, 500_000)))
    firsts = f_pool[rng.choice(len(f_pool), n_base, p=f_w)]
    lasts = l_pool[rng.choice(len(l_pool), n_base, p=l_w)]

    year = rng.integers(1930, 2005, n_base)
    month = rng.integers(1, 13, n_base)
    day = rng.integers(1, 29, n_base)
    dob = np.full((n_base, 10), ord("-"), np.uint8)
    for c, (v, p) in enumerate([(year, 1000), (year, 100), (year, 10), (year, 1)]):
        dob[:, c] = 48 + (v // p) % 10
    for c, v in ((5, month), (8, day)):
        dob[:, c] = 48 + v // 10
        dob[:, c + 1] = 48 + v % 10

    city_idx = rng.integers(0, len(CITIES), n_base)
    cities = np.array(CITIES)[city_idx]
    n_post = max(30, n_base // 2000)
    prefix = np.array([c[:2].upper() for c in CITIES])[city_idx]
    postcodes = np.char.add(prefix, rng.integers(1, n_post, n_base).astype("U"))

    # duplicates with corruption (fixed-width unicode arrays throughout: an
    # object array of 600k Python strings costs seconds to build and to hand
    # to pandas)
    wide = f"U{max(firsts.dtype.itemsize, lasts.dtype.itemsize) // 4 + 1}"
    d_first, d_last = firsts[dup_of].astype(wide), lasts[dup_of].astype(wide)
    hit = rng.random(n_dup) < corruption_rate
    d_first[hit] = typo(rng, d_first[hit])
    hit = rng.random(n_dup) < corruption_rate * 0.6
    d_last[hit] = typo(rng, d_last[hit])
    swap = rng.random(n_dup) < 0.1  # name inversion
    d_first[swap], d_last[swap] = d_last[swap], d_first[swap]
    d_dob = dob[dup_of].copy()
    flip = rng.random(n_dup) < 0.05  # dob day/month swap
    d_dob[np.ix_(flip, [5, 6, 8, 9])] = d_dob[np.ix_(flip, [8, 9, 5, 6])]

    missing = np.random.default_rng(seed + 1).random((rows, 2)) < missing_rate
    order = rng.permutation(rows)

    def text(values, mask=None):
        values = values[order]
        return pd.array(
            pa.array(values, mask=None if mask is None else mask[order]),
            dtype="str",
        )

    return pd.DataFrame({
        "unique_id": np.arange(rows),
        "first_name": text(np.concatenate([firsts.astype(wide), d_first]),
                           missing[:, 0]),
        "surname": text(np.concatenate([lasts.astype(wide), d_last]),
                        missing[:, 1]),
        "dob": text(_to_str(np.concatenate([dob, d_dob]))),
        "city": text(np.concatenate([cities, cities[dup_of]])),
        "postcode": text(np.concatenate([postcodes, postcodes[dup_of]])),
        "cluster": np.concatenate([np.arange(n_base), dup_of])[order],
    })


def split_for_linking(df: pd.DataFrame):
    """Two overlapping 'datasets' for link_only: the first row of every
    cluster on the left, every later row on the right (the original)."""
    first = ~df.duplicated("cluster", keep="first")
    return df[first].reset_index(drop=True), df[~first].reset_index(drop=True)
