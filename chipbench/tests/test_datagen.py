"""The generator: exact row counts for every seed, and the original's pools,
rates, key cardinalities and duplicate share at a small size."""

import numpy as np
import pytest

from chipbench import datagen

ROWS = 26_000


@pytest.mark.parametrize("seed", [0, 4, 12345, 2**31 + 11, 3_000_000_019])
@pytest.mark.parametrize("rows,rate", [(ROWS, 0.3), (9_001, 0.5)])
def test_row_count_is_exact_for_every_seed(seed, rows, rate):
    df = datagen.make_people(rows, duplicate_rate=rate, seed=seed)
    assert len(df) == rows
    assert df.unique_id.tolist() == list(range(rows))
    n_base = round(rows / (1 + rate))
    assert df.cluster.nunique() == n_base
    assert int(df.cluster.duplicated().sum()) == rows - n_base
    left, right = datagen.split_for_linking(df)
    assert (len(left), len(right)) == (n_base, rows - n_base)


def test_same_seed_same_frame_other_seed_other_frame():
    a, b = datagen.make_people(5000, seed=9), datagen.make_people(5000, seed=9)
    assert a.equals(b)
    assert not a.equals(datagen.make_people(5000, seed=10))


def test_rates_and_cardinalities():
    df = datagen.make_people(ROWS, seed=4)
    n_base = round(ROWS / 1.3)
    assert abs(df.first_name.isna().mean() - 0.02) < 0.005
    assert abs(df.surname.isna().mean() - 0.02) < 0.005
    assert set(df.city) <= set(datagen.CITIES) and df.city.nunique() == 18
    assert df.dob.str.fullmatch(r"\d{4}-\d{2}-\d{2}").all()
    assert df.postcode.str.fullmatch(r"[A-Z]{2}\d+").all()
    assert df.postcode.nunique() <= 18 * max(30, n_base // 2000)
    # duplicates: same city and postcode always; ~40% first-name typos,
    # ~24% surname typos, 10% inversions, 5% day/month swaps
    dup = df[df.cluster.duplicated(keep=False)].sort_values(["cluster", "unique_id"])
    a, b = dup.iloc[0::2].reset_index(drop=True), dup.iloc[1::2].reset_index(drop=True)
    assert (a.cluster == b.cluster).all()
    assert (a.city == b.city).all() and (a.postcode == b.postcode).all()
    both = a.first_name.notna() & b.first_name.notna() & a.surname.notna() & b.surname.notna()
    # an inversion shows as such only where neither name also took a typo
    swapped = both & (a.first_name == b.surname) & (a.surname == b.first_name)
    assert abs(swapped[both].mean() - 0.1 * 0.6 * 0.76) < 0.012
    plain = both & ~swapped
    assert 0.38 < (a.first_name != b.first_name)[plain].mean() < 0.48
    assert 0.22 < (a.surname != b.surname)[plain].mean() < 0.33
    assert abs((a.dob != b.dob).mean() - 0.05 * 27 / 28) < 0.02


def test_name_pool_is_distinct_and_zipf():
    rng = np.random.default_rng(0)
    pool, w = datagen.name_pool(rng, datagen.FIRSTS, 1300)
    assert len(set(pool)) == len(pool) == 1300
    assert set(datagen.FIRSTS) <= set(pool)
    assert np.isclose(w.sum(), 1.0) and np.all(np.diff(w) < 0)


def test_typo_is_one_edit():
    rng = np.random.default_rng(1)
    words = np.array(["martha", "jonathan", "ab", "a", "smith"] * 200)
    out = datagen.typo(rng, words)
    for w, o in zip(words, out):
        if len(w) < 2:
            assert o == w
            continue
        assert abs(len(o) - len(w)) <= 1
        if len(o) == len(w):  # substitution or transposition
            diff = [i for i in range(len(w)) if w[i] != o[i]]
            assert len(diff) <= 2
            if len(diff) == 2:
                i, j = diff
                assert j == i + 1 and w[i] == o[j] and w[j] == o[i]
        elif len(o) < len(w):
            assert any(w[:i] + w[i + 1:] == o for i in range(len(w)))
        else:
            assert any(o[:i] + o[i + 1:] == w for i in range(len(o)))


def test_statistics_match_the_original_generator():
    original = pytest.importorskip("benchmarks.datagen")
    want = original.make_people(20_000, seed=4)
    got = datagen.make_people(len(want), seed=4)
    for col in ("first_name", "surname", "dob", "city", "postcode"):
        a, b = want[col].nunique(), got[col].nunique()
        assert abs(a - b) <= 0.05 * a + 3, (col, a, b)
    assert abs(want.cluster.duplicated().mean() - got.cluster.duplicated().mean()) < 0.01
    top = lambda d: d.first_name.value_counts(normalize=True).iloc[:5].to_numpy()  # noqa: E731
    assert np.allclose(top(want), top(got), atol=0.01)
