"""The plain reference against scalar definitions written out longhand, and
the control: the reference in bfloat16, put in the program's place, has to
come out as not correct under every configuration's limits."""

import json
import os

import numpy as np
import pytest

from chipbench import correct, datagen, reference

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def jaro_winkler_scalar(s1: str, s2: str) -> float:
    """Apache commons-text JaroWinklerDistance.apply, line for line."""
    if len(s1) > len(s2):
        longer, shorter = s1, s2
    else:
        longer, shorter = s2, s1
    window = max(len(longer) // 2 - 1, 0)
    match_index = [-1] * len(shorter)
    match_flags = [False] * len(longer)
    matches = 0
    for mi, c1 in enumerate(shorter):
        lo, hi = max(mi - window, 0), min(mi + window + 1, len(longer))
        for xi in range(lo, hi):
            if not match_flags[xi] and c1 == longer[xi]:
                match_index[mi], match_flags[xi] = xi, True
                matches += 1
                break
    if matches == 0:
        return 0.0
    ms1 = [shorter[i] for i in range(len(shorter)) if match_index[i] != -1]
    ms2 = [longer[i] for i in range(len(longer)) if match_flags[i]]
    half = sum(a != b for a, b in zip(ms1, ms2)) // 2
    prefix = 0
    for a, b in zip(s1, s2):
        if a != b:
            break
        prefix += 1
    m = float(matches)
    j = (m / len(s1) + m / len(s2) + (m - half) / m) / 3
    if j < 0.7:
        return j
    return j + min(0.1, 1.0 / len(longer)) * prefix * (1 - j)


def bigram_jaccard_scalar(s1: str, s2: str) -> float:
    a = {s1[i:i + 2] for i in range(len(s1) - 1)}
    b = {s2[i:i + 2] for i in range(len(s2) - 1)}
    return len(a & b) / len(a | b) if a | b else 0.0


def _word_pairs(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    pool, _ = datagen.name_pool(rng, datagen.LASTS, 400)
    left = pool[rng.integers(0, len(pool), n)]
    right = np.where(rng.random(n) < 0.5, datagen.typo(rng, datagen.typo(rng, left)),
                     pool[rng.integers(0, len(pool), n)])
    extra = [("martha", "marhta"), ("dixon", "dicksonx"), ("jellyfish", "smellyfish"),
             ("a", "a"), ("ab", "ba"), ("abcd", "dcba"), ("aaaa", "aa")]
    left = np.concatenate([left, [a for a, _ in extra]])
    right = np.concatenate([right, [b for _, b in extra]])
    return left, right


@pytest.mark.parametrize("kind", ["jaro_winkler", "qgram_jaccard"])
def test_string_similarities_equal_the_scalar_definitions(kind):
    import pandas as pd

    left, right = _word_pairs()
    words = pd.Series(np.concatenate([left, right]))
    mat, length, _ = reference.encode(words)
    n = len(left)
    il, ir = np.arange(n, dtype=np.int32), np.arange(n, 2 * n, dtype=np.int32)
    codes, longest = reference._pair_codes(kind, mat, length, il, ir)
    sim = reference._similarity(kind, longest, np.float64)[0][codes]
    scalar = jaro_winkler_scalar if kind == "jaro_winkler" else bigram_jaccard_scalar
    want = np.array([scalar(a, b) for a, b in zip(left, right)])
    assert np.allclose(sim, want, atol=1e-12)
    if kind == "jaro_winkler":
        assert round(float(sim[n - 7]), 4) == 0.9611  # MARTHA / MARHTA


def _config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def _frames(config, rows, seed):
    gen = {k: v for k, v in config["generator"].items() if k not in ("kind", "rows", "population_seed")}
    df = datagen.make_people(rows, seed=seed, **gen)
    if config["settings"]["link_type"] == "dedupe_only":
        return {"df": df}
    left, right = datagen.split_for_linking(df)
    return {"df_l": left, "df_r": right}


@pytest.mark.parametrize("name,rows", [("baseline_c4", 12_000), ("baseline_c3", 30_000)])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_passes_and_the_bfloat16_control_fails(name, rows, seed):
    config = _config(name)
    frames = _frames(config, rows, seed)
    ref = reference.run(config["settings"], frames)
    assert len(ref["p"]) > 1000 and ref["updates"] > 1
    prep = reference.prepare(config["settings"], frames)
    ok, rows_ = correct.verdict(correct.compare(correct.stand_in(ref), prep), config["limits"])
    assert ok, rows_
    control = reference.run(config["settings"], frames, precision="bfloat16")
    ok, rows_ = correct.verdict(correct.compare(correct.stand_in(control), prep), config["limits"])
    assert not ok, rows_
    failed = {n for n, v, lim in rows_ if lim is not None and v > lim}
    assert failed & {"gamma_wrong", "param_gap", "score_gap", "tf_gap"}, rows_


def test_em_recovers_planted_parameters():
    rng = np.random.default_rng(0)
    n, lam = 200_000, 0.2
    settings = {"comparison_columns": [{"col_name": c, "num_levels": 3} for c in "abc"]}
    m = np.array([[0.05, 0.15, 0.8]] * 3)
    u = np.array([[0.85, 0.1, 0.05]] * 3)
    match = rng.random(n) < lam
    G = np.stack([np.where(match, rng.choice(3, n, p=m[c]), rng.choice(3, n, p=u[c]))
                  for c in range(3)], axis=1).astype(np.int8)
    patterns, counts, index = reference.pattern_table(G, [3, 3, 3])
    assert counts.sum() == n and np.array_equal(patterns[index], G)
    got_lam, got_m, got_u, updates = reference.em({**settings, "max_iterations": 200},
                                                  patterns, counts)
    assert abs(got_lam - lam) < 0.01 and updates < 200
    assert np.abs(got_m - m).max() < 0.02 and np.abs(got_u - u).max() < 0.02
