"""The reference library's comparisons on config 4's people against a plain
reference that knows them.

The deployment ``chipbench/configs/c4_case_library.json`` (the cell
``c4lib_dedupe_virtual``: 4-level name inversion on both names, the
Levenshtein ratio on dob, postcode and surname, through the virtual pair
index) at twelve thousand rows on the CPU: the facade job has to give the pair
set, every gamma level, λ/m/u and every score of
``chipbench.reference_case_library`` within the limits the configuration's file
states. The reference's two new kinds are held to scalar definitions written
out longhand, ties and null guards included; the tie rule (a level is accepted
where it is reachable by settling each tied comparison either way) and the
span counts that say which kernel forms a gamma program is made of
(``string_evals``, ``levenshtein_columns``, ``name_inversion_columns``) are
held here too.
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
from chipbench import datagen  # noqa: E402
from chipbench import reference_case_library as reference  # noqa: E402
from chipbench.readers import span_count  # noqa: E402
from chipbench.tests.test_reference import jaro_winkler_scalar  # noqa: E402
from splink_tpu import Splink  # noqa: E402
from splink_tpu.utils.profiling import spans  # noqa: E402

ROWS = 12000
TIE_94 = ("abcdefghij", "axcdefghij")  # 9 of 10 matched, prefix 1: jw = 0.94 exactly


def load(name):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load("c4_case_library")


def people_of(config, population):
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "population_seed", "rows")}
    return datagen.make_people(rows=ROWS, seed=population, **gen)


def small(config, **over):
    settings = copy.deepcopy(config["settings"])
    settings.update({"pair_batch_size": 1 << 16, "max_resident_pairs": 1024, **over})
    return settings


def job(settings, people):
    linker = Splink(copy.deepcopy(settings), df=people)
    return linker, linker.get_scored_comparisons()


def produced(linker, frame):
    p = frame["match_probability"].to_numpy()
    digest = (len(frame), float(p.sum(dtype=np.float64)), float(linker.params.params["λ"]))
    return {"frame": frame, "tf_frame": None, "params": linker.params.params,
            "digests": [digest], "uid": "unique_id"}


def pattern_stage(linker):
    stage = [s for s in spans(run=linker.run_id) if s["name"] == "gammas_patterns"]
    assert len(stage) == 1
    return stage[0]["counts"]


# --------------------------------------------------------------------------
# The facade job against the reference
# --------------------------------------------------------------------------


@pytest.mark.parametrize("population", [25, 7])
def test_facade_job_equals_the_reference(config, population):
    people = people_of(config, population)
    settings = small(config)
    prep = reference.prepare(settings, {"df": people})
    linker, frame = job(settings, people)
    assert linker._virtual is not None  # the virtual pair index, as in the cell
    got = correct.compare(produced(linker, frame), prep)
    ok, rows = correct.verdict(got, config["limits"])
    assert ok, rows
    assert got["pairs_wrong"] == 0 and got["gamma_wrong"] == 0, rows
    assert got["pairs_produced"] == len(prep["uid_l"]) > 1024
    # every level of every column is populated, the inversion level included
    for c, (name, levels) in enumerate(zip(prep["names"], prep["levels"])):
        seen = set(np.unique(prep["gamma"][:, c]).tolist()) - {-1}
        assert seen == set(range(levels)), (name, seen)
    # which kernel forms the pass was made of
    counts = pattern_stage(linker)
    positions = linker._virtual.n_candidates
    assert counts["string_evals"] == positions * 7  # 2 + 2 Jaro-Winkler, 3 Levenshtein
    assert "two_phase" not in counts  # there is one body, and no count of another
    assert counts["levenshtein_columns"] == 3 and counts["name_inversion_columns"] == 2
    # and the reader of span counts finds it: the mean over the window's jobs
    run = {"jobs": [{}], "failed": 0}
    assert span_count.read(run, ["gammas_patterns", "gammas"], "string_evals") == positions * 7
    assert span_count.read(run, ["gammas_patterns", "gammas"], "no_such_count") is None
    assert span_count.read(run, ["no_such_span"], "string_evals") is None


def test_bfloat16_control_fails(config):
    frames = {"df": people_of(config, 25)}
    settings = small(config)
    prep = reference.prepare(settings, frames)
    ref = reference.run(settings, frames)
    ok, rows = correct.verdict(correct.compare(correct.stand_in(ref), prep), config["limits"])
    assert ok, rows
    control = reference.run(settings, frames, precision="bfloat16")
    ok, rows = correct.verdict(correct.compare(correct.stand_in(control), prep),
                               config["limits"])
    assert not ok, rows
    assert {n for n, v, lim in rows if lim is not None and v > lim} >= {"gamma_wrong"}


def test_baseline_c4_reports_four_string_evaluations_and_no_new_kind():
    config = load("baseline_c4")
    linker, _ = job(small(config), people_of(config, 25))
    counts = pattern_stage(linker)
    # first_name, surname, postcode Jaro-Winkler and the bigram Jaccard
    assert counts["string_evals"] == linker._virtual.n_candidates * 4
    assert "two_phase" not in counts and counts["redo_positions"] == 0
    assert counts["levenshtein_columns"] == 0 and counts["name_inversion_columns"] == 0


def test_materialised_pass_counts_its_kernels_on_the_gammas_stage(config):
    settings = small(config, device_pair_generation="off", max_resident_pairs=1 << 28)
    linker, frame = job(settings, people_of(config, 25))
    stage = [s for s in spans(run=linker.run_id)
             if s["name"] in ("gammas", "gammas_patterns") and "string_evals" in s["counts"]]
    assert len(stage) == 1
    assert stage[0]["counts"]["string_evals"] == len(frame) * 7
    assert "two_phase" not in stage[0]["counts"]


# --------------------------------------------------------------------------
# The reference's new kinds against scalar definitions
# --------------------------------------------------------------------------


def levenshtein_scalar(a: str, b: str) -> int:
    row = list(range(len(b) + 1))
    for i, ca in enumerate(a, start=1):
        new = [i]
        for j, cb in enumerate(b, start=1):
            new.append(min(row[j] + 1, new[j - 1] + 1, row[j - 1] + (ca != cb)))
        row = new
    return row[len(b)]


def levenshtein_level_scalar(a, b, thresholds, top):
    if a is None or b is None:
        return -1
    if a == b:
        return top
    mean = (len(a) + len(b)) / 2
    ratio = levenshtein_scalar(a, b) / mean if mean > 0 else 0.0
    return sum(ratio <= t for t in thresholds)


def inversion_level_scalar(col_l, col_r, other_r, t1=0.94, t2=0.88):
    if col_l is None or col_r is None:
        return -1
    if jaro_winkler_scalar(col_l, col_r) > t1:
        return 3
    if other_r is not None and jaro_winkler_scalar(col_l, other_r) > t1:
        return 2
    return 1 if jaro_winkler_scalar(col_l, col_r) > t2 else 0


def word_table(seed, n=1500):
    """Two name columns over 2n rows (pair k = rows k and n + k): random pool
    names, typos, inversions, equal strings, empty strings, nulls."""
    rng = np.random.default_rng(seed)
    pool, _ = datagen.name_pool(rng, datagen.LASTS, 300)
    a_l, b_l = pool[rng.integers(0, len(pool), (2, n))]
    kind = rng.integers(0, 4, n)
    a_r = np.where(kind == 0, a_l, np.where(kind == 1, datagen.typo(rng, a_l),
                   np.where(kind == 2, b_l, pool[rng.integers(0, len(pool), n)])))
    b_r = np.where(kind == 2, datagen.typo(rng, a_l), np.where(kind == 1, b_l,
                   pool[rng.integers(0, len(pool), n)]))
    table = pd.DataFrame({"a": np.concatenate([a_l, a_r]).astype(object),
                          "b": np.concatenate([b_l, b_r]).astype(object)})
    for col in ("a", "b"):
        table.loc[rng.random(2 * n) < 0.04, col] = None
        table.loc[rng.random(2 * n) < 0.02, col] = ""
    return table, np.arange(n, dtype=np.int32), np.arange(n, 2 * n, dtype=np.int32)


def with_planted(table, idx_l, idx_r, pairs):
    """The table with hand-made (a_l, b_l, a_r, b_r) pairs appended."""
    n = len(table)
    left = pd.DataFrame([(p[0], p[1]) for p in pairs], columns=["a", "b"], dtype=object)
    right = pd.DataFrame([(p[2], p[3]) for p in pairs], columns=["a", "b"], dtype=object)
    k = len(pairs)
    return (pd.concat([table, left, right], ignore_index=True),
            np.concatenate([idx_l, np.arange(n, n + k, dtype=np.int32)]),
            np.concatenate([idx_r, np.arange(n + k, n + 2 * k, dtype=np.int32)]))


def values(table, col, idx):
    return [None if pd.isna(v) else v for v in table[col].to_numpy()[idx]]


@pytest.mark.parametrize("levels,thresholds", [(3, [0.3]), (4, [0.2, 0.4])])
def test_levenshtein_levels_equal_the_scalar_definition(levels, thresholds):
    table, idx_l, idx_r = word_table(seed=levels)
    planted = [
        ("1950-03-12", "x", "1953-08-17", "x"),  # 3 of mean 10: ratio 0.3, a tie
        ("ab", "x", "abcdefgh", "x"),  # 6 of mean 5: far off
        ("abcde", "x", "abcdx", "x"),  # 1 of 5: ratio 0.2, a tie at 4 levels
        ("abcde", "x", "abcxy", "x"),  # 2 of 5: ratio 0.4, a tie at 4 levels
        ("", "x", "", "x"), ("", "x", "abc", "x"), (None, "x", "abc", "x"),
    ]
    table, idx_l, idx_r = with_planted(table, idx_l, idx_r, planted)
    settings = {"comparison_columns": [{
        "col_name": "a", "num_levels": levels,
        "comparison": {"kind": "levenshtein", "thresholds": thresholds}}]}
    G, boundary, reachable = reference.gamma_levels(settings, table, idx_l, idx_r)
    want = [levenshtein_level_scalar(a, b, thresholds, levels - 1)
            for a, b in zip(values(table, "a", idx_l), values(table, "a", idx_r))]
    assert G[:, 0].tolist() == want
    assert set(want) == set(range(-1, levels))
    # the integer distances themselves
    mat, length, _ = reference.encode(table["a"])
    codes, longest = reference._lev_codes(mat, length, idx_l, idx_r)
    left, right = values(table, "a", idx_l), values(table, "a", idx_r)
    assert (codes % (longest + 1)).tolist() == [
        levenshtein_scalar(a or "", b or "") for a, b in zip(left, right)]
    # ties: the planted ones, and what settling each either way gives
    k = len(G) - len(planted)
    tie_rows = {0} if levels == 3 else {2, 3}
    assert {i for i in range(len(planted)) if boundary[k + i, 0]} == tie_rows
    for i in tie_rows:
        level = int(G[k + i, 0])  # `<=` holds on the tie
        assert reachable[k + i, 0] == (1 << level) | (1 << (level - 1))
    clear = ~boundary[:, 0] & (G[:, 0] >= 0)
    assert (reachable[clear, 0] == 1 << G[clear, 0].astype(np.uint8)).all()
    assert (reachable[G[:, 0] < 0, 0] == 0).all()


@pytest.mark.parametrize("column,other", [("a", "b"), ("b", "a")])
def test_name_inversion_levels_equal_the_scalar_definition(column, other):
    table, idx_l, idx_r = word_table(seed=11)
    far = "qqqqqqqq"
    planted = [
        (TIE_94[0], far, TIE_94[1], far),  # self pair on 0.94: level 1 or 3
        (TIE_94[0], far, far, TIE_94[1]),  # cross pair on 0.94: level 0 or 2
        (TIE_94[0], far, far, TIE_94[0]),  # a plain inversion
        (TIE_94[0], far, far, None),  # the other column null on the right: guarded
        (TIE_94[0], None, far, TIE_94[0]),  # null on the LEFT of the other: not guarded
        (None, far, far, TIE_94[0]),
    ]
    if column == "b":
        planted = [(p[1], p[0], p[3], p[2]) for p in planted]
    table, idx_l, idx_r = with_planted(table, idx_l, idx_r, planted)
    settings = {"comparison_columns": [{
        "col_name": column, "num_levels": 4,
        "comparison": {"kind": "name_inversion", "other_columns": [other],
                       "thresholds": [0.94, 0.88]}}]}
    G, boundary, reachable = reference.gamma_levels(settings, table, idx_l, idx_r)
    want = [inversion_level_scalar(a, b, o) for a, b, o in zip(
        values(table, column, idx_l), values(table, column, idx_r), values(table, other, idx_r))]
    k = len(G) - len(planted)
    # off the ties the levels are the scalar definition's, every level among them
    assert [g for g, tied in zip(G[:, 0].tolist(), boundary[:, 0]) if not tied] == [
        w for w, tied in zip(want, boundary[:, 0]) if not tied]
    assert set(G[~boundary[:, 0], 0].tolist()) == {-1, 0, 1, 2, 3}
    assert G[k + 2:, 0].tolist() == [2, 0, 2, -1]
    # on them, what float64 settles lies in the reachable set, and the set is
    # what settling the tied comparison either way gives: a step of TWO
    assert boundary[k:, 0].tolist() == [True, True, False, False, False, False]
    assert reachable[k, 0] == (1 << 1) | (1 << 3)
    assert reachable[k + 1, 0] == (1 << 0) | (1 << 2)
    assert ((reachable[:, 0] >> np.maximum(G[:, 0], 0).astype(np.uint8)) & 1)[G[:, 0] >= 0].all()
    assert (reachable[G[:, 0] < 0, 0] == 0).all()


def test_a_tied_level_is_accepted_only_inside_its_reachable_set(config):
    """The comparison's tie rule: on a cross-pair tie (reachable 0 and 2) the
    other reachable level passes and trains the reference; level 1, adjacent
    to both, is ``gamma_wrong``."""
    people = people_of(config, 25).head(4000).reset_index(drop=True)
    extra = people.head(2).copy()
    extra["unique_id"] = [len(people), len(people) + 1]
    extra["first_name"], extra["surname"] = [TIE_94[0], "qqqqqqqq"], ["qqqqqqqq", TIE_94[1]]
    extra["dob"] = "2001-01-01"  # blocked together by the dob rule alone
    frames = {"df": pd.concat([people, extra], ignore_index=True)}
    settings = small(config)
    prep = reference.prepare(settings, frames)
    ref = reference.run(settings, frames)
    cell = np.flatnonzero((prep["uid_l"] == len(people)) & (prep["uid_r"] == len(people) + 1))
    assert len(cell) == 1 and prep["boundary"][cell[0], 0]
    assert prep["reachable"][cell[0], 0] == 0b101
    base = int(prep["gamma"][cell[0], 0])
    numbers = {}
    for level in (0, 1, 2):
        shown = dict(ref, gamma=ref["gamma"].copy())
        shown["gamma"][cell[0], 0] = level
        numbers[level] = correct.compare(correct.stand_in(shown), prep)
    assert numbers[base]["gamma_wrong"] == 0 and numbers[base]["gamma_boundary_flips"] == 0
    assert numbers[2 - base]["gamma_wrong"] == 0
    assert numbers[2 - base]["gamma_boundary_flips"] == 1
    assert numbers[2 - base]["lev_tie_flips"] == 0  # a Jaro-Winkler tie is float32 noise
    assert numbers[1]["gamma_wrong"] == 1 and numbers[1]["gamma_boundary_flips"] == 0


@pytest.mark.parametrize("column,every", [(2, 1), (5, 4)])
def test_a_levenshtein_ratio_on_its_threshold_has_one_right_level(config, column, every):
    """A ratio that EQUALS its threshold is no float32 noise: ``<=`` holds.
    The level below is reachable, so it is not ``gamma_wrong``, and each such
    cell is counted in ``lev_tie_flips``, whose limit is 0 — every tie of dob
    falling (``<`` for ``<=``) and every fourth of surname_lev's (a division
    one ulp high, as the TPU's was before the thresholds were two ulps wide)."""
    frames = {"df": people_of(config, 25)}
    settings = small(config)
    prep = reference.prepare(settings, frames)
    ref = reference.run(settings, frames)
    ties = np.flatnonzero(prep["boundary"][:, column])[::every]
    assert len(ties) > 0
    shown = dict(ref, gamma=ref["gamma"].copy())
    shown["gamma"][ties, column] -= 1
    numbers = correct.compare(correct.stand_in(shown), prep)
    ok, rows = correct.verdict(numbers, config["limits"])
    assert not ok and numbers["gamma_wrong"] == 0 and numbers["lev_tie_flips"] == len(ties), rows
    assert config["limits"]["lev_tie_flips"] == 0


def test_levenshtein_thresholds_hold_two_ulps_wide():
    """The program's tie contract (``ops/gamma.bucket_difference_le``): a ratio
    that equals a threshold as a rational number takes the level even where
    the backend's division is one ulp high, as the TPU's is; three ulps off is
    another ratio."""
    import jax.numpy as jnp

    from splink_tpu.ops.gamma import bucket_difference_le

    up = lambda x, k: x if k == 0 else up(np.nextafter(x, np.float32(np.inf)), k - 1)  # noqa: E731
    for t, level in ((0.2, 2), (0.4, 1)):
        at = np.float32(t)
        diff = jnp.asarray([up(at, 0), up(at, 1), up(at, 2), up(at, 3)], jnp.float32)
        got = bucket_difference_le(diff, (0.2, 0.4), None, jnp.zeros(4, bool), 3)
        assert got.tolist() == [level, level, level, level - 1]
    # every quotient a column of this width can give: the levels of exact division
    d, l1, l2 = (a.ravel() for a in np.meshgrid(*[np.arange(33)] * 3, indexing="ij"))
    keep = (l1 + l2 > 0) & (d <= np.maximum(l1, l2))
    d, mean = d[keep].astype(np.float64), (l1[keep] + l2[keep]) / 2.0
    got = bucket_difference_le(jnp.asarray(d / mean, jnp.float32), (0.2, 0.4), None,
                               jnp.zeros(len(d), bool), 3)
    assert got.tolist() == ((d / mean <= 0.2).astype(int) + (d / mean <= 0.4)).tolist()
