"""Two-phase Jaro-Winkler gamma scoring (gammas._jw_two_phase, ops/jw_bound).

Three properties keep the optimisation honest:

  * bound soundness — jw_upper_bound never undercuts the exact kernel
    (an unsound bound would silently misclassify pairs below a threshold);
  * bit-identity — the two-phase body and the exact body produce the SAME
    gamma matrix (the pruning is an optimisation, never a result change);
  * overflow redo — when the survivor capacity blows (forced here with
    jw_survivor_divisor = 10**6, capacity floor 1024), every consumer
    (safe _gamma_batch, the flagged G path, the pattern/histogram path)
    redoes the batch through the exact twin instead of scoring survivors
    it had no slots for.
"""

import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp

from splink_tpu.data import encode_table
from splink_tpu.gammas import GammaProgram
from splink_tpu.ops import jw_bound, strings
from splink_tpu.settings import complete_settings_dict

from conftest import py_jaro_winkler

W = 16  # packed char width for the direct-kernel fuzz


def _enc(words, width=W):
    n = len(words)
    b = np.zeros((n, width), np.uint8)
    lens = np.zeros(n, np.int32)
    for i, w in enumerate(words):
        raw = w.encode()[:width]
        b[i, : len(raw)] = np.frombuffer(raw, np.uint8)
        lens[i] = len(raw)
    return b, lens


# ----------------------------------------------------------------------
# Bound soundness
# ----------------------------------------------------------------------


def _fuzz_words(rng, n):
    """Adversarial mix: random words, heavy repeats (nibble-counter
    overflow), shared 4-char prefixes (the unconditional-survivor case),
    near-misses, empties."""
    alphabet = list("abcdefghijklmnopqrstuvwxyz")
    tight = list("abc")  # forces class collisions under the 32-way hash
    words = []
    for _ in range(n):
        r = rng.random()
        if r < 0.15:
            words.append("a" * rng.integers(0, 13))  # counts past cap 7
        elif r < 0.35:
            words.append("".join(rng.choice(tight, rng.integers(0, 12))))
        elif r < 0.55:
            words.append("pref" + "".join(rng.choice(alphabet, rng.integers(0, 8))))
        elif r < 0.6:
            words.append("")
        else:
            words.append("".join(rng.choice(alphabet, rng.integers(1, 12))))
    return words


def test_jw_upper_bound_sound_fuzz():
    """For every fuzzed pair: exact JW <= upper bound + BOUND_MARGIN.
    Soundness is what makes phase-1 exclusion safe — an excluded pair
    provably sits below the lowest threshold."""
    rng = np.random.default_rng(1234)
    words = _fuzz_words(rng, 600)
    bytes_, lens = _enc(words)
    token_ids = np.arange(len(words), dtype=np.int64)
    cnt, pref = jw_bound.jw_bound_row_aux(bytes_, lens, token_ids)

    il = rng.integers(0, len(words), 4000)
    ir = rng.integers(0, len(words), 4000)
    ub = np.asarray(
        jw_bound.jw_upper_bound(
            jnp.asarray(cnt[il]),
            jnp.asarray(pref[il, 0]),
            jnp.asarray(cnt[ir]),
            jnp.asarray(pref[ir, 0]),
            jnp.asarray(lens[il]),
            jnp.asarray(lens[ir]),
            0.1,
            0.7,
        )
    )
    exact = np.asarray(
        strings.jaro_winkler(
            bytes_[il], bytes_[ir], lens[il], lens[ir], 0.1, 0.7
        )
    )
    bad = exact > ub + jw_bound.BOUND_MARGIN
    assert not bad.any(), [
        (words[il[k]], words[ir[k]], float(exact[k]), float(ub[k]))
        for k in np.flatnonzero(bad)[:10]
    ]
    # the device kernel itself agrees with the independent Python oracle
    # on a sample (ties the soundness claim back to ground truth)
    sample = rng.integers(0, 4000, 50)
    want = [py_jaro_winkler(words[il[k]], words[ir[k]]) for k in sample]
    np.testing.assert_allclose(exact[sample], want, atol=1e-6)


def test_jw_bound_aux_null_rows_zero():
    words = ["abc", "", "abc"]
    bytes_, lens = _enc(words)
    token_ids = np.array([0, -1, 0], np.int64)  # middle row null
    cnt, pref = jw_bound.jw_bound_row_aux(bytes_, lens, token_ids)
    assert (cnt[1] == 0).all() and pref[1, 0] == 0
    np.testing.assert_array_equal(cnt[0], cnt[2])


# ----------------------------------------------------------------------
# Gamma bit-identity: two-phase vs exact, through GammaProgram
# ----------------------------------------------------------------------


def _jw_df(n=400, seed=5, similar=False):
    rng = np.random.default_rng(seed)
    if similar:
        # shared 6-char prefix, distinct suffixes: every cross pair is an
        # unconditional survivor (4-char prefix match -> bound 2.0) and no
        # pair is token-equal
        names = np.array([f"prefix{i:04d}" for i in range(n)], dtype=object)
    else:
        base = np.array(
            ["amelia", "amelie", "oliver", "olivia", "isla", "george",
             "georgia", "ava", "eva", "noah", "nora", "", None],
            dtype=object,
        )
        names = base[rng.integers(0, len(base), n)]
    return pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": names,
            "city": np.array(["x", "y"], dtype=object)[rng.integers(0, 2, n)],
        }
    )


def _jw_settings(**overrides):
    s = {
        "link_type": "dedupe_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {
                "col_name": "name",
                "num_levels": 3,
                "comparison": {
                    "kind": "jaro_winkler",
                    "thresholds": [0.94, 0.88],
                },
            },
        ],
    }
    s.update(overrides)
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return complete_settings_dict(s)


def _programs_and_pairs(df, rng_seed=9, **overrides):
    """(two-phase program, exact program, idx_l, idx_r) on one table."""
    s2 = _jw_settings(**overrides)
    s1 = _jw_settings(two_phase_jw="off", **overrides)
    table = encode_table(df, s2)
    prog2 = GammaProgram(s2, table)
    prog1 = GammaProgram(s1, table)
    assert prog2.two_phase_div and prog1.two_phase_div is None
    rng = np.random.default_rng(rng_seed)
    n_pairs = 2048
    il = rng.integers(0, len(df), n_pairs).astype(np.int32)
    ir = rng.integers(0, len(df), n_pairs).astype(np.int32)
    return prog2, prog1, il, ir


def test_two_phase_gamma_bit_identical_to_exact():
    """Realistic name data (some token-equal, some null, some near-miss):
    the two-phase G equals the exact G bit-for-bit, in both the G and the
    pattern/histogram regimes."""
    prog2, prog1, il, ir = _programs_and_pairs(_jw_df())
    G2 = prog2.compute(il, ir, batch_size=512)
    G1 = prog1.compute(il, ir, batch_size=512)
    np.testing.assert_array_equal(G2, G1)

    p2, c2 = prog2.compute_pattern_ids(il, ir, batch_size=512)
    p1, c1 = prog1.compute_pattern_ids(il, ir, batch_size=512)
    np.testing.assert_array_equal(p2, p1)
    np.testing.assert_array_equal(c2, c1)


def test_two_phase_levels_match_thresholds():
    """Spot-check the gamma levels against the oracle similarity: level =
    number of thresholds strictly below the pair's JW score."""
    df = _jw_df(n=60)
    prog2, _, _, _ = _programs_and_pairs(df)
    il = np.arange(0, 30, dtype=np.int32)
    ir = np.arange(30, 60, dtype=np.int32)
    G = prog2.compute(il, ir, batch_size=32)
    names = df["name"].to_numpy()
    for k in range(len(il)):
        a, b = names[il[k]], names[ir[k]]
        if pd.isna(a) or pd.isna(b):  # pandas 3: a missing string is NaN
            assert G[k, 0] == -1  # null level (empty string is a VALUE)
            continue
        sim = py_jaro_winkler(a, b)
        want = (sim > 0.94) + (sim > 0.88)
        assert G[k, 0] == want, (a, b, sim, int(G[k, 0]), want)


# ----------------------------------------------------------------------
# Forced survivor overflow -> exact-twin redo
# ----------------------------------------------------------------------


def test_survivor_overflow_redo_g_and_pattern_regimes():
    """jw_survivor_divisor 10**6 drops capacity to the 1024 floor; 2048
    all-survivor pairs per batch therefore overflow, and every consumer
    must still produce the exact result."""
    df = _jw_df(similar=True)
    prog2, prog1, il, ir = _programs_and_pairs(
        _jw_df(similar=True), jw_survivor_divisor=10**6
    )
    # the overflow really happens: the flagged kernel reports it on a
    # full 2048-pair batch ...
    flagged = np.asarray(
        prog2._gamma_batch_flagged(jnp.asarray(il), jnp.asarray(ir))
    )
    assert flagged[-1, 0] == 1, "survivor capacity did not overflow"

    # ... and each consumer's redo restores exactness:
    # (a) the misuse-proof convenience batch (on-device lax.cond redo)
    G_safe = np.asarray(prog2._gamma_batch(jnp.asarray(il), jnp.asarray(ir)))
    G_exact = prog1.compute(il, ir, batch_size=2048)
    np.testing.assert_array_equal(G_safe, G_exact)

    # (b) the host G regime (flag row read -> exact-twin recompute)
    G2 = prog2.compute(il, ir, batch_size=2048)
    np.testing.assert_array_equal(G2, G_exact)

    # (c) the pattern/histogram regime (flagged batch skipped the
    # histogram; the redo's late accumulation commutes to the same total)
    p2, c2 = prog2.compute_pattern_ids(il, ir, batch_size=2048)
    p1, c1 = prog1.compute_pattern_ids(il, ir, batch_size=2048)
    np.testing.assert_array_equal(p2, p1)
    np.testing.assert_array_equal(c2, c1)
    assert c2.sum() == len(il)


def test_no_overflow_within_capacity():
    """Control for the overflow test: same all-survivor data in a batch
    at the 1024 capacity floor — every survivor has a slot (capacity =
    min(b, max(1024, b // div))), so no flag is raised."""
    prog2, _, il, ir = _programs_and_pairs(_jw_df(similar=True))
    flagged = np.asarray(
        prog2._gamma_batch_flagged(jnp.asarray(il[:1000]), jnp.asarray(ir[:1000]))
    )
    assert flagged[-1, 0] == 0
