"""The plain reference for the configuration ``c4_case_library``: the two
comparisons of geoHeil/splink ``case_statements.py`` that ``reference.py`` does
not know, written from their definitions, on top of what is kind-agnostic
there (candidate pairs, the string encoding, the Jaro-Winkler counts and
similarity, the pattern table, EM, the score). Nothing imported from
``splink_tpu``, nothing taken from what the program made.

  * **Levenshtein ratio** (``case_statements.py:117-141``): ``lev`` is the
    unit-cost edit distance (insert, delete, substitute), the ratio is
    ``lev / ((len_l + len_r) / 2)``; the top level is string equality, below it
    one level per threshold the ratio does not exceed (``<=``), ascending:
    level = top if equal else #{t : ratio <= t}.
  * **Name inversion, 4 levels** (``case_statements.py:248-277``): with jw the
    commons-text Jaro-Winkler of ``reference.py``'s header, t1 > t2 the two
    thresholds and ``other`` the other name column(s):
    3 if jw(col_l, col_r) > t1; else 2 if for some other, other_r is not null
    and jw(col_l, other_r) > t1; else 1 if jw(col_l, col_r) > t2; else 0.
  * null on either side of the column itself = level -1, in both.

The integer work (edit distances by the textbook recurrence, the Jaro-Winkler
counts of the self pair and of each cross pair) runs in plain ``jax.numpy``
over blocks of pairs; ratios, similarities, thresholds and branches are worked
out on the host in ``precision`` once per distinct integer code.
``precision="bfloat16"`` is the control, as in ``reference.py``.

**Ties.** A level here may hang on more than one comparison of a similarity
with an edge (0.94 on the self pair, 0.94 on a cross pair, 0.88, the 0.7 boost
branch of either similarity; 0.3; 0.2 and 0.4). Where a similarity lies within
``BOUNDARY`` of an edge float32 may honestly land on either side, and a tie on
the inversion branch moves the level by two. So beside the level the reference
gives, per cell, the SET of levels reachable by settling each tied comparison
either way (``reachable``, a bit per level); ``boundary`` marks the cells in
which any comparison is tied. For a kind with one similarity the set is the
adjacent-level rule of ``correct.py``. A Levenshtein ratio is a quotient of
small integers, tied only where it EQUALS its threshold: there ``<=`` holds,
the reference says so, and the comparison counts every such cell that took the
other side (``lev_tie_flips``, limit 0) — ``<`` for ``<=`` flips them all, a
division one ulp high some of them.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as base
from chipbench.reference import (BLOCK, BOUNDARY, DEFAULTS, _dtype, _jw_radices,
                                 _pack, _pair_codes, _similarity, _unpack,
                                 candidate_pairs, em, encode, match_probability,
                                 pattern_table)

DEFAULT_INVERSION_THRESHOLDS = [0.94, 0.88]
BOOST_FROM = 0.7  # commons-text adds the prefix bonus only from this jaro


def comparisons(settings: dict) -> list[dict]:
    """As ``reference.comparisons``, with ``levenshtein`` and
    ``name_inversion`` (``others``: its other name columns)."""
    out = []
    for col in settings["comparison_columns"]:
        spec = col.get("comparison") or {}
        kind = spec.get("kind")
        if kind not in ("levenshtein", "name_inversion"):
            out.append(base.comparisons({"comparison_columns": [col]})[0])
            continue
        levels = int(col["num_levels"])
        thresholds = [float(t) for t in spec.get("thresholds", [])]
        if kind == "name_inversion":
            thresholds = thresholds or DEFAULT_INVERSION_THRESHOLDS
            if levels != 4 or len(thresholds) != 2:
                raise ValueError("name inversion is the published 4-level comparison")
        elif len(thresholds) != levels - 2:
            raise ValueError("a Levenshtein column has one threshold per middle level")
        out.append({
            "name": col.get("custom_name", col.get("col_name")),
            "column": spec.get("column", col.get("col_name")),
            "kind": kind, "thresholds": thresholds, "levels": levels,
            "others": list(spec.get("other_columns", [])),
            "tf": bool(col.get("term_frequency_adjustments", False)),
        })
    return out


def _kindless(settings: dict) -> dict:
    """The settings with every column's kind written as one ``reference.py``
    knows: its EM and its priors read the level counts and the m/u
    probabilities of a column and nothing of its kind, and refuse a kind they
    do not know."""
    cols = [{**col, "comparison": {"kind": "exact"},
             "num_levels": int(col.get("num_levels", 2))}
            for col in settings["comparison_columns"]]
    return {**settings, "comparison_columns": cols}


# --------------------------------------------------------------------------
# Levenshtein: integer distances on the device, ratio and levels on the host
# --------------------------------------------------------------------------


def _edit_distance(s1, s2, l1, l2):
    """Unit-cost edit distance per pair by the textbook recurrence
    D[i][j] = min(D[i-1][j] + 1, D[i][j-1] + 1, D[i-1][j-1] + (a_i != b_j)),
    D[i][0] = i, D[0][j] = j, read at D[len_l][len_r]."""
    width = s1.shape[1]
    pick = l2[:, None]
    prev = jnp.broadcast_to(jnp.arange(width + 1, dtype=jnp.int32), (len(l1), width + 1))
    answer = jnp.take_along_axis(prev, pick, axis=1)[:, 0]  # len_l == 0
    for i in range(1, width + 1):
        row = [jnp.full(len(l1), i, jnp.int32)]
        for j in range(1, width + 1):
            differ = (s1[:, i - 1] != s2[:, j - 1]).astype(jnp.int32)
            row.append(jnp.minimum(jnp.minimum(prev[:, j] + 1, row[j - 1] + 1),
                                   prev[:, j - 1] + differ))
        prev = jnp.stack(row, axis=1)
        answer = jnp.where(l1 == i, jnp.take_along_axis(prev, pick, axis=1)[:, 0], answer)
    return answer


@functools.partial(jax.jit, static_argnames=("longest",))
def _lev_block(longest, table, lens, il, ir):
    l1, l2 = lens[il], lens[ir]
    return _pack([l1, l2, _edit_distance(table[il], table[ir], l1, l2)], (longest + 1,) * 3)


def _lev_codes(mat, length, idx_l, idx_r):
    """(code per pair, longest): (len_l, len_r, distance) of every pair, in
    blocks, the byte matrix padded as ``reference._pair_codes`` pads it."""
    mat = np.pad(mat, ((0, 0), (0, -mat.shape[1] % 8)))
    longest = mat.shape[1]
    table, lens = jnp.asarray(mat), jnp.asarray(length)
    n = len(idx_l)
    out = np.zeros(n, np.int32)
    for start in range(0, n, BLOCK):
        stop = min(start + BLOCK, n)
        il = np.zeros(BLOCK, np.int32)
        ir = np.zeros(BLOCK, np.int32)
        il[: stop - start], ir[: stop - start] = idx_l[start:stop], idx_r[start:stop]
        out[start:stop] = np.asarray(_lev_block(longest, table, lens, il, ir))[: stop - start]
    return out, longest


def _outcomes(holds, value, edge):
    """(may hold, may fail) of a comparison of ``value`` with ``edge`` that
    came out as ``holds``: both where the value lies within BOUNDARY of the
    edge, else the outcome alone."""
    with np.errstate(invalid="ignore"):
        near = np.abs(value.astype(np.float64) - edge) < BOUNDARY
    return holds | near, ~holds | near, near


def _reachable(outcomes, level_of):
    """Bit mask of the levels ``level_of(choices)`` gives over every way of
    settling the comparisons: ``outcomes`` is a list of (may hold, may
    fail); ``choices`` one bool per comparison."""
    mask = np.zeros(len(outcomes[0][0]), np.uint8)
    for choices in itertools.product((True, False), repeat=len(outcomes)):
        feasible = np.ones(len(mask), bool)
        for choice, (may_hold, may_fail) in zip(choices, outcomes):
            feasible &= may_hold if choice else may_fail
        mask |= feasible.astype(np.uint8) << np.uint8(level_of(choices))
    return mask


def _levenshtein_tables(longest, thresholds, top, dt):
    """Per (len_l, len_r, distance) code: level, reachable levels, tied. A
    ratio is a quotient of small integers, so it is tied only where it EQUALS
    the threshold as a rational number (3 / 10 against 0.3); there ``<=``
    holds under correctly rounded division and may fail under a division good
    to one ulp, as the TPU's is."""
    l1, l2, d = _unpack(np.arange((longest + 1) ** 3), (longest + 1,) * 3)
    mean = (l1.astype(dt) + l2.astype(dt)) / dt(2.0)
    ratio = np.where(mean > 0, d.astype(dt) / np.where(mean > 0, mean, dt(1.0)), dt(0.0))
    outcomes, tied = [], np.zeros(len(d), bool)
    level = np.zeros(len(d), np.int8)
    for t in thresholds:
        holds = ratio <= dt(t)
        level += holds
        may_hold, may_fail, near = _outcomes(holds, ratio, t)
        outcomes.append((may_hold, may_fail))
        tied |= near
    equal = (d == 0) & (l1 == l2)
    reach = _reachable(outcomes, sum) if outcomes else np.ones(len(d), np.uint8)
    return (np.where(equal, top, level).astype(np.int8),
            np.where(equal, np.uint8(1 << top), reach), tied & ~equal)


# --------------------------------------------------------------------------
# Name inversion: the self pair and each cross pair through the same counts
# --------------------------------------------------------------------------


def _jw_outcome_tables(longest, thresholds, dt):
    """Per Jaro-Winkler code and threshold: (holds, may hold, may fail) of
    ``jw > t``, and the codes tied on any of it. A jaro within BOUNDARY of the
    boost branch may be boosted or not: there the comparison may also come
    out as the other branch's value gives it."""
    sim, ((jaro, _),) = _similarity("jaro_winkler", longest, dt)
    l1, l2, _, _, prefix = _unpack(np.arange(len(sim)), _jw_radices(longest))
    one = dt(1.0)
    longer = np.maximum(np.maximum(l1, l2), 1).astype(dt)
    boosted = jaro + prefix.astype(dt) * np.minimum(dt(0.1), one / longer) * (one - jaro)
    _, _, on_branch = _outcomes(jaro < dt(BOOST_FROM), jaro, BOOST_FROM)
    other = np.where(jaro < dt(BOOST_FROM), boosted, jaro)  # the branch not taken
    tables, tied = {}, on_branch.copy()
    for t in thresholds:
        holds = sim > dt(t)
        may_hold, may_fail, near = _outcomes(holds, sim, t)
        alt = other > dt(t)
        alt_hold, alt_fail, _ = _outcomes(alt, other, t)
        tables[t] = (holds, may_hold | (on_branch & alt_hold), may_fail | (on_branch & alt_fail))
        tied |= near
    return tables, tied


def _inversion_level(choices):
    above_t1, inverted, above_t2 = choices
    return 3 if above_t1 else 2 if inverted else 1 if above_t2 else 0


def _name_inversion(comp, enc, idx_l, idx_r, dt):
    """(level, reachable, tied) per pair, nulls not yet applied; ``enc`` gives
    a column's (bytes, lengths, null)."""
    t1, t2 = comp["thresholds"]
    mats = [enc(name) for name in [comp["column"]] + comp["others"]]
    width = max(mat.shape[1] for mat, _, _ in mats)
    stacked = np.concatenate([np.pad(mat, ((0, 0), (0, width - mat.shape[1])))
                              for mat, _, _ in mats])
    lengths = np.concatenate([length for _, length, _ in mats])
    n = len(mats[0][0])

    codes, longest = _pair_codes("jaro_winkler", stacked, lengths, idx_l, idx_r)
    tables, tied_code = _jw_outcome_tables(longest, (t1, t2), dt)
    above_t1 = [table[codes] for table in tables[t1]]
    above_t2 = [table[codes] for table in tables[t2]]
    tied = tied_code[codes]
    inverted = [np.zeros(len(idx_l), bool), np.zeros(len(idx_l), bool),
                np.ones(len(idx_l), bool)]  # holds, may hold, may fail: over the others
    for k, (_, _, other_null) in enumerate(mats[1:], start=1):
        cross, _ = _pair_codes("jaro_winkler", stacked, lengths, idx_l, idx_r + k * n)
        present = ~other_null[idx_r]  # the published guard: ifnull(other_r, ...) only
        holds, may_hold, may_fail = (table[cross] for table in tables[t1])
        inverted[0] |= holds & present
        inverted[1] |= may_hold & present
        inverted[2] &= may_fail | ~present
        tied |= tied_code[cross] & present
    level = np.where(above_t1[0], 3, np.where(inverted[0], 2, np.where(above_t2[0], 1, 0)))
    reach = _reachable([above_t1[1:], inverted[1:], above_t2[1:]], _inversion_level)
    return level.astype(np.int8), reach, tied


# --------------------------------------------------------------------------
# The job
# --------------------------------------------------------------------------


def gamma_levels(settings, table, idx_l, idx_r, precision="float64"):
    """(G (n_pairs, n_columns) int8, boundary bool, reachable uint8): the
    level of every cell, whether any comparison it hangs on is tied, and the
    levels it may honestly take (bit k = level k; no bit under a null)."""
    dt = _dtype(precision)
    comps = comparisons(settings)
    shape = (len(idx_l), len(comps))
    G, boundary = np.zeros(shape, np.int8), np.zeros(shape, bool)
    reachable = np.zeros(shape, np.uint8)
    encoded: dict = {}

    def enc(name):
        if name not in encoded:
            encoded[name] = encode(table[name])
        return encoded[name]

    for c, (comp, col) in enumerate(zip(comps, settings["comparison_columns"])):
        if comp["kind"] not in ("levenshtein", "name_inversion"):
            # one similarity: the level, and beside a tie its neighbours
            g, near = base.gamma_levels({"comparison_columns": [col]}, table, idx_l, idx_r,
                                        precision)
            g, near = g[:, 0], near[:, 0]
            bits = np.uint8(1) << np.maximum(g, 0).astype(np.uint8)
            around = (bits | bits << np.uint8(1) | bits >> np.uint8(1)) & np.uint8(
                (1 << comp["levels"]) - 1)
            G[:, c], boundary[:, c] = g, near
            reachable[:, c] = np.where(g < 0, 0, np.where(near, around, bits))
            continue
        mat, length, null = enc(comp["column"])
        if comp["kind"] == "levenshtein":
            codes, longest = _lev_codes(mat, length, idx_l, idx_r)
            tables = _levenshtein_tables(longest, comp["thresholds"], comp["levels"] - 1, dt)
            level, reach, tied = (t[codes] for t in tables)
        else:
            level, reach, tied = _name_inversion(comp, enc, idx_l, idx_r, dt)
        either_null = null[idx_l] | null[idx_r]
        G[:, c] = np.where(either_null, -1, level)
        boundary[:, c] = tied & ~either_null
        reachable[:, c] = np.where(either_null, 0, reach)
    return G, boundary, reachable


def prepare(settings: dict, frames: dict, precision: str = "float64") -> dict:
    """As ``reference.prepare``, with ``reachable`` beside ``boundary``."""
    uid = settings.get("unique_id_column_name", DEFAULTS["unique_id_column_name"])
    table, idx_l, idx_r = candidate_pairs(settings, frames)
    G, boundary, reachable = gamma_levels(settings, table, idx_l, idx_r, precision)
    ids = table[uid].to_numpy()
    comps = comparisons(settings)
    return {
        "settings": settings, "precision": precision, "table": table,
        "idx_l": idx_l, "idx_r": idx_r,
        "uid_l": ids[idx_l], "uid_r": ids[idx_r], "n_ids": int(ids.max()) + 1,
        "gamma": G, "boundary": boundary, "reachable": reachable,
        "names": [c["name"] for c in comps], "levels": [c["levels"] for c in comps],
    }


def finish(prep: dict, G=None) -> dict:
    """As ``reference.finish``: EM on the histogram of ``G`` (the prepared
    levels unless given) and every pair's match probability."""
    settings, precision = _kindless(prep["settings"]), prep["precision"]
    G = prep["gamma"] if G is None else G
    patterns, counts, index = pattern_table(G, prep["levels"])
    lam, m, u, updates = em(settings, patterns, counts, precision)
    p = match_probability(patterns, lam, m, u)[index]
    out = {"lam": float(lam), "m": m.astype(np.float64), "u": u.astype(np.float64),
           "updates": updates, "p": p.astype(np.float64)}
    if any(c["tf"] for c in comparisons(prep["settings"])):
        out["tf_evidence"] = base.tf_evidence(settings, prep["table"], prep["idx_l"],
                                              prep["idx_r"], out["p"], out["lam"])
    return out


def run(settings: dict, frames: dict, precision: str = "float64") -> dict:
    """The whole reference job: ``prepare`` and ``finish`` in one result."""
    prep = prepare(settings, frames, precision)
    return {**prep, **finish(prep)}
