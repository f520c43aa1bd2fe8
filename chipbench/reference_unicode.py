"""The plain reference for the configuration ``musicbrainz_songs``: strings
read as Unicode CODE POINTS, at the widths of long free text (48-64
characters). Nothing imported from ``splink_tpu``, nothing taken from what the
program made.

What differs from ``reference.py`` and ``reference_case_library.py``, and why:

  * **encode**: a string column becomes its code points (numpy's fixed-width
    unicode is UCS-4, so a ``<U{w}`` array viewed as uint32 is exactly that),
    where ``reference.encode`` takes ASCII bytes and refuses anything else.
    A character above U+FFFF is one code point here; the reference library's
    Jaro-Winkler runs on Java's UTF-16 units, where it is two. They agree on
    the Basic Multilingual Plane, which is all the configuration's generator
    draws.
  * **Jaro-Winkler similarity per distinct code**: ``reference._similarity``
    tabulates EVERY (len_l, len_r, m, t, prefix) code, (L+1)^4 (L/2+1) of
    them: 589M at width 64, too many to hold. Here the same formula is
    evaluated on the codes the pairs have. The codes themselves are
    ``reference._pair_codes``'s (``_jw_counts`` on the device, integers
    only), packed into int32 by ``reference._jw_radices``, which holds up to
    width 80 (81^4 x 41 < 2^31): a wider column is refused.
  * **Levenshtein distance by a rolled recurrence**: the textbook
    D[i][j] = min(D[i-1][j] + 1, D[i][j-1] + 1, D[i-1][j-1] + (a_i != b_j)),
    walked by ``lax.scan`` over i and j instead of
    ``reference_case_library._edit_distance``'s unrolled L^2 cells, which do
    not compile in useful time at width 48 and above. The ratio, its levels
    and its ties are ``reference_case_library``'s (``_levenshtein_tables``).

Everything else — candidate pairs, the pattern table, EM, the scores, the
tie rule and ``reachable`` — is ``reference.py``'s and
``reference_case_library.py``'s, imported. ``precision="bfloat16"`` is the
control, as there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from chipbench import reference_case_library as library
from chipbench.reference import (BLOCK, BOUNDARY, DEFAULTS, _dtype, _jw_radices, _pack,
                                 _pair_codes, _unpack, candidate_pairs)
from chipbench.reference_case_library import BOOST_FROM

MAX_WIDTH = 80  # the widest column whose Jaro-Winkler code fits int32


def encode(values: pd.Series):
    """(code points (n, w) uint32, lengths (n,), null (n,)) of a string column."""
    null = values.isna().to_numpy()
    text = values.astype(object).where(~null, "").to_numpy().astype("U")
    width = max(text.dtype.itemsize // 4, 1)
    mat = np.ascontiguousarray(text).view(np.uint32).reshape(len(text), width)
    return mat, (mat != 0).sum(axis=1).astype(np.int32), null


def jw_similarity(codes: np.ndarray, longest: int, dt):
    """(similarity, jaro) in ``dt`` of each Jaro-Winkler code: the formula of
    ``reference._similarity`` (commons-text), on the given codes."""
    l1, l2, m, half, prefix = _unpack(codes.astype(np.int64), _jw_radices(longest))
    one = dt(1.0)
    mf, t = m.astype(dt), half.astype(dt)
    f1, f2 = np.maximum(l1, 1).astype(dt), np.maximum(l2, 1).astype(dt)
    safe = np.where(m > 0, mf, one)
    jaro = np.where(m > 0, (mf / f1 + mf / f2 + (mf - t) / safe) / dt(3.0), dt(0.0))
    scale = np.minimum(dt(0.1), one / np.maximum(np.maximum(f1, f2), one))
    boosted = jaro + prefix.astype(dt) * scale * (one - jaro)
    return np.where(jaro < dt(BOOST_FROM), jaro, boosted), jaro


def _jaro_winkler(comp, enc, idx_l, idx_r, dt):
    """(level, tied) per pair, nulls not yet applied: the number of
    thresholds the similarity exceeds; tied where it, or the jaro that picks
    the boost branch, lies within BOUNDARY of an edge."""
    mat, length, _ = enc
    if mat.shape[1] > MAX_WIDTH:
        raise ValueError(f"the reference's Jaro-Winkler codes hold to width {MAX_WIDTH}")
    codes, longest = _pair_codes("jaro_winkler", mat, length, idx_l, idx_r)
    distinct, index = np.unique(codes, return_inverse=True)
    sim, jaro = jw_similarity(distinct, longest, dt)
    level = np.zeros(len(distinct), np.int8)
    near = np.abs(jaro.astype(np.float64) - BOOST_FROM) < BOUNDARY
    for t in comp["thresholds"]:
        level += sim > dt(t)
        near |= np.abs(sim.astype(np.float64) - t) < BOUNDARY
    return level[index], near[index]


def _edit_distance(s1, s2, l1, l2):
    """Unit-cost edit distance per pair, D[len_l][len_r] of the recurrence in
    the module docstring, one row of D a step of a scan over i and one cell
    a step of a scan over j within it."""
    width = s1.shape[1]
    n = s1.shape[0]
    first = jnp.broadcast_to(jnp.arange(width + 1, dtype=jnp.int32), (n, width + 1))

    def row(prev, i):  # prev: D[i-1][.]; returns D[i][.]
        a = s1[:, i - 1]

        def cell(left, j):  # left: D[i][j-1]
            here = jnp.minimum(jnp.minimum(prev[:, j] + 1, left + 1),
                               prev[:, j - 1] + (a != s2[:, j - 1]).astype(jnp.int32))
            return here, here

        start = jnp.full(n, i, jnp.int32)
        _, cells = jax.lax.scan(cell, start, jnp.arange(1, width + 1))
        out = jnp.concatenate([start[:, None], cells.T], axis=1)
        return out, out[jnp.arange(n), l2]

    _, at_len_r = jax.lax.scan(row, first, jnp.arange(1, width + 1))  # (width, n)
    answer = first[jnp.arange(n), l2]  # len_l == 0
    pick = jnp.arange(1, width + 1)[:, None] == l1[None, :]
    return jnp.where(l1 > 0, jnp.sum(jnp.where(pick, at_len_r, 0), axis=0), answer)


@functools.partial(jax.jit, static_argnames=("longest",))
def _lev_block(longest, table, lens, il, ir):
    l1, l2 = lens[il], lens[ir]
    return _pack([l1, l2, _edit_distance(table[il], table[ir], l1, l2)], (longest + 1,) * 3)


def _lev_codes(mat, length, idx_l, idx_r):
    """(code per pair, longest): (len_l, len_r, distance) of every pair, in
    blocks, the matrix padded as ``reference._pair_codes`` pads it."""
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


def gamma_levels(settings, table, idx_l, idx_r, precision="float64"):
    """As ``reference_case_library.gamma_levels`` — (G, boundary, reachable)
    — for the kinds the configuration has: Jaro-Winkler and Levenshtein, on
    code points."""
    dt = _dtype(precision)
    comps = library.comparisons(settings)
    shape = (len(idx_l), len(comps))
    G, boundary = np.zeros(shape, np.int8), np.zeros(shape, bool)
    reachable = np.zeros(shape, np.uint8)
    for c, comp in enumerate(comps):
        enc = encode(table[comp["column"]])
        either_null = enc[2][idx_l] | enc[2][idx_r]
        if comp["kind"] == "jaro_winkler":
            level, near = _jaro_winkler(comp, enc, idx_l, idx_r, dt)
            bits = np.uint8(1) << level.astype(np.uint8)
            around = (bits | bits << np.uint8(1) | bits >> np.uint8(1)) & np.uint8(
                (1 << comp["levels"]) - 1)
            reach = np.where(near, around, bits)
        elif comp["kind"] == "levenshtein":
            codes, longest = _lev_codes(enc[0], enc[1], idx_l, idx_r)
            tables = library._levenshtein_tables(longest, comp["thresholds"],
                                                 comp["levels"] - 1, dt)
            level, reach, near = (t[codes] for t in tables)
        else:
            raise ValueError(f"the reference has no comparison kind {comp['kind']!r}")
        G[:, c] = np.where(either_null, -1, level)
        boundary[:, c] = near & ~either_null
        reachable[:, c] = np.where(either_null, 0, reach)
    return G, boundary, reachable


def prepare(settings: dict, frames: dict, precision: str = "float64") -> dict:
    """As ``reference_case_library.prepare``, with this module's levels."""
    uid = settings.get("unique_id_column_name", DEFAULTS["unique_id_column_name"])
    table, idx_l, idx_r = candidate_pairs(settings, frames)
    G, boundary, reachable = gamma_levels(settings, table, idx_l, idx_r, precision)
    ids = table[uid].to_numpy()
    comps = library.comparisons(settings)
    return {
        "settings": settings, "precision": precision, "table": table,
        "idx_l": idx_l, "idx_r": idx_r,
        "uid_l": ids[idx_l], "uid_r": ids[idx_r], "n_ids": int(ids.max()) + 1,
        "gamma": G, "boundary": boundary, "reachable": reachable,
        "names": [c["name"] for c in comps], "levels": [c["levels"] for c in comps],
    }


finish = library.finish


def run(settings: dict, frames: dict, precision: str = "float64") -> dict:
    """The whole reference job: ``prepare`` and ``finish`` in one result."""
    prep = prepare(settings, frames, precision)
    return {**prep, **finish(prep)}
