"""The plain reference: Fellegi-Sunter linkage written straight from its
definition, with nothing imported from ``splink_tpu`` and nothing taken from
what the program made (no pairs, no gammas, no parameters, no tables).

    frames + settings  ->  candidate pairs (equi-join per blocking rule)
                       ->  gamma levels per comparison column
                       ->  EM on the gamma-pattern histogram (lambda, m, u)
                       ->  match probability per pair
                       ->  ex-post term-frequency adjustment

The per-pair string work (Jaro-Winkler match/transposition counts, bigram set
sizes) is integer arithmetic in plain ``jax.numpy`` over blocks of pairs, so
it runs wherever jax runs; the similarity values, the thresholds, EM and the
scores are computed on the host in ``precision``:

  * ``float64`` — the reference proper;
  * ``bfloat16`` — the CONTROL: the same computation in the nearest precision
    below the float32 the configurations state. It stands in the program's
    place and has to come out as not correct (chipbench/tests, PERF.md §2).

Semantics follow the published Splink definitions the configurations name:
Jaro-Winkler as Apache commons-text ``JaroWinklerDistance`` computes it (the
shorter string walks the longer, window ``max(len)//2 - 1``, transpositions
halved as integers, prefix not capped, scale ``min(0.1, 1/max(len))``, boost
only from 0.7), level = number of thresholds strictly exceeded, null on either
side = level -1 (no evidence), default string thresholds 0.94 / 0.88, default
m/u priors and ``proportion_of_matches`` 0.3, EM stopped when no m or u moved
by ``em_convergence`` (1e-4) or after ``max_iterations`` (25) updates.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

BLOCK = 1 << 18  # pairs per device block of the reference's own gamma pass
BOUNDARY = 1e-6  # a similarity this close to a threshold may round either way
F32_TINY = float(np.finfo(np.float32).tiny)  # the log floor float32 states

DEFAULT_JW_THRESHOLDS = {2: [0.94], 3: [0.94, 0.88], 4: [0.94, 0.88, 0.7]}
DEFAULT_M = {2: [1, 9], 3: [1, 2, 7], 4: [1, 1, 1, 7]}
DEFAULT_U = {2: [9, 1], 3: [7, 2, 1], 4: [7, 1, 1, 1]}
DEFAULTS = {"proportion_of_matches": 0.3, "em_convergence": 1e-4,
            "max_iterations": 25, "unique_id_column_name": "unique_id"}


def _dtype(precision: str):
    if precision == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    return np.dtype(precision).type


# --------------------------------------------------------------------------
# The model, read from the settings as a user writes them
# --------------------------------------------------------------------------


def comparisons(settings: dict) -> list[dict]:
    """One entry per comparison column: name, input column, kind, thresholds,
    number of levels, whether it is term-frequency adjusted."""
    out = []
    for col in settings["comparison_columns"]:
        levels = int(col.get("num_levels", 2))
        spec = col.get("comparison")
        if spec is None:
            spec = {"kind": "jaro_winkler",
                    "thresholds": DEFAULT_JW_THRESHOLDS[levels]}
        kind = spec["kind"]
        if kind not in ("exact", "jaro_winkler", "qgram_jaccard"):
            raise ValueError(f"the reference has no comparison kind {kind!r}")
        out.append({
            "name": col.get("custom_name", col.get("col_name")),
            "column": spec.get("column", col.get("col_name")),
            "kind": kind,
            "thresholds": [float(t) for t in spec.get("thresholds", [])],
            "levels": levels,
            "tf": bool(col.get("term_frequency_adjustments", False)),
        })
    return out


def rule_columns(rule: str) -> list[str]:
    cols = []
    for term in rule.split(" AND "):
        m = re.fullmatch(r"l\.(\w+) = r\.\1", term.strip())
        if not m:
            raise ValueError(f"the reference joins on equalities only: {rule}")
        cols.append(m.group(1))
    return cols


# --------------------------------------------------------------------------
# Candidate pairs
# --------------------------------------------------------------------------


def _key_codes(table: pd.DataFrame, cols: list[str]) -> np.ndarray:
    """One integer per row for the tuple of key columns; -1 where any is null."""
    code = np.zeros(len(table), np.int64)
    null = np.zeros(len(table), bool)
    for col in cols:
        c, uniques = pd.factorize(table[col])  # null -> -1
        null |= c < 0
        code = code * (len(uniques) + 1) + c
    return np.where(null, -1, code)


def _expand(starts, per_item):
    """For each item i, the ``per_item[i]`` consecutive integers from
    ``starts[i]``: (item index, integer), flat, without a Python loop."""
    total = int(per_item.sum())
    if total >= 2**31:
        raise ValueError("more candidate pairs than the reference indexes")
    item = np.repeat(np.arange(len(per_item), dtype=np.int32), per_item)
    first = (np.cumsum(per_item) - per_item).astype(np.int32)
    value = np.arange(total, dtype=np.int32)
    value -= np.repeat(first - starts.astype(np.int32), per_item)
    return item, value


def _rule_pairs(code, left_pos, right_pos, dedupe: bool):
    """All (l, r) positions with equal non-null key code, ascending in (l, r):
    across two sets of rows, or within one (dedupe: l < r by position)."""
    right = right_pos[code[right_pos] >= 0]
    right = right[np.argsort(code[right], kind="stable")]  # by code, then position
    rc = code[right]
    left = left_pos[code[left_pos] >= 0]
    hi = np.searchsorted(rc, code[left], "right")
    if dedupe:  # only the later rows of the same group
        slot = np.empty(len(code), np.int32)
        slot[right] = np.arange(len(right))
        lo = slot[left] + 1
    else:
        lo = np.searchsorted(rc, code[left], "left")
    item, partner = _expand(lo, hi - lo)
    return left[item], right[partner]


def _merge_sorted(a, b):
    """Two ascending arrays of distinct keys -> their ascending union."""
    if not len(a):
        return b
    at = np.searchsorted(a, b)
    fresh = at == np.searchsorted(a, b, "right")  # not in a already
    b, at = b[fresh], at[fresh] + np.arange(int(fresh.sum()))
    out = np.empty(len(a) + len(b), a.dtype)
    from_a = np.ones(len(out), bool)
    from_a[at] = False
    out[from_a], out[at] = a, b
    return out


def candidate_pairs(settings: dict, frames: dict):
    """(table, idx_l, idx_r): the frame the positions index (both inputs
    stacked for a link), and every pair of positions that satisfies at least
    one blocking rule, once, ascending in (l, r). Null keys never match. A
    dedupe pair has the smaller unique id on the left; a link pair is (left
    frame, right frame)."""
    uid = settings.get("unique_id_column_name", DEFAULTS["unique_id_column_name"])
    if settings["link_type"] == "dedupe_only":
        # rows in unique-id order, so that position order is id order
        table = frames["df"].sort_values(uid, kind="stable").reset_index(drop=True)
        left_pos = right_pos = np.arange(len(table))
    elif settings["link_type"] == "link_only":
        n_l = len(frames["df_l"])
        table = pd.concat([frames["df_l"], frames["df_r"]], ignore_index=True)
        left_pos, right_pos = np.arange(n_l), np.arange(n_l, len(table))
    else:
        raise ValueError("the reference covers dedupe_only and link_only")
    dedupe = settings["link_type"] == "dedupe_only"
    n = len(table)
    key = np.zeros(0, np.int64)
    for rule in settings["blocking_rules"]:
        a, b = _rule_pairs(_key_codes(table, rule_columns(rule)), left_pos, right_pos, dedupe)
        key = _merge_sorted(key, a.astype(np.int64) * n + b)
    return table, (key // n).astype(np.int32), (key % n).astype(np.int32)


# --------------------------------------------------------------------------
# Gamma levels
# --------------------------------------------------------------------------


def encode(values: pd.Series):
    """(bytes (n, w) uint8, lengths (n,), null (n,)) of a string column."""
    null = values.isna().to_numpy()
    text = values.astype(object).where(~null, "").to_numpy().astype("U")
    width = max(text.dtype.itemsize // 4, 1)
    raw = np.char.encode(text, "ascii").astype(f"S{width}")
    mat = raw.view(np.uint8).reshape(len(raw), width)
    return mat, (mat != 0).sum(axis=1).astype(np.int32), null


def _jw_counts(s1, s2, l1, l2):
    """Per pair: matches m, mismatched matched positions, common prefix."""
    width = s1.shape[1]
    idx = jnp.arange(width, dtype=jnp.int32)[None, :]
    swap = (l1 > l2)[:, None]
    a, b = jnp.where(swap, s2, s1), jnp.where(swap, s1, s2)
    la, lb = jnp.minimum(l1, l2)[:, None], jnp.maximum(l1, l2)[:, None]
    window = jnp.maximum(lb // 2 - 1, 0)
    used = jnp.zeros(s1.shape, bool)
    hit = []
    for i in range(width):
        cand = ((b == a[:, i:i + 1]) & (jnp.abs(idx - i) <= window)
                & (idx < lb) & ~used & (i < la))
        first = cand & (jnp.cumsum(cand, axis=1) == 1)
        used = used | first
        hit.append(first.any(axis=1))
    hit = jnp.stack(hit, axis=1)
    m = hit.sum(axis=1, dtype=jnp.int32)

    def in_order(s, matched):  # k-th matched character, k = 0..width-1
        rank = jnp.cumsum(matched, axis=1) - 1
        pick = (rank[:, :, None] == idx[:, None, :]) & matched[:, :, None]
        return (s[:, :, None].astype(jnp.int32) * pick).sum(axis=1)

    differ = (in_order(a, hit) != in_order(b, used)) & (idx < m[:, None])
    prefix = jnp.cumprod((s1 == s2) & (idx < la), axis=1).sum(axis=1)
    return m, differ.sum(axis=1, dtype=jnp.int32), prefix.astype(jnp.int32)


def _bigram_counts(s1, s2, l1, l2):
    """Per pair: |A ∩ B| and |A ∪ B| of the sets of distinct bigrams."""

    def grams(s, length):
        code = s[:, :-1].astype(jnp.int32) * 256 + s[:, 1:].astype(jnp.int32)
        pos = jnp.arange(code.shape[1], dtype=jnp.int32)[None, :]
        valid = pos < (length[:, None] - 1)
        same = (code[:, :, None] == code[:, None, :]) & valid[:, None, :]
        earlier = pos[:, None, :] < pos[:, :, None]
        return code, valid, valid & ~(same & earlier).any(axis=2)

    c1, v1, first1 = grams(s1, l1)
    c2, v2, first2 = grams(s2, l2)
    shared = ((c1[:, :, None] == c2[:, None, :]) & v2[:, None, :]).any(axis=2)
    inter = (first1 & shared).sum(axis=1, dtype=jnp.int32)
    union = (first1.sum(axis=1, dtype=jnp.int32)
             + first2.sum(axis=1, dtype=jnp.int32) - inter)
    return inter, union


# The integer counts of a pair are packed into one code per comparison, so
# that similarity, level and boundary are worked out once per distinct code
# (a few million at most) on the host and gathered per pair.

def _jw_radices(longest: int):
    return (longest + 1, longest + 1, longest + 1, longest // 2 + 1, longest + 1)


def _pack(values, radices):
    code = values[0]
    for v, r in zip(values[1:], radices[1:]):
        code = code * r + v
    return code


def _unpack(code, radices):
    out = []
    for r in reversed(radices):
        out.append(code % r)
        code = code // r
    return out[::-1]


@functools.partial(jax.jit, static_argnames=("kind", "longest"))
def _code_block(kind, longest, table, lens, il, ir):
    s1, s2, l1, l2 = table[il], table[ir], lens[il], lens[ir]
    if kind == "jaro_winkler":
        m, differ, prefix = _jw_counts(s1, s2, l1, l2)
        return _pack([l1, l2, m, differ // 2, prefix], _jw_radices(longest))
    inter, union = _bigram_counts(s1, s2, l1, l2)
    return inter * (2 * longest + 1) + union


def _pair_codes(kind: str, mat, length, idx_l, idx_r):
    """(codes per pair, longest string): one comparison's integer pass over
    all pairs in blocks. The byte matrix is padded to a multiple of 8 columns
    and ``longest`` rounded up likewise, so that compiled shapes do not follow
    the longest string of a seed."""
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
        out[start:stop] = np.asarray(
            _code_block(kind, longest, table, lens, il, ir))[: stop - start]
    return out, longest


def _similarity(kind, longest, dt):
    """For EVERY code: (similarity in ``dt``, values whose rounding could flip
    a branch, as (value, edge) pairs)."""
    one = dt(1.0)
    if kind == "qgram_jaccard":
        code = np.arange((longest + 1) * (2 * longest + 1))
        inter, union = (c.astype(dt) for c in _unpack(code, (longest + 1, 2 * longest + 1)))
        safe = np.where(union > 0, union, one)
        return np.where(union > 0, inter / safe, dt(0.0)), []
    radices = _jw_radices(longest)
    l1, l2, m, half, prefix = _unpack(np.arange(int(np.prod(radices))), radices)
    mf, t = m.astype(dt), half.astype(dt)
    f1, f2 = np.maximum(l1, 1).astype(dt), np.maximum(l2, 1).astype(dt)
    safe = np.where(m > 0, mf, one)
    jaro = np.where(m > 0, (mf / f1 + mf / f2 + (mf - t) / safe) / dt(3.0),
                    dt(0.0))
    scale = np.minimum(dt(0.1), one / np.maximum(np.maximum(f1, f2), one))
    boosted = jaro + prefix.astype(dt) * scale * (one - jaro)
    return np.where(jaro < dt(0.7), jaro, boosted), [(jaro, 0.7)]


def gamma_levels(settings, table, idx_l, idx_r, precision="float64"):
    """(G (n_pairs, n_columns) int8, boundary (n_pairs, n_columns) bool):
    ``boundary`` marks cells whose similarity lies within ``BOUNDARY`` of a
    threshold, where float32 may honestly land on either side."""
    dt = _dtype(precision)
    comps = comparisons(settings)
    G = np.zeros((len(idx_l), len(comps)), np.int8)
    boundary = np.zeros(G.shape, bool)
    encoded: dict = {}
    for c, comp in enumerate(comps):
        col = comp["column"]
        if comp["kind"] == "exact":
            codes, _ = pd.factorize(table[col])  # null -> -1
            cl, cr = codes[idx_l], codes[idx_r]
            G[:, c] = np.where((cl < 0) | (cr < 0), -1, cl == cr)
            continue
        if col not in encoded:
            encoded[col] = encode(table[col])
        mat, length, null = encoded[col]
        codes, longest = _pair_codes(comp["kind"], mat, length, idx_l, idx_r)
        sim, branches = _similarity(comp["kind"], longest, dt)
        level = np.zeros(len(sim), np.int8)
        near = np.zeros(len(sim), bool)
        with np.errstate(invalid="ignore"):
            for value, edge in [(sim, t) for t in comp["thresholds"]] + branches:
                near |= np.abs(value.astype(np.float64) - edge) < BOUNDARY
            for t in comp["thresholds"]:
                level += sim > dt(t)
        either_null = null[idx_l] | null[idx_r]
        G[:, c] = np.where(either_null, -1, level[codes])
        boundary[:, c] = near[codes] & ~either_null
    return G, boundary


# --------------------------------------------------------------------------
# EM and scores
# --------------------------------------------------------------------------


def initial_params(settings, precision="float64"):
    dt = _dtype(precision)
    comps = comparisons(settings)
    width = max(c["levels"] for c in comps)
    m = np.zeros((len(comps), width), dt)
    u = np.zeros((len(comps), width), dt)
    for c, (comp, col) in enumerate(zip(comps, settings["comparison_columns"])):
        k = comp["levels"]
        pm = np.asarray(col.get("m_probabilities", DEFAULT_M[k]), np.float64)
        pu = np.asarray(col.get("u_probabilities", DEFAULT_U[k]), np.float64)
        m[c, :k] = (pm / pm.sum()).astype(dt)
        u[c, :k] = (pu / pu.sum()).astype(dt)
    lam = dt(settings.get("proportion_of_matches",
                          DEFAULTS["proportion_of_matches"]))
    return lam, m, u


def match_probability(G, lam, m, u):
    """P(match | gamma) = sigmoid(logit(lambda) + sum_c log m/u); a null
    level adds nothing; a probability of 0 takes float32's smallest log."""
    dt = m.dtype.type
    tiny = dt(F32_TINY)
    log_m, log_u = np.log(np.maximum(m, tiny)), np.log(np.maximum(u, tiny))
    z = np.log(np.maximum(lam, tiny)) - np.log(np.maximum(dt(1.0) - lam, tiny))
    z = np.full(len(G), z, dt)
    for c in range(G.shape[1]):
        g = G[:, c]
        lv = np.maximum(g, 0)
        z = z + np.where(g >= 0, log_m[c, lv] - log_u[c, lv], dt(0.0))
    with np.errstate(over="ignore"):  # exp(+big) -> inf -> p = 0, as meant
        return (dt(1.0) / (dt(1.0) + np.exp(-z))).astype(dt)


def pattern_table(G, levels):
    """(patterns (k, C) int8, counts (k,), index (n,)): the distinct gamma
    rows, how often each occurs and which one every pair has — by a
    mixed-radix code and a bincount, not a sort of the rows."""
    radices = [lv + 1 for lv in levels]  # -1 (null) .. lv-1
    columns = np.asfortranarray(G)
    code = _pack([columns[:, c].astype(np.int32) + 1 for c in range(G.shape[1])], radices)
    counts = np.bincount(code, minlength=int(np.prod(radices)))
    seen = np.flatnonzero(counts)
    patterns = np.stack(_unpack(seen, radices), axis=1).astype(np.int8) - 1
    index = np.cumsum(counts > 0) - 1
    return patterns, counts[seen], index[code]


def em(settings, patterns, counts, precision="float64"):
    """EM on the histogram of gamma patterns. Returns (lambda, m, u, updates)."""
    dt = _dtype(precision)
    lam, m, u = initial_params(settings, precision)
    if len(patterns) == 0:
        return lam, m, u, 0
    w = counts.astype(dt)
    levels = np.arange(m.shape[1])
    onehot = (patterns[:, :, None] == levels[None, None, :]).astype(dt)
    valid = (patterns >= 0).astype(dt)
    tiny = dt(F32_TINY)
    stop = dt(settings.get("em_convergence", DEFAULTS["em_convergence"]))
    updates = 0
    for _ in range(int(settings.get("max_iterations", DEFAULTS["max_iterations"]))):
        p = match_probability(patterns, lam, m, u)
        pm, pu = p * w, (dt(1.0) - p) * w
        new_m = ((onehot * pm[:, None, None]).sum(axis=0)
                 / np.maximum((valid * pm[:, None]).sum(axis=0), tiny)[:, None])
        new_u = ((onehot * pu[:, None, None]).sum(axis=0)
                 / np.maximum((valid * pu[:, None]).sum(axis=0), tiny)[:, None])
        new_lam = dt(pm.sum() / np.maximum(w.sum(), tiny))
        delta = max(np.abs(new_m - m).max(), np.abs(new_u - u).max())
        lam, m, u = new_lam, new_m, new_u
        updates += 1
        if delta < stop:
            break
    return lam, m, u, updates


def bayes_combine(probs):
    num = np.ones(len(probs[0]))
    den = np.ones(len(probs[0]))
    for p in probs:
        p = np.asarray(p, np.float64)
        num, den = num * p, den * (1.0 - p)
    tot = num + den
    return np.where(tot > 0, num / np.where(tot > 0, tot, 1.0), 0.5)


def tf_evidence(settings, table, idx_l, idx_r, p, lam):
    """Ex-post term-frequency adjustment (Splink's
    ``make_adjustment_for_term_frequencies``), one array per flagged column:
    where a pair agrees on the column's value, the mean match probability of
    all pairs agreeing on THAT value, Bayes-combined with 1 - lambda;
    elsewhere 0.5 (no evidence). ``tf_adjusted_match_prob`` is
    ``bayes_combine([p, *evidence])``."""
    p = np.asarray(p, np.float64)
    evidence = []
    for comp in comparisons(settings):
        if not comp["tf"]:
            continue
        codes, _ = pd.factorize(table[comp["column"]])
        cl, cr = codes[idx_l], codes[idx_r]
        agree = (cl >= 0) & (cl == cr)
        adj = np.full(len(p), 0.5)
        if agree.any():
            tokens = cl[agree]
            total = np.bincount(tokens, weights=p[agree])
            count = np.bincount(tokens)
            mean = total / np.maximum(count, 1)
            token_adj = bayes_combine([mean, np.full(len(mean), 1.0 - float(lam))])
            adj[agree] = token_adj[tokens]
        evidence.append(adj)
    return evidence


def prepare(settings: dict, frames: dict, precision: str = "float64") -> dict:
    """The first half of the reference job on ``frames`` ({"df"} or {"df_l",
    "df_r"}): candidate pairs and their gamma levels, with the cells marked
    whose similarity sits on a threshold."""
    uid = settings.get("unique_id_column_name", DEFAULTS["unique_id_column_name"])
    table, idx_l, idx_r = candidate_pairs(settings, frames)
    G, boundary = gamma_levels(settings, table, idx_l, idx_r, precision)
    ids = table[uid].to_numpy()
    comps = comparisons(settings)
    return {
        "settings": settings, "precision": precision, "table": table,
        "idx_l": idx_l, "idx_r": idx_r,
        "uid_l": ids[idx_l], "uid_r": ids[idx_r], "n_ids": int(ids.max()) + 1,
        "gamma": G, "boundary": boundary,
        "names": [c["name"] for c in comps], "levels": [c["levels"] for c in comps],
    }


def finish(prep: dict, G=None) -> dict:
    """The second half: EM on the histogram of ``G`` (the prepared levels
    unless given), every pair's match probability and the TF evidence."""
    settings, precision = prep["settings"], prep["precision"]
    G = prep["gamma"] if G is None else G
    patterns, counts, index = pattern_table(G, prep["levels"])
    lam, m, u, updates = em(settings, patterns, counts, precision)
    p = match_probability(patterns, lam, m, u)[index]
    out = {"lam": float(lam), "m": m.astype(np.float64), "u": u.astype(np.float64),
           "updates": updates, "p": p.astype(np.float64)}
    if any(c["tf"] for c in comparisons(settings)):
        out["tf_evidence"] = tf_evidence(settings, prep["table"], prep["idx_l"],
                                         prep["idx_r"], out["p"], out["lam"])
    return out


def run(settings: dict, frames: dict, precision: str = "float64") -> dict:
    """The whole reference job: ``prepare`` and ``finish`` in one result."""
    prep = prepare(settings, frames, precision)
    return {**prep, **finish(prep)}
