"""The plain reference for a dedupe job with NO blocking rule: every unordered
pair of rows is a candidate (the reference implementation compares all pairs
when ``blocking_rules`` is empty, splink/blocking.py:183-184).
``reference.candidate_pairs`` loops over the rules and would give no pair, so
the pair set is written out here; the gamma levels, the pattern table, EM and
the scores are ``reference.py``'s own functions, imported. Nothing is imported
from the program and nothing is taken from what it made.

    frame + settings  ->  all pairs (l, r), l < r in unique-id order
                      ->  gamma levels per comparison column
                      ->  reference.finish: EM on the pattern histogram, scores

At 10,000 rows that is 49,995,000 pairs. A column has far fewer DISTINCT
values than rows (a few thousand), so a column's levels are worked out once
per ordered pair of distinct values, by ``reference.gamma_levels`` on the
column of distinct values, and each pair of rows looks its cell up: the same
function of the same two strings, computed once instead of some thousand
times. ``precision`` is ``reference.py``'s: float64 the reference proper,
bfloat16 the control that has to come out as not correct.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

from chipbench import reference
from chipbench.reference import finish  # noqa: F401 - the second half is the same


def all_pairs(n: int):
    """(idx_l, idx_r): every (l, r) with 0 <= l < r < n once, ascending in
    (l, r) — row l against each later row."""
    later = n - 1 - np.arange(n)
    idx_l = np.repeat(np.arange(n, dtype=np.int32), later)
    idx_r = (np.concatenate([np.arange(l + 1, n, dtype=np.int32) for l in range(n)])
             if n > 1 else np.zeros(0, np.int32))
    return idx_l, idx_r


def candidate_pairs(settings: dict, frames: dict):
    """(table, idx_l, idx_r): the frame in unique-id order and every pair of
    its positions, the smaller unique id on the left."""
    if settings["link_type"] != "dedupe_only" or settings.get("blocking_rules"):
        raise ValueError("this reference covers dedupe_only with no blocking rule")
    uid = settings.get("unique_id_column_name", reference.DEFAULTS["unique_id_column_name"])
    table = frames["df"].sort_values(uid, kind="stable").reset_index(drop=True)
    return (table, *all_pairs(len(table)))


def gamma_levels(settings, table, idx_l, idx_r, precision="float64"):
    """``reference.gamma_levels`` of the same pairs, each column computed on
    its distinct values (a null is one of them) and looked up per pair."""
    columns = settings["comparison_columns"]
    G = np.zeros((len(idx_l), len(columns)), np.int8)
    boundary = np.zeros(G.shape, bool)
    for c, column in enumerate(columns):
        comp = reference.comparisons({"comparison_columns": [column]})[0]
        code, values = pd.factorize(table[comp["column"]], use_na_sentinel=False)
        d = len(values)
        first, second = np.divmod(np.arange(d * d, dtype=np.int32), np.int32(d))
        cells, near = reference.gamma_levels(
            {"comparison_columns": [column]}, pd.DataFrame({comp["column"]: values}),
            first, second, precision)
        cell = code[idx_l].astype(np.int32) * np.int32(d) + code[idx_r].astype(np.int32)
        G[:, c], boundary[:, c] = cells[cell, 0], near[cell, 0]
    return G, boundary


def prepare(settings: dict, frames: dict, precision: str = "float64") -> dict:
    """``reference.prepare`` with this module's pair set: the same keys."""
    uid = settings.get("unique_id_column_name", reference.DEFAULTS["unique_id_column_name"])
    table, idx_l, idx_r = candidate_pairs(settings, frames)
    G, boundary = gamma_levels(settings, table, idx_l, idx_r, precision)
    ids = table[uid].to_numpy()
    comps = reference.comparisons(settings)
    return {
        "settings": settings, "precision": precision, "table": table,
        "idx_l": idx_l, "idx_r": idx_r,
        "uid_l": ids[idx_l], "uid_r": ids[idx_r], "n_ids": int(ids.max()) + 1,
        "gamma": G, "boundary": boundary,
        "names": [c["name"] for c in comps], "levels": [c["levels"] for c in comps],
    }


def run(settings: dict, frames: dict, precision: str = "float64") -> dict:
    """The whole reference job: ``prepare`` and ``reference.finish``."""
    prep = prepare(settings, frames, precision)
    return {**prep, **reference.finish(prep)}
