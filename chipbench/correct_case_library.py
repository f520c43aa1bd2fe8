"""The comparison that decides ``correct`` for the configuration
``c4_case_library``: ``correct.compare`` with the tie rule of
``reference_case_library`` — a level that hangs on several comparisons is
accepted where it is one of the levels REACHABLE by settling each tied
comparison either way, not where it is adjacent. A Jaro-Winkler similarity
within ``BOUNDARY`` of an edge is float32 noise and is forgiven; a Levenshtein
ratio is "tied" only where it EQUALS its threshold as a rational number, which
has one right answer (``<=`` holds), so the flips accepted in the Levenshtein
columns are a number of their own, ``lev_tie_flips``, held at 0. ``verdict``
and ``stand_in`` are ``correct.py``'s own.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference_case_library as reference
from chipbench.correct import _sorted_keys, program_params, stand_in, verdict  # noqa: F401


def compare(produced: dict, prep: dict) -> dict:
    """The numbers ``correct.compare`` gives, by the same names (no TF
    adjustment: the configuration has none). ``prep``:
    ``reference_case_library.prepare`` of the same frames. A cell that differs
    from the reference's level is accepted iff a comparison it hangs on is
    tied and the program's level is in the cell's ``reachable`` set; the
    reference trains and scores on the levels so settled. ``lev_tie_flips``
    counts the cells so accepted in the Levenshtein columns."""
    frame, uid = produced["frame"], produced["uid"]
    got_l, got_r = frame[f"{uid}_l"].to_numpy(), frame[f"{uid}_r"].to_numpy()
    n_ids = max([prep["n_ids"]] + [int(a.max()) + 1 for a in (got_l, got_r) if len(a)])
    got_key, got_order = _sorted_keys(got_l, got_r, n_ids)
    want_key, want_order = _sorted_keys(prep["uid_l"], prep["uid_r"], n_ids)
    if np.array_equal(got_key, want_key):  # the usual case: no second sort
        common = got_key
        gi = wi = np.arange(len(got_key))
    else:
        repeated = bool((np.diff(got_key) == 0).any())
        common, gi, wi = np.intersect1d(got_key, want_key, assume_unique=not repeated,
                                        return_indices=True)
    g_rows, w_rows = got_order[gi], want_order[wi]
    out = {
        "pairs_produced": len(got_key),
        "pairs_wrong": (len(got_key) - len(common)) + (len(want_key) - len(common)),
    }

    names = prep["names"]
    G = np.stack([frame[f"gamma_{name}"].to_numpy()[g_rows] for name in names],
                 axis=1).astype(np.int8) if len(common) else np.zeros((0, len(names)), np.int8)
    want_G, tied = prep["gamma"][w_rows], prep["boundary"][w_rows]
    differ = G != want_G
    within = (prep["reachable"][w_rows] >> np.clip(G, 0, 7).astype(np.uint8)) & 1
    allowed = differ & tied & (G >= 0) & (within == 1)
    out["gamma_wrong"] = int((differ & ~allowed).sum())
    out["gamma_boundary_cells"] = int(tied.sum())
    out["gamma_boundary_flips"] = int(allowed.sum())
    lev = [c["kind"] == "levenshtein" for c in reference.comparisons(prep["settings"])]
    out["lev_tie_flips"] = int(allowed[:, lev].sum())
    settled = prep["gamma"]
    if allowed.any():
        settled = settled.copy()
        settled[w_rows] = np.where(allowed, G, want_G)
    ref = reference.finish(prep, settled)
    out["reference_updates"] = ref["updates"]

    lam, m, u = program_params(produced["params"], names)
    gap = abs(lam - ref["lam"])
    for c, (pm, pu) in enumerate(zip(m, u)):
        k = len(pm)
        gap = max(gap, float(np.abs(np.asarray(pm) - ref["m"][c, :k]).max()),
                  float(np.abs(np.asarray(pu) - ref["u"][c, :k]).max()))
    out["param_gap"] = gap

    p = frame["match_probability"].to_numpy(np.float64)[g_rows]
    finite = np.isfinite(p)
    out["score_gap"] = float(np.abs(p - ref["p"][w_rows])[finite].max(initial=0.0))
    out["scores_not_finite"] = int((~finite).sum())

    digests = produced["digests"]
    out["jobs_differ"] = sum(1 for d in digests if d != digests[-1])
    return out
