"""The comparison that decides ``correct``: what the timed jobs produced,
against the plain reference, each number beside its limit.

The limits are data: the ``limits`` group of the configuration's file, set as
PERF.md §2 records (lower reading: the program over a dozen seeds; upper
reading: the bfloat16 control). A number without a limit there is an error,
not a pass.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

from chipbench import reference

MISSING = 1e30  # a number that could not be read fails every limit (and is JSON)


def _sorted_keys(uid_l, uid_r, n_ids):
    """(keys ascending, the order that sorts them); no sort where they are."""
    key = np.asarray(uid_l, np.int64) * n_ids + np.asarray(uid_r, np.int64)
    if len(key) < 2 or bool((key[1:] > key[:-1]).all()):
        return key, np.arange(len(key))
    order = np.argsort(key)
    return key[order], order


def program_params(params: dict, names: list[str]):
    """(lambda, m, u) from the program's public ``linker.params.params``."""
    lam = float(params["λ"])
    m, u = [], []
    for name in names:
        col = params["π"][f"gamma_{name}"]
        for rows, dist in ((m, "prob_dist_match"), (u, "prob_dist_non_match")):
            levels = col[dist]
            rows.append([float(levels[f"level_{k}"]["probability"])
                         for k in range(len(levels))])
    return lam, m, u


def compare(produced: dict, prep: dict) -> dict:
    """Numbers compared, by short plain name.

    ``produced``: ``frame`` (the scored frame of the job checked),
    ``params`` (that job's ``linker.params.params``), ``uid`` (the id
    column's name), ``digests`` (one tuple per job of the window, the checked
    job's last) and, where the job adjusts for term frequencies, ``tf_frame``.
    ``prep``: ``reference.prepare`` of the same frames.

    Where a similarity sits within ``reference.BOUNDARY`` of a threshold,
    float32 may honestly land on either side (on the chip a few hundred cells
    per job do): there the adjacent level the program took is accepted, and
    the reference trains and scores on the levels so settled, so that a
    handful of ties does not read as a gap in the parameters.
    """
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
    want_G = prep["gamma"][w_rows]
    differ = G != want_G
    allowed = differ & prep["boundary"][w_rows] & (np.abs(G - want_G) == 1)
    out["gamma_wrong"] = int((differ & ~allowed).sum())
    out["gamma_boundary_cells"] = int(prep["boundary"][w_rows].sum())
    out["gamma_boundary_flips"] = int(allowed.sum())
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

    if "tf_evidence" in ref and produced.get("tf_frame") is not None:  # the job made the TF call
        tf = produced["tf_frame"]
        aligned = (len(tf) == len(frame)
                   and np.array_equal(tf[f"{uid}_l"].to_numpy(), got_l)
                   and np.array_equal(tf[f"{uid}_r"].to_numpy(), got_r))
        out["tf_gap"] = MISSING
        if aligned:
            tp = tf["tf_adjusted_match_prob"].to_numpy(np.float64)[g_rows]
            want_tf = reference.bayes_combine([ref["p"]] + ref["tf_evidence"])[w_rows]
            if np.isfinite(tp).all():
                out["tf_gap"] = float(np.abs(tp - want_tf).max(initial=0.0))

    digests = produced["digests"]
    out["jobs_differ"] = sum(1 for d in digests if d != digests[-1])
    return out


INFORMATIVE = ("pairs_produced", "gamma_boundary_cells", "gamma_boundary_flips",
               "reference_updates")


def verdict(numbers: dict, limits: dict):
    """(correct, [[name, value, limit], ...]) — every compared number needs a
    limit in the configuration's file; the informative counts carry None."""
    rows, ok = [], True
    for name, value in numbers.items():
        if name in INFORMATIVE:
            rows.append([name, value, None])
            continue
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the configuration's file")
        limit = limits[name]
        rows.append([name, value, limit])
        ok = ok and bool(np.isfinite(value)) and value <= limit
    if numbers.get("pairs_produced", 0) == 0:
        ok = False
    return ok, rows


def stand_in(ref: dict, uid: str = "unique_id") -> dict:
    """What :func:`compare` takes, built from a reference-shaped result (the
    control, or the reference itself) put in the program's place."""
    frame = pd.DataFrame({
        "match_probability": ref["p"].astype(np.float32),
        f"{uid}_l": ref["uid_l"], f"{uid}_r": ref["uid_r"],
        **{f"gamma_{n}": ref["gamma"][:, c].astype(np.int64)
           for c, n in enumerate(ref["names"])},
    })
    pi = {}
    for c, name in enumerate(ref["names"]):
        pi[f"gamma_{name}"] = {
            dist: {f"level_{lv}": {"value": lv, "probability": float(tab[c, lv])}
                   for lv in range(ref["levels"][c])}
            for dist, tab in (("prob_dist_match", ref["m"]),
                              ("prob_dist_non_match", ref["u"]))
        }
    out = {"frame": frame, "params": {"λ": ref["lam"], "π": pi}, "uid": uid,
           "digests": [(len(frame), 0.0, ref["lam"])], "tf_frame": None}
    if "tf_evidence" in ref:
        out["tf_frame"] = frame.assign(tf_adjusted_match_prob=reference.bayes_combine(
            [ref["p"]] + ref["tf_evidence"]))
    return out
