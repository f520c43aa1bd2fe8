"""Prototype + measurement for the two-phase Jaro-Winkler bound.

Measures, on config-4-shaped dob-blocked pairs, what fraction of pairs a
cheap upper bound can prove below the lowest JW threshold (the survivors
are the only pairs that need the exact O(L^2) kernel). Run on the CPU tier:

    JAX_PLATFORMS=cpu python benchmarks/jw_bound_proto.py [n_rows] [n_pairs]
"""

import os
import sys
import time

# a CPU prototype: pin the platform before first use
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pandas as pd

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def dob_blocked_pairs(df, n_sample, seed=0):
    codes = pd.factorize(df["dob"])[0]
    order = np.argsort(codes, kind="stable")
    sc = codes[order]
    starts = np.flatnonzero(np.r_[True, sc[1:] != sc[:-1]])
    ends = np.r_[starts[1:], len(sc)]
    il, ir = [], []
    for s, e in zip(starts, ends):
        m = e - s
        if m < 2:
            continue
        rows = order[s:e]
        ii, jj = np.triu_indices(m, k=1)
        il.append(rows[ii])
        ir.append(rows[jj])
    il = np.concatenate(il)
    ir = np.concatenate(ir)
    rng = np.random.default_rng(seed)
    sel = rng.choice(len(il), min(n_sample, len(il)), replace=False)
    return il[sel], ir[sel]


def encode(colvals, width=16):
    vals = ["" if v is None else str(v)[:width] for v in colvals]
    b = np.zeros((len(vals), width), np.uint8)
    ln = np.array([len(v) for v in vals], np.int32)
    for i, v in enumerate(vals):
        if v:
            b[i, : len(v)] = np.frombuffer(v.encode("ascii"), np.uint8)
    return b, ln


def np_bound(s1, s2, l1, l2, n_classes=32):
    """Numpy model of the device bound: hashed-class count min-sum +
    exact <=4-char prefix. Returns jw upper bound per pair."""
    cls1 = s1 & (n_classes - 1)
    cls2 = s2 & (n_classes - 1)
    n = len(s1)
    W = s1.shape[1]
    pos_valid1 = np.arange(W)[None, :] < l1[:, None]
    pos_valid2 = np.arange(W)[None, :] < l2[:, None]
    row = np.repeat(np.arange(n), W)
    c1 = np.bincount(
        (row * n_classes + cls1.ravel())[pos_valid1.ravel()],
        minlength=n * n_classes,
    ).reshape(n, n_classes)
    c2 = np.bincount(
        (row * n_classes + cls2.ravel())[pos_valid2.ravel()],
        minlength=n * n_classes,
    ).reshape(n, n_classes)
    # nibble cap 7 with per-row overflow -> trivial la bound
    ovf = (c1 > 7).any(axis=1) | (c2 > 7).any(axis=1)
    m_ub = np.minimum(np.minimum(c1, 7), np.minimum(c2, 7)).sum(axis=1)
    la = np.minimum(l1, l2)
    lb = np.maximum(l1, l2)
    m_ub = np.where(ovf, la, np.minimum(m_ub, la))
    with np.errstate(divide="ignore", invalid="ignore"):
        jaro_ub = np.where(
            m_ub > 0, (m_ub / np.maximum(l1, 1) + m_ub / np.maximum(l2, 1) + 1.0) / 3.0, 0.0
        )
    p4 = np.zeros(n, np.int32)
    run = np.ones(n, bool)
    for k in range(4):
        run = run & (s1[:, k] == s2[:, k]) & (k < la)
        p4 += run
    scale = np.minimum(0.1, 1.0 / np.maximum(lb, 1))
    jw_ub = np.where(jaro_ub < 0.7, jaro_ub, jaro_ub + p4 * scale * (1.0 - jaro_ub))
    return np.where(p4 >= 4, 2.0, jw_ub)  # full-4 prefix: cannot bound


def main():
    n_rows = int(sys.argv[1]) if len(sys.argv) > 1 else 200_000
    n_pairs = int(sys.argv[2]) if len(sys.argv) > 2 else 200_000

    from datagen import make_people

    t0 = time.perf_counter()
    df = make_people(n_rows, seed=4)
    il, ir = dob_blocked_pairs(df, n_pairs)
    print(f"data+pairs {time.perf_counter()-t0:.1f}s n={len(il)}", flush=True)

    import jax.numpy as jnp

    from splink_tpu.ops.strings import jaro_winkler_vmapped

    for col, thr in (("first_name", 0.88), ("surname", 0.88), ("postcode", 0.94)):
        t0 = time.perf_counter()
        b, ln = encode(df[col].to_numpy(object))
        s1, s2, l1, l2 = b[il], b[ir], ln[il], ln[ir]
        jw = np.asarray(
            jaro_winkler_vmapped(
                jnp.asarray(s1), jnp.asarray(s2), jnp.asarray(l1),
                jnp.asarray(l2), 0.1, 0.7,
            )
        )
        t_jw = time.perf_counter() - t0
        t0 = time.perf_counter()
        jw_ub = np_bound(s1, s2, l1, l2)
        t_b = time.perf_counter() - t0
        equal = (l1 == l2) & (s1 == s2).all(axis=1) & (l1 > 0)
        sound = bool((jw_ub >= jw - 1e-6).all())
        surv = (jw_ub >= thr) & ~equal
        true_pos = jw >= thr
        missed = int((true_pos & ~surv & ~equal).sum())
        print(
            f"{col}: sound={sound} survivor_rate={surv.mean():.4f} "
            f"equal_rate={equal.mean():.4f} true_rate={true_pos.mean():.4f} "
            f"missed={missed} (jw {t_jw:.1f}s bound {t_b:.1f}s)",
            flush=True,
        )


if __name__ == "__main__":
    main()
