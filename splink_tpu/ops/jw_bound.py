"""Cheap Jaro-Winkler upper bound for two-phase gamma scoring.

The gamma program needs only the LEVEL a pair's JW similarity falls in, not
the score itself — and on config-4-shaped blocked pairs ~92% of pairs sit
below the lowest threshold (survivor rates 3.7% first_name / 2.9% surname /
0.2% postcode, plus 4-8% token-equal pairs whose level is known without any
kernel: counted once on a synthetic config-4 sample by a prototype since
deleted, not per blocking rule — ROADMAP D1 asks for that reading). A sound upper bound that costs a
few dozen word ops per pair therefore lets the exact O(L^2) kernel run on a
compacted survivor subset only (gammas._jw_two_phase).

Bound construction (all quantities per pair, overline = upper bound):

  * matched chars m <= sum_c min(n1_c, n2_c) over 32 hashed character
    classes (byte & 31). Hashing MERGES classes, and
    min(a1+a2, b1+b2) >= min(a1,b1) + min(a2,b2), so the hashed min-sum
    only loosens the bound — never unsound. Counts are capped at 7 (one
    nibble with a SWAR guard bit); a row with any class count > 7 sets an
    overflow flag and falls back to the trivial bound m <= min(l1, l2).
  * transpositions t >= 0, so (m - t)/m <= 1.
  * jaro <= (m̄/l1 + m̄/l2 + 1) / 3.
  * the Winkler boost needs the common-prefix run: the first FOUR chars of
    each side ride along exactly (one packed uint32 lane), so ell is exact
    for runs < 4; a full 4-char match means the run may extend beyond what
    we stored — those pairs are unconditional survivors (bound 2.0).
  * boost-threshold case analysis: if jaro_ub < boost_threshold the true
    jaro is also below it and jw = jaro <= jaro_ub; otherwise
    jw <= jaro_ub + ell*scale*(1 - jaro_ub) whether or not the true jaro
    reached the threshold.

Aux layout per row (packed into the gamma row table, gammas.pack_table):
4 uint32 lanes of 32x 4-bit class counts + 1 uint32 lane holding chars
[0..3] in bytes 0..3 (low byte = char 0) with the count-overflow flag in
bit 31 (safe: ASCII chars <= 127; wide codepoints store their low byte,
which only ever OVERSTATES the prefix run — still sound).

Reference target: the jar's JaroWinklerSimilarity UDF semantics
(/root/reference/splink/case_statements.py:84), exact kernel
ops/strings.jaro_winkler.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

N_CLASSES = 32
NIBBLE_CAP = 7
OVERFLOW_BIT = np.uint32(1 << 31)

# survivor = ub >= lowest_threshold - MARGIN: absorbs f32 rounding between
# the bound arithmetic and the exact kernel's arithmetic. Extra survivors
# get the exact kernel, so the margin can only add work, never change
# results.
BOUND_MARGIN = 1e-6


def jw_bound_row_aux(bytes_, lengths, token_ids):
    """Host-side per-row aux for the device bound: (counts (n, 4) uint32,
    prefix (n, 1) uint32). Computed once per unique token id and gathered
    back (factorise-first, like qgram_row_aux); null rows (token -1) keep
    zeros — null pairs never consult the bound."""
    n, w = bytes_.shape
    out_cnt = np.zeros((n, 4), np.uint32)
    out_pref = np.zeros((n, 1), np.uint32)
    valid = token_ids >= 0
    if not valid.any():
        return out_cnt, out_pref
    toks = token_ids[valid]
    uniq, first_idx = np.unique(toks, return_index=True)
    reps = np.flatnonzero(valid)[first_idx]
    B = bytes_[reps].astype(np.uint32)
    L = np.minimum(lengths[reps].astype(np.int64), w)
    V = len(reps)

    pos_valid = np.arange(w)[None, :] < L[:, None]
    cls = (B & (N_CLASSES - 1)).astype(np.int64)
    flat = (np.arange(V)[:, None] * N_CLASSES + cls)[pos_valid]
    counts = np.bincount(flat, minlength=V * N_CLASSES).reshape(V, N_CLASSES)
    ovf = (counts > NIBBLE_CAP).any(axis=1)
    counts = np.minimum(counts, NIBBLE_CAP).astype(np.uint32)
    lanes = np.zeros((V, 4), np.uint32)
    for lane in range(4):
        for k in range(8):
            lanes[:, lane] |= counts[:, lane * 8 + k] << np.uint32(4 * k)

    pref = np.zeros(V, np.uint32)
    for k in range(min(4, w)):
        ch = np.where(k < L, B[:, k] & 0xFF, 0).astype(np.uint32)
        pref |= ch << np.uint32(8 * k)
    pref |= np.where(ovf, OVERFLOW_BIT, np.uint32(0))

    pos = np.searchsorted(uniq, toks)
    rows = np.flatnonzero(valid)
    out_cnt[rows] = lanes[pos]
    out_pref[rows, 0] = pref[pos]
    return out_cnt, out_pref


def _nibble_min_sum(x, y):
    """sum over 8 nibbles of min(x_nib, y_nib), SWAR. Requires nibbles <= 7
    (bit 3 of each nibble is the borrow guard)."""
    H = jnp.uint32(0x88888888)
    F = jnp.uint32(0x0F0F0F0F)
    t = (x | H) - y  # per nibble: x + 8 - y; bit 3 set iff x >= y
    mask = ((t & H) >> 3) * jnp.uint32(15)  # 0xF per nibble where x >= y
    mn = (y & mask) | (x & ~mask)
    s = (mn & F) + ((mn >> 4) & F)
    s = s + (s >> 8)
    return ((s + (s >> 16)) & jnp.uint32(0xFF)).astype(jnp.int32)


def jw_upper_bound(cnt1, pref1, cnt2, pref2, l1, l2,
                   prefix_scale=0.1, boost_threshold=0.7):
    """(b,) float32 >= the exact jaro_winkler of each pair; 2.0 where the
    bound cannot exclude (4-char prefix match). Inputs: the packed aux
    lanes of both sides ((b, 4) uint32 counts, (b,) uint32 prefix lane)
    and int32 lengths."""
    l1 = l1.astype(jnp.int32)
    l2 = l2.astype(jnp.int32)
    m = _nibble_min_sum(cnt1[:, 0], cnt2[:, 0])
    for lane in range(1, 4):
        m = m + _nibble_min_sum(cnt1[:, lane], cnt2[:, lane])
    la = jnp.minimum(l1, l2)
    lb = jnp.maximum(l1, l2)
    ovf = ((pref1 | pref2) & jnp.uint32(OVERFLOW_BIT)) != 0
    m_ub = jnp.where(ovf, la, jnp.minimum(m, la)).astype(jnp.float32)
    l1f = jnp.maximum(l1.astype(jnp.float32), 1.0)
    l2f = jnp.maximum(l2.astype(jnp.float32), 1.0)
    jaro_ub = jnp.where(
        m_ub > 0, (m_ub / l1f + m_ub / l2f + 1.0) / 3.0, 0.0
    )
    d = (pref1 ^ pref2) & jnp.uint32(0x7FFFFFFF)
    # nested prefix flags: c1 implies c0 etc., so the run length is a sum
    c0 = ((d & jnp.uint32(0xFF)) == 0) & (la > 0)
    c1 = ((d & jnp.uint32(0xFFFF)) == 0) & (la > 1)
    c2 = ((d & jnp.uint32(0xFFFFFF)) == 0) & (la > 2)
    c3 = (d == 0) & (la > 3)
    p4 = (
        c0.astype(jnp.int32) + c1.astype(jnp.int32)
        + c2.astype(jnp.int32) + c3.astype(jnp.int32)
    )
    scale = jnp.minimum(
        jnp.float32(prefix_scale), 1.0 / jnp.maximum(lb.astype(jnp.float32), 1.0)
    )
    boosted = jaro_ub + p4.astype(jnp.float32) * scale * (1.0 - jaro_ub)
    ub = jnp.where(jaro_ub < boost_threshold, jaro_ub, boosted)
    return jnp.where(p4 >= 4, jnp.float32(2.0), ub)
