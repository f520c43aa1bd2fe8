"""Batched string-similarity kernels for TPU.

TPU-native replacements for the reference's JVM string UDFs
(jars/scala-udf-similarity-0.0.6.jar, registered at
/root/reference/tests/test_spark.py:44-56) and Spark's builtin
``levenshtein()`` (/root/reference/splink/case_statements.py:121). Strings are
pre-encoded host-side into fixed-width code arrays plus lengths (see
splink_tpu/data.py): uint8 bytes for an all-ASCII column, uint32 Unicode code
points for any other, the width the column's ``max_string_length`` caps
(default 24), so every kernel here is shape-static, branch-free and
vmappable: the batch axis maps onto VPU lanes and the per-string axis is a
fixed L. On a TPU the batched entry points (``jaro_winkler``,
``levenshtein``, ``levenshtein_ratio``) take the Pallas kernels of
strings_pallas.py up to its MAX_PALLAS_WIDTH (64) whatever the code dtype;
the vmapped forms here serve wider columns and every other backend.

Characters are Unicode CODE POINTS. The reference's Jaro-Winkler UDF runs on
Java strings, i.e. UTF-16 code units: the two agree on every string of the
Basic Multilingual Plane, and differ on a character above U+FFFF (one code
point here, a surrogate pair of two units there: lengths, windows and
matches count it once here, twice there; tests/test_music_deployment.py pins
what this program does with one).

Design notes:
  * jaro_winkler: the greedy character-matching pass is inherently sequential
    in the s1 index, so we run a fixed-trip-count ``lax.fori_loop`` over the L
    positions with O(L) vectorised work per step (O(L^2) total).
  * levenshtein: row-recurrence DP. The insertion chain within a row is a
    prefix-min, so each row update is fully vectorised via ``lax.cummin``
    (new[j] = j + cummin(t[j] - j)); ``lax.scan`` walks the L rows.
  * No data-dependent shapes anywhere; padding rows/chars are masked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _f(x):
    return x.astype(jnp.float32)


def jaro_winkler_single(
    s1, s2, l1, l2, prefix_scale: float = 0.1, boost_threshold: float = 0.7
):
    """Jaro-Winkler similarity of two fixed-width code arrays, matching the
    reference jar's JaroWinklerSimilarity UDF BIT-FOR-BIT in structure (the
    commons-text JaroWinklerDistance.apply the Scala wrapper delegates to,
    verified against its bytecode by scripts/jvm_mini.py; golden table
    tests/data/jar_similarity_vectors.json):

      * the greedy matching pass iterates the SHORTER string's characters
        over the longer (matches() assigns min/max — direction changes the
        greedy assignment when lengths differ);
      * transpositions = floor(mismatched-matched-positions / 2) — an
        INTEGER halving (Java's `transpositions / 2`), not /2.0;
      * the Winkler prefix run is NOT capped at 4, and its scaling factor
        is min(prefix_scale, 1/max(l1, l2));
      * the boost applies only when jaro >= boost_threshold (0.7, Java's
        `j < 0.7 ? j : boosted`);
      * m == 0 returns 0.0 — including BOTH strings empty.

    The greedy matching pass is sequential in the short-side index (shared
    used2 state), but every per-step operation is a dense (L,) vector op —
    the "first eligible partner" is selected with a cumsum-based first-true
    mask and consumed with a one-hot OR, never a scatter or argmax, so the
    vmapped batch runs entirely on the VPU.
    """
    L = s1.shape[0]
    idx = jnp.arange(L, dtype=jnp.int32)
    l1 = l1.astype(jnp.int32)
    l2 = l2.astype(jnp.int32)
    # iterate the shorter string over the longer (jar matches() semantics)
    swap = l1 > l2
    a = jnp.where(swap, s2, s1)
    b = jnp.where(swap, s1, s2)
    la = jnp.minimum(l1, l2)
    lb = jnp.maximum(l1, l2)
    valid_b = idx < lb
    window = jnp.maximum(lb // 2 - 1, 0)

    def step(used_b, xs):
        ch, i = xs
        cand = (
            (b == ch) & (jnp.abs(idx - i) <= window) & valid_b & (~used_b) & (i < la)
        )
        # one-hot of first eligible j
        first = cand & (jnp.cumsum(cand, dtype=jnp.int32) == 1)
        return used_b | first, first.any()

    used_b, matched_a = lax.scan(
        step, jnp.zeros(L, bool), (a, jnp.arange(L, dtype=jnp.int32))
    )
    m = jnp.sum(matched_a, dtype=jnp.int32)

    # Order-preserving compaction of each side's matched characters via a
    # rank-indicator matmul (MXU work, no scatters): seq[k] = sum_i
    # s[i] * [rank(i) == k], rank = prefix count of matches.
    def compact(s, matched):
        rank = jnp.cumsum(matched, dtype=jnp.int32) - 1
        ind = (rank[:, None] == idx[None, :]) & matched[:, None]  # (L, L)
        # HIGHEST: a TPU's default f32 matmul rounds its operands to
        # bfloat16, which would merge neighbouring code points above 256
        return jnp.matmul(
            s.astype(jnp.float32) * matched, ind.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )

    seq1 = compact(a, matched_a)
    seq2 = compact(b, used_b)
    in_match = idx < m
    mismatched = jnp.sum((seq1 != seq2) & in_match, dtype=jnp.int32)

    mf = _f(m)
    t = _f(mismatched // 2)  # Java integer division
    jaro = jnp.where(
        m > 0,
        (mf / _f(l1) + mf / _f(l2) + (mf - t) / mf) / 3.0,
        0.0,
    )

    prefix_run = jnp.cumprod(
        (s1 == s2) & (idx < la), dtype=jnp.int32
    )
    ell = jnp.sum(prefix_run, dtype=jnp.int32).astype(jnp.float32)  # NOT capped (jar)
    scale = jnp.minimum(prefix_scale, 1.0 / jnp.maximum(_f(lb), 1.0))
    boosted = jaro + ell * scale * (1.0 - jaro)
    return jnp.where(jaro < boost_threshold, jaro, boosted)


def jaro_winkler_bitmask_single(
    s1, s2, l1, l2, prefix_scale: float = 0.1, boost_threshold: float = 0.7
):
    """Jaro-Winkler via packed uint32 position bitmasks — bit-identical to
    :func:`jaro_winkler_single` (same greedy first-eligible assignment, same
    jar semantics) but with the sequential matching pass reduced to ~4 SCALAR
    word ops per step instead of (L,) vector ops, and the two order-preserving
    compaction matmuls replaced by one fused (L, L) boolean reduction.

    Requires L <= 32 (candidate sets fit one uint32 word). The dispatcher
    falls back to the vector formulation for wider columns.

    Structure:
      * eligibility masks: E[i] = bitmask over j of (b[j] == a[i] and j in
        the Jaro window of i) — built once as a fused (L, L) compare + pow2
        reduction;
      * greedy pass: ``first = avail & (~avail + 1)`` extracts the lowest
        eligible j (== the first-true cumsum trick, cheaper by L);
      * transpositions: matched pair (i, j) aligns rank1[i] with rank2[j];
        mismatches are counted with one (L, L) masked reduction instead of
        materialising both compacted sequences.
    """
    L = s1.shape[0]
    idx = jnp.arange(L, dtype=jnp.int32)
    l1 = l1.astype(jnp.int32)
    l2 = l2.astype(jnp.int32)
    swap = l1 > l2
    a = jnp.where(swap, s2, s1)
    b = jnp.where(swap, s1, s2)
    la = jnp.minimum(l1, l2)
    lb = jnp.maximum(l1, l2)
    window = jnp.maximum(lb // 2 - 1, 0)

    eq = a[:, None] == b[None, :]  # (L, L)
    valid_b = idx < lb
    pow2 = (jnp.uint32(1) << idx.astype(jnp.uint32))[None, :]
    E = jnp.sum(
        jnp.where(eq & valid_b[None, :], pow2, jnp.uint32(0)),
        axis=1,
        dtype=jnp.uint32,
    )

    def upto(k):  # bits [0, k) set; k in [0, 32]
        k = k.astype(jnp.uint32)
        return jnp.where(
            k >= 32,
            jnp.uint32(0xFFFFFFFF),
            (jnp.uint32(1) << k) - jnp.uint32(1),
        )

    win_mask = upto(idx + window + 1) & ~upto(jnp.maximum(idx - window, 0))
    masks = jnp.where(idx < la, E & win_mask, jnp.uint32(0))

    def step(used, mask_i):
        avail = mask_i & ~used
        first = avail & (~avail + jnp.uint32(1))  # lowest set bit
        return used | first, first

    used, firsts = lax.scan(step, jnp.uint32(0), masks)
    matched_a = firsts != 0
    m = jnp.sum(matched_a, dtype=jnp.int32)

    used_j = ((used >> idx.astype(jnp.uint32)) & 1).astype(jnp.int32)
    rank1 = jnp.cumsum(matched_a, dtype=jnp.int32) - 1
    rank2 = jnp.cumsum(used_j, dtype=jnp.int32) - 1
    aligned = (
        (rank1[:, None] == rank2[None, :])
        & matched_a[:, None]
        & (used_j[None, :] == 1)
    )
    mismatched = jnp.sum(aligned & ~eq, dtype=jnp.int32)

    mf = _f(m)
    t = _f(mismatched // 2)  # Java integer division
    jaro = jnp.where(
        m > 0,
        (mf / _f(l1) + mf / _f(l2) + (mf - t) / mf) / 3.0,
        0.0,
    )

    prefix_run = jnp.cumprod(
        (s1 == s2) & (idx < la), dtype=jnp.int32
    )
    ell = jnp.sum(prefix_run, dtype=jnp.int32).astype(jnp.float32)  # NOT capped (jar)
    scale = jnp.minimum(prefix_scale, 1.0 / jnp.maximum(_f(lb), 1.0))
    boosted = jaro + ell * scale * (1.0 - jaro)
    return jnp.where(jaro < boost_threshold, jaro, boosted)


def levenshtein_single(s1, s2, l1, l2):
    """Levenshtein edit distance between two fixed-width code arrays.

    Row DP with the insertion chain solved as a prefix-min:
    row_i[j] = j + cummin_k<=j (min(row_{i-1}[k] + 1, row_{i-1}[k-1] + cost) - k).
    Rows past l1 pass through unchanged so the final carry is row l1; we then
    read entry l2.
    """
    L = s1.shape[0]
    l1 = l1.astype(jnp.int32)
    l2 = l2.astype(jnp.int32)
    idx = jnp.arange(L + 1, dtype=jnp.int32)
    row0 = idx

    def step(prev_row, xs):
        ch, i = xs
        cost = jnp.where(s2 == ch, 0, 1).astype(jnp.int32)
        substitute = prev_row[:-1] + cost
        delete = prev_row[1:] + 1
        t = jnp.concatenate([(i + 1)[None], jnp.minimum(substitute, delete)])
        new_row = idx + lax.cummin(t - idx)
        new_row = jnp.where(i < l1, new_row, prev_row)
        return new_row, None

    final_row, _ = lax.scan(step, row0, (s1, jnp.arange(L, dtype=jnp.int32)))
    return final_row[l2]


def levenshtein_ratio_single(s1, s2, l1, l2):
    """levenshtein / mean length — the reference's fallback similarity metric
    (/root/reference/splink/case_statements.py:121: lev/((len_l+len_r)/2))."""
    d = _f(levenshtein_single(s1, s2, l1, l2))
    denom = (_f(l1) + _f(l2)) / 2.0
    return jnp.where(denom > 0, d / denom, 0.0)


def exact_equal_single(s1, s2, l1, l2):
    """Exact string equality on padded arrays (padding bytes are always 0)."""
    return jnp.all(s1 == s2) & (l1 == l2)


# Batched versions: vmap over the leading pair axis.
_jaro_winkler_vector_vmapped = jax.vmap(
    jaro_winkler_single, in_axes=(0, 0, 0, 0, None, None)
)
_jaro_winkler_bitmask_vmapped = jax.vmap(
    jaro_winkler_bitmask_single, in_axes=(0, 0, 0, 0, None, None)
)


def jaro_winkler_vmapped(s1, s2, l1, l2, prefix_scale=0.1, boost_threshold=0.7):
    """Batched JW: packed-bitmask formulation when the width fits one uint32
    (all practical columns; fewer ops per pair — the gap has not been
    timed on the chip), vector formulation beyond."""
    if s1.shape[1] <= 32:
        return _jaro_winkler_bitmask_vmapped(
            s1, s2, l1, l2, prefix_scale, boost_threshold
        )
    return _jaro_winkler_vector_vmapped(s1, s2, l1, l2, prefix_scale, boost_threshold)
levenshtein_vmapped = jax.vmap(levenshtein_single)
levenshtein_ratio_vmapped = jax.vmap(levenshtein_ratio_single)
exact_equal = jax.vmap(exact_equal_single)


def levenshtein(s1, s2, l1, l2):
    """Batched Levenshtein distance: Pallas lane-tile kernel on TPU up to
    width 64 (``strings_pallas.pallas_supported``), vmapped row-DP
    elsewhere."""
    from .strings_pallas import levenshtein_pallas, pallas_supported

    if pallas_supported(s1):
        return levenshtein_pallas(s1, s2, l1, l2).astype(jnp.int32)
    return levenshtein_vmapped(s1, s2, l1, l2)


def levenshtein_ratio(s1, s2, l1, l2):
    """levenshtein / mean length, batched with kernel dispatch."""
    from .strings_pallas import levenshtein_pallas, pallas_supported

    if not pallas_supported(s1):
        return levenshtein_ratio_vmapped(s1, s2, l1, l2)
    d = levenshtein_pallas(s1, s2, l1, l2)
    denom = (_f(l1) + _f(l2)) / 2.0
    return jnp.where(denom > 0, d / denom, 0.0)


def jaro_winkler(s1, s2, l1, l2, prefix_scale=0.1, boost_threshold=0.7):
    """Batched Jaro-Winkler: Pallas lane-tile kernel on TPU up to width 64,
    bytes or code points (``strings_pallas.pallas_supported``), vmapped
    pure-JAX elsewhere (wider columns, CPU)."""
    from .strings_pallas import jaro_winkler_pallas, pallas_supported

    if pallas_supported(s1):
        return jaro_winkler_pallas(s1, s2, l1, l2, prefix_scale, boost_threshold)
    return jaro_winkler_vmapped(s1, s2, l1, l2, prefix_scale, boost_threshold)


def jaro_winkler_batch(s1, s2, l1, l2, prefix_scale=0.1, boost_threshold=0.7):
    return jaro_winkler(s1, s2, l1, l2, prefix_scale, boost_threshold)
